"""Host milliseconds per step in the overlapped loader's ``resolve``
lane (feature-cache planning and the misses' store reads): its mean
``resolve`` span, one a batch, which holds the stage's own work and not
its waits on the queues (``programspans.stage_ms``)."""

import programspans


def read(ctx):
    return programspans.stage_ms(ctx, "resolve")
