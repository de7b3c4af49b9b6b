"""Host milliseconds per step in the overlapped loader's ``sample`` lane
(the cached sampler's dispatch and hop assembly): its mean ``sample``
span, one a batch, which holds the stage's own work and not its waits
on the queues (``programspans.stage_ms``)."""

import programspans


def read(ctx):
    return programspans.stage_ms(ctx, "sample")
