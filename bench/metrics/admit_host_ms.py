"""Host milliseconds per step in the overlapped loader's ``admit`` lane
(installing fetched rows in HBM and gathering the batch): its mean
``admit`` span, one a batch, which holds the stage's own work and not
its waits on the queues (``programspans.stage_ms``)."""

import programspans


def read(ctx):
    return programspans.stage_ms(ctx, "admit")
