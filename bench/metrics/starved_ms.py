"""Milliseconds per step in which the device ran no op while the train
loop waited for its batch: the device's idle gaps in the traced window
(``tracereduce.idle_gaps``) inside the program's ``consume.wait``
spans, on the slowest chip."""

import programspans
import tracereduce


def read(ctx):
    waits = tracereduce.merge(programspans.clipped(ctx, ("consume.wait",)))
    if not waits or ctx.steps == 0:
        return None
    lo, hi = ctx.trace.window()
    by_chip = {}
    for chip in sorted({o.chip for o in ctx.ops}) or [0]:
        ns = 0.0
        for gs, ge in tracereduce.idle_gaps(ctx.ops, chip, lo, hi):
            for ws, we in waits:
                ns += max(0.0, min(ge, we) - max(gs, ws))
        by_chip[chip] = ns / 1e9
    return ctx.per_step(by_chip)
