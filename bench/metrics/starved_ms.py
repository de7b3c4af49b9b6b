"""Milliseconds per step in which the device ran no op while the train
loop waited for its batch: the device's idle gaps in the traced window
(``tracereduce.idle_gaps``) inside the program's ``consume.wait``
spans, averaged over chips."""

import programspans
import tracereduce


def read(ctx):
    waits = tracereduce.merge(programspans.clipped(ctx, ("consume.wait",)))
    if not waits or ctx.steps == 0:
        return None
    lo, hi = ctx.trace.window()
    ns = 0.0
    for chip in sorted({o.chip for o in ctx.ops}) or [0]:
        for gs, ge in tracereduce.idle_gaps(ctx.ops, chip, lo, hi):
            for ws, we in waits:
                ns += max(0.0, min(ge, we) - max(gs, ws))
    return ctx.per_step(ns / 1e9)
