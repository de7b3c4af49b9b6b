"""Host milliseconds per step in the DiskStore's reads: its
``disk.read_group`` spans (a pool task's or a serial caller's block
preads with their cache bookkeeping), clipped to the traced window and
summed over the threads that read."""

import programspans


def read(ctx):
    found = programspans.clipped(ctx, ("disk.read_group",))
    if not found or ctx.steps == 0:
        return None
    return sum(b - a for a, b in found) / 1e6 / ctx.steps
