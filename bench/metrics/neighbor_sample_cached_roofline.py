"""Percent of its HBM roofline the ``neighbor_sample_cached`` kernel
(sampling through the HBM edge-block cache) reached: every hop's least
sampling traffic (``flops.sample_bytes``) over the kernel's device time
per step."""

import flops


def read(ctx):
    rows = flops.hop_rows(ctx.batch, ctx.cfg["fanouts"])
    need = sum(flops.sample_bytes(rows[h], f)
               for h, f in enumerate(ctx.cfg["fanouts"]))
    return ctx.roofline("neighbor_sample_cached", need)
