"""Percent of its HBM roofline the ``feature_gather_rows`` kernel
reached: every hop's rows read and written once at the logical width
(``flops.gather_bytes``) over the kernel's device time per step."""

import flops


def read(ctx):
    rows = sum(flops.hop_rows(ctx.batch, ctx.cfg["fanouts"]))
    return ctx.roofline("feature_gather_rows",
                        flops.gather_bytes(rows, ctx.cfg["feat_dim"]))
