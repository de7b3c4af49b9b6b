"""Percent of its HBM roofline the ``feature_gather_cached`` kernel
reached: each step's unique rows (the batch's ``subgraph_nodes``) read
through the slot table and written once at the logical width
(``flops.gather_bytes``), over the kernel's device time per step."""

import flops


def read(ctx):
    if not ctx.uniq_rows:
        return None
    rows = sum(ctx.uniq_rows) / len(ctx.uniq_rows)
    return ctx.roofline("feature_gather_cached",
                        flops.gather_bytes(rows, ctx.cfg["feat_dim"],
                                           cached=True))
