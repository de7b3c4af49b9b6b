"""The whole step's share of the chips' bf16 peak: GraphSAGE's forward
and backward matmul FLOPs per step (``flops.step_matmul_flops``), times
steps, over the window and the chips' peak, in percent."""

import flops


def read(ctx):
    if ctx.peak is None or ctx.steps == 0:
        return None
    c = ctx.cfg
    per_step = flops.step_matmul_flops(ctx.batch, c["fanouts"],
                                       c["feat_dim"], c["hidden"],
                                       c["n_classes"])
    rate = per_step * ctx.steps / ctx.window_s
    return 100.0 * rate / (ctx.chips * ctx.peak["bf16_flops"])
