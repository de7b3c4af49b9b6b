"""The whole step's share of the chips' bf16 peak: the arch's forward and
backward matmul FLOPs per step (``bench/archs/<arch>.py``'s
``matmul_flops``), times steps, over the window and the chips' peak, in
percent."""


def read(ctx):
    if ctx.peak is None or ctx.steps == 0:
        return None
    rate = ctx.arch.matmul_flops(ctx.cfg, ctx.batch) * ctx.steps \
        / ctx.window_s
    return 100.0 * rate / (ctx.chips * ctx.peak["bf16_flops"])
