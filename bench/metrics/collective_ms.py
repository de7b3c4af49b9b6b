"""Device milliseconds per step in exchanges between chips: the trace's
all-reduce, all-gather, reduce-scatter, all-to-all and
collective-permute ops (``tracereduce.Op.collective``), on the slowest
chip.  Nothing where the trace holds none, as on one chip."""


def read(ctx):
    if ctx.device is None or max(ctx.device["collective_s"].values()) <= 0:
        return None
    return ctx.per_step(ctx.device["collective_s"])
