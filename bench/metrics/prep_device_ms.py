"""Device milliseconds per step outside the train-step program: the
data plane's sampling, gathers, exchanges and cache admissions, from the
trace, on the slowest chip."""


def read(ctx):
    if ctx.device is None or max(ctx.device["prep_device_s"].values()) <= 0:
        return None
    return ctx.per_step(ctx.device["prep_device_s"])
