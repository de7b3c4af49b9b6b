"""Device milliseconds per step outside the train-step program: the
data plane's sampling, gathers and cache admissions, from the trace."""


def read(ctx):
    if ctx.device is None or ctx.device["prep_device_s"] <= 0:
        return None
    return ctx.per_step(ctx.device["prep_device_s"])
