"""Device milliseconds per step of the jitted train step, from the
trace, on the slowest chip."""


def read(ctx):
    if ctx.device is None or max(ctx.device["step_device_s"].values()) <= 0:
        return None
    return ctx.per_step(ctx.device["step_device_s"])
