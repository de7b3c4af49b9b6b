"""Device milliseconds per step of the jitted train step, from the
trace."""


def read(ctx):
    if ctx.device is None or ctx.device["step_device_s"] <= 0:
        return None
    return ctx.per_step(ctx.device["step_device_s"])
