"""Percent of the traced window in which the device ran no op: one
minus the union of op intervals over the window, averaged over chips."""


def read(ctx):
    if ctx.device is None or ctx.device["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - ctx.device["busy_s"] / ctx.device["window_s"])
