"""Percent of the traced window in which the device ran no op: one
minus the union of op intervals over the window, on the slowest chip
(the one busiest, which the others wait for)."""


def read(ctx):
    if ctx.device is None or ctx.device["window_s"] <= 0:
        return None
    busiest = max(ctx.device["busy_by_chip"].values())
    return 100.0 * (1.0 - busiest / ctx.device["window_s"])
