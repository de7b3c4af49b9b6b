"""Percent of HBM feature-row cache lookups that hit, over the window
(``devcache`` hits and misses)."""


def read(ctx):
    hits, misses = ctx.delta("devcache", "hits"), ctx.delta("devcache",
                                                            "misses")
    if hits is None or misses is None or hits + misses == 0:
        return None
    return 100.0 * hits / (hits + misses)
