"""Percent of HBM edge-block cache lookups that hit, over the window
(``edgecache`` hits and misses)."""


def read(ctx):
    hits, misses = ctx.delta("edgecache", "hits"), ctx.delta("edgecache",
                                                             "misses")
    if hits is None or misses is None or hits + misses == 0:
        return None
    return 100.0 * hits / (hits + misses)
