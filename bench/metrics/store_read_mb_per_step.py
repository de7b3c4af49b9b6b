"""Megabytes (1e6 B) the DiskStore read from its files per step, over
the window (the store's ``bytes_fetched``)."""


def read(ctx):
    fetched = ctx.delta("store", "bytes_fetched")
    if fetched is None or ctx.steps == 0:
        return None
    return fetched / 1e6 / ctx.steps
