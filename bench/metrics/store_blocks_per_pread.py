"""Disk blocks the DiskStore fetched per ``pread`` it issued, over the
window (window deltas of its ``block_fetches`` and ``preads``): 1 where
every block is read alone, more where its reads coalesce runs of
consecutive missed blocks.  Nothing where the store counts no preads."""


def read(ctx):
    fetched = ctx.delta("store", "block_fetches")
    preads = ctx.delta("store", "preads")
    if fetched is None or not preads:
        return None
    return fetched / preads
