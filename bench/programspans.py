"""The program's own host spans (``repro.obs.trace_span``), read from the
traced window of the profiler's trace, where they lie on the device's
clock.  The parent of a change that adds a span has none: every reader
then finds nothing and returns None."""


def clipped(ctx, names) -> list:
    """(start, end) of every host span named in ``names``, clipped to the
    traced window; spans wholly outside are dropped."""
    if ctx.trace is None:
        return []
    lo, hi = ctx.trace.window()
    out = []
    for s in ctx.trace.spans:
        if s.name in names:
            a, b = max(s.start, lo), min(s.end, hi)
            if b > a:
                out.append((a, b))
    return out


def stage_ms(ctx, name: str):
    """Mean host milliseconds of the ``name`` stage spans that start in
    the traced window: a lane's time per batch, and so per step, since
    each batch passes each lane once.  Whole spans, averaged, and not a
    clipped sum over steps: a span already open when the profiler starts
    is never recorded, so a lane whose stage takes a step's time would
    lose up to one stage of an 8-step window.  None where the window
    holds no such span."""
    if ctx.trace is None:
        return None
    lo, hi = ctx.trace.window()
    found = [s.end - s.start for s in ctx.trace.spans
             if s.name == name and lo <= s.start < hi]
    if not found:
        return None
    return sum(found) / len(found) / 1e6
