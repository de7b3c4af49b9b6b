"""Host milliseconds per step the train loop waited in ``get_batch``,
timed by the benchmark's own feed around the pipeline."""


def read(ctx):
    if not ctx.wait_s:
        return None
    return sum(ctx.wait_s) * 1e3 / len(ctx.wait_s)
