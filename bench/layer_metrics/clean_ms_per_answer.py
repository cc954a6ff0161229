"""Self time of the executor's cleaning phases (``clean.*`` spans, on the
serving and cleaner threads) in the window, per answer."""

from spans import self_times


def read(ctx):
    if ctx.answers <= 0:
        return None
    own = self_times(ctx.spans)
    total = sum(t for i, t in own.items() if ctx.spans[i].name.startswith("clean."))
    return 1e3 * total / ctx.answers
