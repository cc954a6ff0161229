"""Share of the window's answers the service cache gave (ServiceMetrics
hit and answered counters, deltas over the window)."""


def read(ctx):
    m0, m1 = ctx.counters
    answered = m1["answered"] - m0["answered"]
    if answered <= 0:
        return None
    return 100.0 * (m1["cache_hits"] - m0["cache_hits"]) / answered
