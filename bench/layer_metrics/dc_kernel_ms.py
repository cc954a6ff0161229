"""Device time of the DC pair-scan kernel in the window: the summed
durations of its events in the profiler trace."""


def read(ctx):
    if ctx.trace is None or ctx.trace.kernel_events == 0:
        return None
    return ctx.trace.kernel_s * 1e3
