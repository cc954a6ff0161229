"""95th percentile of the time tickets waited from submit to the start of
serving (the server's ``serve.queue_wait`` spans in the window)."""

import numpy as np

from spans import named


def read(ctx):
    waits = [s.dur * 1e3 for s in named(ctx.spans, "serve.queue_wait")]
    if not waits:
        return None
    return float(np.percentile(waits, 95))
