"""95th percentile of the time tickets waited from submit until a serving
step popped them into a batch (the ``admit_s`` of the server's
``serve.queue_wait`` spans in the window); the rest of their queue wait is
spent behind the batch's earlier tickets."""

import numpy as np

from spans import named


def read(ctx):
    waits = [
        s.attrs["admit_s"] * 1e3
        for s in named(ctx.spans, "serve.queue_wait")
        if "admit_s" in s.attrs
    ]
    if not waits:
        return None
    return float(np.percentile(waits, 95))
