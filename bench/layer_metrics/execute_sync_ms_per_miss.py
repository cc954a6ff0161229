"""Time a cache miss spends blocked in device-to-host reads (the ``sync_s``
the program charges to the window's ``daisy.execute`` spans), per miss."""

from misses import charged, misses


def read(ctx):
    spans = misses(ctx.spans)
    if spans is None:
        return None
    return 1e3 * sum(charged(s, "sync_s") for s in spans) / len(spans)
