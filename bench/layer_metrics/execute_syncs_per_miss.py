"""Device-to-host reads a cache miss makes (the ``syncs`` the program
charges to the window's ``daisy.execute`` spans), per miss."""

from misses import charged, misses


def read(ctx):
    spans = misses(ctx.spans)
    if spans is None:
        return None
    return sum(charged(s, "syncs") for s in spans) / len(spans)
