"""Time a cache miss spends in JAX's tracing, lowering and compiling (or
loading from the persistent cache), as charged to the window's
``daisy.execute`` spans, per miss."""

from misses import JIT, charged, misses


def read(ctx):
    spans = misses(ctx.spans)
    if spans is None:
        return None
    return 1e3 * sum(charged(s, *JIT) for s in spans) / len(spans)
