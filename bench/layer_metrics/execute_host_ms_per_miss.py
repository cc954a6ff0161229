"""Host time of a cache miss outside its device reads and JAX's tracing,
lowering and compiling: the Python, numpy and dispatch remainder of the
window's ``daisy.execute`` spans, per miss."""

from misses import JIT, charged, misses


def read(ctx):
    spans = misses(ctx.spans)
    if spans is None:
        return None
    rest = sum(s.dur - charged(s, "sync_s", *JIT) for s in spans)
    return 1e3 * rest / len(spans)
