"""The window's cache misses as the executor's ``daisy.execute`` spans, and
what the program charged to each: host reads (``syncs``, ``sync_s``) and
JAX's tracing, lowering and compiling (``trace_s``, ``lower_s``,
``compile_s``), each inclusive of the spans beneath it.

A program whose spans carry no ``span_id`` charges nothing to them: the
readers then report nothing rather than zeros."""

from __future__ import annotations

from typing import List, Optional

EXECUTE = "daisy.execute"
JIT = ("trace_s", "lower_s", "compile_s")


def misses(spans) -> Optional[List]:
    """The ``daisy.execute`` spans, or None where there are none or the
    program does not charge its spans."""
    out = [s for s in spans if s.name == EXECUTE]
    if not out or "span_id" not in getattr(type(out[0]), "_fields", ()):
        return None
    return out


def charged(span, *keys) -> float:
    return float(sum(span.attrs.get(k, 0) for k in keys))
