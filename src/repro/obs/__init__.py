"""repro.obs — span tracing, latency histograms, and per-phase cost
attribution for the serving stack (DESIGN.md §13).

The paper's thesis is that cleaning cost is driven by — and should be
attributed to — the analysis workload; this package is the layer that
makes the attribution observable.  Three pieces (recording never syncs
a device value and never changes answers or clean versions — the
bit-neutrality contract, gated in tests/test_obs.py):

* ``trace``   ``Tracer.span(name, **attrs)`` context managers writing
              ``(name, t0, dur, thread, attrs, span_id, parent_id)``
              events on the monotone clock into a thread-safe bounded
              ring buffer, each also a profiler annotation; a per-thread
              open-span stack that passes the request id ``seq`` down and
              takes the charges of ``to_host`` (the served path's one
              device-to-host read: ``syncs``, ``sync_s``) and of JAX's
              compile events (``trace_s``, ``lower_s``, ``compile_s``,
              ``compiles``, ``cache_loads``); disabled mode
              (``NULL_TRACER``) is a strict no-op;
* ``hist``    fixed-bucket log-scale ``LatencyHistogram`` giving
              p50/p95/p99 without retaining samples — what
              ``ServiceMetrics.snapshot()["latency"]`` reports per
              ticket class, the prerequisite for SLO classes;
* ``export``  Chrome trace-event (Perfetto-loadable) JSON export, the
              per-phase ``rollup`` with exclusive self-times, and the
              wall-clock ``coverage`` gate the serving benchmarks
              enforce.

Instrumented seams: ``Daisy(tracer=...)`` (``daisy.execute`` and its
phases: plan / step / filter / join / groupby; clean-step phases: relax /
detect / repair / mark, ingest deltas), ``QueryServer(tracer=...)``
(queue-wait, batch formation, cache lookup, execute, commit, ingest
barriers), ``BackgroundCleaner(tracer=...)`` (increments, yields,
preemption waits), and the sharded detection path (shuffle, per-shard
scan, overflow retries).  ``repro.launch.serve --trace out.json`` wires
them all and dumps the trace; ``tools/trace_summary.py`` reads it back.
"""

from repro.obs.export import (
    chrome_trace,
    coverage,
    events_from_chrome,
    format_rollup,
    load_trace,
    rollup,
    top_spans,
    write_trace,
)
from repro.obs.hist import LatencyHistogram
from repro.obs.trace import (
    NULL_TRACER,
    NullTracer,
    SpanEvent,
    Tracer,
    host_reads,
    to_host,
)

__all__ = [
    "LatencyHistogram",
    "NULL_TRACER",
    "NullTracer",
    "SpanEvent",
    "Tracer",
    "chrome_trace",
    "coverage",
    "events_from_chrome",
    "format_rollup",
    "host_reads",
    "load_trace",
    "rollup",
    "to_host",
    "top_spans",
    "write_trace",
]
