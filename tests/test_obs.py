"""repro.obs — span tracing, trace export, latency histograms
(DESIGN.md §13).

Covers the histogram's one-bucket percentile bound against a
sorted-sample reference (property-based), the tracer's ring-buffer
bounding and thread-safety under a writer race, the disabled mode's
shared no-op, the Chrome trace-event schema round-trip, the open-span
stack (parents, the inherited request id, inclusive charging of host
reads and compiles, no child inside a clean.* span), and the
bit-neutrality contract: serving answers are bit-identical with tracing
on vs off.
"""

import importlib.util
import json
import math
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core.constraints import FD
from repro.core.executor import Daisy, DaisyConfig
from repro.core.operators import GroupBySpec, Pred, Query
from repro.core.relation import make_relation
from repro.obs import (
    LatencyHistogram,
    NULL_TRACER,
    SpanEvent,
    Tracer,
    chrome_trace,
    coverage,
    events_from_chrome,
    host_reads,
    load_trace,
    rollup,
    to_host,
    top_spans,
    write_trace,
)
from repro.obs import trace as obs_trace
from repro.service import QueryServer

SETTINGS = dict(max_examples=25, deadline=None)

# one bucket's width at the default 16 buckets/decade — the histogram's
# documented relative error bound
BUCKET_RATIO = 10.0 ** (1.0 / 16.0)


# ------------------------------------------------------------------ histogram
@settings(**SETTINGS)
@given(
    st.lists(st.integers(1, 10_000_000), min_size=1, max_size=200),
    st.integers(0, 100),
)
def test_histogram_percentile_vs_sorted_reference(micros, q):
    """Reported percentile is >= the true order statistic (upper-edge
    reporting) and within one bucket's width of it."""
    hist = LatencyHistogram()
    samples = [v * 1e-6 for v in micros]  # 1us .. 10s, inside [lo, hi)
    for s in samples:
        hist.observe(s)
    # the order statistic numpy's percentile(method='lower') picks
    ref = sorted(samples)[int(q / 100.0 * (len(samples) - 1))]
    got = hist.percentile(q)
    assert got >= ref * (1.0 - 1e-9)
    assert got <= ref * BUCKET_RATIO * (1.0 + 1e-9)


def test_histogram_edges_and_snapshot():
    hist = LatencyHistogram(lo=1e-3, hi=1.0, buckets_per_decade=4)
    assert hist.percentile(50) == 0.0  # empty
    hist.observe(1e-5)  # underflow reports lo
    assert hist.percentile(0) == hist.lo
    hist.observe(5.0)  # overflow reports the exact observed max
    assert hist.percentile(100) == 5.0
    assert hist.max == 5.0
    assert math.isclose(hist.mean, (1e-5 + 5.0) / 2)
    snap = hist.snapshot()
    assert set(snap) == {"count", "mean_s", "p50_s", "p95_s", "p99_s", "max_s"}
    assert snap["count"] == 2
    json.dumps(snap)  # JSON-serializable


def test_histogram_merge():
    a, b = LatencyHistogram(), LatencyHistogram()
    for v in (0.001, 0.002, 0.004):
        a.observe(v)
    for v in (0.1, 0.2):
        b.observe(v)
    a.merge(b)
    assert a.count == 5
    assert a.max == 0.2
    assert a.percentile(100) >= 0.2 * (1 - 1e-9)
    mismatched = LatencyHistogram(buckets_per_decade=8)
    try:
        a.merge(mismatched)
        raise AssertionError("merge across bucket layouts must fail")
    except ValueError:
        pass


# --------------------------------------------------------------- ring buffer
def test_ring_buffer_keeps_newest():
    tr = Tracer(capacity=4)
    for i in range(10):
        tr.record("s", float(i), 1.0, seq=i)
    assert len(tr) == 4
    assert tr.dropped == 6
    assert [e.attrs["seq"] for e in tr.events()] == [6, 7, 8, 9]


def test_ring_buffer_thread_safety_under_writer_race():
    tr = Tracer(capacity=64)
    per_thread = 100

    def writer(tag):
        for i in range(per_thread):
            with tr.span("race", tag=tag, i=i):
                pass

    threads = [
        threading.Thread(target=writer, args=(t,), name=f"writer-{t}")
        for t in range(4)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    events = tr.events()
    assert len(tr) == 64 and len(events) == 64
    assert tr.dropped == 4 * per_thread - 64
    for ev in events:  # no torn records
        assert ev.name == "race" and ev.dur >= 0.0
        assert ev.thread.startswith("writer-")
        assert 0 <= ev.attrs["i"] < per_thread


def test_null_tracer_strict_noop():
    span = NULL_TRACER.span("x", a=1)
    assert span is NULL_TRACER.span("y")  # one shared context manager
    with span as sp:
        sp.set(late=True)
    NULL_TRACER.record("x", 0.0, 1.0)
    NULL_TRACER.instant("x")
    assert len(NULL_TRACER) == 0 and not NULL_TRACER
    assert NULL_TRACER.events() == []


def test_late_set_attrs_recorded():
    tr = Tracer()
    with tr.span("phase", early=1) as sp:
        sp.set(late=2)
    (ev,) = tr.events()
    assert ev.attrs == {"early": 1, "late": 2}


# ------------------------------------------------------------------- export
def _synthetic_events():
    return [
        SpanEvent("serve.execute", 1.0, 0.5, "serving", {"seq": 0}),
        SpanEvent("clean.detect", 1.1, 0.2, "serving", {"pairs": 42}),
        SpanEvent("bg.yield", 1.3, 0.0, "background-cleaner", {}),
        SpanEvent("serve.queue_wait", 0.9, 0.7, "queue", {"kind": "query"}),
    ]


def test_chrome_trace_schema_and_roundtrip(tmp_path):
    events = _synthetic_events()
    trace = chrome_trace(events, origin=0.5)
    json.dumps(trace)  # Perfetto needs plain JSON
    recs = trace["traceEvents"]
    metas = [r for r in recs if r["ph"] == "M"]
    assert {m["args"]["name"] for m in metas} == {
        "serving", "background-cleaner", "queue",
    }
    complete = [r for r in recs if r["ph"] == "X"]
    assert all(r["ts"] >= 0 and r["dur"] > 0 for r in complete)
    assert [r for r in recs if r["ph"] == "i"]  # the instant survives
    # round-trip back to events: origin-relative, same order/attrs
    back = events_from_chrome(trace)
    assert [e.name for e in back] == [e.name for e in events]
    for orig, rt in zip(events, back):
        assert rt.thread == orig.thread and rt.attrs == orig.attrs
        assert abs(rt.t0 - (orig.t0 - 0.5)) < 1e-9
        assert abs(rt.dur - orig.dur) < 1e-9
    # and through the file API
    path = str(tmp_path / "t.json")
    write_trace(path, events, origin=0.5)
    assert [e.name for e in load_trace(path)] == [e.name for e in events]


def test_rollup_self_time_stack_subtraction():
    events = [
        SpanEvent("parent", 0.0, 10.0, "t1", {}),
        SpanEvent("child", 2.0, 3.0, "t1", {}),
        SpanEvent("child", 6.0, 1.0, "t1", {}),
        # same interval on another thread must NOT subtract from t1's parent
        SpanEvent("other", 2.0, 3.0, "t2", {}),
    ]
    roll = rollup(events)
    assert roll["parent"]["count"] == 1
    assert math.isclose(roll["parent"]["total_s"], 10.0)
    assert math.isclose(roll["parent"]["self_s"], 6.0)  # 10 - 3 - 1
    assert roll["child"]["count"] == 2
    assert math.isclose(roll["child"]["self_s"], 4.0)
    assert math.isclose(roll["other"]["self_s"], 3.0)
    # self-times partition each thread's covered wall-clock
    assert math.isclose(
        sum(a["self_s"] for a in roll.values()), 10.0 + 3.0
    )


def test_coverage_windows_and_exclusion():
    events = [
        SpanEvent("a", 0.0, 1.0, "serving", {}),
        SpanEvent("b", 0.5, 1.0, "serving", {}),  # overlap counted once
        SpanEvent("q", 0.0, 4.0, "queue", {}),
    ]
    assert math.isclose(
        coverage(events, [(0.0, 2.0)], exclude_threads=("queue",)), 0.75
    )
    assert math.isclose(coverage(events, [(0.0, 2.0)]), 1.0)  # queue counts
    assert math.isclose(
        coverage(events, [(0.0, 1.0), (3.0, 4.0)], exclude_threads=("queue",)),
        0.5,
    )
    assert coverage(events, []) == 0.0


def test_top_spans_orders_by_duration():
    events = _synthetic_events()
    top = top_spans(events, k=2)
    assert [e.name for e in top] == ["serve.queue_wait", "serve.execute"]


# ------------------------------------------------- serving: neutrality + cost
def _demo_db():
    return {
        "t": make_relation(
            {
                "zip": np.array([1, 1, 2, 2, 3, 3]),
                "city": np.array([10, 11, 20, 21, 30, 30]),
            },
            overlay=["zip", "city"],
            k=4,
            rules=["zc"],
        )
    }


DEMO_RULES = {"t": [FD("zc", "zip", "city")]}
DEMO_QUERIES = [
    Query("t", preds=(Pred("zip", "==", 1),)),
    Query("t", preds=(Pred("zip", "==", 2),)),
    Query("t", groupby=GroupBySpec(keys=("city",), agg="count")),
]


def _serve_all(tracer):
    daisy = Daisy(
        _demo_db(), DEMO_RULES, DaisyConfig(use_cost_model=False),
        tracer=tracer,
    )
    server = QueryServer(daisy)
    session = server.open_session("u")
    tickets = [server.submit(session, q) for q in DEMO_QUERIES]
    server.drain()
    outs = []
    for t in tickets:
        res = t.result
        if res.groups is not None:
            outs.append({k: np.asarray(v).tolist() for k, v in res.groups.items()})
        else:
            outs.append(np.asarray(res.mask).tolist())
    return outs, daisy.clean_version, server


def test_traced_serving_bit_identical():
    """The bit-neutrality contract: tracing must never change answers or
    versions (DESIGN.md §13) — the traced run IS the untraced run plus
    span records."""
    traced_tracer = Tracer()
    plain, plain_version, _ = _serve_all(NULL_TRACER)
    traced, traced_version, server = _serve_all(traced_tracer)
    assert traced == plain
    assert traced_version == plain_version
    names = {e.name for e in traced_tracer.events()}
    # every serving layer showed up in the one shared trace
    assert {"serve.batch", "serve.cache_lookup", "serve.commit",
            "daisy.execute", "clean.detect", "clean.repair",
            "serve.queue_wait"} <= names
    # and the server surfaced per-class latency percentiles
    lat = server.snapshot()["latency"]
    assert "query" in lat and lat["query"]["count"] == len(DEMO_QUERIES)
    assert lat["query"]["p50_s"] > 0.0


def test_disabled_tracer_overhead_within_3_percent():
    """The untraced serving loop's tracing tax, checked by construction
    rather than by a wall clock (timings under parallel test workers are
    noise): every disabled span site returns the one shared, immutable
    no-op and pushes nothing on the open-span stack, and a host read with
    no span open costs one counter increment and charges nothing — so the
    cache-hit path pays two no-op call sites and a miss one increment per
    read, far inside 3% of a serve."""
    span = NULL_TRACER.span("serve.execute", seq=0, table="t")
    assert span is NULL_TRACER.span("serve.cache_lookup")
    assert type(span).__slots__ == ()  # nothing to allocate or mutate
    with span as sp:
        assert not obs_trace._OPEN.stack
        sp.set(hit=True)
    x = jnp.arange(4)
    before = host_reads()
    out = to_host(jnp.sum(x))
    assert int(out) == 6 and host_reads() == before + 1
    assert not obs_trace._OPEN.stack


# ------------------------------------------------ open-span stack + charging
def test_span_stack_parents_and_inherited_seq():
    tr = Tracer()
    seen = {}

    def other_thread():
        with tr.span("bg.increment") as sp:
            seen["bg"] = sp.parent_id

    with tr.span("serve.execute", seq=7) as outer:
        with tr.span("daisy.execute") as mid:
            t = threading.Thread(target=other_thread)
            t.start()
            t.join()
            with tr.span("clean.detect", seq=99):
                pass
        tr.record("serve.queue_wait", 0.0, 1.0, thread="queue", seq=7)
    by = {e.name: e for e in tr.events()}
    assert by["daisy.execute"].parent_id == outer.span_id
    assert by["clean.detect"].parent_id == mid.span_id
    assert by["serve.execute"].parent_id == 0
    assert by["bg.increment"].parent_id == 0 and seen["bg"] == 0
    # the request id flows down; a span's own seq wins
    assert by["daisy.execute"].attrs["seq"] == 7
    assert by["clean.detect"].attrs["seq"] == 99
    assert "seq" not in by["bg.increment"].attrs
    ids = [e.span_id for e in tr.events()]
    assert len(set(ids)) == len(ids) and all(ids)
    assert by["serve.queue_wait"].parent_id == 0
    assert not obs_trace._OPEN.stack


def test_chrome_roundtrip_keeps_span_ids():
    tr = Tracer()
    with tr.span("serve.execute", seq=1):
        with tr.span("daisy.execute"):
            pass
    events = tr.events()
    back = events_from_chrome(chrome_trace(events, origin=tr.created))
    for orig, rt in zip(events, back):
        assert (rt.span_id, rt.parent_id) == (orig.span_id, orig.parent_id)
        assert rt.attrs == orig.attrs
    assert back[0].parent_id == back[1].span_id


def test_host_reads_charge_every_open_span():
    tr = Tracer()
    x = jnp.arange(8)
    with tr.span("outer") as outer:
        to_host(jnp.sum(x))
        with tr.span("inner"):
            to_host(jnp.max(x))
            to_host(jnp.min(x))
    assert outer.attrs["syncs"] == 3
    by = {e.name: e for e in tr.events()}
    assert by["inner"].attrs["syncs"] == 2
    assert 0.0 <= by["inner"].attrs["sync_s"] <= by["outer"].attrs["sync_s"]
    assert by["outer"].attrs["sync_s"] <= by["outer"].dur


def test_jit_charges_nested_traces_once():
    tr = Tracer()
    offset = np.float32(0.25)  # a constant no other test compiles

    @jax.jit
    def inner(v):
        return v * 3.0 + offset

    @jax.jit
    def outer(v):
        return inner(v) + inner(v * 2.0)

    with tr.span("step"):
        outer(jnp.ones(37, jnp.float32)).block_until_ready()
    (ev,) = tr.events()
    a = ev.attrs
    assert a["compiles"] >= 1 and a["trace_s"] > 0.0 and a["lower_s"] > 0.0
    jit = a["trace_s"] + a["lower_s"] + a["compile_s"]
    assert jit <= ev.dur


def _fresh_demo_daisy(tracer):
    # a capacity no other test uses, so the first execute compiles
    db = {
        "t": make_relation(
            {
                "zip": np.array([1, 1, 2, 2, 3, 3]),
                "city": np.array([10, 11, 20, 21, 30, 30]),
            },
            capacity=72, overlay=["zip", "city"], k=4, rules=["zc"],
        )
    }
    return Daisy(db, DEMO_RULES, DaisyConfig(use_cost_model=False), tracer=tracer)


def test_traced_execute_charges_inclusively():
    tr = Tracer()
    daisy = _fresh_demo_daisy(tr)
    server = QueryServer(daisy)
    session = server.open_session("u")
    for q in DEMO_QUERIES:
        before = daisy.host_syncs
        server.submit(session, q)
        server.drain()
        ex = [e for e in tr.events() if e.name == "daisy.execute"][-1]
        # every read of the miss went through to_host, charged to execute
        assert ex.attrs.get("syncs", 0) == daisy.host_syncs - before
        charged = sum(
            ex.attrs.get(k, 0.0) for k in ("sync_s", "trace_s", "lower_s", "compile_s")
        )
        assert charged <= ex.dur
    events = tr.events()
    first = [e for e in events if e.name == "daisy.execute"][0]
    assert first.attrs["compiles"] > 0  # a fresh shape compiles
    by_id = {e.span_id: e for e in events}
    for e in events:
        parent = by_id.get(e.parent_id)
        if parent is None:
            continue
        # inclusive charges: a child never holds more than its parent
        for key in ("syncs", "sync_s", "trace_s", "lower_s", "compile_s", "compiles"):
            assert e.attrs.get(key, 0) <= parent.attrs.get(key, 0) + 1e-12
    # the phases sit under daisy.execute and carry the ticket's seq
    phases = [e for e in events if e.name.startswith("execute.")]
    assert {"execute.plan", "execute.step", "execute.filter",
            "execute.groupby"} <= {e.name for e in phases}
    for e in phases:
        assert by_id[e.parent_id].name == "daisy.execute"
        assert e.attrs["seq"] == by_id[e.parent_id].attrs["seq"]
    steps = [e for e in events if e.name == "execute.step"]
    assert all(e.attrs["outcome"] in ("skipped", "cleaned") for e in steps)
    assert all(e.attrs["rule"] == "zc" for e in steps)
    # queue waits split at admission
    for w in (e for e in events if e.name == "serve.queue_wait"):
        assert 0.0 <= w.attrs["admit_s"] <= w.dur


def test_no_span_opens_inside_clean_spans():
    """Only the mesh path's dist.* spans may nest in a clean.* span: a child
    there would move the clean phases' self time."""
    from repro.core.constraints import DC, Atom
    from repro.service import BackgroundCleaner

    rng = np.random.default_rng(3)
    n = 300
    zips = rng.integers(0, 20, n)
    data = {
        "zip": zips,
        "city": np.where(rng.random(n) < 0.05, rng.integers(0, 10, n), zips // 2),
        "price": rng.integers(0, 100, n).astype(np.float32),
        "disc": rng.integers(0, 10, n).astype(np.float32),
    }
    rules = {"t": [
        FD("zc", "zip", "city"),
        DC("pd", [Atom("price", "<", "price"), Atom("disc", ">", "disc")]),
    ]}
    tr = Tracer()
    db = {"t": make_relation(data, overlay=list(data), k=4, rules=["zc", "pd"])}
    daisy = Daisy(db, rules, DaisyConfig(dc_block=128), tracer=tr)
    server = QueryServer(daisy)
    session = server.open_session("u")
    for q in (
        Query("t", preds=(Pred("zip", "==", 3),)),
        Query("t", preds=(Pred("price", "<", 20.0),)),
    ):
        server.submit(session, q)
        server.drain()
    assert BackgroundCleaner(daisy, server=server).drain(max_increments=2)
    server.submit(session, Query(
        "t", preds=(Pred("price", ">=", 20.0),),
        groupby=GroupBySpec(keys=("city",), agg="count"),
    ))
    server.drain()
    events = tr.events()
    by_id = {e.span_id: e for e in events}
    names = {e.name for e in events}
    assert {"clean.detect", "clean.relax", "execute.step", "bg.increment"} <= names
    for e in events:
        parent = by_id.get(e.parent_id)
        if parent is not None and parent.name.startswith("clean."):
            assert e.name.startswith("dist."), (parent.name, e.name)
    assert {n for n in names if n.startswith("clean.")} <= {
        "clean.relax", "clean.detect", "clean.repair", "clean.mark",
        "clean.ingest_delta",
    }


def test_null_tracer_records_nothing_and_leaves_no_attrs():
    daisy = Daisy(_demo_db(), DEMO_RULES, DaisyConfig(use_cost_model=False))
    server = QueryServer(daisy)
    session = server.open_session("u")
    tickets = [server.submit(session, q) for q in DEMO_QUERIES]
    server.drain()
    assert len(NULL_TRACER) == 0 and NULL_TRACER.events() == []
    assert not obs_trace._OPEN.stack
    assert all(t.admitted == 0.0 for t in tickets)  # no admission stamp
    for t in tickets:
        for step in t.result.report.steps:
            assert "syncs" not in step.asdict()


# ------------------------------------------------------------- trace_summary
def test_trace_summary_cli(tmp_path):
    spec = importlib.util.spec_from_file_location(
        "trace_summary",
        os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "tools", "trace_summary.py",
        ),
    )
    trace_summary = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(trace_summary)
    path = str(tmp_path / "t.json")
    write_trace(path, _synthetic_events())
    out = trace_summary.summarize(path, top_k=2)
    assert "serve.execute" in out and "clean.detect" in out
    assert "top 2 slowest" in out
    assert trace_summary.main(["--trace", path, "--top", "1"]) == 0
    empty = str(tmp_path / "empty.json")
    write_trace(empty, [])
    assert trace_summary.summarize(empty).endswith("no spans")
