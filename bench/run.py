"""The benchmark: analyst sessions served by Daisy's query service on a TPU.

    python3 bench/run.py --workload ssb-lo.q1-ranges --seed 7 --seconds 40 --trace 0

One run serves one cell of ``BENCHMARK.json`` (a configuration under a
traffic mix) for ``--seconds`` and prints, as the last line of standard
output, one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics with ``--trace 0``, its
per-layer metrics with ``--trace 1``), ``device``, ``dc_tiles_launched``
(DC kernel tiles the window launched), ``warm_up_passes``,
``window_compiles`` (backend compiles or cache loads inside the window:
count, seconds)
and, when traced, ``breakdown``, followed by ``compared``: each number the
correctness check compared, with its limit.

What runs is the program's served path and nothing else: sessions submit
to ``QueryServer.submit``, one serving thread runs ``QueryServer.run`` and
a ``BackgroundCleaner`` cleans behind it, so every answer goes through
``Daisy.execute``, the ``clean.*`` phases, ``detect_auto`` and the Pallas
DC pair-scan kernel.  The benchmark makes the data and the traffic from
``--seed`` (``bench/configs``, ``bench/traffic``), reads the metrics with
its own readers (``bench/layer_metrics``) and decides ``correct`` with its
own numpy reference (``bench/reference.py``).  Without a TPU, or with
fewer chips than the cell asks for, it exits non-zero and prints no
result.

A cell is found by its names alone: ``bench/configs/<config>.json`` and
``<config>.py``, ``bench/traffic/<traffic>.json`` and
``bench/layer_metrics/<metric>.py``.  A configuration may hold several
tables (``tables``, see ``bench/datagen.py``): every table is served by
one ``Daisy``, each with its own candidate overlays and rules, warm-up
waits until every rule of every table is clean, and the correctness check
samples each table's state.  Its traffic may join them (``table``,
``joins`` and ``"table.column"`` names, see ``bench/traffic.py``); join
answers are judged by the configuration's own ``check_answers``, and a run
whose configuration has none stops rather than pass them unchecked.
"""

from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

import numpy as np  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

import datagen  # noqa: E402
import reference  # noqa: E402
import traffic  # noqa: E402

# a query still unanswered this long after the window closed counts as failed
GRACE_S = 60.0
# a warm-up pass issues no new query after this long, clean or not
WARM_UP_CAP_S = 120.0
# warm-up passes at most, while the previous pass still met new shapes
WARM_UP_PASSES = 3
# how long stopping may wait for a step or an increment in progress (in a
# cold checkout one of them may be compiling)
STOP_S = 900.0
# traces and other run artefacts, at a fixed path inside the checkout
RUNS_DIR = BENCH / ".runs"


def fail(msg: str, code: int = 2) -> None:
    print(f"bench: {msg}", file=sys.stderr)
    sys.exit(code)


def load_benchmark() -> dict:
    path = ROOT / "BENCHMARK.json"
    if not path.exists():
        fail(f"{path} not found")
    return json.loads(path.read_text())


def find_cell(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    fail(f"no workload {name!r} in BENCHMARK.json")


def require_device(chips: int):
    """The chips this cell runs on; exits non-zero without a TPU."""
    import jax

    backend = jax.default_backend()
    if backend != "tpu":
        fail(f"no TPU: JAX's default backend is {backend!r}", 3)
    devices = jax.devices()
    if len(devices) < chips:
        fail(f"the cell needs {chips} chips, JAX sees {len(devices)}", 3)
    return devices[:chips]


def load_peaks(kind: str) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())
    if kind not in table["devices"]:
        fail(f"device kind {kind!r} has no entry in bench/peaks.json", 3)
    return table["devices"][kind]


def configure_compile_cache() -> str:
    """JAX's persistent compilation cache where the program places it (the
    directory ``JAX_COMPILATION_CACHE_DIR`` names, else
    ``<checkout>/.jax_cache``), keeping every program the warm-up compiles,
    however fast."""
    from repro.launch.compile_cache import configure_compile_cache as place

    path = place()
    cache_writes(True)
    return path


def cache_writes(on: bool) -> None:
    """Let compiles write to the persistent cache, or stop them (reads go
    on).  The window writes nothing, so that no run finds there what an
    earlier run's window compiled: only warm-ups fill the cache."""
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      0.0 if on else 1e12)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileClock:
    """Backend compile time as JAX reports it: the time to compile a
    lowered program, or to load it from the persistent cache (the program
    lowers a new closure on many calls, so a cached program is loaded
    again).  Each compile is kept as a host interval ending when JAX
    reported it.  Also counts the programs written to the persistent
    cache."""

    EVENT = "/jax/core/compile/backend_compile_duration"
    WRITE = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        import jax

        self.events = []  # (end perf_counter, seconds)
        self.writes = 0
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._record)
        jax.monitoring.register_event_listener(self._count_write)

    def _record(self, event, duration, **_):
        if event == self.EVENT:
            with self._lock:
                self.events.append((time.perf_counter(), float(duration)))

    def _count_write(self, event, **_):
        if event == self.WRITE:
            with self._lock:
                self.writes += 1

    def between(self, t0: float, t1: float):
        """The compiles that overlap ``[t0, t1]``, clipped to it, as
        (end, seconds)."""
        with self._lock:
            out = []
            for end, d in self.events:
                lo, hi = max(end - d, t0), min(end, t1)
                if hi > lo:
                    out.append((hi, hi - lo))
            return out


# ---------------------------------------------------------------- instance
def program_rules(cfg):
    from repro.core.constraints import DC, FD, Atom

    out = []
    for rule in cfg["rules"]:
        if "fd" in rule:
            out.append(FD(rule["name"], tuple(rule["fd"]["lhs"]), rule["fd"]["rhs"]))
        else:
            out.append(DC(rule["name"], [Atom(l, op, r) for l, op, r in rule["dc"]]))
    return out


def overlay_attrs(cfg):
    """Every attribute a rule may repair gets a candidate overlay."""
    attrs = set()
    for rule in cfg["rules"]:
        if "fd" in rule:
            attrs.update(rule["fd"]["lhs"])
            attrs.add(rule["fd"]["rhs"])
        else:
            for left, _, right in rule["dc"]:
                attrs.update((left, right))
    return [c for c in cfg["columns"] if c in attrs]


class Deployment:
    """One served instance: one relation per table, executor, server,
    serving thread and background cleaner (not yet started), as the
    configuration states them."""

    def __init__(self, cfg, data, tracer=None):
        from repro.core.executor import Daisy, DaisyConfig
        from repro.core.relation import make_relation
        from repro.service import BackgroundCleaner, QueryServer
        from repro.service.cache import ResultCache

        self.views = datagen.tables(cfg)
        self.tables = tuple(self.views)
        self.table = self.tables[0]  # the one a query names by default
        daisy_cfg = DaisyConfig(**cfg["daisy"])
        rels = {
            t: make_relation(
                data[t], overlay=overlay_attrs(view), k=daisy_cfg.k,
                rules=[r["name"] for r in view["rules"]],
            )
            for t, view in self.views.items()
        }
        self.daisy = Daisy(
            rels, {t: program_rules(view) for t, view in self.views.items()},
            daisy_cfg, tracer=tracer,
        )
        srv = cfg["server"]
        self.server = QueryServer(
            self.daisy, cache=ResultCache(srv["cache_entries"]),
            max_batch=srv["max_batch"],
        )
        self.serving = threading.Thread(
            target=self.server.run, name="serving", daemon=True
        )
        self.serving.start()
        self.cleaner = BackgroundCleaner(
            self.daisy, server=self.server, **cfg["background"]
        )

    def scopes(self):
        """Every (table, rule) the background cleaner and warm-up clean."""
        return [(t, r["name"]) for t, view in self.views.items() for r in view["rules"]]

    def relations(self):
        return [self.daisy.db[t] for t in self.tables]

    def stop(self) -> None:
        self.server.stop()
        self.cleaner.stop(timeout=STOP_S)
        self.serving.join(STOP_S)
        if self.serving.is_alive():
            raise RuntimeError("serving thread did not stop")


def build_query(q: traffic.QuerySpec):
    """The program's ``Query`` for a drawn query."""
    from repro.core.operators import GroupBySpec, JoinClause, Query

    groupby = None
    if q.groupby is not None:
        keys, agg, value = q.groupby
        groupby = GroupBySpec(keys=keys, agg=agg, value=value, table=q.groupby_table)
    joins = tuple(JoinClause(j.right, j.left_on, j.right_on, _preds(j.preds))
                  for j in q.joins) if q.joins else ()
    return Query(q.table, preds=_preds(q.preds), project=q.project, joins=joins,
                 groupby=groupby)


def _preds(preds):
    from repro.core.operators import Pred

    return tuple(Pred(c, op, v) for c, op, v in preds)


def warm_up(cfg, mix, seed: int, data, clock: CompileClock) -> int:
    """Compile, or load from the persistent cache, what the window will run
    (``data``: each table's dirty columns); returns the passes it took.

    The program compiles a kernel for every DC worklist length it meets, and
    those lengths follow from the data, the traffic and how the sessions and
    the background cleaner interleave.  So a pass does what the window does,
    on a scratch copy of this run's instance: the sessions' streams of this
    seed, closed loop, with the background cleaner on, until every template
    has been answered and every rule's scope on every table is clean; then
    the copy is thrown away.  Passes repeat while the last one still wrote
    a program to the persistent cache (met a shape no earlier run had), up
    to ``WARM_UP_PASSES``.  The window starts from a fresh copy."""
    names = {t.name for t in mix.templates}
    for n in range(1, WARM_UP_PASSES + 1):
        written = clock.writes
        dep = Deployment(cfg, data)
        scopes = dep.scopes()
        for table, rule in scopes:
            dep.daisy.cold_count(table, rule)  # sized before the threads read it
        stop = threading.Event()
        t0 = time.perf_counter()
        try:
            threads, records = serve_window(dep, mix, seed, t0, float("inf"), stop)
            while time.perf_counter() < t0 + WARM_UP_CAP_S:
                time.sleep(0.05)
                with records.lock:
                    seen = {r.spec.template for r in records if r.t1 is not None}
                if seen == names and not any(
                    dep.daisy.cold_count(table, rule) for table, rule in scopes
                ):
                    break
            stop.set()
            # no limit: in a cold checkout one query compiles for minutes
            for th in threads:
                th.join()
        finally:
            dep.stop()
        del dep
        gc.collect()
        if clock.writes == written:
            break
    return n


# ------------------------------------------------------------------ window
class Record:
    __slots__ = ("spec", "ticket", "t0", "t1")

    def __init__(self, spec, ticket, t0):
        self.spec, self.ticket, self.t0, self.t1 = spec, ticket, t0, None


class Records(list):
    """The records of one serving loop, appended as queries are submitted."""

    def __init__(self):
        super().__init__()
        self.lock = threading.Lock()


def serve_window(dep, mix, seed: int, t_start: float, t_end: float,
                 stop: threading.Event = None):
    """Closed loop: each session submits its next query as soon as the
    previous one is answered, until the window closes (or ``stop`` is set).
    Returns the session threads and the records of every query submitted,
    which grow while they run."""
    records = Records()
    stop = stop or threading.Event()
    bounded = t_end != float("inf")

    def session(i: int) -> None:
        stream = mix.session_stream([int(seed), datagen.STREAM_TRAFFIC, i])
        sess = dep.server.open_session(f"analyst{i}")
        while True:
            query_spec = next(stream)
            q = build_query(query_spec)
            t0 = time.perf_counter()
            if t0 >= t_end or stop.is_set():
                break
            rec = Record(query_spec, dep.server.submit(sess, q), t0)
            with records.lock:
                records.append(rec)
            wait = max(t_end + GRACE_S - t0, 0.0) if bounded else None
            if not rec.ticket.event.wait(wait):
                break
            rec.t1 = time.perf_counter()

    threads = [
        threading.Thread(target=session, args=(i,), name=f"analyst{i}")
        for i in range(mix.sessions)
    ]
    while time.perf_counter() < t_start:
        time.sleep(0.001)
    for th in threads:
        th.start()
    # the cleaner starts with the sessions, so every window begins from
    # the same dirty instance
    dep.cleaner.start()
    return threads, records


# ----------------------------------------------------------- layer metrics
def load_reader(name: str):
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", BENCH / "layer_metrics" / f"{name}.py"
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def applies(metric: dict, cell: str, e2e_names) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric["moves"] in e2e_names


# -------------------------------------------------------------------- run
def run_cell(cell: dict, bench: dict, seed: int, seconds: float, trace: bool,
             devices=None, peaks=None, overrides=None, root: Path = BENCH) -> dict:
    """One run of one cell; returns the result object (without printing).

    ``overrides`` replaces configuration keys (``datagen.override``; the
    tests run small instances on the CPU with it), and ``root`` is the
    directory that holds ``configs`` and ``traffic``."""
    import jax

    from repro.obs import Tracer
    from repro.obs.trace import NULL_TRACER

    devices = devices or jax.devices()[:cell["chips"]]
    cfg = datagen.override(datagen.load_config(cell["config"], root), overrides or {})
    mix = traffic.Mix(traffic.load_mix(cell["traffic"], root), cfg.get("domains", {}),
                      next(iter(datagen.tables(cfg))))
    clock = CompileClock()

    insts = datagen.generate(cfg, seed)
    data = {t: inst.dirty for t, inst in insts.items()}
    cache_writes(True)
    passes = warm_up(cfg, mix, seed, data, clock)
    cache_writes(False)
    tracer = Tracer(capacity=1 << 21) if trace else NULL_TRACER
    dep = Deployment(cfg, data, tracer=tracer)
    jax.block_until_ready([rel.valid for rel in dep.relations()])
    setup_s = time.perf_counter() - PROCESS_START

    trace_dir = RUNS_DIR / f"trace-{cell['name']}"
    if trace:
        from reduce_trace import start_trace

        shutil.rmtree(trace_dir, ignore_errors=True)
        start_trace(trace_dir)
    m0 = counters(dep)
    t_start = time.perf_counter() + 0.01
    t_end = t_start + seconds
    threads, records = serve_window(dep, mix, seed, t_start, t_end)
    window_mark = None
    if trace:
        from reduce_trace import window_annotation

        window_mark = window_annotation(t_start, t_end)
    else:
        time.sleep(max(t_end - time.perf_counter(), 0.0))
    m1 = counters(dep)
    for th in threads:
        th.join(GRACE_S + 5)
    if any(th.is_alive() for th in threads):
        raise RuntimeError("a session thread did not finish")
    dep.stop()
    if trace:
        # stop only once the device has drained, so that an operation
        # running across the window's end is in the trace, clipped to it
        jax.block_until_ready(dep.relations())
        jax.profiler.stop_trace()
    stats = [d.memory_stats() or {} for d in devices]
    peak = max(s.get("peak_bytes_in_use", 0) for s in stats)

    answered = [r for r in records if r.t1 is not None and r.ticket.error is None]
    failed = len(records) - len(answered)
    in_window = sum(1 for r in answered if r.t1 <= t_end)
    lat_ms = np.array([(r.t1 - r.t0) * 1e3 for r in answered])
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": int(peak),
    }

    e2e = [m for m in bench["end_to_end"]
           if cell["name"] in m.get("workloads", [cell["name"]])]
    metrics, breakdown = {}, None
    if not trace:
        values = {
            "answers_per_s": in_window / seconds,
            "query_p95_ms": float(np.percentile(lat_ms, 95)) if len(lat_ms) else None,
            "query_p50_ms": float(np.percentile(lat_ms, 50)) if len(lat_ms) else None,
            "setup_s": setup_s,
        }
        for m in e2e:
            if values.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    else:
        from reduce_trace import reduce_trace

        spans = [e for e in tracer.events() if t_start <= e.t0 < t_end]
        compiles = clock.between(t_start, t_end)
        red = reduce_trace(trace_dir, window_mark, spans, compiles)
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = red.busy_s
        device["window_s"] = red.window_s
        breakdown = red.breakdown
        # ``data``: the columns of a one-table configuration (None where it
        # has several); ``tables``: each table's view and columns
        ctx = SimpleNamespace(
            cfg=cfg, data=data[dep.table] if len(data) == 1 else None,
            tables={t: SimpleNamespace(cfg=dep.views[t], data=data[t]) for t in data},
            answers=in_window, spans=spans, compiles=compiles, trace=red,
            counters=(m0, m1), peaks=peaks,
        )
        names = {m["name"] for m in e2e}
        for m in bench["per_layer"]:
            if not applies(m, cell["name"], names):
                continue
            value = load_reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    compared = reference.check(cfg, insts, dep, records, seed)
    del dep
    correct = failed == 0 and all(c["value"] <= c["limit"] for c in compared.values())
    in_window = clock.between(t_start, t_end)
    result = {
        "correct": bool(correct),
        "attempted": len(records),
        "failed": failed,
        "metrics": metrics,
        "device": device,
        "dc_tiles_launched": m1["tiles_launched"] - m0["tiles_launched"],
        "warm_up_passes": passes,
        "window_compiles": [len(in_window), sum(d for _, d in in_window)],
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["compared"] = compared
    return result


def counters(dep) -> dict:
    """Program counters read at the window's edges (host ints)."""
    m, d = dep.server.metrics, dep.daisy
    return {
        "answered": m.queries, "cache_hits": m.cache_hits,
        "detect_pairs": d.detect_pairs, "tiles_launched": d.tiles_launched,
    }


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        fail("--seed must be >= 0")

    bench = load_benchmark()
    cell = find_cell(bench, args.workload)
    if not (ROOT / "src" / "repro").is_dir():
        fail("the program (src/repro) is not in this checkout")
    devices = require_device(cell["chips"])
    peaks = load_peaks(devices[0].device_kind)
    sys.path.insert(0, str(ROOT / "src"))
    configure_compile_cache()

    result = run_cell(cell, bench, args.seed, args.seconds, bool(args.trace),
                      devices=devices, peaks=peaks)
    print(f"DC kernel tiles launched in the window: {result['dc_tiles_launched']}",
          file=sys.stderr)
    n, secs = result["window_compiles"]
    print(f"warm-up passes: {result['warm_up_passes']}; backend compiles or "
          f"cache loads in the window: {n} ({secs:.3f} s)", file=sys.stderr)
    for name, c in result["compared"].items():
        print(f"compared {name}: {c['value']} (limit {c['limit']})", file=sys.stderr)
    print(json.dumps(result))


if __name__ == "__main__":
    main()
