"""Benchmark orchestrator: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--full] [--only NAME]
        [--json-out [DIR]] [--trace]

Default is the quick profile (CI-sized datasets); --full runs the
paper-scale sweeps.  CSVs land in experiments/bench/.

``--json-out`` writes one machine-readable ``BENCH_<name>.json`` per
benchmark (acceptance gates, headline numbers, and — under ``--trace`` —
the per-phase span rollup from DESIGN.md §13) into DIR (default: the CSV
output dir).  CI uploads these as artifacts so a run's gate results are
inspectable without re-running.  ``--trace`` hands a live ``repro.obs``
tracer to every benchmark whose ``run`` accepts one, which also arms
their trace-coverage gates.
"""

from __future__ import annotations

import argparse
import inspect
import json
import os
import sys
import time
import traceback

from benchmarks import (
    fig07_orderkey_selectivity,
    fig08_suppkey_selectivity,
    fig09_cost_model_switch,
    fig10_multi_rule,
    fig11_violation_scaling,
    fig12_dc_inequality,
    fig13_join_queries,
    fig_dist_detect,
    kernel_sparsity,
    serve_bg_warmup,
    serve_ingest,
    serve_overload,
    serve_throughput,
    table5_accuracy,
    table8_exploratory,
)
from benchmarks.common import OUT_DIR
from repro.launch.compile_cache import configure_compile_cache

MODULES = [
    ("fig07", fig07_orderkey_selectivity),
    ("fig08", fig08_suppkey_selectivity),
    ("fig09", fig09_cost_model_switch),
    ("fig10", fig10_multi_rule),
    ("fig11", fig11_violation_scaling),
    ("fig12", fig12_dc_inequality),
    ("fig13", fig13_join_queries),
    ("fig_dist", fig_dist_detect),
    ("kernel_sparsity", kernel_sparsity),
    ("serve", serve_throughput),
    ("serve_bg", serve_bg_warmup),
    ("serve_ingest", serve_ingest),
    ("serve_overload", serve_overload),
    ("table5", table5_accuracy),
    ("table8", table8_exploratory),
]


def _run_one(name, mod, quick: bool, trace: bool):
    """Run one benchmark, normalizing its return into the JSON record
    shape.  Benchmarks predating ISSUE 8 return a CSV path; the traced
    serving benchmarks return ``{artifact, gates, headline, rollup}``."""
    kwargs = {"quick": quick}
    tracer = None
    if trace and "tracer" in inspect.signature(mod.run).parameters:
        from repro.obs import Tracer

        tracer = Tracer()
        kwargs["tracer"] = tracer
    out = mod.run(**kwargs)
    if not isinstance(out, dict):
        out = {"artifact": out}
    record = {
        "benchmark": name,
        "quick": quick,
        "status": "ok",
        "artifact": out.get("artifact"),
        "gates": out.get("gates", {}),
        "headline": out.get("headline", {}),
        "rollup": out.get("rollup"),
    }
    if tracer is not None:
        record["spans"] = len(tracer)
        record["dropped_spans"] = tracer.dropped
    return record


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None)
    ap.add_argument(
        "--json-out", nargs="?", const=OUT_DIR, default=None, metavar="DIR",
        help="write BENCH_<name>.json per benchmark (default DIR: %(const)s)",
    )
    ap.add_argument(
        "--trace", action="store_true",
        help="trace benchmarks that accept a tracer; arms coverage gates",
    )
    args = ap.parse_args()
    configure_compile_cache()
    quick = not args.full
    failures = 0
    for name, mod in MODULES:
        if args.only and args.only != name:
            continue
        print(f"=== {name} ===")
        t0 = time.time()
        try:
            record = _run_one(name, mod, quick, args.trace)
            record["seconds"] = round(time.time() - t0, 3)
            print(f"--- {name} done in {time.time()-t0:.1f}s\n")
        except Exception:
            failures += 1
            record = {
                "benchmark": name, "quick": quick, "status": "failed",
                "seconds": round(time.time() - t0, 3),
                "error": traceback.format_exc(limit=8),
            }
            print(f"!!! {name} FAILED")
            traceback.print_exc()
        if args.json_out:
            os.makedirs(args.json_out, exist_ok=True)
            path = os.path.join(args.json_out, f"BENCH_{name}.json")
            with open(path, "w") as f:
                json.dump(record, f, indent=2, sort_keys=True)
            print(f"    wrote {path}")
    if failures:
        sys.exit(f"{failures} benchmarks failed")
    print("all benchmarks complete")


if __name__ == "__main__":
    main()
