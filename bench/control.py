"""Readings of the control and the planted faults at a cell's own size.

    python3 bench/control.py --workload ssb-lo.q1-ranges --seeds 1,2,3 --seconds 20
    python3 bench/control.py --workload tax.state-salary --seeds 4,5,6 --case half_batch

Runs the cell once per seed in one process with the control (or a fault
of ``bench/faults.py``) patched into the program, and prints one JSON line
per seed with the numbers the correctness check compared.  The
benchmark's own runs never run this; it is how the limits' upper readings
are taken on the chip.  Like the benchmark, it exits non-zero without a
TPU.
"""

from __future__ import annotations

import argparse
import json
import sys

import run


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--case", default="control")
    args = ap.parse_args(argv)

    bench = run.load_benchmark()
    cell = run.find_cell(bench, args.workload)
    devices = run.require_device(cell["chips"])
    sys.path.insert(0, str(run.ROOT / "src"))
    run.configure_compile_cache()
    import faults

    patch = faults.control if args.case == "control" else faults.FAULTS[args.case]
    for seed in (int(s) for s in args.seeds.split(",")):
        with patch():
            result = run.run_cell(cell, bench, seed, args.seconds, False,
                                  devices=devices)
        print(json.dumps({
            "case": args.case, "workload": args.workload, "seed": seed,
            "correct": result["correct"], "attempted": result["attempted"],
            "failed": result["failed"], "compared": result["compared"],
        }), flush=True)


if __name__ == "__main__":
    main()
