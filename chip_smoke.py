"""On-chip smoke check: the served cleaning path, run once on a TPU and checked.

    python chip_smoke.py             # one chip: served path + kernel check
    python chip_smoke.py --chips 4   # four chips: sharded detection only

One process holds the chip and runs every phase in turn; any failed check
exits non-zero before the last line.  Without a TPU the script exits
non-zero at once: nothing here falls back to the CPU, to Pallas interpret
mode or to the reference oracle in place of the kernel.

Phases (one chip):

* **served** — the ``repro.launch.serve --workload queries`` deployment
  (hospital-style table, FD ``zip -> city``, DC ``beds < beds AND
  quality > quality``) at 2^18 rows, served through ``QueryServer`` from
  several sessions with the background cleaner on and one streamed ingest
  chunk.  Requires no ticket error, every query answered, and DC tiles
  launched (the Pallas kernel ran).
* **kernel** — ``kops.dc_pair_scan`` on the device's Pallas kernel vs the
  reference oracle on the same arrays and worklists, at 2^18 rows of SSB
  lineorder: one DC on an int8-encoded column, one on a rank-coded
  column, each over a few hundred row blocks x all col blocks and over a
  sparse worklist.  Counts and stats must be bit-identical.

With ``--chips 4`` only the sharded path runs, with what it is compared
with: an equality-keyed DC at 2^20 rows through ``detect_auto`` on a
``("data", "model") = (4, 1)`` mesh, bit-identical to the dense scan, with
routed rows on every device.

Wall times, compile times and peak device memory are printed as one-run
smoke numbers, not measurements.  The last line of standard output is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

SERVED_ROWS = 1 << 18
INGEST_ROWS = 2048
KERNEL_ROWS = 1 << 18
SHARDED_ROWS = 4 * SERVED_ROWS
BLOCK = 256


def fail(msg: str) -> None:
    sys.exit(f"chip_smoke: FAILED: {msg}")


def check(cond: bool, msg: str) -> None:
    if not cond:
        fail(msg)


class CompileClock:
    """Backend compile time as JAX reports it; programs found in the
    persistent compilation cache are not compiled and do not count."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.count, self.seconds = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._record)

    def _record(self, event, duration, **_):
        if event == self.EVENT:
            self.count += 1
            self.seconds += duration

    def __str__(self):
        return f"{self.count} backend compiles, {self.seconds:.3f}s"


def _ready(tree):
    import jax

    return jax.block_until_ready(tree)


def served_phase(clock: CompileClock) -> None:
    """The serving deployment end to end through ``QueryServer``."""
    from repro.core.operators import Pred, Query
    from repro.launch.serve import ServeOptions, run_queries, served_failures
    from repro.service import QueryServer

    opts = ServeOptions(
        sessions=4, requests=48, rows=SERVED_ROWS - INGEST_ROWS,
        background=True, ingest_chunks=1, ingest_rows=INGEST_ROWS, seed=0,
    )
    print(
        f"[served] {SERVED_ROWS} rows ({opts.rows} seed + 1 ingest chunk of "
        f"{INGEST_ROWS}); {opts.requests} queries from {opts.sessions} "
        "sessions, background cleaner on"
    )
    t0 = time.perf_counter()
    server = run_queries(opts)
    wall = time.perf_counter() - t0
    snap = server.snapshot()
    failures = served_failures(snap, opts.requests)
    check(not failures, "served path: " + "; ".join(failures))
    daisy = server.daisy
    final_rows = int(daisy.db["h"].num_rows())
    check(final_rows == SERVED_ROWS, f"instance holds {final_rows} rows")
    print(
        f"[served] ok: errors {snap['errors']}, answered {snap['answered']}"
        f"/{opts.requests}, DC tiles launched {daisy.tiles_launched} "
        f"(foreground {snap['tiles_launched']}); wall {wall:.3f}s incl. "
        f"compiles, {clock} so far (one-run smoke numbers, not measurements)"
    )

    # the workload's Zipf draw rarely reaches its DC ranking view at this
    # size, so serve that view once more: its cleaning must launch DC tiles
    ranking = QueryServer(daisy)
    ticket = ranking.submit(
        ranking.open_session("ranking"), Query("h", preds=(Pred("beds", ">=", 400),))
    )
    t0 = time.perf_counter()
    ranking.drain()
    wall = time.perf_counter() - t0
    rsnap = ranking.snapshot()
    check(ticket.error is None, f"ranking query raised {ticket.error!r}")
    check(not served_failures(rsnap, 1), "ranking query unanswered")
    check(rsnap["tiles_launched"] > 0, "no DC tile launched: the kernel never ran")
    print(
        f"[served] DC ranking view (beds >= 400): "
        f"{ticket.result.report.result_size} rows, {rsnap['tiles_launched']} "
        f"DC tiles launched, {wall:.3f}s incl. compiles (one-run smoke number)"
    )


def _dc_scan_args(cols, atoms):
    """Encode a DC's columns exactly as ``detect_dc`` does, and build the
    fused-scan arguments."""
    from repro.core.constraints import flip_op
    from repro.core.detect import _T1_REDUCE
    from repro.kernels import ops as kops

    plan = kops.plan_dc_encodings(cols, atoms)
    check(plan is not None, f"no encoding planned for {atoms}")
    enc = {a: kops.encode_column(c, plan[a]) for a, c in cols.items()}
    ops = tuple(op for _, _, op in atoms)
    flipped = tuple(flip_op(op) for op in ops)
    return (
        [enc[left] for left, _, _ in atoms],
        [enc[right] for _, right, _ in atoms],
        ops,
        flipped,
        tuple(_T1_REDUCE[op] for op in ops),
        tuple(_T1_REDUCE[op] for op in flipped),
    ), {a: (plan[a].kind, str(enc[a].dtype)) for a in cols}


def _assert_bit_identical(got, want, what: str) -> int:
    pairs = zip(
        (got.t1_count, *got.t1_stat, got.t2_count, *got.t2_stat),
        (want.t1_count, *want.t1_stat, want.t2_count, *want.t2_stat),
    )
    for i, (g, w) in enumerate(pairs):
        g, w = np.asarray(g), np.asarray(w)
        check(g.dtype == w.dtype, f"{what}: output {i} dtype {g.dtype} != {w.dtype}")
        check(np.array_equal(g, w), f"{what}: output {i} differs from the oracle")
    check(got.tiles == want.tiles, f"{what}: tile stats {got.tiles} != {want.tiles}")
    return int(np.asarray(got.t1_count).sum())


def kernel_phase(clock: CompileClock) -> None:
    """Pallas vs the reference oracle on the device, at full size."""
    import jax.numpy as jnp

    from repro.data.generators import ssb_lineorder
    from repro.kernels import ops as kops

    n = KERNEL_ROWS
    nb = n // BLOCK
    data = ssb_lineorder(n, n_orderkeys=n // 4, n_suppkeys=100, seed=0)
    cols = {k: jnp.asarray(v) for k, v in data.items()}
    rng = np.random.default_rng(1)
    scope = jnp.asarray(rng.random(n) < 0.9)
    worklists = {
        f"{nb // 4} row blocks x all col blocks": dict(
            row_block_ids=np.arange(0, nb, 4, dtype=np.int32)
        ),
        "sparse": dict(
            row_block_ids=np.sort(rng.choice(nb, nb // 5, replace=False)),
            col_block_ids=np.sort(rng.choice(nb, 3 * nb // 10, replace=False)),
        ),
    }
    dcs = {
        "quantity< discount>": [
            ("quantity", "quantity", "<"), ("discount", "discount", ">"),
        ],
        "suppkey== extended_price<": [
            ("suppkey", "suppkey", "=="),
            ("extended_price", "extended_price", "<"),
        ],
    }
    for name, atoms in dcs.items():
        attrs = sorted({a for atom in atoms for a in atom[:2]})
        args, kinds = _dc_scan_args({a: cols[a] for a in attrs}, atoms)
        print(f"[kernel] DC {name}: encodings {kinds}")
        for wname, restr in worklists.items():
            def scan(force):
                return _ready(
                    kops.dc_pair_scan(
                        *args[:4], scope, scope, *args[4:], block=BLOCK,
                        force=force, **restr,
                    )
                )

            compiled = clock.seconds
            t0 = time.perf_counter()
            got = scan(None)
            first = time.perf_counter() - t0
            compiled = clock.seconds - compiled
            t0 = time.perf_counter()
            got = scan(None)
            second = time.perf_counter() - t0
            want = scan("ref")
            violations = _assert_bit_identical(got, want, f"{name} / {wname}")
            print(
                f"[kernel]   {wname}: {got.tiles.launched} tiles, {violations} "
                f"t1 violations, bit-identical to ref; pallas first call "
                f"{first:.3f}s incl. {compiled:.3f}s backend compile, second "
                f"{second:.3f}s (one-run smoke numbers)"
            )
            check(violations > 0, f"{name} / {wname}: no violation to compare")


def sharded_phase() -> None:
    """Sharded detection on a four-chip mesh vs the dense scan."""
    import jax
    from jax.sharding import Mesh

    from repro.core.constraints import DC, Atom
    from repro.core.detect import detect_auto
    from repro.core.relation import make_relation
    from repro.dist.shuffle import shuffle_by_key

    devices = jax.devices()[:4]
    mesh = Mesh(np.asarray(devices).reshape(4, 1), ("data", "model"))
    n = SHARDED_ROWS
    rng = np.random.default_rng(0)
    rel = make_relation(
        {
            "dept": rng.integers(0, 1000, n).astype(np.int32),
            "salary": rng.integers(1, 200, n).astype(np.float32),
            "tax": rng.integers(1, 200, n).astype(np.float32) / 10.0,
        },
        overlay=["salary", "tax"], k=4, rules=["phi"],
    )
    dc = DC("phi", [Atom("dept", "==", "dept"), Atom("salary", "<", "salary"),
                    Atom("tax", ">", "tax")])
    print(f"[sharded] {n} rows, DC dept== salary< tax>, mesh (data, model) = (4, 1)")

    # the routing alone: every device must receive rows
    keys = rel.columns["dept"].reshape(4, n // 4)
    routed = _ready(
        shuffle_by_key(keys, keys[..., None], rel.valid.reshape(4, n // 4), mesh)
    )
    placed = {}
    for shard in routed.valid.addressable_shards:
        placed[shard.device.id] = int(np.asarray(shard.data).sum())
    payload_bytes = {
        s.device.id: s.data.nbytes for s in routed.payload.addressable_shards
    }
    print(f"[sharded] routed rows per device {placed}, payload bytes per "
          f"device {payload_bytes}")
    check(sorted(placed) == sorted(d.id for d in devices),
          f"routed layout covers devices {sorted(placed)}")
    check(all(v > 0 for v in placed.values()), "a device received no rows")

    t0 = time.perf_counter()
    sharded = _ready(detect_auto(rel, dc, rel.valid, rel.valid, mesh=mesh))
    t_sharded = time.perf_counter() - t0
    t0 = time.perf_counter()
    dense = _ready(detect_auto(rel, dc, rel.valid, rel.valid))
    t_dense = time.perf_counter() - t0
    info = sharded.info
    detect_path = "dense" if info is None else "sharded"
    check(detect_path == "sharded", f"detect_path {detect_path}")
    check(info.n_shards == 4, f"n_shards {info.n_shards}")
    check(min(info.per_shard_rows) > 0, f"per-shard rows {info.per_shard_rows}")
    got, want = sharded.detection, dense.detection
    for i, (g, w) in enumerate(zip(
        (got.t1_count, got.t2_count, *got.t1_stat, *got.t2_stat),
        (want.t1_count, want.t2_count, *want.t1_stat, *want.t2_stat),
    )):
        check(np.array_equal(np.asarray(g), np.asarray(w)),
              f"sharded output {i} differs from the dense scan")
    violations = int(np.asarray(want.t1_count).sum())
    check(violations > 0, "no violation to compare")
    print(
        f"[sharded] ok: detect_path sharded, n_shards {info.n_shards}, "
        f"per-shard rows {info.per_shard_rows}, {violations} t1 violations "
        f"bit-identical to dense; sharded {t_sharded:.3f}s, dense "
        f"{t_dense:.3f}s incl. compiles (one-run smoke numbers)"
    )


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="4 runs only the sharded-detection phase on a 4-chip mesh",
    )
    args = ap.parse_args()

    import jax

    backend = jax.default_backend()
    if backend != "tpu":
        fail(f"no TPU: JAX's default backend is {backend!r}")
    devices = jax.devices()
    print(
        f"device {devices[0].device_kind} x{len(devices)} "
        f"(platform {devices[0].platform}), jax {jax.__version__}"
    )
    check(len(devices) >= args.chips, f"--chips {args.chips} but {len(devices)} devices")

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))
    from repro.launch.compile_cache import configure_compile_cache

    print(f"compile cache: {configure_compile_cache()}")
    clock = CompileClock()
    t0 = time.perf_counter()
    if args.chips == 4:
        sharded_phase()
    else:
        served_phase(clock)
        kernel_phase(clock)
    for d in devices[:args.chips]:
        stats = d.memory_stats() or {}
        print(
            f"device {d.id}: peak memory "
            f"{stats.get('peak_bytes_in_use', 'not reported')} bytes"
        )
    print(
        f"all phases passed in {time.perf_counter() - t0:.3f}s, {clock} "
        "(one-run smoke numbers)"
    )
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
        },
    }))


if __name__ == "__main__":
    main()
