"""Sharded detection (dist/detect.py) vs the dense scans — DESIGN.md §8.

The sharded path must be BIT-identical to the dense one: counts, extremal
partner stats, candidate tables, frequencies, flags.  In-process tests use
logical shards on the single CPU device (the routing/scan/un-route math is
the same); the subprocess test repeats the equivalence on a real 8-device
mesh where ``shard_map`` actually partitions the shards.
"""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.constraints import DC, FD, Atom, equality_key_attrs
from repro.core.detect import (
    detect_dc,
    detect_dc_auto,
    detect_fd,
    detect_fd_auto,
    will_shard,
)
from repro.core.executor import Daisy, DaisyConfig
from repro.core.operators import Pred, Query
from repro.core.relation import make_relation
from repro.dist.detect import (
    detect_dc_sharded_info,
    detect_fd_sharded_info,
    pair_count_report,
)


def one_device_mesh():
    from jax.sharding import Mesh

    return Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1), ("data", "model"))


def random_rel(n=96, n_keys=7, seed=0):
    rng = np.random.default_rng(seed)
    return make_relation(
        {
            "dept": rng.integers(0, n_keys, n).astype(np.int32),
            "salary": rng.integers(1, 9, n).astype(np.float32),
            "tax": rng.integers(1, 9, n).astype(np.float32) / 10.0,
        },
        overlay=["salary", "tax"],
        k=4,
        rules=["phi"],
    )


DC_EQ = DC(
    "phi",
    [
        Atom("dept", "==", "dept"),
        Atom("salary", "<", "salary"),
        Atom("tax", ">", "tax"),
    ],
)
DC_NO_EQ = DC(
    "phi_noeq", [Atom("salary", "<", "salary"), Atom("tax", ">", "tax")]
)


def assert_dc_equal(dense, shard):
    np.testing.assert_array_equal(np.asarray(dense.t1_count), np.asarray(shard.t1_count))
    np.testing.assert_array_equal(np.asarray(dense.t2_count), np.asarray(shard.t2_count))
    for a in range(len(dense.t1_stat)):
        np.testing.assert_array_equal(
            np.asarray(dense.t1_stat[a]), np.asarray(shard.t1_stat[a])
        )
        np.testing.assert_array_equal(
            np.asarray(dense.t2_stat[a]), np.asarray(shard.t2_stat[a])
        )


class TestDCShardedEquivalence:
    def test_full_scope_bit_identical(self):
        rel = random_rel()
        dense = detect_dc(rel, DC_EQ, rel.valid, rel.valid)
        shard, info = detect_dc_sharded_info(
            rel, DC_EQ, rel.valid, rel.valid, one_device_mesh(), n_shards=4
        )
        assert_dc_equal(dense, shard)
        assert info.n_shards == 4 and info.routed_rows == 96
        # sharding actually shrinks the comparison space
        assert info.sharded_pairs < info.dense_pairs
        assert int(np.asarray(dense.t1_count).sum()) > 0  # non-trivial case

    def test_asymmetric_scopes(self):
        """Incremental-cleaning shape: row_scope (answer) vs col_scope (rest)."""
        rel = random_rel(seed=3)
        rng = np.random.default_rng(4)
        rs = jnp.asarray(rng.random(96) < 0.3) & rel.valid
        cs = jnp.asarray(rng.random(96) < 0.8) & rel.valid
        dense = detect_dc(rel, DC_EQ, rs, cs)
        shard, _ = detect_dc_sharded_info(
            rel, DC_EQ, rs, cs, one_device_mesh(), n_shards=4
        )
        assert_dc_equal(dense, shard)

    def test_overflow_retry_on_skew(self):
        """One key -> one shard: the first shuffle overflows its capacity
        and the driver retries with a doubled factor, still bit-identical."""
        rng = np.random.default_rng(1)
        n = 64
        rel = make_relation(
            {
                "dept": np.zeros(n, np.int32),
                "salary": rng.integers(1, 9, n).astype(np.float32),
                "tax": rng.integers(1, 9, n).astype(np.float32) / 10.0,
            },
            overlay=["salary", "tax"],
            k=4,
            rules=["phi"],
        )
        dense = detect_dc(rel, DC_EQ, rel.valid, rel.valid)
        shard, info = detect_dc_sharded_info(
            rel, DC_EQ, rel.valid, rel.valid, one_device_mesh(), n_shards=4
        )
        assert info.retries >= 1
        assert info.capacity_factor > 2.0
        assert info.per_shard_rows == [64, 0, 0, 0]
        assert_dc_equal(dense, shard)

    def test_negative_zero_key_routes_together(self):
        """-0.0 == 0.0 must share a shard (float keys collapse -0.0)."""
        rel = make_relation(
            {
                "pivot": np.array([0.0, -0.0, 0.0, 1.0], dtype=np.float32),
                "salary": np.array([1.0, 3.0, 2.0, 5.0], dtype=np.float32),
                "tax": np.array([0.1, 0.2, 0.3, 0.1], dtype=np.float32),
            },
            overlay=["salary", "tax"],
            k=4,
        )
        dc = DC(
            "phi0",
            [
                Atom("pivot", "==", "pivot"),
                Atom("salary", "<", "salary"),
                Atom("tax", ">", "tax"),
            ],
        )
        dense = detect_dc(rel, dc, rel.valid, rel.valid)
        shard, _ = detect_dc_sharded_info(
            rel, dc, rel.valid, rel.valid, one_device_mesh(), n_shards=2
        )
        assert int(np.asarray(dense.t1_count).sum()) == 1  # row2 vs row1
        assert_dc_equal(dense, shard)

    def test_sub_one_capacity_factor_clamped(self):
        """factor < 1 must not shrink the un-route scatter target below the
        relation capacity (rows would silently drop)."""
        rel = random_rel(seed=11)
        dense = detect_dc(rel, DC_EQ, rel.valid, rel.valid)
        shard, _ = detect_dc_sharded_info(
            rel, DC_EQ, rel.valid, rel.valid, one_device_mesh(),
            n_shards=4, capacity_factor=0.5,
        )
        assert shard.t1_count.shape == dense.t1_count.shape
        assert_dc_equal(dense, shard)

    def test_no_equality_atom_rejected(self):
        rel = random_rel()
        assert equality_key_attrs(DC_NO_EQ) == ()
        with pytest.raises(ValueError, match="no same-attribute equality atom"):
            detect_dc_sharded_info(
                rel, DC_NO_EQ, rel.valid, rel.valid, one_device_mesh(), n_shards=4
            )


class TestFDShardedEquivalence:
    def test_bit_identical_both_groupings(self):
        rng = np.random.default_rng(5)
        n = 80
        rel = make_relation(
            {
                "zip": rng.integers(0, 9, n).astype(np.int32),
                "city": rng.integers(0, 5, n).astype(np.int32),
            },
            overlay=["zip", "city"],
            k=8,
            rules=["fd"],
        )
        fd = FD("fd", "zip", "city")
        dense = detect_fd(rel, fd, rel.valid, k=8)
        shard, info = detect_fd_sharded_info(
            rel, fd, rel.valid, one_device_mesh(), k=8, n_shards=4
        )
        np.testing.assert_array_equal(np.asarray(dense.violated), np.asarray(shard.violated))
        np.testing.assert_array_equal(np.asarray(dense.rhs_cand), np.asarray(shard.rhs_cand))
        np.testing.assert_array_equal(np.asarray(dense.rhs_count), np.asarray(shard.rhs_count))
        np.testing.assert_array_equal(np.asarray(dense.lhs_cand), np.asarray(shard.lhs_cand))
        np.testing.assert_array_equal(np.asarray(dense.lhs_count), np.asarray(shard.lhs_count))
        assert bool(np.asarray(dense.overflow)) == bool(np.asarray(shard.overflow))
        assert info.routed_rows == n

    def test_multi_attr_lhs(self):
        rng = np.random.default_rng(6)
        n = 60
        rel = make_relation(
            {
                "a": rng.integers(0, 4, n).astype(np.int32),
                "b": rng.integers(0, 3, n).astype(np.int32),
                "y": rng.integers(0, 5, n).astype(np.int32),
            },
            overlay=["y"],
            k=8,
            rules=["fd2"],
        )
        fd = FD("fd2", ("a", "b"), "y")
        dense = detect_fd(rel, fd, rel.valid, k=8)
        shard, _ = detect_fd_sharded_info(
            rel, fd, rel.valid, one_device_mesh(), k=8, n_shards=4
        )
        np.testing.assert_array_equal(np.asarray(dense.violated), np.asarray(shard.violated))
        np.testing.assert_array_equal(np.asarray(dense.rhs_cand), np.asarray(shard.rhs_cand))
        np.testing.assert_array_equal(np.asarray(dense.rhs_count), np.asarray(shard.rhs_count))
        assert dense.lhs_cand is None and shard.lhs_cand is None


class TestDispatch:
    def test_no_mesh_falls_back_dense(self, monkeypatch):
        import repro.dist.detect as ddet

        def boom(*a, **k):  # the sharded path must NOT be taken
            raise AssertionError("sharded path taken without a mesh")

        monkeypatch.setattr(ddet, "detect_dc_sharded", boom)
        rel = random_rel()
        det = detect_dc_auto(rel, DC_EQ, rel.valid, rel.valid, mesh=None)
        dense = detect_dc(rel, DC_EQ, rel.valid, rel.valid)
        assert_dc_equal(dense, det)

    def test_no_equality_atom_falls_back_dense(self, monkeypatch):
        import repro.dist.detect as ddet

        def boom(*a, **k):
            raise AssertionError("sharded path taken for a keyless DC")

        monkeypatch.setattr(ddet, "detect_dc_sharded", boom)
        rel = random_rel()
        assert not will_shard(DC_NO_EQ, one_device_mesh(), 4)
        det = detect_dc_auto(
            rel, DC_NO_EQ, rel.valid, rel.valid, mesh=one_device_mesh(), n_shards=4
        )
        dense = detect_dc(rel, DC_NO_EQ, rel.valid, rel.valid)
        assert_dc_equal(dense, det)

    def test_mesh_with_key_takes_sharded(self):
        rel = random_rel()
        mesh = one_device_mesh()
        assert will_shard(DC_EQ, mesh, 4)
        assert will_shard(FD("f", "dept", "salary"), mesh, 4)
        det = detect_dc_auto(rel, DC_EQ, rel.valid, rel.valid, mesh=mesh, n_shards=4)
        dense = detect_dc(rel, DC_EQ, rel.valid, rel.valid)
        assert_dc_equal(dense, det)

    def test_fd_auto_equivalent(self):
        rel = random_rel()
        fd = FD("f", "dept", "salary")
        dense = detect_fd(rel, fd, rel.valid, k=4)
        auto = detect_fd_auto(rel, fd, rel.valid, k=4, mesh=one_device_mesh(), n_shards=4)
        np.testing.assert_array_equal(np.asarray(dense.rhs_cand), np.asarray(auto.rhs_cand))
        np.testing.assert_array_equal(np.asarray(dense.violated), np.asarray(auto.violated))


class TestExecutorIntegration:
    def test_daisy_sharded_matches_dense(self):
        """End-to-end: the same query workload over a mesh-configured Daisy
        produces the same repairs and reports the sharded path."""
        def build(mesh):
            rel = random_rel(seed=9)
            cfg = DaisyConfig(k=4, mesh=mesh, detect_shards=4)
            return Daisy({"t": rel}, {"t": [DC_EQ]}, cfg)

        q = Query(table="t", preds=(Pred("salary", ">", 2.0),), project=("salary", "tax"))
        d_dense = build(None)
        d_shard = build(one_device_mesh())
        r_dense = d_dense.execute(q)
        r_shard = d_shard.execute(q)
        assert [s.mode for s in r_dense.report.steps] == [
            s.mode for s in r_shard.report.steps
        ]
        assert r_shard.report.steps[0].detect_path == "sharded"
        assert r_dense.report.steps[0].detect_path == "dense"
        np.testing.assert_array_equal(np.asarray(r_dense.mask), np.asarray(r_shard.mask))
        for attr in ("salary", "tax"):
            np.testing.assert_array_equal(
                np.asarray(d_dense.db["t"].cand[attr]),
                np.asarray(d_shard.db["t"].cand[attr]),
            )
            np.testing.assert_array_equal(
                np.asarray(d_dense.db["t"].ccount[attr]),
                np.asarray(d_shard.db["t"].ccount[attr]),
            )


class TestPairCountReport:
    def test_uniform_savings(self):
        rep = pair_count_report(1024, 16)
        assert rep["dense_pairs"] == 1024**2
        assert rep["sharded_pairs_uniform"] == 16 * 64**2
        assert rep["pair_savings_x"] == pytest.approx(16.0)

    def test_single_shard_no_savings(self):
        rep = pair_count_report(100, 1)
        assert rep["pair_savings_x"] == pytest.approx(1.0)


_SUBPROCESS_TEST = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, numpy as np
    import jax.numpy as jnp
    from jax.sharding import Mesh

    mesh = Mesh(np.asarray(jax.devices()[:8]).reshape(4, 2), ("data", "model"))

    from repro.core.constraints import DC, Atom
    from repro.core.relation import make_relation
    from repro.core.detect import detect_dc
    from repro.dist.detect import detect_dc_sharded_info

    rng = np.random.default_rng(0)
    n = 128
    rel = make_relation(
        {
            "dept": rng.integers(0, 11, n).astype(np.int32),
            "salary": rng.integers(1, 9, n).astype(np.float32),
            "tax": rng.integers(1, 9, n).astype(np.float32) / 10.0,
        },
        overlay=["salary", "tax"], k=4, rules=["phi"],
    )
    dc = DC("phi", [Atom("dept", "==", "dept"), Atom("salary", "<", "salary"),
                    Atom("tax", ">", "tax")])
    dense = detect_dc(rel, dc, rel.valid, rel.valid)
    # n_shards == the mesh's DP extent (4): shard_map partitions the scans
    shard, info = detect_dc_sharded_info(rel, dc, rel.valid, rel.valid, mesh)
    assert info.n_shards == 4, info
    np.testing.assert_array_equal(np.asarray(dense.t1_count), np.asarray(shard.t1_count))
    np.testing.assert_array_equal(np.asarray(dense.t2_count), np.asarray(shard.t2_count))
    for a in range(3):
        np.testing.assert_array_equal(np.asarray(dense.t1_stat[a]),
                                      np.asarray(shard.t1_stat[a]))
        np.testing.assert_array_equal(np.asarray(dense.t2_stat[a]),
                                      np.asarray(shard.t2_stat[a]))
    assert int(np.asarray(dense.t1_count).sum()) > 0
    print("SUBPROCESS_OK")
""")


@pytest.mark.slow
def test_sharded_detect_on_mesh_subprocess():
    """Dense/sharded equivalence with shard_map on a real 4x2 device mesh."""
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    res = subprocess.run(
        [sys.executable, "-c", _SUBPROCESS_TEST],
        capture_output=True, text=True, timeout=1200,
        # the child must never reach for an accelerator the parent may hold
        env={"PYTHONPATH": os.path.join(repo_root, "src"),
             "PATH": os.environ.get("PATH", "/usr/bin:/bin"),
             "JAX_PLATFORMS": "cpu"},
        cwd=repo_root,
    )
    assert "SUBPROCESS_OK" in res.stdout, res.stdout + "\n" + res.stderr
