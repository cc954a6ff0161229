"""Pallas kernel validation: interpret-mode kernel vs pure-jnp oracle.

Every kernel sweeps shapes/dtypes and asserts allclose against ref.py.
"""

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.constraints import DC, Atom, flip_op
from repro.core.detect import _T1_REDUCE
from repro.core.executor import Daisy, DaisyConfig
from repro.core.operators import Pred, Query
from repro.core.relation import make_relation
from repro.kernels import ops as kops
from repro.kernels import ref
from repro.kernels.dc_pairs import dc_pair_scan_pallas, resolve_block_ids
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.semijoin import semijoin_pallas

SETTINGS = dict(max_examples=10, deadline=None)


# ------------------------------------------------------------------ dc_pairs
def _fused_scan(cols, ops, rs, cs, block, force, **restr):
    """Both-role scan of the DC ``t1.c op t2.c`` over each column in turn."""
    flipped = tuple(flip_op(op) for op in ops)
    return kops.dc_pair_scan(
        cols, cols, tuple(ops), flipped, rs, cs,
        tuple(_T1_REDUCE[op] for op in ops),
        tuple(_T1_REDUCE[op] for op in flipped),
        block=block, force=force, **restr,
    )


def _assert_scans_equal(got, want):
    for g, w in zip(
        (got.t1_count, *got.t1_stat, got.t2_count, *got.t2_stat),
        (want.t1_count, *want.t1_stat, want.t2_count, *want.t2_stat),
    ):
        assert g.dtype == w.dtype
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


class TestDCPairsKernel:
    @pytest.mark.parametrize("n", [7, 64, 130, 300])
    @pytest.mark.parametrize("block", [64, 128])
    def test_matches_ref_int(self, n, block):
        rng = np.random.default_rng(n)
        a = jnp.asarray(rng.integers(0, 50, n).astype(np.int32))
        b = jnp.asarray(rng.integers(0, 50, n).astype(np.int32))
        rs = jnp.asarray(rng.random(n) < 0.7)
        cs = jnp.asarray(rng.random(n) < 0.7)
        args = ([a, b], ["<", ">"], rs, cs, block)
        _assert_scans_equal(
            _fused_scan(*args, "interpret"), _fused_scan(*args, "ref")
        )

    @pytest.mark.parametrize("ops", [["<"], ["<=", ">="], ["==", "!="]])
    def test_op_sweep_float(self, ops):
        rng = np.random.default_rng(3)
        n = 96
        cols = [jnp.asarray(rng.uniform(0, 10, n).astype(np.float32)) for _ in ops]
        rs = jnp.asarray(np.ones(n, bool))
        cs = jnp.asarray(np.ones(n, bool))
        args = (cols, ops, rs, cs, 32)
        _assert_scans_equal(
            _fused_scan(*args, "interpret"), _fused_scan(*args, "ref")
        )

    @pytest.mark.parametrize(
        "dtype", [np.int8, np.int16, jnp.bfloat16, np.float32]
    )
    def test_encoded_dtypes_match_ref(self, dtype):
        """Narrow (encoded) tiles are widened in VMEM; counts and stats —
        returned in the column's own dtype — match the oracle's."""
        rng = np.random.default_rng(11)
        n = 200
        cols = [
            jnp.asarray(rng.integers(-20, 20, n).astype(np.float32)).astype(dtype)
            for _ in range(2)
        ]
        rs = jnp.asarray(rng.random(n) < 0.8)
        cs = jnp.asarray(rng.random(n) < 0.8)
        args = (cols, ["<", ">="], rs, cs, 64)
        _assert_scans_equal(
            _fused_scan(*args, "interpret"), _fused_scan(*args, "ref")
        )

    def test_compiled_block_must_fill_lanes(self):
        a = jnp.arange(64, dtype=jnp.int32)
        with pytest.raises(ValueError, match="multiple of 128"):
            dc_pair_scan_pallas(
                [a], [a], ["<"], [">"], a > 0, a > 0, ["max"], ["min"],
                block=64,
            )

    def test_count_matches_brute_force(self):
        rng = np.random.default_rng(9)
        n = 48
        a = rng.integers(0, 20, n).astype(np.int32)
        b = rng.integers(0, 20, n).astype(np.int32)
        count, _ = ref.dc_role_scan(
            [jnp.asarray(a), jnp.asarray(b)],
            [jnp.asarray(a), jnp.asarray(b)],
            ["<", ">"],
            jnp.ones(n, bool),
            jnp.ones(n, bool),
            ["max", "min"],
            block=16,
        )
        expect = np.zeros(n, np.int32)
        for i in range(n):
            for j in range(n):
                if i != j and a[i] < a[j] and b[i] > b[j]:
                    expect[i] += 1
        np.testing.assert_array_equal(np.asarray(count), expect)

    @given(st.integers(4, 80), st.integers(0, 2**31 - 1))
    @settings(**SETTINGS)
    def test_property_random(self, n, seed):
        rng = np.random.default_rng(seed)
        a = jnp.asarray(rng.integers(0, 8, n).astype(np.int32))
        rs = jnp.asarray(rng.random(n) < 0.5)
        cs = jnp.asarray(rng.random(n) < 0.5)
        args = ([a], ["<"], rs, cs, 32)
        _assert_scans_equal(
            _fused_scan(*args, "interpret"), _fused_scan(*args, "ref")
        )


# ------------------------------------------------- block-sparse worklist (§15)
def _pair_scan(a, op, rs, cs, force, block=16, **restr):
    flipped = (flip_op(op),)
    return kops.dc_pair_scan(
        [a], [a], (op,), flipped, rs, cs,
        (_T1_REDUCE[op],), (_T1_REDUCE[flip_op(op)],),
        block=block, force=force, **restr,
    )


class TestDCPairsBlockSparse:
    """The ledger-masked worklist contract (DESIGN.md §15): restricting the
    row side to a block worklist is EXACTLY the dense scan with the
    non-worklist rows scoped out — both roles, counts and stats — and the
    launch geometry matches the worklist."""

    @given(
        st.integers(4, 96),
        st.integers(0, 2**31 - 1),
        st.sampled_from(["<", "<=", ">", ">=", "==", "!="]),
        st.sampled_from(["ref", "interpret"]),
    )
    @settings(**SETTINGS)
    def test_masked_equals_dense_on_cold_subset(self, n, seed, op, force):
        block = 16
        rng = np.random.default_rng(seed)
        a = jnp.asarray(rng.integers(0, 6, n).astype(np.int32))
        rs = jnp.asarray(rng.random(n) < 0.7)
        cs = jnp.asarray(rng.random(n) < 0.7)
        nb = -(-n // block)
        ids = np.flatnonzero(rng.random(nb) < 0.5).astype(np.int32)
        cold_rows = np.zeros(nb * block, bool)
        for b in ids:
            cold_rows[b * block : (b + 1) * block] = True
        sparse = _pair_scan(a, op, rs, cs, force, row_block_ids=ids)
        dense = _pair_scan(
            a, op, rs & jnp.asarray(cold_rows[:n]), cs, "ref"
        )
        assert sparse.tiles.launched == int(ids.size) * nb
        np.testing.assert_array_equal(
            np.asarray(sparse.t1_count), np.asarray(dense.t1_count)
        )
        np.testing.assert_array_equal(
            np.asarray(sparse.t2_count), np.asarray(dense.t2_count)
        )
        np.testing.assert_array_equal(
            np.asarray(sparse.t1_stat[0]), np.asarray(dense.t1_stat[0])
        )
        np.testing.assert_array_equal(
            np.asarray(sparse.t2_stat[0]), np.asarray(dense.t2_stat[0])
        )

    @pytest.mark.parametrize("force", ["ref", "interpret"])
    def test_all_checked_zero_launches(self, force):
        """A fully converged scope launches nothing and returns zeros and
        reduce identities — with no kernel call at all."""
        n = 48
        rng = np.random.default_rng(1)
        a = jnp.asarray(rng.integers(0, 6, n).astype(np.int32))
        scope = jnp.ones(n, bool)
        res = _pair_scan(
            a, "<", scope, scope, force,
            row_block_ids=np.array([], dtype=np.int32),
        )
        assert res.tiles.launched == 0
        assert not np.asarray(res.t1_count).any()
        assert not np.asarray(res.t2_count).any()
        np.testing.assert_array_equal(
            np.asarray(res.t1_stat[0]), np.iinfo(np.int32).min
        )
        np.testing.assert_array_equal(
            np.asarray(res.t2_stat[0]), np.iinfo(np.int32).max
        )

    @pytest.mark.parametrize("force", ["ref", "interpret"])
    def test_all_cold_matches_unrestricted(self, force):
        n, block = 80, 16
        rng = np.random.default_rng(2)
        a = jnp.asarray(rng.integers(0, 9, n).astype(np.int32))
        rs = jnp.asarray(rng.random(n) < 0.8)
        cs = jnp.asarray(rng.random(n) < 0.8)
        nb = -(-n // block)
        full = _pair_scan(
            a, "<=", rs, cs, force, row_block_ids=np.arange(nb, dtype=np.int32)
        )
        dense = _pair_scan(a, "<=", rs, cs, "ref")
        assert full.tiles.launched == dense.tiles.launched == nb * nb
        np.testing.assert_array_equal(
            np.asarray(full.t1_count), np.asarray(dense.t1_count)
        )
        np.testing.assert_array_equal(
            np.asarray(full.t1_stat[0]), np.asarray(dense.t1_stat[0])
        )

    def test_resolve_block_ids(self):
        np.testing.assert_array_equal(resolve_block_ids(4), [0, 1, 2, 3])
        np.testing.assert_array_equal(resolve_block_ids(4, blocks=(1, 3)), [1, 2])
        np.testing.assert_array_equal(
            resolve_block_ids(4, block_ids=np.array([3, 1, 3])), [1, 3]
        )
        with pytest.raises(ValueError):
            resolve_block_ids(4, block_ids=np.array([4]))


# ------------------------------------------------- compressed encodings (§15)
class TestEncodings:
    def test_boundary_columns_fall_back(self):
        """Columns straddling the exactness boundary must demote: int8
        overflow, non-integral floats, NaN."""
        overflow = np.arange(200, dtype=np.int32)  # max 199 > 127
        # 0.1f32 etc. do NOT round-trip through bf16, and are not integral
        nonint = np.array([0.1, 0.2, 0.3], dtype=np.float32)
        nanny = np.array([1.0, np.nan], dtype=np.float32)
        small = np.arange(-5, 6, dtype=np.int32)
        plan = kops.plan_dc_encodings(
            {"o": jnp.asarray(overflow), "s": jnp.asarray(small)},
            [("o", "o", "<"), ("s", "s", ">")],
        )
        assert plan["o"].kind == "orig" and plan["s"].kind == "int8"
        assert kops.plan_dc_encodings(
            {"x": jnp.asarray(nonint)}, [("x", "x", "<")]
        ) is None
        plan_nan = kops.plan_dc_encodings(
            {"x": jnp.asarray(nanny)}, [("x", "x", "==")]
        )
        assert plan_nan is None or plan_nan["x"].kind == "orig"

    def test_atom_sides_share_kind(self):
        """Both sides of an atom must land on one kind — an int8-able column
        compared against an overflow column demotes to orig."""
        plan = kops.plan_dc_encodings(
            {
                "a": jnp.asarray(np.arange(10, dtype=np.int32)),
                "b": jnp.asarray(np.arange(1000, 1010, dtype=np.int32)),
            },
            [("a", "b", "<")],
        )
        assert plan is None

    def test_encode_decode_roundtrip(self):
        vals = np.array([3.0, -7.0, 3.0, 100.0], dtype=np.float32)
        plan = kops.plan_dc_encodings(
            {"v": jnp.asarray(vals)}, [("v", "v", "==")]
        )
        assert plan["v"].kind == "code"
        codes = kops.encode_column(jnp.asarray(vals), plan["v"])
        dec = kops.decode_stat(
            codes, jnp.ones(4, jnp.int32), plan["v"], np.float32, "min"
        )
        np.testing.assert_array_equal(np.asarray(dec), vals)

    @pytest.mark.parametrize("encode", [True, False])
    def test_bit_identical_through_daisy(self, encode):
        """A DC mixing an encodable column with a boundary one produces the
        same answers and candidate state with the planner on or off."""
        n = 96
        rng = np.random.default_rng(23)
        qty = rng.integers(0, 100, n).astype(np.float32)  # int8-able
        price = rng.uniform(0.0, 500.0, n).astype(np.float32)  # orig
        rel = make_relation(
            {"qty": qty, "price": price}, overlay=["qty", "price"],
            k=8, rules=["qp"],
        )
        dc = DC("qp", [Atom("qty", "<", "qty"), Atom("price", ">", "price")])
        cfg = DaisyConfig(
            use_cost_model=False, accuracy_threshold=2.0,
            dc_block=16, strip_rows=16, dc_partitions=4,
            kernel_encodings=encode,
        )
        daisy = Daisy({"t": rel}, {"t": [dc]}, cfg)
        res = daisy.execute(Query("t", preds=(Pred("qty", ">=", 0.0),)))
        if not hasattr(TestEncodings, "_baseline"):
            TestEncodings._baseline = {}
        base = TestEncodings._baseline
        key_mask = np.asarray(res.mask)
        cand = {
            a: np.asarray(daisy.db["t"].cand[a]) for a in ("qty", "price")
        }
        if "mask" in base:
            np.testing.assert_array_equal(key_mask, base["mask"])
            for a in cand:
                np.testing.assert_array_equal(cand[a], base["cand"][a])
        else:
            base["mask"] = key_mask
            base["cand"] = cand


# ------------------------------------------------------------------ semijoin
class TestSemijoinKernel:
    @pytest.mark.parametrize("n,m", [(5, 7), (64, 64), (100, 257), (513, 100)])
    @pytest.mark.parametrize("block", [64, 256])
    def test_matches_ref(self, n, m, block):
        rng = np.random.default_rng(n * m)
        q = jnp.asarray(rng.integers(0, 40, n).astype(np.int32))
        k = jnp.asarray(rng.integers(0, 40, m).astype(np.int32))
        qm = jnp.asarray(rng.random(n) < 0.8)
        km = jnp.asarray(rng.random(m) < 0.8)
        r = ref.semijoin(q, qm, k, km, block=block)
        p = semijoin_pallas(q, qm, k, km, block=block, interpret=True)
        np.testing.assert_array_equal(np.asarray(r), np.asarray(p))

    def test_matches_numpy(self):
        rng = np.random.default_rng(0)
        q = rng.integers(0, 10, 50).astype(np.int32)
        k = rng.integers(0, 10, 30).astype(np.int32)
        km = rng.random(30) < 0.5
        got = ref.semijoin(
            jnp.asarray(q), jnp.ones(50, bool), jnp.asarray(k), jnp.asarray(km)
        )
        np.testing.assert_array_equal(np.asarray(got), np.isin(q, k[km]))

    def test_empty_key_set(self):
        q = jnp.arange(10, dtype=jnp.int32)
        k = jnp.arange(10, dtype=jnp.int32)
        got = semijoin_pallas(
            q, jnp.ones(10, bool), k, jnp.zeros(10, bool), interpret=True
        )
        assert not np.asarray(got).any()


# ----------------------------------------------------------- flash attention
class TestFlashAttention:
    @pytest.mark.parametrize("hq,hkv", [(4, 4), (4, 2), (8, 1)])
    @pytest.mark.parametrize("sq,sk", [(128, 128), (256, 256)])
    def test_causal_matches_ref(self, hq, hkv, sq, sk):
        rng = np.random.default_rng(hq * sq)
        d = 64
        q = jnp.asarray(rng.standard_normal((2, hq, sq, d)).astype(np.float32))
        k = jnp.asarray(rng.standard_normal((2, hkv, sk, d)).astype(np.float32))
        v = jnp.asarray(rng.standard_normal((2, hkv, sk, d)).astype(np.float32))
        r = ref.attention(q, k, v, causal=True)
        p = flash_attention_pallas(q, k, v, causal=True, block_q=64, block_kv=64,
                                   interpret=True)
        np.testing.assert_allclose(np.asarray(r), np.asarray(p), atol=2e-5, rtol=2e-5)

    def test_sliding_window(self):
        rng = np.random.default_rng(5)
        q = jnp.asarray(rng.standard_normal((1, 2, 256, 32)).astype(np.float32))
        k = jnp.asarray(rng.standard_normal((1, 2, 256, 32)).astype(np.float32))
        v = jnp.asarray(rng.standard_normal((1, 2, 256, 32)).astype(np.float32))
        r = ref.attention(q, k, v, causal=True, window=64)
        p = flash_attention_pallas(
            q, k, v, causal=True, window=64, block_q=64, block_kv=64, interpret=True
        )
        np.testing.assert_allclose(np.asarray(r), np.asarray(p), atol=2e-5, rtol=2e-5)

    def test_noncausal(self):
        rng = np.random.default_rng(6)
        q = jnp.asarray(rng.standard_normal((1, 2, 128, 32)).astype(np.float32))
        k = jnp.asarray(rng.standard_normal((1, 2, 128, 32)).astype(np.float32))
        v = jnp.asarray(rng.standard_normal((1, 2, 128, 32)).astype(np.float32))
        r = ref.attention(q, k, v, causal=False)
        p = flash_attention_pallas(q, k, v, causal=False, block_q=64, block_kv=64,
                                   interpret=True)
        np.testing.assert_allclose(np.asarray(r), np.asarray(p), atol=2e-5, rtol=2e-5)

    def test_bf16_io(self):
        rng = np.random.default_rng(7)
        q = jnp.asarray(rng.standard_normal((1, 2, 128, 64))).astype(jnp.bfloat16)
        k = jnp.asarray(rng.standard_normal((1, 2, 128, 64))).astype(jnp.bfloat16)
        v = jnp.asarray(rng.standard_normal((1, 2, 128, 64))).astype(jnp.bfloat16)
        r = ref.attention(q, k, v, causal=True)
        p = flash_attention_pallas(q, k, v, causal=True, block_q=64, block_kv=64,
                                   interpret=True)
        np.testing.assert_allclose(
            np.asarray(r, np.float32), np.asarray(p, np.float32), atol=3e-2
        )

    def test_softmax_rows_sum_to_one_effect(self):
        """Uniform V must pass through attention unchanged."""
        q = jnp.ones((1, 1, 128, 32), jnp.float32)
        k = jnp.ones((1, 1, 128, 32), jnp.float32)
        v = jnp.full((1, 1, 128, 32), 3.0, jnp.float32)
        p = flash_attention_pallas(q, k, v, causal=True, block_q=64, block_kv=64,
                                   interpret=True)
        np.testing.assert_allclose(np.asarray(p), 3.0, rtol=1e-6)
