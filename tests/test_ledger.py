"""The work ledger and strip semantics (DESIGN.md §11).

The load-bearing property: cleaning a scope as a union of partition-strip
increments leaves the relation row-for-row identical to one full pass —
for DCs (strip x rest scans through the strip-scoped kernel entry) and
FDs (whole-lhs-group sweeps).  That identity is what makes background
strip increments, foreground partial-work reuse and the serial reference
interchangeable, so it is property-tested over random relations and strip
schedules, not just spot-checked.
"""

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.constraints import DC, FD, Atom
from repro.core.executor import Daisy, DaisyConfig
from repro.core.ledger import StripLedger, WorkLedger, resolve_strip_rows
from repro.core.operators import Pred, Query
from repro.core.planner import strip_step
from repro.core.relation import make_relation
from repro.kernels import ops as kops

SETTINGS = dict(max_examples=15, deadline=None)


def dc_relation(n: int, seed: int):
    rng = np.random.default_rng(seed)
    price = rng.uniform(0.0, 50.0, n).astype(np.float32)
    disc = (50.0 - price + rng.normal(0, 4.0, n)).astype(np.float32)
    return make_relation(
        {"price": price, "disc": disc}, overlay=["price", "disc"],
        k=8, rules=["pd"],
    )


DC_PD = DC("pd", [Atom("price", "<", "price"), Atom("disc", ">", "disc")])


def fd_relation(n: int, seed: int, groups: int = 6):
    rng = np.random.default_rng(seed)
    a = rng.integers(0, groups, n).astype(np.int32)
    b = (a * 10 + rng.integers(0, 3, n)).astype(np.int32)
    return make_relation({"a": a, "b": b}, overlay=["a", "b"], k=8, rules=["r"])


FD_AB = FD("r", "a", "b")


def dc_daisy(n: int, seed: int, block: int = 8):
    return Daisy(
        {"t": dc_relation(n, seed)}, {"t": [DC_PD]},
        DaisyConfig(use_cost_model=False, dc_block=block, strip_rows=block,
                    dc_partitions=4),
    )


def assert_same_state(a: Daisy, b: Daisy, table: str, attrs):
    for attr in attrs:
        np.testing.assert_array_equal(
            np.asarray(a.db[table].cand[attr]), np.asarray(b.db[table].cand[attr])
        )
        np.testing.assert_array_equal(
            np.asarray(a.db[table].ccount[attr]),
            np.asarray(b.db[table].ccount[attr]),
        )
    for rule, checked in a.db[table].checked.items():
        np.testing.assert_array_equal(
            np.asarray(checked), np.asarray(b.db[table].checked[rule])
        )


# ----------------------------------------------------- strip-union property
class TestStripUnionIdentity:
    @given(st.integers(10, 60), st.integers(0, 10**6), st.integers(1, 3))
    @settings(**SETTINGS)
    def test_dc_strip_union_equals_full_pass(self, n, seed, per_call):
        """Union of bounded DC strip increments == one full pass, row for
        row (candidates, counts, checked bits) — any strip batch size."""
        inc = dc_daisy(n, seed)
        full = dc_daisy(n, seed)
        steps = 0
        while inc.clean_scope_increment("t", "pd", max_strips=per_call):
            steps += 1
            assert steps < 100
        assert full.clean_scope_increment("t", "pd") is not None
        assert inc.cold_count("t", "pd") == 0
        assert_same_state(inc, full, "t", ("price", "disc"))

    @given(st.integers(12, 60), st.integers(0, 10**6), st.integers(4, 16))
    @settings(**SETTINGS)
    def test_fd_increment_union_equals_full_pass(self, n, seed, max_rows):
        """Union of bounded FD group-sweep increments == one unbounded
        sweep (the §11 identity on the FD side)."""
        cfg = lambda: DaisyConfig(use_cost_model=False)  # noqa: E731
        inc = Daisy({"t": fd_relation(n, seed)}, {"t": [FD_AB]}, cfg())
        full = Daisy({"t": fd_relation(n, seed)}, {"t": [FD_AB]}, cfg())
        steps = 0
        while inc.clean_scope_increment("t", "r", max_rows=max_rows):
            steps += 1
            assert steps < 100
        while full.clean_scope_increment("t", "r"):
            pass
        assert inc.cold_count("t", "r") == 0
        assert_same_state(inc, full, "t", ("a", "b"))

    def test_interleaved_query_and_strips_match_serial(self):
        """Strip increments interleaved with a foreground DC query converge
        on the serial reference's state — the §11 ledger-equal argument:
        when every row's evidence is merged exactly once (full-coverage
        scopes; the strip schedule only permutes WHICH pass merges it),
        the final overlay is schedule-independent.  The query spans the
        whole relation so its cleaning step is itself a cold-strip sweep
        (the §4.2 partner strip is empty; answer-overlap partner evidence
        is intentionally out of scope — it repeats per schedule)."""
        n, seed = 48, 3
        inter = dc_daisy(n, seed)
        serial = dc_daisy(n, seed)
        q = Query("t", preds=(Pred("price", ">=", -1.0),))
        inter.clean_scope_increment("t", "pd", max_strips=2)
        a = inter.execute(q)
        b = serial.execute(q)
        np.testing.assert_array_equal(np.asarray(a.mask), np.asarray(b.mask))
        while inter.clean_scope_increment("t", "pd", max_strips=1):
            pass
        while serial.clean_scope_increment("t", "pd"):
            pass
        assert inter.cold_count("t", "pd") == 0
        assert_same_state(inter, serial, "t", ("price", "disc"))


# ------------------------------------------------- strip-scoped kernel entry
class TestStripScopedScan:
    @staticmethod
    def _scan(col, row_scope, col_scope, block, force, **restr):
        return kops.dc_pair_scan(
            [col], [col], ["<"], [">"], row_scope, col_scope, ["max"],
            ["min"], block=block, force=force, **restr,
        )

    @pytest.mark.parametrize("force", ["ref", "interpret"])
    def test_row_blocks_matches_masked_full_scan(self, force):
        rng = np.random.default_rng(0)
        n, block = 40, 8
        cols = [rng.integers(0, 9, n).astype(np.int32) for _ in range(2)]
        scope = np.ones(n, bool)
        for lo, hi in ((0, 1), (1, 3), (3, 5), (0, 5)):
            strip_mask = np.zeros(n, bool)
            strip_mask[lo * block : hi * block] = True
            want = self._scan(cols[0], scope & strip_mask, scope, block, force)
            got = self._scan(
                cols[0], scope & strip_mask, scope, block, force,
                row_blocks=(lo, hi),
            )
            for g, w in zip(
                (got.t1_count, got.t1_stat[0], got.t2_count, got.t2_stat[0]),
                (want.t1_count, want.t1_stat[0], want.t2_count, want.t2_stat[0]),
            ):
                np.testing.assert_array_equal(np.asarray(g), np.asarray(w))

    def test_row_blocks_validation(self):
        rng = np.random.default_rng(1)
        col = rng.integers(0, 5, 16).astype(np.int32)
        scope = np.ones(16, bool)
        with pytest.raises(ValueError):
            self._scan(col, scope, scope, 8, "ref", row_blocks=(1, 5))


# --------------------------------------------------------------- the ledger
class TestWorkLedger:
    def test_resolve_strip_rows_alignment(self):
        assert resolve_strip_rows(None, 256) == 256
        assert resolve_strip_rows(300, 256) == 512
        assert resolve_strip_rows(8, 8) == 8
        with pytest.raises(ValueError):
            resolve_strip_rows(-4, 8)

    def test_strip_geometry_and_coverage(self):
        scope = StripLedger("t", "r", capacity=40, strip_rows=8)
        assert scope.n_strips == 5
        cold = np.zeros(40, bool)
        cold[3] = cold[17] = True
        scope.observe_cold(cold)
        assert list(scope.cold_strips()) == [0, 2]
        assert scope.cold_count == 2
        assert scope.strips_done == 3
        assert scope.support == pytest.approx(0.6)
        mask = scope.strip_mask([0, 2])
        assert mask[:8].all() and mask[16:24].all() and not mask[8:16].any()
        assert scope.strip_blocks([2], block=8) == (2, 3)
        assert scope.strip_blocks([0, 2], block=4) == (0, 6)

    def test_versions_and_progress(self):
        ledger = WorkLedger(strip_rows=8, block=8)
        ledger.register("t", "r", 16, np.ones(16, bool))
        assert ledger.version("t", "r") == 0
        assert ledger.versions([("t", "r"), ("u", "x")]) == (0, 0)
        ledger.bump("t", "r")
        ledger.commit("t", "r", np.zeros(16, bool))
        assert ledger.version("t", "r") == 2
        prog = ledger.progress()
        assert prog == {
            "t/r": {
                "strips_done": 2,
                "strips_total": 2,
                "cold_rows": 0,
                "tiles_launched": 0,
                "tiles_skipped": 0,
            }
        }
        assert ledger.support("t", "r") == 1.0
        assert ledger.support("nope", "x") == 1.0  # unknown scopes read warm

    def test_daisy_ledger_tracks_checked_commits(self):
        daisy = dc_daisy(32, seed=9)
        scope = daisy.ledger.scope("t", "pd")
        assert scope.cold_count == 32 and scope.strips_done == scope.n_strips - 4
        v0 = daisy.scope_version("t", "pd")
        rep = daisy.clean_scope_increment("t", "pd", max_strips=1)
        assert rep.mode == "strip"
        assert daisy.scope_version("t", "pd") > v0
        assert scope.cold_count == 24
        assert len(scope.cold_strips()) == 3

    def test_bump_then_grow_seeds_all_cold(self):
        """A scope first seen through a bare version bump (capacity 0) and
        later grown without a cold mask must read ALL-COLD — a warm-seeded
        unknown scope would skip every clean forever."""
        ledger = WorkLedger(strip_rows=8, block=8)
        ledger.bump("t", "r")
        scope = ledger.register("t", "r", 32)
        assert scope.version == 1  # the bump survived the growth
        assert scope.cold_count == 32
        assert scope.support == 0.0
        assert list(scope.cold_strips()) == [0, 1, 2, 3]

    def test_dc_rule_added_to_live_daisy_stays_cleanable(self):
        """The table5 dynamic-rule pattern, DC edition: a rule appended to
        a running Daisy (ledger scope created lazily) must still clean —
        its first full step may not resolve to an empty strip set."""
        daisy = dc_daisy(32, seed=9)
        daisy.rules["t"].append(
            DC("pd2", [Atom("disc", "<", "disc"), Atom("price", ">", "price")])
        )
        daisy._collect_stats()
        rep = daisy.clean_scope_increment("t", "pd2")
        assert rep is not None and rep.mode == "full"
        assert daisy.cold_count("t", "pd2") == 0
        q = Query("t", preds=(Pred("disc", ">=", 0.0),))
        assert daisy.execute(q).report.steps[1].mode == "skipped"

    def test_planner_strip_step_carries_strips(self):
        step = strip_step("t", DC_PD, np.array([1, 3]))
        assert step.mode == "strip" and step.strips == (1, 3)

    def test_foreground_full_skips_background_strips(self):
        """Partial-work reuse: the detect-pair cost of a full clean shrinks
        strictly with background strip progress (the ledger gate)."""
        cold = dc_daisy(64, seed=4)
        half = dc_daisy(64, seed=4)
        for _ in range(4):
            assert half.clean_scope_increment("t", "pd", max_strips=1)
        q = Query("t", preds=(Pred("price", ">=", 0.0),))
        cold.config.accuracy_threshold = 2.0  # force full cleaning
        half.config.accuracy_threshold = 2.0
        p0 = cold.detect_pairs
        mask_cold = np.asarray(cold.execute(q).mask)
        cold_pairs = cold.detect_pairs - p0
        p0 = half.detect_pairs
        mask_half = np.asarray(half.execute(q).mask)
        half_pairs = half.detect_pairs - p0
        assert half_pairs < cold_pairs
        np.testing.assert_array_equal(mask_cold, mask_half)
        assert_same_state(cold, half, "t", ("price", "disc"))
