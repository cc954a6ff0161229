"""The background cleaner (DESIGN.md §10): seeded foreground/background
interleaving stays bit-identical to the PR 3 serial service, preemption
yields to foreground tickets within one increment, and per-scope cache
invalidation evicts exactly the touched fingerprints.

The interleaving tests use cluster-DISJOINT data (each zip group's city
values are unique to the group), where every answer is a pure function of
its own group's cleaning state — so bit-identity must hold for EVERY
schedule, which is what the seeded sweep asserts.
"""

import json
import threading

import numpy as np
import pytest

from repro.core.constraints import FD
from repro.core.cost import (
    CostModel,
    ScopePriority,
    prioritize_scopes,
    sharded_detect_cost,
)
from repro.core.executor import Daisy, DaisyConfig
from repro.core.ledger import TABLE_ROWS_RULE
from repro.core.operators import Pred, Query
from repro.core.relation import make_relation
from repro.service import BackgroundCleaner, QueryServer, rule_deps

GROUPS = 6
PER = 8
N = GROUPS * PER


def disjoint_factory(seed: int = 5):
    """Disjoint clusters: group g's city values live in [g*8, (g+1)*8);
    row 0 of each group is dirty, row 1 clean (deterministic detect work)."""
    rng = np.random.default_rng(seed)
    zipc = np.repeat(np.arange(GROUPS, dtype=np.int32), PER)
    city = (zipc * 8).astype(np.int32)
    edit = rng.random(N) < 0.3
    edit[0::PER] = True
    edit[1::PER] = False
    city[edit] = (zipc[edit] * 8 + rng.integers(1, 8, int(edit.sum()))).astype(
        np.int32
    )
    return {
        "h": make_relation(
            {"zip": zipc, "city": city}, overlay=["zip", "city"], k=8, rules=["zc"]
        )
    }


RULES = {"h": [FD("zc", "zip", "city")]}


def fresh_daisy(factory=disjoint_factory, rules=RULES):
    return Daisy(factory(), rules, DaisyConfig(use_cost_model=False))


def view(g: int) -> Query:
    """Group g's majority-city view — its answer depends on the group's
    repair candidates, so bit-identity is a real check."""
    return Query("h", preds=(Pred("city", "==", g * 8),))


# ------------------------------------------------------------- interleaving
class TestSeededInterleaving:
    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_bit_identical_to_serial_service(self, seed):
        """Any seeded interleaving of foreground queries and background
        increments answers bit-identically to the PR 3 serial service
        (no background) over the same query order — and converges on the
        same final candidate state."""
        rng = np.random.default_rng(seed)
        queries = [view(int(g)) for g in rng.integers(0, GROUPS, 18)]

        daisy = fresh_daisy()
        server = QueryServer(daisy)
        cleaner = BackgroundCleaner(daisy, server=server, increment_rows=PER)
        sess = server.open_session("s")
        answers = []
        it = iter(queries)
        pending = next(it, None)
        while pending is not None:
            if rng.random() < 0.5:
                t = server.submit(sess, pending)
                server.drain()
                answers.append(np.asarray(t.result.mask))
                pending = next(it, None)
            else:
                cleaner.drain(max_increments=int(rng.integers(1, 3)))

        serial = fresh_daisy()
        for q, got in zip(queries, answers):
            np.testing.assert_array_equal(
                got, np.asarray(serial.execute(q).mask), err_msg=str(q)
            )

        # converged state: finish background, run every view serially on the
        # reference; overlays must match exactly (Lemma 4 / §10 argument)
        cleaner.drain()
        for g in range(GROUPS):
            serial.execute(view(g))
        for attr in ("zip", "city"):
            np.testing.assert_array_equal(
                np.asarray(daisy.db["h"].cand[attr]),
                np.asarray(serial.db["h"].cand[attr]),
            )
            np.testing.assert_array_equal(
                np.asarray(daisy.db["h"].ccount[attr]),
                np.asarray(serial.db["h"].ccount[attr]),
            )

    def test_warmed_scope_serves_first_touch_without_detect(self):
        daisy = fresh_daisy()
        server = QueryServer(daisy)
        cleaner = BackgroundCleaner(daisy, server=server, increment_rows=N)
        assert cleaner.drain() >= 1
        assert daisy.cold_count("h", "zc") == 0
        sess = server.open_session("s")
        for g in range(GROUPS):
            server.submit(sess, view(g))
        server.drain()
        assert server.metrics.detect_calls == 0  # foreground paid nothing
        assert server.metrics.bg_detect_calls > 0


# --------------------------------------------------------------- preemption
class TestPreemption:
    def test_drain_yields_to_pending_foreground(self):
        daisy = fresh_daisy()
        server = QueryServer(daisy)
        cleaner = BackgroundCleaner(daisy, server=server, increment_rows=PER)
        sess = server.open_session("s")
        server.submit(sess, view(0))
        assert cleaner.preempted()
        assert cleaner.drain() == 0  # yielded before any increment
        assert server.metrics.bg_yields == 1
        server.drain()
        assert not cleaner.preempted()
        assert cleaner.drain(max_increments=1) == 1

    def test_increment_releases_lock_between_steps(self):
        """Preemption points: after every increment the executor lock is
        free — a foreground thread is never blocked across increments."""
        daisy = fresh_daisy()
        cleaner = BackgroundCleaner(daisy, increment_rows=PER)
        while cleaner.step() is not None:
            acquired = daisy.lock.acquire(timeout=1.0)
            assert acquired
            daisy.lock.release()

    def test_latency_bound_under_running_cleaner(self):
        """A query submitted while the cleaner thread churns a large cold
        backlog is answered within a small multiple of one increment."""
        daisy = fresh_daisy()
        server = QueryServer(daisy)
        cleaner = BackgroundCleaner(
            daisy, server=server, increment_rows=PER, idle_wait=0.005
        )
        serving = threading.Thread(target=server.run, daemon=True)
        serving.start()
        cleaner.start()
        try:
            sess = server.open_session("s")
            res = server.query(sess, view(GROUPS - 1), timeout=60)
            assert res.mask is not None
        finally:
            cleaner.stop()
            server.stop()
            serving.join(timeout=30)
        assert not serving.is_alive()


def dc_daisy(n: int = 64, seed: int = 7, block: int = 8):
    """A DC scope with many cold strips (n/block of them): the backlog the
    strip-grained increments must work through with bounded pauses."""
    from repro.core.constraints import DC, Atom
    from repro.core.relation import make_relation

    rng = np.random.default_rng(seed)
    price = rng.uniform(0.0, 50.0, n).astype(np.float32)
    disc = (50.0 - price + rng.normal(0, 4.0, n)).astype(np.float32)
    rel = make_relation(
        {"price": price, "disc": disc}, overlay=["price", "disc"],
        k=8, rules=["pd"],
    )
    dc = DC("pd", [Atom("price", "<", "price"), Atom("disc", ">", "disc")])
    return Daisy(
        {"t": rel}, {"t": [dc]},
        DaisyConfig(use_cost_model=False, dc_block=block, strip_rows=block,
                    dc_partitions=4),
    )


class TestDCPreemption:
    """The §11 bound: background DC cleaning is now per-strip increments
    that release the executor lock between strips — mirroring the FD
    ``increment_rows`` latency tests above."""

    def test_dc_increments_are_strip_bounded_and_release_lock(self):
        daisy = dc_daisy()
        scope = daisy.ledger.scope("t", "pd")
        backlog = len(scope.cold_strips())
        assert backlog >= 8  # a real multi-increment backlog
        cleaner = BackgroundCleaner(daisy, increment_strips=1)
        strip_rows = daisy.ledger.strip_rows
        increments = 0
        while True:
            rep = cleaner.step()
            if rep is None:
                break
            increments += 1
            # bounded: one increment cleans at most one strip of rows
            assert rep.step.answer_size <= strip_rows or rep.step.mode == "full"
            # the lock is free between increments — a foreground ticket
            # waits at most one strip scan, not a full pairwise pass
            assert daisy.lock.acquire(timeout=1.0)
            daisy.lock.release()
        assert increments == backlog
        assert daisy.cold_count("t", "pd") == 0

    def test_dc_drain_yields_between_strips(self):
        """Pending foreground work preempts a DC backlog mid-scope: drain
        stops between strip increments, not after the whole scope."""
        daisy = dc_daisy()
        server = QueryServer(daisy)
        cleaner = BackgroundCleaner(daisy, server=server, increment_strips=1)
        assert cleaner.drain(max_increments=2) == 2
        assert daisy.cold_count("t", "pd") > 0  # mid-scope
        sess = server.open_session("s")
        server.submit(sess, Query("t", preds=(Pred("price", ">=", 0.0),)))
        assert cleaner.preempted()
        assert cleaner.drain() == 0  # yielded with the scope still cold
        assert server.metrics.bg_yields == 1

    def test_dc_latency_bound_under_running_cleaner(self):
        """A DC-touching query submitted while the cleaner thread churns a
        many-strip backlog is answered promptly (within the test timeout,
        i.e. a small multiple of one strip increment — not after a full
        pairwise pass of the whole backlog)."""
        daisy = dc_daisy(n=128)
        server = QueryServer(daisy)
        cleaner = BackgroundCleaner(
            daisy, server=server, increment_strips=1, idle_wait=0.005
        )
        serving = threading.Thread(target=server.run, daemon=True)
        serving.start()
        cleaner.start()
        try:
            sess = server.open_session("s")
            res = server.query(
                sess, Query("t", preds=(Pred("price", ">=", 25.0),)), timeout=60
            )
            assert res.mask is not None
        finally:
            cleaner.stop()
            server.stop()
            serving.join(timeout=30)
        assert not serving.is_alive()


# ----------------------------------------------------------------- the cache
class TestCacheExactness:
    def two_table_db(self):
        db = disjoint_factory()
        db["t2"] = make_relation(
            {"a": np.array([1, 1, 2, 2]), "b": np.array([5, 6, 7, 8])},
            overlay=["a", "b"],
            k=4,
            rules=["ab"],
        )
        return db

    TWO_RULES = {"h": [FD("zc", "zip", "city")], "t2": [FD("ab", "a", "b")]}

    def test_background_bumps_invalidate_exactly_touched_scopes(self):
        daisy = Daisy(self.two_table_db(), self.TWO_RULES,
                      DaisyConfig(use_cost_model=False))
        server = QueryServer(daisy)
        cleaner = BackgroundCleaner(daisy, server=server, increment_rows=4)
        sess = server.open_session("s")
        qa, qb = view(0), Query("t2", preds=(Pred("b", "==", 5),))
        server.submit(sess, qa)
        server.submit(sess, qb)
        server.drain()

        # clean ONLY t2's rule in the background
        assert daisy.clean_scope_increment("t2", "ab") is not None
        server.submit(sess, qa)  # h untouched -> still a hit
        server.submit(sess, qb)  # t2 advanced -> stale, re-executed
        server.drain()
        assert server.cache.stale == 1
        assert [e.cached for e in sess.lineage] == [False, False, True, False]

        # clean h's rule: now qa goes stale exactly once, qb stays cached
        while daisy.clean_scope_increment("h", "zc") is not None:
            pass
        t5 = server.submit(sess, qa)
        t6 = server.submit(sess, qb)
        server.drain()
        assert not t5.cached and t6.cached
        assert server.cache.stale == 2

    def test_no_rule_overlap_never_invalidated(self):
        """A query depending on no rule carries only its table's ``__rows__``
        pseudo-dependency (ingest invalidation, DESIGN.md §12): background
        cleaning bumps rule scopes, never ``__rows__``, so it can never
        evict the entry."""
        daisy = Daisy(self.two_table_db(), self.TWO_RULES,
                      DaisyConfig(use_cost_model=False))
        server = QueryServer(daisy)
        sess = server.open_session("s")
        q = Query("t2", preds=())  # no rule attrs -> only the rows pseudo-dep
        assert rule_deps(q, daisy.rules) == (("t2", TABLE_ROWS_RULE),)
        server.submit(sess, q)
        server.drain()
        BackgroundCleaner(daisy, server=server).drain()
        t = server.submit(sess, q)
        server.drain()
        assert t.cached and server.cache.stale == 0

    def test_equal_vectors_bit_identical_after_background(self):
        """The §10 version contract: with the dependency vector unchanged
        since the entry was stored, a re-execution is bit-identical."""
        daisy = fresh_daisy()
        server = QueryServer(daisy)
        sess = server.open_session("s")
        BackgroundCleaner(daisy, server=server).drain()
        t1 = server.submit(sess, view(2))
        server.drain()
        v = daisy.scope_versions(t1.deps)
        again = daisy.execute(view(2))
        assert daisy.scope_versions(t1.deps) == v
        np.testing.assert_array_equal(
            np.asarray(t1.result.mask), np.asarray(again.mask)
        )


# ------------------------------------------------------------ DC + priority
class TestDCBackground:
    def test_dc_scope_full_cleans_in_one_increment(self, salary_rel, dc_sal_tax):
        daisy = Daisy(
            {"t": salary_rel}, {"t": [dc_sal_tax]},
            DaisyConfig(use_cost_model=False, dc_partitions=4),
        )
        serial = Daisy(
            {"t": salary_rel}, {"t": [dc_sal_tax]},
            DaisyConfig(use_cost_model=False, dc_partitions=4),
        )
        rep = daisy.clean_scope_increment("t", "dc_sal_tax")
        assert rep is not None and rep.mode == "full"
        assert daisy.cold_count("t", "dc_sal_tax") == 0
        d0 = daisy.detect_calls
        q = Query("t", preds=(Pred("salary", ">=", 0.0),))
        got = daisy.execute(q)
        assert got.report.steps[0].mode == "skipped"
        assert daisy.detect_calls == d0
        # serial reference full-cleans via the cost-model switch path
        serial.execute(Query("t", preds=(Pred("salary", ">=", 0.0),)))
        np.testing.assert_array_equal(
            np.asarray(got.mask), np.asarray(serial.execute(q).mask)
        )


class TestPriorityModel:
    def test_touch_probability_orders_scopes(self):
        daisy = Daisy(
            TestCacheExactness().two_table_db(), TestCacheExactness.TWO_RULES,
            DaisyConfig(use_cost_model=False),
        )
        server = QueryServer(daisy)
        cleaner = BackgroundCleaner(daisy, server=server)
        sess = server.open_session("s")
        for _ in range(5):  # demand concentrates on t2's rule
            server.submit(sess, Query("t2", preds=(Pred("b", "==", 5),)))
        server.drain()
        scopes = cleaner.cold_scopes()
        assert [s.table for s in scopes][0] == "t2" or (
            # expected_pairs can outweigh touches; assert the touch signal
            # itself is right instead of the blend
            cleaner.rule_touches()[("t2", "ab")] == 5
        )
        touches = cleaner.rule_touches()
        assert touches == {("t2", "ab"): 5}

    def test_prioritize_scopes_deterministic_and_cold_only(self):
        a = ScopePriority("t", "r1", cold_rows=10, expected_pairs=100.0,
                          touch_probability=0.5)
        b = ScopePriority("t", "r2", cold_rows=10, expected_pairs=100.0,
                          touch_probability=0.5)
        warm = ScopePriority("t", "r0", cold_rows=0, expected_pairs=1e9,
                             touch_probability=1.0)
        hot = ScopePriority("u", "r3", cold_rows=5, expected_pairs=100.0,
                            touch_probability=0.9)
        out = prioritize_scopes([b, warm, hot, a])
        assert [s.rule for s in out] == ["r3", "r1", "r2"]

    def test_sharded_pricing_feeds_df_effective(self):
        class Info:
            n_shards = 4
            per_shard_rows = [2, 2, 2, 2]
            routed_rows = 8
            retries = 1
            sharded_pairs = 16

        cost = sharded_detect_cost(Info(), n_rows=100)
        # uniform at n=100 over 4 shards: 4*25^2 = 2500, no skew, 2 shuffles
        assert cost == 2500 + 2 * 100
        cm = CostModel(n=100, epsilon=10, p=2.0, df=10_000.0)
        assert cm.df_effective == 10_000.0
        cm.observe_detect_cost(cost)
        assert cm.df_effective == cost
        cm.observe_detect_cost(cost * 2)  # never regresses to a worse observation
        assert cm.df_effective == cost


# ------------------------------------------------------------------- metrics
def test_snapshot_background_attribution_serializable():
    daisy = fresh_daisy()
    server = QueryServer(daisy)
    cleaner = BackgroundCleaner(daisy, server=server, increment_rows=PER)
    sess = server.open_session("s")
    server.submit(sess, view(0))
    assert cleaner.drain() == 0  # yield counted
    server.drain()
    cleaner.drain()
    snap = server.snapshot()
    json.dumps(snap)
    assert snap["background"]["yields"] == 1
    assert snap["background"]["increments"] >= 1
    assert snap["background"]["scopes_completed"] == 1
    assert snap["background"]["detect_calls"] > 0
    assert snap["foreground"]["detect_calls"] == snap["detect_calls"]
    assert (
        snap["detect_calls"] + snap["background"]["detect_calls"]
        == daisy.detect_calls
    )
