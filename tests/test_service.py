"""repro.service — the concurrent query-serving subsystem (DESIGN.md §9).

Covers the cache soundness contract (equal clean_version => bit-identical
answers), stable query fingerprints, scheduler batching (one detect/repair
pass per cluster; answers bit-identical to a serial fresh-instance run),
session limits/lineage, serializable step reports, and the quickstart
example.
"""

import json
import os
import runpy
from pathlib import Path

import numpy as np
import pytest

from repro.core.constraints import FD
from repro.core.executor import Daisy, DaisyConfig
from repro.core.operators import (
    GroupBySpec,
    JoinClause,
    Pred,
    Query,
    query_fingerprint,
)
from repro.core.relation import make_relation
from repro.service import (
    QueryServer,
    ResultCache,
    Session,
    SessionLimitError,
    batch_tickets,
    cluster_key,
)
from tests.conftest import LA, NY, SF

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def fresh_daisy(rel_factory, rules):
    return Daisy(rel_factory(), rules, DaisyConfig(use_cost_model=False))


def cities_factory():
    return {
        "cities": make_relation(
            {
                "zip": np.array([9001, 9001, 9001, 10001, 10001]),
                "city": np.array([LA, SF, LA, SF, NY]),
            },
            overlay=["zip", "city"],
            k=4,
            rules=["zip_city"],
        )
    }


CITY_RULES = {"cities": [FD("zip_city", "zip", "city")]}


def two_cluster_factory():
    """Two disjoint dirty zip groups (no shared city values, so relaxation
    closures never bridge them)."""
    return {
        "t": make_relation(
            {
                "zip": np.array([1, 1, 2, 2]),
                "city": np.array([10, 11, 20, 21]),
            },
            overlay=["zip", "city"],
            k=4,
            rules=["zc"],
        )
    }


TWO_CLUSTER_RULES = {"t": [FD("zc", "zip", "city")]}


# ---------------------------------------------------------------- fingerprint
class TestFingerprint:
    def test_stable_and_order_normalized(self):
        a = Query("t", preds=(Pred("x", "==", 1), Pred("y", ">", 2.5)))
        b = Query("t", preds=(Pred("y", ">", 2.5), Pred("x", "==", 1)))
        assert query_fingerprint(a) == query_fingerprint(b)
        assert len(query_fingerprint(a)) == 16
        int(query_fingerprint(a), 16)  # hex digest

    def test_discriminates(self):
        base = Query("t", preds=(Pred("x", "==", 1),))
        assert query_fingerprint(base) != query_fingerprint(
            Query("t", preds=(Pred("x", "==", 2),))
        )
        assert query_fingerprint(base) != query_fingerprint(
            Query("t", preds=(Pred("x", ">=", 1),))
        )
        assert query_fingerprint(base) != query_fingerprint(
            Query("u", preds=(Pred("x", "==", 1),))
        )
        assert query_fingerprint(base) != query_fingerprint(
            Query("t", preds=(Pred("x", "==", 1),), groupby=GroupBySpec(keys=("x",)))
        )
        assert query_fingerprint(base) != query_fingerprint(
            Query("t", preds=(Pred("x", "==", 1),),
                  joins=(JoinClause("u", "x", "x"),))
        )
        # projection feeds the planner's rule-overlap decision, so it is
        # cache-key-relevant (its order is not)
        assert query_fingerprint(base) != query_fingerprint(
            Query("t", preds=(Pred("x", "==", 1),), project=("y",))
        )
        assert query_fingerprint(
            Query("t", project=("y", "z"))
        ) == query_fingerprint(Query("t", project=("z", "y")))

    def test_int_float_distinct(self):
        # 1 and 1.0 select the same rows but must not be forced to collide
        # with 1.0000001; exact-bit float canonicalization keeps both stable.
        qa = Query("t", preds=(Pred("x", "==", 1.0),))
        qb = Query("t", preds=(Pred("x", "==", 1.0000001),))
        assert query_fingerprint(qa) != query_fingerprint(qb)
        assert query_fingerprint(qa) == query_fingerprint(
            Query("t", preds=(Pred("x", "==", 1.0),))
        )


# --------------------------------------------------------------- clean version
class TestCleanVersion:
    def test_bumps_on_mutation_and_stabilizes(self):
        daisy = fresh_daisy(cities_factory, CITY_RULES)
        assert daisy.clean_version == 0
        q = Query("cities", preds=(Pred("city", "==", LA),))
        daisy.execute(q)
        v1 = daisy.clean_version
        assert v1 > 0  # apply_candidates + mark_checked both bumped
        daisy.execute(q)
        assert daisy.clean_version == v1  # checked scope => skip, no commit

    def test_equal_versions_bit_identical_answers(self):
        """The cache soundness contract: same fingerprint at the same
        clean_version answers bit-identically."""
        daisy = fresh_daisy(cities_factory, CITY_RULES)
        q = Query("cities", preds=(Pred("zip", "==", 9001),))
        first = daisy.execute(q)
        v = daisy.clean_version
        for _ in range(3):
            again = daisy.execute(q)
            assert daisy.clean_version == v
            np.testing.assert_array_equal(
                np.asarray(first.mask), np.asarray(again.mask)
            )

    def test_dc_repeat_skips_without_bump(self, salary_rel, dc_sal_tax):
        daisy = Daisy(
            {"t": salary_rel},
            {"t": [dc_sal_tax]},
            DaisyConfig(use_cost_model=False, dc_partitions=4),
        )
        q = Query("t", preds=(Pred("salary", ">=", 0.0),))
        r1 = daisy.execute(q)
        assert r1.report.steps[0].mode in ("incremental", "full")
        v = daisy.clean_version
        d = daisy.detect_calls
        r2 = daisy.execute(q)
        assert r2.report.steps[0].mode == "skipped"
        assert daisy.clean_version == v
        assert daisy.detect_calls == d
        np.testing.assert_array_equal(np.asarray(r1.mask), np.asarray(r2.mask))


# -------------------------------------------------------------------- reports
class TestSerializableReports:
    def test_exec_report_json_round_trip(self):
        daisy = fresh_daisy(cities_factory, CITY_RULES)
        res = daisy.execute(Query("cities", preds=(Pred("city", "==", LA),)))
        blob = json.dumps(res.report.asdict())
        back = json.loads(blob)
        assert back["steps"][0]["rule"] == "zip_city"
        assert back["result_size"] == res.report.result_size


# ---------------------------------------------------------------------- cache
class TestResultCache:
    def test_hit_requires_matching_version(self):
        cache = ResultCache(capacity=4)
        cache.put("fp", 3, "answer")
        assert cache.get("fp", 3) == "answer"
        assert cache.get("fp", 4) is None  # instance advanced -> stale
        assert cache.stale == 1
        assert cache.get("fp", 3) is None  # stale entries are dropped

    def test_lru_eviction(self):
        cache = ResultCache(capacity=2)
        cache.put("a", 0, 1)
        cache.put("b", 0, 2)
        assert cache.get("a", 0) == 1  # refresh a
        cache.put("c", 0, 3)  # evicts b
        assert cache.get("b", 0) is None
        assert cache.get("a", 0) == 1
        assert cache.get("c", 0) == 3
        assert cache.evictions == 1


# ------------------------------------------------------------------- sessions
class TestSession:
    def test_limits(self):
        s = Session("u0", max_inflight=1, max_queries=2)
        s.admit()
        with pytest.raises(SessionLimitError):
            s.admit()  # inflight bound
        s.fail()
        s.admit()
        s.fail()
        with pytest.raises(SessionLimitError):
            s.admit()  # lifetime quota

    def test_lineage_records_cache_provenance(self):
        daisy = fresh_daisy(cities_factory, CITY_RULES)
        srv = QueryServer(daisy)
        sess = srv.open_session("analyst")
        q = Query("cities", preds=(Pred("city", "==", LA),))
        srv.submit(sess, q)
        srv.submit(sess, q)
        srv.drain()
        assert [e.cached for e in sess.lineage] == [False, True]
        assert sess.lineage[0].clean_version == sess.lineage[1].clean_version
        snap = sess.snapshot()
        assert snap["answered"] == 2 and snap["cached_answers"] == 1


# ----------------------------------------------------------- scheduler batches
class TestSchedulerBatching:
    def test_cluster_key_groups_overlapping_sigma(self):
        rules = TWO_CLUSTER_RULES
        qa = Query("t", preds=(Pred("zip", "==", 1),))
        qb = Query("t", preds=(Pred("zip", "==", 1), Pred("city", ">=", 0)))
        qc = Query("t", preds=(Pred("zip", "==", 2),))
        assert cluster_key(qa, rules) == cluster_key(qb, rules)
        assert cluster_key(qa, rules) != cluster_key(qc, rules)

    def test_one_detect_pass_per_cluster(self):
        """N sessions issuing overlapping-σ queries: one detect/repair pass
        per cluster, answers bit-identical to a serial fresh Daisy."""
        daisy = fresh_daisy(two_cluster_factory, TWO_CLUSTER_RULES)
        srv = QueryServer(daisy, max_batch=16)
        sessions = [srv.open_session() for _ in range(6)]
        # cluster 1 twice per session (same σ), cluster 2 once per session
        queries = [
            Query("t", preds=(Pred("zip", "==", 1),)),
            Query("t", preds=(Pred("zip", "==", 1), Pred("city", ">=", 0))),
            Query("t", preds=(Pred("zip", "==", 2),)),
        ]
        tickets = []
        for sess in sessions:
            for q in queries:
                tickets.append(srv.submit(sess, q))
        assert srv.drain() == len(tickets)

        assert daisy.detect_calls == 2  # exactly one pass per cluster
        assert daisy.repair_calls == 2
        # batching grouped the two same-cluster fingerprints ahead of cluster 2
        groups = batch_tickets(tickets, daisy.rules)
        assert [len(g) for g in groups] == [12, 6]

        # bit-identical to running the same queries serially through a fresh
        # Daisy (the offline-equivalence harness's comparison, per ticket)
        serial = fresh_daisy(two_cluster_factory, TWO_CLUSTER_RULES)
        for ticket in tickets:
            ref = serial.execute(ticket.query)
            np.testing.assert_array_equal(
                np.asarray(ticket.result.mask),
                np.asarray(ref.mask),
                err_msg=str(ticket.query),
            )

    def test_stale_hits_reexecute_like_serial(self):
        """A cached answer is invalidated exactly when the instance advances;
        the re-execution matches the serial fresh-instance answer."""
        daisy = fresh_daisy(two_cluster_factory, TWO_CLUSTER_RULES)
        srv = QueryServer(daisy)
        sess = srv.open_session()
        qa = Query("t", preds=(Pred("zip", "==", 1),))
        qb = Query("t", preds=(Pred("zip", "==", 2),))
        t1 = srv.submit(sess, qa)
        srv.drain()
        v1 = t1.clean_version
        t2 = srv.submit(sess, qb)  # cleans cluster 2 -> version moves
        srv.drain()
        assert t2.clean_version > v1
        t3 = srv.submit(sess, qa)  # stale entry -> re-execute
        srv.drain()
        assert not t3.cached and srv.cache.stale == 1
        t4 = srv.submit(sess, qa)  # version now stable -> hit
        srv.drain()
        assert t4.cached
        serial = fresh_daisy(two_cluster_factory, TWO_CLUSTER_RULES)
        for q in (qa, qb, qa, qa):
            ref = serial.execute(q)
        np.testing.assert_array_equal(np.asarray(t4.result.mask), np.asarray(ref.mask))


# ------------------------------------------------------------------- metrics
class TestMetrics:
    def test_snapshot_serializable_and_consistent(self):
        daisy = fresh_daisy(cities_factory, CITY_RULES)
        srv = QueryServer(daisy)
        sess = srv.open_session()
        q = Query("cities", preds=(Pred("city", "==", LA),))
        for _ in range(4):
            srv.submit(sess, q)
        srv.drain()
        snap = srv.snapshot()
        json.dumps(snap)  # everything host-serializable
        assert snap["queries"] == 4
        assert snap["executions"] == 1
        assert snap["cache_hits"] == 3
        assert snap["cache"]["hits"] == 3
        assert snap["clean_version"] == daisy.clean_version
        assert snap["recent_reports"][0]["steps"][0]["rule"] == "zip_city"


# -------------------------------------------------------------------- example
def test_example_serve_queries_runs(capsys):
    runpy.run_path(
        os.path.join(ROOT, "examples", "serve_queries.py"), run_name="__main__"
    )
    out = capsys.readouterr().out
    assert "cache" in out


# ------------------------------------------------------------ the serve CLI
class TestServeCLI:
    ARGV = ["serve", "--workload", "queries", "--rows", "64",
            "--requests", "4", "--sessions", "2"]

    def test_errored_tickets_fail_the_run(self, monkeypatch):
        """A ticket error is surfaced to its caller and the server keeps
        serving — the run as a whole must still exit non-zero."""
        from repro.launch import compile_cache, serve

        def boom(self, query):
            raise RuntimeError("injected execute failure")

        monkeypatch.setattr(compile_cache, "configure_compile_cache", lambda: "")
        monkeypatch.setattr(Daisy, "execute", boom)
        monkeypatch.setattr("sys.argv", self.ARGV)
        with pytest.raises(SystemExit) as exc:
            serve.main()
        assert "4 tickets raised" in str(exc.value.code)
        assert "answered 0 of 4" in str(exc.value.code)

    def test_clean_run_exits_zero(self, monkeypatch, capsys):
        from repro.launch import compile_cache, serve

        monkeypatch.setattr(compile_cache, "configure_compile_cache", lambda: "")
        monkeypatch.setattr("sys.argv", self.ARGV)
        serve.main()
        assert "served 4 queries" in capsys.readouterr().out

    def test_served_failures(self):
        from repro.launch.serve import served_failures

        assert served_failures({"errors": 0, "answered": 5}, 5) == []
        assert served_failures({"errors": 0, "answered": 4}, 5) == [
            "answered 4 of 5 queries"
        ]


def test_compile_cache_placement(monkeypatch):
    """The environment variable wins and nothing is set in code; without
    it the cache sits at one fixed path inside the checkout."""
    import jax

    from repro.launch import compile_cache as cc

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(cc.ENV_VAR, "/placed/by/the/caller")
    assert cc.configure_compile_cache() == "/placed/by/the/caller"
    assert jax.config.jax_compilation_cache_dir == before
    monkeypatch.delenv(cc.ENV_VAR)
    try:
        assert cc.configure_compile_cache() == str(cc.CHECKOUT_CACHE)
        assert jax.config.jax_compilation_cache_dir == str(cc.CHECKOUT_CACHE)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert cc.CHECKOUT_CACHE == Path(ROOT) / ".jax_cache"
