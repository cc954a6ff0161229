"""Daisy executor: query processing woven with cleaning operators (§4-§6).

``Daisy.execute(query)`` runs the cleaning-aware plan:

1. the planner injects a cleaning step per overlapping rule (planner.py);
2. ``clean_sigma`` steps relax the (dirty) answer, detect violations over the
   correlated cluster, merge probabilistic repairs, and flag the cluster
   checked;
3. the final answer is recomputed over the now-probabilistic relation with
   possible-world semantics (a tuple qualifies iff >= 1 candidate does);
4. joins run as base-join + incremental join of the relaxation extras
   (Fig. 5), are deduped, keep lineage, and are re-checked (Def. 3 (d) —
   Lemma 5 says the re-check finds nothing; we count to prove it);
5. per-rule online cost models (Inequality (1)) accumulate the observed
   work and flip the strategy to full cleaning mid-workload (Figs. 9/14);
   DC rules consult Algorithm 2's accuracy estimate instead.

The executor owns the database state: every query returns a result AND
advances the gradually-cleaned probabilistic instance (§6).

Cleaning progress — scope versions, per-strip coverage, cold-row counts,
the Algorithm-2 support fraction — lives in ONE structure, the
``core.ledger.WorkLedger`` (DESIGN.md §11): every commit path funnels
through ``_apply``/``_mark``, which bump the ledger and refresh its
per-strip cold counts, and every consumer (the planner's strip-pruned
full cleans, the background cleaner's bounded DC increments, the service
cache's version vectors, the metrics progress export) reads the same
ledger instead of keeping its own notion of what is done.
"""

from __future__ import annotations

import dataclasses
import threading
from typing import Dict, List, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import stats as statsmod
from repro.core.constraints import DC, FD
from repro.core.cost import CostModel, sharded_detect_cost
from repro.core.detect import detect_auto, detect_fd
from repro.core.ledger import TABLE_ROWS_RULE, WorkLedger
from repro.obs.trace import NULL_TRACER, host_reads, to_host
from repro.core.operators import (
    GroupBySpec,
    JoinState,
    Query,
    dedupe_pairs,
    expected_value,
    filter_mask,
    key_candidates,
    prob_equijoin,
    _finalize_groupby,
)
from repro.core.planner import (
    CleanStep,
    PlanInfo,
    plan_query,
    probe_step,
    strip_step,
)
from repro.core.relax import relax_fd
from repro.core.relation import Relation, append_rows
from repro.core.repair import Candidates, dc_repair_candidates, fd_repair_candidates
from repro.core.setops import group_distinct_candidates
from repro.core.update import apply_candidates, mark_checked, unchecked


class _FilterCounts:
    """Process-wide count of the answers the executor filtered anew and of
    those it reused (``Daisy.filter_evals`` / ``filter_reuses``)."""

    __slots__ = ("evals", "reuses", "_lock")

    def __init__(self):
        self.evals = self.reuses = 0
        self._lock = threading.Lock()

    def add(self, reused: bool) -> None:
        with self._lock:
            if reused:
                self.reuses += 1
            else:
                self.evals += 1


_FILTERS = _FilterCounts()


@dataclasses.dataclass
class _Answer:
    """A step's answer mask, and its size once a gate or the final filter
    has read it."""

    mask: jnp.ndarray
    size: Optional[int] = None


class _AnswerMemo:
    """The answer one ``execute`` computed last, keyed by the relation object
    and the predicates it filtered: a step that leaves the relation as it
    was (a skip) hands its answer to the next step and the final filter."""

    __slots__ = ("rel", "key", "answer", "evals", "reuses")

    def __init__(self):
        self.rel = self.key = self.answer = None
        self.evals = self.reuses = 0


@jax.jit
def _step_gate(answer, valid, checked, dirty=None, pivot=None):
    """A clean step's skip gate as one program: the answer's size; ``hit``,
    whether the answer holds a row the step would clean (unchecked for the
    rule and, for an FD, in a dirty group: the Fig. 11 gate); and, given
    Algorithm 2's pivot column, its extremes over the answer."""
    live = valid if checked is None else valid & ~checked
    hit = answer & live
    if dirty is not None:
        hit = hit & dirty
    out = {"size": jnp.sum(answer), "hit": jnp.any(hit)}
    if pivot is not None:
        if jnp.issubdtype(pivot.dtype, jnp.floating):
            lo, hi = -jnp.inf, jnp.inf
        else:
            lo, hi = jnp.iinfo(pivot.dtype).min, jnp.iinfo(pivot.dtype).max
        out["lo"] = jnp.min(pivot, where=answer, initial=hi)
        out["hi"] = jnp.max(pivot, where=answer, initial=lo)
    return out


def _blocks_attr(blocks) -> Optional[List[int]]:
    """JSON-safe span annotation for a kernel block range: ``[lo, hi)`` as
    plain ints (ledger block bounds can be numpy scalars), None passthrough."""
    if blocks is None:
        return None
    lo, hi = blocks
    return [int(lo), int(hi)]


@dataclasses.dataclass
class DaisyConfig:
    k: int = 8
    join_capacity: int = 8192
    join_row_block: int = 2048
    dc_partitions: int = 16
    dc_block: int = 256
    accuracy_threshold: float = 0.5
    expected_queries: int = 50
    use_cost_model: bool = True
    collect_stats: bool = True
    max_relax_iters: Optional[int] = None
    lemma1_fast_path: bool = False
    # sharded detection (DESIGN.md §8): with a mesh set, equality-keyed
    # rules detect over shuffle_by_key (detect_shards logical shards;
    # None -> the mesh's data-parallel extent).  Results are bit-identical
    # to the dense scans, so this is purely an execution-strategy knob.
    mesh: Optional[object] = None
    detect_shards: Optional[int] = None
    # work-ledger strip size (DESIGN.md §11): rows per partition strip, the
    # grain background DC increments and partial-work reuse operate at.
    # None -> one detect tile (dc_block); always rounded up to a whole
    # number of tiles so strips align with the dc_pairs grid.
    strip_rows: Optional[int] = None
    # compressed atom encodings (DESIGN.md §15): let the DC detect planner
    # scan int8/bf16/rank-code columns where the exactness proof holds.
    # Results are bit-identical either way — this is a bandwidth knob.
    kernel_encodings: bool = True


@dataclasses.dataclass
class StepReport:
    rule: str
    table: str
    mode: str  # incremental | full | strip | skipped
    detect_path: str = "dense"  # dense | sharded
    answer_size: int = 0
    extra: int = 0
    repaired: int = 0
    # comparison-space size this step's detects scanned: rows x partners for
    # DCs, scope rows for the FD group-by — the partial-work-reuse gauge
    # (benchmarks/serve_bg_warmup.py gates that a half-cleaned scope costs
    # strictly fewer pairs than a cold one, DESIGN.md §11)
    detect_pairs: int = 0
    # kernel launch geometry (DESIGN.md §15): DC tile pairs this step's
    # scans launched vs skipped by the ledger-masked worklist — the
    # block-sparsity gauge next to the row-level detect_pairs one
    tiles_launched: int = 0
    tiles_skipped: int = 0
    relax_iterations: int = 0
    relax_converged: bool = True
    alg2_accuracy: float = 1.0
    alg2_support: float = 0.0

    def asdict(self) -> Dict[str, object]:
        """Plain-scalar dict (all fields are host ints/floats/strs/bools), so
        service metrics can ship reports through json without touching jax."""
        return dataclasses.asdict(self)


@dataclasses.dataclass
class ExecReport:
    steps: List[StepReport] = dataclasses.field(default_factory=list)
    notes: List[str] = dataclasses.field(default_factory=list)
    result_size: int = 0
    recheck_violations: int = 0
    join_overflow: bool = False

    def asdict(self) -> Dict[str, object]:
        return dataclasses.asdict(self)


@dataclasses.dataclass
class DaisyResult:
    mask: Optional[jnp.ndarray] = None  # SP result (mask over base table)
    join: Optional[JoinState] = None  # join lineage
    groups: Optional[Dict[str, jnp.ndarray]] = None  # group-by output
    report: ExecReport = dataclasses.field(default_factory=ExecReport)


@dataclasses.dataclass
class IngestReport:
    """What one ``Daisy.ingest`` call did (DESIGN.md §12): where the rows
    landed, whether the relation grew, which strips went fresh, and which
    rule scopes queued an ingest-delta for their next cleaning step."""

    table: str
    rows: int  # appended row count
    start: int  # row index of the first appended row
    capacity_before: int
    capacity: int
    grown: bool
    fresh_strips: int  # strips (per rule scope, max over rules) marked fresh
    pending_rules: List[str] = dataclasses.field(default_factory=list)
    versions: Dict[str, int] = dataclasses.field(default_factory=dict)

    def asdict(self) -> Dict[str, object]:
        """Plain-scalar dict for service metrics / json."""
        return dataclasses.asdict(self)


class Daisy:
    """Query-driven cleaning engine (the system of §6, JAX-native)."""

    def __init__(
        self,
        db: Dict[str, Relation],
        rules: Dict[str, Sequence[FD | DC]],
        config: DaisyConfig | None = None,
        tracer=None,
    ):
        self.db = dict(db)
        self.rules = {t: list(rs) for t, rs in rules.items()}
        self.config = config or DaisyConfig()
        # observability seam (DESIGN.md §13): spans around execute and its
        # phases (plan / step / filter / join / groupby), every clean phase
        # (relax / detect / repair / mark), and ingest.  Defaults to the
        # strict no-op tracer, so untraced runs pay only the call site.
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.stats: Dict[Tuple[str, str], object] = {}
        # each FD's dirty-row mask, resident on the device beside its stats
        self._dirty_rows: Dict[Tuple[str, str], jnp.ndarray] = {}
        self.cost: Dict[Tuple[str, str], CostModel] = {}
        # the answer the running ``execute`` computed last (None between
        # queries, so no device buffer outlives one)
        self._memo: Optional[_AnswerMemo] = None
        # serving hooks (DESIGN.md §9/§10): a monotone version counter bumped
        # on every candidate-merge / checked-bit commit (the service cache's
        # invalidation signal), cumulative detect/repair invocation and
        # pair counters (the work the cache amortizes), the last observed
        # sharded routing per rule (feeds the cost model and the background
        # priority model), and a re-entrancy lock so concurrent sessions can
        # share one executor without torn read-modify-writes of ``self.db``.
        # Per-scope versions and strip coverage live in the work ledger
        # (DESIGN.md §11) — the executor bumps it on every commit.
        self._clean_version = 0
        self.sharded_info: Dict[Tuple[str, str], object] = {}
        self.detect_calls = 0
        self.repair_calls = 0
        self.detect_pairs = 0
        self.tiles_launched = 0
        self.tiles_skipped = 0
        self._lock = threading.RLock()
        self.ledger = WorkLedger(self.config.strip_rows, self.config.dc_block)
        if self.config.collect_stats:
            self._collect_stats()
        for table, rs in self.rules.items():
            for rule in rs:
                self.ledger.register(
                    table, rule.name, self.db[table].capacity,
                    to_host(self.cold_rows(table, rule.name)),
                )

    @property
    def clean_version(self) -> int:
        """Monotone clean-state version: equal versions guarantee bit-identical
        query answers (the cleaning steps of a re-executed query skip, so the
        answer is a pure function of the instance — the cache soundness
        contract, asserted in tests/test_service.py)."""
        return self._clean_version

    @property
    def host_syncs(self) -> int:
        """Device-to-host reads made through ``repro.obs.to_host`` so far:
        the count the ``syncs`` span attrs charge (DESIGN.md §13).  The
        counter is process-wide — every executor, server and cleaner
        thread in the process adds to it — so read it as a delta around
        the work to attribute."""
        return host_reads()

    @property
    def filter_evals(self) -> int:
        """Answers the executor filtered anew, process-wide like
        ``host_syncs`` (a miss's steps and final filter share one)."""
        return _FILTERS.evals

    @property
    def filter_reuses(self) -> int:
        """Answers the executor reused from the step before, process-wide."""
        return _FILTERS.reuses

    @property
    def lock(self) -> threading.RLock:
        """The executor's re-entrancy lock.  Callers that must read versioned
        state and act on it atomically with respect to a concurrent cleaner —
        the service layer's cache-lookup-or-execute, the background cleaner's
        increments — take this lock; ``execute`` re-acquires it re-entrantly."""
        return self._lock

    def scope_version(self, table: str, rule_name: str) -> int:
        """Monotone per-(table, rule) version: bumped exactly when a commit
        for THAT rule advances the instance.  Equal scope versions over a
        query's overlapping rules imply a bit-identical answer (DESIGN.md
        §10/§11) — the refinement the service cache keys on so background
        cleaning of one rule never invalidates another rule's entries.
        Backed by the work ledger."""
        return self.ledger.version(table, rule_name)

    def scope_versions(self, deps: Sequence[Tuple[str, str]]) -> Tuple[int, ...]:
        """Version vector over a dependency list of (table, rule) pairs (the
        service cache's key half; read under ``lock`` when a background
        cleaner may be committing concurrently)."""
        return self.ledger.versions(deps)

    def _apply(self, rel: Relation, deltas, table: str, rule_name: str) -> Relation:
        """``apply_candidates`` + version bumps (every overlay merge advances
        the probabilistic instance globally and for the committing rule)."""
        self._clean_version += 1
        self.ledger.bump(table, rule_name)
        return apply_candidates(rel, deltas)

    def _mark(self, rel: Relation, table: str, rule_name: str, scope) -> Relation:
        """``mark_checked`` + version bump + ledger coverage refresh: checked
        bits steer future cleaning, so they are part of the versioned state,
        and they are exactly what moves strip coverage (DESIGN.md §11)."""
        with self.tracer.span("clean.mark", rule=rule_name, table=table):
            self._clean_version += 1
            rel = mark_checked(rel, rule_name, scope)
            self.ledger.commit(
                table, rule_name, to_host(self._cold_mask(rel, table, rule_name))
            )
            cm = self.cost.get((table, rule_name))
            if cm is not None:
                cm.observe_progress(self.ledger.scope(table, rule_name).cold_fraction)
        return rel

    # ------------------------------------------------------------ statistics
    def _collect_stats(self) -> None:
        """Precompute per-(table, rule) statistics (§5.2.3, §7/Fig 11)."""
        for table, rules in self.rules.items():
            rel = self.db[table]
            n = int(to_host(rel.num_rows()))
            for rule in rules:
                key = (table, rule.name)
                if isinstance(rule, FD):
                    st = self._set_fd_stats(key, rel, rule)
                    df = float(n)  # hash/sort group-by detection cost
                    self.cost[key] = CostModel(
                        n=n,
                        epsilon=st.epsilon,
                        p=st.p_est,
                        df=df,
                        expected_queries=self.config.expected_queries,
                    )
                else:
                    st = statsmod.dc_stats(rel, rule, p=self.config.dc_partitions)
                    df = n * n / max(self.config.dc_partitions, 1)
                    self.stats[key] = st
                    self.cost[key] = CostModel(
                        n=n,
                        epsilon=int(st.range_vio.sum()),
                        p=2.0,
                        df=df,
                        expected_queries=self.config.expected_queries,
                    )

    def _set_fd_stats(self, key, rel: Relation, fd: FD) -> statsmod.FDStats:
        st = statsmod.fd_stats(rel, fd)
        self.stats[key] = st
        self._dirty_rows[key] = jnp.asarray(st.dirty_row)
        return st

    def _refresh_stats(self, table: str) -> None:
        """Recompute one table's per-rule statistics after an append and
        fold the new instance size into the existing cost models in place
        (histories and the switched flag survive: an append changes the
        economics of FUTURE work, not what already happened)."""
        rel = self.db[table]
        n = int(to_host(rel.num_rows()))
        for rule in self.rules.get(table, ()):
            key = (table, rule.name)
            cm = self.cost.get(key)
            if isinstance(rule, FD):
                st = self._set_fd_stats(key, rel, rule)
                if cm is not None:
                    cm.n, cm.df = n, float(n)
                    cm.epsilon, cm.p = st.epsilon, st.p_est
            else:
                st = statsmod.dc_stats(rel, rule, p=self.config.dc_partitions)
                self.stats[key] = st
                if cm is not None:
                    cm.n = n
                    cm.df = n * n / max(self.config.dc_partitions, 1)
                    cm.epsilon = int(st.range_vio.sum())

    # ---------------------------------------------------------------- ingest
    def ingest(self, table: str, rows: Mapping[str, np.ndarray]) -> IngestReport:
        """Append rows into a live table — THE streaming-ingest entry point
        (DESIGN.md §12).

        Under ``lock``, in order: the rows land in the relation's spare
        capacity (growing via ``next_pow2`` when full; every pre-existing
        overlay/checked/cand array is preserved bit-for-bit); the table's
        statistics and cost models refresh; and each rule scope's work
        ledger extends — the fresh rows' strips read as COLD and FRESH,
        with no existing checked state invalidated.  Scopes that already
        hold checked rows queue a ``PendingIngest`` delta: the next
        cleaning step touching the scope (foreground or background) gives
        those rows the fresh partners' evidence in O(new x all) work
        instead of a stop-the-world re-clean (``_process_pending``).

        Cache invalidation is exact: only the table's ``TABLE_ROWS_RULE``
        pseudo-scope version bumps here (rule scope versions move when
        their deltas merge), so every cached answer reading this table
        goes stale exactly once and entries over other tables survive.
        """
        with self._lock, self.tracer.span("daisy.ingest", table=table) as sp:
            report = self._ingest_locked(table, rows)
            sp.set(rows=report.rows, grown=report.grown)
            return report

    def _ingest_locked(
        self, table: str, rows: Mapping[str, np.ndarray]
    ) -> IngestReport:
        with self._lock:  # re-entrant; ``ingest`` already holds it
            if table not in self.db:
                raise KeyError(f"unknown table {table!r}")
            rel = self.db[table]
            cap_before = rel.capacity
            # snapshot per-rule ingest-delta inputs BEFORE the append: which
            # rows are checked, and (FDs) which rows were statically dirty —
            # the had-evidence/checked-while-clean classifier (DESIGN.md §12)
            had_checked: Dict[str, np.ndarray] = {}
            old_dirty: Dict[str, np.ndarray] = {}
            for rule in self.rules.get(table, ()):
                ch = rel.checked.get(rule.name)
                if ch is None:
                    continue
                ch_np = to_host(ch)
                if ch_np.any():
                    had_checked[rule.name] = ch_np
                    if isinstance(rule, FD):
                        st = self.stats.get((table, rule.name))
                        dirty = (
                            st.dirty_row if st is not None
                            else statsmod.fd_stats(rel, rule).dirty_row
                        )
                        old_dirty[rule.name] = np.asarray(dirty, dtype=bool)
            new_rel, start = append_rows(rel, rows)
            n_new = int(to_host(new_rel.valid).sum()) - start
            report = IngestReport(
                table=table, rows=n_new, start=start,
                capacity_before=cap_before, capacity=new_rel.capacity,
                grown=new_rel.capacity != cap_before, fresh_strips=0,
            )
            if n_new == 0:
                return report
            self.db[table] = new_rel
            hi = start + n_new
            if self.config.collect_stats:
                self._refresh_stats(table)
            cap = new_rel.capacity
            for rule in self.rules.get(table, ()):
                checked = had_checked.get(rule.name)
                od = old_dirty.get(rule.name)
                if checked is not None and checked.shape[0] < cap:
                    checked = np.pad(checked, (0, cap - checked.shape[0]))
                if od is not None and od.shape[0] < cap:
                    od = np.pad(od, (0, cap - od.shape[0]))
                cold = to_host(self._cold_mask(new_rel, table, rule.name))
                scope = self.ledger.record_ingest(
                    table, rule.name, cap, cold, start, hi,
                    checked=checked, old_dirty=od,
                )
                report.fresh_strips = max(report.fresh_strips, len(scope.fresh))
                if scope.pending:
                    report.pending_rules.append(rule.name)
                cm = self.cost.get((table, rule.name))
                if cm is not None:
                    cm.observe_progress(scope.cold_fraction)
            self.ledger.bump(table, TABLE_ROWS_RULE)
            report.versions = {
                rule.name: self.ledger.version(table, rule.name)
                for rule in self.rules.get(table, ())
            }
            report.versions[TABLE_ROWS_RULE] = self.ledger.version(
                table, TABLE_ROWS_RULE
            )
            return report

    # -------------------------------------------------------------- planning
    def _want_full(self) -> Dict[Tuple[str, str], bool]:
        if not self.config.use_cost_model:
            return {}
        return {key: cm.should_switch_to_full() for key, cm in self.cost.items()}

    # ---------------------------------------------------------- detect path
    def _detect_mesh(self, step: CleanStep):
        """The mesh to detect on for this step: the configured mesh when the
        planner marked the rule shardable, else None (dense scan)."""
        return self.config.mesh if step.shardable else None

    # ------------------------------------------------- background increments
    def _rule_named(self, table: str, rule_name: str):
        for rule in self.rules.get(table, ()):
            if rule.name == rule_name:
                return rule
        raise KeyError(f"no rule {rule_name!r} on table {table!r}")

    def _cold_mask(self, rel: Relation, table: str, rule_name: str) -> jnp.ndarray:
        """Cold rows of ``rel`` for a rule: unchecked rows, intersected for
        FDs with the statically-known dirty groups (clean groups skip via
        the Fig. 11 dirty-group gate without ever being marked, so they are
        not background work either).  The single definition the ledger's
        per-strip counts are folded from (DESIGN.md §11)."""
        self._rule_named(table, rule_name)  # KeyError for an unknown rule
        cold = unchecked(rel, rule_name)
        dirty = self._dirty_rows.get((table, rule_name))
        if dirty is not None:
            cold = cold & dirty
        return cold

    def cold_rows(self, table: str, rule_name: str) -> jnp.ndarray:
        """Rows a first-touch foreground query would still pay detect work
        for (see ``_cold_mask``).  Read under ``lock`` if a cleaner may be
        committing concurrently."""
        return self._cold_mask(self.db[table], table, rule_name)

    def cold_count(self, table: str, rule_name: str) -> int:
        """Host count of ``cold_rows`` — a ledger read (no device sync):
        the per-strip counts are refreshed at every ``_mark`` commit.  A
        scope the ledger has never sized (a rule appended to a live Daisy)
        is registered from the real cold mask on first read."""
        scope = self.ledger.scope(table, rule_name)
        cap = self.db[table].capacity
        if scope is None or scope.capacity < cap:
            scope = self.ledger.register(
                table, rule_name, cap,
                to_host(self.cold_rows(table, rule_name)),
            )
        return scope.cold_count

    def _fd_increment_seed(
        self,
        rel: Relation,
        fd: FD,
        cold: jnp.ndarray,
        max_rows: Optional[int],
        prefer: Optional[jnp.ndarray] = None,
    ) -> jnp.ndarray:
        """Whole-lhs-group seed mask for one background FD increment: the
        first (ascending group id) cold groups whose valid rows total at
        least ``max_rows`` (always >= 1 group).  Groups are taken whole —
        candidates are per-group evidence, so a split group would merge
        different candidate sets than the foreground path (DESIGN.md §10).
        ``prefer`` front-loads groups intersecting that mask (the freshly
        ingested strips, DESIGN.md §12) ahead of the ascending sweep."""
        valid = to_host(rel.valid)
        cold_np = to_host(cold)
        gid = np.zeros(valid.shape[0], dtype=np.int64)
        for attr in fd.lhs:
            _, inv = np.unique(to_host(rel.columns[attr]), return_inverse=True)
            gid = gid * (int(inv.max()) + 1) + inv
        # densify the combined key so per-group sizes are one bincount pass
        _, gid = np.unique(gid, return_inverse=True)
        cold_groups = np.unique(gid[cold_np])
        if prefer is not None:
            pref = np.unique(gid[to_host(prefer) & cold_np])
            rest = cold_groups[~np.isin(cold_groups, pref)]
            cold_groups = np.concatenate([pref, rest])
        if max_rows is not None:
            sizes = np.bincount(gid[valid], minlength=int(gid.max()) + 1)
            cum = np.cumsum(sizes[cold_groups])
            # smallest prefix of cold groups reaching max_rows (>= 1 group)
            cut = int(np.searchsorted(cum, max_rows)) + 1
            cold_groups = cold_groups[:cut]
        return jnp.asarray(valid & np.isin(gid, cold_groups))

    def clean_scope_increment(
        self,
        table: str,
        rule_name: str,
        max_rows: Optional[int] = None,
        max_strips: Optional[int] = None,
    ) -> Optional[StepReport]:
        """One preemptible background-cleaning increment for a rule scope
        (DESIGN.md §10/§11); returns its ``StepReport`` or ``None`` when the
        scope is already warm.

        Runs under ``lock`` and commits through the same ``_apply``/``_mark``
        path as foreground steps, so every increment bumps the global and
        per-scope ledger versions exactly like a query would.  FDs clean up
        to ``max_rows`` cold rows per call, seeded on whole lhs groups and
        run through the foreground incremental pipeline (relax closure,
        detect, repair, mark) — by Lemma 4 the accumulated state is the one
        the same sweeps issued as queries would reach.  DCs clean up to
        ``max_strips`` ledger strips per call (strip x rest-of-dataset
        scans through the strip-scoped kernel entry; ``None`` sweeps every
        cold strip, i.e. the remaining full clean in one increment) — the
        strip union is row-for-row identical to one full pass (DESIGN.md
        §11), so a DC increment's preemption latency is now one strip scan,
        exactly like the FD ``max_rows`` bound.  Cost-model histories are
        not polluted (``record_cost=False``)."""
        with self._lock:
            rule = self._rule_named(table, rule_name)
            report = ExecReport()
            # ingest-deltas first (DESIGN.md §12): a scope can look warm
            # (zero cold rows) while its checked rows are stale against
            # fresh partners — a pending-only increment still reports.
            pending_rep = self._process_pending(table, rule, report)
            rel = self.db[table]
            cold = self.cold_rows(table, rule_name)
            if not bool(to_host(jnp.any(cold))):
                return pending_rep
            if isinstance(rule, FD):
                scope_l = self.ledger.scope(table, rule_name)
                prefer = None
                if scope_l is not None and scope_l.fresh:
                    prefer = jnp.asarray(
                        scope_l.strip_mask(sorted(scope_l.fresh))
                    )
                seed = self._fd_increment_seed(
                    rel, rule, cold, max_rows, prefer=prefer
                )
                self._clean_fd(
                    probe_step(table, rule), report,
                    answer_override=seed, record_cost=False,
                )
            else:
                # register-and-refresh from the cold mask just computed, so a
                # rule appended to a live Daisy (lazily-created scope) hands
                # the strip engine its real cold strips
                scope = self.ledger.register(
                    table, rule_name, rel.capacity, to_host(cold)
                )
                strips = scope.cold_strips(fresh_first=True)
                if max_strips is not None:
                    strips = strips[: max(int(max_strips), 1)]
                self._clean_dc(
                    strip_step(table, rule, strips), report, record_cost=False
                )
            return report.steps[-1] if report.steps else None

    # -------------------------------------------------------- ingest deltas
    def _process_pending(
        self, table: str, rule, report: Optional[ExecReport] = None
    ) -> Optional[StepReport]:
        """Drain a scope's queued ingest-deltas (DESIGN.md §12): for every
        append since the scope's last cleaning step, give the rows that were
        CHECKED at append time the evidence the fresh rows owe them — an
        O(checked x fresh) scan, never a re-clean.  Runs at the top of every
        cleaning path (foreground steps, background increments) BEFORE any
        skip gate, because a scope can look warm while its checked rows are
        stale against fresh partners.  No rows are marked here: the fresh
        rows stay cold and collect their own full evidence at their first
        clean, so checked bits are never invalidated by an append."""
        pendings = self.ledger.take_pending(table, rule.name)
        if not pendings:
            return None
        rep = StepReport(rule.name, table, "ingest-delta")
        with self.tracer.span(
            "clean.ingest_delta", rule=rule.name, table=table,
            deltas=len(pendings),
        ) as sp:
            if isinstance(rule, FD):
                self._ingest_delta_fd(table, rule, pendings, rep)
            else:
                self._ingest_delta_dc(table, rule, pendings, rep)
            sp.set(pairs=rep.detect_pairs)
        if report is not None:
            report.steps.append(rep)
        return rep

    def _ingest_delta_fd(
        self, table: str, fd: FD, pendings, rep: StepReport
    ) -> None:
        """FD ingest-delta: re-derive candidate evidence for checked rows
        whose lhs group gained fresh members, processing appends in time
        order against the instance each one saw (``rows < hi`` masking
        makes multi-append draining exact).

        Per append, over the relaxation closure of the fresh rows' groups:

        * checked rows that were DIRTY at append time already merged their
          group's old evidence — they get the FRESH-WEIGHTED counts only
          (each fresh member contributes weight 1, old members 0: by Lemma 4
          the sum equals one merge over the whole group);
        * checked rows that were CLEAN at append time (checked-while-clean:
          marked by a pass whose detection saw no violation, so no overlay)
          and are violated NOW get the FULL group counts — their first and
          only evidence merge, identical to what a from-scratch clean gives.

        Zero-weight candidate slots merge as bitwise no-ops, so rows whose
        group gained nothing are untouched."""
        k = self.config.k
        for ent in pendings:
            rel = self.db[table]
            cap = rel.capacity
            pos = np.arange(cap)
            checked = np.zeros(cap, dtype=bool)
            c = np.asarray(ent.checked, dtype=bool)
            checked[: min(c.shape[0], cap)] = c[:cap]
            dirty = np.zeros(cap, dtype=bool)
            if ent.old_dirty is not None:
                d = np.asarray(ent.old_dirty, dtype=bool)
                dirty[: min(d.shape[0], cap)] = d[:cap]
            fresh = jnp.asarray((pos >= ent.lo) & (pos < ent.hi))
            # the instance THIS append saw: rows below its high-water mark
            rel_hi = dataclasses.replace(
                rel, valid=rel.valid & jnp.asarray(pos < ent.hi)
            )
            seed = fresh & rel_hi.valid
            if not bool(to_host(jnp.any(seed))):
                continue
            self.detect_calls += 1
            res = relax_fd(
                rel_hi, seed, fd,
                max_iters=self.config.max_relax_iters, use_rhs=True,
            )
            scope = (seed | res.extra) & rel_hi.valid
            scope_n = int(to_host(jnp.sum(scope)))
            rep.answer_size += int(to_host(jnp.sum(seed)))
            rep.extra += int(to_host(jnp.sum(res.extra)))
            rep.detect_pairs += scope_n  # group-by is O(scope)
            self.detect_pairs += scope_n
            lhs_cols = [rel.columns[a] for a in fd.lhs]
            rhs_col = rel.columns[fd.rhs]
            wt = jnp.where(fresh, jnp.float32(1.0), jnp.float32(0.0))
            full_v, full_n, violated, _ = group_distinct_candidates(
                lhs_cols, rhs_col, scope, k
            )
            fresh_v, fresh_n, _, _ = group_distinct_candidates(
                lhs_cols, rhs_col, scope, k, weight=wt
            )
            lhs_single = len(fd.lhs) == 1
            if lhs_single:
                lfull_v, lfull_n, _, _ = group_distinct_candidates(
                    [rhs_col], lhs_cols[0], scope, k
                )
                lfresh_v, lfresh_n, _, _ = group_distinct_candidates(
                    [rhs_col], lhs_cols[0], scope, k, weight=wt
                )
            checked_j = jnp.asarray(checked)
            t_fresh = checked_j & violated & jnp.asarray(dirty) & scope
            t_full = checked_j & violated & ~jnp.asarray(dirty) & scope
            kinds = jnp.zeros(full_v.shape, jnp.int8)
            deltas = []
            for rows_mask, rv, rn, lv, ln in (
                (t_fresh, fresh_v, fresh_n,
                 *((lfresh_v, lfresh_n) if lhs_single else (None, None))),
                (t_full, full_v, full_n,
                 *((lfull_v, lfull_n) if lhs_single else (None, None))),
            ):
                if not bool(to_host(jnp.any(rows_mask))):
                    continue
                deltas.append((fd.rhs, Candidates(rv, rn, kinds, rows_mask)))
                if lv is not None:
                    deltas.append((fd.lhs[0], Candidates(lv, ln, kinds, rows_mask)))
            if deltas:
                self.repair_calls += 1
                rep.repaired += int(to_host(jnp.sum(t_fresh | t_full)))
                self.db[table] = self._apply(rel, deltas, table, fd.name)

    def _ingest_delta_dc(
        self, table: str, dc: DC, pendings, rep: StepReport
    ) -> None:
        """DC ingest-delta: one [checked x fresh] matrix strip per append —
        rows already marked checked absorb the appended partners' evidence
        through the col-scoped kernel entry, O(checked x new) pairs instead
        of the O(n^2) full grid.  The fresh rows themselves stay cold: their
        own [fresh x all] evidence arrives at their first (strip or full)
        clean, which — both scopes living below the append's high-water
        mark — never re-touches a checked strip (benchmark gate (c))."""
        block = self.config.dc_block
        cm = self.cost.get((table, dc.name))
        for ent in pendings:
            rel = self.db[table]
            cap = rel.capacity
            pos = np.arange(cap)
            checked = np.zeros(cap, dtype=bool)
            c = np.asarray(ent.checked, dtype=bool)
            checked[: min(c.shape[0], cap)] = c[:cap]
            fresh = jnp.asarray((pos >= ent.lo) & (pos < ent.hi))
            row_scope = jnp.asarray(checked) & rel.valid
            if not bool(to_host(jnp.any(row_scope & rel.valid))):
                continue
            row_block_ids = self._active_blocks(row_scope)
            col_blocks = (ent.lo // block, -(-ent.hi // block))
            rep.answer_size += int(to_host(jnp.sum(fresh & rel.valid)))
            # dense scan only: the sharded path has no partner-side
            # restriction, and a delta is small by construction
            rel, det = self._dc_detect_repair(
                rel, dc, row_scope, fresh, None, None, cm, rep,
                col_blocks=col_blocks, row_block_ids=row_block_ids,
            )
            rep.repaired += int(to_host(jnp.sum(
                ((det.t1_count > 0) | (det.t2_count > 0)) & row_scope
            )))
            self.db[table] = rel

    # ------------------------------------------------------------- FD steps
    def _clean_fd(
        self,
        step: CleanStep,
        report: ExecReport,
        answer_override: Optional[jnp.ndarray] = None,
        record_cost: bool = True,
    ) -> None:
        """One FD cleaning step.  ``answer_override`` substitutes an explicit
        answer mask for the predicate filter (the background cleaner's
        cold-group sweeps, DESIGN.md §10 — the step then runs exactly the
        relax/detect/repair/mark pipeline a query selecting those rows
        would); ``record_cost=False`` keeps background work out of the
        per-query cost-model history."""
        table, fd = step.table, step.rule
        self._process_pending(table, fd, report)
        rel = self.db[table]
        cm = self.cost.get((table, fd.name))
        st = self.stats.get((table, fd.name))
        rep = StepReport(fd.name, table, step.mode)

        mark_scope = None
        if step.mode == "full":
            # partial-work reuse (DESIGN.md §11): detect only lhs groups that
            # still hold cold rows, taken whole (candidates are per-group
            # evidence), instead of re-scanning groups earlier passes —
            # foreground or background — already covered.  The mark still
            # covers the whole relation: skipped groups are either fully
            # checked already or statically clean (detection over them merges
            # nothing), which is exactly what the unshrunk scan committed.
            cold = self._cold_mask(rel, table, fd.name)
            if bool(to_host(jnp.any(cold))):
                scope = self._fd_increment_seed(rel, fd, cold, None)
            else:
                scope = rel.valid
            mark_scope = rel.valid
            rep.answer_size = int(to_host(jnp.sum(scope)))
        else:
            ans = (
                _Answer(answer_override)
                if answer_override is not None
                else self._answer(table, step.preds)
            )
            answer = ans.mask
            # Fig. 11 skip: answer touches no dirty group and nothing unchecked
            if st is not None:
                gate = self._gate(
                    ans, rel, fd.name, dirty=self._dirty_rows[(table, fd.name)]
                )
                rep.answer_size = ans.size
                if not gate["hit"]:
                    rep.mode = "skipped"
                    report.steps.append(rep)
                    if cm and record_cost:
                        cm.record(rep.answer_size, 0, 0.0, 0)
                    return
            else:
                rep.answer_size = self._size(ans)
            with self.tracer.span(
                "clean.relax", rule=fd.name, table=table
            ) as sp:
                res = relax_fd(
                    rel,
                    answer,
                    fd,
                    max_iters=self.config.max_relax_iters,
                    use_rhs=step.use_rhs,
                )
                scope = answer | res.extra
                rep.extra = int(to_host(jnp.sum(res.extra)))
                rep.relax_iterations = int(to_host(res.iterations))
                rep.relax_converged = bool(to_host(res.converged))
                sp.set(extra=rep.extra, iterations=rep.relax_iterations)

        repair_scope = scope & unchecked(rel, fd.name)
        if not bool(to_host(jnp.any(repair_scope))):
            # everything in scope already checked for this rule (e.g. the
            # post-clean query phase of the offline baseline) — skip the
            # detection/repair/merge entirely.
            rep.mode = "skipped"
            report.steps.append(rep)
            if cm and record_cost:
                cm.record(rep.answer_size, rep.extra, 0.0, 0)
            return
        mesh = self._detect_mesh(step)
        self.detect_calls += 1
        rep.detect_pairs = int(to_host(jnp.sum(scope)))  # group-by is O(scope)
        self.detect_pairs += rep.detect_pairs
        with self.tracer.span(
            "clean.detect", rule=fd.name, table=table, mode=rep.mode,
            pairs=rep.detect_pairs,
        ) as sp:
            det, sinfo = detect_auto(
                rel, fd, scope, k=self.config.k,
                mesh=mesh, n_shards=self.config.detect_shards,
                strip_rows=self.ledger.strip_rows, tracer=self.tracer,
            )
            if sinfo is not None:
                rep.detect_path = "sharded"
                self._observe_sharded(table, fd.name, sinfo, cm)
            sp.set(path=rep.detect_path)
        self.repair_calls += 1
        with self.tracer.span("clean.repair", rule=fd.name, table=table) as sp:
            deltas = fd_repair_candidates(rel, fd, det, repair_scope)
            rep.repaired = int(to_host(jnp.sum(det.violated & repair_scope)))
            rel = self._apply(rel, deltas, table, fd.name)
            sp.set(repaired=rep.repaired)
        rel = self._mark(
            rel, table, fd.name, scope if mark_scope is None else mark_scope
        )
        self.db[table] = rel
        if cm and record_cost:
            d_i = float(to_host(jnp.sum(scope)))
            cm.record(rep.answer_size, rep.extra, d_i, rep.repaired)
            if step.mode == "full":
                cm.mark_switched()
        report.steps.append(rep)

    def _observe_sharded(self, table: str, rule_name: str, info, cm) -> None:
        """Record a sharded routing's ``ShardedDetectInfo`` and feed its
        observed cost to the rule's cost model, so the full/partial decision
        (and the background priority model, DESIGN.md §10) price the shuffle
        path the executor will actually take."""
        self.sharded_info[(table, rule_name)] = info
        if cm is not None:
            cm.observe_detect_cost(sharded_detect_cost(info, n_rows=cm.n))

    # ------------------------------------------------------------- DC steps
    def _dc_detect_repair(
        self, rel, dc, row_scope, col_scope, row_blocks, mesh, cm, rep,
        col_blocks=None, row_block_ids=None, col_block_ids=None,
    ):
        """One detect + repair-candidate pass of the DC increment engine:
        scan ``row_scope x col_scope`` (strip-scoped to ``row_blocks`` /
        ``col_blocks``, or block-sparse via ``row_block_ids`` /
        ``col_block_ids``, DESIGN.md §15), merge the role fixes for
        ``row_scope`` rows, account the scanned comparison space and the
        launch geometry.  Returns ``(rel, detect_result)``."""
        table = rep.table
        self.detect_calls += 1
        rows = int(to_host(jnp.sum(row_scope & rel.valid)))
        cols = int(to_host(jnp.sum(col_scope & rel.valid)))
        rep.detect_pairs += rows * cols
        self.detect_pairs += rows * cols
        with self.tracer.span(
            "clean.detect", rule=dc.name, table=table, mode=rep.mode,
            pairs=rows * cols,
            row_blocks=_blocks_attr(row_blocks),
            col_blocks=_blocks_attr(col_blocks),
            row_block_ids=None if row_block_ids is None else len(row_block_ids),
            col_block_ids=None if col_block_ids is None else len(col_block_ids),
        ) as sp:
            det, sinfo = detect_auto(
                rel, dc, row_scope, col_scope, block=self.config.dc_block,
                mesh=mesh, n_shards=self.config.detect_shards,
                row_blocks=row_blocks, col_blocks=col_blocks,
                row_block_ids=row_block_ids, col_block_ids=col_block_ids,
                strip_rows=self.ledger.strip_rows, tracer=self.tracer,
                encode=self.config.kernel_encodings,
            )
            if sinfo is not None:
                rep.detect_path = "sharded"
                self._observe_sharded(table, dc.name, sinfo, cm)
            launched = int(getattr(det, "tiles_launched", 0))
            skipped = max(int(getattr(det, "tiles_total", 0)) - launched, 0)
            rep.tiles_launched += launched
            rep.tiles_skipped += skipped
            self.tiles_launched += launched
            self.tiles_skipped += skipped
            scope = self.ledger.scope(table, dc.name)
            if scope is not None:
                scope.note_tiles(launched, skipped)
            if cm is not None and rep.mode == "full" and det.tiles_total:
                # the measured tile-level sparsity of a full-mode scan —
                # the cost model's detect term refines on it (DESIGN.md §15)
                cm.observe_tile_sparsity(launched / det.tiles_total)
            sp.set(
                path=rep.detect_path,
                tiles_launched=launched, tiles_skipped=skipped,
            )
        self.repair_calls += 1
        with self.tracer.span("clean.repair", rule=dc.name, table=table):
            deltas = dc_repair_candidates(rel, dc, det, row_scope, k=self.config.k)
            rel = self._apply(rel, deltas, table, dc.name)
        return rel, det

    def _active_blocks(self, mask) -> Optional[np.ndarray]:
        """EXACT kernel-grid block ids holding the mask's nonzero rows
        (None for an empty mask) — the block-sparse worklist side for
        answer-shaped scans (DESIGN.md §15): blocks between two active runs
        are absent from the launch, not merely scope-pruned inside it."""
        idx = np.flatnonzero(to_host(mask))
        if idx.size == 0:
            return None
        return np.unique(idx // self.config.dc_block).astype(np.int32)

    def _clean_dc(
        self, step: CleanStep, report: ExecReport, record_cost: bool = True
    ) -> None:
        """One DC cleaning step through the strip-grained increment engine
        (DESIGN.md §11).  Modes:

        * ``auto`` — Algorithm 2 resolves full vs incremental at execution
          time; its support input is the ledger's strip-coverage fraction;
        * ``incremental`` — the answer's matrix strip [answer x rest] plus
          the partner strip [rest x answer] (§4.2);
        * ``full`` — the REMAINING cold strips x the whole dataset: strips
          earlier passes (foreground or background) covered are skipped,
          both in the scope mask and in the kernel grid (partial-work
          reuse, the §11 refinement of the all-or-nothing full pass — and
          what makes a full clean after background progress merge each
          row's evidence exactly once);
        * ``strip`` — an explicit cold-strip subset (``step.strips``): the
          background cleaner's bounded-latency increment.  A strip sweep
          that covers every cold strip IS the remaining full clean and is
          reported as mode ``full``.

        ``record_cost=False`` keeps background work out of the per-query
        cost-model history (a scope-completing sweep still marks the rule
        switched: after it, nothing is left for the switch to buy)."""
        table, dc = step.table, step.rule
        self._process_pending(table, dc, report)
        rel = self.db[table]
        key = (table, dc.name)
        cm = self.cost.get(key)
        st: statsmod.DCStats = self.stats.get(key)
        scope_ledger = self.ledger.register(table, dc.name, rel.capacity)
        rep = StepReport(dc.name, table, step.mode)

        mode = step.mode
        ans = self._answer(table, step.preds)
        gate = None
        if mode == "auto":
            mode = "incremental"
            if st is not None:
                # Algorithm 2 reads only the extremes of the answer's pivot
                # values, which the gate returns with its size
                gate = self._gate(ans, rel, dc.name, pivot=rel.columns[st.pivot])
                dec = statsmod.algorithm2_decide(
                    st,
                    np.array([gate["lo"], gate["hi"]]),
                    ans.size,
                    scope_ledger.support,
                    self.config.accuracy_threshold,
                )
                rep.alg2_accuracy = dec.accuracy
                rep.alg2_support = dec.support
                mode = "full" if dec.full_clean else "incremental"

        # resolve the scan scope: which rows of the comparison matrix this
        # step owns, and the covering kernel block range (the strip grid)
        cold_ids = scope_ledger.cold_strips()
        cold_frac = scope_ledger.cold_fraction
        row_blocks = None
        row_block_ids = None
        if mode == "incremental":
            # the answer's unchecked rows, counted by the gate
            if gate is None:
                gate = self._gate(ans, rel, dc.name)
            rep.mode = mode
            rep.answer_size = ans.size
            has_work = bool(gate["hit"])
        else:
            live = unchecked(rel, dc.name)
            sel = cold_ids
            if step.strips is not None:
                # drop strips that raced warm since the step was planned
                sel = np.intersect1d(
                    np.asarray(step.strips, dtype=np.int64), cold_ids
                )
            if mode == "strip" and len(sel) < len(cold_ids):
                rep.mode = "strip"
            else:
                mode = "full"  # covers every cold strip == remaining full clean
            if len(sel):
                row_scope = jnp.asarray(scope_ledger.strip_mask(sel)) & live
                # EXACT cold-strip block ids, not the covering range: warm
                # strips between cold ones never launch (DESIGN.md §15)
                row_block_ids = scope_ledger.strip_block_ids(
                    sel, self.config.dc_block
                )
            else:
                row_scope = jnp.zeros_like(rel.valid)
            if mode == "strip":
                rep.answer_size = int(to_host(jnp.sum(row_scope)))
            else:
                rep.mode = mode
                rep.answer_size = self._size(ans)
            has_work = bool(to_host(jnp.any(row_scope)))

        # idempotence gate (the DC analogue of the FD dirty-group skip): when
        # everything this step would scope is already checked for the rule,
        # the pass that checked it also merged its DC evidence, so
        # re-detecting would only re-merge the same evidence — double-counting
        # candidate support and advancing clean_version for no state change.
        # Repeated queries therefore skip, keeping answers version-stable
        # (the service cache's contract, DESIGN.md §9).
        if not has_work:
            rep.mode = "skipped"
            report.steps.append(rep)
            if cm and record_cost:
                cm.record(rep.answer_size, 0, 0.0, 0)
            return

        mesh = self._detect_mesh(step)
        col_scope = rel.valid
        if mode == "incremental":
            answer = ans.mask
            row_scope = answer & unchecked(rel, dc.name)
            row_block_ids = self._active_blocks(row_scope)
        rel, det = self._dc_detect_repair(
            rel, dc, row_scope, col_scope, row_blocks, mesh, cm, rep,
            row_block_ids=row_block_ids,
        )
        repaired = (det.t1_count > 0) | (det.t2_count > 0)
        rep.repaired = int(to_host(jnp.sum(repaired & row_scope)))

        if mode == "incremental":
            # partners of the answer (the DC-correlated tuples, §4.2) get
            # their role fixes too — the incremental matrix strip
            # [rest x answer], partner-side-restricted to the answer's
            # active blocks (DESIGN.md §15)
            partner_scope = rel.valid & ~answer
            rel, det2 = self._dc_detect_repair(
                rel, dc, partner_scope, answer, None, mesh, cm, rep,
                row_block_ids=self._active_blocks(partner_scope),
                col_block_ids=self._active_blocks(answer),
            )
            rep.extra = int(
                to_host(
                    jnp.sum(((det2.t1_count > 0) | (det2.t2_count > 0)) & partner_scope)
                )
            )

        rel = self._mark(rel, table, dc.name, row_scope)
        self.db[table] = rel
        if cm and record_cost:
            n = cm.n
            d_i = (
                float(rep.answer_size) * n / max(self.config.dc_partitions, 1)
                if mode == "incremental"
                else cm.df_effective * cold_frac
            )
            cm.record(rep.answer_size, rep.extra, d_i, rep.repaired)
        if cm and rep.mode == "full":
            cm.mark_switched()
        report.steps.append(rep)

    # ------------------------------------------------------------ execution
    def _run_steps(self, plan: PlanInfo, report: ExecReport) -> None:
        for step in plan.steps:
            # one phase span per planned step, around its gates, Algorithm
            # 2, cold masks and clean.* spans (never inside one of those)
            with self.tracer.span(
                "execute.step", rule=step.rule.name, mode=step.mode
            ) as sp:
                if isinstance(step.rule, FD):
                    self._clean_fd(step, report)
                else:
                    self._clean_dc(step, report)
                if self.tracer:
                    ran = report.steps[-1].mode
                    skipped = ran == "skipped"
                    sp.set(
                        mode=step.mode if skipped else ran,
                        outcome="skipped" if skipped else "cleaned",
                    )

    def execute(self, query: Query) -> DaisyResult:
        # re-entrant: many serving sessions may share one executor; the lock
        # serializes the read-modify-write of self.db / cost / version state
        # so concurrent callers interleave at query granularity (candidate
        # merges stay Lemma-4 order-independent either way).
        with self._lock, self.tracer.span(
            "daisy.execute", table=query.table, joins=len(query.joins)
        ) as sp:
            self._memo = memo = _AnswerMemo()
            try:
                with self.tracer.span("execute.plan"):
                    plan = plan_query(
                        query, self.rules, self._want_full(),
                        lemma1_fast_path=self.config.lemma1_fast_path,
                        ledger=self.ledger,
                    )
                report = ExecReport(notes=list(plan.notes))

                if not query.joins:
                    result = self._execute_sp(query, plan, report)
                else:
                    result = self._execute_join(query, plan, report)
            finally:
                self._memo = None
            sp.set(
                steps=len(report.steps), result_size=report.result_size,
                filter_evals=memo.evals, filter_reuses=memo.reuses,
            )
            return result

    def _answer(self, table: str, preds) -> _Answer:
        """The answer of ``preds`` over ``table``: inside ``execute``, the
        last one computed while the relation object is the same (a cleaned
        step or an ingest-delta replaces it), else filtered anew."""
        rel = self.db[table]
        memo = self._memo
        key = tuple(preds)
        if memo is not None and memo.rel is rel and memo.key == key:
            memo.reuses += 1
            _FILTERS.add(reused=True)
            return memo.answer
        ans = _Answer(filter_mask(rel, preds))
        _FILTERS.add(reused=False)
        if memo is not None:
            memo.rel, memo.key, memo.answer = rel, key, ans
            memo.evals += 1
        return ans

    def _gate(self, ans: _Answer, rel: Relation, rule_name: str, **extra):
        """Run ``_step_gate`` for one step and read its scalars in one
        ``to_host``; records the answer's size on ``ans``."""
        out = to_host(_step_gate(ans.mask, rel.valid, rel.checked.get(rule_name), **extra))
        ans.size = int(out["size"])
        return out

    def _size(self, ans: _Answer) -> int:
        if ans.size is None:
            ans.size = int(to_host(jnp.sum(ans.mask)))
        return ans.size

    # ----------------------------------------------------------- SP queries
    def _execute_sp(self, query: Query, plan: PlanInfo, report: ExecReport) -> DaisyResult:
        self._run_steps(plan, report)
        rel = self.db[query.table]
        with self.tracer.span("execute.filter"):
            ans = self._answer(query.table, query.preds)
            mask = ans.mask
            report.result_size = self._size(ans)
        result = DaisyResult(mask=mask, report=report)
        if query.groupby is not None:
            with self.tracer.span("execute.groupby"):
                result.groups = self._groupby_sp(rel, mask, query.groupby)
        return result

    def _groupby_sp(self, rel: Relation, mask, spec: GroupBySpec):
        from repro.core.operators import groupby_agg

        return groupby_agg(rel, mask, spec)

    # --------------------------------------------------------- join queries
    def _execute_join(self, query: Query, plan: PlanInfo, report: ExecReport) -> DaisyResult:
        # pre-clean qualifying masks (the dirty base join inputs)
        with self.tracer.span("execute.filter", stage="pre"):
            pre_masks: Dict[str, jnp.ndarray] = {
                query.table: filter_mask(self.db[query.table], query.preds)
            }
            for j in query.joins:
                pre_masks[j.right] = filter_mask(self.db[j.right], j.right_preds)

        # clean each side's qualifying part (push-down, §5.1)
        self._run_steps(plan, report)

        with self.tracer.span("execute.filter", stage="post"):
            post_masks: Dict[str, jnp.ndarray] = {
                query.table: filter_mask(self.db[query.table], query.preds)
            }
            for j in query.joins:
                post_masks[j.right] = filter_mask(self.db[j.right], j.right_preds)

        with self.tracer.span("execute.join", joins=len(query.joins)):
            state: Optional[JoinState] = None
            for j in query.joins:
                state = self._join_once(query, state, j, pre_masks, post_masks, report)
            report.result_size = int(to_host(jnp.sum(state.valid)))
            report.recheck_violations = self._recheck(state)
        result = DaisyResult(join=state, report=report)
        if query.groupby is not None:
            with self.tracer.span("execute.groupby"):
                result.groups = self._groupby_join(state, query.groupby)
        return result

    def _key_source(self, state: Optional[JoinState], base: str, col: str) -> str:
        """Which table provides ``col`` for the current join state."""
        tables = [base] if state is None else list(state.tables)
        for t in tables:
            if col in self.db[t].columns:
                return t
        raise KeyError(f"join key {col!r} not found among {tables}")

    def _join_once(
        self,
        query: Query,
        state: Optional[JoinState],
        j,
        pre_masks,
        post_masks,
        report: ExecReport,
    ) -> JoinState:
        cfg = self.config
        left_table = self._key_source(state, query.table, j.left_on)
        rel_l = self.db[left_table]
        rel_r = self.db[j.right]
        kv_l, al_l = key_candidates(rel_l, j.left_on)
        kv_r, al_r = key_candidates(rel_r, j.right_on)

        if state is None:
            pre_l, post_l = pre_masks[query.table], post_masks[query.table]
            pre_r, post_r = pre_masks[j.right], post_masks[j.right]
            # base join on the dirty qualifying parts
            li, ri, v, ovf = prob_equijoin(
                kv_l, al_l, pre_l, kv_r, al_r, pre_r,
                cfg.join_capacity, cfg.join_row_block,
            )
            # incremental join of the relaxation extras (Fig. 5):
            # extras_l x post_r, then pre_l x extras_r
            extra_l = post_l & ~pre_l
            extra_r = post_r & ~pre_r
            li2, ri2, v2, ovf2 = prob_equijoin(
                kv_l, al_l, extra_l, kv_r, al_r, post_r,
                cfg.join_capacity, cfg.join_row_block,
            )
            li3, ri3, v3, ovf3 = prob_equijoin(
                kv_l, al_l, pre_l, kv_r, al_r, extra_r,
                cfg.join_capacity, cfg.join_row_block,
            )
            li = jnp.concatenate([li, li2, li3])
            ri = jnp.concatenate([ri, ri2, ri3])
            v = jnp.concatenate([v, v2, v3])
            v = dedupe_pairs(li, ri, v)
            # compact to capacity
            order = jnp.argsort(~v, stable=True)[: cfg.join_capacity]
            li, ri, v = li[order], ri[order], v[order]
            overflow = ovf | ovf2 | ovf3
            report.join_overflow = bool(to_host(overflow))
            return JoinState(
                tables=(left_table, j.right),
                rows={left_table: li, j.right: ri},
                valid=v,
                overflow=overflow,
            )

        # chained join: gather current result's key candidates
        rows_l = state.rows[left_table]
        kv_res = kv_l[rows_l]
        al_res = al_l[rows_l] & state.valid[:, None]
        post_r = post_masks.get(j.right, self.db[j.right].valid)
        li, ri, v, ovf = prob_equijoin(
            kv_res, al_res, state.valid, kv_r, al_r, post_r,
            cfg.join_capacity, cfg.join_row_block,
        )
        v = dedupe_pairs(li, ri, v)
        new_rows = {
            t: jnp.where(v, r[jnp.minimum(li, r.shape[0] - 1)], r.shape[0])
            for t, r in state.rows.items()
        }
        new_rows[j.right] = jnp.where(v, ri, rel_r.capacity)
        report.join_overflow = report.join_overflow or bool(to_host(ovf))
        return JoinState(
            tables=state.tables + (j.right,),
            rows=new_rows,
            valid=v,
            overflow=state.overflow | ovf,
        )

    def _recheck(self, state: JoinState) -> int:
        """Def. 3 (d): re-check the stitched join result for violations.
        Lemma 5 predicts zero NEW violations among unchecked rows."""
        total = 0
        for table in state.tables:
            rel = self.db[table]
            used = jnp.zeros((rel.capacity,), bool).at[
                jnp.where(state.valid, state.rows[table], rel.capacity)
            ].set(True, mode="drop")
            for rule in self.rules.get(table, ()):
                if isinstance(rule, FD):
                    self.detect_calls += 1
                    det = detect_fd(rel, rule, used & rel.valid, k=self.config.k)
                    fresh = det.violated & unchecked(rel, rule.name)
                    total += int(to_host(jnp.sum(fresh)))
        return total

    def _groupby_join(self, state: JoinState, spec: GroupBySpec):
        """Group-by over join lineage: gather key/value columns, aggregate
        with expected-value semantics."""
        table = spec.table or self._key_source(state, state.tables[0], spec.keys[0])
        rel = self.db[table]
        rows = state.rows[table]
        safe = jnp.minimum(rows, rel.capacity - 1)
        keys = [rel.columns[a][safe] for a in spec.keys]
        w = state.valid.astype(jnp.float32)
        if spec.value:
            vt = spec.table or self._key_source(state, state.tables[0], spec.value)
            vrel = self.db[vt]
            vrows = jnp.minimum(state.rows[vt], vrel.capacity - 1)
            v = expected_value(vrel, spec.value)[vrows]
        else:
            v = jnp.zeros_like(w)
        return _finalize_groupby(spec, keys, state.valid, w, v)
