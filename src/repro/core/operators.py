"""Query AST + probabilistic execution primitives (paper §4, §5).

The supported query template (§5):

    SELECT <list> FROM T [, (J)]
    [WHERE col op val [AND col op val ...]]
    [GROUP BY keys [agg]]

Execution follows the paper's possible-worlds semantics over the
attribute-level-uncertain relation:

* **filter**: a tuple qualifies iff >= 1 candidate qualifies
  (``Relation.candidate_matches``);
* **join**: a pair qualifies iff the candidate value sets of the join keys
  overlap (§4: "for (self-)joins on probabilistic join keys, a pair
  qualifies iff the candidate values of the join keys overlap"); lineage =
  the originating row-id arrays, kept in the result;
* **group-by**: expected-value aggregation — each candidate contributes its
  probability mass to its group (the probabilistic-DB expectation semantics
  of [34], the paper's uncertainty model).

Static shapes throughout: masks for SP results, fixed-capacity (li, ri) index
arrays + overflow flag for joins (jnp.nonzero with static size).
"""

from __future__ import annotations

import dataclasses
import functools
import hashlib
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.relation import CAND_VALUE, Relation, possible_match
from repro.core.setops import group_info, unique_counts


# --------------------------------------------------------------------- AST
@dataclasses.dataclass(frozen=True)
class Pred:
    col: str
    op: str
    value: float | int


@dataclasses.dataclass(frozen=True)
class JoinClause:
    right: str  # right table name
    left_on: str
    right_on: str
    right_preds: Tuple[Pred, ...] = ()


@dataclasses.dataclass(frozen=True)
class GroupBySpec:
    keys: Tuple[str, ...]
    agg: str = "count"  # count | sum | avg
    value: Optional[str] = None  # aggregated column (for sum/avg)
    table: Optional[str] = None  # which table the key/value columns live in


@dataclasses.dataclass(frozen=True)
class Query:
    table: str
    preds: Tuple[Pred, ...] = ()
    project: Tuple[str, ...] = ()
    joins: Tuple[JoinClause, ...] = ()
    groupby: Optional[GroupBySpec] = None

    @property
    def attrs(self) -> Tuple[str, ...]:
        out = list(self.project)
        for p in self.preds:
            out.append(p.col)
        for j in self.joins:
            out.append(j.left_on)
            out.append(j.right_on)
            for p in j.right_preds:
                out.append(p.col)
        if self.groupby:
            out.extend(self.groupby.keys)
            if self.groupby.value:
                out.append(self.groupby.value)
        return tuple(dict.fromkeys(out))


# ----------------------------------------------------------- fingerprinting
def _fp_value(v) -> str:
    """Canonical token for a predicate constant: bools/ints by value, floats
    by exact bit pattern (hex), so equal constants always tokenize equally
    while 1 and 1.0000001 never collide."""
    if isinstance(v, (bool, np.bool_)):
        return f"b{int(v)}"
    if isinstance(v, (int, np.integer)):
        return f"i{int(v)}"
    return f"f{float(v).hex()}"


def _fp_preds(preds: Sequence[Pred]) -> List[Tuple[str, str, str]]:
    return sorted((p.col, p.op, _fp_value(p.value)) for p in preds)


def query_fingerprint(query: Query) -> str:
    """Stable fingerprint of a query's logical content (DESIGN.md §9).

    The service cache keys on ``(fingerprint, clean_version)``, so this must
    be deterministic across processes — hashlib over a canonical token
    stream, never ``hash()`` (PYTHONHASHSEED).  Conjunctive predicates are
    order-normalized (AND commutes); join order is preserved because it
    decides capacity truncation and is therefore answer-relevant.
    """
    parts: List[str] = ["T", query.table]
    # projection feeds Query.attrs and hence the planner's rule-overlap
    # decision, so it is state-trajectory-relevant even though it never
    # filters rows; list order is not (attrs dedups into a set check).
    for col in sorted(query.project):
        parts += ["R", col]
    for col, op, val in _fp_preds(query.preds):
        parts += ["P", col, op, val]
    for j in query.joins:
        parts += ["J", j.right, j.left_on, j.right_on]
        for col, op, val in _fp_preds(j.right_preds):
            parts += ["P", col, op, val]
    g = query.groupby
    if g is not None:
        parts += ["G", ",".join(g.keys), g.agg, g.value or "", g.table or ""]
    return hashlib.sha256("\x1f".join(parts).encode()).hexdigest()[:16]


# ----------------------------------------------------------------- results
@dataclasses.dataclass
class JoinState:
    """Lineage of a (possibly multi-way) join: per-table originating row ids
    for each result pair (the paper's probabilistic-join lineage)."""

    tables: Tuple[str, ...]
    rows: Dict[str, jnp.ndarray]  # table -> (cap_out,) int32 row ids
    valid: jnp.ndarray  # (cap_out,) bool
    overflow: jnp.ndarray  # () bool


# ----------------------------------------------------------------- filters
@functools.partial(jax.jit, static_argnums=0)
def _filter_program(structure, valid, arrays, values):
    """The conjunctive possible-world filter as one program.  ``structure``
    (static) holds ``(col, op, has_candidates)`` per predicate; ``arrays``
    the column, plus candidates, kinds and counts where it has them; and
    ``values`` the predicate constants, traced, so a new constant reuses
    the program and a Python scalar stays weakly typed (it promotes as the
    eager ``column op value`` does)."""
    mask = valid
    for (_, op, _), arrs, value in zip(structure, arrays, values):
        mask = mask & possible_match(op, value, *arrs)
    return mask


def filter_mask(rel: Relation, preds: Sequence[Pred]) -> jnp.ndarray:
    """Possible-world conjunctive filter: a row qualifies iff every predicate
    has a qualifying candidate (``Relation.candidate_matches``)."""
    if not preds:
        return rel.valid
    structure, arrays = [], []
    for p in preds:
        has = p.col in rel.cand
        structure.append((p.col, p.op, has))
        arrays.append(
            (rel.columns[p.col], rel.cand[p.col], rel.ckind[p.col], rel.ccount[p.col])
            if has else (rel.columns[p.col],)
        )
    return _filter_program(
        tuple(structure), rel.valid, tuple(arrays), tuple(p.value for p in preds)
    )


def key_candidates(rel: Relation, attr: str) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(cap, K) candidate values + alive mask for a join key.  Rows without
    an overlay expose their primary value as the single candidate.  Range
    candidates (CAND_LT/GT) do not participate in equi-join matching."""
    col = rel.columns[attr]
    if attr not in rel.cand:
        return col[:, None], rel.valid[:, None]
    cand = rel.cand[attr]
    alive = (rel.ccount[attr] > 0) & (rel.ckind[attr] == CAND_VALUE)
    has = jnp.any(alive, axis=1)
    # no-overlay rows: candidate 0 = primary value
    vals = jnp.where(has[:, None], cand, jnp.concatenate(
        [col[:, None], cand[:, 1:]], axis=1))
    alive = jnp.where(
        has[:, None],
        alive,
        jnp.zeros_like(alive).at[:, 0].set(True),
    )
    return vals, alive & rel.valid[:, None]


def candidate_overlap_matrix(
    l_vals: jnp.ndarray,
    l_alive: jnp.ndarray,
    r_vals: jnp.ndarray,
    r_alive: jnp.ndarray,
) -> jnp.ndarray:
    """(n_l, n_r) bool — candidate sets overlap (the possible-world join)."""
    kl = l_vals.shape[1]
    kr = r_vals.shape[1]
    match = jnp.zeros((l_vals.shape[0], r_vals.shape[0]), dtype=bool)
    for a in range(kl):
        for b in range(kr):
            m = (l_vals[:, a][:, None] == r_vals[:, b][None, :]) & (
                l_alive[:, a][:, None] & r_alive[:, b][None, :]
            )
            match = match | m
    return match


def prob_equijoin(
    l_vals: jnp.ndarray,
    l_alive: jnp.ndarray,
    mask_l: jnp.ndarray,
    r_vals: jnp.ndarray,
    r_alive: jnp.ndarray,
    mask_r: jnp.ndarray,
    cap_out: int,
    row_block: int = 1024,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Possible-world equi-join.  Returns (li, ri, valid, overflow) with
    static output capacity ``cap_out``.  Processes left rows in blocks so the
    match matrix stays bounded."""
    n_l = l_vals.shape[0]
    n_r = r_vals.shape[0]
    nb = -(-n_l // row_block)
    all_li, all_ri, all_v = [], [], []
    overflow = jnp.bool_(False)
    for b in range(nb):
        lo = b * row_block
        hi = min(lo + row_block, n_l)
        match = candidate_overlap_matrix(
            l_vals[lo:hi], l_alive[lo:hi], r_vals, r_alive
        )
        match = match & mask_l[lo:hi, None] & mask_r[None, :]
        cnt = jnp.sum(match.astype(jnp.int32))
        li, ri = jnp.nonzero(
            match, size=cap_out, fill_value=(hi - lo, n_r)
        )
        v = li < (hi - lo)
        overflow = overflow | (cnt > cap_out)
        all_li.append(jnp.where(v, li + lo, n_l))
        all_ri.append(ri)
        all_v.append(v)
    li = jnp.concatenate(all_li)
    ri = jnp.concatenate(all_ri)
    v = jnp.concatenate(all_v)
    # compact valid pairs to the front, truncate to cap_out
    order = jnp.argsort(~v, stable=True)
    li, ri, v = li[order][:cap_out], ri[order][:cap_out], v[order][:cap_out]
    overflow = overflow | (jnp.sum(jnp.concatenate(all_v).astype(jnp.int32)) > cap_out)
    return li, ri, v, overflow


def dedupe_pairs(
    li: jnp.ndarray, ri: jnp.ndarray, valid: jnp.ndarray
) -> jnp.ndarray:
    """Mark duplicate (li, ri) pairs invalid (keep first occurrence)."""
    n = li.shape[0]
    big = jnp.int32(np.iinfo(np.int32).max)
    k1 = jnp.where(valid, li, big)
    k2 = jnp.where(valid, ri, big)
    pos = jnp.arange(n, dtype=jnp.int32)
    sk1, sk2, spos = jax.lax.sort((k1, k2, pos), num_keys=2)
    dup = jnp.zeros((n,), bool)
    if n > 1:
        dup = dup.at[1:].set((sk1[1:] == sk1[:-1]) & (sk2[1:] == sk2[:-1]))
    keep_sorted = ~dup
    keep = jnp.zeros((n,), bool).at[spos].set(keep_sorted)
    return valid & keep


# ---------------------------------------------------------------- group-by
def expected_value(rel: Relation, attr: str) -> jnp.ndarray:
    """Per-row expected value of a (possibly probabilistic) numeric column."""
    col = rel.columns[attr].astype(jnp.float32)
    if attr not in rel.cand:
        return col
    probs = rel.probs(attr)
    vals = jnp.where(
        rel.ckind[attr] == CAND_VALUE, rel.cand[attr].astype(jnp.float32), col[:, None]
    )
    has = jnp.any(rel.ccount[attr] > 0, axis=1)
    exp = jnp.sum(probs * vals, axis=1)
    return jnp.where(has, exp, col)


def groupby_agg(
    rel: Relation,
    mask: jnp.ndarray,
    spec: GroupBySpec,
    weights: jnp.ndarray | None = None,
) -> Dict[str, jnp.ndarray]:
    """Expected-value group-by over (possibly probabilistic) keys.

    Probabilistic keys contribute probability-weighted mass to each candidate
    key's group.  Returns dense arrays: key columns, per-group weighted count
    and aggregate, plus ``num_groups``.
    """
    base_w = mask.astype(jnp.float32) if weights is None else jnp.where(mask, weights, 0.0)
    vcol = expected_value(rel, spec.value) if spec.value else jnp.zeros_like(base_w)

    # expand probabilistic single-key groupings; multi-key uses primary values
    if len(spec.keys) == 1 and spec.keys[0] in rel.cand:
        attr = spec.keys[0]
        kv, alive = key_candidates(rel, attr)
        probs = rel.probs(attr)
        has = jnp.any(rel.ccount[attr] > 0, axis=1)
        w = jnp.where(
            has[:, None], probs, jnp.zeros_like(probs).at[:, 0].set(1.0)
        ) * base_w[:, None]
        flat_keys = [kv.reshape(-1)]
        flat_w = w.reshape(-1)
        flat_v = jnp.repeat(vcol, kv.shape[1])
        flat_mask = (flat_w > 0)
    else:
        flat_keys = [rel.columns[a] for a in spec.keys]
        flat_w = base_w
        flat_v = vcol
        flat_mask = mask

    return _finalize_groupby(spec, flat_keys, flat_mask, flat_w, flat_v)


def _finalize_groupby(spec, flat_keys, flat_mask, flat_w, flat_v):
    """Segment-sum per distinct key.  ``group_info`` gids are dense in sorted
    key order and ``unique_counts`` emits uniques in the same order, so
    unique ``i`` aligns with segment ``i`` by construction (masked rows land
    in the trailing sentinel segment and contribute zero weight)."""
    n = flat_keys[0].shape[0]
    gid, _ = group_info(flat_keys, flat_mask)
    wsum = jax.ops.segment_sum(jnp.where(flat_mask, flat_w, 0.0), gid, num_segments=n)
    vsum = jax.ops.segment_sum(
        jnp.where(flat_mask, flat_w * flat_v, 0.0), gid, num_segments=n
    )
    uvals, _, nuniq = unique_counts(flat_keys, flat_mask)
    result = {f"key_{a}": uvals[i] for i, a in enumerate(spec.keys)}
    result["count"] = wsum
    if spec.agg == "sum":
        result["agg"] = vsum
    elif spec.agg == "avg":
        result["agg"] = jnp.where(wsum > 0, vsum / jnp.maximum(wsum, 1e-30), 0.0)
    else:
        result["agg"] = wsum
    result["num_groups"] = nuniq
    return result
