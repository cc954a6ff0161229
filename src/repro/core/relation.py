"""Columnar, fixed-capacity, probabilistic relation.

TPU adaptation of Daisy's Spark RDD rows (DESIGN.md §2):

* columns are dense ``int32``/``float32`` arrays of a fixed ``capacity`` with a
  validity mask — no dynamic row sets, everything is mask/scatter based so every
  operator JITs to a static shape;
* string attributes are dictionary-encoded to ``int32`` codes host-side
  (``Dictionary``); equality of codes == equality of strings, so FD semantics
  are unchanged;
* attribute-level uncertainty (Suciu-style, §4 of the paper) is a dense overlay:
  up to ``K`` candidate values per cell with *counts* (probabilities are derived
  ``count / sum(count)``).  Keeping raw counts makes the multi-rule merge of
  Lemma 4 exactly commutative/associative;
* general-DC range candidates carry a per-candidate kind code
  (``CAND_VALUE`` / ``CAND_LT`` / ``CAND_GT``), matching the paper's
  "original value or a value satisfying the range" fixes (Example 4);
* provenance to the original values (``orig``) and per-rule ``checked`` flags
  are first-class, which is what enables the incremental multi-rule behaviour
  of Table 7.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.trace import to_host

# Sentinel pushed to the end of sorts; also the "invalid" key. Encoded values
# produced by Dictionary start at 0 and stay well below this.
SENTINEL = np.int32(2**31 - 1)

# Candidate kinds (attribute-level uncertainty cells).
CAND_VALUE = np.int8(0)  # candidate is a concrete value
CAND_LT = np.int8(1)  # candidate is the open range (-inf, bound)
CAND_GT = np.int8(2)  # candidate is the open range (bound, +inf)


class Dictionary:
    """Host-side string dictionary (string -> int32 code)."""

    def __init__(self, values: Optional[Sequence[str]] = None):
        self._to_code: Dict[str, int] = {}
        self._to_str: List[str] = []
        if values is not None:
            for v in values:
                self.encode(v)

    def encode(self, value: str) -> int:
        code = self._to_code.get(value)
        if code is None:
            code = len(self._to_str)
            self._to_code[value] = code
            self._to_str.append(value)
        return code

    def encode_many(self, values: Sequence[str]) -> np.ndarray:
        return np.asarray([self.encode(v) for v in values], dtype=np.int32)

    def decode(self, code: int) -> str:
        return self._to_str[int(code)]

    def __len__(self) -> int:
        return len(self._to_str)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class Relation:
    """Fixed-capacity columnar relation with a probabilistic overlay.

    Attributes
    ----------
    columns:   name -> (cap,) primary value per cell (the current best value —
               candidate 0 of the overlay when the cell is uncertain).
    valid:     (cap,) bool row validity.
    cand:      name -> (cap, K) candidate values        (overlay attrs only)
    ccount:    name -> (cap, K) float32 candidate counts (0 == empty slot)
    ckind:     name -> (cap, K) int8 candidate kinds (CAND_VALUE/LT/GT)
    orig:      name -> (cap,) provenance: the pre-cleaning original value
    checked:   rule name -> (cap,) bool "tuple checked for this rule"
    """

    columns: Dict[str, jnp.ndarray]
    valid: jnp.ndarray
    cand: Dict[str, jnp.ndarray]
    ccount: Dict[str, jnp.ndarray]
    ckind: Dict[str, jnp.ndarray]
    orig: Dict[str, jnp.ndarray]
    checked: Dict[str, jnp.ndarray]

    # ---------------------------------------------------------------- pytree
    def tree_flatten(self):
        names = sorted(self.columns)
        onames = sorted(self.cand)
        gnames = sorted(self.orig)
        rnames = sorted(self.checked)
        leaves = (
            [self.columns[n] for n in names]
            + [self.valid]
            + [self.cand[n] for n in onames]
            + [self.ccount[n] for n in onames]
            + [self.ckind[n] for n in onames]
            + [self.orig[n] for n in gnames]
            + [self.checked[n] for n in rnames]
        )
        aux = (tuple(names), tuple(onames), tuple(gnames), tuple(rnames))
        return leaves, aux

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        names, onames, gnames, rnames = aux
        it = iter(leaves)
        columns = {n: next(it) for n in names}
        valid = next(it)
        cand = {n: next(it) for n in onames}
        ccount = {n: next(it) for n in onames}
        ckind = {n: next(it) for n in onames}
        orig = {n: next(it) for n in gnames}
        checked = {n: next(it) for n in rnames}
        return cls(columns, valid, cand, ccount, ckind, orig, checked)

    # ------------------------------------------------------------ properties
    @property
    def capacity(self) -> int:
        return int(self.valid.shape[0])

    @property
    def k(self) -> int:
        for v in self.cand.values():
            return int(v.shape[1])
        return 0

    @property
    def names(self) -> Tuple[str, ...]:
        return tuple(sorted(self.columns))

    def num_rows(self) -> jnp.ndarray:
        return jnp.sum(self.valid.astype(jnp.int32))

    # ------------------------------------------------------------- overlays
    def has_overlay(self, name: str) -> bool:
        return name in self.cand

    def probs(self, name: str) -> jnp.ndarray:
        """(cap, K) candidate probabilities (counts normalized per row)."""
        c = self.ccount[name]
        tot = jnp.sum(c, axis=1, keepdims=True)
        return jnp.where(tot > 0, c / jnp.maximum(tot, 1e-30), 0.0)

    def is_uncertain(self, name: str) -> jnp.ndarray:
        """(cap,) bool — cell has >= 2 candidates."""
        return jnp.sum((self.ccount[name] > 0).astype(jnp.int32), axis=1) >= 2

    def candidate_matches(self, name: str, op: str, value) -> jnp.ndarray:
        """Possible-world predicate: (cap,) bool — does ANY candidate of
        ``name`` satisfy ``op value``?  (Paper §4: "query operators output a
        tuple iff at least one candidate value qualifies".)

        Range candidates (CAND_LT/CAND_GT) qualify when the candidate range
        overlaps the predicate's satisfying set.
        """
        if name not in self.cand:
            return possible_match(op, value, self.columns[name])
        return possible_match(
            op, value, self.columns[name],
            self.cand[name], self.ckind[name], self.ccount[name],
        )


def possible_match(op: str, value, column, cand=None, ckind=None, ccount=None):
    """``Relation.candidate_matches`` over the arrays it reads: the column
    and, for an overlay attribute, its candidates, kinds and counts.  Plain
    jnp, so it runs eagerly or inside a jitted program alike."""
    if cand is None:
        return _apply_op(column, op, value)
    alive = ccount > 0
    val_ok = _apply_op(cand, op, value)
    # Range candidate overlap rules against {EQ, NE, LT, LE, GT, GE} preds.
    lt_ok = _range_lt_overlaps(cand, op, value)  # candidate == (-inf, cand)
    gt_ok = _range_gt_overlaps(cand, op, value)  # candidate == (cand, +inf)
    ok = jnp.where(ckind == CAND_LT, lt_ok, jnp.where(ckind == CAND_GT, gt_ok, val_ok))
    any_ok = jnp.any(ok & alive, axis=1)
    no_cand = ~jnp.any(alive, axis=1)
    base_ok = _apply_op(column, op, value)
    return jnp.where(no_cand, base_ok, any_ok)


def _apply_op(x: jnp.ndarray, op: str, value) -> jnp.ndarray:
    if op == "==":
        return x == value
    if op == "!=":
        return x != value
    if op == "<":
        return x < value
    if op == "<=":
        return x <= value
    if op == ">":
        return x > value
    if op == ">=":
        return x >= value
    raise ValueError(f"unknown op {op!r}")


def _range_lt_overlaps(bound: jnp.ndarray, op: str, value) -> jnp.ndarray:
    """Does the candidate range (-inf, bound) intersect {x : x op value}?"""
    if op == "==":
        return value < bound
    if op == "!=":
        return jnp.ones_like(bound, dtype=bool)
    if op in ("<", "<="):
        return jnp.ones_like(bound, dtype=bool)  # range extends to -inf
    if op in (">", ">="):
        return bound > value  # some x with value < x < bound exists
    raise ValueError(op)


def _range_gt_overlaps(bound: jnp.ndarray, op: str, value) -> jnp.ndarray:
    """Does the candidate range (bound, +inf) intersect {x : x op value}?"""
    if op == "==":
        return value > bound
    if op == "!=":
        return jnp.ones_like(bound, dtype=bool)
    if op in (">", ">="):
        return jnp.ones_like(bound, dtype=bool)  # range extends to +inf
    if op in ("<", "<="):
        return value > bound  # some x with bound < x < value exists
    raise ValueError(op)


def make_relation(
    data: Mapping[str, np.ndarray],
    capacity: Optional[int] = None,
    overlay: Sequence[str] = (),
    k: int = 8,
    rules: Sequence[str] = (),
) -> Relation:
    """Build a Relation from host numpy columns.

    ``overlay`` lists attributes that may become probabilistic (the attributes
    appearing in some constraint).  ``rules`` pre-registers per-rule checked
    flags.
    """
    names = list(data)
    if not names:
        raise ValueError("empty relation")
    n = len(np.asarray(data[names[0]]))
    cap = capacity or n
    if cap < n:
        raise ValueError(f"capacity {cap} < rows {n}")

    columns = {}
    for name in names:
        arr = np.asarray(data[name])
        if arr.dtype.kind in "iu":
            arr = arr.astype(np.int32)
            pad_val = SENTINEL
        else:
            arr = arr.astype(np.float32)
            pad_val = np.float32(np.nan)
        out = np.full((cap,), pad_val, dtype=arr.dtype)
        out[:n] = arr
        columns[name] = jnp.asarray(out)

    valid = jnp.asarray(np.arange(cap) < n)

    cand, ccount, ckind, orig = {}, {}, {}, {}
    for name in overlay:
        col = columns[name]
        cv = jnp.zeros((cap, k), dtype=col.dtype)
        cand[name] = cv.at[:, 0].set(col)
        # count 0 everywhere -> "no overlay yet"; cells become uncertain only
        # once a repair writes counts.
        ccount[name] = jnp.zeros((cap, k), dtype=jnp.float32)
        ckind[name] = jnp.zeros((cap, k), dtype=jnp.int8)
        orig[name] = col
    checked = {r: jnp.zeros((cap,), dtype=bool) for r in rules}
    return Relation(columns, valid, cand, ccount, ckind, orig, checked)


def _pad_value(dtype) -> object:
    return np.float32(np.nan) if dtype == jnp.float32 else SENTINEL


def _grow_relation(rel: Relation, capacity: int) -> Relation:
    """Re-pad every array of ``rel`` to ``capacity`` rows.

    The first ``rel.capacity`` rows of every array are carried over
    bit-for-bit (overlay counts, kinds, checked flags, provenance); the
    new tail gets exactly the spare-row state ``make_relation`` would have
    produced: pad values in columns/orig, ``valid=False``, empty overlay
    with candidate slot 0 mirroring the (pad) column value, and unchecked.
    """
    old = rel.capacity
    if capacity < old:
        raise ValueError(f"cannot shrink capacity {old} -> {capacity}")
    if capacity == old:
        return rel
    extra = capacity - old
    k = rel.k

    def pad1(arr, fill):
        tail = jnp.full((extra,), fill, dtype=arr.dtype)
        return jnp.concatenate([arr, tail])

    columns = {n: pad1(c, _pad_value(c.dtype)) for n, c in rel.columns.items()}
    valid = pad1(rel.valid, False)
    cand, ccount, ckind, orig = {}, {}, {}, {}
    for name, cv in rel.cand.items():
        pad = _pad_value(cv.dtype)
        tail = jnp.zeros((extra, k), dtype=cv.dtype).at[:, 0].set(pad)
        cand[name] = jnp.concatenate([cv, tail])
        ccount[name] = jnp.concatenate(
            [rel.ccount[name], jnp.zeros((extra, k), dtype=jnp.float32)]
        )
        ckind[name] = jnp.concatenate(
            [rel.ckind[name], jnp.zeros((extra, k), dtype=jnp.int8)]
        )
        orig[name] = pad1(rel.orig[name], pad)
    checked = {r: pad1(c, False) for r, c in rel.checked.items()}
    return Relation(columns, valid, cand, ccount, ckind, orig, checked)


def append_rows(rel: Relation, data: Mapping[str, np.ndarray]) -> Tuple[Relation, int]:
    """Append host rows into a relation's spare capacity (DESIGN.md §12).

    ``data`` must provide exactly the relation's columns.  Rows land at
    the end of the valid prefix (``valid`` stays a prefix mask, the
    invariant every strip/ledger computation relies on); when the spare
    capacity runs out the relation grows to ``next_pow2`` of the needed
    row count, preserving all pre-existing overlay/checked/cand state
    bit-for-bit.  Fresh rows start exactly like ``make_relation`` rows:
    certain (empty overlay, candidate slot 0 = the value), unchecked for
    every rule, with ``orig`` provenance equal to the ingested value.

    Returns ``(new_relation, start)`` where ``start`` is the row index of
    the first appended row.  Pure — the input relation is not mutated.
    """
    names = set(rel.columns)
    if set(data) != names:
        raise ValueError(
            f"ingest columns {sorted(data)} != relation columns {sorted(names)}"
        )
    arrays = {n: np.asarray(v) for n, v in data.items()}
    lengths = {len(a) for a in arrays.values()}
    if len(lengths) != 1:
        raise ValueError(f"ragged ingest batch: column lengths {sorted(lengths)}")
    n_new = lengths.pop()
    if n_new == 0:
        return rel, int(to_host(rel.valid).sum())

    start = int(to_host(rel.valid).sum())
    needed = start + n_new
    if needed > rel.capacity:
        rel = _grow_relation(rel, next_pow2(needed))
    stop = start + n_new

    columns = dict(rel.columns)
    for name, arr in arrays.items():
        col = columns[name]
        if col.dtype == jnp.float32:
            vals = jnp.asarray(arr.astype(np.float32))
        else:
            if arr.dtype.kind not in "iu":
                raise ValueError(f"column {name!r} expects integer values")
            vals = jnp.asarray(arr.astype(np.int32))
        columns[name] = col.at[start:stop].set(vals)
    valid = rel.valid.at[start:stop].set(True)
    cand, orig = dict(rel.cand), dict(rel.orig)
    for name in rel.cand:
        cand[name] = cand[name].at[start:stop, 0].set(columns[name][start:stop])
        orig[name] = orig[name].at[start:stop].set(columns[name][start:stop])
    return (
        Relation(columns, valid, cand, dict(rel.ccount), dict(rel.ckind), orig, rel.checked),
        start,
    )


def masked_keys(values: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    """Replace masked-out entries with the sort sentinel."""
    if values.dtype == jnp.float32:
        return jnp.where(mask, values, jnp.float32(np.inf))
    return jnp.where(mask, values, SENTINEL)


def next_pow2(n: int) -> int:
    n = max(int(n), 1)
    return 1 << (n - 1).bit_length()
