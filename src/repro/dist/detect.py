"""Sharded violation detection over the key-routed shuffle (DESIGN.md §8).

The paper's general-DC detection is a partitioned theta-join over the
comparison space (§4.2): the O(n^2) pairwise matrix is split so each
partition scans independently.  Here the partitioning is the equality-atom
key: a violating pair (t1, t2) must satisfy every atom, so for any
equality atom ``t1.a == t2.a`` both rows agree on ``a`` — hash-routing
every row by its combined equality-key value (``shuffle_by_key``) puts all
of a row's potential partners on its own shard, and the existing
``dc_pairs`` role scans run locally per shard with no cross-shard pairs
lost.  The same argument shards FD detection by the lhs (groups live
whole on one shard), and — via a second routing pass keyed on the rhs —
the swapped P(lhs | rhs) grouping too.

Correctness invariants (enforced bit-exactly by tests/test_dist_detect.py):

* every row appears at most once in the routed layout, so the local scans'
  diagonal exclusion still means "never pair a row with itself";
* counts are sums and stats are min/max over a row's partner set, all of
  which lives on the row's shard — per-shard results equal the dense
  scan's row-for-row, not just in aggregate;
* rows outside both scopes are not routed at all; they get count 0 and the
  reduce identity, exactly as the dense scan gives them.

Skewed keys overflow the shuffle's per-shard capacity; the driver retries
with a doubled capacity factor until the overflow flag clears (a factor of
``n_shards`` provably cannot overflow, so the loop terminates).

``n_shards`` is a *logical* shard count: the routed leading dim.  When the
mesh has data-parallel axes whose extent divides it, the per-shard scans
run under ``shard_map`` (each device scans only its resident shards);
otherwise they run as a ``vmap`` over the logical shards on one device —
identical numerics, which is what lets the equivalence tests run on CPU.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.constraints import DC, FD, equality_key_attrs, flip_op
from repro.core.detect import DCDetectResult, FDDetectResult, _T1_REDUCE
from repro.core.relation import Relation
from repro.core.setops import group_distinct_candidates
from repro.obs.trace import NULL_TRACER
from repro.kernels import ops as kops
from repro.kernels.ref import _identity
from repro.dist.sharding import dp_axes
from repro.dist.shuffle import CAPACITY_FACTOR, shuffle_by_key


@dataclasses.dataclass
class ShardedDetectInfo:
    """What the routing actually did — consumed by launch/dryrun.py's
    pair-count report, the executor's cost model, and asserted on by the
    overflow-retry tests."""

    n_shards: int
    capacity_factor: float  # the factor that finally fit
    retries: int  # shuffles beyond the first
    routed_rows: int  # valid rows after routing
    per_shard_rows: List[int]  # routed row count per shard
    dense_pairs: int  # cap^2 — the dense scan's comparison space
    sharded_pairs: int  # sum_s rows_s^2 — what the shards scanned
    # distinct SOURCE ledger strips (DESIGN.md §11) each shard's routed rows
    # came from, when the caller passed its strip size: how a key-routed
    # shard's work maps back onto the work ledger's strip grid (the per-host
    # work partition the sharded service will consume).  None when the
    # caller did not report a strip size.
    per_shard_strips: Optional[List[int]] = None
    # launch geometry of the per-shard scans (DESIGN.md §15): the routed
    # layout compacts valid rows to a per-shard slot prefix, so each
    # shard's fused scan restricts to the occupied block range — tile
    # pairs over empty slack slots never launch.  DC path only (0 for FDs).
    tiles_launched: int = 0
    tiles_total: int = 0


def default_n_shards(mesh) -> int:
    """Logical shard count for a mesh: the data-parallel extent (1 when the
    mesh has no data axes to spread over)."""
    axes = dp_axes(mesh)
    return int(np.prod([mesh.shape[a] for a in axes])) if axes else 1


# ----------------------------------------------------------------- routing
def _transport(col: jnp.ndarray) -> jnp.ndarray:
    """View a column as int32 for payload transport (bit-exact round trip)."""
    if col.dtype == jnp.int32:
        return col
    return jax.lax.bitcast_convert_type(col.astype(jnp.float32), jnp.int32)


def _untransport(col: jnp.ndarray, dtype) -> jnp.ndarray:
    if dtype == jnp.int32:
        return col
    return jax.lax.bitcast_convert_type(col, jnp.float32).astype(dtype)


def _combine_keys(cols: Sequence[jnp.ndarray]) -> jnp.ndarray:
    """Hash-combine key columns into one int32 routing key.

    Equal value tuples MUST produce equal keys (collisions merely co-locate
    unrelated keys, which costs capacity, never correctness) — so float
    columns collapse -0.0 onto +0.0 before the bit view.
    """
    h = None
    for c in cols:
        if c.dtype != jnp.int32:
            c = c.astype(jnp.float32)
            c = jnp.where(c == 0.0, jnp.float32(0.0), c)
            ci = jax.lax.bitcast_convert_type(c, jnp.int32)
        else:
            ci = c
        h = ci if h is None else (h * jnp.int32(1_000_003)) ^ ci
    return h


def _route(
    key: jnp.ndarray,  # (cap,) int32
    payload_cols: Sequence[jnp.ndarray],  # (cap,) each, int32-transported
    valid: jnp.ndarray,  # (cap,) bool
    mesh,
    n_shards: int,
    capacity_factor: float,
    tracer=None,
):
    """Shuffle rows by key with overflow-retry.  Returns (result, factor,
    retries) where ``result`` has leading dims (n_shards, cap_routed).
    ``tracer`` spans the whole routing (``dist.shuffle``) and marks each
    overflow retry with an instant (DESIGN.md §13)."""
    tracer = tracer if tracer is not None else NULL_TRACER
    cap = key.shape[0]
    n_local = -(-cap // n_shards)
    padded = n_shards * n_local
    # factor >= 1 keeps the routed slot space at least ``padded`` wide, so
    # _unroute's scatter target covers every source index (and the empty-
    # slot sentinel ``padded`` stays filtered by the valid mask, never OOB
    # into a smaller buffer).
    capacity_factor = max(capacity_factor, 1.0)

    def shard_view(x, fill=0):
        return jnp.pad(x, [(0, padded - cap)] + [(0, 0)] * (x.ndim - 1),
                       constant_values=fill).reshape((n_shards, n_local) + x.shape[1:])

    keys2 = shard_view(key)
    payload2 = shard_view(jnp.stack(payload_cols, axis=-1))
    valid2 = shard_view(valid, fill=False)

    factor, retries = capacity_factor, 0
    with tracer.span("dist.shuffle", n_shards=n_shards, rows=int(cap)) as sp:
        while True:
            res = shuffle_by_key(
                keys2, payload2, valid2, mesh, capacity_factor=factor
            )
            if not bool(np.asarray(res.overflow)) or factor >= n_shards:
                sp.set(retries=retries, capacity_factor=float(factor))
                return res, factor, retries
            factor = min(factor * 2.0, float(n_shards))
            retries += 1
            tracer.instant(
                "dist.shuffle_overflow_retry", capacity_factor=float(factor)
            )


@functools.lru_cache(maxsize=None)
def _per_shard_fn(fn, mesh, n_shards: int):
    """Jitted runner for ``fn`` (one logical shard -> pytree of per-row
    outputs) over the leading shard dim: ``shard_map`` over the data axes
    when they divide ``n_shards`` (each device vmaps its resident shards),
    plain ``vmap`` otherwise.

    Cached so repeated detect calls (the executor's incremental steps)
    reuse one jit cache instead of retracing — ``fn`` must come from a
    cached builder (``_dc_local_scan`` / ``_fd_local_group``) so its
    identity is stable across calls."""
    batched = jax.vmap(fn)
    axes = dp_axes(mesh)
    extent = int(np.prod([mesh.shape[a] for a in axes])) if axes else 1
    if extent > 1 and n_shards % extent == 0:
        spec = P(axes if len(axes) > 1 else axes[0])
        sharded = jax.shard_map(
            batched,
            mesh=mesh,
            # one positional argument; the bare spec is a pytree prefix
            # applying to every leaf of the args pytree
            in_specs=(spec,),
            out_specs=spec,
            check_vma=False,
        )
        return jax.jit(sharded)
    return jax.jit(batched)


def _per_shard(fn, mesh, n_shards: int, args, tracer=None, span_attrs=None):
    tracer = tracer if tracer is not None else NULL_TRACER
    with tracer.span(
        "dist.shard_scan", n_shards=n_shards, **(span_attrs or {})
    ), mesh:
        return _per_shard_fn(fn, mesh, n_shards)(args)


def _unroute(routed: jnp.ndarray, src: jnp.ndarray, valid: jnp.ndarray,
             cap: int, init):
    """Scatter per-slot results back to original row order.  ``init`` fills
    rows that were never routed (the dense scan's value for them)."""
    flat = routed.reshape((-1,) + routed.shape[2:])
    idx = jnp.where(valid.reshape(-1), src.reshape(-1), src.size)
    out = jnp.full((src.size,) + flat.shape[1:], init, flat.dtype)
    return out.at[idx].set(flat, mode="drop")[:cap]


def _info(res, n_shards, factor, retries, cap,
          strip_rows: Optional[int] = None) -> ShardedDetectInfo:
    per_shard = np.asarray(jnp.sum(res.valid.astype(jnp.int32), axis=1))
    per_shard_strips = None
    if strip_rows:
        # distinct source strips per shard: the routed slots' original row
        # indices (res.src), bucketed by the caller's ledger strip grid
        src = np.asarray(res.src)
        valid = np.asarray(res.valid)
        per_shard_strips = [
            len(np.unique(src[s][valid[s]] // int(strip_rows)))
            for s in range(src.shape[0])
        ]
    return ShardedDetectInfo(
        n_shards=n_shards,
        capacity_factor=factor,
        retries=retries,
        routed_rows=int(per_shard.sum()),
        per_shard_rows=[int(c) for c in per_shard],
        dense_pairs=int(cap) ** 2,
        sharded_pairs=int((per_shard.astype(np.int64) ** 2).sum()),
        per_shard_strips=per_shard_strips,
    )


# ---------------------------------------------------------------- DC path
@functools.lru_cache(maxsize=None)
def _dc_local_scan(ops: Tuple[str, ...], flipped: Tuple[str, ...],
                   t1_red: Tuple[str, ...], t2_red: Tuple[str, ...],
                   block: int, hi: int):
    """One logical shard's FUSED both-role scan (DESIGN.md §15); cached so
    its identity (and thus the jit cache in ``_per_shard_fn``) is stable
    across calls.  ``hi`` is the occupied block range of the routed slot
    prefix — the shuffle compacts valid rows to slots ``[0, count_s)``, so
    restricting every shard to blocks ``[0, hi)`` (``hi`` from the MAX
    occupancy, a static host value under vmap/shard_map) launches no tile
    pair over pure capacity slack while staying bit-identical."""

    def local_scan(args):
        lc, rc, lrs, lcs = args
        res = kops.dc_pair_scan(
            lc, rc, ops, flipped, lrs, lcs, t1_red, t2_red, block=block,
            row_blocks=(0, hi), col_blocks=(0, hi),
        )
        return (res.t1_count, res.t2_count, res.t1_stat, res.t2_stat)

    return local_scan


def detect_dc_sharded_info(
    rel: Relation,
    dc: DC,
    row_scope: jnp.ndarray,
    col_scope: jnp.ndarray,
    mesh,
    n_shards: Optional[int] = None,
    block: int = 256,
    capacity_factor: float = CAPACITY_FACTOR,
    strip_rows: Optional[int] = None,
    tracer=None,
) -> Tuple[DCDetectResult, ShardedDetectInfo]:
    """Sharded ``detect_dc``: bit-identical to the dense scan for DCs with
    at least one same-attribute equality atom.  Also returns routing info
    (``strip_rows`` adds the per-shard source-strip coverage report,
    DESIGN.md §11)."""
    key_attrs = equality_key_attrs(dc)
    if not key_attrs:
        raise ValueError(
            f"DC {dc.name!r} has no same-attribute equality atom — "
            "sharded detection cannot route it; use the dense detect_dc"
        )
    n_shards = n_shards or default_n_shards(mesh)
    if n_shards < 2:
        raise ValueError("n_shards must be >= 2 (use detect_dc on one shard)")

    cap = rel.capacity
    row_scope = row_scope & rel.valid
    col_scope = col_scope & rel.valid
    participate = row_scope | col_scope

    # payload: every atom column (deduped) + the two scope masks
    attrs: List[str] = []
    for a in dc.atoms:
        for name in (a.left, a.right):
            if name not in attrs:
                attrs.append(name)
    dtypes = {name: rel.columns[name].dtype for name in attrs}
    payload_cols = [_transport(rel.columns[name]) for name in attrs]
    payload_cols.append(row_scope.astype(jnp.int32))
    payload_cols.append(col_scope.astype(jnp.int32))

    key = _combine_keys([rel.columns[a] for a in key_attrs])
    res, factor, retries = _route(
        key, payload_cols, participate, mesh, n_shards, capacity_factor,
        tracer=tracer,
    )

    cols = {
        name: _untransport(res.payload[..., i], dtypes[name])
        for i, name in enumerate(attrs)
    }
    rs = (res.payload[..., -2] > 0) & res.valid
    cs = (res.payload[..., -1] > 0) & res.valid

    ops = tuple(a.op for a in dc.atoms)
    flipped = tuple(flip_op(op) for op in ops)
    t1_red = tuple(_T1_REDUCE[op] for op in ops)
    t2_red = tuple(_T1_REDUCE[op] for op in flipped)
    l_names = [a.left for a in dc.atoms]
    r_names = [a.right for a in dc.atoms]

    args = (
        tuple(cols[n] for n in l_names),
        tuple(cols[n] for n in r_names),
        rs,
        cs,
    )

    # Occupied block range of the routed slot prefix (DESIGN.md §15): the
    # shuffle compacts each shard's valid rows to slots [0, count_s), so the
    # fused scan restricts to blocks [0, hi) with hi sized by the fullest
    # shard — a static host value, shared by all shards under vmap.
    cap_routed = int(res.valid.shape[-1])
    nb_local = max(-(-cap_routed // block), 1)
    occupancy = int(np.asarray(jnp.sum(res.valid.astype(jnp.int32), axis=1)).max())
    hi = min(nb_local, max(-(-occupancy // block), 1))
    tiles_launched = n_shards * hi * hi
    tiles_total = n_shards * nb_local * nb_local

    t1c, t2c, t1s, t2s = _per_shard(
        _dc_local_scan(ops, flipped, t1_red, t2_red, block, hi), mesh,
        n_shards, args, tracer=tracer,
        span_attrs={
            "tiles_launched": tiles_launched,
            "tiles_skipped": tiles_total - tiles_launched,
        },
    )

    t1_count = _unroute(t1c, res.src, res.valid, cap, jnp.int32(0))
    t2_count = _unroute(t2c, res.src, res.valid, cap, jnp.int32(0))
    t1_stat = tuple(
        _unroute(s, res.src, res.valid, cap, _identity(dtypes[n], red))
        for s, n, red in zip(t1s, r_names, t1_red)
    )
    t2_stat = tuple(
        _unroute(s, res.src, res.valid, cap, _identity(dtypes[n], red))
        for s, n, red in zip(t2s, l_names, t2_red)
    )
    per_shard_bytes = kops._scan_bytes(
        kops.distinct_columns(args[0], args[1])[0], len(ops), block, hi, hi,
        nb_local,
    )
    det = DCDetectResult(
        t1_count, t2_count, t1_stat, t2_stat,
        tiles_launched=tiles_launched, tiles_total=tiles_total,
        bytes_moved=n_shards * per_shard_bytes,
    )
    info = _info(res, n_shards, factor, retries, cap, strip_rows=strip_rows)
    info.tiles_launched = tiles_launched
    info.tiles_total = tiles_total
    return det, info


def detect_dc_sharded(
    rel: Relation,
    dc: DC,
    row_scope: jnp.ndarray,
    col_scope: jnp.ndarray,
    mesh,
    n_shards: Optional[int] = None,
    block: int = 256,
    capacity_factor: float = CAPACITY_FACTOR,
) -> DCDetectResult:
    det, _ = detect_dc_sharded_info(
        rel, dc, row_scope, col_scope, mesh,
        n_shards=n_shards, block=block, capacity_factor=capacity_factor,
    )
    return det


# ---------------------------------------------------------------- FD path
@functools.lru_cache(maxsize=None)
def _fd_local_group(k: int):
    def local(args):
        ks, v, m = args
        return group_distinct_candidates(ks, v, m, k)

    return local


def _grouped_candidates_sharded(
    key_cols: Sequence[jnp.ndarray],
    value_col: jnp.ndarray,
    scope: jnp.ndarray,
    k: int,
    mesh,
    n_shards: int,
    capacity_factor: float,
    strip_rows: Optional[int] = None,
    tracer=None,
):
    """Sharded ``group_distinct_candidates``: route rows by the group key so
    each group lives whole on one shard, group locally, un-route."""
    cap = value_col.shape[0]
    dtypes = [c.dtype for c in key_cols] + [value_col.dtype]
    payload = [_transport(c) for c in key_cols] + [_transport(value_col)]
    res, factor, retries = _route(
        _combine_keys(key_cols), payload, scope, mesh, n_shards,
        capacity_factor, tracer=tracer,
    )
    n_keys = len(key_cols)
    keys_r = [_untransport(res.payload[..., i], dtypes[i]) for i in range(n_keys)]
    value_r = _untransport(res.payload[..., n_keys], dtypes[n_keys])

    cand, count, violated, overflow = _per_shard(
        _fd_local_group(k), mesh, n_shards, (tuple(keys_r), value_r, res.valid),
        tracer=tracer,
    )
    return (
        _unroute(cand, res.src, res.valid, cap, jnp.zeros((), value_col.dtype)),
        _unroute(count, res.src, res.valid, cap, jnp.float32(0.0)),
        _unroute(violated, res.src, res.valid, cap, False),
        jnp.any(overflow),
        _info(res, n_shards, factor, retries, cap, strip_rows=strip_rows),
    )


def detect_fd_sharded_info(
    rel: Relation,
    fd: FD,
    scope: jnp.ndarray,
    mesh,
    k: Optional[int] = None,
    n_shards: Optional[int] = None,
    capacity_factor: float = CAPACITY_FACTOR,
    strip_rows: Optional[int] = None,
    tracer=None,
) -> Tuple[FDDetectResult, ShardedDetectInfo]:
    """Sharded ``detect_fd``: lhs groups route whole onto one shard; the
    swapped P(lhs | rhs) grouping (single-attribute lhs) uses a second
    routing pass keyed on the rhs.  Bit-identical to the dense path.
    ``strip_rows`` adds the per-shard strip-coverage report (§11)."""
    k = k or max(rel.k, 2)
    n_shards = n_shards or default_n_shards(mesh)
    if n_shards < 2:
        raise ValueError("n_shards must be >= 2 (use detect_fd on one shard)")
    scope = scope & rel.valid
    lhs_cols = [rel.columns[a] for a in fd.lhs]
    rhs_col = rel.columns[fd.rhs]

    rhs_cand, rhs_count, violated, overflow, info = _grouped_candidates_sharded(
        lhs_cols, rhs_col, scope, k, mesh, n_shards, capacity_factor,
        strip_rows=strip_rows, tracer=tracer,
    )
    lhs_cand = lhs_count = None
    if len(fd.lhs) == 1:
        lhs_cand, lhs_count, _, ovf2, _ = _grouped_candidates_sharded(
            [rhs_col], lhs_cols[0], scope, k, mesh, n_shards, capacity_factor,
            tracer=tracer,
        )
        overflow = overflow | ovf2
    det = FDDetectResult(violated, rhs_cand, rhs_count, lhs_cand, lhs_count, overflow)
    return det, info


def detect_fd_sharded(
    rel: Relation,
    fd: FD,
    scope: jnp.ndarray,
    mesh,
    k: Optional[int] = None,
    n_shards: Optional[int] = None,
    capacity_factor: float = CAPACITY_FACTOR,
) -> FDDetectResult:
    det, _ = detect_fd_sharded_info(
        rel, fd, scope, mesh, k=k, n_shards=n_shards,
        capacity_factor=capacity_factor,
    )
    return det


# ------------------------------------------------------------- reporting
def pair_count_report(n_rows: int, n_shards: int,
                      capacity_factor: float = CAPACITY_FACTOR) -> dict:
    """Capacity-planning arithmetic for the dry-run (DESIGN.md §8): dense
    vs sharded comparison-space size under uniform keys.  The sharded scan
    touches ``n_shards * (n_rows / n_shards)^2`` pairs — an ``n_shards``-x
    saving — at the cost of one all-to-all of the routed payload."""
    if n_shards < 1:
        raise ValueError("n_shards must be >= 1")
    per_shard = -(-n_rows // n_shards)
    dense = int(n_rows) ** 2
    sharded = n_shards * per_shard**2
    return {
        "n_rows": int(n_rows),
        "n_shards": int(n_shards),
        "dense_pairs": dense,
        "sharded_pairs_uniform": sharded,
        "pair_savings_x": (dense / sharded) if sharded else 1.0,
        "per_shard_capacity_rows": int(per_shard * capacity_factor),
    }
