"""Pallas TPU kernel: the fused blocked theta-join scan for DC violation
detection.

The paper's DC error detection partitions the cartesian-product matrix into
``p`` partitions and prunes partitions whose boundary ranges cannot produce a
violation (§4.2, Fig. 3/4).  On TPU this becomes a grid of (block, block)
VMEM tiles over the comparison matrix:

* **block-sparse worklist grid** (DESIGN.md §15): the launch is a 1-D grid
  over a host-built worklist of *active* tile pairs — the cross product of
  the active row-block ids and active col-block ids.  The two id arrays are
  scalar-prefetched (``pltpu.PrefetchScalarGridSpec``) so the BlockSpec
  index maps read ``rid[g // ncols]`` / ``cid[g % ncols]`` before the tile's
  DMAs are issued; checked x checked tile pairs are never launched and never
  move bytes.  A contiguous ``(lo, hi)`` range and the dense grid are just
  worklists that happen to be ``arange``s — one code path for all of them;
* per-tile **bound pruning**: per-block min/max of each atom column are
  precomputed (scope-masked), held whole in SMEM and indexed by the same
  prefetched ids; a tile whose bounds make some atom unsatisfiable
  everywhere skips its body with ``@pl.when`` — the paper's partition
  pruning, at tile granularity, on top of the worklist sparsity;
* the VPU evaluates the atom predicates for all block x block pairs of the
  tile at once (the Spark version loops over JVM tuples);
* outputs are row-indexed (violation count + per-atom extremal partner
  value, which is the bound of the candidate *range* fix, Example 4) and
  accumulate across the worklist's column-innermost order — each output
  block is revisited consecutively, as the TPU grid requires.

Both tuple roles (t1 with the atoms as written, t2 with them flipped — see
core/detect.py) run in one launch over one worklist, loading each distinct
atom column once per tile instead of twice (DESIGN.md §15's fusion
contract).

Layout (DESIGN.md §7).  Mosaic compiles only lane-dense, natively tiled
blocks, so every column and scope travels as ``(nb, 1, block)``: a tile is
one ``(1, block)`` row of lanes, DMA'd in its own (possibly narrow,
encoded) dtype and widened to 32 bits in VMEM, which is exact.  A pairwise
predicate needs the row values down the sublanes, so the row tile is
transposed once per worklist row — when its column sweep starts — into a
lane-replicated ``(block, 128)`` scratch; every tile of the sweep then
compares those against the lane-oriented column tile with no per-tile
relayout.  Counts and stats accumulate lane-partial in ``(block, 128)``
scratch (folding a tile's partner lanes into 128 is elementwise), and the
last tile of the sweep reduces them into lane-dense ``(1, block)`` output
blocks.
"""

from __future__ import annotations

import functools
import math
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128

_COMBINE = {"sum": jnp.add, "min": jnp.minimum, "max": jnp.maximum}
_REDUCE = {"sum": jnp.sum, "min": jnp.min, "max": jnp.max}


def _ident_value(dtype, reduce):
    """Reduce identity of ``dtype`` as a Python scalar (int8 atoms carry
    int8 identities — the host-side stat decode maps them back, DESIGN.md
    §15)."""
    if jnp.issubdtype(dtype, jnp.integer):
        info = np.iinfo(dtype)
        return int(info.max if reduce == "min" else info.min)
    return float(np.inf if reduce == "min" else -np.inf)


def _ident(dtype, reduce):
    """Reduce identity in the array's OWN dtype."""
    return jnp.array(_ident_value(dtype, reduce), dtype)


def _wide(dtype):
    """The 32-bit dtype a (possibly encoded) column is compared in.  Every
    encoding widens exactly (int8/int16 -> int32, bf16 -> f32), so
    predicate outcomes and extrema are those of the narrow values."""
    return jnp.int32 if jnp.issubdtype(dtype, jnp.integer) else jnp.float32


def _tile_possible(op, lmin, lmax, rmin, rmax):
    """Can ``l op r`` hold for ANY (l, r) with l in [lmin,lmax], r in [rmin,rmax]?"""
    if op == "<":
        return lmin < rmax
    if op == "<=":
        return lmin <= rmax
    if op == ">":
        return lmax > rmin
    if op == ">=":
        return lmax >= rmin
    if op == "==":
        return (lmin <= rmax) & (rmin <= lmax)
    if op == "!=":  # only impossible when both ranges are the same singleton
        return ~((lmin == lmax) & (rmin == rmax) & (lmin == rmin))
    raise ValueError(op)


def _cmp(op, a, b):
    return {
        "==": lambda: a == b,
        "!=": lambda: a != b,
        "<": lambda: a < b,
        "<=": lambda: a <= b,
        ">": lambda: a > b,
        ">=": lambda: a >= b,
    }[op]()


def resolve_block_ids(
    nb: int,
    blocks: Optional[Tuple[int, int]] = None,
    block_ids=None,
) -> np.ndarray:
    """Normalize a grid restriction into the sorted, deduped worklist-side
    id array: explicit ``block_ids`` win, else the contiguous ``(lo, hi)``
    range, else the full grid.  Every launch path funnels through this, so
    dense and contiguous-strip scans are just worklists that happen to be
    ``arange``s."""
    if block_ids is not None:
        ids = np.unique(np.asarray(block_ids, dtype=np.int32).ravel())
        if ids.size and (ids[0] < 0 or ids[-1] >= nb):
            raise ValueError(f"block ids {ids!r} outside grid [0, {nb})")
        return ids
    if blocks is None:
        return np.arange(nb, dtype=np.int32)
    lo, hi = blocks
    if not (0 <= lo < hi <= nb):
        raise ValueError(f"blocks {blocks!r} outside grid [0, {nb})")
    return np.arange(lo, hi, dtype=np.int32)


def _empty_role_outputs(n, r_cols, reduces):
    """What a scan with an empty worklist returns: count 0 and the reduce
    identity everywhere — exactly the full grid's value for scoped-out rows."""
    count = jnp.zeros((n,), jnp.int32)
    stats = [
        jnp.full((n,), _ident(c.dtype, red), c.dtype)
        for c, red in zip(r_cols, reduces)
    ]
    return count, stats


def _stitch(outs, row_ids, block, npad, n, dtypes, reduces):
    """Scatter worklist-compact outputs back to full row width: rows in
    unlaunched blocks take count 0 / the reduce identity (what the dense
    grid gives scoped-out rows).  Stats arrive widened and leave in
    ``dtypes`` (the columns' own); the cast is exact."""
    nb = npad // block
    if row_ids.size == nb:  # dense row coverage: outputs are already in order
        return outs[0][:n], [
            s[:n].astype(dt) for s, dt in zip(outs[1:], dtypes)
        ]
    ridx = jnp.asarray(
        (row_ids[:, None] * block + np.arange(block)[None, :]).reshape(-1)
    )
    count = jnp.zeros((npad,), jnp.int32).at[ridx].set(outs[0])[:n]
    stats = [
        jnp.full((npad,), _ident_value(dt, red), s.dtype)
        .at[ridx].set(s)[:n].astype(dt)
        for s, dt, red in zip(outs[1:], dtypes, reduces)
    ]
    return count, stats


def _block_bounds(vals, scope, reduce, nb, block):
    """Scope-masked per-block bounds (identity outside scope keeps the
    ``@pl.when`` pruning sound), widened to the 32-bit SMEM word."""
    ident = _ident(vals.dtype, reduce)
    masked = jnp.where(scope > 0, vals, ident)
    resh = masked.reshape(nb, block)
    red = jnp.min(resh, axis=1) if reduce == "min" else jnp.max(resh, axis=1)
    return red.astype(_wide(vals.dtype))


def _fold_lanes(x, reduce, lanes):
    """(rows, k * lanes) -> (rows, lanes): combine the lane groups
    elementwise — the cross-lane part of the reduction waits for the end of
    the row's sweep."""
    parts = [x[:, k * lanes:(k + 1) * lanes] for k in range(x.shape[1] // lanes)]
    return functools.reduce(_COMBINE[reduce], parts)


# ---------------------------------------------------------- fused-role kernel
def _pair_kernel(
    ops: Tuple[str, ...],
    flipped: Tuple[str, ...],
    t1_reduces: Tuple[str, ...],
    t2_reduces: Tuple[str, ...],
    l_idx: Tuple[int, ...],
    r_idx: Tuple[int, ...],
    t1_idents: Tuple[float, ...],
    t2_idents: Tuple[float, ...],
    n_distinct: int,
    block: int,
    lanes: int,
    ncols: int,
    *refs,
):
    """Both tuple roles in one tile visit (DESIGN.md §15 fusion contract):
    role t1 evaluates the atoms as written over (row, col), role t2 the
    flipped atoms — each distinct atom column's row and col tiles are
    loaded ONCE and serve both roles."""
    n_atoms = len(ops)
    it = iter(refs)

    def take(count):
        return tuple(next(it) for _ in range(count))

    (rid_ref,) = take(1)
    (cid_ref,) = take(1)
    rowv = take(n_distinct)  # distinct columns, row-side (1, block) tiles
    (rs,) = take(1)
    colv = take(n_distinct)  # distinct columns, col-side (1, block) tiles
    (cs,) = take(1)
    row_min = take(n_distinct)  # whole (nb,) SMEM bounds under the ROW scope
    row_max = take(n_distinct)
    col_min = take(n_distinct)  # ... under the COL scope
    col_max = take(n_distinct)
    (t1_count_ref,) = take(1)  # (1, block) lane-dense outputs
    (t2_count_ref,) = take(1)
    t1_stat_refs = take(n_atoms)
    t2_stat_refs = take(n_atoms)
    row_t = take(n_distinct)  # (block, lanes) scratch: row values per sublane
    (rs_t,) = take(1)
    (t1_acc,) = take(1)  # (block, lanes) lane-partial accumulators
    (t2_acc,) = take(1)
    t1_part = take(n_atoms)
    t2_part = take(n_atoms)

    g = pl.program_id(0)
    r = rid_ref[g // ncols]
    c = cid_ref[g % ncols]

    def to_sublanes(tile):
        """(1, block) -> (block, lanes) with out[i, :] == tile[0, i]."""
        return jnp.broadcast_to(tile, (lanes, block)).T

    @pl.when(g % ncols == 0)
    def _start_row():
        for d in range(n_distinct):
            row_t[d][...] = to_sublanes(rowv[d][...].astype(row_t[d].dtype))
        rs_t[...] = to_sublanes(rs[...])
        t1_acc[...] = jnp.zeros_like(t1_acc)
        t2_acc[...] = jnp.zeros_like(t2_acc)
        for part, ident in zip(t1_part + t2_part, t1_idents + t2_idents):
            part[...] = jnp.full(part.shape, ident, part.dtype)

    possible1 = jnp.bool_(True)
    possible2 = jnp.bool_(True)
    for a, (op, fop) in enumerate(zip(ops, flipped)):
        li, ri = l_idx[a], r_idx[a]
        possible1 = possible1 & _tile_possible(
            op, row_min[li][r], row_max[li][r], col_min[ri][c], col_max[ri][c]
        )
        possible2 = possible2 & _tile_possible(
            fop, row_min[ri][r], row_max[ri][r], col_min[li][c], col_max[li][c]
        )

    def rows(ref):  # sublane-oriented row values, broadcast over the partners
        return jnp.concatenate([ref[...]] * (block // lanes), axis=1)

    def cols(d):  # lane-oriented partner values, broadcast over the rows
        tile = colv[d][...].astype(row_t[d].dtype)
        return jnp.broadcast_to(tile, (block, block))

    def base():
        row_ids = r * block + jax.lax.broadcasted_iota(
            jnp.int32, (block, block), 0
        )
        col_ids = c * block + jax.lax.broadcasted_iota(
            jnp.int32, (block, block), 1
        )
        in_scope = (rows(rs_t) > 0) & (jnp.broadcast_to(cs[...], (block, block)) > 0)
        return in_scope & (row_ids != col_ids)

    def accumulate(viol, acc, parts, stat_src, reduces, idents):
        acc[...] += _fold_lanes(viol.astype(jnp.int32), "sum", lanes)
        for a, red in enumerate(reduces):
            vals = jnp.where(viol, cols(stat_src[a]), idents[a])
            parts[a][...] = _COMBINE[red](
                parts[a][...], _fold_lanes(vals, red, lanes)
            )

    @pl.when(possible1)
    def _role_t1():
        viol = base()
        for a, op in enumerate(ops):
            viol = viol & _cmp(op, rows(row_t[l_idx[a]]), cols(r_idx[a]))
        accumulate(viol, t1_acc, t1_part, r_idx, t1_reduces, t1_idents)

    @pl.when(possible2)
    def _role_t2():
        viol = base()
        for a, fop in enumerate(flipped):
            viol = viol & _cmp(fop, rows(row_t[r_idx[a]]), cols(l_idx[a]))
        accumulate(viol, t2_acc, t2_part, l_idx, t2_reduces, t2_idents)

    @pl.when(g % ncols == ncols - 1)
    def _finish_row():  # (block, lanes) partials -> lane-dense (1, block)
        for out, part, red in zip(
            (t1_count_ref, t2_count_ref) + t1_stat_refs + t2_stat_refs,
            (t1_acc, t2_acc) + t1_part + t2_part,
            ("sum", "sum") + t1_reduces + t2_reduces,
        ):
            out[...] = _REDUCE[red](part[...].T, axis=0, keepdims=True)


def distinct_columns(
    l_cols: Sequence[jnp.ndarray], r_cols: Sequence[jnp.ndarray]
) -> Tuple[List[jnp.ndarray], Tuple[int, ...], Tuple[int, ...]]:
    """Dedup the atom columns by array identity: same-attribute atoms (the
    common DC shape) load one tile per side for both roles.  Returns the
    distinct column list plus per-atom indices into it."""
    distinct: List[jnp.ndarray] = []
    index: dict = {}

    def at(col):
        key = id(col)
        if key not in index:
            index[key] = len(distinct)
            distinct.append(col)
        return index[key]

    l_idx = tuple(at(c) for c in l_cols)
    r_idx = tuple(at(c) for c in r_cols)
    return distinct, l_idx, r_idx


def dc_pair_scan_pallas(
    l_cols: Sequence[jnp.ndarray],
    r_cols: Sequence[jnp.ndarray],
    ops: Sequence[str],
    flipped: Sequence[str],
    row_scope: jnp.ndarray,
    col_scope: jnp.ndarray,
    t1_reduces: Sequence[str],
    t2_reduces: Sequence[str],
    block: int = 256,
    interpret: bool = False,
    row_blocks: Optional[Tuple[int, int]] = None,
    col_blocks: Optional[Tuple[int, int]] = None,
    row_block_ids=None,
    col_block_ids=None,
):
    """Fused BOTH-role scan: one worklist launch computes the t1 detection
    (atoms as written) and the t2 detection (``flipped`` atoms) over the
    same tiles (DESIGN.md §15).

    Shapes are padded to a multiple of ``block``; padded rows are scoped
    out.  ``row_block_ids`` / ``col_block_ids`` are the block-sparse
    worklist entry: only the cross product of the given row and col block
    ids is launched.  ``row_blocks`` / ``col_blocks`` are the contiguous
    ``(lo, hi)`` sugar (the §11 strip entry and §12 ingest-delta entry);
    they resolve to ``arange`` worklists.  Rows outside the launched blocks
    get count 0 and the reduce identity, exactly as if they were scoped
    out.  Compiled for the chip, ``block`` must be a multiple of 128 (one
    lane row per tile).

    Returns ``(t1_count, t1_stats, t2_count, t2_stats)``, each full row
    width, bit-identical to ``ref.dc_pair_scan``."""
    if not interpret and block % LANES:
        raise ValueError(f"block {block} must be a multiple of {LANES} on TPU")
    n_atoms = len(ops)
    n = l_cols[0].shape[0]
    nb = -(-n // block)
    npad = nb * block
    rid = resolve_block_ids(nb, row_blocks, row_block_ids)
    cid = resolve_block_ids(nb, col_blocks, col_block_ids)
    if rid.size == 0 or cid.size == 0:
        t1c, t1s = _empty_role_outputs(n, r_cols, t1_reduces)
        t2c, t2s = _empty_role_outputs(n, l_cols, t2_reduces)
        return t1c, t1s, t2c, t2s
    nrows, ncols = rid.size, cid.size
    lanes = math.gcd(block, LANES)

    distinct, l_idx, r_idx = distinct_columns(l_cols, r_cols)
    n_distinct = len(distinct)

    def pad1(x):
        return jnp.pad(x, (0, npad - n))

    def tiles(x):  # lane-dense tile rows
        return x.reshape(nb, 1, block)

    rs = pad1(row_scope).astype(jnp.int32)
    cs = pad1(col_scope).astype(jnp.int32)
    dp = [pad1(c) for c in distinct]
    row_min = [_block_bounds(c, rs, "min", nb, block) for c in dp]
    row_max = [_block_bounds(c, rs, "max", nb, block) for c in dp]
    col_min = [_block_bounds(c, cs, "min", nb, block) for c in dp]
    col_max = [_block_bounds(c, cs, "max", nb, block) for c in dp]

    tile_shape = (pl.Squeezed(), 1, block)
    row_s = pl.BlockSpec(tile_shape, lambda g, rid, cid: (rid[g // ncols], 0, 0))
    col_s = pl.BlockSpec(tile_shape, lambda g, rid, cid: (cid[g % ncols], 0, 0))
    bound_s = pl.BlockSpec(memory_space=pltpu.SMEM)
    out_s = pl.BlockSpec(tile_shape, lambda g, rid, cid: (g // ncols, 0, 0))
    in_specs = (
        [row_s] * (n_distinct + 1) + [col_s] * (n_distinct + 1)
        + [bound_s] * 4 * n_distinct
    )
    # stats accumulate and leave the kernel in the widened dtype; _stitch
    # casts them back to the column's own dtype
    stat_dtypes = [c.dtype for c in r_cols] + [c.dtype for c in l_cols]
    out_shape = [jax.ShapeDtypeStruct((nrows, 1, block), jnp.int32)] * 2 + [
        jax.ShapeDtypeStruct((nrows, 1, block), _wide(dt)) for dt in stat_dtypes
    ]
    scratch = (
        [pltpu.VMEM((block, lanes), _wide(c.dtype)) for c in distinct]
        + [pltpu.VMEM((block, lanes), jnp.int32)] * 3
        + [pltpu.VMEM((block, lanes), _wide(dt)) for dt in stat_dtypes]
    )
    idents = tuple(
        _ident_value(dt, red)
        for dt, red in zip(stat_dtypes, tuple(t1_reduces) + tuple(t2_reduces))
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(nrows * ncols,),
        in_specs=in_specs,
        out_specs=[out_s] * (2 + 2 * n_atoms),
        scratch_shapes=scratch,
    )
    kernel = functools.partial(
        _pair_kernel, tuple(ops), tuple(flipped), tuple(t1_reduces),
        tuple(t2_reduces), l_idx, r_idx, idents[:n_atoms], idents[n_atoms:],
        n_distinct, block, lanes, ncols,
    )
    outs = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
    )(
        jnp.asarray(rid), jnp.asarray(cid),
        *[tiles(c) for c in dp], tiles(rs), *[tiles(c) for c in dp], tiles(cs),
        *row_min, *row_max, *col_min, *col_max,
    )
    flat = [o.reshape(-1) for o in outs]
    t1c, t1s = _stitch(
        [flat[0]] + flat[2:2 + n_atoms], rid, block, npad, n,
        stat_dtypes[:n_atoms], t1_reduces,
    )
    t2c, t2s = _stitch(
        [flat[1]] + flat[2 + n_atoms:], rid, block, npad, n,
        stat_dtypes[n_atoms:], t2_reduces,
    )
    return t1c, t1s, t2c, t2s
