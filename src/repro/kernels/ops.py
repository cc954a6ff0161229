"""Jit'd dispatch wrappers for the Pallas kernels.

Selection policy (``force`` overrides):

* on TPU -> compiled Pallas kernels;
* elsewhere -> the pure-jnp oracles from ``ref.py`` (vectorized, fast on CPU).
  Interpret-mode Pallas execution is reserved for the kernel-correctness
  tests (``force="interpret"``) because it runs the kernel body per grid step
  in Python — correct but orders of magnitude slower than the oracle.
"""

from __future__ import annotations

from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref
from repro.kernels.dc_pairs import (
    dc_pair_scan_pallas,
    distinct_columns,
    resolve_block_ids,
)
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.semijoin import semijoin_pallas


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _mode(force: str | None) -> str:
    if force is not None:
        return force
    return "pallas" if on_tpu() else "ref"


class TileStats(NamedTuple):
    """Launch geometry + modeled HBM traffic of one DC scan (DESIGN.md §15).

    ``bytes_moved`` is computed from the launch geometry and the ACTUAL
    operand dtypes (so compressed encodings show up as fewer bytes) — a
    deterministic model of the kernel's DMA traffic (``_scan_bytes``), not
    a hardware counter, which keeps the CI gates reproducible on any
    backend.
    """

    launched: int  # tile pairs actually launched (the worklist size)
    total: int  # tile pairs a dense scan would launch (nb x nb)
    bytes_moved: int  # modeled bytes DMA'd by the launch


def _scan_bytes(
    distinct: Sequence[jnp.ndarray],
    n_atoms: int,
    block: int,
    nrows: int,
    ncols: int,
    nb: int,
) -> int:
    """Modeled HBM traffic of one fused launch over an ``nrows x ncols``
    worklist (DESIGN.md §15).  Each DISTINCT atom column moves one tile per
    side in its own dtype (the fusion contract — shared columns are not
    re-fetched per role), and each side's scope moves an int32 tile.  Col
    tiles change at every grid step; row tiles and the lane-dense outputs
    (2 counts + both roles' stats, widened to 32 bits) change once per
    worklist row; the whole per-block bound arrays (4 per distinct column,
    32-bit) are copied into SMEM once per launch."""
    side = sum(block * c.dtype.itemsize for c in distinct) + block * 4
    out = (2 + 2 * n_atoms) * block * 4
    bounds = 4 * len(distinct) * nb * 4
    return nrows * ncols * side + nrows * (side + out) + bounds


class DCPairScanResult(NamedTuple):
    t1_count: jnp.ndarray
    t1_stat: Tuple[jnp.ndarray, ...]
    t2_count: jnp.ndarray
    t2_stat: Tuple[jnp.ndarray, ...]
    tiles: TileStats


def dc_pair_scan(
    l_cols: Sequence[jnp.ndarray],
    r_cols: Sequence[jnp.ndarray],
    ops: Sequence[str],
    flipped: Sequence[str],
    row_scope: jnp.ndarray,
    col_scope: jnp.ndarray,
    t1_reduces: Sequence[str],
    t2_reduces: Sequence[str],
    block: int = 256,
    force: str | None = None,
    row_blocks: Tuple[int, int] | None = None,
    col_blocks: Tuple[int, int] | None = None,
    row_block_ids=None,
    col_block_ids=None,
) -> DCPairScanResult:
    """Fused BOTH-role DC scan over one block worklist (DESIGN.md §15).

    One call computes role t1 (atoms as written) and role t2 (``flipped``
    atoms, column sides swapped) — on the Pallas path a single launch that
    loads each distinct atom column once per tile.  The returned
    ``TileStats`` carry the worklist geometry and modeled bytes for
    telemetry; an empty worklist returns identities with zero launches and
    no kernel call at all."""
    n = l_cols[0].shape[0]
    nb = -(-n // block)
    rid = resolve_block_ids(nb, row_blocks, row_block_ids)
    cid = resolve_block_ids(nb, col_blocks, col_block_ids)
    launched = int(rid.size) * int(cid.size)
    distinct, _, _ = distinct_columns(l_cols, r_cols)
    tiles = TileStats(
        launched=launched,
        total=nb * nb,
        bytes_moved=(
            _scan_bytes(distinct, len(ops), block, rid.size, cid.size, nb)
            if launched else 0
        ),
    )
    mode = _mode(force)
    restr = dict(
        block=block, row_block_ids=rid, col_block_ids=cid,
    )
    if mode == "ref":
        t1c, t1s, t2c, t2s = ref.dc_pair_scan(
            l_cols, r_cols, ops, flipped, row_scope, col_scope,
            t1_reduces, t2_reduces, **restr,
        )
    else:
        t1c, t1s, t2c, t2s = dc_pair_scan_pallas(
            l_cols, r_cols, ops, flipped, row_scope, col_scope,
            t1_reduces, t2_reduces, interpret=(mode == "interpret"), **restr,
        )
    return DCPairScanResult(t1c, tuple(t1s), t2c, tuple(t2s), tiles)


# ------------------------------------------------------- compressed encodings
# Exactness-proved atom compression (DESIGN.md §15): a column may be scanned
# in a narrower dtype only when the predicate outcomes are PROVABLY identical
# to the f32/int32 originals.  Three encodings, strongest first:
#
# * ``code``  — order-preserving dense ranks (exact hashing of the value set)
#               for attributes whose every touching atom is a same-attribute
#               ==/!= atom: codes are equal iff values are equal;
# * ``int8``  — identity cast for integer-valued columns within int8 range:
#               every comparison op is preserved by the identity map;
# * ``bf16``  — for float columns that round-trip f32 -> bf16 -> f32 exactly
#               (NaN never round-trips, so NaN columns fall out naturally);
# * ``orig``  — the always-sound fallback.
#
# Both sides of every atom must land on the SAME encoding kind (comparing an
# int8 tile against an f32 tile proves nothing), so the planner runs a
# fixpoint demotion until every atom is consistent.


class ColumnEncoding(NamedTuple):
    kind: str  # "orig" | "int8" | "bf16" | "code"
    table: Optional[np.ndarray]  # code: sorted distinct values (decode table)
    code_dtype: object = None  # code: np.int8/np.int16/np.int32


_ENC_RANK = {"orig": 0, "bf16": 1, "int8": 2, "code": 3}


def _eligible_kinds(arr: np.ndarray) -> set:
    """Encoding kinds this column alone can prove exact (code eligibility is
    atom-context dependent and handled by the planner)."""
    kinds = {"orig"}
    if arr.size == 0:
        return kinds
    if np.issubdtype(arr.dtype, np.integer):
        if arr.min() >= -128 and arr.max() <= 127:
            kinds.add("int8")
        return kinds
    if np.isnan(arr).any():
        return kinds
    if np.all(arr == np.floor(arr)) and arr.min() >= -128 and arr.max() <= 127:
        kinds.add("int8")
    rt = np.asarray(jnp.asarray(arr).astype(jnp.bfloat16).astype(arr.dtype))
    if np.array_equal(rt, arr):
        kinds.add("bf16")
    return kinds


def plan_dc_encodings(
    cols: Dict[str, jnp.ndarray],
    atoms: Sequence[Tuple[str, str, str]],
) -> Optional[Dict[str, ColumnEncoding]]:
    """Choose one exact encoding per attribute for a DC's atom columns.

    ``atoms`` is ``[(left_attr, right_attr, op), ...]``.  Returns ``None``
    when nothing compresses (all ``orig``) so callers can skip the encode
    pass entirely.  Planning is host-side numpy over the base columns —
    O(n) per attribute, noise next to the O(n^2/block) scan it feeds."""
    host = {a: np.asarray(c) for a, c in cols.items()}
    eligible = {a: _eligible_kinds(arr) for a, arr in host.items()}
    # code: every atom touching the attr is a same-attribute equality atom
    # (and the column is NaN-free — code(NaN) == code(NaN) would flip !=)
    touching: Dict[str, List[Tuple[str, str, str]]] = {a: [] for a in host}
    for lname, rname, op in atoms:
        touching[lname].append((lname, rname, op))
        if rname != lname:
            touching[rname].append((lname, rname, op))
    for a, arr in host.items():
        if not touching[a]:
            continue
        same_eq = all(
            ln == rn == a and op in ("==", "!=") for ln, rn, op in touching[a]
        )
        no_nan = not (
            np.issubdtype(arr.dtype, np.floating) and np.isnan(arr).any()
        )
        if same_eq and no_nan and arr.size:
            eligible[a].add("code")
    enc = {
        a: max(kinds, key=_ENC_RANK.__getitem__) for a, kinds in eligible.items()
    }
    # fixpoint: both sides of every atom must share a kind both can prove
    changed = True
    while changed:
        changed = False
        for lname, rname, _ in atoms:
            if enc[lname] == enc[rname]:
                continue
            common = eligible[lname] & eligible[rname]
            cap = min(_ENC_RANK[enc[lname]], _ENC_RANK[enc[rname]])
            k = max(
                (c for c in common if _ENC_RANK[c] <= cap),
                key=_ENC_RANK.__getitem__,
            )
            enc[lname] = enc[rname] = k
            changed = True
    if all(k == "orig" for k in enc.values()):
        return None
    out = {}
    for a, kind in enc.items():
        if kind == "code":
            table = np.unique(host[a])
            cdt = (
                np.int8 if table.size <= 127
                else np.int16 if table.size <= 32767
                else np.int32
            )
            out[a] = ColumnEncoding("code", table, cdt)
        else:
            out[a] = ColumnEncoding(kind, None)
    return out


def encode_column(col: jnp.ndarray, enc: ColumnEncoding) -> jnp.ndarray:
    if enc.kind == "orig":
        return col
    if enc.kind == "int8":
        return col.astype(jnp.int8)
    if enc.kind == "bf16":
        return col.astype(jnp.bfloat16)
    if enc.kind == "code":
        codes = np.searchsorted(enc.table, np.asarray(col))
        return jnp.asarray(codes.astype(enc.code_dtype))
    raise ValueError(enc.kind)


def decode_stat(
    stat: jnp.ndarray,
    count: jnp.ndarray,
    enc: ColumnEncoding,
    orig_dtype,
    reduce: str,
) -> jnp.ndarray:
    """Map an encoded extremal-partner stat back to the original value
    space.  Rows with ``count == 0`` hold the ENCODED identity sentinel
    (e.g. int8 127), which has no preimage — they are rewritten to the
    original dtype's identity, exactly what an unencoded scan yields."""
    ident = ref._identity(orig_dtype, reduce)
    if enc.kind == "orig":
        return stat
    if enc.kind == "code":
        idx = jnp.clip(stat.astype(jnp.int32), 0, len(enc.table) - 1)
        dec = jnp.asarray(enc.table)[idx]
    else:
        dec = stat.astype(orig_dtype)
    return jnp.where(count > 0, dec, ident)


def semijoin(
    query: jnp.ndarray,
    query_mask: jnp.ndarray,
    keys: jnp.ndarray,
    keys_mask: jnp.ndarray,
    block: int = 512,
    force: str | None = None,
) -> jnp.ndarray:
    mode = _mode(force)
    if mode == "ref":
        return ref.semijoin(query, query_mask, keys, keys_mask, block=block)
    return semijoin_pallas(
        query, query_mask, keys, keys_mask, block=block, interpret=(mode == "interpret")
    )


def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    causal: bool = True,
    window: int | None = None,
    scale: float | None = None,
    block_q: int = 128,
    block_kv: int = 128,
    force: str | None = None,
) -> jnp.ndarray:
    mode = _mode(force)
    if mode == "ref":
        # long sequences: the blocked online-softmax path (O(s) live memory,
        # same tiling as the Pallas kernel); short ones: the exact oracle.
        sq, sk = q.shape[2], k.shape[2]
        if sq >= 1024 and sq % 512 == 0 and sk % 1024 == 0:
            return ref.attention_blocked(
                q, k, v, causal=causal, window=window, scale=scale
            )
        return ref.attention(q, k, v, causal=causal, window=window, scale=scale)
    return flash_attention_pallas(
        q,
        k,
        v,
        causal=causal,
        window=window,
        scale=scale,
        block_q=block_q,
        block_kv=block_kv,
        interpret=(mode == "interpret"),
    )
