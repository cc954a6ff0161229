"""The work the DC scan needs, from the launched worklists alone.

Compulsory HBM traffic of one launch over ``nrows`` row blocks and
``ncols`` column blocks: every distinct column of the DC, at the narrowest
width that represents its values exactly, plus a one-byte scope, read once
for each row block and once for each column block the worklist names; and
the outputs (a count and one extremal value per atom, for both tuple
roles, 32 bits each) written once per row of the launched row blocks.
This depends on the rule and the data, not on the kernel's layout, so a
later change to the kernel cannot move it.
"""

from __future__ import annotations

from typing import Dict, Iterable, Sequence

import numpy as np

SCOPE_BYTES = 1
OUT_BYTES = 4


def exact_width(values: np.ndarray, equality_only: bool) -> int:
    """Bytes per value of the narrowest exact representation: ranks for a
    column compared only for equality, 8- or 16-bit integers for whole
    numbers in range, bfloat16 where every value survives the round trip;
    4 bytes otherwise."""
    if equality_only:
        distinct = len(np.unique(values))
        return 1 if distinct <= 127 else 2 if distinct <= 32767 else 4
    floating = np.issubdtype(values.dtype, np.floating)
    if floating and np.isnan(values).any():
        return 4
    if not floating or np.all(values == np.floor(values)):
        lo, hi = values.min(), values.max()
        if -128 <= lo and hi <= 127:
            return 1
        if -32768 <= lo and hi <= 32767:
            return 2
    if floating:
        import ml_dtypes

        if np.array_equal(values.astype(ml_dtypes.bfloat16).astype(values.dtype), values):
            return 2
    return 4


def dc_widths(atoms: Sequence[Sequence[str]], data: Dict[str, np.ndarray]) -> Dict[str, int]:
    attrs = sorted({a for left, _, right in atoms for a in (left, right)})
    eq_only = {
        a: all(op in ("==", "!=") for left, op, right in atoms if a in (left, right))
        for a in attrs
    }
    return {a: exact_width(np.asarray(data[a]), eq_only[a]) for a in attrs}


def launch_bytes(nrows: int, ncols: int, block: int, widths: Iterable[int],
                 n_atoms: int) -> int:
    side = block * (sum(widths) + SCOPE_BYTES)
    out = nrows * block * (2 + 2 * n_atoms) * OUT_BYTES
    return (nrows + ncols) * side + out
