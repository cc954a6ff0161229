"""The control and the planted faults the correctness check must refuse.

Each is a context manager that patches the program underneath a run:

* ``control`` — the DC scan in bfloat16 where the configuration states
  32-bit values: the step a later change could be tempted by (half the
  bytes per column), taken even where it is not exact.  The program has
  this encoding path of its own and uses it only where the round trip is
  exact; here it is switched on for every column it does not rank-code.
* ``state_unchanged`` — a cleaning step returns the relation unchanged
  (no candidate is merged).
* ``half_batch`` — the DC kernel scans only half of each worklist's
  partner blocks.
* ``answer_altered`` — each executed answer is altered where it is
  produced: a one-table answer loses its first qualifying row; a join
  answer's first valid pair of lineage takes the next row of the last
  joined table in place of its own (a dropped pair would go unseen where
  the pair is one the semantics allow but do not require).

The cells run on one chip, so the fault of a left-out exchange between
chips does not arise.
"""

from __future__ import annotations

import contextlib
import dataclasses

import numpy as np


@contextlib.contextmanager
def _patch(obj, name, value):
    old = getattr(obj, name)
    setattr(obj, name, value)
    try:
        yield
    finally:
        setattr(obj, name, old)


def control():
    from repro.kernels import ops as kops

    plan = kops.plan_dc_encodings

    def lossy(cols, atoms):
        out = dict(plan(cols, atoms) or {})
        for name in cols:
            if out.get(name, kops.ColumnEncoding("orig", None)).kind != "code":
                out[name] = kops.ColumnEncoding("bf16", None)
        return out

    return _patch(kops, "plan_dc_encodings", lossy)


def state_unchanged():
    from repro.core import executor

    return _patch(executor, "apply_candidates", lambda rel, deltas: rel)


def half_batch():
    from repro.kernels import ops as kops
    from repro.kernels.dc_pairs import resolve_block_ids

    scan = kops.dc_pair_scan

    def half(l_cols, r_cols, *args, block=256, col_blocks=None,
             col_block_ids=None, **kw):
        nb = -(-l_cols[0].shape[0] // block)
        cid = resolve_block_ids(nb, col_blocks, col_block_ids)
        keep = cid[::2] if cid.size > 1 else cid
        return scan(l_cols, r_cols, *args, block=block, col_block_ids=keep, **kw)

    return _patch(kops, "dc_pair_scan", half)


def _first(flags) -> int:
    return int(np.argmax(np.asarray(flags)))


@contextlib.contextmanager
def answer_altered():
    from repro.core.executor import Daisy

    execute_sp, execute_join = Daisy._execute_sp, Daisy._execute_join

    def altered_sp(self, query, plan, report):
        result = execute_sp(self, query, plan, report)
        result.mask = result.mask.at[_first(result.mask)].set(False)
        return result

    def altered_join(self, query, plan, report):
        result = execute_join(self, query, plan, report)
        join = result.join
        last = join.tables[-1]
        rows = {**join.rows, last: join.rows[last].at[_first(join.valid)].add(1)}
        result.join = dataclasses.replace(join, rows=rows)
        return result

    with _patch(Daisy, "_execute_sp", altered_sp), \
            _patch(Daisy, "_execute_join", altered_join):
        yield


FAULTS = {
    "state_unchanged": state_unchanged,
    "half_batch": half_batch,
    "answer_altered": answer_altered,
}
