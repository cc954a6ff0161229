"""What decides ``correct``: a plain numpy reference of the rules' semantics.

It imports nothing of the program and takes nothing the program made: it
works from the dirty instance the benchmark generated and the rules in the
configuration file.  It judges two things the window produced.

* **Served answers** (``answer_rows_wrong``).  Every answer a session
  received, cache hits included (a hit is the executed answer it returns),
  is held to what Daisy's possible-world semantics fix whatever the order
  of cleaning: a row that no rule can repair on a predicate's attribute
  qualifies exactly when its values satisfy the predicates; a row that a
  rule can repair qualifies only if some value the rule can give it does,
  and does qualify when its values satisfy the predicates and the rules
  keep those values among its candidates;
  a group-by key counts at least its unrepairable rows and at most those
  plus the repairable rows that can take the key, and likewise for a sum.
  The count of rows and groups outside these bounds is compared; limit 0.
* **The cleaned instance** (``state_rows_wrong``).  Rows the window marked
  checked for a rule must hold exactly the candidates of one full pass of
  the rule over the whole relation: for an FD the distinct right-hand
  values of the row's group with their frequencies; for a DC each violated
  inequality atom's range fix, bounded by the extremal value over all the
  row's violating partners (Example 4 of the Daisy paper), with at least
  the violating-pair count as weight.  Unchecked rows may hold partial
  evidence only: a range no tighter than the full one, and only where the
  row violates the DC at all.  This covers relaxation (an FD's evidence is
  its whole group), detection (the DC kernel's counts and extremal
  partners), repair and the merge.  A seeded sample of rows of each table
  is compared, each table against its own rules; limit 0.

Answers of one table are judged here.  Answers this reference cannot read,
such as a join's lineage, go to the configuration's own
``check_answers(tables, sems, answers)`` (``tables``: each table's view of
the configuration; ``sems``: each table's ``Semantics``; ``answers``: the
``(traffic.QuerySpec, result)`` of each sampled join answer), which returns
its own numbers, each ``{"value", "limit"}``, for ``compared``.  It is
called whenever the configuration defines it.  A join answer in a run
whose configuration defines none stops the run (``UncheckedAnswers``): it
is never passed unchecked.
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np

import datagen

# the program's overlay format: candidate kinds
CAND_VALUE, CAND_LT, CAND_GT = 0, 1, 2
FIX_KIND = {"<": CAND_GT, "<=": CAND_GT, ">": CAND_LT, ">=": CAND_LT}
REDUCE = {"<": np.max, "<=": np.max, ">": np.min, ">=": np.min}
FLIP = {"==": "==", "!=": "!=", "<": ">", "<=": ">=", ">": "<", ">=": "<="}
OPS = {
    "==": np.equal, "!=": np.not_equal, "<": np.less, "<=": np.less_equal,
    ">": np.greater, ">=": np.greater_equal,
}

# rows sampled per rule for the state comparison
SAMPLE_CHECKED = 256
SAMPLE_UNCHECKED = 128
# distinct answers held to the answer checks (all of them when fewer)
MAX_ANSWERS = 500
# rounding room of float32 group sums: a few hundred terms per group give
# relative errors of order 1e-5; the bounds are whole rows or sums of
# non-negative values, so this room never admits a missing row
REL_TOL = 1e-4
ABS_TOL = 1e-3


class FDModel:
    def __init__(self, name, lhs, rhs, data):
        self.name, self.lhs, self.rhs = name, tuple(lhs), rhs
        keys = np.stack([data[a] for a in self.lhs], axis=1)
        _, self.gid = np.unique(keys, axis=0, return_inverse=True)
        self.gid = self.gid.ravel()
        pairs = np.stack([self.gid, data[rhs]], axis=1)
        uniq, counts = np.unique(pairs, axis=0, return_counts=True)
        self.pair_g, self.pair_v, self.pair_c = uniq[:, 0], uniq[:, 1], counts
        distinct = np.bincount(self.pair_g, minlength=self.gid.max() + 1)
        self.distinct = distinct
        self.violated = distinct[self.gid] >= 2
        self.first = np.searchsorted(self.pair_g, np.arange(len(distinct)))
        if len(self.lhs) == 1:
            lr = np.unique(np.stack([data[self.lhs[0]], data[rhs]], axis=1), axis=0)
            self.lr_lhs, self.lr_rhs = lr[:, 0], lr[:, 1]

    def group_pairs(self, row: int):
        g = self.gid[row]
        lo, hi = self.first[g], self.first[g] + self.distinct[g]
        return self.pair_v[lo:hi], self.pair_c[lo:hi]


class DCModel:
    def __init__(self, name, atoms, data):
        self.name = name
        self.atoms = [tuple(a) for a in atoms]
        self.data = data
        self.ineq = [a for a in self.atoms if a[1] in FIX_KIND]
        self.viol_t1 = self._violators(flip=False)
        self.viol_t2 = self._violators(flip=True)

    def _violators(self, flip: bool) -> np.ndarray:
        """Rows that have at least one violating partner in the role (t1,
        or t2 with ``flip``), over the whole relation: sort within the
        equality partition, then a running extreme answers "is there a
        partner beyond me on both inequalities" in O(n log n)."""
        eq = [a for a in self.atoms if a[1] == "=="]
        if len(self.ineq) != 2 or any(l != r for l, _, r in self.atoms) or (
            len(eq) + 2 != len(self.atoms)
        ) or any(op not in ("<", ">") for _, op, _ in self.ineq):
            raise NotImplementedError(
                f"{self.name}: the reference handles same-attribute equalities "
                "plus two strict inequalities"
            )
        (a, op_a, _), (b, op_b, _) = self.ineq
        if flip:
            op_a, op_b = FLIP[op_a], FLIP[op_b]
        x = self.data[a].astype(np.float64)
        y = self.data[b].astype(np.float64)
        n = len(x)
        part = np.zeros(n, np.int64)
        for attr, _, _ in eq:
            _, inv = np.unique(self.data[attr], return_inverse=True)
            part = part * (inv.max() + 1) + inv.ravel()
        # partner j needs x_j beyond x_i: '<' means x_i < x_j (larger x_j)
        sx = x if op_a == "<" else -x
        sy = y if op_b == "<" else -y
        # want j with sx_j > sx_i and sy_j > sy_i: per partition, sort by sx
        # descending and keep the max sy of strictly larger sx
        order = np.lexsort((-sx, part))
        p, xs, ys = part[order], sx[order], sy[order]
        out = np.zeros(n, bool)
        bounds = np.flatnonzero(np.r_[True, p[1:] != p[:-1], True])
        for lo, hi in zip(bounds[:-1], bounds[1:]):
            xv, yv = xs[lo:hi], ys[lo:hi]
            # runs of equal sx: a partner must come from an earlier run
            run_start = np.r_[True, xv[1:] != xv[:-1]]
            run_id = np.cumsum(run_start) - 1
            run_max = np.maximum.reduceat(yv, np.flatnonzero(run_start))
            before = np.r_[-np.inf, np.maximum.accumulate(run_max)[:-1]]
            out[order[lo:hi]] = before[run_id] > yv
        return out

    def partners(self, row: int):
        """Boolean masks of row's violating partners as t1 and as t2."""
        n = len(next(iter(self.data.values())))
        t1 = np.ones(n, bool)
        t2 = np.ones(n, bool)
        for left, op, right in self.atoms:
            t1 &= OPS[op](self.data[left][row], self.data[right])
            t2 &= OPS[op](self.data[left], self.data[right][row])
        t1[row] = t2[row] = False
        return t1, t2

    def expected_fixes(self, row: int) -> Dict[tuple, list]:
        """(attr, kind) -> [bound, pair count] of one full pass."""
        t1, t2 = self.partners(row)
        c1, c2 = int(t1.sum()), int(t2.sum())
        fixes: Dict[tuple, list] = {}

        def add(attr, op, values, count):
            kind = FIX_KIND[op]
            bound = REDUCE[op](values)
            if (attr, kind) in fixes:
                prev = fixes[(attr, kind)]
                prev[0] = max(prev[0], bound) if kind == CAND_GT else min(prev[0], bound)
                prev[1] += count
            else:
                fixes[(attr, kind)] = [bound, count]

        for left, op, right in self.ineq:
            if c1:
                add(left, op, self.data[right][t1], c1)
            if c2:
                add(right, FLIP[op], self.data[left][t2], c2)
        return fixes


class Semantics:
    """The rules of one configuration over one dirty instance."""

    def __init__(self, cfg: dict, data: Dict[str, np.ndarray]):
        self.data = data
        self.k = int(cfg["daisy"].get("k", 8))
        self.fds: List[FDModel] = []
        self.dcs: List[DCModel] = []
        for rule in cfg["rules"]:
            if "fd" in rule:
                self.fds.append(FDModel(rule["name"], rule["fd"]["lhs"],
                                        rule["fd"]["rhs"], data))
            else:
                self.dcs.append(DCModel(rule["name"], rule["dc"], data))
        n = len(next(iter(data.values())))
        # attr -> [(writer kind, rule model)] and the rows it may repair
        self.writers: Dict[str, list] = {}
        self.repairable: Dict[str, np.ndarray] = {}

        def write(attr, kind, model, rows):
            self.writers.setdefault(attr, []).append((kind, model, rows))
            self.repairable[attr] = self.repairable.get(attr, np.zeros(n, bool)) | rows

        for fd in self.fds:
            write(fd.rhs, "fd_rhs", fd, fd.violated)
            if len(fd.lhs) == 1:
                write(fd.lhs[0], "fd_lhs", fd, fd.violated)
        for dc in self.dcs:
            for left, _, right in dc.ineq:
                write(left, "dc", dc, dc.viol_t1)
                write(right, "dc", dc, dc.viol_t2)
        self._pred_cache: Dict[tuple, tuple] = {}

    def can_repair(self, attr: str) -> np.ndarray:
        n = len(next(iter(self.data.values())))
        return self.repairable.get(attr, np.zeros(n, bool))

    # ------------------------------------------------------------ answers
    def _possible(self, col, op, value):
        """Could the row's value, or some value a rule can give it, satisfy
        ``op value``?"""
        out = OPS[op](self.data[col], value)
        for kind, model, rows in self.writers.get(col, ()):
            if kind == "dc" or op != "==":
                extra = rows  # a range fix, or a non-equality: assume it may
            elif kind == "fd_rhs":  # the distinct rhs values of the row's group
                extra = np.isin(model.gid, model.pair_g[model.pair_v == value])
            else:  # fd_lhs: the lhs values that share the row's rhs value
                extra = np.isin(self.data[model.rhs], model.lr_rhs[model.lr_lhs == value])
            out |= rows & extra
        return out

    def keeps_original(self, attr: str) -> np.ndarray:
        """Rows whose candidates for ``attr`` always include the original
        value: a DC keeps it beside its range fixes, an FD's right-hand
        candidates are its group's distinct values (the row's own among
        them) while they fit the overlay; an FD's left-hand candidates come
        from other rows and may leave it out."""
        n = len(self.data[attr])
        keep = np.ones(n, bool)
        slots = np.zeros(n, np.int64)
        for kind, model, rows in self.writers.get(attr, ()):
            if kind == "fd_lhs":
                keep &= ~rows
            elif kind == "fd_rhs":
                slots += np.where(rows, model.distinct[model.gid], 0)
            else:
                slots += np.where(rows, 3, 0)  # the value and two ranges at most
        return keep & (slots <= self.k)

    def pred_masks(self, preds):
        key = tuple(preds)
        if key not in self._pred_cache:
            exact = np.ones(len(self.data[preds[0][0]]), bool)
            possible = np.ones_like(exact)
            fixed = np.ones_like(exact)
            kept = np.ones_like(exact)
            for col, op, value in preds:
                exact &= OPS[op](self.data[col], value)
                fixed &= ~self.can_repair(col)
                kept &= self.keeps_original(col)
                possible &= self._possible(col, op, value)
            self._pred_cache[key] = (exact, fixed, kept, possible)
        return self._pred_cache[key]

    def check_answer(self, spec, mask: np.ndarray, groups) -> int:
        """Rows and groups of one answer outside the semantic bounds."""
        exact, fixed, kept, possible = self.pred_masks(spec.preds)
        wrong = int(np.sum(fixed & (mask != exact)))
        wrong += int(np.sum(~fixed & kept & exact & ~mask))
        wrong += int(np.sum(mask & ~possible))
        if spec.groupby is not None and groups is not None:
            wrong += self._check_groups(spec.groupby, mask, groups)
        return wrong

    def _check_groups(self, groupby, mask, groups) -> int:
        keys, agg, value = groupby
        if len(keys) != 1 or agg not in ("count", "sum"):
            return 0
        key = keys[0]
        kv = self.data[key]
        rep = self.can_repair(key)
        fixed_rows = mask & ~rep
        loose = mask & rep
        size = int(max(kv.max(), 0)) + 1
        c_lo = np.bincount(kv[fixed_rows], minlength=size).astype(np.float64)
        writers = self.writers.get(key, [])
        x = None
        if agg == "sum":
            if any(w[0] != "dc" for w in self.writers.get(value, ())):
                return 0  # expected values of FD-repaired values are not bounded here
            x = self.data[value].astype(np.float64)
            if (x < 0).any():
                return 0
            a_lo = np.bincount(kv[fixed_rows], weights=x[fixed_rows], minlength=size)
        if len(writers) == 1 and writers[0][0] == "fd_rhs":
            fd = writers[0][1]
            per_group = np.bincount(fd.gid[loose], minlength=len(fd.distinct))
            c_hi = c_lo + np.bincount(fd.pair_v, weights=per_group[fd.pair_g],
                                      minlength=size)
            if x is not None:
                wg = np.bincount(fd.gid[loose], weights=x[loose],
                                 minlength=len(fd.distinct))
                a_hi = a_lo + np.bincount(fd.pair_v, weights=wg[fd.pair_g],
                                          minlength=size)
        else:
            c_hi = c_lo + loose.sum()
            if x is not None:
                a_hi = a_lo + x[loose].sum()
        num = int(np.asarray(groups["num_groups"]))
        got_k = np.asarray(groups[f"key_{key}"])[:num].astype(np.int64)
        got_c = np.asarray(groups["count"])[:num].astype(np.float64)
        got_a = np.asarray(groups["agg"])[:num].astype(np.float64)
        count = np.zeros(size)
        total = np.zeros(size)
        inside = (got_k >= 0) & (got_k < size)
        wrong = int(np.sum(~inside & (got_c > ABS_TOL)))
        np.add.at(count, got_k[inside], got_c[inside])
        np.add.at(total, got_k[inside], got_a[inside])
        tol = ABS_TOL + REL_TOL * c_hi
        wrong += int(np.sum((count < c_lo - tol) | (count > c_hi + tol)))
        if x is not None:
            tol = ABS_TOL + REL_TOL * a_hi
            wrong += int(np.sum((total < a_lo - tol) | (total > a_hi + tol)))
        return wrong

    # -------------------------------------------------------------- state
    def check_state(self, checked: Dict[str, np.ndarray], overlay, rows) -> int:
        """Sampled rows whose candidates disagree with one full pass.
        ``overlay(attr, rows)`` returns the program's (values, counts, kinds)
        for those rows."""
        wrong = 0
        for fd in self.fds:
            if len(self.writers.get(fd.rhs, [])) != 1:
                continue
            sample = rows[fd.name]["checked"]
            values, counts, _ = overlay(fd.rhs, sample)
            for i, row in enumerate(sample):
                alive = counts[i] > 0
                got = sorted(zip(values[i][alive].tolist(), counts[i][alive].tolist()))
                if fd.violated[row]:
                    v, c = fd.group_pairs(row)
                    if len(v) > self.k:
                        continue
                    want = sorted(zip(v.tolist(), c.astype(float).tolist()))
                else:
                    want = []
                wrong += got != want
        for dc in self.dcs:
            attrs = sorted({a for l, _, r in dc.ineq for a in (l, r)
                            if all(w[0] == "dc" and w[1] is dc for w in self.writers[a])})
            for state in ("checked", "unchecked"):
                sample = rows[dc.name][state]
                got = {a: overlay(a, sample) for a in attrs}
                for i, row in enumerate(sample):
                    want = dc.expected_fixes(row)
                    wrong += not all(
                        self._dc_row_ok(a, row, got[a], i, want, state == "checked")
                        for a in attrs
                    )
        return wrong

    def _dc_row_ok(self, attr, row, got, i, want, checked) -> bool:
        values, counts, kinds = (g[i] for g in got)
        alive = counts > 0
        ranges = {int(k): (v, c) for v, c, k in
                  zip(values[alive], counts[alive], kinds[alive]) if k != CAND_VALUE}
        if len(ranges) != int(np.sum(alive & (kinds != CAND_VALUE))):
            return False  # two ranges of one kind were not coalesced
        expect = {kind: wc for (a, kind), wc in want.items() if a == attr}
        orig = self.data[attr][row]
        value_slots = [(v, c) for v, c, k in zip(values[alive], counts[alive], kinds[alive])
                       if k == CAND_VALUE]
        if checked:
            if set(ranges) != set(expect):
                return False
            for kind, (bound, count) in expect.items():
                v, c = ranges[kind]
                if v != bound or c < count:
                    return False
            if expect:
                need = sum(count for _, count in expect.values())
                if not any(v == orig and c >= need for v, c in value_slots):
                    return False
            return True
        for kind, (v, _) in ranges.items():
            if kind not in expect:
                return False
            bound = expect[kind][0]
            if (kind == CAND_GT and v > bound) or (kind == CAND_LT and v < bound):
                return False
        return True


class UncheckedAnswers(RuntimeError):
    """Join answers were served and the configuration has no reference for
    them."""


def sample_rows(sem: Semantics, checked: Dict[str, np.ndarray], r: np.random.Generator):
    out = {}
    for model in sem.fds + sem.dcs:
        ch = checked[model.name]
        pick = {}
        for state, pool, size in (
            ("checked", np.flatnonzero(ch), SAMPLE_CHECKED),
            ("unchecked", np.flatnonzero(~ch), SAMPLE_UNCHECKED),
        ):
            pick[state] = np.sort(r.choice(pool, min(size, len(pool)), replace=False))
        out[model.name] = pick
    return out


def check(cfg: dict, insts, dep, records, seed: int) -> Dict[str, dict]:
    """The numbers ``correct`` compares, each with its limit.  ``insts``:
    each table's generated instance."""
    views = datagen.tables(cfg)
    sems = {t: Semantics(views[t], insts[t].dirty) for t in views}
    results = {}
    for rec in records:
        if rec.t1 is not None and rec.ticket.error is None:
            results.setdefault(id(rec.ticket.result), (rec.spec, rec.ticket.result))
    chosen = list(results.values())
    if len(chosen) > MAX_ANSWERS:
        r = datagen.rng(seed, datagen.STREAM_ANSWERS)
        chosen = [chosen[i] for i in sorted(r.choice(len(chosen), MAX_ANSWERS, replace=False))]
    answer_wrong = 0
    joined = []
    for q, result in chosen:
        if q.joins:
            joined.append((q, result))
            continue
        mask = np.asarray(result.mask)
        groups = None
        if result.groups is not None:
            groups = {k: np.asarray(v) for k, v in result.groups.items()}
        answer_wrong += sems[q.table].check_answer(q, mask, groups)
    hook = cfg.get("_check_answers")
    if joined and hook is None:
        raise UncheckedAnswers(
            f"configuration {cfg['name']!r} served {len(joined)} join answers and "
            "defines no check_answers in its module: a join answer is never "
            "passed unchecked"
        )
    own = {} if hook is None else hook(views, sems, joined)

    r = datagen.rng(seed, datagen.STREAM_SAMPLE)
    state_wrong = 0
    for t, sem in sems.items():
        rel = dep.daisy.db[t]
        n = insts[t].rows
        checked = {name: np.asarray(c)[:n] for name, c in rel.checked.items()}
        rows = sample_rows(sem, checked, r)

        def overlay(attr, idx):
            idx = np.asarray(idx, np.int32)
            return (np.asarray(rel.cand[attr][idx]), np.asarray(rel.ccount[attr][idx]),
                    np.asarray(rel.ckind[attr][idx]))

        state_wrong += sem.check_state(checked, overlay, rows)
    compared = {
        "answer_rows_wrong": {"value": answer_wrong, "limit": 0},
        "state_rows_wrong": {"value": state_wrong, "limit": 0},
    }
    clash = set(own) & set(compared)
    if clash:
        raise ValueError(f"{cfg['name']}: check_answers reuses the names {sorted(clash)}")
    compared.update(own)
    return compared
