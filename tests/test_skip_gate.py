"""A clean step's skip gate: one compiled program over one answer per miss.

* the compiled possible-world filter against a plain numpy reference;
* the step gate's size, skip bit and pivot extremes against numpy over the
  whole columns, and Algorithm 2 fed the extremes against Algorithm 2 fed
  every answer row's pivot value;
* the executor against an eager copy of itself (the filter as a chain of
  ``candidate_matches``, each gate in numpy over host copies) and against
  itself with the answer forgotten between steps: same reports, answers,
  cost records and ``clean_version``;
* new predicate values reuse the compiled programs;
* a miss filters once, and again only after a step that cleaned.
"""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import executor as ex
from repro.core import operators as ops
from repro.core.constraints import DC, FD, Atom
from repro.core.executor import Daisy, DaisyConfig
from repro.core.operators import Pred, Query, filter_mask
from repro.core.relation import CAND_GT, CAND_LT, CAND_VALUE, make_relation
from repro.core.stats import algorithm2_decide, dc_stats
from repro.core.update import unchecked
from repro.obs import Tracer
from repro.obs.trace import to_host

CMP = {
    "==": np.equal, "!=": np.not_equal, "<": np.less,
    "<=": np.less_equal, ">": np.greater, ">=": np.greater_equal,
}


# ------------------------------------------------------------------ filter
def np_possible(op, value, column, cand=None, kind=None, count=None):
    """Does some candidate of each cell satisfy ``op value`` (the column's
    own value where the cell has none)?  Compared in the type JAX promotes
    to: float32 once the column or the value is a float (a Python scalar is
    weakly typed), else int32.  A range candidate ``(-inf, b)`` or
    ``(b, +inf)`` qualifies when it meets ``{x : x op value}``."""
    t = np.float32 if column.dtype.kind == "f" or isinstance(value, float) else np.int32
    v = t(value)
    base = CMP[op](column.astype(t), v)
    if cand is None:
        return base
    c = cand.astype(t)
    everywhere = np.ones(c.shape, bool)
    below = {"==": v < c, "!=": everywhere, "<": everywhere, "<=": everywhere,
             ">": c > v, ">=": c > v}[op]
    above = {"==": v > c, "!=": everywhere, ">": everywhere, ">=": everywhere,
             "<": v > c, "<=": v > c}[op]
    ok = np.where(kind == CAND_LT, below, np.where(kind == CAND_GT, above, CMP[op](c, v)))
    alive = count > 0
    return np.where(alive.any(axis=1), (ok & alive).any(axis=1), base)


def overlay_relation(seed=0, n=300, cap=320, k=4):
    """A plain int column ``a`` and overlay columns ``b`` (int) and ``f``
    (float) whose cells hold value, below-bound and above-bound candidates,
    some cells none; ``cap - n`` padding rows are invalid."""
    r = np.random.default_rng(seed)
    rel = make_relation(
        {"a": r.integers(0, 20, n), "b": r.integers(0, 20, n),
         "f": r.uniform(0, 20, n).astype(np.float32)},
        capacity=cap, overlay=["b", "f"], k=k,
    )
    cand, ckind, ccount = dict(rel.cand), dict(rel.ckind), dict(rel.ccount)
    for name, dtype in (("b", np.int32), ("f", np.float32)):
        vals = r.integers(0, 20, (cap, k)) if dtype == np.int32 else r.uniform(0, 20, (cap, k))
        kinds = r.choice([CAND_VALUE, CAND_LT, CAND_GT], (cap, k), p=[0.6, 0.2, 0.2])
        counts = np.where(r.random((cap, k)) < 0.5, r.integers(1, 4, (cap, k)), 0)
        counts[r.random(cap) < 0.3] = 0  # cells with no candidate
        cand[name] = jnp.asarray(vals.astype(dtype))
        ckind[name] = jnp.asarray(kinds.astype(np.int8))
        ccount[name] = jnp.asarray(counts.astype(np.float32))
    return dataclasses.replace(rel, cand=cand, ckind=ckind, ccount=ccount)


def np_filter(rel, preds):
    mask = np.asarray(rel.valid).copy()
    for p in preds:
        arrays = [np.asarray(rel.columns[p.col])]
        if p.col in rel.cand:
            arrays += [np.asarray(x[p.col]) for x in (rel.cand, rel.ckind, rel.ccount)]
        mask &= np_possible(p.op, p.value, *arrays)
    return mask


@pytest.mark.parametrize("value", [7, 7.5], ids=["int", "float"])
@pytest.mark.parametrize("op", list(CMP))
@pytest.mark.parametrize("col", ["a", "b", "f"], ids=["plain", "int-cands", "float-cands"])
def test_compiled_filter_matches_numpy(col, op, value):
    rel = overlay_relation()
    preds = (Pred(col, op, value),)
    got = np.asarray(filter_mask(rel, preds))
    np.testing.assert_array_equal(got, np_filter(rel, preds))
    # and the eager chain it replaces, bit for bit
    eager = rel.valid & rel.candidate_matches(col, op, value)
    np.testing.assert_array_equal(got, np.asarray(eager))


def test_compiled_filter_conjunction():
    rel = overlay_relation(seed=1)
    for a, b, f in ((5, 12, 3.5), (9, 2, 11.25)):
        preds = (Pred("a", ">=", a), Pred("b", "<", b), Pred("f", "!=", f),
                 Pred("b", ">", 1))
        np.testing.assert_array_equal(
            np.asarray(filter_mask(rel, preds)), np_filter(rel, preds)
        )
    assert filter_mask(rel, ()) is rel.valid


# ------------------------------------------------------------------- gates
def gate_inputs(answer_kind, pivot_dtype, with_checked, n=256):
    r = np.random.default_rng(3)
    valid = np.arange(n) < 240
    checked = (r.random(n) < 0.4) & valid
    dirty = (r.random(n) < 0.3) & valid
    if answer_kind == "empty":
        answer = np.zeros(n, bool)
    elif answer_kind == "some":
        answer = valid & (r.random(n) < 0.2)
    else:  # every answer row dirty and unchecked
        answer = dirty & ~checked
    pivot = r.integers(-500, 500, n).astype(pivot_dtype)
    if pivot_dtype == np.float32:
        pivot = pivot + np.float32(0.25)
    return valid, (checked if with_checked else None), dirty, answer, pivot


@pytest.mark.parametrize("with_checked", [True, False], ids=["checked", "no-checked"])
@pytest.mark.parametrize("pivot_dtype", [np.int32, np.float32], ids=["int", "float"])
@pytest.mark.parametrize("answer_kind", ["empty", "some", "all-dirty"])
def test_gates_match_numpy(answer_kind, pivot_dtype, with_checked):
    valid, checked, dirty, answer, pivot = gate_inputs(
        answer_kind, pivot_dtype, with_checked
    )
    live = valid if checked is None else valid & ~checked
    dev = lambda x: None if x is None else jnp.asarray(x)  # noqa: E731

    fd = to_host(ex._step_gate(dev(answer), dev(valid), dev(checked), dirty=dev(dirty)))
    assert int(fd["size"]) == int(answer.sum())
    assert bool(fd["hit"]) == bool((answer & dirty & live).any())
    assert answer_kind != "all-dirty" or bool(fd["hit"])

    dc = to_host(ex._step_gate(dev(answer), dev(valid), dev(checked), pivot=dev(pivot)))
    assert int(dc["size"]) == int(answer.sum())
    assert bool(dc["hit"]) == bool((answer & live).any())
    if answer.any():
        assert dc["lo"] == pivot[answer].min() and dc["hi"] == pivot[answer].max()

    rel = make_relation({"p": pivot[valid], "q": -pivot[valid]}, capacity=len(valid))
    st = dc_stats(rel, DC("d", [Atom("p", "<", "p"), Atom("q", ">", "q")]), p=8)
    for support, threshold in ((0.0, 0.5), (0.3, 0.99), (0.9, 0.01)):
        want = algorithm2_decide(st, pivot[answer], int(answer.sum()), support, threshold)
        got = algorithm2_decide(
            st, np.array([dc["lo"], dc["hi"]]), int(dc["size"]), support, threshold
        )
        assert got == want


# ---------------------------------------------------------------- executor
TAX_RULES = {"tax": [
    FD("tax_zc", "zip", "city"),
    FD("tax_zs", "zip", "state"),
    DC("tax_dc", [Atom("state", "==", "state"), Atom("salary", "<", "salary"),
                  Atom("rate", ">", "rate")]),
]}


def tax_relation(seed=0, n=512, states=6, zips=48):
    """A small Tax instance: FDs zip -> city, zip -> state and the
    state/salary/rate DC hold before 10% errors on city, state and rate."""
    r = np.random.default_rng(seed)
    zip_state = r.integers(0, states, zips)
    zipc = r.integers(0, zips, n)
    state, city = zip_state[zipc], zipc // 2
    salary = r.integers(10_000, 200_000, n)
    bracket = (salary - 10_000) * 6 // 190_000
    rate = r.integers(0, 8, states)[state] + 0.25 * bracket
    for col, hi in ((city, zips // 2), (state, states)):
        edit = r.random(n) < 0.1
        col[edit] = r.integers(0, hi, edit.sum())
    edit = r.random(n) < 0.1
    rate[edit] = rate[edit] + 0.25 * r.integers(-3, 4, edit.sum())
    return make_relation(
        {"zip": zipc, "city": city, "state": state, "salary": salary,
         "rate": rate.astype(np.float32)},
        overlay=["zip", "city", "state", "salary", "rate"],
        rules=[rule.name for rule in TAX_RULES["tax"]],
    )


def tax_queries(seed, count, states=6, zips=48):
    """State and salary-band queries alternating with zip lookups, each
    drawn anew: the same two predicate structures with new values."""
    r = np.random.default_rng(seed)
    out = []
    for i in range(count):
        if i % 2 == 0:
            lo = int(r.integers(10_000, 180_000))
            out.append(Query("tax", (
                Pred("state", "==", int(r.integers(0, states))),
                Pred("salary", ">=", lo), Pred("salary", "<=", lo + 19_999),
            )))
        else:
            out.append(Query(
                "tax", (Pred("zip", "==", int(r.integers(0, zips))),),
                project=("city", "state"),
            ))
    return out


def mixed_sequence():
    """Misses that clean, and repeats of them whose steps all skip."""
    qs = tax_queries(11, 8)
    return qs[:4] + qs[:2] + qs[4:] + qs[3:6]


class EagerDaisy(Daisy):
    """The executor deciding the eager way: every answer filtered anew as a
    chain of ``candidate_matches``, and each gate's scalars from numpy over
    host copies of the whole columns."""

    def _answer(self, table, preds):
        rel = self.db[table]
        mask = rel.valid
        for p in preds:
            mask = mask & rel.candidate_matches(p.col, p.op, p.value)
        return ex._Answer(mask)

    def _gate(self, ans, rel, rule_name, dirty=None, pivot=None):
        answer = np.asarray(ans.mask)
        hit = answer & np.asarray(unchecked(rel, rule_name))
        if dirty is not None:
            hit &= np.asarray(dirty)
        out = {"size": int(answer.sum()), "hit": bool(hit.any())}
        if pivot is not None and answer.any():
            values = np.asarray(pivot)[answer]
            out["lo"], out["hi"] = values.min(), values.max()
        elif pivot is not None:
            out["lo"] = out["hi"] = 0
        ans.size = out["size"]
        return out


class ForgetfulDaisy(Daisy):
    """The executor with its answer forgotten before every step."""

    def _answer(self, table, preds):
        if self._memo is not None:
            self._memo.rel = None
        return super()._answer(table, preds)


def serve(cls, queries):
    daisy = cls({"tax": tax_relation()}, TAX_RULES)
    trail = []
    for q in queries:
        res = daisy.execute(q)
        trail.append((
            np.asarray(res.mask), res.report.result_size,
            [s.asdict() for s in res.report.steps], daisy.clean_version,
        ))
    costs = {key: [dataclasses.astuple(c) for c in cm.history]
             for key, cm in daisy.cost.items()}
    return trail, costs, daisy.db["tax"]


@pytest.fixture(scope="module")
def served():
    return serve(Daisy, mixed_sequence())


@pytest.mark.parametrize("reference", [EagerDaisy, ForgetfulDaisy],
                         ids=["eager", "memo-cleared"])
def test_executor_matches_reference(served, reference):
    trail, costs, rel = served
    ref_trail, ref_costs, ref_rel = serve(reference, mixed_sequence())
    modes = [s["mode"] for _, _, steps, _ in trail for s in steps]
    assert "skipped" in modes and set(modes) - {"skipped"}
    for (m, size, steps, version), (rm, rsize, rsteps, rversion) in zip(trail, ref_trail):
        np.testing.assert_array_equal(m, rm)
        assert (size, steps, version) == (rsize, rsteps, rversion)
    assert costs == ref_costs
    for name in ("cand", "ccount", "ckind", "checked"):
        for attr, arr in getattr(rel, name).items():
            np.testing.assert_array_equal(
                np.asarray(arr), np.asarray(getattr(ref_rel, name)[attr])
            )


def test_new_values_reuse_the_compiled_programs():
    daisy = Daisy({"tax": tax_relation()}, TAX_RULES)
    ops._filter_program.clear_cache()
    ex._step_gate.clear_cache()
    queries = tax_queries(5, 20)
    for q in queries:
        daisy.execute(q)
    assert len({q.preds for q in queries}) == 20
    # one program per predicate structure: state and band, zip lookup
    assert ops._filter_program._cache_size() == 2
    # one gate per rule kind: the FD's dirty-group gate, the DC's pivot gate
    assert ex._step_gate._cache_size() == 2


def traced_miss(daisy, query):
    daisy.tracer = Tracer()
    e0, r0 = daisy.filter_evals, daisy.filter_reuses
    res = daisy.execute(query)
    (span,) = [e for e in daisy.tracer.events() if e.name == "daisy.execute"]
    daisy.tracer.clear()
    evals, reuses = daisy.filter_evals - e0, daisy.filter_reuses - r0
    assert (span.attrs["filter_evals"], span.attrs["filter_reuses"]) == (evals, reuses)
    return res, evals, reuses


def test_miss_whose_steps_skip_filters_once():
    # no cost model: its switch to a full clean would clean again
    daisy = Daisy({"tax": tax_relation()}, TAX_RULES, DaisyConfig(use_cost_model=False))
    query = tax_queries(7, 1)[0]
    # repairs widen the answer (new candidates qualify), so repeat the
    # query until its answer is clean
    for _ in range(10):
        if all(s.mode == "skipped" for s in daisy.execute(query).report.steps):
            break
    res, evals, reuses = traced_miss(daisy, query)
    steps = res.report.steps
    assert steps and all(s.mode == "skipped" for s in steps)
    # each step and the final filter take the first step's answer
    assert (evals, reuses) == (1, len(steps))


def test_miss_filters_again_after_a_cleaning_step():
    # no cost model: an FD switched to a full clean filters nothing
    daisy = Daisy({"tax": tax_relation()}, TAX_RULES, DaisyConfig(use_cost_model=False))
    res, evals, reuses = traced_miss(daisy, tax_queries(7, 2)[1])
    cleaned = sum(s.mode != "skipped" for s in res.report.steps)
    assert cleaned >= 1
    # a cleaned step replaces the relation: whoever reads the answer next
    # filters anew
    assert evals == 1 + cleaned
    assert evals + reuses == len(res.report.steps) + 1
