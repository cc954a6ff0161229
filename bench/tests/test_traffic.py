"""The traffic generator: a seed fixes each session's queries, draws follow
the mix's shares and Zipf ranks, every seed sends the same mix, every
query names real columns of the tables it reads, and qualified names
resolve to their tables."""

import collections
import itertools

import numpy as np
import pytest

import datagen
import records
import traffic


def mix(cell, root=records.BENCH):
    cfg = datagen.load_config(cell["config"], root)
    m = traffic.Mix(traffic.load_mix(cell["traffic"], root), cfg.get("domains", {}),
                    next(iter(datagen.tables(cfg))))
    return m, cfg


def named(traffic_name):
    """The mix of the cell of ``BENCHMARK.json`` that sends this traffic."""
    return mix(next(p.values[0] for p in records.cells()
                    if p.values[0]["traffic"] == traffic_name))


@pytest.mark.parametrize("cell, root", records.cells())
def test_seed_fixes_each_session(cell, root):
    m, _ = mix(cell, root)
    a = list(itertools.islice(m.session_stream([7, 1, 0]), 200))
    b = list(itertools.islice(m.session_stream([7, 1, 0]), 200))
    c = list(itertools.islice(m.session_stream([7, 1, 1]), 200))
    assert a == b and a != c


@pytest.mark.parametrize("cell, root", records.cells())
def test_queries_name_real_columns(cell, root):
    m, cfg = mix(cell, root)
    views = datagen.tables(cfg)
    for q in itertools.islice(m.session_stream([3, 1, 0]), 500):
        cols = {q.table: {c for c, _, _ in q.preds} | set(q.project)}
        for j in q.joins:
            cols[j.right] = {c for c, _, _ in j.preds} | {j.right_on}
            assert any(j.left_on in views[t]["columns"] for t in cols if t != j.right)
        if q.groupby:
            keys, _, value = q.groupby
            names = set(keys) | {value} - {None}
            if q.groupby_table is not None:
                cols[q.groupby_table] |= names
            else:  # each in some table the query reads
                assert all(any(n in views[t]["columns"] for t in cols) for n in names)
        for t, c in cols.items():
            assert c <= set(views[t]["columns"]), t


def test_q1_draws_zipf_over_42_queries():
    m, _ = named("q1-ranges")
    draws = [q.preds for q in itertools.islice(m.session_stream([11, 1, 0]), 20000)]
    counts = collections.Counter(draws)
    assert len(counts) == 42
    w = datagen.zipf_weights(42, 1.1)
    t = m.templates[0]
    first = t.draw(t.drawers(np.random.default_rng(0)))  # any draw has 5 preds
    assert len(first.preds) == 5
    top = counts.most_common(1)[0][1] / len(draws)
    assert top == pytest.approx(w[0], rel=0.1)


def test_state_salary_shares():
    m, _ = named("state-salary")
    qs = list(itertools.islice(m.session_stream([5, 1, 0]), 20000))
    share = sum(q.template == "zip_lookup" for q in qs) / len(qs)
    assert share == pytest.approx(0.5, abs=0.02)
    states = collections.Counter(q.preds[0][2] for q in qs if q.template == "state_band")
    assert states.most_common(1)[0][0] == 0  # rank 1 is the most populous state


@pytest.mark.parametrize("cell, root", records.cells())
def test_every_seed_sends_the_same_mix(cell, root):
    """Over whole stratified blocks, each template and each rank at least as
    likely as one stratum comes up as often in every seed, within one a
    block; seeds differ in the order."""
    m, _ = mix(cell, root)
    n = traffic.STRATA * 8
    runs = [list(itertools.islice(m.session_stream([seed, 1, 0]), n))
            for seed in (2**31 + 5, 9, 123456789)]
    assert runs[0] != runs[1]
    tmpl = [collections.Counter(q.template for q in qs) for qs in runs]
    for c in tmpl[1:]:
        for name_ in m.templates:
            assert abs(c[name_.name] - tmpl[0][name_.name]) <= 8
    # the most likely first predicate of the first template
    head = [collections.Counter(first_pred(q) for q in qs
                                if q.template == m.templates[0].name)
            for qs in runs]
    top = head[0].most_common(1)[0][0]
    spread = max(h[top] for h in head) - min(h[top] for h in head)
    assert spread <= 8 + 2


def first_pred(q):
    return (q.preds + tuple(p for j in q.joins for p in j.preds))[0]


def resolve(preds=(), groupby=None, joins=(("d", "k", "k"),), table=None):
    """The query of a template of one slot with one choice, ``preds``, as
    the mix draws it with ``f`` as the default table."""
    spec = {
        "name": "t", "share": 1.0,
        "slots": [{"choices": [[list(p) for p in preds]], "draw": {"uniform": True}}],
        "joins": [{"right": r, "left_on": lo, "right_on": ro} for r, lo, ro in joins],
    }
    if table is not None:
        spec["table"] = table
    if groupby is not None:
        keys, agg, value = groupby
        spec["groupby"] = {"keys": list(keys), "agg": agg, "value": value}
    t = traffic._Template(spec, {}, "f")
    return t.draw(t.drawers(np.random.default_rng(0)))


def test_qualified_predicates_go_to_their_tables():
    q = resolve([("f.x", "==", 1), ("y", "<", 2), ("d.z", ">=", 3), ("e.w", "==", 4)])
    assert q.table == "f"  # the default table
    # a prefix that names no table of the query is part of the column's name
    assert q.preds == (("x", "==", 1), ("y", "<", 2), ("e.w", "==", 4))
    (j,) = q.joins
    assert (j.right, j.left_on, j.right_on, j.preds) == ("d", "k", "k", (("z", ">=", 3),))
    assert resolve(table="g").table == "g"


def test_groupby_names_one_table_or_are_passed_as_written():
    # unqualified: no table, as a one-table query has always been built
    q = resolve(groupby=(("a",), "sum", "b"))
    assert (q.groupby, q.groupby_table) == ((("a",), "sum", "b"), None)
    # all on one table, written or default
    q = resolve(groupby=(("d.a",), "sum", "d.b"))
    assert (q.groupby, q.groupby_table) == ((("a",), "sum", "b"), "d")
    q = resolve(groupby=(("f.a", "c"), "count", None))
    assert (q.groupby, q.groupby_table) == ((("a", "c"), "count", None), "f")
    # spanning tables: as written, for the program to resolve
    q = resolve(groupby=(("d.a", "f.c"), "sum", "b"))
    assert (q.groupby, q.groupby_table) == ((("d.a", "f.c"), "sum", "b"), None)


def test_a_table_joined_twice_is_refused():
    with pytest.raises(ValueError, match="joined twice"):
        resolve(joins=(("d", "k", "k"), ("d", "j", "j")))
