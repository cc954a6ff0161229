"""The traffic generator: a seed fixes each session's queries, draws follow
the mix's shares and Zipf ranks, every seed sends the same mix, and every
query names real columns."""

import collections
import itertools

import numpy as np
import pytest

import datagen
import traffic

MIXES = {"q1-ranges": "ssb-lo", "state-salary": "tax"}


def mix(name):
    cfg = datagen.load_config(MIXES[name])
    return traffic.Mix(traffic.load_mix(name), cfg.get("domains", {})), cfg


@pytest.mark.parametrize("name", sorted(MIXES))
def test_seed_fixes_each_session(name):
    m, _ = mix(name)
    a = list(itertools.islice(m.session_stream([7, 1, 0]), 200))
    b = list(itertools.islice(m.session_stream([7, 1, 0]), 200))
    c = list(itertools.islice(m.session_stream([7, 1, 1]), 200))
    assert a == b and a != c


@pytest.mark.parametrize("name", sorted(MIXES))
def test_queries_name_real_columns(name):
    m, cfg = mix(name)
    for q in itertools.islice(m.session_stream([3, 1, 0]), 500):
        cols = {c for c, _, _ in q.preds} | set(q.project)
        if q.groupby:
            cols |= set(q.groupby[0]) | {q.groupby[2]} - {None}
        assert cols <= set(cfg["columns"])


def test_q1_draws_zipf_over_42_queries():
    m, _ = mix("q1-ranges")
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
    m, _ = mix("state-salary")
    qs = list(itertools.islice(m.session_stream([5, 1, 0]), 20000))
    share = sum(q.template == "zip_lookup" for q in qs) / len(qs)
    assert share == pytest.approx(0.5, abs=0.02)
    states = collections.Counter(q.preds[0][2] for q in qs if q.template == "state_band")
    assert states.most_common(1)[0][0] == 0  # rank 1 is the most populous state


@pytest.mark.parametrize("name", sorted(MIXES))
def test_every_seed_sends_the_same_mix(name):
    """Over whole stratified blocks, each template and each rank at least as
    likely as one stratum comes up as often in every seed, within one a
    block; seeds differ in the order."""
    m, _ = mix(name)
    n = traffic.STRATA * 8
    runs = [list(itertools.islice(m.session_stream([seed, 1, 0]), n))
            for seed in (2**31 + 5, 9, 123456789)]
    assert runs[0] != runs[1]
    tmpl = [collections.Counter(q.template for q in qs) for qs in runs]
    for c in tmpl[1:]:
        for name_ in m.templates:
            assert abs(c[name_.name] - tmpl[0][name_.name]) <= 8
    # the most likely first slot value of the first template
    head = [collections.Counter(q.preds[0] for q in qs if q.template == m.templates[0].name)
            for qs in runs]
    top = head[0].most_common(1)[0][0]
    spread = max(h[top] for h in head) - min(h[top] for h in head)
    assert spread <= 8 + 2
