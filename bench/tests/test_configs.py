"""Each configuration's generator, table by table: the clean instance
satisfies every rule, errors go where and as often as the configuration
states, and a seed fixes the instance."""

import numpy as np
import pytest

import datagen
import records
from reference import DCModel, FDModel


# the generators run at twice ``small`` (8,192 rows for SSB and Tax, as
# before the sizes moved into the configurations): the error-rate check's
# 4-sigma room shrinks with the rows, and the rule checks see more of them
SCALE = 2


def table_of(name, root, table, seed):
    cfg = records.small(name, root, SCALE)
    return datagen.tables(cfg)[table], datagen.generate(cfg, seed)[table]


@pytest.mark.parametrize("name, root, table", records.tables())
def test_clean_instance_satisfies_every_rule(name, root, table):
    view, inst = table_of(name, root, table, 2**31 + 17)
    assert set(inst.clean) == set(view["columns"])
    for rule in view["rules"]:
        if "fd" in rule:
            fd = FDModel(rule["name"], rule["fd"]["lhs"], rule["fd"]["rhs"], inst.clean)
            assert not fd.violated.any(), rule["name"]
        else:
            dc = DCModel(rule["name"], rule["dc"], inst.clean)
            assert not dc.viol_t1.any() and not dc.viol_t2.any(), rule["name"]
            # and by brute force over a sample of rows
            for row in np.random.default_rng(0).choice(view["rows"], 64, replace=False):
                t1, t2 = dc.partners(int(row))
                assert not t1.any() and not t2.any()


@pytest.mark.parametrize("name, root, table", records.tables())
def test_error_rates_are_the_stated_ones(name, root, table):
    view, inst = table_of(name, root, table, 5)
    n = view["rows"]
    assert inst.rows == n
    for rule, err in view["errors"].items():
        edited = inst.edited[rule]
        attr = err["attr"]
        changed = inst.dirty[attr] != inst.clean[attr]
        assert np.array_equal(changed, edited), rule
        sigma = np.sqrt(err["rows"] * (1 - err["rows"]) / n)
        assert abs(edited.mean() - err["rows"]) < 4 * sigma, rule
    untouched = set(view["columns"]) - {e["attr"] for e in view["errors"].values()}
    for col in untouched:
        assert np.array_equal(inst.dirty[col], inst.clean[col]), col


@pytest.mark.parametrize("name, root, table", records.tables())
def test_rules_are_violated_after_errors(name, root, table):
    view, inst = table_of(name, root, table, 9)
    for rule in view["rules"]:
        if "fd" in rule:
            fd = FDModel(rule["name"], rule["fd"]["lhs"], rule["fd"]["rhs"], inst.dirty)
            assert fd.violated.any(), rule["name"]
        else:
            dc = DCModel(rule["name"], rule["dc"], inst.dirty)
            assert dc.viol_t1.any() and dc.viol_t2.any(), rule["name"]


@pytest.mark.parametrize("name, root, table", records.tables())
def test_a_seed_fixes_the_instance(name, root, table):
    a, b, c = (table_of(name, root, table, s)[1] for s in (3, 3, 4))
    assert all(np.array_equal(a.dirty[k], b.dirty[k]) for k in a.dirty)
    assert not all(np.array_equal(a.dirty[k], c.dirty[k]) for k in a.dirty)


def has_dc(name, root, table):
    return any("dc" in r for r in datagen.tables(datagen.load_config(name, root))[table]["rules"])


DC_TABLES = [p for p in records.tables() if has_dc(*p.values)]


@pytest.mark.parametrize("name, root, table", DC_TABLES)
def test_violators_match_brute_force(name, root, table):
    view, inst = table_of(name, root, table, 21)
    for rule in (r for r in view["rules"] if "dc" in r):
        dc = DCModel(rule["name"], rule["dc"], inst.dirty)
        for row in np.random.default_rng(1).choice(view["rows"], 200, replace=False):
            t1, t2 = dc.partners(int(row))
            assert dc.viol_t1[row] == t1.any()
            assert dc.viol_t2[row] == t2.any()
