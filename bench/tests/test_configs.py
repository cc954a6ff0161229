"""Each configuration's generator: the clean instance satisfies every rule,
errors go where and as often as the configuration states, and a seed
fixes the instance."""

import numpy as np
import pytest

import datagen
from reference import DCModel, FDModel

SMALL = {
    "ssb-lo": {"rows": 8192, "orders": 2048},
    "tax": {"rows": 8192, "zips": 512},
}


def small(name):
    cfg = datagen.load_config(name)
    cfg.update(SMALL[name])
    return cfg


@pytest.mark.parametrize("name", sorted(SMALL))
def test_clean_instance_satisfies_every_rule(name):
    cfg = small(name)
    inst = datagen.generate(cfg, 2**31 + 17)
    assert set(inst.clean) == set(cfg["columns"])
    for rule in cfg["rules"]:
        if "fd" in rule:
            fd = FDModel(rule["name"], rule["fd"]["lhs"], rule["fd"]["rhs"], inst.clean)
            assert not fd.violated.any(), rule["name"]
        else:
            dc = DCModel(rule["name"], rule["dc"], inst.clean)
            assert not dc.viol_t1.any() and not dc.viol_t2.any(), rule["name"]
            # and by brute force over a sample of rows
            for row in np.random.default_rng(0).choice(cfg["rows"], 64, replace=False):
                t1, t2 = dc.partners(int(row))
                assert not t1.any() and not t2.any()


@pytest.mark.parametrize("name", sorted(SMALL))
def test_error_rates_are_the_stated_ones(name):
    cfg = small(name)
    inst = datagen.generate(cfg, 5)
    n = cfg["rows"]
    for rule, err in cfg["errors"].items():
        edited = inst.edited[rule]
        attr = err["attr"]
        changed = inst.dirty[attr] != inst.clean[attr]
        assert np.array_equal(changed, edited), rule
        sigma = np.sqrt(err["rows"] * (1 - err["rows"]) / n)
        assert abs(edited.mean() - err["rows"]) < 4 * sigma, rule
    untouched = set(cfg["columns"]) - {e["attr"] for e in cfg["errors"].values()}
    for col in untouched:
        assert np.array_equal(inst.dirty[col], inst.clean[col]), col


@pytest.mark.parametrize("name", sorted(SMALL))
def test_rules_are_violated_after_errors(name):
    cfg = small(name)
    inst = datagen.generate(cfg, 9)
    for rule in cfg["rules"]:
        if "fd" in rule:
            fd = FDModel(rule["name"], rule["fd"]["lhs"], rule["fd"]["rhs"], inst.dirty)
            assert fd.violated.any(), rule["name"]
        else:
            dc = DCModel(rule["name"], rule["dc"], inst.dirty)
            assert dc.viol_t1.any() and dc.viol_t2.any(), rule["name"]


@pytest.mark.parametrize("name", sorted(SMALL))
def test_a_seed_fixes_the_instance(name):
    cfg = small(name)
    a, b, c = (datagen.generate(cfg, s) for s in (3, 3, 4))
    assert all(np.array_equal(a.dirty[k], b.dirty[k]) for k in a.dirty)
    assert not all(np.array_equal(a.dirty[k], c.dirty[k]) for k in a.dirty)


def test_violators_match_brute_force():
    cfg = small("tax")
    inst = datagen.generate(cfg, 21)
    rule = next(r for r in cfg["rules"] if "dc" in r)
    dc = DCModel(rule["name"], rule["dc"], inst.dirty)
    for row in np.random.default_rng(1).choice(cfg["rows"], 200, replace=False):
        t1, t2 = dc.partners(int(row))
        assert dc.viol_t1[row] == t1.any()
        assert dc.viol_t2[row] == t2.any()
