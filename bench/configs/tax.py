"""Tax generator (the ``tax`` configuration).

The 15 Tax attributes of Chu, Ilyas and Papotti (ICDE 2013), strings as
int32 codes.  The clean instance satisfies FD ``zip -> city``, FD
``zip -> state`` and the DC ``NOT(t1.state = t2.state AND t1.salary <
t2.salary AND t1.rate > t2.rate)``; errors are then injected as
``tax.json`` states.
"""

from __future__ import annotations

import numpy as np

from datagen import Instance, replace_values, zipf_weights


def generate(cfg, r) -> Instance:
    n, n_states, n_zips = cfg["rows"], cfg["states"], cfg["zips"]
    w = zipf_weights(n_states, cfg["state_zipf"])
    zips_per_state = np.maximum(1, np.floor(w * n_zips).astype(int))
    zips_per_state[0] += n_zips - zips_per_state.sum()
    zip_state = np.repeat(np.arange(n_states, dtype=np.int32), zips_per_state)
    zip_state = zip_state[r.permutation(n_zips)]  # zip codes in random order
    zip_first = np.zeros(n_states + 1, int)
    order = np.argsort(zip_state, kind="stable")  # zips grouped by state
    zip_first[1:] = np.cumsum(zips_per_state)
    n_cities = max(1, n_zips // cfg["zips_per_city"])
    # cities never cross states: a zip's city is drawn among its state's
    city_first = np.floor(zip_first * n_cities / n_zips).astype(int)
    zip_city = np.empty(n_zips, np.int32)
    for s in range(n_states):
        zs = order[zip_first[s]:zip_first[s + 1]]
        lo, hi = city_first[s], max(city_first[s + 1], city_first[s] + 1)
        zip_city[zs] = r.integers(lo, hi, len(zs))

    state = r.choice(n_states, n, p=w).astype(np.int32)
    pick = r.integers(0, np.iinfo(np.int64).max, n) % zips_per_state[state]
    zipc = order[zip_first[state] + pick].astype(np.int32)
    s0, s1 = cfg["salary"]
    salary = r.integers(s0, s1 + 1, n).astype(np.int32)
    # per-state brackets at salary quantiles; rate non-decreasing in salary
    nb = cfg["brackets"]
    edges = np.quantile(np.arange(s0, s1 + 1), np.arange(1, nb) / nb)
    bracket = np.searchsorted(edges, salary, side="right")
    base = r.integers(0, 8, n_states)
    step = cfg["rate_step"]
    rate = (base[state] + bracket * step).astype(np.float32)
    e0, e1 = cfg["exemptions"]
    exemp = r.integers(e0, e1 + 1, (3, n_states)).astype(np.int32)
    area = r.integers(0, cfg["area_codes_per_state"], n) + state * cfg["area_codes_per_state"]
    clean = {
        "fname": r.integers(0, cfg["first_names"], n).astype(np.int32),
        "lname": r.integers(0, cfg["last_names"], n).astype(np.int32),
        "gender": r.integers(0, 2, n).astype(np.int32),
        "areacode": area.astype(np.int32),
        "phone": r.integers(1_000_000, 10_000_000, n).astype(np.int32),
        "city": zip_city[zipc],
        "state": state,
        "zip": zipc,
        "marital": r.integers(0, 2, n).astype(np.int32),
        "haschild": r.integers(0, 2, n).astype(np.int32),
        "salary": salary,
        "rate": rate,
        "singleexemp": exemp[0][state],
        "marriedexemp": exemp[1][state],
        "childexemp": exemp[2][state],
    }
    dirty = dict(clean)
    edited = {}
    edit = r.random(n) < cfg["errors"]["tax_zc"]["rows"]
    dirty["city"] = replace_values(r, clean["city"], edit, 0, n_cities - 1)
    edited["tax_zc"] = edit
    edit = r.random(n) < cfg["errors"]["tax_zs"]["rows"]
    dirty["state"] = replace_values(r, clean["state"], edit, 0, n_states - 1)
    edited["tax_zs"] = edit
    edit = r.random(n) < cfg["errors"]["tax_dc"]["rows"]
    other = replace_values(r, bracket.astype(np.int64), edit, 0, nb - 1)
    dirty["rate"] = (base[state] + other * step).astype(np.float32)
    edited["tax_dc"] = edit
    return Instance(clean, dirty, edited)
