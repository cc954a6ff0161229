"""A toy star (the ``toy-star`` test configuration): a fact table
``orders`` joined to one dimension ``supplier`` on ``suppkey``.

The clean fact satisfies FD ``orderkey -> suppkey`` (one supplier per
order), so its errors make the join key probabilistic, and the Fig. 12 DC
``NOT(t1.price < t2.price AND t1.discount > t2.discount)`` of the Daisy
paper (discount a step function of price), so the DC kernel cleans the
fact under the join.  The clean dimension satisfies FD ``city -> region``.
``check_answers`` is this configuration's reference for its join answers.
"""

from __future__ import annotations

import numpy as np

from datagen import Instance, replace_values, step_function, tables

# rounding room of float32 group sums, as in the shared reference
REL_TOL = 1e-4
ABS_TOL = 1e-3


def generate(cfg, r):
    views = tables(cfg)
    fact, dim = views["orders"], views["supplier"]
    n_s, n_o = dim["rows"], cfg["orders"]

    city = np.arange(n_s, dtype=np.int32) % cfg["cities"]
    region = (city % cfg["regions"]).astype(np.int32)
    clean_s = {"suppkey": np.arange(n_s, dtype=np.int32), "city": city, "region": region}
    edit_s = r.random(n_s) < dim["errors"]["s_fd"]["rows"]
    dirty_s = dict(clean_s, region=replace_values(r, region, edit_s, 0, cfg["regions"] - 1))

    n = fact["rows"]
    order = r.integers(0, n_o, n).astype(np.int32)
    y0, y1 = cfg["years"]
    d0, d1 = cfg["discount"]
    price = np.round(r.uniform(*cfg["price"], n), 2).astype(np.float32)
    clean_o = {
        "orderkey": order,
        "suppkey": r.integers(0, n_s, n_o).astype(np.int32)[order],
        "year": r.integers(y0, y1 + 1, n).astype(np.int32),
        "price": price,
        "discount": (d0 + step_function(price, d1 - d0 + 1)).astype(np.int32),
        "revenue": np.round(r.uniform(1.0, 100.0, n), 2).astype(np.float32),
    }
    edit_fd = r.random(n) < fact["errors"]["o_fd"]["rows"]
    edit_dc = r.random(n) < fact["errors"]["o_dc"]["rows"]
    dirty_o = dict(
        clean_o,
        suppkey=replace_values(r, clean_o["suppkey"], edit_fd, 0, n_s - 1),
        discount=replace_values(r, clean_o["discount"], edit_dc, d0, d1),
    )
    return {
        "orders": Instance(clean_o, dirty_o, {"o_fd": edit_fd, "o_dc": edit_dc}),
        "supplier": Instance(clean_s, dirty_s, {"s_fd": edit_s}),
    }


def _rows(sem, preds):
    """Rows that must qualify, and rows that may, by the shared
    reference's masks."""
    if not preds:
        n = len(next(iter(sem.data.values())))
        return np.ones(n, bool), np.ones(n, bool)
    exact, fixed, kept, possible = sem.pred_masks(preds)
    return exact & (fixed | kept), possible


def _keys(left, lcol, right, rcol):
    """Pairs whose key candidates must overlap (equal values, each kept
    among its row's candidates), and pairs whose may (some value both
    rows can take: Daisy section 4's possible-world join)."""
    lv, rv = left.data[lcol], right.data[rcol]
    must = ((lv[:, None] == rv[None, :]) & left.keeps_original(lcol)[:, None]
            & right.keeps_original(rcol)[None, :])
    may = np.zeros_like(must)
    # every value a right key can take is some right row's value
    for v in np.unique(rv):
        may |= (left.pred_masks(((lcol, "==", v),))[3][:, None]
                & right.pred_masks(((rcol, "==", v),))[3][None, :])
    return must, may


def check_answers(tables, sems, answers):
    """Pairs of the join outside the bounds (duplicates and row ids out of
    range included), and groups whose count or sum lies outside the
    bounds of the pairs that must and may qualify."""
    wrong = 0
    for q, result in answers:
        (j,) = q.joins
        left, right = sems[q.table], sems[j.right]
        must_l, may_l = _rows(left, q.preds)
        must_r, may_r = _rows(right, j.preds)
        must_k, may_k = _keys(left, j.left_on, right, j.right_on)
        must = must_l[:, None] & must_r[None, :] & must_k
        may = may_l[:, None] & may_r[None, :] & may_k

        valid = np.asarray(result.join.valid)
        li = np.asarray(result.join.rows[q.table])[valid]
        ri = np.asarray(result.join.rows[j.right])[valid]
        inside = (li >= 0) & (li < must.shape[0]) & (ri >= 0) & (ri < must.shape[1])
        wrong += int(np.sum(~inside))
        count = np.zeros(must.shape, np.int64)
        np.add.at(count, (li[inside], ri[inside]), 1)
        got = count > 0
        wrong += int(np.sum(count - got))  # a pair twice
        wrong += int(np.sum(got & ~may)) + int(np.sum(must & ~got))
        if q.groupby is not None:
            wrong += _check_groups(q, left.data, must, may, result.groups)
    return {"join_pairs_wrong": {"value": wrong, "limit": 0}}


def _check_groups(q, data, must, may, groups) -> int:
    """Group-by over one fact-table key with a count or a sum of a column
    no rule repairs: each group lies between its must and may pairs."""
    (key,), agg, value = q.groupby
    kv = data[key].astype(np.int64)
    size = int(kv.max()) + 1
    x = data[value].astype(np.float64) if agg == "sum" else None
    bounds = []
    for pairs in (must, may):
        per_row = pairs.sum(axis=1)
        bounds.append((np.bincount(kv, weights=per_row, minlength=size),
                       None if x is None else
                       np.bincount(kv, weights=per_row * x, minlength=size)))
    (c_lo, a_lo), (c_hi, a_hi) = bounds
    num = int(np.asarray(groups["num_groups"]))
    got_k = np.asarray(groups[f"key_{key}"])[:num].astype(np.int64)
    got_c = np.asarray(groups["count"])[:num].astype(np.float64)
    got_a = np.asarray(groups["agg"])[:num].astype(np.float64)
    inside = (got_k >= 0) & (got_k < size)
    wrong = int(np.sum(~inside & (got_c > ABS_TOL)))
    count, total = np.zeros(size), np.zeros(size)
    np.add.at(count, got_k[inside], got_c[inside])
    np.add.at(total, got_k[inside], got_a[inside])
    checks = [(count, c_lo, c_hi)] + ([] if x is None else [(total, a_lo, a_hi)])
    for got, lo, hi in checks:
        tol = ABS_TOL + REL_TOL * hi
        wrong += int(np.sum((got < lo - tol) | (got > hi + tol)))
    return wrong
