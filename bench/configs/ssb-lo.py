"""SSB LINEORDER generator (the ``ssb-lo`` configuration).

All 17 LINEORDER columns with SSB's domains, strings as int32 codes and
dates as ``yyyymmdd`` integers.  The clean instance satisfies the FD
``orderkey -> suppkey`` (one supplier per order) and the Fig. 12 DC
``NOT(t1.extendedprice < t2.extendedprice AND t1.discount > t2.discount)``
(discount a step function of price); errors are then injected as
``ssb-lo.json`` states.
"""

from __future__ import annotations

import numpy as np

from datagen import Instance, replace_values, step_function


def _lines_per_order(r, n_rows, n_orders, lo, hi):
    counts = r.integers(lo, hi + 1, n_orders)
    diff = n_rows - int(counts.sum())
    while diff:
        idx = r.integers(0, n_orders, abs(diff))
        step = 1 if diff > 0 else -1
        room = (counts[idx] + step >= lo) & (counts[idx] + step <= hi)
        idx = np.unique(idx[room])[: abs(diff)]
        counts[idx] += step
        diff -= step * len(idx)
    return counts


def _yyyymmdd(days: np.ndarray, first_year: int) -> np.ndarray:
    """Day numbers from January 1 of ``first_year`` as yyyymmdd integers."""
    span = np.arange(int(days.max()) + 1)
    d = np.datetime64(f"{first_year}-01-01") + span.astype("timedelta64[D]")
    table = np.char.replace(np.datetime_as_string(d, unit="D"), "-", "").astype(np.int32)
    return table[days]


def generate(cfg, r) -> Instance:
    n, n_orders = cfg["rows"], cfg["orders"]
    counts = _lines_per_order(r, n, n_orders, *cfg["lines_per_order"])
    order = np.repeat(np.arange(n_orders, dtype=np.int32), counts)
    line = (np.arange(n) - np.repeat(np.cumsum(counts) - counts, counts) + 1)

    y0, y1 = cfg["orderdate_years"]
    n_days = int((np.datetime64(f"{y1 + 1}-01-01") - np.datetime64(f"{y0}-01-01"))
                 .astype(int))
    order_day = r.integers(0, n_days, n_orders)
    day = order_day[order]
    c0, c1 = cfg["commit_days"]
    commit_day = day + r.integers(c0, c1 + 1, n)

    quantity = r.integers(cfg["quantity"][0], cfg["quantity"][1] + 1, n)
    p0, p1 = cfg["retail_price"]
    retail = np.round(r.uniform(p0, p1, n), 2)
    extendedprice = (quantity * retail).astype(np.float32)
    d0, d1 = cfg["discount"]
    discount = d0 + step_function(extendedprice, d1 - d0 + 1)
    ordtotal = np.bincount(order, weights=extendedprice, minlength=n_orders)

    clean = {
        "orderkey": order,
        "linenumber": line.astype(np.int32),
        "custkey": r.integers(0, cfg["customers"], n_orders).astype(np.int32)[order],
        "partkey": r.integers(0, cfg["parts"], n).astype(np.int32),
        "suppkey": r.integers(0, cfg["suppliers"], n_orders).astype(np.int32)[order],
        "orderdate": _yyyymmdd(day, y0),
        "orderpriority": r.integers(0, cfg["order_priorities"], n_orders)
        .astype(np.int32)[order],
        "shippriority": np.zeros(n, np.int32),
        "quantity": quantity.astype(np.int32),
        "extendedprice": extendedprice,
        "ordtotalprice": ordtotal.astype(np.float32)[order],
        "discount": discount.astype(np.int32),
        "revenue": (extendedprice * (100 - discount) / 100).astype(np.float32),
        "supplycost": (0.6 * retail).astype(np.float32),
        "tax": r.integers(cfg["tax"][0], cfg["tax"][1] + 1, n).astype(np.int32),
        "commitdate": _yyyymmdd(commit_day, y0),
        "shipmode": r.integers(0, cfg["ship_modes"], n).astype(np.int32),
    }
    perm = r.permutation(n)
    clean = {k: v[perm] for k, v in clean.items()}

    dirty = dict(clean)
    edited = {}
    fd_err = cfg["errors"]["lo_fd"]
    edit = r.random(n) < fd_err["rows"]
    dirty["suppkey"] = replace_values(
        r, clean["suppkey"], edit, 0, cfg["suppliers"] - 1
    )
    edited["lo_fd"] = edit
    dc_err = cfg["errors"]["lo_dc"]
    edit = r.random(n) < dc_err["rows"]
    dirty["discount"] = replace_values(r, clean["discount"], edit, d0, d1)
    edited["lo_dc"] = edit
    return Instance(clean, dirty, edited)
