"""The DC kernel's share of its HBM roofline: the compulsory bytes of the
worklists launched in the window (``bench/work.py``, from the launch
geometry the ``clean.detect`` spans record) at the chip's peak HBM
bandwidth, over the kernel's device time.  HBM bound only: the chip
publishes no rate for the vector compares the kernel spends its time on,
so no compute bound is claimed.  Each span's rows, rules and data are its
table's (the span's ``table`` attribute)."""

import math

from work import dc_widths, launch_bytes


def read(ctx):
    if ctx.trace is None or ctx.trace.kernel_events == 0:
        return None
    tables = {}
    total = 0
    for s in ctx.spans:
        a = s.attrs
        if s.name != "clean.detect" or not a.get("tiles_launched"):
            continue
        if a["table"] not in tables:
            tables[a["table"]] = _table(ctx.tables[a["table"]])
        dcs, widths, block, nb = tables[a["table"]]
        if a.get("rule") not in dcs:
            continue
        nrows = _side(a.get("row_block_ids"), a.get("row_blocks"), nb)
        ncols = _side(a.get("col_block_ids"), a.get("col_blocks"), nb)
        if nrows * ncols != a["tiles_launched"]:
            raise ValueError(f"launch geometry {nrows}x{ncols} != {a['tiles_launched']} tiles")
        rule = a["rule"]
        total += launch_bytes(nrows, ncols, block, widths[rule].values(), len(dcs[rule]))
    if total == 0:
        return None
    least_s = total / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / ctx.trace.kernel_s


def _table(view):
    """The table's DCs, their columns' exact widths, block and blocks."""
    dcs = {r["name"]: r["dc"] for r in view.cfg["rules"] if "dc" in r}
    block = int(view.cfg["daisy"].get("dc_block", 256))
    widths = {name: dc_widths(atoms, view.data) for name, atoms in dcs.items()}
    return dcs, widths, block, math.ceil(view.cfg["rows"] / block)


def _side(ids, blocks, nb):
    if ids is not None:
        return int(ids)
    if blocks is not None:
        return int(blocks[1]) - int(blocks[0])
    return nb
