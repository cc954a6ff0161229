"""The DC kernel's share of its HBM roofline: the compulsory bytes of the
worklists launched in the window (``bench/work.py``, from the launch
geometry the ``clean.detect`` spans record) at the chip's peak HBM
bandwidth, over the kernel's device time.  HBM bound only: the chip
publishes no rate for the vector compares the kernel spends its time on,
so no compute bound is claimed."""

import math

from work import dc_widths, launch_bytes


def read(ctx):
    if ctx.trace is None or ctx.trace.kernel_events == 0:
        return None
    dcs = {r["name"]: r["dc"] for r in ctx.cfg["rules"] if "dc" in r}
    block = int(ctx.cfg["daisy"].get("dc_block", 256))
    nb = math.ceil(ctx.cfg["rows"] / block)
    widths = {name: dc_widths(atoms, ctx.data) for name, atoms in dcs.items()}
    total = 0
    for s in ctx.spans:
        a = s.attrs
        if s.name != "clean.detect" or a.get("rule") not in dcs:
            continue
        if not a.get("tiles_launched"):
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


def _side(ids, blocks, nb):
    if ids is not None:
        return int(ids)
    if blocks is not None:
        return int(blocks[1]) - int(blocks[0])
    return nb
