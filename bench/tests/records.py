"""What the bench tests cover, read from the records and not listed here:
the configurations and cells of ``BENCHMARK.json``, and those of the toy
star under ``data/toy-star`` (a multi-table configuration served by the
same harness, kept out of ``BENCHMARK.json``).  Each entry comes with the
directory that holds its ``configs`` and ``traffic``."""

import json
from pathlib import Path

import pytest

import datagen

BENCH = Path(__file__).resolve().parents[1]
TOY = Path(__file__).resolve().parent / "data" / "toy-star"
RECORDS = ((BENCH.parent / "BENCHMARK.json", BENCH), (TOY / "benchmark.json", TOY))


def _records():
    for path, root in RECORDS:
        yield json.loads(path.read_text()), root


def configs():
    """``(name, root)`` of every configuration."""
    return [pytest.param(c["name"], root, id=c["name"])
            for rec, root in _records() for c in rec["configs"]]


def tables():
    """``(name, root, table)`` of every table of every configuration."""
    out = []
    for rec, root in _records():
        for c in rec["configs"]:
            for table in datagen.tables(datagen.load_config(c["name"], root)):
                out.append(pytest.param(c["name"], root, table, id=f"{c['name']}:{table}"))
    return out


def cells():
    """``(cell, root)`` of every cell."""
    return [pytest.param(cell, root, id=cell["name"])
            for rec, root in _records() for cell in rec["workloads"]]


def small(name: str, root: Path = BENCH, scale: int = 1) -> dict:
    """The configuration at the size its ``small`` states, with each of
    those sizes (the whole numbers, a table's among them) times ``scale``."""
    cfg = datagen.load_config(name, root)
    return datagen.override(cfg, _scaled(cfg.get("small", {}), scale))


def _scaled(overrides: dict, scale: int) -> dict:
    out = {}
    for k, v in overrides.items():
        if k == "tables":
            v = {t: _scaled(o, scale) for t, o in v.items()}
        elif isinstance(v, int):
            v = v * scale
        out[k] = v
    return out
