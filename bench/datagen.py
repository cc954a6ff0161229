"""Shared pieces of the configuration generators under ``bench/configs``.

A configuration is a JSON file of sizes and rules plus a module of the same
name that generates its instance from a seed.  The module exposes
``generate(cfg, rng) -> Instance``: the clean instance, the dirty one that
is served, and which rows each rule's error injection edited.  Everything
is host numpy; the benchmark never imports the program's own generators,
which later changes to the program may alter.

A configuration of one table keeps ``rows``, ``columns``, ``rules`` and
``errors`` at its top level, and its table is named by ``table`` (default:
the configuration's ``name``).  A configuration of several tables lists
them under ``tables``, each entry with its own ``name``, ``rows``,
``columns``, ``rules``, ``errors`` and ``reduced``; its module's
``generate`` returns ``{table: Instance}``.  ``tables(cfg)`` gives each
table's view: the configuration's shared keys (``daisy``, ``server``, ...)
with the table's own on top.

The module may also define ``check_answers`` (see ``bench/reference.py``):
its own reference for answers the shared one cannot read, such as join
lineage.

``small`` holds the sizes the CPU tests run the configuration at, as
overrides (see ``override``).
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Dict

import numpy as np

BENCH = Path(__file__).resolve().parent

# one stream of the seed per purpose, so the same --seed always gives the
# same data and traffic whatever else a run does
STREAM_DATA, STREAM_TRAFFIC, STREAM_SAMPLE, STREAM_ANSWERS = 0, 1, 2, 3


def rng(seed: int, stream: int) -> np.random.Generator:
    if seed < 0:
        raise ValueError(f"seed {seed} must be a whole number >= 0")
    return np.random.default_rng([int(seed), stream])


@dataclasses.dataclass
class Instance:
    clean: Dict[str, np.ndarray]
    dirty: Dict[str, np.ndarray]
    edited: Dict[str, np.ndarray]  # rule name -> bool mask of edited rows

    @property
    def rows(self) -> int:
        return len(next(iter(self.dirty.values())))


def load_config(name: str, root: Path = BENCH) -> dict:
    """The configuration's JSON under ``root/configs``, with the generator
    module found by name (and its ``check_answers``, where it has one)."""
    cfg = json.loads((root / "configs" / f"{name}.json").read_text())
    spec = importlib.util.spec_from_file_location(
        f"bench_config_{name.replace('-', '_')}", root / "configs" / f"{name}.py"
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    cfg["_generate"] = module.generate
    if hasattr(module, "check_answers"):
        cfg["_check_answers"] = module.check_answers
    return cfg


def override(cfg: dict, overrides: dict) -> dict:
    """``cfg`` with the keys of ``overrides`` replaced; under ``tables``,
    ``overrides`` maps a table's name to the keys replaced in its entry."""
    out = {**cfg, **{k: v for k, v in overrides.items() if k != "tables"}}
    if "tables" in overrides:
        per = overrides["tables"]
        unknown = set(per) - {t["name"] for t in cfg["tables"]}
        if unknown:
            raise KeyError(f"{cfg['name']}: no tables {sorted(unknown)}")
        out["tables"] = [{**t, **per.get(t["name"], {})} for t in cfg["tables"]]
    return out


def tables(cfg: dict) -> Dict[str, dict]:
    """Each table's view of the configuration, in the configuration's order
    (the first is the one a query names by default)."""
    if "tables" not in cfg:
        return {cfg.get("table", cfg["name"]): cfg}
    shared = {k: v for k, v in cfg.items() if k != "tables"}
    return {t["name"]: {**shared, **t} for t in cfg["tables"]}


def generate(cfg: dict, seed: int, stream: int = STREAM_DATA) -> Dict[str, Instance]:
    """Every table's instance, by table name."""
    out = cfg["_generate"](cfg, rng(seed, stream))
    if isinstance(out, Instance):
        out = {cfg.get("table", cfg["name"]): out}
    names = list(tables(cfg))
    if set(out) != set(names):
        raise ValueError(f"{cfg['name']}: generated tables {sorted(out)}, "
                         f"configured {names}")
    return {t: out[t] for t in names}


def replace_values(
    r: np.random.Generator, values: np.ndarray, edit: np.ndarray, lo: int, hi: int
) -> np.ndarray:
    """``values`` with each edited entry replaced by a different value drawn
    uniformly from ``lo..hi`` (inclusive)."""
    out = values.copy()
    span = hi - lo + 1
    shift = r.integers(1, span, int(edit.sum()))
    out[edit] = lo + (values[edit] - lo + shift) % span
    return out


def zipf_weights(n: int, s: float) -> np.ndarray:
    """Probabilities of ranks 1..n under a finite Zipf law with exponent s."""
    w = 1.0 / np.arange(1, n + 1, dtype=np.float64) ** s
    return w / w.sum()


def step_function(values: np.ndarray, levels: int) -> np.ndarray:
    """Level 0..levels-1 of each value by its quantile: non-decreasing in the
    value, so an order DC between the two holds."""
    ranks = np.argsort(np.argsort(values, kind="stable"), kind="stable")
    return (ranks * levels // len(values)).astype(np.int32)
