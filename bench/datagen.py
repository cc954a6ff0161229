"""Shared pieces of the configuration generators under ``bench/configs``.

A configuration is a JSON file of sizes and rules plus a module of the same
name that generates its instance from a seed.  The module exposes
``generate(cfg, seed) -> Instance``: the clean instance, the dirty one that
is served, and which rows each rule's error injection edited.  Everything
is host numpy; the benchmark never imports the program's own generators,
which later changes to the program may alter.
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


def load_config(name: str) -> dict:
    """The configuration's JSON, with the generator module found by name."""
    path = BENCH / "configs" / f"{name}.json"
    cfg = json.loads(path.read_text())
    spec = importlib.util.spec_from_file_location(
        f"bench_config_{name.replace('-', '_')}", BENCH / "configs" / f"{name}.py"
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    cfg["_generate"] = module.generate
    return cfg


def generate(cfg: dict, seed: int, stream: int = STREAM_DATA) -> Instance:
    return cfg["_generate"](cfg, rng(seed, stream))


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
