"""The cells of ``BENCHMARK.json`` that were measured before the harness
served configurations of several tables send the same queries and serve the
same instances: ``data/golden.json`` holds what the harness built for them
then (see its ``about``)."""

import hashlib
import itertools
import json
from pathlib import Path

import numpy as np
import pytest

import datagen
import run
import traffic
from repro.core.operators import query_fingerprint

GOLDEN = json.loads((Path(__file__).resolve().parent / "data" / "golden.json").read_text())


@pytest.mark.parametrize("cell_name, stream", [
    (cell, stream) for cell, streams in GOLDEN["queries"].items() for stream in streams
])
def test_cells_send_the_recorded_queries(cell_name, stream):
    want = GOLDEN["queries"][cell_name][stream]
    seed, session = (int(x) for x in stream.split("/"))
    cell = run.find_cell(run.load_benchmark(), cell_name)
    cfg = datagen.load_config(cell["config"])
    mix = traffic.Mix(traffic.load_mix(cell["traffic"]), cfg.get("domains", {}),
                      next(iter(datagen.tables(cfg))))
    specs = mix.session_stream([seed, datagen.STREAM_TRAFFIC, session])
    n = len(want["fingerprints"].split())
    # what a session submits
    queries = [run.build_query(s) for s in itertools.islice(specs, n)]
    assert " ".join(query_fingerprint(q) for q in queries) == want["fingerprints"]
    got = hashlib.sha256("\n".join(map(repr, queries)).encode()).hexdigest()
    assert got == want["repr_sha256"]


def digest(a: np.ndarray) -> str:
    a = np.ascontiguousarray(a)
    return hashlib.sha256(str(a.dtype).encode() + a.tobytes()).hexdigest()


@pytest.mark.parametrize("name, seed", [
    (name, seed) for name, rec in GOLDEN["instances"].items() for seed in rec["seeds"]
])
def test_configs_generate_the_recorded_instances(name, seed):
    want = GOLDEN["instances"][name]
    cfg = datagen.load_config(name)
    assert cfg["small"] == want["small"]
    insts = datagen.generate(datagen.override(cfg, cfg["small"]), int(seed))
    assert list(insts) == list(want["seeds"][seed])
    for table, inst in insts.items():
        got = {f"{part}.{k}": digest(v)
               for part in ("clean", "dirty", "edited")
               for k, v in getattr(inst, part).items()}
        assert got == want["seeds"][seed][table], table
