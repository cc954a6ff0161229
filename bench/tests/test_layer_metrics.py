"""The per-miss executor readers, the admission reader and the DC scan's
roofline reader, on hand-made spans: what they compute, and that a program
whose spans carry no charges gets no reading rather than a zero."""

import importlib.util
import math
from collections import namedtuple
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from repro.obs import SpanEvent

METRICS = Path(__file__).resolve().parents[1] / "layer_metrics"
# a span as a program without the open-span stack records it
OldSpan = namedtuple("OldSpan", "name t0 dur thread attrs")


def reader(name):
    spec = importlib.util.spec_from_file_location(f"m_{name}", METRICS / f"{name}.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def execute(t0, dur, span_id, **attrs):
    return SpanEvent("daisy.execute", t0, dur, "serving", attrs, span_id, 0)


SPANS = [
    execute(0.0, 0.040, 1, syncs=20, sync_s=0.004, trace_s=0.001,
            lower_s=0.002, compile_s=0.003, seq=0),
    # a miss with nothing charged: no reads, no compiles
    execute(1.0, 0.010, 2, seq=1),
    SpanEvent("clean.detect", 0.01, 0.02, "serving",
              {"syncs": 5, "sync_s": 0.001}, 3, 1),
    SpanEvent("serve.cache_lookup", 2.0, 0.001, "serving", {"hit": True}, 4, 0),
]


@pytest.mark.parametrize("name, expected", [
    ("execute_host_ms_per_miss", 1e3 * ((0.040 - 0.010) + 0.010) / 2),
    ("execute_sync_ms_per_miss", 1e3 * 0.004 / 2),
    ("execute_syncs_per_miss", 20 / 2),
    ("execute_jit_ms_per_miss", 1e3 * 0.006 / 2),
])
def test_per_miss_readers(name, expected):
    value = reader(name)(SimpleNamespace(spans=SPANS))
    assert math.isclose(value, expected)


@pytest.mark.parametrize("name", [
    "execute_host_ms_per_miss", "execute_sync_ms_per_miss",
    "execute_syncs_per_miss", "execute_jit_ms_per_miss",
])
def test_per_miss_readers_report_nothing_without_charges(name):
    read = reader(name)
    old = [OldSpan("daisy.execute", 0.0, 0.04, "serving", {"seq": 0})]
    assert read(SimpleNamespace(spans=old)) is None
    assert read(SimpleNamespace(spans=SPANS[3:])) is None  # no miss


def test_queue_admit_p95_ms():
    read = reader("queue_admit_p95_ms")
    waits = [
        SpanEvent("serve.queue_wait", float(i), 0.01, "queue",
                  {"seq": i, "admit_s": i / 1e3}, i + 1, 0)
        for i in range(101)
    ]
    assert math.isclose(read(SimpleNamespace(spans=waits)), 95.0)
    old = [OldSpan("serve.queue_wait", 0.0, 0.01, "queue", {"seq": 0})]
    assert read(SimpleNamespace(spans=old)) is None


def detect(table, rule, span_id, **attrs):
    return SpanEvent("clean.detect", 0.1 * span_id, 0.01, "serving",
                     {"table": table, "rule": rule, **attrs}, span_id, 0)


def test_dc_scan_roofline_pct_takes_each_span_from_its_table():
    """Two tables, each with its own rows, block and DC: each launch's
    bytes come from its own table's widths and geometry."""
    dc_a = [["x", "<", "x"], ["y", ">", "y"]]
    dc_b = [["s", "==", "s"], ["u", "<", "u"], ["v", ">", "v"]]
    tables = {
        "a": SimpleNamespace(
            cfg={"rows": 1024, "daisy": {}, "rules": [
                {"name": "a_fd", "fd": {"lhs": ["x"], "rhs": "y"}},
                {"name": "a_dc", "dc": dc_a}]},
            data={"x": np.arange(1024) % 101,  # 1 byte
                  "y": np.linspace(0.1, 1.0, 1024, dtype=np.float32)}),  # 4 bytes
        "b": SimpleNamespace(
            cfg={"rows": 512, "daisy": {"dc_block": 128}, "rules": [
                {"name": "b_dc", "dc": dc_b}]},
            data={"s": np.arange(512) % 3,  # equality only, 3 ranks: 1 byte
                  "u": np.arange(512) * 2,  # 2 bytes
                  "v": (np.arange(512) % 64 / 4).astype(np.float32)}),  # bf16: 2 bytes
    }
    spans = [
        detect("a", "a_fd", 1),  # an FD launches no tiles
        detect("a", "a_dc", 2, tiles_launched=12, row_block_ids=3),  # x 4 blocks
        detect("a", "a_dc", 3, tiles_launched=6, row_blocks=(0, 2), col_blocks=(1, 4)),
        detect("b", "b_dc", 4, tiles_launched=8, row_block_ids=4, col_block_ids=2),
    ]
    # a: 256 * (1 + 4 + 1 scope) per block side, 256 * (2 + 2*2) * 4 out per row block
    a = (3 + 4) * 1536 + 3 * 6144 + (2 + 3) * 1536 + 2 * 6144
    # b: 128 * (1 + 2 + 2 + 1) per block side, 128 * (2 + 2*3) * 4 out per row block
    b = (4 + 2) * 768 + 4 * 4096
    ctx = SimpleNamespace(
        spans=spans, tables=tables, peaks={"hbm_bytes_per_s": 1e9},
        trace=SimpleNamespace(kernel_events=3, kernel_s=1e-3),
    )
    value = reader("dc_scan_roofline_pct")(ctx)
    assert math.isclose(value, 100.0 * (a + b) / 1e9 / 1e-3)
