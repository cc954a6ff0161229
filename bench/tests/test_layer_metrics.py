"""The per-miss executor readers and the admission reader, on hand-made
spans: what they compute, and that a program whose spans carry no charges
gets no reading rather than a zero."""

import importlib.util
import math
from collections import namedtuple
from pathlib import Path
from types import SimpleNamespace

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
