"""The ``execute_filter_evals_per_miss`` reader on hand-made spans."""

import importlib.util
import math
from pathlib import Path
from types import SimpleNamespace

from repro.obs import SpanEvent

READER = Path(__file__).resolve().parents[1] / "layer_metrics" / "execute_filter_evals_per_miss.py"


def read(spans):
    spec = importlib.util.spec_from_file_location("m_filter_evals", READER)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read(SimpleNamespace(spans=spans))


def execute(span_id, **attrs):
    return SpanEvent("daisy.execute", float(span_id), 0.01, "serving", attrs, span_id, 0)


def test_evals_per_miss():
    spans = [
        execute(1, filter_evals=1, filter_reuses=2),
        execute(2, filter_evals=2, filter_reuses=1),
        SpanEvent("execute.filter", 0.0, 0.001, "serving", {}, 3, 1),
    ]
    assert math.isclose(read(spans), 1.5)


def test_no_reading_without_the_count():
    # the spans of a program that records ids but not the count
    assert read([execute(1, syncs=3)]) is None
    assert read([]) is None
