"""The trace reduction: on hand-made intervals, and on a small trace
recorded on a TPU v5 lite (three DC kernel scans with host gaps between
them, inside the ``bench.window`` annotation; ``data/small.xplane.pb`` with
the host spans of that run in ``data/small_host.json``)."""

import json
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

import reduce_trace as rt

DATA = Path(__file__).resolve().parent / "data"


def test_merge_and_union():
    s = np.array([0.0, 5, 1, 20, 30])
    e = np.array([2.0, 10, 3, 25, 30])
    assert rt.merge(s, e) == [(0.0, 3.0), (5.0, 10.0), (20.0, 25.0), (30.0, 30.0)]
    assert rt.merge(np.array([]), np.array([])) == []


def test_covering():
    mids = np.array([1.0, 4, 12, 22, -1])
    got = rt.covering(mids, np.array([0.0, 10, 20]), np.array([2.0, 15, 21]))
    assert got.tolist() == [True, False, True, False, False]


def test_reduce_events_busy_kernel_and_gaps():
    window = (0.0, 100.0)
    devices = {0: [("fusion.1", -5.0, 10.0), ("%tpu_custom_call.1 = (s32[8,1,256])", 20.0, 40.0),
                   ("fusion.2", 30.0, 50.0), ("copy", 90.0, 120.0)]}
    host = {
        "compile": (np.array([55.0]), np.array([70.0])),
        "clean.detect": (np.array([50.0]), np.array([89.0])),
        "serve.idle": (np.array([10.0]), np.array([20.0])),
    }
    red = rt.reduce_events(window, devices, host, kernel=rt.DC_KERNEL)
    # busy: [0,10] + [20,50] + [90,100] = 50 of 100
    assert red.busy_s == pytest.approx(50e-9)
    assert red.window_s == pytest.approx(100e-9)
    assert red.idle_pct == pytest.approx(50.0)
    assert red.kernel_s == pytest.approx(20e-9) and red.kernel_events == 1
    gaps = dict(red.idle_gaps)
    # gap [10,20] mid 15 -> serve.idle; gap [50,90] mid 70 -> compile (inner)
    assert gaps == {"serve.idle": pytest.approx(10e-9), "compile": pytest.approx(40e-9)}
    ops = dict(red.device_ops)
    assert ops["fusion.1"] == pytest.approx(10e-9)  # clipped to the window
    assert ops["copy"] == pytest.approx(10e-9)


@pytest.mark.parametrize("name, label", [
    ("fusion.1", "fusion.1"),
    ("%fusion.1 = f32[1048576]{0:T(1024)S(1)} fusion(f32[131072,16]{0,1:T(8,128)S(1)} %x), kind=kCustom",
     "%fusion.1 f32[1048576]"),
    ("%tpu_custom_call.1 = (s32[512,1,256]{2,1,0:T(1,128)}, f32[508,1,256]{2,1,0:T(1,128)}) "
     "custom-call(s32[512]{0:T(512)S(1)} %copy-done.7), custom_call_target=\"tpu_custom_call\"",
     "%tpu_custom_call.1 (s32[512,1,256], f32[508,1,256])"),
])
def test_op_label_keeps_instruction_and_shape(name, label):
    assert rt.op_label(name) == label


def test_recorded_chip_trace():
    path = DATA / "small.xplane.pb"
    host = json.loads((DATA / "small_host.json").read_text())
    spans = [SimpleNamespace(**s) for s in host["spans"]]
    window, devices = rt.read_events(path)
    assert window is not None and window[1] > window[0]
    assert list(devices) == [0] and devices[0]
    offset = window[0] - host["perf_ns"]
    red = rt.reduce_events(window, devices, rt.host_intervals(spans, [], offset))
    assert red.devices == 1
    assert 0 < red.busy_s < red.window_s
    # three kernel scans of 8, 16 and 8 row blocks by 64 column blocks
    assert red.kernel_events == 3
    assert 0 < red.kernel_s <= red.busy_s
    gaps = dict(red.idle_gaps)
    # the three 20 ms sleeps between scans are idle and named by their span
    assert gaps["serve.idle"] == pytest.approx(0.06, rel=0.2)
    assert sum(gaps.values()) == pytest.approx(red.window_s - red.busy_s, rel=1e-6)
