"""From a profiler trace of the window to device metrics.

The JAX profiler writes one ``.xplane.pb`` per run.  Its events carry
start and duration in nanoseconds from the start of the profiling session,
on one clock for host and device planes.  The benchmark marks the window
with a host annotation (``bench.window``) and records the host's
``perf_counter`` when it opens, which puts the program's spans (taken on
``perf_counter``) and the compile intervals on the trace's clock.

* busy time: the union of the intervals in which an operation runs on a
  device (the ``XLA Ops`` line of each ``/device:TPU:N`` plane), clipped
  to the window, averaged over the devices used;
* kernel time: the summed device durations of the events a kernel name
  matches;
* breakdown: the device operations that took most time (by instruction
  and result shape), and the idle time
  of the devices grouped by what the host was doing then (the innermost
  program span open at the middle of each gap, or a compile).
"""

from __future__ import annotations

import dataclasses
import re
import time
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

WINDOW_NAME = "bench.window"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
# the DC pair-scan kernel as it appears on the device's op line: it has no
# name of its own yet, and it is the only Pallas call on the served path
DC_KERNEL = re.compile(r"tpu_custom_call")
# host activities, innermost first: the first that covers a gap names it
HOST_ORDER = (
    "compile", "clean.detect", "clean.repair", "clean.relax", "clean.mark",
    "clean.ingest_delta", "daisy.execute", "serve.cache_lookup",
    "serve.commit", "serve.batch", "bg.increment", "serve.execute",
    "serve.ingest", "bg.preempted", "serve.idle",
)
TOP = 10
# longest name a breakdown entry keeps
NAME_CHARS = 120
LAYOUT = re.compile(r"\{[^{}]*\}")


def op_label(name: str) -> str:
    """A device op's HLO text cut to its instruction and result shape: the
    trace names each op by its whole instruction, operands and layouts
    included, which runs to thousands of characters."""
    if " = " not in name:
        return name[:NAME_CHARS]
    lhs, rhs = name.split(" = ", 1)
    rhs = LAYOUT.sub("", LAYOUT.sub("", rhs))
    shape = rhs.split(") ", 1)[0] + ")" if rhs.startswith("(") else rhs.split(" ", 1)[0]
    return f"{lhs} {shape}"[:NAME_CHARS]


def start_trace(log_dir: Path) -> None:
    """Device and host activity only: the Python tracer is off, so the
    trace stays small and the serving threads are not slowed."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)


@dataclasses.dataclass
class WindowMark:
    perf_ns: int  # perf_counter when the annotation opened, in ns


def window_annotation(t_start: float, t_end: float) -> WindowMark:
    """Hold the ``bench.window`` annotation open from ``t_start`` to
    ``t_end`` (both ``perf_counter`` seconds) on the calling thread."""
    import jax

    while time.perf_counter() < t_start:
        time.sleep(0.0005)
    ann = jax.profiler.TraceAnnotation(WINDOW_NAME)
    before = time.perf_counter_ns()
    ann.__enter__()
    after = time.perf_counter_ns()
    try:
        time.sleep(max(t_end - time.perf_counter(), 0.0))
    finally:
        ann.__exit__(None, None, None)
    return WindowMark((before + after) // 2)


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float  # averaged over devices
    devices: int
    kernel_s: float  # summed over devices
    kernel_events: int
    device_ops: List[list]
    idle_gaps: List[list]

    @property
    def idle_pct(self) -> float:
        return 100.0 * (1.0 - self.busy_s / self.window_s)

    @property
    def breakdown(self) -> Dict[str, list]:
        return {"device_ops": self.device_ops, "idle_gaps": self.idle_gaps}


def merge(starts: np.ndarray, ends: np.ndarray) -> List[Tuple[float, float]]:
    """The disjoint blocks the intervals cover, in order."""
    if len(starts) == 0:
        return []
    order = np.argsort(starts, kind="stable")
    s, run_end = starts[order], np.maximum.accumulate(ends[order])
    # a block starts where an interval begins after all earlier ones end
    new = np.flatnonzero(s[1:] > run_end[:-1]) + 1
    first, last = np.r_[0, new], np.r_[new - 1, len(s) - 1]
    return [(float(s[a]), float(run_end[b])) for a, b in zip(first, last)]


def covering(mids: np.ndarray, starts: np.ndarray, ends: np.ndarray) -> np.ndarray:
    """For each point, is it inside some interval?"""
    if len(starts) == 0:
        return np.zeros(len(mids), bool)
    order = np.argsort(starts, kind="stable")
    s, run_end = starts[order], np.maximum.accumulate(ends[order])
    idx = np.searchsorted(s, mids, side="right") - 1
    ok = idx >= 0
    out = np.zeros(len(mids), bool)
    out[ok] = run_end[idx[ok]] >= mids[ok]
    return out


def read_events(path: Path):
    """(window, device events, host events) from one ``.xplane.pb``:
    window is (start, end) ns; device events map device id to a list of
    (name, start, end)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    window = None
    devices: Dict[int, list] = {}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                devices[int(m.group(1))] = [
                    (e.name, e.start_ns, e.start_ns + e.duration_ns) for e in line.events
                ]
            elif plane.name.startswith("/host:") and window is None:
                for e in line.events:
                    if e.name == WINDOW_NAME:
                        window = (e.start_ns, e.start_ns + e.duration_ns)
                        break
    return window, devices


def find_trace(log_dir: Path) -> Path:
    found = sorted(Path(log_dir).glob("**/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def reduce_events(
    window: Tuple[float, float],
    devices: Dict[int, list],
    host: Dict[str, Tuple[np.ndarray, np.ndarray]],
    kernel: re.Pattern = DC_KERNEL,
) -> Reduction:
    """The reduction proper, on events already on one clock (ns).

    ``host`` maps an activity name to its (starts, ends) arrays."""
    w0, w1 = window
    busy, kernel_ns, kernel_n = [], 0.0, 0
    op_time: Dict[str, float] = {}
    gap_time: Dict[str, float] = {}
    for _, events in sorted(devices.items()):
        if not events:
            busy.append(0.0)
            continue
        names = [e[0] for e in events]
        s = np.clip(np.array([e[1] for e in events], np.float64), w0, w1)
        e = np.clip(np.array([e[2] for e in events], np.float64), w0, w1)
        keep = e > s
        merged = merge(s[keep], e[keep])
        busy.append(sum(b - a for a, b in merged))
        for name, a, b, k in zip(names, s, e, keep):
            if k:
                label = op_label(name)
                op_time[label] = op_time.get(label, 0.0) + (b - a)
                if kernel.search(name):
                    kernel_ns += b - a
                    kernel_n += 1
        edges = [w0] + [x for ab in merged for x in ab] + [w1]
        gaps = np.array(edges, np.float64).reshape(-1, 2)
        gaps = gaps[gaps[:, 1] > gaps[:, 0]]
        mids = gaps.mean(axis=1)
        label = np.full(len(gaps), "", object)
        for name in list(HOST_ORDER) + sorted(set(host) - set(HOST_ORDER)):
            if name not in host:
                continue
            hit = (label == "") & covering(mids, *host[name])
            label[hit] = name
        label[label == ""] = "no host span"
        for name, (a, b) in zip(label, gaps):
            gap_time[name] = gap_time.get(name, 0.0) + (b - a)
    n_dev = max(len(busy), 1)
    top = sorted(op_time.items(), key=lambda kv: -kv[1])[:TOP]
    gaps_top = sorted(gap_time.items(), key=lambda kv: -kv[1])[:TOP]
    return Reduction(
        window_s=(w1 - w0) / 1e9,
        busy_s=sum(busy) / n_dev / 1e9,
        devices=len(busy),
        kernel_s=kernel_ns / 1e9,
        kernel_events=kernel_n,
        device_ops=[[name, t / 1e9] for name, t in top],
        idle_gaps=[[name, t / n_dev / 1e9] for name, t in gaps_top],
    )


def host_intervals(spans: Sequence, compiles: Sequence, offset_ns: float):
    """Program spans (perf_counter seconds) and compiles (end, seconds) as
    (starts, ends) ns arrays per activity name on the trace's clock."""
    acc: Dict[str, list] = {}
    for s in spans:
        t0 = s.t0 * 1e9 + offset_ns
        acc.setdefault(s.name, []).append((t0, t0 + s.dur * 1e9))
    for end, dur in compiles:
        t1 = end * 1e9 + offset_ns
        acc.setdefault("compile", []).append((t1 - dur * 1e9, t1))
    return {
        name: (np.array([a for a, _ in v]), np.array([b for _, b in v]))
        for name, v in acc.items()
    }


def reduce_trace(log_dir: Path, mark: Optional[WindowMark], spans, compiles) -> Reduction:
    window, devices = read_events(find_trace(log_dir))
    if window is None or mark is None:
        raise RuntimeError("the trace holds no bench.window annotation")
    if not devices:
        raise RuntimeError("the trace holds no device op events")
    offset = window[0] - mark.perf_ns
    return reduce_events(window, devices, host_intervals(spans, compiles, offset))
