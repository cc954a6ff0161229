"""Reading the program's spans (``repro.obs`` events: name, t0, dur,
thread, attrs) the way the per-layer readers need them.

The self-time arithmetic repeats that of ``repro.obs.export.rollup`` on
purpose: how spans become a metric is part of the yardstick, which a later
change to the program must not move."""

from __future__ import annotations

from typing import Dict, List


def named(spans, name: str) -> List:
    return [s for s in spans if s.name == name]


def self_times(spans) -> Dict[int, float]:
    """Exclusive time of every span (by index): its duration minus that of
    the spans directly nested in it on the same thread."""
    by_thread: Dict[str, List[int]] = {}
    for i, s in enumerate(spans):
        by_thread.setdefault(s.thread, []).append(i)
    out = {i: s.dur for i, s in enumerate(spans)}
    for idx in by_thread.values():
        idx.sort(key=lambda i: (spans[i].t0, -spans[i].dur))
        stack: List[int] = []
        for i in idx:
            s = spans[i]
            while stack and spans[stack[-1]].t0 + spans[stack[-1]].dur <= s.t0:
                stack.pop()
            if stack:
                out[stack[-1]] -= s.dur
            stack.append(i)
    return out
