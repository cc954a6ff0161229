"""Span tracing for the serving stack (DESIGN.md §13).

One ``Tracer`` is threaded through every layer that does attributable
work — the executor's clean phases, the server's per-ticket serving
stages, the background cleaner's increments, the sharded detection's
shuffle/scan — and collects ``SpanEvent`` records into a thread-safe
bounded ring buffer.  Recording a span never syncs a device value and
never changes what the instrumented code computes (the bit-neutrality
contract, asserted by tests/test_obs.py).

Clock and thread contract:

* timestamps are ``time.perf_counter()`` — one monotone clock shared by
  every thread, so spans from the serving thread, the background cleaner
  and the shuffle path order correctly against each other;
* a span belongs to the thread that closed it, and spans on one thread
  are well-nested (context managers) — which is what lets
  ``obs.export.rollup`` compute exclusive self-times by stack
  subtraction.  Events recorded with an explicit ``thread`` (the
  server's queue-wait spans, which overlap many serving spans) live on
  their own synthetic track precisely to keep the real threads' nesting
  intact.

Open-span stack (enabled tracers only).  Every thread keeps the spans it
has open, outermost first.  A span records its ``span_id`` and the
``parent_id`` of the span open around it, and inherits the request id
``seq`` from it, so every span beneath one served ticket (``daisy.execute``,
its ``execute.*`` phases, the ``clean.*`` phases) carries that ticket's
``seq``.  The stack is also where host work is charged, inclusively, like
``dur``:

* ``to_host(x)`` — the one device-to-host read of the served path, built on
  ``jax.device_get`` — adds ``syncs`` (reads) and ``sync_s`` (seconds
  blocked) to every open span of the calling thread, and counts every
  read, traced or not, in a process-wide counter (``host_reads``);
* JAX's own compile events (one ``jax.monitoring`` listener, installed
  when the first enabled tracer is made) add ``trace_s``, ``lower_s``,
  ``compile_s``, ``compiles`` and ``cache_loads`` the same way.  An event
  nested in another one on the same thread (an inner function traced
  while an outer one is) is charged once, by the innermost.

While enabled, ``span`` also opens a ``jax.profiler.TraceAnnotation``
(named as the span, with its ``span_id``), so a profiler trace shows the
program's spans on its host plane, on the device trace's own clock.

Disabled mode is a strict no-op: ``NULL_TRACER.span(...)`` returns one
shared, immutable context manager and records nothing — no allocation
beyond the kwargs dict at the call site, no lock, no branch in
``__enter__``/``__exit__``, nothing pushed on the stack.  Layers default
their ``tracer`` seam to ``NULL_TRACER``, so an untraced serving loop pays
only that call overhead, and ``to_host`` one counter increment.
"""

from __future__ import annotations

import itertools
import threading
import time
from typing import Dict, List, NamedTuple, Optional

import jax

# JAX's compile events (``jax._src.dispatch``) and the attr each charges
JIT_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/core/compile/backend_compile_duration": "compile_s",
}
CACHE_LOAD_EVENT = "/jax/compilation_cache/cache_hits"


class SpanEvent(NamedTuple):
    """One closed span: ``t0``/``dur`` on the monotone clock
    (``time.perf_counter``), ``thread`` the recording thread's name (or
    the explicit track for externally-timed events), ``attrs`` host-
    scalar annotations (mode, detect_pairs, strip ranges, charged host
    reads and compiles, ...); ``span_id`` is unique in the process and
    ``parent_id`` names the span open around it (0: none)."""

    name: str
    t0: float
    dur: float
    thread: str
    attrs: Dict[str, object]
    span_id: int = 0
    parent_id: int = 0


class _OpenSpans(threading.local):
    """Per-thread accounting state: the open spans, outermost first, and
    the intervals (``time.time``) of the compile events already charged
    while they are open."""

    def __init__(self):
        self.stack: List["_Span"] = []
        self.jit: List[tuple] = []


_OPEN = _OpenSpans()
_IDS = itertools.count(1)


def _charge(stack, key: str, amount) -> None:
    for sp in stack:
        attrs = sp.attrs
        attrs[key] = attrs.get(key, 0) + amount


class _HostReads:
    """Process-wide count of device-to-host reads."""

    __slots__ = ("n", "_lock")

    def __init__(self):
        self.n = 0
        self._lock = threading.Lock()

    def add(self) -> None:
        with self._lock:
            self.n += 1


_READS = _HostReads()


def host_reads() -> int:
    """Device-to-host reads made through ``to_host`` in this process, on
    every thread, since it started."""
    return _READS.n


def to_host(x):
    """Read ``x`` (an array or a pytree of them) to the host with
    ``jax.device_get``, blocking until it is computed; numpy and Python
    values pass through.  Counts the read (``host_reads``) and, when
    spans are open on the calling thread, charges each of them one sync
    and the seconds it blocked."""
    _READS.add()
    stack = _OPEN.stack
    if not stack:
        return jax.device_get(x)
    t0 = time.perf_counter()
    out = jax.device_get(x)
    dt = time.perf_counter() - t0
    _charge(stack, "syncs", 1)
    _charge(stack, "sync_s", dt)
    return out


def _on_jit_span(event: str, start: float, end: float, **_) -> None:
    key = JIT_EVENTS.get(event)
    if key is None:
        return
    local = _OPEN
    if not local.stack:
        return
    # an event reported after the events nested in it (a function traced
    # while another is): charge only the time they did not
    inner, keep = 0.0, []
    for s, e in local.jit:
        if s >= start and e <= end:
            inner += e - s
        else:
            keep.append((s, e))
    keep.append((start, end))
    local.jit = keep
    _charge(local.stack, key, max(end - start - inner, 0.0))
    if key == "compile_s":
        _charge(local.stack, "compiles", 1)


def _on_event(event: str, **_) -> None:
    if event == CACHE_LOAD_EVENT and _OPEN.stack:
        _charge(_OPEN.stack, "cache_loads", 1)


_JIT_LISTENER = threading.Lock()
_jit_listening = False


def _listen_to_jit() -> None:
    """Install the compile-event listener once per process."""
    global _jit_listening
    with _JIT_LISTENER:
        if _jit_listening:
            return
        jax.monitoring.register_event_time_span_listener(_on_jit_span)
        jax.monitoring.register_event_listener(_on_event)
        _jit_listening = True


class _NullSpan:
    """The shared disabled-mode context manager: enter/exit do nothing."""

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def set(self, **attrs) -> None:
        """Ignore late attribute annotations (disabled mode)."""


_NULL_SPAN = _NullSpan()


class _Span:
    """Context manager for one live span: pushed on its thread's open-span
    stack and mirrored as a profiler annotation while open; records into
    its tracer on exit."""

    __slots__ = ("_tracer", "name", "attrs", "t0", "span_id", "parent_id",
                 "_ann")

    def __init__(self, tracer: "Tracer", name: str, attrs: Dict[str, object]):
        self._tracer = tracer
        self.name = name
        self.attrs = attrs

    def set(self, **attrs) -> None:
        """Annotate the span after entry (e.g. a detect path only known
        once dispatch resolved)."""
        self.attrs.update(attrs)

    def __enter__(self) -> "_Span":
        stack = _OPEN.stack
        self.span_id = next(_IDS)
        self.parent_id = 0
        if stack:
            parent = stack[-1]
            self.parent_id = parent.span_id
            if "seq" in parent.attrs:  # the request id flows down
                self.attrs.setdefault("seq", parent.attrs["seq"])
        stack.append(self)
        self._ann = jax.profiler.TraceAnnotation(self.name, span_id=self.span_id)
        self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        dur = time.perf_counter() - self.t0
        self._ann.__exit__(None, None, None)
        stack = _OPEN.stack
        if self in stack:  # absent when exited on another thread
            stack.remove(self)
        if not stack:
            _OPEN.jit = []
        self._tracer._add(SpanEvent(
            self.name, self.t0, dur, threading.current_thread().name,
            self.attrs, self.span_id, self.parent_id,
        ))
        return False


class Tracer:
    """Thread-safe bounded span recorder.

    ``capacity`` bounds the ring buffer: the newest ``capacity`` events
    are kept, older ones are dropped oldest-first (``dropped`` counts
    them), so a long-lived traced server has bounded memory.  All
    mutation happens under one lock; ``span``/``record``/``instant`` are
    safe from any thread.
    """

    def __init__(self, capacity: int = 65536, enabled: bool = True):
        if capacity < 1:
            raise ValueError("tracer capacity must be >= 1")
        self.capacity = capacity
        self.enabled = enabled
        self.created = time.perf_counter()
        self.dropped = 0
        self._lock = threading.Lock()
        self._events: List[SpanEvent] = []
        self._head = 0  # ring start once the buffer saturates
        if enabled:
            _listen_to_jit()

    def __bool__(self) -> bool:
        """Truthiness == enabled, so hot paths can gate optional work
        (building an attrs dict) with ``if tracer:``."""
        return self.enabled

    def span(self, name: str, **attrs):
        """Open a span context manager; the event is recorded when the
        ``with`` block exits.  Returns the shared no-op when disabled."""
        if not self.enabled:
            return _NULL_SPAN
        return _Span(self, name, attrs)

    def record(self, name: str, t0: float, dur: float,
               thread: Optional[str] = None, **attrs) -> None:
        """Record one externally-timed span (``t0`` must come from
        ``time.perf_counter``).  ``thread`` overrides the track — pass a
        synthetic name for events that overlap a real thread's nesting
        (the server's queue-wait spans).  Such a span is not on any open
        stack: it has no parent, charges nothing, and is not mirrored in
        the profiler."""
        if not self.enabled:
            return
        self._add(SpanEvent(
            name, t0, dur,
            thread if thread is not None else threading.current_thread().name,
            attrs, next(_IDS),
        ))

    def _add(self, event: SpanEvent) -> None:
        with self._lock:
            if len(self._events) < self.capacity:
                self._events.append(event)
            else:
                self._events[self._head] = event
                self._head = (self._head + 1) % self.capacity
                self.dropped += 1

    def instant(self, name: str, **attrs) -> None:
        """Record a zero-duration marker (a yield, an overflow retry)."""
        self.record(name, time.perf_counter(), 0.0, **attrs)

    def events(self) -> List[SpanEvent]:
        """Snapshot of buffered events in recording order (thread-safe)."""
        with self._lock:
            return self._events[self._head:] + self._events[:self._head]

    def __len__(self) -> int:
        with self._lock:
            return len(self._events)

    def clear(self) -> None:
        """Drop all buffered events (the ``dropped`` counter survives as
        a lifetime total)."""
        with self._lock:
            self._events = []
            self._head = 0


class NullTracer(Tracer):
    """The always-disabled tracer every instrumentation seam defaults to.

    A real (if degenerate) ``Tracer``, so ``isinstance`` checks and the
    full API hold; ``span`` short-circuits to the shared no-op via the
    base class's ``enabled`` gate and ``record`` drops everything."""

    def __init__(self):
        super().__init__(capacity=1, enabled=False)


NULL_TRACER = NullTracer()
