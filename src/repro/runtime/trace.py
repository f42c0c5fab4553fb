"""Span-level execution tracing for the planner/runtime stack (DESIGN.md §11).

Zero-dependency (stdlib only) so it imports without jax — the hot paths in
`core/lower.py` and `core/bucketing.py` stay importable on machines with no
accelerator stack.  Two kinds of names, one for each side of a jitted step:

* ``scope(name)`` names the operations staged inside a ``jit`` /
  ``shard_map`` body (the model's layers, the ZeRO-3 halves, the
  optimizer, the schedule executor's steps, rounds and folds).  It is
  ``jax.named_scope``: it writes only the ``op_name`` metadata of the
  compiled program, so it costs nothing when the step runs, and a
  profiler trace of the step carries the program's module, so each
  device op can be put under the scope it came from.
* ``Tracer.span(name)`` times host code (the planner, the numpy
  interpreter, checkpoints, the input pipeline).  Whenever a JAX profiler
  session is recording, the span is also a ``jax.profiler.TraceAnnotation``
  of the same name, on the profiler's clock beside the device's ops, so
  an idle gap of the device can be matched to what the host was doing.
  That holds whether or not the ring below is enabled.

The ring and its Chrome exporter are opt-in:

* **Disabled by default.**  The default tracer starts disabled; a span
  then costs one boolean check plus one call that asks whether a profiler
  session is recording (a dictionary lookup before jax is imported), so
  the <2 %% smoke-train-step overhead gate (``benchmarks/telemetry_bench.py``)
  holds with the instrumentation compiled in.
* **Monotonic clocks.**  ``time.perf_counter_ns`` only; wall time never
  enters a duration.
* **Thread safety.**  The open-span *stack* is thread-local (spans nest
  per thread); the finished-span ring is shared behind a lock and spans
  carry the originating thread id so exported traces keep one Chrome
  ``tid`` lane per thread.
* **Ring-buffered.**  At most ``capacity`` finished spans are retained
  (oldest dropped), so a long training run cannot grow memory unboundedly.

A span opened inside traced code would time the tracing, not the device:
such sites use ``scope``.
"""
from __future__ import annotations

import json
import sys
import threading
import time
from collections import deque
from dataclasses import dataclass, field


@dataclass
class Span:
    """One finished span. ``t0``/``t1`` are perf_counter_ns ticks."""
    name: str
    t0: int
    t1: int
    depth: int
    tid: int
    args: dict | None = None

    @property
    def duration_s(self) -> float:
        return (self.t1 - self.t0) / 1e9


class _OpenSpan:
    """Context manager handed out by ``Tracer.span`` while recording."""
    __slots__ = ("_tracer", "name", "t0", "args", "_stack", "_ann")

    def __init__(self, tracer: "Tracer", name: str, args: dict | None):
        self._tracer = tracer
        self.name = name
        self.args = args
        self._stack = tracer._local_stack()
        self.t0 = 0
        self._ann = None

    def __enter__(self) -> "_OpenSpan":
        self._stack.append(self)
        if _recording():
            self._ann = _Annotation(self.name)
            self._ann.__enter__()
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        t1 = time.perf_counter_ns()
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        stack = self._stack
        # pop self (exceptions can skip inner __exit__ only via interpreter
        # errors; defensively unwind to self)
        while stack and stack.pop() is not self:
            pass
        self._tracer._finish(self, t1, depth=len(stack))
        return None


class _NullSpan:
    """Shared no-op context manager for the disabled path."""
    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> None:
        return None


_NULL_SPAN = _NullSpan()


def _no_jax_yet() -> bool:
    """Whether a JAX profiler session is recording. Until jaxlib's profiler
    is imported none can be; once it is, `_recording` becomes its own
    check and `_Annotation` the class behind `jax.profiler.TraceAnnotation`."""
    global _recording, _Annotation
    prof = sys.modules.get("jaxlib._profiler")
    if prof is None:
        return False
    _Annotation = prof.TraceMe
    _recording = prof.TraceMe.is_enabled
    return _recording()


_recording = _no_jax_yet
_Annotation = None


def scope(name: str):
    """Name the operations staged inside the block: use inside a jitted or
    `shard_map`'d body, where a host span would time the tracing. The
    name lands in each op's `op_name` metadata; the program is otherwise
    unchanged."""
    import jax
    return jax.named_scope(name)


class Tracer:
    """Nested-span tracer with a bounded buffer and a Chrome exporter."""

    def __init__(self, capacity: int = 65536, enabled: bool = False):
        if capacity <= 0:
            raise ValueError("capacity must be positive")
        self.enabled = enabled
        self._spans: deque[Span] = deque(maxlen=capacity)
        self._lock = threading.Lock()
        self._tls = threading.local()

    # -- recording ----------------------------------------------------------
    def _local_stack(self) -> list:
        stack = getattr(self._tls, "stack", None)
        if stack is None:
            stack = self._tls.stack = []
        return stack

    def span(self, name: str, **args):
        """Open a host span: ``with tracer.span("plan/lookup", level=lvl):``

        Disabled, it is a profiler annotation of the same name while a JAX
        profiler session records, and a shared null context otherwise.
        """
        if not self.enabled:
            return _Annotation(name) if _recording() else _NULL_SPAN
        return _OpenSpan(self, name, args or None)

    def _finish(self, open_span: _OpenSpan, t1: int, depth: int) -> None:
        span = Span(open_span.name, open_span.t0, t1, depth,
                    threading.get_ident(), open_span.args)
        with self._lock:
            self._spans.append(span)

    def instant(self, name: str, **args) -> None:
        """Record a zero-duration marker (refit fired, plan swapped...)."""
        if not self.enabled:
            return
        now = time.perf_counter_ns()
        depth = len(self._local_stack())
        span = Span(name, now, now, depth, threading.get_ident(),
                    args or None)
        with self._lock:
            self._spans.append(span)

    # -- inspection ---------------------------------------------------------
    @property
    def spans(self) -> list[Span]:
        with self._lock:
            return list(self._spans)

    def clear(self) -> None:
        with self._lock:
            self._spans.clear()

    # -- export -------------------------------------------------------------
    def to_chrome(self, process_name: str = "repro") -> list[dict]:
        """Chrome trace event list: complete ("X") events in microseconds,
        one pid for the process, one tid lane per recording thread."""
        spans = self.spans
        if not spans:
            return []
        t_base = min(s.t0 for s in spans)
        tids = {}
        events: list[dict] = [{
            "name": "process_name", "ph": "M", "pid": 1, "tid": 0,
            "args": {"name": process_name},
        }]
        for s in spans:
            tid = tids.setdefault(s.tid, len(tids))
            ev = {
                "name": s.name,
                "ph": "X",
                "pid": 1,
                "tid": tid,
                "ts": (s.t0 - t_base) / 1e3,
                "dur": (s.t1 - s.t0) / 1e3,
            }
            if s.args:
                ev["args"] = dict(s.args)
            events.append(ev)
        for raw, tid in tids.items():
            events.append({"name": "thread_name", "ph": "M", "pid": 1,
                           "tid": tid, "args": {"name": f"thread-{tid}"}})
        return events

    def export_chrome(self, path: str, process_name: str = "repro") -> int:
        """Write a chrome://tracing-loadable JSON file; returns #events."""
        events = self.to_chrome(process_name)
        with open(path, "w") as f:
            json.dump({"traceEvents": events,
                       "displayTimeUnit": "ms"}, f)
        return len(events)


# ---------------------------------------------------------------------------
# Process-wide default (same pattern as telemetry.default_telemetry)
# ---------------------------------------------------------------------------
_default: Tracer | None = None
_default_lock = threading.Lock()


def default_tracer() -> Tracer:
    """The process-wide tracer (created disabled on first use)."""
    global _default
    if _default is None:
        with _default_lock:
            if _default is None:
                _default = Tracer(enabled=False)
    return _default


def peek_default_tracer() -> Tracer | None:
    """The default tracer if one exists, without creating it."""
    return _default


def set_default_tracer(tracer: Tracer | None) -> Tracer | None:
    """Swap the process-wide tracer (tests, scoped capture); returns old."""
    global _default
    with _default_lock:
        old, _default = _default, tracer
    return old
