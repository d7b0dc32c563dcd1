"""Span-based phase tracing: one clock, JSONL events, Perfetto export.

Parity: the reference scattered its clocks — ``PerformanceListener``
(wall deltas), Spark ``CommonSparkTrainingStats`` (phase timers), the
SBE ``StatsListener`` pipeline (timestamps per report). Here every
host-side phase is a ``span("device_step")`` against ONE process-wide
monotonic origin, so data-load, device-step, collective, checkpoint and
eval time compose into a single timeline.

Outputs:
- every span closes into the registry histogram
  ``dl4j_phase_duration_ms{phase=...}`` (always on — O(µs)/span);
- every span opens a ``jax.profiler.TraceAnnotation`` named
  ``dl4j/<name>``: while a device trace is being taken
  (``util/profiler.trace``) the span lies on the capture's ``/host:CPU``
  plane on the profiler's own clock, beside the device's ops — that is
  the shared clock, no offset is computed
  (``util/profiler.host_spans`` / ``gaps_by_host_span`` read it). With
  no capture running it costs a flag test, and a process that has not
  imported jax (the router, the broker, the UI) opens none: this package
  does not import jax itself;
- with a tracer enabled, spans also append structured JSONL events
  (``scripts/check_telemetry_schema.py`` validates the stream) and
  buffer for Chrome ``trace_event`` export.

Spans form trees: each has an ``id``, the id of the span that was open
on the same thread when it started (``parent``, a thread-local stack)
and ``dispatch``, the id of its tree's root — shared by all spans of one
dispatch (``fit_scan``: ``device_step`` > ``launch``, ``fetch``).
"""

from __future__ import annotations

import itertools
import json
import os
import sys
import threading
import time
from typing import Any, Dict, List, Optional

from deeplearning4j_tpu.monitor.registry import get_registry

# The single process clock origin: every span/event timestamp of the
# JSONL / Chrome exports is microseconds since this module first loaded.
# A util/profiler.py device trace runs on the profiler's clock instead;
# the spans reach it as TraceAnnotations (TRACE_PREFIX), so the two are
# never aligned by an offset.
_ORIGIN = time.perf_counter()

#: prefix of the spans' TraceAnnotations in a device trace's host plane
TRACE_PREFIX = "dl4j/"

PHASE_HISTOGRAM = "dl4j_phase_duration_ms"
_PHASE_HELP = "Host-side phase durations by span name"


def now_us() -> float:
    """Microseconds since the process clock origin (one clock for every
    telemetry consumer in this process)."""
    return (time.perf_counter() - _ORIGIN) * 1e6


def to_origin_us(perf_t: float) -> float:
    """Convert a raw ``time.perf_counter()`` reading to microseconds on
    the process clock origin — lets callers that already hold host-side
    timestamps (request submit times, dispatch starts) emit spans
    post-hoc without re-reading the clock."""
    return (perf_t - _ORIGIN) * 1e6


_span_ids = itertools.count(1)
_open_spans = threading.local()  # .stack: this thread's open spans
_trace_annotation = None  # jax.profiler.TraceAnnotation, once jax is loaded


def _annotation_class():
    """``jax.profiler.TraceAnnotation`` where the process has imported
    jax (only such a process can take a device trace), else ``None``."""
    global _trace_annotation
    if _trace_annotation is None and "jax" in sys.modules:
        from jax.profiler import TraceAnnotation
        _trace_annotation = TraceAnnotation
    return _trace_annotation


class _Span:
    """Context manager for one phase occurrence, made by ``span()``;
    cheap: two perf_counter reads, one TraceAnnotation, one histogram
    observe, plus a JSONL line when a tracer is active."""

    __slots__ = ("name", "attrs", "id", "parent", "dispatch", "_t0",
                 "_tracer", "_annotation")

    def __init__(self, name: str, tracer: Optional["PhaseTracer"],
                 attrs: Dict[str, Any]):
        self.name = name
        self.attrs = attrs
        self.id = self.dispatch = 0
        self.parent: Optional[int] = None
        self._tracer = tracer
        self._t0 = 0.0
        self._annotation = None

    def __enter__(self) -> "_Span":
        try:
            stack = _open_spans.stack
        except AttributeError:
            stack = _open_spans.stack = []
        self.id = next(_span_ids)
        if stack:
            self.parent, self.dispatch = stack[-1].id, stack[-1].dispatch
        else:
            self.dispatch = self.id
        stack.append(self)
        annotation = _trace_annotation or _annotation_class()
        if annotation is not None:
            self._annotation = annotation(
                TRACE_PREFIX + self.name, id=self.id, dispatch=self.dispatch)
            self._annotation.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        t1 = time.perf_counter()
        if self._annotation is not None:
            self._annotation.__exit__(exc_type, exc, tb)
        stack = getattr(_open_spans, "stack", None)  # None: another thread
        if stack and stack[-1] is self:
            stack.pop()
        elif stack and self in stack:  # closed out of order: leave no
            stack.remove(self)         # stale parent behind
        dur_us = (t1 - self._t0) * 1e6
        if exc_type is not None:
            self.attrs = {**self.attrs, "error": exc_type.__name__}
        try:
            get_registry().histogram(
                PHASE_HISTOGRAM, _PHASE_HELP,
                phase=self.name).observe(dur_us / 1e3)
        except Exception:
            pass  # telemetry must never break the training loop
        if self._tracer is not None:
            self._tracer._record_span(self, (self._t0 - _ORIGIN) * 1e6,
                                      dur_us)


class PhaseTracer:
    """Collects span/event records; writes JSONL as they close and
    exports the buffered timeline as Chrome ``trace_event`` JSON."""

    def __init__(self, jsonl_path: Optional[str] = None,
                 max_events: int = 1_000_000):
        self.jsonl_path = jsonl_path
        self.max_events = max_events
        self.dropped = 0
        self._events: List[Dict[str, Any]] = []
        self._lock = threading.Lock()
        self._file = open(jsonl_path, "a") if jsonl_path else None
        self._pid = os.getpid()

    # ------------------------------------------------------------ record

    def _emit(self, record: Dict[str, Any]) -> None:
        with self._lock:
            if len(self._events) < self.max_events:
                self._events.append(record)
            else:
                self.dropped += 1  # never silently pretend full coverage
            if self._file is not None:
                self._file.write(json.dumps(record) + "\n")
                self._file.flush()

    def _record_span(self, span: "_Span", ts_us: float,
                     dur_us: float) -> None:
        rec = {"type": "span", "name": span.name, "ts_us": round(ts_us, 3),
               "dur_us": round(dur_us, 3), "pid": self._pid,
               "tid": threading.get_ident(), "id": span.id,
               "parent": span.parent, "dispatch": span.dispatch}
        if span.attrs:
            rec["attrs"] = span.attrs
        self._emit(rec)

    def event(self, name: str, **attrs) -> None:
        """Instant event (NaN flag, averaging boundary, ...)."""
        rec = {"type": "event", "name": name, "ts_us": round(now_us(), 3),
               "pid": self._pid, "tid": threading.get_ident()}
        if attrs:
            rec["attrs"] = attrs
        self._emit(rec)

    # ------------------------------------------------------------ export

    def events(self) -> List[Dict[str, Any]]:
        with self._lock:
            return list(self._events)

    def chrome_trace(self) -> Dict[str, Any]:
        """Chrome ``trace_event`` JSON object (Perfetto/chrome://tracing).
        Spans are complete events (ph=X), instant events ph=i."""
        trace: List[Dict[str, Any]] = [
            {"ph": "M", "name": "process_name", "pid": self._pid, "tid": 0,
             "args": {"name": "deeplearning4j_tpu host"}}]
        for e in self.events():
            base = {"name": e["name"], "cat": "phase", "pid": e["pid"],
                    "tid": e["tid"], "ts": e["ts_us"],
                    "args": e.get("attrs", {})}
            if e["type"] == "span":
                args = {**base["args"], "id": e["id"], "parent": e["parent"],
                        "dispatch": e["dispatch"]}
                trace.append({**base, "args": args, "ph": "X",
                              "dur": e["dur_us"]})
            else:
                trace.append({**base, "ph": "i", "s": "t"})
        return {"displayTimeUnit": "ms", "traceEvents": trace}

    def export_chrome_trace(self, path: str) -> str:
        with open(path, "w") as f:
            json.dump(self.chrome_trace(), f)
        return path

    def close(self) -> None:
        with self._lock:
            if self._file is not None:
                self._file.close()
                self._file = None


# ------------------------------------------------------------ module API

_active: Optional[PhaseTracer] = None
_active_lock = threading.Lock()
_NO_ATTRS: Dict[str, Any] = {}


def enable_tracing(jsonl_path: Optional[str] = None,
                   max_events: int = 1_000_000) -> PhaseTracer:
    """Install a process-wide tracer; returns it. Replaces (and closes)
    any previous tracer."""
    global _active
    tracer = PhaseTracer(jsonl_path, max_events=max_events)
    with _active_lock:
        old, _active = _active, tracer
    if old is not None:
        old.close()
    return tracer


def disable_tracing() -> Optional[PhaseTracer]:
    """Stop tracing; returns the (closed) tracer so callers can still
    export its buffered timeline."""
    global _active
    with _active_lock:
        old, _active = _active, None
    if old is not None:
        old.close()
    return old


def active_tracer() -> Optional[PhaseTracer]:
    return _active


def span(name: str, **attrs) -> _Span:
    """Time a host-side phase::

        with span("device_step", iteration=i):
            ...

    Always feeds ``dl4j_phase_duration_ms{phase=name}`` and a
    ``dl4j/<name>`` TraceAnnotation; with tracing enabled, also emits a
    JSONL/Perfetto span. Spans opened inside it on the same thread are
    its children. Exceptions propagate (the span closes with an
    ``error`` attr)."""
    return _Span(name, _active, attrs if attrs else _NO_ATTRS)


def mark(name: str, **attrs) -> None:
    """Instant event into the active tracer (no-op when tracing is off)."""
    t = _active
    if t is not None:
        t.event(name, **attrs)
