"""Per-request span tracing for the serving stack.

Every request moving through an engine leaves a chain of *span events*:

    enqueue -> admit -> prefill -> decode -> complete | evicted | failed

Engines emit through the module-level `emit()` / `span()` entry points;
when no tracer is installed both are a single `is None` check, so the
un-telemetered hot path pays nothing.  An installed `Tracer` keeps a
bounded ring buffer (served by the `/trace` endpoint) and can mirror
every event to a JSONL file for offline tooling.

Timestamps: `t` is `time.perf_counter()` (monotonic — use for intra-
process ordering and durations), `wall` is `time.time()` (epoch — use to
line events up with external logs).  `start_ns` / `end_ns` are
`time.time_ns()` at the phase's start and end (equal for a point event;
where only `dur_s` is known, `start_ns` is `end_ns` less it):
the realtime clock the JAX profiler stamps its host events with and maps
device timestamps onto, so an event lines up with a profiler trace.
`span()` additionally wraps the body in `jax.named_scope` +
`jax.profiler.TraceAnnotation` so device profiles carry the same phase
names as the JSONL stream.
"""

from __future__ import annotations

import contextlib
import json
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import jax

# Canonical phase names, in request-lifecycle order.  `decode_burst` is a
# batch-level event (one per decode wave, not per request) and is excluded
# from per-request chains.
PHASES = ("enqueue", "admit", "prefill", "decode", "forward", "complete", "evicted", "failed")
TERMINAL = ("complete", "evicted", "failed")


@dataclass
class SpanEvent:
    phase: str
    t: float                      # monotonic seconds (time.perf_counter)
    wall: float                   # epoch seconds (time.time)
    start_ns: int                 # epoch nanoseconds (time.time_ns), phase start
    end_ns: int                   # epoch nanoseconds (time.time_ns), phase end
    request: Optional[str] = None
    dur_s: Optional[float] = None
    labels: Dict[str, Any] = field(default_factory=dict)

    def to_dict(self) -> dict:
        d = {"phase": self.phase, "t": self.t, "wall": self.wall,
             "start_ns": self.start_ns, "end_ns": self.end_ns}
        if self.request is not None:
            d["request"] = self.request
        if self.dur_s is not None:
            d["dur_s"] = self.dur_s
        if self.labels:
            d.update(self.labels)
        return d


class Tracer:
    """Bounded ring buffer of span events + optional JSONL mirror."""

    def __init__(self, capacity: int = 2048, jsonl_path: Optional[str] = None):
        self.capacity = int(capacity)
        self._ring: deque = deque(maxlen=self.capacity)
        self._lock = threading.Lock()
        self._file = open(jsonl_path, "a", buffering=1) if jsonl_path else None
        self.jsonl_path = jsonl_path

    def emit(
        self,
        phase: str,
        request: Optional[str] = None,
        dur_s: Optional[float] = None,
        start_ns: Optional[int] = None,
        end_ns: Optional[int] = None,
        **labels: Any,
    ) -> SpanEvent:
        """Record one event; ``end_ns`` defaults to now and ``start_ns``
        to ``end_ns`` less ``dur_s`` (so a point event has the two equal)."""
        end_ns = time.time_ns() if end_ns is None else end_ns
        if start_ns is None:
            start_ns = end_ns - round((dur_s or 0.0) * 1e9)
        ev = SpanEvent(
            phase=phase,
            t=time.perf_counter(),
            wall=time.time(),
            start_ns=start_ns,
            end_ns=end_ns,
            request=request,
            dur_s=dur_s,
            labels=labels,
        )
        with self._lock:
            self._ring.append(ev)
            if self._file is not None:
                self._file.write(json.dumps(ev.to_dict()) + "\n")
        return ev

    def recent(self, n: Optional[int] = None, request: Optional[str] = None) -> List[SpanEvent]:
        with self._lock:
            evs = list(self._ring)
        if request is not None:
            evs = [e for e in evs if e.request == request]
        if n is not None:
            evs = evs[-int(n):]
        return evs

    def phases(self, request: str) -> List[str]:
        """Ordered phase names seen for one request (duplicates collapsed
        to first occurrence) — the span-chain a completeness check asserts."""
        seen: List[str] = []
        for ev in self.recent(request=request):
            if ev.phase not in seen:
                seen.append(ev.phase)
        return seen

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()

    def close(self) -> None:
        with self._lock:
            if self._file is not None:
                self._file.close()
                self._file = None


# -- module-level install point ----------------------------------------------

_tracer: Optional[Tracer] = None


def install(tracer: Optional[Tracer]) -> Optional[Tracer]:
    """Install (or with None, uninstall) the process tracer; returns the
    previous one so callers can restore it."""
    global _tracer
    prev, _tracer = _tracer, tracer
    return prev


def uninstall() -> Optional[Tracer]:
    return install(None)


def current() -> Optional[Tracer]:
    return _tracer


def emit(phase: str, request: Optional[str] = None, dur_s: Optional[float] = None, **labels: Any):
    """Fire-and-forget span event; no-op (one None check) when tracing is off."""
    tr = _tracer
    if tr is None:
        return None
    return tr.emit(phase, request=request, dur_s=dur_s, **labels)


@contextlib.contextmanager
def span(phase: str, request: Optional[str] = None, emit_event: bool = True, **labels: Any):
    """Time a phase and line it up with XLA profiles.

    Wraps the body in `jax.named_scope` + `jax.profiler.TraceAnnotation`
    (so traced HLO and device timelines carry the phase name) and, unless
    `emit_event=False`, emits one event with the measured wall duration
    and the annotation's `start_ns`/`end_ns`.  Yields the event's label
    dict: the body may add labels it only learns while it runs.
    """
    tr = _tracer
    if tr is None:
        yield labels
        return
    start_ns = time.time_ns()
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation(phase), jax.named_scope(phase):
        yield labels
    dur_s = time.perf_counter() - t0
    end_ns = time.time_ns()
    if emit_event:
        tr.emit(phase, request=request, dur_s=dur_s, start_ns=start_ns, end_ns=end_ns, **labels)
