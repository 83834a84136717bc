"""Unified telemetry for the serving stack.

Three pillars, each usable alone:

* `obs.metrics`  — dependency-free Counter/Gauge/Histogram registry with
  Prometheus-text and JSON renderers (`docs/observability.md` inventories
  the exported families).
* `obs.trace`    — per-request span events (enqueue → admit → prefill →
  decode → complete/evicted) with a ring buffer + optional JSONL mirror.
* `obs.quant_health` — sampled in-path monitors for the low-bit
  activation pathology (clip rate / scale crest / overflow) per
  `PrecisionPlan` site.

`enable_all()` flips everything on for a serving process (AsyncServer
calls it when started with a metrics port); `disable_all()` restores the
zero-overhead default.  The kernel probe's launch counts are not part of
live telemetry: they are recorded when a graph is traced, not when it
runs, so on a serving process they stop moving after warm-up.
"""

from __future__ import annotations

from typing import Optional

from repro.obs import metrics, quant_health, trace

__all__ = [
    "metrics",
    "trace",
    "quant_health",
    "enable_all",
    "disable_all",
    "enabled",
]


_enabled = False


def enabled() -> bool:
    return _enabled


def enable_all(
    registry: Optional[metrics.Registry] = None,
    trace_capacity: int = 2048,
    trace_path: Optional[str] = None,
    quant_every: int = 64,
) -> trace.Tracer:
    """Turn on live telemetry: inline metrics, span tracing and sampled
    quant-health monitors.

    Idempotent; a tracer already installed is kept unless `trace_path`
    asks for a JSONL mirror it doesn't have.  Returns the active tracer.
    Note jit caches compiled graphs — quant-health monitors only appear
    in forwards traced *after* this call.
    """
    global _enabled
    metrics.set_live(True)
    quant_health.enable(every=quant_every, registry=registry)
    tr = trace.current()
    if tr is None or (trace_path is not None and tr.jsonl_path != trace_path):
        tr = trace.Tracer(capacity=trace_capacity, jsonl_path=trace_path)
        trace.install(tr)
    _enabled = True
    return tr


def disable_all() -> None:
    """Back to the zero-overhead default.  Leaves already-compiled graphs
    as they are (quant-health callbacks baked into a traced graph keep
    firing but drop their samples once disabled here)."""
    global _enabled
    metrics.set_live(False)
    quant_health.disable()
    tr = trace.uninstall()
    if tr is not None:
        tr.close()
    _enabled = False
