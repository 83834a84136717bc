"""In-path quantization-health monitors.

VersaQ-3D's failure mode is silent: a minority of saturated activation
channels (the outlier pathology Fig. 1/4 measures) eats the low-bit
dynamic range and accuracy degrades with no crash to point at.  These
monitors watch the serve-time quantize path and attribute three cheap
signals to `PrecisionPlan` site paths:

* **clip rate** — fraction of elements landing in the extreme quant bin
  (|q| == qmax).  Per-token dynamic scales mean nothing is ever clipped
  *off*, so a high extreme-bin fraction is the live proxy for "one
  outlier channel owns the scale".
* **scale crest** — mean per-token crest factor amax/rms.  High crest =
  the scale is set by a spike far above the typical magnitude, i.e. most
  of the quant grid is wasted (scale saturation).
* **overflow** — count of |round(x/scale)| > qmax before clamping.  With
  symmetric amax scales this is the rounding-edge case at exactly amax;
  a nonzero rate on the packed-int4 path flags values that would wrap if
  the clamp were ever dropped.

Monitoring is OFF by default and costs nothing when off (`enabled()` is
a dict lookup at trace time).  When on, `monitor()` adds a few cheap
elementwise reductions to the traced graph and ships three scalars to
the host via `jax.debug.callback`; the host side counts every arrival
(`quant_health_callbacks_total`) and samples every `every`-th call per
site before touching the rest of the metrics registry.  The host time
spent inside the callbacks is summed process-wide (`host_seconds()`) and
each callback runs under a `quant_health.observe` profiler annotation.
A site inside a scanned stack of blocks carries one name for every
layer of the scan.

Note: enable *before* the forward is traced — jit caches compiled
graphs, so a graph traced while monitoring was off never reports.
Leave monitors off while autotuning/eval_shape-based planning runs.
"""

from __future__ import annotations

import functools
import threading
import time
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.quantize import int_range
from repro.obs import metrics as obs_metrics

_lock = threading.Lock()
_cfg: Dict[str, object] = {"every": 0, "registry": None}
_calls: Dict[str, int] = {}
_host_s = 0.0  # seconds spent inside _observe, process-wide


def enable(every: int = 16, registry: Optional[obs_metrics.Registry] = None) -> None:
    """Turn monitors on, sampling every `every`-th call per site."""
    if every < 1:
        raise ValueError("every must be >= 1")
    with _lock:
        _cfg["every"] = int(every)
        _cfg["registry"] = registry
        _calls.clear()


def disable() -> None:
    with _lock:
        _cfg["every"] = 0
        _cfg["registry"] = None
        _calls.clear()


def enabled() -> bool:
    return _cfg["every"] > 0  # type: ignore[operator]


def _registry() -> obs_metrics.Registry:
    reg = _cfg["registry"]
    return reg if isinstance(reg, obs_metrics.Registry) else obs_metrics.default()


def host_seconds() -> float:
    """Host seconds spent inside the callbacks since the process started
    (every engine's); a caller reads it before and after a forward."""
    with _lock:
        return _host_s


def _observe(site: str, a_bits: int, clip_frac, crest, overflow) -> None:
    """Host-side sink (runs under jax.debug.callback).  Values arrive as
    numpy scalars — or batched arrays under vmap — so reduce defensively."""
    global _host_s
    every = _cfg["every"]
    if not every:
        return
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("quant_health.observe"):
        _record(site, a_bits, int(every), clip_frac, crest, overflow)  # type: ignore[arg-type]
    with _lock:
        _host_s += time.perf_counter() - t0


def _record(site: str, a_bits: int, every: int, clip_frac, crest, overflow) -> None:
    with _lock:
        n = _calls.get(site, 0)
        _calls[site] = n + 1
    reg = _registry()
    lbl = dict(site=site, a_bits=str(a_bits))
    reg.counter(
        "quant_health_callbacks_total", "Quant-health callbacks that reached the host",
        ("site", "a_bits"),
    ).inc(1.0, **lbl)
    if n % every:
        return
    reg.gauge(
        "quant_clip_rate", "Fraction of activations in the extreme quant bin", ("site", "a_bits")
    ).set(float(np.mean(clip_frac)), **lbl)
    reg.gauge(
        "quant_scale_crest", "Mean per-token crest factor amax/rms of quantized activations",
        ("site", "a_bits"),
    ).set(float(np.mean(crest)), **lbl)
    reg.counter(
        "quant_overflow_total", "Pre-clamp |round(x/scale)| > qmax occurrences", ("site", "a_bits")
    ).inc(float(np.sum(overflow)), **lbl)
    reg.counter(
        "quant_health_samples_total", "Quant-health samples recorded", ("site", "a_bits")
    ).inc(1.0, **lbl)


def monitor(site: Optional[str], x, a_bits: int) -> None:
    """Observe the activation tensor a site is about to quantize.

    Call from inside the (possibly jitted) forward; emits nothing when
    monitoring is off or the site is unnamed.  Mirrors the quantizer's
    own scale rule (symmetric per-token amax / qmax — `core.quantize`).
    """
    if site is None or not enabled():
        return
    qmax = float(int_range(int(a_bits))[1])
    xf = jnp.abs(x.astype(jnp.float32))
    amax = jnp.max(xf, axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / qmax
    q = jnp.round(xf / scale)
    clip_frac = jnp.mean((q >= qmax).astype(jnp.float32))
    overflow = jnp.sum((q > qmax).astype(jnp.int32))
    rms = jnp.sqrt(jnp.mean(xf * xf, axis=-1, keepdims=True))
    crest = jnp.mean(amax / (rms + 1e-8))
    jax.debug.callback(
        functools.partial(_observe, str(site), int(a_bits)), clip_frac, crest, overflow
    )


def sites_sampled() -> Dict[str, int]:
    """Host-side call counts per site (mostly for tests/diagnostics)."""
    with _lock:
        return dict(_calls)
