"""Reduce a profiler trace (``.xplane.pb``) to the numbers the metrics read.

The device's busy time is the union of the intervals in which an
operation ran, taken on each TPU plane's op line and averaged over the
chips; idle share is one minus busy over the window, where the window is
the host span the harness opens around its measured window.  Each idle gap
is named by the innermost host span open over its middle, among the names
the caller passes.  Only ``jax.profiler.ProfileData`` is used.
"""
from __future__ import annotations

import dataclasses
import glob
import os

DEVICE_PREFIX = "/device:TPU:"
OP_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float  # union of op intervals in the window, averaged over chips
    op_s: dict  # op name -> summed device seconds in the window, over chips
    gaps: list  # (span name, seconds), longest first
    n_devices: int

    def ops_matching(self, needles) -> float:
        """Summed device seconds of the ops whose name contains a needle."""
        return sum(s for name, s in self.op_s.items() if any(n in name for n in needles))


def find(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {trace_dir}, found {paths}")
    return paths[0]


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def reduce(path: str, window_span: str, span_names=()) -> Reduced:
    """Reduce the trace file at ``path``; see :func:`reduce_profile`."""
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(path), window_span, span_names)


def reduce_profile(pd, window_span: str, span_names=()) -> Reduced:
    """``pd`` is a ``jax.profiler.ProfileData``; ``window_span`` names the
    host span that bounds the window, ``span_names`` the host spans a gap
    may be named by."""
    host, devices = [], []
    for plane in pd.planes:
        if plane.name == HOST_PLANE:
            host = [(e.name, e.start_ns, e.end_ns) for line in plane.lines for e in line.events]
        elif plane.name.startswith(DEVICE_PREFIX):
            ops = [(e.name, e.start_ns, e.end_ns)
                   for line in plane.lines if line.name == OP_LINE for e in line.events]
            if ops:
                devices.append(ops)
    windows = [(s, e) for name, s, e in host if name == window_span]
    if len(windows) != 1:
        raise ValueError(f"expected one host span {window_span!r}, found {len(windows)}")
    w0, w1 = windows[0]
    if not devices:
        raise ValueError(f"no {DEVICE_PREFIX}* plane with an {OP_LINE!r} line in the trace")
    spans = sorted((s, e, n) for n, s, e in host if n in span_names)
    op_s: dict = {}
    busy = 0.0
    gaps = []
    for ops in devices:
        clipped = [(n, max(s, w0), min(e, w1)) for n, s, e in ops if e > w0 and s < w1]
        for n, s, e in clipped:
            op_s[n] = op_s.get(n, 0.0) + (e - s) * 1e-9
        merged = _union([(s, e) for _, s, e in clipped])
        busy += sum(e - s for s, e in merged) * 1e-9
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                gaps.append((_name_at((s + e) / 2, spans), (e - s) * 1e-9))
    gaps.sort(key=lambda g: -g[1])
    n = len(devices)
    return Reduced(window_s=(w1 - w0) * 1e-9, busy_s=busy / n, op_s=op_s, gaps=gaps, n_devices=n)


def _name_at(t: float, spans) -> str:
    """The innermost (latest-starting) span open at ``t``."""
    name = "no span"
    for s, e, n in spans:
        if s > t:
            break
        if e >= t:
            name = n
    return name
