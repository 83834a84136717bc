"""VGGT scene-serving benchmark: one cell of ``BENCHMARK.json`` per run.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  The cell names a configuration and a
traffic mix; both are files found by name (``bench/configs/<name>.json``,
``bench/traffic/<name>.json``), and each per-layer metric is a reader in
``bench/metrics/<name>.py``.  A run

1. refuses to measure without a TPU (exit code 3, no result line);
2. sets up: weights from the seed on the device, the program's server
   (``program.serve``), the traffic's scenes, one round of traffic through
   the server to compile and warm every shape the window uses;
3. measures a closed loop of ``clients`` callers for ``--seconds``: each
   submits a scene, waits for its result and submits the next.  The window
   closes at the first forward that completes after ``--seconds``, and
   counts every scene that forward delivered;
4. with ``--trace 1`` records a profiler trace of the window and reports
   the per-layer metrics that list the cell (one that reads nothing ends
   the run with exit code 4), otherwise its end-to-end metrics;
5. frees the program and compares what the window returned, for a sample
   of its scenes drawn from the seed, with ``reference.py`` (``COMPARED``);
   each number compared is printed beside its limit, on stderr and under
   ``check``.

The last line of stdout is the result as one JSON object.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from collections import deque  # noqa: E402

import numpy as np  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import jax  # noqa: E402

from bench import program, scenes, weights, work, xtrace  # noqa: E402
from bench.reference import Reference  # noqa: E402

RESULT_TIMEOUT_S = 900  # one forward of the largest cell takes about 12 s
CLOSE_GRACE_S = 1.0  # co-batched scenes of the closing forward arrive within it
WINDOW_SPAN = "bench.window"
HOST_SPANS = ("bench.submit", "bench.wait", "forward")
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# Each number ``correct`` compares, and the served outputs it takes together:
# the root mean square of served − reference over every element of those
# outputs in every sampled scene, in the outputs' own units.  (The tokens
# are LayerNorm outputs of unit RMS, so theirs is also a relative gap.)
COMPARED = {
    "tokens": ("tokens",),  # the aggregator's final normed tokens
    "dpt": ("points", "depth", "conf"),  # the DPT head's outputs
    "pose": ("pose",),  # the camera head
}


class NoChip(RuntimeError):
    pass


class MissingMetric(RuntimeError):
    pass


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list  # every end-to-end metric of BENCHMARK.json
    per_layer: list  # the per-layer metrics that list this cell


def load_cell(workload: str, root: str = ROOT) -> Cell:
    """The cell's entry, its configuration and traffic files, and the
    metrics it reports, all found by name."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json: {sorted(cells)}")
    w = cells[workload]

    def data(kind, name):
        with open(os.path.join(root, "bench", kind, name + ".json")) as f:
            return json.load(f)

    layer = [m for m in bench["per_layer"] if workload in m["workloads"]]
    return Cell(workload, int(w["chips"]), data("configs", w["config"]),
                data("traffic", w["traffic"]), bench["end_to_end"], layer)


def reader(name: str, root: str = ROOT):
    """``read(measured) -> float | None`` of ``bench/metrics/<name>.py``."""
    path = os.path.join(root, "bench", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def device_info(chips: int, require_tpu: bool = True) -> dict:
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"JAX finds no TPU (platform {devs[0].platform!r}); nothing is measured")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX finds {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": chips}


def peak_for(kind: str, root: str = ROOT) -> dict:
    with open(os.path.join(root, "bench", "peaks.json")) as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise KeyError(f"device_kind {kind!r} is not in bench/peaks.json ({sorted(table)})")
    return table[kind]


def configure_cache(root: str = ROOT) -> None:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    unless ``JAX_COMPILATION_CACHE_DIR`` names one."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", os.path.join(root, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


@dataclasses.dataclass
class Measured:
    """What the metric readers read (see ``bench/metrics``)."""

    config: dict
    traffic: dict
    peak: dict | None
    work: dict  # per scene, ``work.scene_work``
    scenes: list  # per counted scene: {"request", "frames", "latency_s"}
    window_s: float  # host clock, first submit to the closing forward
    events: list  # the program's span events (``obs/trace.py``)
    stats: dict  # the engine's counts over the window
    trace: xtrace.Reduced | None


class Compiles:
    """Counts compilations (and persistent-cache loads) inside ``with``."""

    def __init__(self):
        self.n = 0

    def _on(self, event, duration, **kw):
        if event == COMPILE_EVENT:
            self.n += 1

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._on)


def _drive(srv, pool, order, clients: int, seconds: float):
    """The closed loop; returns (counted, failed, attempted, t0, t_close,
    results by request id, requests still in flight at the close).
    ``pool`` holds device arrays."""
    pending = deque()
    k = 0

    def submit():
        nonlocal k
        i = order[k % len(order)]
        k += 1
        with jax.profiler.TraceAnnotation("bench.submit"):
            t = time.perf_counter()
            req = srv.submit(pool[i])
        pending.append((req, t, i))

    counted, results, failed = [], {}, 0
    t0 = time.perf_counter()
    end = t0 + seconds
    for _ in range(clients):
        submit()
    t_close = None
    while pending and t_close is None:
        req, ts, i = pending.popleft()
        with jax.profiler.TraceAnnotation("bench.wait"):
            try:
                out = srv.result(req, timeout=RESULT_TIMEOUT_S)
            except Exception as e:  # a request the server failed: counted, not fatal
                print(f"info: request {req.req_id} failed: {e!r}", file=sys.stderr)
                out = None
        td = time.perf_counter()
        if out is None:
            failed += 1
        else:
            counted.append({"request": req.req_id, "scene": i, "latency_s": td - ts})
            results[req.req_id] = out
        if td < end:
            submit()
            continue
        t_close = td
        for req, ts, i in list(pending):  # co-batched scenes of the closing forward
            try:
                out = srv.result(req, timeout=CLOSE_GRACE_S)
            except TimeoutError:
                continue
            except Exception:
                failed += 1
                pending.remove((req, ts, i))
                continue
            td = time.perf_counter()
            counted.append({"request": req.req_id, "scene": i, "latency_s": td - ts})
            results[req.req_id] = out
            pending.remove((req, ts, i))
            t_close = td
    return counted, failed, len(counted) + failed, t0, t_close, results, pending


def _stats(eng) -> dict:
    s = eng.stats
    return {"calls": s.calls, "items": s.items,
            "padded_items": sum(b.padded_items for b in s.buckets.values()),
            "compiles": s.compiles}


def rel_gap(got, ref) -> float:
    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    return float(np.linalg.norm(got - ref) / np.linalg.norm(ref))


def layer_metrics(cell: Cell, m: Measured, root: str = ROOT) -> dict:
    """The cell's per-layer metrics; each lists the cell, so each must read."""
    out = {}
    for spec in cell.per_layer:
        v = reader(spec["name"], root)(m)
        if v is None:
            raise MissingMetric(f"{spec['name']} read nothing in {cell.name}")
        out[spec["name"]] = {"value": v, "unit": spec["unit"]}
    return out


def measure(cell: Cell, seed: int, seconds: float, trace: bool, *, root: str = ROOT,
            require_tpu: bool = True, policy: str | None = None,
            keep: list | None = None) -> dict:
    """One run of ``cell``; returns the result object the CLI prints.
    ``policy`` replaces the configuration's precision (the control).
    ``keep``, where given, receives each compared scene's served and
    reference answers (all but the tokens) and its gaps."""
    c, tr = cell.config, cell.traffic
    if tr["loop"] != "closed":
        raise ValueError(f"traffic loop {tr['loop']!r}: the harness drives closed loops only")
    device = device_info(cell.chips, require_tpu)
    peak = peak_for(device["kind"], root) if require_tpu else None
    frames, patches, clients = int(tr["frames"]), int(tr["patches"]), int(tr["clients"])

    # ---- set-up --------------------------------------------------------
    w = jax.block_until_ready(weights.init(c, seed))
    pool = [jax.device_put(scenes.scene(frames, patches, c["d_model"], i, seed))
            for i in range(int(tr["scene_pool"]))]
    order = np.random.default_rng(np.random.SeedSequence([seed, 1])).permutation(len(pool))
    srv, eng = program.serve(c, w, int(tr["max_batch"]), policy=policy)
    try:
        # warm-up: one scene per client, all in flight at once, so that it
        # fills the batch bucket the window fills
        warm = [srv.submit(pool[i % len(pool)]) for i in range(clients)]
        for req in warm:
            jax.block_until_ready(srv.result(req, timeout=RESULT_TIMEOUT_S))
        del warm, req
        gc.collect()
        gc.freeze()
        before = _stats(eng)
        setup_s = time.perf_counter() - T_START

        # ---- window ----------------------------------------------------
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
        if trace:
            jax.profiler.start_trace(trace_dir)
        with Compiles() as compiles, jax.profiler.TraceAnnotation(WINDOW_SPAN):
            counted, failed, attempted, t0, t_close, results, late = _drive(
                srv, pool, order, clients, seconds)
        for req, _, _ in late:  # partial forward after the close: not counted
            try:
                srv.result(req, timeout=RESULT_TIMEOUT_S)
            except Exception:
                pass
        if trace:
            jax.profiler.stop_trace()
        after = _stats(eng)
        in_window = compiles.n
        mem = max(d.memory_stats().get("peak_bytes_in_use", 0)
                  for d in jax.devices()[: cell.chips]) if require_tpu else 0
        events = program.telemetry()
    finally:
        program.shutdown(srv)
    stats = {k: after[k] - before[k] for k in after}
    print(f"info: setup_s {setup_s:.3f}, window {t_close - t0:.3f} s, scenes {len(counted)}, "
          f"failed {failed}, forwards {stats['calls']}, compiles in the window: "
          f"{in_window} (engine buckets {stats['compiles']})", file=sys.stderr)

    reduced = None
    if trace:
        try:
            reduced = xtrace.reduce(xtrace.find(trace_dir), WINDOW_SPAN, HOST_SPANS)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
    m = Measured(
        config=c, traffic=tr, peak=peak, work=work.scene_work(c, frames, patches),
        scenes=[dict(s, frames=frames) for s in counted], window_s=t_close - t0,
        events=events, stats=stats, trace=reduced,
    )
    metrics = {}
    if trace:
        metrics = layer_metrics(cell, m, root)
    else:
        e2e = {
            "frames_per_s": frames * len(counted) / m.window_s,
            "scene_p50_ms": float(np.percentile([s["latency_s"] for s in counted], 50)) * 1e3,
            "scene_p90_ms": float(np.percentile([s["latency_s"] for s in counted], 90)) * 1e3,
            "setup_s": setup_s,
        }
        for spec in cell.end_to_end:
            metrics[spec["name"]] = {"value": e2e[spec["name"]], "unit": spec["unit"]}
    device["memory_peak_bytes"] = int(mem)
    if reduced is not None:
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s

    # ---- correct: the window's own answers against the reference -----------
    rng = np.random.default_rng(np.random.SeedSequence([seed, 2]))
    pick = rng.choice(len(counted), size=min(int(tr["check_scenes"]), len(counted)),
                      replace=False) if counted else []
    limits = c["limits"]
    outputs = sorted({k for n in limits for k in COMPARED[n]})
    # copies: the host views of device buffers go when the program is freed
    served = [(counted[j]["scene"], {k: np.array(results[counted[j]["request"]][k], copy=True)
                                     for k in outputs}) for j in pick]
    del results, pool, srv, eng
    gc.unfreeze()
    gc.collect()
    ref = Reference(c)
    prep = ref.prepare(w)
    del w
    err2 = {n: 0.0 for n in limits}
    count = {n: 0 for n in limits}
    for i, got in served:
        r = ref.forward(prep, jax.device_put(scenes.scene(frames, patches, c["d_model"], i, seed)))
        r = {k: np.asarray(r[k], np.float64) for k in outputs}
        for n in limits:
            for k in COMPARED[n]:
                err2[n] += float(np.sum((np.asarray(got[k], np.float64) - r[k]) ** 2))
                count[n] += r[k].size
        if keep is not None:
            keep.append({"scene": i, "gaps": {k: rel_gap(got[k], r[k]) for k in outputs},
                         "got": {k: v for k, v in got.items() if k != "tokens"},
                         "ref": {k: v for k, v in r.items() if k != "tokens"}})
    gaps = {n: float(np.sqrt(err2[n] / count[n])) if count[n] else float("inf")
            for n in limits}
    check = {n: {"value": g if np.isfinite(g) else float("inf"), "limit": limits[n]}  # NaN -> inf
             for n, g in gaps.items()}
    ok = bool(served) and failed == 0 and all(v["value"] <= v["limit"] for v in check.values())
    result = {"correct": ok, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if reduced is not None:
        result["breakdown"] = {
            "device_ops": sorted(([n, s] for n, s in reduced.op_s.items()),
                                 key=lambda x: -x[1])[:10],
            "idle_gaps": [[n, s] for n, s in reduced.gaps[:10]],
        }
    result["check"] = check
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    configure_cache()
    try:
        result = measure(cell, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        print(f"error: {e}", file=sys.stderr)
        return 3
    except MissingMetric as e:
        print(f"error: {e}", file=sys.stderr)
        return 4
    for k, v in result["check"].items():
        print(f"check: {k} {v['value']!r} <= {v['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
