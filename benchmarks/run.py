"""Benchmark driver: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines.  Accuracy benchmarks are
structured proxies (no pretrained VGGT/Co3Dv2 offline — see DESIGN.md §6);
runtime benchmarks are roofline-model numbers plus interpret-mode kernel
timings (CPU container; TPU v5e is the target).

``--only key1,key2`` runs a subset (substring match on the module title)
— CI's benchmarks-smoke job uses this to catch kernel/benchmark drift on
the fast modules without paying for the trained-fixture ones.
"""
import argparse
import json
import sys
import time
import traceback

from benchmarks import (
    common,
    fig3_profile,
    fig10_bitwidth,
    fig11_ablation,
    fig13_runtime,
    fig14_frames,
    fused_datapath,
    kernels_micro,
    roofline,
    serve_continuous_bench,
    table1_quant_accuracy,
)
from repro.kernels import probe
from repro.runtime.compile_cache import configure_compile_cache

MODULES = [
    ("table1+2 (quant accuracy)", table1_quant_accuracy),
    ("fig10 (bitwidth sensitivity)", fig10_bitwidth),
    ("fig11 (ablation)", fig11_ablation),
    ("fig3 (profile breakdown)", fig3_profile),
    ("fig13 (runtime reduction)", fig13_runtime),
    ("fig14 (speedup vs S)", fig14_frames),
    ("kernels (micro)", kernels_micro),
    # NOTE: no "kernels" substring in the title — `--only kernels` must
    # keep selecting the micro benchmark alone; this point is `--only fused`
    ("fused datapath (unified)", fused_datapath),
    ("continuous (serve scheduler)", serve_continuous_bench),
    ("roofline (dry-run table)", roofline),
]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--only", default=None,
        help="comma-separated substrings; run only matching module titles "
             "(e.g. --only fig10,kernels)",
    )
    ap.add_argument(
        "--json", default=None, metavar="PATH",
        help="also write a machine-readable summary (per-bench rows, kernel "
             "call counts, modeled intermediate bytes) — the BENCH_*.json "
             "trajectory format",
    )
    args = ap.parse_args(argv)
    configure_compile_cache()
    modules = MODULES
    if args.only:
        keys = [k.strip().lower() for k in args.only.split(",") if k.strip()]
        modules = [(t, m) for t, m in MODULES if any(k in t.lower() for k in keys)]
        if not modules:
            titles = [t for t, _ in MODULES]
            raise SystemExit(f"--only {args.only!r} matched none of {titles}")
    print("name,us_per_call,derived")
    failures, benches = [], []
    for title, mod in modules:
        t0 = time.time()
        print(f"# --- {title} ---")
        common.reset_rows()
        ok = True
        with probe.tracking() as log:
            try:
                mod.main()
            except Exception:
                ok = False
                failures.append(title)
                traceback.print_exc()
        dt = time.time() - t0
        print(f"# ({title}: {dt:.1f}s)")
        bench = {
            "title": title,
            "ok": ok,
            "seconds": round(dt, 2),
            "rows": common.collected_rows(),
            "kernel_calls": log.by_name(),
            "kernel_bytes": dict(log.nbytes),
            "metrics": _bench_metrics(log),
        }
        benches.append(bench)
    if args.json:
        _write_json(args.json, args.only, benches)
    if failures:
        print("# FAILED:", failures)
        sys.exit(1)


def _bench_metrics(log) -> dict:
    """The bench's kernel traffic rendered through the same registry
    schema ``/metrics`` serves live — trajectory points and a scraped
    engine report identical metric families."""
    from repro.obs import metrics as obs_metrics

    reg = obs_metrics.Registry()
    obs_metrics.export_kernel_counters(reg, log.by_name(), dict(log.nbytes))
    return reg.render_json(collect=False)


def _git_revision() -> str | None:
    import os
    import subprocess

    try:
        out = subprocess.run(
            ["git", "rev-parse", "HEAD"],
            capture_output=True, text=True, timeout=10,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        )
    except (OSError, subprocess.SubprocessError):
        return None
    return out.stdout.strip() if out.returncode == 0 else None


def _write_json(path: str, only, benches: list[dict]) -> None:
    import platform

    import jax

    # schema_version history:
    #   1 — per-bench rows + kernel calls/bytes
    #   2 — + git revision, platform block, per-bench "metrics" registry
    #       render (comparable with the live /metrics families); needed to
    #       compare BENCH_*.json trajectory points across machines/backends
    blob = {
        "schema_version": 2,
        "version": 2,  # legacy alias of schema_version
        "generated_by": "benchmarks/run.py",
        "date": time.strftime("%Y-%m-%d"),
        "revision": _git_revision(),
        "backend": jax.default_backend(),
        "platform": {
            "python": platform.python_version(),
            "jax": jax.__version__,
            "os": platform.platform(),
            "machine": platform.machine(),
            "device_kind": jax.devices()[0].device_kind if jax.devices() else None,
            "device_count": jax.device_count(),
        },
        "only": only,
        "benches": benches,
    }
    with open(path, "w") as f:
        json.dump(blob, f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"# wrote {path}")


if __name__ == "__main__":
    main()
