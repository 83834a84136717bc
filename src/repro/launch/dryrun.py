import os
os.environ["XLA_FLAGS"] = (
    os.environ.get("DRYRUN_EXTRA_XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=512"
).strip()

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

THE TWO LINES ABOVE MUST STAY FIRST — jax locks the device count on first
init, and the production meshes need 512 placeholder devices.

Usage:
  python -m repro.launch.dryrun --arch qwen3-14b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all [--mesh both] [--out results/dryrun]

``--all`` drives one subprocess per cell (fresh XLA each time, results
cached as JSON); single-cell mode does the work in-process:

    with mesh:
        lowered = jax.jit(step, in_shardings=...).lower(*input_specs)
        compiled = lowered.compile()
        print(compiled.memory_analysis())
        print(compiled.cost_analysis())

plus the roofline-term extraction of launch/roofline_util.py.
"""
import argparse
import json
import subprocess
import sys
import time
import traceback


def run_cell(arch: str, shape: str, mesh_kind: str, opts: dict) -> dict:
    import jax

    from repro.configs import get_config
    from repro.launch import specs
    from repro.launch.mesh import make_production_mesh
    from repro.launch import roofline_util as ru

    cfg = get_config(arch)
    ok, why = specs.applicable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape, "mesh": mesh_kind, "status": "skipped", "reason": why}
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))

    # ---- pass 1: the REAL artifact — full depth, scanned layers ----
    # proves the sharding config compiles and fits (memory analysis).
    t0 = time.time()
    with mesh:
        cell = specs.make_cell(cfg, shape, mesh, **opts)
        lowered = jax.jit(cell.fn, in_shardings=cell.in_shardings).lower(*cell.args)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0
        mem = compiled.memory_analysis()
        print(f"--- {arch} × {shape} × {mesh_kind} ---")
        print("memory_analysis:", mem)
        cost = compiled.cost_analysis()
        print("cost_analysis flops (scan body counted once):",
              (cost[0] if isinstance(cost, list) else cost).get("flops"))
        rl_scan = ru.extract(compiled)

    # ---- pass 2: roofline terms via trip-count-exact extrapolation ----
    # XLA's cost_analysis counts while-loop bodies ONCE, so the scanned
    # lowering undercounts FLOPs/bytes by ~n_groups.  Layer stacks are
    # homogeneous => costs are affine in the group count: measure fully
    # unrolled g=1 and g=2 lowerings and extrapolate.
    n_groups = (cfg.n_layers - cfg.first_dense) // len(cfg.pattern)
    terms = {}
    if n_groups >= 2:
        # prefer (2, 4): g=1 has boundary-fusion artifacts (embed/head
        # folding into the single group) that can produce negative slopes.
        # Long-period patterns (jamba: 8 layers/group) keep (1, 2) to bound
        # the unrolled compile size.
        g_lo, g_hi = (2, 4) if (n_groups >= 4 and len(cfg.pattern) < 4) else (1, 2)
        pts = {}
        for g in (g_lo, g_hi):
            cfg_g = specs.reduced_cfg(cfg, g)
            with mesh:
                cell_g = specs.make_cell(cfg_g, shape, mesh, unroll=True, **opts)
                comp_g = jax.jit(cell_g.fn, in_shardings=cell_g.in_shardings).lower(*cell_g.args).compile()
                pts[g] = ru.extract(comp_g)
        for key in ("flops_per_dev", "hbm_bytes_per_dev", "coll_bytes_per_dev"):
            slope = max(0.0, (pts[g_hi][key] - pts[g_lo][key]) / (g_hi - g_lo))
            terms[key] = max(
                pts[g_lo][key] + (n_groups - g_lo) * slope, pts[g_hi][key]
            )
    else:
        with mesh:
            cell_g = specs.make_cell(cfg, shape, mesh, unroll=True, **opts)
            comp_g = jax.jit(cell_g.fn, in_shardings=cell_g.in_shardings).lower(*cell_g.args).compile()
            full = ru.extract(comp_g)
        terms = {k: full[k] for k in ("flops_per_dev", "hbm_bytes_per_dev", "coll_bytes_per_dev")}

    sh = specs.SHAPES[shape]
    n_chips = 512 if mesh_kind == "multi" else 256
    # analytic correction for inner TIME scans (mamba/rwkv recurrences,
    # whose per-step bodies XLA also counts once and cannot be unrolled)
    corr = ru.time_scan_flops(cfg, sh.kind, sh.seq, sh.batch) / n_chips
    terms["flops_per_dev"] += corr
    rl = ru.Roofline(
        flops=terms["flops_per_dev"],
        hbm_bytes=terms["hbm_bytes_per_dev"],
        coll_bytes=terms["coll_bytes_per_dev"],
    ).as_dict()
    mf = ru.model_flops(cfg, sh.kind, sh.seq, sh.batch)
    rl.update(
        arch=arch,
        shape=shape,
        mesh=mesh_kind,
        status="ok",
        n_chips=n_chips,
        n_groups=n_groups,
        lower_s=round(t_lower, 1),
        compile_s=round(t_compile, 1),
        scan_artifact=rl_scan,
        time_scan_flops_corr_per_dev=corr,
        model_flops_total=mf,
        model_flops_per_dev=mf / n_chips,
        useful_flops_ratio=(mf / n_chips) / max(rl["flops_per_dev"], 1.0),
        opts={k: str(v) for k, v in opts.items()},
    )
    return rl


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--tag", default="baseline")
    ap.add_argument("--force", action="store_true")
    # hillclimb options
    ap.add_argument("--no-sp", action="store_true", help="disable TP sequence sharding of activations")
    ap.add_argument("--zero1", action="store_true", help="shard optimizer state over data axis")
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--remat-dots", action="store_true", help="dots_saveable remat policy")
    ap.add_argument("--attn", default=None, choices=[None, "vanilla", "flash", "two_stage"])
    ap.add_argument("--kv-bf16", action="store_true", help="bf16 KV cache (unquantized baseline)")
    ap.add_argument("--fp-serve", action="store_true", help="bf16 weights for serve cells")
    ap.add_argument("--act-sp", action="store_true", help="TP-SP residual sharding in prefill")
    ap.add_argument("--kv-seq-model", action="store_true", help="decode: shard cache seq over model")
    ap.add_argument("--attn-bf16", action="store_true", help="bf16 streaming-attention compute")
    ap.add_argument("--timeout", type=int, default=2400)
    args = ap.parse_args()

    opts = {}
    if args.no_sp:
        opts["seq_sp"] = False
    if args.zero1:
        opts["zero1"] = True
    if args.no_remat:
        opts["remat"] = False
    if args.remat_dots:
        opts["remat"] = "dots"
    if args.attn:
        opts["attn"] = args.attn
    if args.kv_bf16:
        import jax.numpy as _jnp
        opts["kv_dtype"] = _jnp.bfloat16
    if args.fp_serve:
        opts["fp_serve"] = True
    if args.act_sp:
        opts["act_sp"] = True
    if args.kv_seq_model:
        opts["kv_seq_model"] = True
    if args.attn_bf16:
        opts["attn_bf16"] = True

    if args.all:
        from repro.configs import ASSIGNED  # safe: no device use

        meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
        shapes = ["train_4k", "prefill_32k", "decode_32k", "long_500k"]
        cells = [(a, s) for a in ASSIGNED for s in shapes]
        # the paper's own model, with frame-count shapes
        cells += [("vggt-1b", s) for s in ("vggt_serve_s8", "vggt_serve_s32", "vggt_train_s4")]
        os.makedirs(args.out, exist_ok=True)
        failures = []
        for arch, shape in cells:
                for mesh_kind in meshes:
                    name = f"{arch}__{shape}__{mesh_kind}__{args.tag}.json"
                    path = os.path.join(args.out, name)
                    if os.path.exists(path) and not args.force:
                        print("cached:", name)
                        continue
                    cmd = [
                        sys.executable, "-m", "repro.launch.dryrun",
                        "--arch", arch, "--shape", shape, "--mesh", mesh_kind,
                        "--out", args.out, "--tag", args.tag,
                    ]
                    for flag, on in (
                        ("--no-sp", args.no_sp), ("--zero1", args.zero1),
                        ("--no-remat", args.no_remat), ("--remat-dots", args.remat_dots),
                        ("--kv-bf16", args.kv_bf16), ("--fp-serve", args.fp_serve),
                        ("--act-sp", args.act_sp), ("--kv-seq-model", args.kv_seq_model),
                    ):
                        if on:
                            cmd.append(flag)
                    if args.attn:
                        cmd += ["--attn", args.attn]
                    print(">>", " ".join(cmd), flush=True)
                    # the cell runs on fake host devices; the child never
                    # takes a chip, which belongs to one process at a time
                    r = subprocess.run(
                        cmd, timeout=args.timeout,
                        env=dict(os.environ, JAX_PLATFORMS="cpu"),
                    )
                    if r.returncode != 0:
                        failures.append(name)
        if failures:
            print("FAILED cells:", failures)
            sys.exit(1)
        print("all cells ok")
        return

    assert args.arch and args.shape
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    for mesh_kind in meshes:
        try:
            res = run_cell(args.arch, args.shape, mesh_kind, opts)
        except Exception:
            res = {
                "arch": args.arch, "shape": args.shape, "mesh": mesh_kind,
                "status": "error", "traceback": traceback.format_exc(),
            }
        os.makedirs(args.out, exist_ok=True)
        name = f"{args.arch}__{args.shape}__{mesh_kind}__{args.tag}.json"
        with open(os.path.join(args.out, name), "w") as f:
            json.dump(res, f, indent=1)
        print(json.dumps({k: v for k, v in res.items() if k not in ("traceback", "collectives", "memory")}, indent=1))
        if res["status"] == "error":
            print(res["traceback"])
            sys.exit(1)


if __name__ == "__main__":
    main()
