"""Bring-up check: VGGT-1B served at W4A8 on one TPU chip.

    python chip_smoke.py [--seed N]

One process, four phases in this order; the first that fails ends the run
with a non-zero exit code and no result line:

1. device   — JAX must see a TPU.  There is no fallback to the CPU.
2. kernels  — the Pallas kernels at VGGT-1B widths against their
   references, which run under ``default_matmul_precision("highest")``:
   ``two_stage_mha`` over a scene's 10,992 global tokens (16 heads, dh 64),
   ``fused_linear`` for ``wqkv`` (LN prologue, 1024→3072, IDCT epilogue)
   and ``wo`` (1024→1024), and ``fused_ffn_apply`` (1024→4096→1024, gelu).
3. serve    — 4 requests of 1 scene × 8 frames × 1,369 patches through
   ``VGGTEngine`` (``w4a8:fused``, two-stage attention) behind
   ``AsyncServer``.  Every request must return finite pose, points and
   depth; the kernel launch counts must show the two-stage kernel and the
   fused kernels in both blocks of the AA pair, so that neither the
   attention emulation nor a per-site fusion fallback can stand in for
   them; the compiled ``KernelSchedule`` must list no fallback.
4. accuracy — the served scene against the fp forward and against the jnp
   emulation of the same quantized weights.

Weights are random, from ``--seed``.  LayerScale starts at 0.1 instead of
the config's 1e-5: at 1e-5 the 24 AA pairs hardly move the residual
stream, and every comparison in phase 4 would pass whatever the kernels
computed.

Lines starting with ``info:`` are informational (timings, compile counts).
The last line is ``{"ok": true, "device": {...}}``.
"""
import argparse
import dataclasses
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.runtime.compile_cache import configure_compile_cache  # noqa: E402

FRAMES, PATCHES, REQUESTS = 8, 1369, 4
POLICY = "w4a8:fused"
LAYERSCALE = 0.1

# Tolerances (relative L2 error).
#  * Kernel parity, 1e-2: the kernels do the reference's arithmetic.  The
#    integer dots are exact on both sides; what differs is the float work
#    around them — Mosaic's exp, the MXU passes of the in-kernel IDCT/WHT
#    dots, reduction order — and a one-ulp difference can move a value
#    across a rounding boundary of the int8 quantizer (one LSB, 1/127 of
#    a row's range).  A wrong tile, a lost nibble plane or a dropped
#    epilogue gives errors of order 1.
#  * Fused FFN, 5e-2: as above, but the hidden activation is requantized
#    inside the kernel, so boundary moves land on a 4096-wide row before
#    the down projection sums them.
#  * Served W4A8 vs fp, 0.25, and kernel path vs emulation, 0.15: the
#    bounds of tests/serving/test_vggt_engine.py, which the engine meets at
#    smoke size with the same LayerScale regime.
TOL_KERNEL = 1e-2
TOL_FFN = 5e-2
TOL_FP = 0.25
TOL_EMULATION = 0.15


def info(msg: str) -> None:
    print(f"info: {msg}", flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"FAILED: {what}")
    print(f"ok: {what}", flush=True)


def rel(a, b) -> float:
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def _highest(fn, *args):
    with jax.default_matmul_precision("highest"):
        return jax.block_until_ready(jax.jit(fn)(*args))


def phase_device() -> dict:
    devices = jax.devices()
    d = devices[0]
    print(f"device: platform={d.platform} kind={d.device_kind} count={len(devices)}",
          flush=True)
    check(d.platform == "tpu", "JAX sees a TPU")
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devices)}


def _attention_reference(q, k, v):
    """``two_stage_attention_ref`` on the int8 operands ``two_stage_mha``
    builds (per-token Q/K scales, one V scale per head), one head at a
    time so that the [L, L] scores of only one head are live."""
    from repro.core.quantize import quantize_per_token
    from repro.kernels import ref

    b, h, length, dh = q.shape
    qq = quantize_per_token(q.reshape(b * h, length, dh), 8)
    kq = quantize_per_token(k.reshape(b * h, length, dh), 8)
    vf = v.reshape(b * h, length, dh)
    vs = jnp.maximum(jnp.max(jnp.abs(vf), axis=(1, 2), keepdims=True), 1e-8) / 127.0
    vv = jnp.clip(jnp.round(vf / vs), -127, 127).astype(jnp.int8)

    def one_head(args):
        return ref.two_stage_attention_ref(*args, causal=False)

    out = jax.lax.map(one_head, (qq.values, qq.scale, kq.values, kq.scale, vv, vs))
    return out.reshape(b, h, length, dh)


def _without_kernels(tree):
    """The same quantized tree with every site on the jnp emulation."""
    from repro.core.versaq import QuantLinear

    return jax.tree.map(
        lambda x: dataclasses.replace(x, use_kernel=False)
        if isinstance(x, QuantLinear) else x,
        tree,
        is_leaf=lambda x: isinstance(x, QuantLinear),
    )


def phase_kernels(cfg, seed: int, frames: int = FRAMES, patches: int = PATCHES) -> None:
    from repro.core.model_quant import quantize_vggt
    from repro.core.versaq import apply_ffn, apply_linear
    from repro.kernels import ops, probe
    from repro.launch.specs import ServeSpec
    from repro.models import vggt

    tokens = frames * (patches + cfg.n_special_tokens)
    kq, kk, kv, kx, kw = jax.random.split(jax.random.PRNGKey(seed), 5)
    shape = (1, cfg.n_heads, tokens, cfg.head_dim)
    q, k, v = (jax.random.normal(key, shape) for key in (kq, kk, kv))
    attn = jax.jit(ops.two_stage_mha)
    with probe.tracking() as log:
        t0 = time.perf_counter()
        got = jax.block_until_ready(attn(q, k, v))
    t1 = time.perf_counter()
    jax.block_until_ready(attn(q, k, v))
    info(f"two_stage_mha {shape}: first call {t1 - t0:.3f} s (compile included), "
         f"second {time.perf_counter() - t1:.3f} s")
    check(log.by_name() == {"two_stage_mha": 2}, "two_stage_mha ran the kernel")
    err = rel(got, _highest(_attention_reference, q, k, v))
    check(err < TOL_KERNEL, f"two_stage_mha vs two_stage_attention_ref: {err:.3e} < {TOL_KERNEL}")

    one = cfg.with_(n_layers=1)
    plan = ServeSpec.parse(POLICY).materialize()
    tree = quantize_vggt(one, vggt.init_params(one, kw), plan)
    block = jax.tree.map(lambda a: a[0], tree["blocks"]["global"])
    x = jax.random.normal(kx, (tokens, cfg.d_model))
    for name in ("wqkv", "wo"):
        p = block["attn"][name]
        with probe.tracking() as log:
            got = jax.block_until_ready(jax.jit(ops.fused_linear)(x, p))
        check(log.by_name() == {"fused_matmul": 1}, f"fused_linear {name} ran the kernel")
        err = rel(got, _highest(apply_linear, _without_kernels(p), x))
        check(err < TOL_KERNEL,
              f"fused_linear {name} {tuple(p.qw.values.shape)} vs emulation: "
              f"{err:.3e} < {TOL_KERNEL}")
    f = block["ffn"]
    with probe.tracking() as log:
        got = jax.block_until_ready(jax.jit(ops.fused_ffn_apply)(x, f))
    check(log.by_name() == {"fused_ffn": 1}, "fused_ffn_apply ran the kernel")
    err = rel(got, _highest(apply_ffn, _without_kernels(f), x))
    check(err < TOL_FFN, f"fused_ffn_apply vs emulation: {err:.3e} < {TOL_FFN}")


def phase_serve(cfg, seed: int, frames: int = FRAMES, patches: int = PATCHES,
                requests: int = REQUESTS):
    """Serve the scenes; returns (raw params, engine, first scene, its result)."""
    from repro.core.precision.compiler import compile_schedule
    from repro.data.pipeline import scene_batch
    from repro.kernels import probe
    from repro.launch.specs import ServeSpec
    from repro.models import vggt
    from repro.serving.server import AsyncServer
    from repro.serving.vggt_engine import VGGTEngine

    plan = ServeSpec.parse(POLICY).materialize()
    t0 = time.perf_counter()
    params = vggt.init_params(cfg, jax.random.PRNGKey(seed))
    eng = VGGTEngine(cfg, params, policy=plan, attn_impl="two_stage", max_batch=1)
    jax.block_until_ready(eng.params)  # quantize now, outside the serving window
    info(f"init + quantize {time.perf_counter() - t0:.1f} s")
    scenes = [
        jnp.asarray(scene_batch(1, frames, patches, cfg.d_model, r, seed=seed)["patches"])
        for r in range(requests)
    ]
    counters = probe.enable_global()
    counters.reset()
    try:
        t0 = time.perf_counter()
        with AsyncServer(eng) as srv:
            pending = [srv.submit(s) for s in scenes]
            outs = [srv.result(r, timeout=1200) for r in pending]
        info(f"served {len(outs)} requests in {time.perf_counter() - t0:.1f} s "
             f"(compiles {eng.stats.compiles}, the first request includes them)")
        launches = counters.by_name()
    finally:
        probe.disable_global()
    info(eng.stats.format())
    for i, out in enumerate(outs):
        for key, shape in (("pose", (1, frames, 9)), ("points", (1, frames, patches, 3)),
                           ("depth", (1, frames, patches))):
            a = out[key]
            check(a.shape == shape and bool(jnp.isfinite(a).all()),
                  f"request {i} {key} {a.shape} finite")
    # launches are counted at trace time: one trace of the scan body per
    # compile, holding the frame and the global block of an AA pair
    c = eng.stats.compiles
    want = {"two_stage_mha": 4 * c, "fused_matmul": 4 * c, "fused_ffn": 2 * c}
    check(launches == want, f"kernel launches {launches} == {want}")
    schedule = compile_schedule(eng.cfg, plan)
    bad = [(s.site, s.kernel, s.fallback) for s in schedule.sites
           if s.kernel not in ("fused", "matmul") or s.fallback]
    groups = sorted(g.name for g in schedule.groups)
    check(not bad and schedule.attention.impl == "two_stage"
          and groups == ["frame.attn.wqkv", "frame.ffn", "global.attn.wqkv", "global.ffn"],
          f"KernelSchedule: fused groups {groups}, every site on a kernel, "
          f"no fallback {bad}")
    return params, eng, scenes[0], outs[0]


def phase_accuracy(cfg, params, eng, scene, served) -> None:
    from repro.kernels import probe
    from repro.models import vggt

    t0 = time.perf_counter()
    fp = _highest(functools.partial(vggt.forward, cfg), params, scene)
    info(f"fp forward {time.perf_counter() - t0:.1f} s (compile included)")
    err = rel(served["points"], fp["points"])
    info(f"pose error vs fp {rel(served['pose'], fp['pose']):.3e}, "
         f"depth {rel(served['depth'], fp['depth']):.3e}")
    check(err < TOL_FP, f"served W4A8 points vs fp forward: {err:.3e} < {TOL_FP}")
    del fp
    emu_cfg = eng.cfg.with_(attn_use_kernel=False)
    with probe.tracking() as log:
        emu = _highest(functools.partial(vggt.forward, emu_cfg),
                       _without_kernels(eng.params), scene)
    check(log.count == 0, "the emulation ran no kernel")
    err = rel(served["points"], emu["points"])
    check(err < TOL_EMULATION,
          f"served points vs quantized emulation: {err:.3e} < {TOL_EMULATION}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    configure_compile_cache()
    from repro.configs import get_config

    device = phase_device()
    cfg = get_config("vggt-1b").with_(layerscale_init=LAYERSCALE)
    phase_kernels(cfg, args.seed)
    params, eng, scene, served = phase_serve(cfg, args.seed)
    phase_accuracy(cfg, params, eng, scene, served)
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
