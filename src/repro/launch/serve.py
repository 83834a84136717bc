"""Serving launcher: quantize + serve batched requests through the
bucketed engines behind the async server loop.

LM prefill/decode serving (prompt-length + batch buckets, micro-batched):

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-14b-smoke \
      --policy w4a8 --requests 8 --prompt-len 16 --gen 32

VGGT feed-forward serving (bucketed + micro-batched engine):

  PYTHONPATH=src python -m repro.launch.serve --arch vggt-1b-smoke \
      --policy w4a8 --requests 6 --frames 4 --patches 64 --attn-impl two_stage

Precision tiers (one engine, several quantization levels; requests are
assigned tiers round-robin and only coalesce within their tier):

  PYTHONPATH=src python -m repro.launch.serve --arch vggt-1b-smoke \
      --tiers quality=fp,balanced=w4a8,fast=plan --requests 6

Tier specs: ``fp`` (full precision), ``w<bits>a<bits>`` (uniform),
``plan`` (the ``core.precision`` sensitivity planner's mixed plan), and
``:fused`` variants (``w4a8:fused``, ``plan:fused``) that serve through
the unified-datapath fused kernels (one Pallas launch per FFN layer,
merged QKV with in-kernel norm prologue — docs/kernels.md).
"""
import argparse

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core.versaq import QuantPolicy
from repro.data.pipeline import mixed_len_prompts, scene_batch
from repro.runtime.compile_cache import configure_compile_cache
from repro.serving.engine import Engine
from repro.serving.server import AsyncServer

# How long the launcher waits for one request.  The first request of a
# shape includes its compile, and requests queue behind each other: one
# 32-frame VGGT-1B scene at W4A8 takes about 111 s on a TPU v5e chip, so
# the fourth of four waits about 8 minutes.
RESULT_TIMEOUT_S = 3600


def _parse_policy(s: str, method: str):
    """Thin wrapper over :class:`repro.launch.specs.ServeSpec` — one
    shared grammar for ``--policy`` and ``--tiers`` values instead of
    the launcher's old ad-hoc string slicing."""
    from repro.launch.specs import ServeSpec

    try:
        spec = ServeSpec.parse(s, method)
    except ValueError as e:
        raise ValueError(f"policy {s!r}: {e}") from e
    if spec.level == "plan":
        raise ValueError(
            f"policy {s!r}: 'plan' is only valid in --tiers "
            f"(the planner needs named tiers + weights)"
        )
    return spec.materialize()


def _policy(args) -> QuantPolicy | None:
    return _parse_policy(args.policy, args.method)


def _tiers(args, cfg, params) -> dict | None:
    """Parse ``--tiers name=spec,...`` via ``ServeSpec.parse_tiers``;
    ``plan`` runs the sensitivity planner on the freshly-initialized
    weights (reported to stdout)."""
    from repro.launch.specs import ServeSpec

    specs = ServeSpec.parse_tiers(args.tiers, args.method)
    if specs is None:
        return None
    return {
        name: spec.materialize(cfg, params, name=name, verbose=True)
        for name, spec in specs.items()
    }


def _tier_cycle(tiers: dict | None, n: int) -> list[str | None]:
    """Round-robin tier assignment for n requests (None = default path)."""
    if not tiers:
        return [None] * n
    names = list(tiers)
    return [names[i % len(names)] for i in range(n)]


def _robustness_kwargs(args) -> dict:
    """Shared fault-tolerance flags (docs/robustness.md) for both engine
    constructors: admission bounds, degradation ladder, chaos plan."""
    kw: dict = {}
    if args.max_pending is not None:
        kw["max_pending"] = args.max_pending
    if args.max_queued_tokens is not None:
        kw["max_queued_tokens"] = args.max_queued_tokens
    if args.max_pending is not None or args.max_queued_tokens is not None:
        kw["admission"] = args.admission
    if args.degrade:
        kw["degrade"] = True
    if args.faults is not None:
        kw["faults"] = args.faults
    return kw


def _collect(srv: AsyncServer, reqs: list) -> list:
    """Gather results, reporting per-request serving errors (quarantine,
    shed, deadline — expected events under --faults / admission bounds)
    instead of dying on the first one.  Returns the successful outputs."""
    from repro.serving.batching import ServeError

    outs = []
    for i, r in enumerate(reqs):
        if r is None:  # rejected at submit (QueueFull under --admission reject)
            continue
        try:
            outs.append(srv.result(r, timeout=RESULT_TIMEOUT_S))
        except ServeError as e:
            print(f"request {i}: {type(e).__name__}: {e}")
    return outs


def _submit(srv: AsyncServer, i: int, *a, **kw):
    """Submit one request; a QueueFull at enqueue (admission reject) is an
    expected outcome under --max-pending, not a launcher crash."""
    from repro.serving.batching import QueueFull

    try:
        return srv.submit(*a, **kw)
    except QueueFull as e:
        print(f"request {i}: QueueFull: {e}")
        return None


def _server(eng, args) -> AsyncServer:
    """AsyncServer wired to the CLI's telemetry flags: ``--metrics-port``
    exposes /metrics, /stats and /trace (docs/observability.md) and turns
    live telemetry on; ``--trace-jsonl`` mirrors span events to a file."""
    if args.trace_jsonl is not None:
        from repro import obs

        obs.enable_all(trace_path=args.trace_jsonl)
    srv = AsyncServer(eng, metrics_port=args.metrics_port)
    srv.start()
    if srv.metrics_address is not None:
        host, port = srv.metrics_address
        print(f"telemetry: http://{host}:{port}/metrics  /stats  /trace")
    return srv


def serve_vggt(cfg, args) -> tuple[int, int]:
    from repro.models import vggt
    from repro.serving.vggt_engine import VGGTEngine

    params = vggt.init_params(cfg, jax.random.PRNGKey(0))
    tiers = _tiers(args, cfg, params)
    eng = VGGTEngine(
        cfg,
        params,
        policy=None if (tiers or args.schedule) else _policy(args),
        schedule=args.schedule,
        tiers=tiers,
        attn_impl=args.attn_impl,
        max_batch=args.batch,
        max_wait_s=args.max_wait_s,
        **_robustness_kwargs(args),
    )
    assign = _tier_cycle(tiers, args.requests)
    with _server(eng, args) as srv:
        reqs = [
            _submit(srv, r, jnp.asarray(
                scene_batch(args.scenes, args.frames, args.patches, cfg.d_model, r)["patches"]
            ), tier=assign[r])
            for r in range(args.requests)
        ]
        outs = _collect(srv, reqs)
    shapes = ""
    if outs:
        shapes = (f" -> poses{tuple(outs[-1]['pose'].shape)} "
                  f"points{tuple(outs[-1]['points'].shape)}")
    print(f"served {len(outs)}/{len(reqs)} requests{shapes}")
    print(eng.stats.format())
    return len(outs), len(reqs)


def serve_lm(cfg, args) -> tuple[int, int]:
    from repro.models import lm

    key = jax.random.PRNGKey(0)
    params = lm.init_params(cfg, key)
    tiers = _tiers(args, cfg, params)
    eng = Engine(
        cfg,
        params,
        policy=None if (tiers or args.schedule) else _policy(args),
        schedule=args.schedule,
        tiers=tiers,
        attn_impl=args.attn_impl,
        max_len=args.prompt_len + args.gen,
        max_batch=args.batch,
        max_wait_s=args.max_wait_s,
        mode=args.mode,
        **_robustness_kwargs(args),
    )
    # mixed-length traffic (full + non-pow2 short prompts) exercises the
    # masked length-padded bucket variants alongside warm bucket reuse
    prompts = mixed_len_prompts(cfg.vocab_size, args.requests, args.prompt_len)
    assign = _tier_cycle(tiers, len(prompts))
    with _server(eng, args) as srv:
        reqs = [
            _submit(srv, i, p, args.gen, tier=t, deadline_s=args.deadline_s)
            for i, (p, t) in enumerate(zip(prompts, assign))
        ]
        outs = _collect(srv, reqs)
    print(f"served {len(outs)}/{len(reqs)} requests -> "
          f"{sum(o.shape[-1] for o in outs)} tokens")
    print(f"prefill {eng.stats.prefill_s*1e3:.1f}ms  "
          f"decode {eng.stats.decode_s*1e3:.1f}ms  "
          f"({eng.stats.decode_tokens_per_s:.0f} decode tok/s)")
    print(eng.stats.format())
    return len(outs), len(reqs)


def _failures_expected(args) -> bool:
    """Flags under which a failed request is an intended outcome: chaos
    faults, admission bounds that reject or shed, and request deadlines."""
    return (
        args.faults is not None
        or args.max_pending is not None
        or args.max_queued_tokens is not None
        or args.deadline_s is not None
    )


def main():
    configure_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-14b-smoke")
    ap.add_argument("--policy", default="w4a8",
                    help="w<bits>a<bits>[:fused] (w4a8, w4a16, w4a8:fused) | fp")
    ap.add_argument("--tiers", default=None,
                    help="serve precision tiers: name=spec[,name=spec...], "
                         "spec in {fp, w<bits>a<bits>[:fused], plan[:fused]}; "
                         "overrides --policy")
    ap.add_argument("--schedule", default=None,
                    help="serve from a compiled KernelSchedule JSON "
                         "(launch/compile.py output); overrides --policy "
                         "and conflicts with --tiers")
    ap.add_argument("--method", default="versaq", help="versaq|quarot|rtn")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--max-wait-s", type=float, default=0.005,
                    help="micro-batch deadline driven by the async loop")
    ap.add_argument("--mode", default="auto",
                    help="LM scheduler: auto | continuous (slot-based "
                         "continuous batching) | bucket (drain-then-refill)")
    ap.add_argument("--deadline-s", type=float, default=None,
                    help="per-request SLA: evict (fail) requests not "
                         "served within this many seconds")
    # vggt serving
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--scenes", type=int, default=2, help="scenes per request")
    ap.add_argument("--frames", type=int, default=4)
    ap.add_argument("--patches", type=int, default=64)
    ap.add_argument("--attn-impl", default=None,
                    help="override cfg.attn_impl (two_stage = INT8 Pallas kernel)")
    # robustness (docs/robustness.md)
    ap.add_argument("--max-pending", type=int, default=None,
                    help="admission control: bound the pending queue at "
                         "this many requests (QueueFull past it)")
    ap.add_argument("--max-queued-tokens", type=int, default=None,
                    help="admission control: bound the queued work in "
                         "tokens (prompt+gen for LM, patch tokens for VGGT)")
    ap.add_argument("--admission", default="reject", choices=("reject", "shed"),
                    help="over-full queue policy: reject the new request "
                         "or shed the least-valuable queued one")
    ap.add_argument("--degrade", action="store_true",
                    help="degradation ladder: under sustained SLA pressure "
                         "auto-downshift unpinned admissions to cheaper "
                         "tiers, recover with hysteresis")
    ap.add_argument("--faults", default=None,
                    help="chaos fault plan, e.g. "
                         "'nan@decode.logits:req=1,step=3;seed=7' "
                         "(see serving/faults.py for the grammar)")
    # observability (docs/observability.md)
    ap.add_argument("--metrics-port", type=int, default=None,
                    help="expose /metrics (Prometheus), /stats (JSON) and "
                         "/trace (span ring buffer) on this port; 0 binds "
                         "an ephemeral port.  Turns live telemetry on.")
    ap.add_argument("--trace-jsonl", default=None,
                    help="mirror span events to this JSONL file (implies "
                         "live telemetry)")
    args = ap.parse_args()

    cfg = get_config(args.arch)
    served, total = (serve_vggt if cfg.vggt else serve_lm)(cfg, args)
    if served < total and not _failures_expected(args):
        raise SystemExit(f"{total - served} of {total} requests failed")


if __name__ == "__main__":
    main()
