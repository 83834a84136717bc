"""Live observability tour: serve quantized LM traffic with the full
telemetry stack on and consume every surface a production scrape would
(docs/observability.md).

1. Build a tiny W8A8 kernel-routed LM engine and start ``AsyncServer``
   with ``metrics_port=0`` (ephemeral) — live telemetry flips on, span
   events mirror to a JSONL file.
2. Submit mixed-length prompt traffic and await the results.
3. Scrape ``/metrics`` (Prometheus text), ``/stats`` (summary JSON) and
   ``/trace?request=`` (one request's span chain) over real HTTP.
4. Tail the JSONL trace file and print the per-request chains plus the
   quant-health callback and sample counters the registry collected.

Run:  PYTHONPATH=src python examples/observe_serving.py [--requests 4]
"""
import argparse
import json
import tempfile
import urllib.request

import jax

from repro import obs
from repro.configs import get_config
from repro.core.precision import PrecisionPlan
from repro.data.pipeline import mixed_len_prompts
from repro.models import lm
from repro.serving.engine import Engine
from repro.serving.server import AsyncServer

TINY = dict(d_model=64, n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128, vocab_size=64)


def _get(addr, path):
    host, port = addr
    with urllib.request.urlopen(f"http://{host}:{port}{path}", timeout=60) as r:
        return r.read().decode()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=8)
    args = ap.parse_args()

    cfg = get_config("qwen3-14b-smoke").with_(**TINY)
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    eng = Engine(
        cfg, params, max_len=args.prompt_len + args.gen, mode="continuous",
        max_wait_s=0.002,
        policy=PrecisionPlan(default="w8a8", use_kernel=True, name="demo"),
    )

    trace_path = tempfile.mktemp(suffix=".jsonl", prefix="spans_")
    # enable before the server so the JSONL mirror catches every event;
    # quant_every=1 samples every monitored call (demo volume is tiny)
    obs.enable_all(trace_path=trace_path, quant_every=1)

    prompts = mixed_len_prompts(cfg.vocab_size, args.requests, args.prompt_len)
    with AsyncServer(eng, metrics_port=0) as srv:
        addr = srv.metrics_address
        print(f"telemetry: http://{addr[0]}:{addr[1]}/metrics  /stats  /trace")
        print(f"span JSONL: {trace_path}")

        reqs = [srv.submit(p, args.gen) for p in prompts]
        outs = [srv.result(r, timeout=600) for r in reqs]
        jax.effects_barrier()  # drain quant-health debug callbacks
        print(f"served {len(outs)} requests "
              f"-> {sum(o.shape[-1] for o in outs)} tokens")

        # ---- /metrics: Prometheus text ---------------------------------
        metrics_text = _get(addr, "/metrics")
        wanted = [
            "serve_admitted_total", "serve_bucket_calls_total",
            "serve_request_latency_seconds_bucket", "quant_health_callbacks_total",
            "quant_clip_rate", "quant_health_samples_total",
        ]
        present = [n for n in wanted if n in metrics_text]
        print(f"scraped /metrics: {len(metrics_text.splitlines())} lines, "
              f"families present: {present}")
        for line in metrics_text.splitlines():
            if line.startswith(("quant_health_callbacks_total{", "quant_clip_rate{")):
                print(f"  {line}")

        # ---- /stats: the unified engine summary ------------------------
        stats = json.loads(_get(addr, "/stats"))
        print(f"scraped /stats: kind={stats['kind']} totals={stats['totals']} "
              f"scheduler={stats['scheduler']}")

        # ---- /trace: one request's span chain --------------------------
        chain = json.loads(_get(addr, f"/trace?request={reqs[0].req_id}"))
        phases = list(dict.fromkeys(e["phase"] for e in chain))
        print(f"scraped /trace for {reqs[0].req_id}: chain={' -> '.join(phases)}")

    # ---- offline: tail the JSONL mirror --------------------------------
    events = [json.loads(ln) for ln in open(trace_path)]
    by_req = {}
    for ev in events:
        if "request" in ev:
            by_req.setdefault(ev["request"], []).append(ev["phase"])
    complete = sum(
        1 for phases in by_req.values()
        if phases and phases[-1] in ("complete", "evicted", "failed")
    )
    print(f"JSONL trace: {len(events)} events, {len(by_req)} request chains, "
          f"{complete} closed")
    obs.disable_all()
    assert complete == len(reqs), "every request chain must close"
    assert all(n in metrics_text for n in wanted), "missing metric families"
    print("observability tour OK")


if __name__ == "__main__":
    main()
