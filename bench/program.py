"""The system under test, as a user deploys it.

``launch/serve.py``'s path: ``AsyncServer(VGGTEngine(cfg, params,
policy=<spec>, attn_impl=<impl>, max_batch=...))`` with the engine's
defaults, started with a metrics port, which is how a deployment exposes
``/metrics`` and which turns the program's telemetry on (span events,
kernel counters, quant-health monitors) before anything is traced.

This module is the only one of the benchmark that imports the program;
``src`` must be importable (``run.py`` puts it on the path).
"""
from __future__ import annotations

import jax


def to_params(w: dict) -> dict:
    """``weights.init``'s plain tree in the layout of ``models/vggt.py``
    (the same arrays, no copies)."""
    from repro.core.versaq import Norm

    def ln(g, b):
        return Norm(g=g, b=b, kind="ln")

    def lin(wk, bk=None):
        return {"w": wk, "b": bk}

    def block(p):
        return {
            "attn_norm": ln(p["ln1_g"], p["ln1_b"]),
            "attn": {k: lin(p[k], p.get("b" + k[1])) for k in ("wq", "wk", "wv", "wo")},
            "ffn_norm": ln(p["ln2_g"], p["ln2_b"]),
            "ffn": {"w_up": lin(p["w_up"], p["b_up"]),
                    "w_down": lin(p["w_down"], p["b_down"])},
            "ls1": p["ls1"],
            "ls2": p["ls2"],
        }

    return {
        "patch_proj": lin(w["patch_w"], w["patch_b"]),
        "special_tokens": w["special"],
        "blocks": {k: block(v) for k, v in w["blocks"].items()},
        "final_norm": ln(w["lnf_g"], w["lnf_b"]),
        "camera_head": {"fc1": lin(w["cam1_w"], w["cam1_b"]),
                        "fc2": lin(w["cam2_w"], w["cam2_b"])},
        "dpt_head": {"fc1": lin(w["dpt1_w"], w["dpt1_b"]),
                     "fc2": lin(w["dpt2_w"], w["dpt2_b"])},
    }


def model_config(config: dict):
    """The program's ``ModelConfig`` for a benchmark configuration: the
    registered architecture with the configuration's sizes."""
    from repro.configs import get_config

    return get_config(config["program_arch"]).with_(
        n_layers=config["n_aa_pairs"], d_model=config["d_model"],
        n_heads=config["n_heads"], n_kv_heads=config["n_heads"],
        head_dim=config["head_dim"], d_ff=config["d_ff"],
        n_special_tokens=config["n_special_tokens"],
        layerscale_init=config["layerscale"], attn_bias=config["attn_bias"],
    )


def serve(config: dict, weights: dict, max_batch: int, policy: str | None = None):
    """Quantize the weights and start the server; returns (server, engine).

    ``policy`` replaces the configuration's precision (the control runs
    the program's lower-precision path through the same server)."""
    from repro.launch.specs import ServeSpec
    from repro.serving.server import AsyncServer
    from repro.serving.vggt_engine import VGGTEngine

    eng = VGGTEngine(
        model_config(config), to_params(weights),
        policy=ServeSpec.parse(policy or config["policy"]).materialize(),
        attn_impl=config["attn_impl"], max_batch=max_batch,
    )
    jax.block_until_ready(eng.params)  # quantize during set-up
    return AsyncServer(eng, metrics_port=0).start(), eng


def telemetry():
    """The program's span events so far (``obs/trace.py``), as dicts."""
    from repro.obs import trace

    tr = trace.current()
    return [] if tr is None else [e.to_dict() for e in tr.recent()]


def shutdown(srv) -> None:
    """Stop the server and turn the process-wide telemetry off again."""
    from repro import obs

    srv.stop()
    obs.disable_all()
