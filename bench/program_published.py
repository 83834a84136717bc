"""VGGT-1B as published, served through the same path as ``program``:
the shared ``to_params`` tree with every LayerNorm at the configuration's
``norm_eps``, and each block's q/k LayerNorms (``q_norm``/``k_norm``) in
its attention; the two special-token sets pass through as they are.  The
program's architecture is the configuration's ``program_arch``
(``vggt-1b-published``: QK-norm, 2D RoPE, two special-token sets, exact
GELU)."""
from __future__ import annotations

import dataclasses
import functools

import jax

from bench import program
from bench.program import model_config, shutdown, telemetry  # noqa: F401


def to_params(w: dict, eps: float) -> dict:
    from repro.core.versaq import Norm

    def with_eps(n):
        return dataclasses.replace(n, eps=eps) if isinstance(n, Norm) else n

    tree = jax.tree.map(with_eps, program.to_params(w), is_leaf=lambda n: isinstance(n, Norm))
    for name, blk in w["blocks"].items():
        attn = tree["blocks"][name]["attn"]
        for norm, (g, b) in (("q_norm", ("qn_g", "qn_b")), ("k_norm", ("kn_g", "kn_b"))):
            attn[norm] = Norm(g=blk[g], b=blk[b], kind="ln", eps=eps)
    return tree


def serve(config: dict, w: dict, max_batch: int, policy: str | None = None):
    return program.serve(config, w, max_batch, policy,
                         to_params=functools.partial(to_params, eps=float(config["norm_eps"])),
                         model_config=model_config)
