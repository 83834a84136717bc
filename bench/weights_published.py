"""Random weights of VGGT-1B as published, from a seed: ``weights.init``'s
tree, whose blocks also hold the q and k LayerNorms over a head's
features (``qn_g``, ``qn_b``, ``kn_g``, ``kn_b``, each [head_dim], drawn at
the configuration's norm scales) and whose ``special`` holds two sets of
special tokens, [2, n_special_tokens, d] (frame 0 takes set 0, the other
frames set 1)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench import weights


def _block(key, c: dict) -> dict:
    p = weights._block(key, c)
    dh = c["head_dim"]
    ks = jax.random.split(jax.random.fold_in(key, 1), 4)
    for i, (name, std, mean) in enumerate((("qn_g", "norm_gamma_std", 1.0),
                                           ("qn_b", "norm_beta_std", 0.0),
                                           ("kn_g", "norm_gamma_std", 1.0),
                                           ("kn_b", "norm_beta_std", 0.0))):
        p[name] = weights._drawn(c, name, ks[i], dh, std, mean)
    return p


def init(config: dict, seed: int) -> dict:
    w = weights.init(config, seed, block=_block)
    shape = (int(config["n_special_sets"]), int(config["n_special_tokens"]),
             int(config["d_model"]))
    w["special"] = jax.random.normal(weights.key_for(seed, 1), shape, jnp.float32) * 0.02
    return w
