"""Random VGGT weights from a seed, made on the device in one jitted call.

The tree is the benchmark's own plain layout (``init``), read by the
reference as it is and handed to the program through ``program.to_params``.
Linear weights are N(0, 1/fan_in) and special tokens N(0, 0.02²), as
initialised; LayerScale is the configuration's ``layerscale``.  Norms and
biases are drawn at a trained-like scale from the configuration's
``init``: LayerNorm γ = 1 + N(0, σγ²), β = N(0, σβ²), every bias N(0, σb²)
(the attention projections carry ``qkv``/``proj`` biases where
``attn_bias`` says so), so that the comparison sees every bias fold and
epilogue.  ``init["zero"]`` names the tensors drawn as 0 instead.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

N_POSE = 9  # quaternion (4) + translation (3) + focal (2)
N_DPT = 5  # xyz (3) + depth + confidence


def key_for(seed: int, tag: int) -> jax.Array:
    """A PRNG key for any non-negative seed, also one wider than 32 bits."""
    word = np.random.SeedSequence([int(seed), tag]).generate_state(1, dtype=np.uint32)[0]
    return jax.random.PRNGKey(int(word))


def _lin(k, n_in, n_out):
    return jax.random.normal(k, (n_in, n_out), jnp.float32) / np.sqrt(n_in)


def _drawn(c: dict, name: str, k, n: int, std: str, mean: float = 0.0):
    """A norm or bias vector: ``mean`` + N(0, init[std]²), or 0 if named in
    ``init["zero"]``."""
    init = c["init"]
    if name in init["zero"]:
        return jnp.zeros((n,), jnp.float32)
    return mean + init[std] * jax.random.normal(k, (n,), jnp.float32)


def _block(key, c: dict) -> dict:
    d, f = c["d_model"], c["d_ff"]
    hd = c["n_heads"] * c["head_dim"]
    ks = jax.random.split(key, 16)
    ls = jnp.full((d,), c["layerscale"], jnp.float32)
    p = {
        "ln1_g": _drawn(c, "ln1_g", ks[6], d, "norm_gamma_std", 1.0),
        "ln1_b": _drawn(c, "ln1_b", ks[7], d, "norm_beta_std"),
        "wq": _lin(ks[0], d, hd), "wk": _lin(ks[1], d, hd), "wv": _lin(ks[2], d, hd),
        "wo": _lin(ks[3], hd, d), "ls1": ls,
        "ln2_g": _drawn(c, "ln2_g", ks[8], d, "norm_gamma_std", 1.0),
        "ln2_b": _drawn(c, "ln2_b", ks[9], d, "norm_beta_std"),
        "w_up": _lin(ks[4], d, f), "b_up": _drawn(c, "b_up", ks[10], f, "bias_std"),
        "w_down": _lin(ks[5], f, d), "b_down": _drawn(c, "b_down", ks[11], d, "bias_std"),
        "ls2": ls,
    }
    if c["attn_bias"]:
        for i, (name, n) in enumerate((("bq", hd), ("bk", hd), ("bv", hd), ("bo", d))):
            p[name] = _drawn(c, name, ks[12 + i], n, "bias_std")
    return p


@functools.partial(jax.jit, static_argnums=1)
def _init(key, frozen: tuple) -> dict:
    c = dict(frozen)
    c["init"] = dict(c["init"])
    d = c["d_model"]
    ks = jax.random.split(key, 16)
    pairs = c["n_aa_pairs"]
    blocks = {
        name: jax.vmap(lambda k: _block(k, c))(jax.random.split(kb, pairs))
        for name, kb in (("frame", ks[0]), ("global", ks[1]))
    }
    return {
        "patch_w": _lin(ks[2], d, d), "patch_b": _drawn(c, "patch_b", ks[8], d, "bias_std"),
        "special": jax.random.normal(ks[3], (c["n_special_tokens"], d), jnp.float32) * 0.02,
        "blocks": blocks,
        "lnf_g": _drawn(c, "lnf_g", ks[9], d, "norm_gamma_std", 1.0),
        "lnf_b": _drawn(c, "lnf_b", ks[10], d, "norm_beta_std"),
        "cam1_w": _lin(ks[4], d, d), "cam1_b": _drawn(c, "cam1_b", ks[11], d, "bias_std"),
        "cam2_w": _lin(ks[5], d, N_POSE),
        "cam2_b": _drawn(c, "cam2_b", ks[12], N_POSE, "bias_std"),
        "dpt1_w": _lin(ks[6], d, d), "dpt1_b": _drawn(c, "dpt1_b", ks[13], d, "bias_std"),
        "dpt2_w": _lin(ks[7], d, N_DPT), "dpt2_b": _drawn(c, "dpt2_b", ks[14], N_DPT, "bias_std"),
    }


SHAPE_KEYS = ("n_aa_pairs", "d_model", "n_heads", "head_dim", "d_ff",
              "n_special_tokens", "layerscale", "attn_bias")


def init(config: dict, seed: int) -> dict:
    """The configuration's weights for ``seed`` (float32, on the default device)."""
    init_ = tuple((k, tuple(v) if isinstance(v, list) else v)
                  for k, v in sorted(config["init"].items()))
    frozen = tuple((k, config[k]) for k in SHAPE_KEYS) + (("init", init_),)
    return _init(key_for(seed, 0), frozen)
