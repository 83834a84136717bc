"""Plain float32 reference of VGGT served at W4A{8,4} (VersaQ-3D, paper §III).

Written from the paper's description, importing nothing of the program
under test: the forward of ``weights.init``'s plain tree in ``jax.numpy``,
every matmul under ``default_matmul_precision("highest")``.  It keeps
the quantizers the configuration states, because they are the model as
served: against an unquantized forward the 4-bit weights alone put the
W4A8 and the W4A4 answers about equally far off, and no comparison could
tell the two apart.

* Offline (Fig. 6): each projection W becomes ``Hᵀ·γ·W·Dᵀ`` — blocked
  Walsh-Hadamard H on the input side (the residual stream is kept
  rotated), the pre-norm's γ folded in, and the blocked orthonormal DCT-II
  D on the output side — then symmetric ``w_bits`` per output channel.
  V and O carry a per-head Hadamard pair, O and the FFN down projection
  take LayerScale and the output-side H, and the down projection's input
  side takes the H of the FFN width.
* Online (Fig. 5): LayerNorm statistics in the rotated domain, symmetric
  per-token ``a_bits`` quantization before every projection (the FFN's
  hidden after its GELU and WHT too), the block IDCT after each integer
  matmul, then the bias.
* Attention (Alg. 1): per-head WHT of Q and K, Q/K quantized per token and
  V per head to ``attn_bits``, exact integer scores, softmax with
  ``round(127·exp(s − max))`` probabilities against the integer V.
* Heads: the final LayerNorm (γ, β folded into the first layers), camera
  head on the first special token, DPT stub on the patch tokens, in float.

The block sizes are the configuration's: the largest power of two that
divides a width (at most ``hadamard_block_cap``) for H, ``dct_block`` for D.

A configuration whose blocks differ names a reference module of its own
that subclasses ``Reference`` and overrides the methods that differ:
``_prep_block`` (offline), ``_block`` or ``_attention`` (online).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np


def _pow2_block(dim: int, cap: int) -> int:
    b = dim & -dim
    while b > cap:
        b //= 2
    return b


def _hadamard(n: int) -> np.ndarray:
    h = np.ones((1, 1))
    while h.shape[0] < n:
        h = np.block([[h, h], [h, -h]])
    return h / math.sqrt(n)


def _dct(n: int) -> np.ndarray:
    """Orthonormal DCT-II, rows are the basis."""
    k = np.arange(n)[:, None]
    i = np.arange(n)[None, :]
    d = np.cos(np.pi * (2 * i + 1) * k / (2 * n)) * math.sqrt(2.0 / n)
    d[0] /= math.sqrt(2.0)
    return d


def wht(x, cap: int):
    """Blocked Walsh-Hadamard transform along the last axis (x·H)."""
    dim = x.shape[-1]
    b = _pow2_block(dim, cap)
    lead = x.shape[:-1]
    y = x.reshape(lead + (dim // b, b))
    h = 1
    while h < b:
        y = y.reshape(lead + (dim // b, b // (2 * h), 2, h))
        y = jnp.stack([y[..., 0, :] + y[..., 1, :], y[..., 0, :] - y[..., 1, :]], axis=-2)
        h *= 2
    return (y.reshape(lead + (dim,)) / math.sqrt(b)).astype(x.dtype)


def _rows(w, cap):
    """H·W along the input (row) axis of a [in, out] matrix."""
    return wht(w.T, cap).T


def _heads_cols(w, n_heads, cap):
    lead = w.shape[:-1]
    return wht(w.reshape(lead + (n_heads, -1)), cap).reshape(w.shape)


def _heads_rows(w, n_heads, cap):
    return _heads_cols(w.T, n_heads, cap).T


def _blocked(y, mat):
    b = mat.shape[0]
    lead = y.shape[:-1]
    return (y.reshape(lead + (y.shape[-1] // b, b)) @ mat).reshape(y.shape)


def quantize(x, bits: int, axis):
    """Symmetric quantization: (integer values as float32, scale)."""
    qmax = 2.0 ** (bits - 1) - 1
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / qmax
    return jnp.clip(jnp.round(x / scale), -qmax, qmax), scale


class Reference:
    """The reference for one configuration (its JSON as a dict)."""

    def __init__(self, config: dict):
        self.c = config
        self.cap = int(config["hadamard_block_cap"])
        self.dct = jnp.asarray(_dct(int(config["dct_block"])), jnp.float32)

    # ---- offline -------------------------------------------------------

    def _site(self, w):
        """Output-side DCT, then per-output-channel ``w_bits``: int8 values + scales."""
        if w.shape[-1] % self.dct.shape[0] == 0:
            w = _blocked(w, self.dct.T)
        q, s = quantize(w, int(self.c["w_bits"]), axis=0)
        return q.astype(jnp.int8), s

    def _prep_block(self, p):
        c, cap = self.c, self.cap
        nh = int(c["n_heads"])
        g1, b1, g2, b2 = p["ln1_g"], p["ln1_b"], p["ln2_g"], p["ln2_b"]
        wq = _rows(g1[:, None] * p["wq"], cap)
        wk = _rows(g1[:, None] * p["wk"], cap)
        wv = _heads_cols(_rows(g1[:, None] * p["wv"], cap), nh, cap)
        wo = wht(_heads_rows(p["wo"] * p["ls1"][None, :], nh, cap), cap)
        w_up = _rows(g2[:, None] * p["w_up"], cap)
        w_down = wht(_rows(p["w_down"] * p["ls2"][None, :], cap), cap)
        # the projections' own biases, where the configuration has them
        bq, bk, bv, bo = (p.get(k, jnp.zeros((w.shape[-1],), jnp.float32))
                          for k, w in (("bq", p["wq"]), ("bk", p["wk"]), ("bv", p["wv"]),
                                       ("bo", p["wo"])))
        bv = _heads_cols(bv + b1 @ p["wv"], nh, cap)
        out = {
            "b_qkv": jnp.concatenate([bq + b1 @ p["wq"], bk + b1 @ p["wk"], bv]),
            "b_o": wht(bo * p["ls1"], cap),
            "b_up": p["b_up"] + b2 @ p["w_up"],
            "b_down": wht(p["b_down"] * p["ls2"], cap),
        }
        for name, w in (("qkv", jnp.concatenate([wq, wk, wv], axis=1)), ("o", wo),
                        ("up", w_up), ("down", w_down)):
            out["w_" + name], out["s_" + name] = self._site(w)
        return out

    @functools.partial(jax.jit, static_argnums=0)
    def prepare(self, w: dict) -> dict:
        """Fold and quantize the plain weights (``weights.init``)."""
        with jax.default_matmul_precision("highest"):
            cap = self.cap
            return {
                "patch_w": wht(w["patch_w"], cap), "patch_b": wht(w["patch_b"], cap),
                "special": wht(w["special"], cap),
                "blocks": {k: jax.vmap(self._prep_block)(v) for k, v in w["blocks"].items()},
                **{f"{h}1_w": _rows(w["lnf_g"][:, None] * w[f"{h}1_w"], cap)
                   for h in ("cam", "dpt")},
                **{f"{h}1_b": w[f"{h}1_b"] + w["lnf_b"] @ w[f"{h}1_w"] for h in ("cam", "dpt")},
                **{k: w[k] for k in ("cam2_w", "cam2_b", "dpt2_w", "dpt2_b")},
            }

    # ---- online --------------------------------------------------------

    def _norm(self, x):
        """LayerNorm without γ/β, on and to the rotated stream."""
        ones = wht(jnp.ones((x.shape[-1],), jnp.float32), self.cap)
        mu = (x @ ones)[..., None] / x.shape[-1]
        xc = x - mu * ones
        var = jnp.mean(xc * xc, axis=-1, keepdims=True)
        return xc * jax.lax.rsqrt(var + float(self.c["norm_eps"]))

    def _linear(self, x, w, s, b, bits=None):
        a, sa = quantize(x, int(bits or self.c["a_bits"]), axis=-1)
        y = (a @ w.astype(jnp.float32)) * sa * s
        if y.shape[-1] % self.dct.shape[0] == 0:
            y = _blocked(y, self.dct)
        return y + b

    def _attention(self, p, q, k, v, t):
        """[N, L, H, dh] each, q and k before their per-head WHT, ->
        [N, L, H·dh]; one head at a time.  ``p`` is the block's prepared
        weights and ``t`` the tokens of one frame (L is a multiple of it),
        for a configuration whose q and k depend on a token's place."""
        q, k = wht(q, self.cap), wht(k, self.cap)
        bits = int(self.c["attn_bits"])
        qmax = 2.0 ** (bits - 1) - 1
        dh = q.shape[-1]

        def head(args):
            qh, kh, vh = args  # [N, L, dh]
            qi, sq = quantize(qh, bits, axis=-1)
            ki, sk = quantize(kh, bits, axis=-1)
            vi, sv = quantize(vh, bits, axis=(1, 2))
            s = jnp.einsum("nqd,nkd->nqk", qi, ki) * sq * jnp.swapaxes(sk, 1, 2)
            p = jnp.exp(s / math.sqrt(dh) - jnp.max(s / math.sqrt(dh), -1, keepdims=True))
            o = jnp.einsum("nqk,nkd->nqd", jnp.round(p * qmax), vi)
            return o * (sv / qmax) / jnp.sum(p, -1, keepdims=True)

        o = jax.lax.map(head, tuple(jnp.moveaxis(u, 2, 0) for u in (q, k, v)))
        return jnp.moveaxis(o, 0, 2).reshape(q.shape[:2] + (-1,))

    def _block(self, p, x, t):
        """One AA block over x [N, L, d]; ``t`` as in ``_attention``."""
        n, length, d = x.shape
        nh, dh = int(self.c["n_heads"]), int(self.c["head_dim"])
        qkv = self._linear(self._norm(x), p["w_qkv"], p["s_qkv"], p["b_qkv"])
        q, k, v = (u.reshape(n, length, nh, dh) for u in jnp.split(qkv, 3, axis=-1))
        o = self._attention(p, q, k, v, t)
        x = x + self._linear(o, p["w_o"], p["s_o"], p["b_o"])
        h = self._linear(self._norm(x), p["w_up"], p["s_up"], p["b_up"])
        h = wht(jax.nn.gelu(h, approximate=True), self.cap)
        return x + self._linear(h, p["w_down"], p["s_down"], p["b_down"])

    @functools.partial(jax.jit, static_argnums=0)
    def forward(self, prep: dict, patches) -> dict:
        """patches [B, S, P, d] -> pose, points, depth, conf, tokens (rotated)."""
        with jax.default_matmul_precision("highest"):
            b, s, p_, d = patches.shape
            ns = prep["special"].shape[0]
            x = patches @ prep["patch_w"] + prep["patch_b"]
            spec = jnp.broadcast_to(prep["special"], (b, s, ns, d))
            x = jnp.concatenate([spec, x], axis=2)
            t = ns + p_

            def pair(xc, bp):
                xc = self._block(bp["frame"], xc.reshape(b * s, t, d), t)
                xc = self._block(bp["global"], xc.reshape(b, s * t, d), t)
                return xc.reshape(b, s, t, d), None

            x, _ = jax.lax.scan(pair, x, prep["blocks"])
            x = self._norm(x)
            pose = jnp.tanh(x[:, :, 0] @ prep["cam1_w"] + prep["cam1_b"])
            pose = pose @ prep["cam2_w"] + prep["cam2_b"]
            h = jax.nn.gelu(x[:, :, ns:] @ prep["dpt1_w"] + prep["dpt1_b"], approximate=True)
            out = h @ prep["dpt2_w"] + prep["dpt2_b"]
            return {"pose": pose, "points": out[..., :3], "depth": out[..., 3],
                    "conf": jax.nn.sigmoid(out[..., 4]), "tokens": x}
