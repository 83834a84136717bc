"""Plain float32 reference of VGGT-1B as published, served at W4A{8,4}.

``reference.Reference`` with the published block (facebookresearch/vggt,
``vggt/layers/block.py``, ``attention.py``, ``rope.py``;
``vggt/models/aggregator.py``), written from those equations:

* after the qkv projection, q and k of each head take a LayerNorm over
  the head's ``head_dim`` features (``nn.LayerNorm(head_dim)``: one γ, β
  shared by the heads, ``norm_eps``), then 2D RoPE with base
  ``rope_theta``: features [0, dh/2) rotate by the patch's row, [dh/2, dh)
  by its column, each half as ``x·cos + rotate_half(x)·sin`` with
  frequencies ``theta^(-(0, 2, …)/(dh/2))`` repeated over its two
  quarters.  A frame's patches lie on a square row-major grid at
  positions (row + 1, column + 1), its special tokens at (0, 0); global
  attention reuses each frame's positions.  Then the served flow's
  per-head WHT and INT8 attention, as in ``reference``;
* the FFN's GELU is exact (``nn.GELU()``, erf);
* frame 0 of a scene takes special-token set 0, frames 1..S-1 set 1
  (``slice_expand_and_flatten``).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from bench import reference
from bench.reference import wht


def rope2d(t: int, n_special: int, dh: int, theta: float):
    """cos, sin [t, dh] of one frame: ``n_special`` tokens at (0, 0), then
    a square grid of patches at (row + 1, column + 1)."""
    side = math.isqrt(t - n_special)
    assert side * side == t - n_special, (t, n_special)
    rows = [(0.0, 0.0)] * n_special + [(y + 1.0, x + 1.0)
                                       for y in range(side) for x in range(side)]
    pos = jnp.asarray(rows, jnp.float32)  # [t, 2]: (row, column)
    half = dh // 2
    inv = 1.0 / (theta ** (jnp.arange(0, half, 2, dtype=jnp.float32) / half))

    def one_axis(p):  # [t] -> [t, half], angles repeated over the two quarters
        a = p[:, None] * inv[None, :]
        return jnp.concatenate([a, a], axis=-1)

    ang = jnp.concatenate([one_axis(pos[:, 0]), one_axis(pos[:, 1])], axis=-1)
    return jnp.cos(ang), jnp.sin(ang)


def _rotate_half(x):
    h = x.shape[-1] // 2
    return jnp.concatenate([-x[..., h:], x[..., :h]], axis=-1)


class Reference(reference.Reference):
    def _prep_block(self, p):
        out = super()._prep_block(p)
        out.update({k: p[k] for k in ("qn_g", "qn_b", "kn_g", "kn_b")})
        return out

    def _head_norm(self, x, g, b):
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + float(self.c["norm_eps"])) * g + b

    def _rope(self, x, t):
        """x [N, L, H, dh], L a whole number of frames of t tokens."""
        n, length, h, dh = x.shape
        cos, sin = rope2d(t, int(self.c["n_special_tokens"]), dh, float(self.c["rope_theta"]))
        x = x.reshape(n, length // t, t, h, dh)
        c, s = cos[:, None, :], sin[:, None, :]
        halves = [x[..., : dh // 2], x[..., dh // 2 :]]
        out = [u * c[..., i * dh // 2 : (i + 1) * dh // 2]
               + _rotate_half(u) * s[..., i * dh // 2 : (i + 1) * dh // 2]
               for i, u in enumerate(halves)]
        return jnp.concatenate(out, axis=-1).reshape(n, length, h, dh)

    def _attention(self, p, q, k, v, t):
        q = self._rope(self._head_norm(q, p["qn_g"], p["qn_b"]), t)
        k = self._rope(self._head_norm(k, p["kn_g"], p["kn_b"]), t)
        return super()._attention(p, q, k, v, t)

    def _block(self, p, x, t):
        n, length, d = x.shape
        nh, dh = int(self.c["n_heads"]), int(self.c["head_dim"])
        qkv = self._linear(self._norm(x), p["w_qkv"], p["s_qkv"], p["b_qkv"])
        q, k, v = (u.reshape(n, length, nh, dh) for u in jnp.split(qkv, 3, axis=-1))
        o = self._attention(p, q, k, v, t)
        x = x + self._linear(o, p["w_o"], p["s_o"], p["b_o"])
        h = self._linear(self._norm(x), p["w_up"], p["s_up"], p["b_up"])
        h = wht(jax.nn.gelu(h, approximate=False), self.cap)
        return x + self._linear(h, p["w_down"], p["s_down"], p["b_down"])

    @functools.partial(jax.jit, static_argnums=0)
    def forward(self, prep: dict, patches) -> dict:
        """patches [B, S, P, d] -> pose, points, depth, conf, tokens (rotated)."""
        with jax.default_matmul_precision("highest"):
            b, s, p_, d = patches.shape
            ns = prep["special"].shape[1]
            x = patches @ prep["patch_w"] + prep["patch_b"]
            first = jnp.broadcast_to(prep["special"][0], (b, 1, ns, d))
            rest = jnp.broadcast_to(prep["special"][1], (b, s - 1, ns, d))
            x = jnp.concatenate([jnp.concatenate([first, rest], axis=1), x], axis=2)
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
