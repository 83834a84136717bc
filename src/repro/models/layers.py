"""Shared layer primitives for the model zoo.

Every linear/norm goes through ``core.versaq.apply_linear``/``apply_norm``
so the same model code runs full-precision (plain dict params) and
VersaQ-quantized (``QuantLinear``/``FoldedNorm`` params) — the paper's
flow is a parameter transformation, not a different model.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.core.versaq import Norm, apply_linear, apply_norm

__all__ = [
    "dense",
    "norm",
    "init_linear",
    "init_norm",
    "embed",
    "rope_freqs",
    "apply_rope",
    "rope2d_table",
    "apply_rope2d",
    "sincos_positions",
    "gelu",
    "gelu_erf",
    "silu",
]

dense = apply_linear
norm = apply_norm


def init_linear(key, d_in: int, d_out: int, *, bias: bool = False, dtype=jnp.float32, scale: float | None = None):
    s = scale if scale is not None else 1.0 / math.sqrt(d_in)
    p = {"w": (jax.random.normal(key, (d_in, d_out)) * s).astype(dtype)}
    p["b"] = jnp.zeros((d_out,), dtype) if bias else None
    return p


def init_norm(dim: int, *, kind: str = "rms", bias: bool = False, dtype=jnp.float32,
              eps: float = 1e-6):
    return Norm(
        g=jnp.ones((dim,), dtype),
        b=jnp.zeros((dim,), dtype) if bias else None,
        kind=kind,
        eps=eps,
    )


def embed(table: jnp.ndarray, ids: jnp.ndarray) -> jnp.ndarray:
    return jnp.take(table, ids, axis=0)


def rope_freqs(head_dim: int, theta: float, positions: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """cos/sin tables [*, L, head_dim//2] for given positions [*, L]."""
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    ang = positions[..., None].astype(jnp.float32) * inv
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray) -> jnp.ndarray:
    """Rotate pairs. x: [..., L, H, dh]; cos/sin: [..., L, dh//2]."""
    dh = x.shape[-1]
    x1 = x[..., : dh // 2]
    x2 = x[..., dh // 2 :]
    # broadcast cos/sin over the head axis (x is [..., L, H, dh])
    c = cos[..., :, None, :]
    s = sin[..., :, None, :]
    out = jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1)
    return out.astype(x.dtype)


def rope2d_table(
    grid: tuple[int, int], n_special: int, head_dim: int, theta: float
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """2D RoPE cos/sin tables [T, head_dim] for one frame of ``n_special``
    special tokens followed by a row-major ``grid`` = (rows, cols) of
    patches (published VGGT, ``vggt/layers/rope.py``): the patch at (y, x)
    has position (y + 1, x + 1), the special tokens (0, 0).  Features
    [0, head_dim/2) rotate by the row, the rest by the column; each half
    rotates ``rotate_half``-style with frequencies
    ``theta^(-(0, 2, …)/(head_dim/2))`` repeated over its two quarters."""
    rows, cols = grid
    y, x = jnp.meshgrid(jnp.arange(rows), jnp.arange(cols), indexing="ij")
    pos = jnp.stack([y.reshape(-1), x.reshape(-1)], axis=-1).astype(jnp.float32) + 1.0
    pos = jnp.concatenate([jnp.zeros((n_special, 2), jnp.float32), pos])  # [T, 2]
    half = head_dim // 2
    inv = 1.0 / (theta ** (jnp.arange(0, half, 2, dtype=jnp.float32) / half))
    ang = pos[:, :, None] * inv  # [T, 2, half/2]
    ang = jnp.concatenate([ang, ang], axis=-1).reshape(pos.shape[0], head_dim)
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope2d(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray) -> jnp.ndarray:
    """2D RoPE on x [N, L, H, dh] whose L tokens are whole frames of the
    [T, dh] tables' T tokens: token ℓ takes row ℓ mod T (global attention
    reuses each frame's positions).  Each quarter pair of a half rotates
    as ``x·cos + rotate_half(x)·sin``."""
    n, length, h, dh = x.shape
    t = cos.shape[0]
    xf = x.reshape(n, length // t, t, h, 4, dh // 4)
    a, b, c, d = (xf[..., i, :] for i in range(4))
    rot = jnp.stack([-b, a, -d, c], axis=-2).reshape(x.shape)
    c_ = cos[:, None, :]  # broadcast over heads; frames broadcast below
    s_ = sin[:, None, :]
    out = x.reshape(n, length // t, t, h, dh) * c_ + rot.reshape(n, length // t, t, h, dh) * s_
    return out.reshape(x.shape).astype(x.dtype)


def sincos_positions(length: int, dim: int, dtype=jnp.float32) -> jnp.ndarray:
    """Classic transformer sinusoidal position table [length, dim]."""
    pos = jnp.arange(length, dtype=jnp.float32)[:, None]
    i = jnp.arange(dim // 2, dtype=jnp.float32)[None, :]
    ang = pos / (10_000.0 ** (2 * i / dim))
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1).astype(dtype)


def gelu(x):
    return jax.nn.gelu(x, approximate=True)


def gelu_erf(x):
    return jax.nn.gelu(x, approximate=False)


def silu(x):
    return jax.nn.silu(x)
