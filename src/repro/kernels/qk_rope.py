"""Pallas TPU kernel: published VGGT's q/k preparation in one pass over HBM.

Between the fused ``wqkv`` projection and two-stage attention, each head's
q and k (published VGGT, ``vggt/layers/attention.py``) take

  LayerNorm over the head's ``dh`` features (one γ, β shared by the heads;
  q and k have their own)
  → 2D RoPE (``models.layers.rope2d_table``) → the quantized flow's online
  per-head WHT (``core.versaq.head_wht``).

Unfused, XLA reads and writes q and k once per step.  Here one launch
reads q and k where the ``wqkv`` site left them and writes float q and k
for ``kernels.ops.two_stage_mha``.

Layout.  The ``wqkv`` output is viewed as ``[F, T, W]``: F frames of T
tokens (a global sequence of S frames is S such frames of rows, and its
tokens reuse their frame's positions), q in columns ``[0, H·dh)`` and k
in ``[H·dh, 2·H·dh)``.  The grid is ``(F, H·dh / bn)``: a step
reads one frame's T rows of one ``bn``-wide column block of q and of k
and writes that block of each output; the [T, w] cos/sin tables and the
constant matrices are the same block at every step, so they stay in VMEM.

Arithmetic.  Each ``w``-lane slice (``w`` = 128 lanes hold ``128/dh``
heads) is worked on in place, so no head ever leaves its lanes (folding
heads into rows is a relayout Mosaic refuses).  A head's mean and
variance are full-row sums of the slice masked to the head's lanes, put
back on those lanes; the rest is two float32 matmuls against constant
[w, w] matrices:

  xn = (x − mean)·rsqrt(var + eps)·γ + β
  y = (xn·cos + (xn·R)·sin)·H      R: rotate_half of each quarter pair
                                   H = I ⊗ H_dh, the per-head WHT

The matmuls run at ``HIGHEST`` precision: R and H are exact in bf16, but
xn is not.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["qk_norm_rope"]

LANE = 128
VMEM_LIMIT = 64 * 1024 * 1024
COL_BLOCKS = (512, 256, 128)  # column block widths tried, widest first


def launch_name(role: str | None) -> str:
    return "qk_norm_rope" if role is None else f"qk_norm_rope_{role}"


def slice_matrices(w: int, dh: int) -> np.ndarray:
    """The [2, w, w] constants R, H for a ``w``-lane slice of ``w/dh``
    heads (see the module docstring)."""
    eye = np.eye(w // dh)
    q = dh // 4
    rot = np.zeros((dh, dh))  # (x·R)[j] = rotate_half within each half
    for half in (0, 2 * q):
        for j in range(q):
            rot[half + q + j, half + j] = -1.0  # out[j] = −x[j+q]
            rot[half + j, half + q + j] = 1.0  # out[j+q] = x[j]
    h = np.ones((1, 1))
    while h.shape[0] < dh:  # Sylvester order, as transforms.fast_wht
        h = np.block([[h, h], [h, -h]])
    h /= np.sqrt(dh)
    mats = [rot, h]
    return np.stack([np.kron(eye, m) for m in mats]).astype(np.float32)


def _kernel(q_ref, k_ref, cos_ref, sin_ref, n_ref, m_ref, qo_ref, ko_ref, *, w, dh, eps):
    hi = jax.lax.Precision.HIGHEST

    def dot(x, i):
        return jnp.dot(x, m_ref[i], precision=hi, preferred_element_type=jnp.float32)

    cos, sin = cos_ref[...], sin_ref[...]
    norms = n_ref[...]
    lane = jax.lax.broadcasted_iota(jnp.int32, cos.shape, 1)
    heads = [(lane >= j) & (lane < j + dh) for j in range(0, w, dh)]

    def head_mean(v):  # each head's mean over its lanes, on those lanes
        out = v
        for m in heads:
            out = jnp.where(m, jnp.sum(jnp.where(m, v, 0.0), axis=-1, keepdims=True) / dh, out)
        return out

    for i, (src, dst) in enumerate(((q_ref, qo_ref), (k_ref, ko_ref))):
        g, b = norms[2 * i : 2 * i + 1], norms[2 * i + 1 : 2 * i + 2]
        for c in range(0, src.shape[-1], w):
            x = src[0, :, c : c + w].astype(jnp.float32)
            xc = x - head_mean(x)
            xn = xc * jax.lax.rsqrt(head_mean(xc * xc) + eps) * g + b
            dst[0, :, c : c + w] = dot(xn * cos + dot(xn, 0) * sin, 1).astype(dst.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("n_heads", "head_dim", "eps", "bn", "role", "interpret"),
)
def qk_norm_rope(
    x: jnp.ndarray,
    cos: jnp.ndarray,
    sin: jnp.ndarray,
    norms: jnp.ndarray,
    *,
    n_heads: int,
    head_dim: int,
    eps: float,
    bn: int,
    role: str | None = None,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """q and k, [F, T, n_heads·head_dim] float32 each, from the [F, T, W]
    array ``x`` whose first 2·n_heads·head_dim columns hold them (``bn``
    divides n_heads·head_dim).  ``cos``/``sin`` are one frame's
    [T, head_dim] tables; ``norms`` [4, head_dim] the LayerNorm weights
    γ_q, β_q, γ_k, β_k, each shared by the heads.  Launches are named
    ``qk_norm_rope_<role>``."""
    f, t, _ = x.shape
    hd = n_heads * head_dim
    w = min(LANE, hd)
    assert hd % bn == 0 and bn % w == 0 and w % head_dim == 0, (hd, bn, w, head_dim)
    reps = w // head_dim
    tables = [jnp.tile(a.astype(jnp.float32), (1, reps)) for a in (cos, sin)]
    norms = jnp.tile(norms.astype(jnp.float32), (1, reps))
    mats = jnp.asarray(slice_matrices(w, head_dim))
    kb = hd // bn  # k's first column block
    const = lambda shape: pl.BlockSpec(shape, lambda i, j: (0,) * len(shape))  # noqa: E731
    return pl.pallas_call(
        functools.partial(_kernel, w=w, dh=head_dim, eps=eps),
        grid=(f, hd // bn),
        in_specs=[
            pl.BlockSpec((1, t, bn), lambda i, j: (i, 0, j)),
            pl.BlockSpec((1, t, bn), lambda i, j: (i, 0, kb + j)),
            const((t, w)),
            const((t, w)),
            const((4, w)),
            const((2, w, w)),
        ],
        out_specs=[
            pl.BlockSpec((1, t, bn), lambda i, j: (i, 0, j)),
            pl.BlockSpec((1, t, bn), lambda i, j: (i, 0, j)),
        ],
        out_shape=[jax.ShapeDtypeStruct((f, t, hd), jnp.float32)] * 2,
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"), vmem_limit_bytes=VMEM_LIMIT
        ),
        name=launch_name(role),
    )(x, x, *tables, norms, mats)
