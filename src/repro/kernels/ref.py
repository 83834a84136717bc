"""Pure-jnp oracles for every Pallas kernel in this package.

Each kernel's test sweeps shapes/dtypes and asserts allclose against these.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.core import transforms
from repro.core.quantize import unpack_int4

__all__ = [
    "quant_matmul_ref",
    "attention_ref",
    "two_stage_attention_ref",
    "wht_ref",
]


def quant_matmul_ref(xv, xs, wv, ws, *, packed: bool, out_dtype=jnp.float32):
    """Oracle for the integer matmul: exact int32 accumulate, then scale.

    xv [M,K] int8, xs [M,1] f32, wv [K,N] int8 (or [K//2,N] uint8 packed),
    ws [1,N] f32.
    """
    if packed:
        wv = unpack_int4(wv, axis=0)
    acc = jnp.dot(xv.astype(jnp.int32), wv.astype(jnp.int32))
    return (acc.astype(jnp.float32) * xs * ws).astype(out_dtype)


def attention_ref(q, k, v, *, causal: bool, scale: float | None = None):
    """FP softmax attention oracle. q,k,v: [..., L, dh] float."""
    dh = q.shape[-1]
    scale = scale if scale is not None else 1.0 / jnp.sqrt(jnp.float32(dh))
    s = jnp.einsum("...qd,...kd->...qk", q.astype(jnp.float32), k.astype(jnp.float32)) * scale
    if causal:
        lq, lk = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((lq, lk), bool), k=lk - lq)
        s = jnp.where(mask, s, -jnp.inf)
    p = jnp.exp(s - s.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    return jnp.einsum("...qk,...kd->...qd", p, v.astype(jnp.float32))


@functools.partial(jax.jit, static_argnames=("causal", "scale"))
def two_stage_attention_ref(
    qv, qs, kv, ks, vv, vs, *, causal: bool, scale: float | None = None
):
    """Oracle for the INT two-stage kernel (paper Alg. 1), including the
    INT8 re-quantization of the softmax probabilities (Alg. 1 line 11).

    qv/kv/vv: [..., L, dh] int8; qs/ks: per-token scales [..., L, 1] f32;
    vs: per-tensor (per-head) scalar scale.

    Jitted, so that XLA fuses the score epilogue into one loop as it does
    the kernel's body in interpret mode: a CPU fusion contracts
    ``s·k − M`` into one fused multiply-add, which rounds once where the
    op-by-op form rounds twice and can flip ⌊127·exp(s−M)⌉.
    """
    dh = qv.shape[-1]
    scale = scale if scale is not None else 1.0 / (dh**0.5)
    # integer-exact dot first, scales after, in the kernel's exact op order:
    # the q scale with the softmax scale folded in, then the key scale
    # (int8 products/sums are exact in f32; any other order rounds
    # differently and flips ⌊127·exp(s−M)⌉ at boundaries)
    s_int = jnp.einsum(
        "...qd,...kd->...qk", qv.astype(jnp.float32), kv.astype(jnp.float32)
    )
    s = s_int * (qs * scale) * jnp.swapaxes(ks, -1, -2)
    if causal:
        lq, lk = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((lq, lk), bool), k=lk - lq)
        s = jnp.where(mask, s, -jnp.inf)
    m = s.max(-1, keepdims=True)
    p = jnp.exp(s - m)
    l = p.sum(-1, keepdims=True)
    # Alg.1 line 11: quant(S) to int8 with the optimal per-row scale —
    # exp(s−M) has row max 1 so ⌊127·exp(s−M)⌉ spans the full range; the
    # 1/Σ normalization folds into the output scale.  As in the kernel,
    # the factor 127 enters through the exponent: exp(s − (M − ln 127)).
    pq = jnp.round(jnp.exp(s - (m - math.log(127.0))))
    o = jnp.einsum("...qk,...kd->...qd", pq, vv.astype(jnp.float32))
    return o * (vs / 127.0) / l


def wht_ref(x):
    """Blocked Walsh-Hadamard transform oracle (dense matmul)."""
    dim = x.shape[-1]
    hb = transforms.blocked_hadamard_matrix(dim, dtype=jnp.float32)
    return (x.astype(jnp.float32) @ hb).astype(x.dtype)
