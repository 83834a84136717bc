"""Pallas TPU kernel: blocked Walsh-Hadamard transform (online rotation).

TPU adaptation of the accelerator's "±1 WHT mode" (§IV-B): the Hadamard
matrix is never stored and never multiplied —

* the **inter-lane** factor H_{g} (g = block/128 groups) is computed as a
  log₂(g) add/sub butterfly over sublane groups (pure VPU adds), and
* the **intra-lane** factor H_128 is a single 128×128 MXU dot — on TPU one
  dense [128,128] matmul is faster than eight shuffle stages across lanes,
  so this is where the "±1 PE" insight lands on real hardware.

Since H_block = H_g ⊗ H_128, composing the two gives the exact blocked WHT.
For blocks < 128 the kernel falls back to a single small dot.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import transforms

LANE = 128


def _wht_kernel(x_ref, h_ref, o_ref, *, block, rows):
    x = x_ref[...]  # [br, d]
    d = x.shape[-1]
    g = block // LANE if block >= LANE else 1
    nblk = d // block
    if block >= LANE:
        # view as [br, nblk, g, LANE]
        xv = x.reshape(rows, nblk, g, LANE).astype(jnp.float32)
        # inter-lane butterfly over the g dimension (adds/subs only)
        h = 1
        while h < g:
            xv = xv.reshape(rows, nblk, g // (2 * h), 2, h, LANE)
            a = xv[:, :, :, 0]
            b = xv[:, :, :, 1]
            xv = jnp.stack([a + b, a - b], axis=3)
            h *= 2
        xv = xv.reshape(rows, nblk, g, LANE)
        # intra-lane factor: one MXU dot with H_128
        xv = jnp.einsum("rngl,lm->rngm", xv, h_ref[...])
        scale = 1.0 / math.sqrt(g)
        o_ref[...] = (xv * scale).reshape(rows, d).astype(o_ref.dtype)
    else:
        xv = x.reshape(rows * nblk, block).astype(jnp.float32)
        xv = jnp.dot(xv, h_ref[...], preferred_element_type=jnp.float32)
        o_ref[...] = xv.reshape(rows, d).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block", "br", "interpret"))
def wht(
    x: jnp.ndarray,
    *,
    block: int | None = None,
    br: int = 256,
    interpret: bool = False,
) -> jnp.ndarray:
    """Blocked WHT along the last axis of a 2D array [R, d]."""
    r, d = x.shape
    block = block or transforms.block_size_for(d)
    br = min(br, r)
    assert r % br == 0
    hsize = LANE if block >= LANE else block
    h = transforms.hadamard_matrix(hsize, dtype=jnp.float32)
    return pl.pallas_call(
        functools.partial(_wht_kernel, block=block, rows=br),
        grid=(r // br,),
        in_specs=[
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            pl.BlockSpec((hsize, hsize), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((br, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r, d), x.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
    )(x, h)
