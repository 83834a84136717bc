"""Pallas TPU kernel: integer matmul for VersaQ quantized linears.

TPU adaptation of the paper's reconfigurable INT PE array (§IV-B):

* **W8A8** — int8 × int8 → int32 straight onto the MXU
  (``preferred_element_type=jnp.int32``), output-stationary accumulation in
  a VMEM scratch tile across the K grid dimension (the systolic-array
  partial-sum locality of the paper, expressed as BlockSpec tiling).

* **W4A8 / W4A4** — weights packed two-int4-per-byte in HBM (the paper's
  INT4 mode halves *memory traffic*; TPU's MXU has no INT4 rate so compute
  runs at int8 rate — DESIGN.md §2).  The packed layout stores original
  K-rows ``[0, K/2)`` in low nibbles and ``[K/2, K)`` in high nibbles, so a
  packed K-tile maps to two *contiguous* activation K-tiles: the kernel
  receives the activation twice under different index maps and issues two
  MXU dots per step — no in-kernel deinterleave.

Scales are applied once at the final K step: per-token activation scale
[M,1] × per-channel weight scale [1,N] — matching the accelerator's
Quantization Unit placement at the array output.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BM = 256
DEFAULT_BN = 256
DEFAULT_BK = 512


def unpack_int4_planes(wp: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Packed uint8 [r, N] -> sign-extended (low, high) int8 nibble planes.

    Computed in int32: Mosaic has no int8 vector compare and no uint8
    shift on TPU.  ``(v ^ 8) - 8`` sign-extends a 4-bit value without a
    compare, and ``w >> 4`` of a byte is already its high nibble.
    """
    w = wp.astype(jnp.int32)
    lo = ((w & 0xF) ^ 8) - 8
    hi = ((w >> 4) ^ 8) - 8
    return lo.astype(jnp.int8), hi.astype(jnp.int8)


def _w8_kernel(x_ref, w_ref, xs_ref, ws_ref, o_ref, acc_ref, *, nk):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...],
        w_ref[...],
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )

    @pl.when(k == nk - 1)
    def _fin():
        o_ref[...] = (
            acc_ref[...].astype(jnp.float32) * xs_ref[...] * ws_ref[...]
        ).astype(o_ref.dtype)


def _w4_kernel(xlo_ref, xhi_ref, wp_ref, xs_ref, ws_ref, o_ref, acc_ref, *, nk):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    wlo, whi = unpack_int4_planes(wp_ref[...])
    dn = (((1,), (0,)), ((), ()))
    acc_ref[...] += jax.lax.dot_general(
        xlo_ref[...], wlo, dn, preferred_element_type=jnp.int32
    )
    acc_ref[...] += jax.lax.dot_general(
        xhi_ref[...], whi, dn, preferred_element_type=jnp.int32
    )

    @pl.when(k == nk - 1)
    def _fin():
        o_ref[...] = (
            acc_ref[...].astype(jnp.float32) * xs_ref[...] * ws_ref[...]
        ).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("packed", "out_dtype", "bm", "bn", "bk", "interpret"),
)
def quant_matmul(
    xv: jnp.ndarray,
    xs: jnp.ndarray,
    wv: jnp.ndarray,
    ws: jnp.ndarray,
    *,
    packed: bool,
    out_dtype=jnp.float32,
    bm: int = DEFAULT_BM,
    bn: int = DEFAULT_BN,
    bk: int = DEFAULT_BK,
    interpret: bool = False,
) -> jnp.ndarray:
    """y[M,N] = (xv·wv) * xs * ws.

    xv [M,K] int8, xs [M,1] f32, ws [1,N] f32;
    wv [K,N] int8, or [K//2,N] uint8 when ``packed``.
    """
    m, kdim = xv.shape
    n = wv.shape[-1]
    bm = min(bm, m)
    bn = min(bn, n)
    grid_k_unit = bk
    if packed:
        # one grid step covers bk original K rows = bk//2 packed rows
        kp = wv.shape[0]
        assert kp * 2 == kdim, (kp, kdim)
        bk = min(bk, kdim)
        assert kdim % bk == 0 and bk % 2 == 0
        nk = kdim // bk
        bk2 = bk // 2
        nkb = kdim // 2 // bk2  # == nk
        grid = (m // bm, n // bn, nk)
        kernel = functools.partial(_w4_kernel, nk=nk)
        in_specs = [
            # activation lo-half rows: original rows [k*bk2, (k+1)*bk2)
            pl.BlockSpec((bm, bk2), lambda i, j, k: (i, k)),
            # activation hi-half rows: original rows [K/2 + k*bk2, ...)
            pl.BlockSpec((bm, bk2), lambda i, j, k, _nkb=nkb: (i, _nkb + k)),
            pl.BlockSpec((bk2, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((bm, 1), lambda i, j, k: (i, 0)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ]
        operands = (xv, xv, wv, xs, ws)
    else:
        bk = min(bk, kdim)
        assert kdim % bk == 0
        nk = kdim // bk
        grid = (m // bm, n // bn, nk)
        kernel = functools.partial(_w8_kernel, nk=nk)
        in_specs = [
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((bm, 1), lambda i, j, k: (i, 0)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ]
        operands = (xv, wv, xs, ws)
    del grid_k_unit
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
    )(*operands)
