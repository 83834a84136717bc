"""Pallas TPU kernel: two-stage recomputation-based attention (paper Alg. 1).

The paper's answer to VGGT's long-sequence global attention: instead of
FlashAttention's single pass (which must carry a running O accumulator and
rescale it whenever the row max moves), split the work into

  **Stage ①** — stream K tiles against each Q tile and maintain only
  the softmax statistics ``M`` (row max) and ``Σ`` (row sum), Eq. 8-9.
  No V traffic, no O accumulator: the VMEM working set is one Q tile, one
  K tile and two [T_Q, 1] vectors.

  **Stage ②** — *recompute* Q·Kᵀ (cheap INT8 MXU work) against **larger**
  K/V tiles using the now-final (M, Σ): every probability is exact on first
  computation (Eq. 10), so O tiles are produced once, in order, with no
  rescaling and no O re-reads — the paper's claimed buffer/memory-traffic
  saving, at the cost of one extra QKᵀ pass.

Both stages run the score matmul in INT8 (dequantizing per-token scales
before the softmax exactly like Alg. 1 line 4), and Stage ② re-quantizes
the probabilities to INT8 (line 11) so the P·V matmul also hits the MXU in
int8 — V therefore carries a per-head (per-tensor) scale, since a
per-token V scale would not factor out of the contraction.

Tiles.  The paper's T_Q = T_K = 64 (Stage ①) and T_V = 2048 (Stage ②)
size its ASIC's buffers.  On a TPU v5e a grid step costs a fixed
0.35–0.4 µs whatever its tile holds, and a score tile lies on the 128-lane
vreg with its key axis on the lanes, so the tiles here follow the vreg and
the MXU instead: ``kernels.ops.attention_tiles`` pads each token axis to
a multiple of 128 and picks 128-aligned tiles of tens to hundreds of
thousands of scores (T_Q/T_K/T_V below are its targets, chosen by a sweep
on the chip).  Stage ① masks the padded keys only in the key tiles that
reach past ``kv_len``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

# Tile targets (``kernels.ops.attention_tiles`` resolves them per length),
# from a sweep of both stages at VGGT-1B's frame and global lengths on a
# TPU v5e (benchmarks/attn_tile_sweep.py): every larger tile was faster,
# up to 512 query rows and whole key rows of 2,816.
T_Q = 512
T_K = 4096
T_V = 4096


def _stage1_kernel(
    qv_ref, kv_ref, qs_ref, ks_ref, m_ref, l_ref, m_acc, l_acc, *, nk, scale, causal,
    bq, bk, kv_len
):
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_acc[...] = jnp.full_like(m_acc, NEG_INF)
        l_acc[...] = jnp.zeros_like(l_acc)

    def accumulate(kv_len):
        s = jax.lax.dot_general(
            qv_ref[0],
            kv_ref[0],
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.int32,
        )
        s = s.astype(jnp.float32) * qs_ref[0] * ks_ref[0].T * scale  # dequant (line 4)
        if causal or kv_len is not None:
            cols = j * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            if causal:
                rows = i * bq + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
                s = jnp.where(rows >= cols, s, NEG_INF)
            if kv_len is not None:  # padded tail keys are not real
                s = jnp.where(cols < kv_len, s, NEG_INF)
        m_new = jnp.maximum(m_acc[...], s.max(axis=-1, keepdims=True))  # Eq. 8
        l_acc[...] = l_acc[...] * jnp.exp(m_acc[...] - m_new) + jnp.exp(s - m_new).sum(
            axis=-1, keepdims=True
        )  # Eq. 9
        m_acc[...] = m_new

    if kv_len is None:
        accumulate(None)
    else:
        # only the key tiles that reach past kv_len pay for the mask
        first_padded = kv_len // bk
        if first_padded > 0:
            pl.when(j < first_padded)(lambda: accumulate(None))
        pl.when(j >= first_padded)(lambda: accumulate(kv_len))

    @pl.when(j == nk - 1)
    def _fin():
        m_ref[0] = m_acc[...]
        l_ref[0] = jnp.maximum(l_acc[...], 1e-30)


def _stage2_kernel(
    qv_ref,
    kv_ref,
    vv_ref,
    qs_ref,
    ks_ref,
    m_ref,
    l_ref,
    o_ref,
    acc_ref,
    *,
    nkv,
    scale,
    v_scale,
    causal,
    bq,
    bkv,
    kv_len,
):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # recompute scores against the mega-tile (lines 9-10)
    s = jax.lax.dot_general(
        qv_ref[0],
        kv_ref[0],
        (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    s = s.astype(jnp.float32) * qs_ref[0] * ks_ref[0].T * scale
    if causal or kv_len is not None:
        cols = j * bkv + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        if causal:
            i = pl.program_id(1)
            rows = i * bq + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            s = jnp.where(rows >= cols, s, NEG_INF)
        if kv_len is not None:
            s = jnp.where(cols < kv_len, s, NEG_INF)
    # Eq. 10 with the 1/Σ folded into the output scale: exp(s−M) has row max
    # exactly 1, so ⌊127·exp(s−M)⌉ uses the full INT8 range for any Σ
    # (line 11's quant(S) with an optimal per-row scale).
    p = jnp.exp(s - m_ref[0])
    pq = jnp.round(p * 127.0).astype(jnp.int8)
    part = jax.lax.dot_general(
        pq, vv_ref[0], (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32
    )
    # f32 accumulate across mega-tiles: per-tile int32 is exact
    # (≤127·127·bkv < 2³¹) and f32 carry avoids overflow at 500k+ contexts.
    acc_ref[...] += part.astype(jnp.float32)

    @pl.when(j == nkv - 1)
    def _fin():
        o_ref[0] = (
            acc_ref[...] * (v_scale / 127.0) / l_ref[0]
        ).astype(o_ref.dtype)


def _launch_name(role: str | None, stage: str) -> str | None:
    return None if role is None else f"two_stage_attention_{role}_{stage}"


def _kv_row_map(bh: int, kv_bh: int, q_heads: int | None, kv_heads: int | None):
    """Grid row of K/V for query row ``b``: GQA query heads share a K/V
    head, gathered by the index map instead of a broadcast copy."""
    if q_heads is not None and kv_heads is not None and q_heads != kv_heads:
        assert q_heads % kv_heads == 0, (q_heads, kv_heads)
        assert bh % q_heads == 0 and kv_bh == bh // q_heads * kv_heads
        g = q_heads // kv_heads
        return lambda b: (b // q_heads) * kv_heads + (b % q_heads) // g
    assert kv_bh == bh, (kv_bh, bh)
    return lambda b: b


def attention_stats(
    qv: jnp.ndarray,
    qs: jnp.ndarray,
    kv: jnp.ndarray,
    ks: jnp.ndarray,
    *,
    causal: bool = False,
    scale: float | None = None,
    bq: int = T_Q,
    bk: int = T_K,
    interpret: bool = False,
    q_heads: int | None = None,
    kv_heads: int | None = None,
    kv_len: int | None = None,
    role: str | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Stage ① alone: the row max ``M`` and row sum ``Σ`` ([BH, Lq, 1] f32)
    of the dequantized scores, over a (BH, Lq/bq, Lk/bk) grid.  Arguments
    as :func:`two_stage_attention`'s."""
    bh, lq, dh = qv.shape
    lk = kv.shape[1]
    scale = scale if scale is not None else 1.0 / (dh**0.5)
    bq = min(bq, lq)
    bk = min(bk, lk)
    assert lq % bq == 0 and lk % bk == 0, (lq, bq, lk, bk)
    nq, nk = lq // bq, lk // bk
    if kv_len is not None and kv_len >= lk:
        kv_len = None
    kv_row = _kv_row_map(bh, kv.shape[0], q_heads, kv_heads)
    return pl.pallas_call(
        functools.partial(
            _stage1_kernel, nk=nk, scale=scale, causal=causal, bq=bq, bk=bk,
            kv_len=kv_len,
        ),
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, dh), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, dh), lambda b, i, j: (kv_row(b), j, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, 1), lambda b, i, j: (kv_row(b), j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, lq, 1), jnp.float32),
            jax.ShapeDtypeStruct((bh, lq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        name=_launch_name(role, "stats"),
    )(qv, kv, qs, ks)


@functools.partial(
    jax.jit,
    static_argnames=(
        "causal", "scale", "bq", "bk", "bkv", "out_dtype", "interpret",
        "q_heads", "kv_heads", "kv_len", "role",
    ),
)
def two_stage_attention(
    qv: jnp.ndarray,
    qs: jnp.ndarray,
    kv: jnp.ndarray,
    ks: jnp.ndarray,
    vv: jnp.ndarray,
    v_scale: jnp.ndarray,
    *,
    causal: bool = False,
    scale: float | None = None,
    bq: int = T_Q,
    bk: int = T_K,
    bkv: int = T_V,
    out_dtype=jnp.float32,
    interpret: bool = False,
    q_heads: int | None = None,
    kv_heads: int | None = None,
    kv_len: int | None = None,
    role: str | None = None,
) -> jnp.ndarray:
    """Two-stage INT8 attention over [BH, L, dh] int8 tensors.

    qv/kv/vv: [BH, L, dh] int8; qs/ks: [BH, L, 1] f32 per-token scales;
    v_scale: [BH, 1, 1] f32 per-head scale.  Returns [BH, Lq, dh] float.

    **GQA**: when ``q_heads``/``kv_heads`` are given, kv/ks/vv carry only
    ``BHkv = B·kv_heads`` rows and the grid's K/V index maps gather the
    shared head for each query head — no broadcast copy of K/V to the full
    head count ever materializes (``v_scale`` stays per *query* head: it
    is [BH, 1, 1] scalars, not tensor traffic).

    **kv_len**: real key count when L was lane-padded; the kernel masks
    the tail columns out of both stages' softmax.

    **role**: a caller's static label (VGGT's ``frame`` / ``global``
    blocks).  The two launches are then named
    ``two_stage_attention_<role>_stats`` (stage ①) and
    ``two_stage_attention_<role>_out`` (stage ②) in compiled HLO and in
    device traces; without one both keep this function's name.
    """
    bh, lq, dh = qv.shape
    lk = kv.shape[1]
    scale = scale if scale is not None else 1.0 / (dh**0.5)
    bq = min(bq, lq)
    bkv = min(bkv, lk)
    assert lk % bkv == 0
    nq, nkv = lq // bq, lk // bkv
    if kv_len is not None and kv_len >= lk:
        kv_len = None  # nothing padded: skip the mask
    kv_row = _kv_row_map(bh, kv.shape[0], q_heads, kv_heads)

    m, l = attention_stats(
        qv, qs, kv, ks, causal=causal, scale=scale, bq=bq, bk=bk,
        interpret=interpret, q_heads=q_heads, kv_heads=kv_heads, kv_len=kv_len,
        role=role,
    )

    # Stage ②: recompute with mega-tiles, final stats as inputs
    out = pl.pallas_call(
        functools.partial(
            _stage2_kernel,
            nkv=nkv,
            scale=scale,
            v_scale=1.0,  # folded below via v_scale multiply; kept scalar here
            causal=causal,
            bq=bq,
            bkv=bkv,
            kv_len=kv_len,
        ),
        grid=(bh, nq, nkv),
        in_specs=[
            pl.BlockSpec((1, bq, dh), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bkv, dh), lambda b, i, j: (kv_row(b), j, 0)),
            pl.BlockSpec((1, bkv, dh), lambda b, i, j: (kv_row(b), j, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bkv, 1), lambda b, i, j: (kv_row(b), j, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, dh), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, lq, dh), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bq, dh), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        name=_launch_name(role, "out"),
    )(qv, kv, vv, qs, ks, m, l)
    return (out * v_scale).astype(out_dtype)


def vmem_bytes_two_stage(bq: int, bk: int, bkv: int, dh: int) -> dict:
    """Structural VMEM working-set model (used by benchmarks/fig13).

    Stage ①: q tile (int8) + k tile (int8) + 2 stat vectors.
    Stage ②: q + K mega + V mega (int8) + O acc (int32) + stats.
    FlashAttention comparison: q + k + v tiles + O acc (f32) + m/l carries,
    all at the *same* tile size, plus the running-rescale acc in f32.
    """
    s1 = bq * dh + bk * dh + 2 * bq * 4
    s2 = bq * dh + bkv * dh * 2 + bq * dh * 4 + 2 * bq * 4 + bq * 4
    flash = bq * dh + bkv * dh * 2 + bq * dh * 4 + 3 * bq * 4
    return {"stage1": s1, "stage2": s2, "flash_same_tiles": flash}
