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
reach past ``kv_len``; stage ② masks none, because the caller pads V with
zero rows.  The per-token scalars (q and k scales, ``M``, ``Σ``) cross
the launch boundary as lane-dense rows, and each stage turns the query
side into VMEM columns once per query tile.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LN_127 = math.log(127.0)

# Tile targets (``kernels.ops.attention_tiles`` resolves them per length),
# from a sweep of both stages at VGGT-1B's frame and global lengths on a
# TPU v5e (benchmarks/attn_tile_sweep.py): every larger tile was faster,
# up to 512 query rows and whole key rows of 2,816.
T_Q = 512
T_K = 4096
T_V = 4096


def _scores(qv_ref, kv_ref, qcol_ref, ks_ref):
    """Dequantized scores of one tile (Alg. 1 line 4): the exact int32
    ``QKᵀ``, then the q column (softmax scale folded in) and the key row."""
    s = jax.lax.dot_general(
        qv_ref[0],
        kv_ref[0],
        (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    return s.astype(jnp.float32) * qcol_ref[...] * ks_ref[0]


def _stage1_kernel(
    qv_ref, kv_ref, qs_ref, ks_ref, m_ref, l_ref, qcol, m_acc, l_acc, *, nk, scale,
    causal, bq, bk, kv_len
):
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        qcol[...] = (qs_ref[0] * scale).T
        m_acc[...] = jnp.full_like(m_acc, NEG_INF)
        l_acc[...] = jnp.zeros_like(l_acc)

    def accumulate(kv_len):
        s = _scores(qv_ref, kv_ref, qcol, ks_ref)
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
        m_ref[0] = m_acc[...].T
        l_ref[0] = jnp.maximum(l_acc[...], 1e-30).T


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
    qcol,
    mcol,
    *,
    nkv,
    scale,
    causal,
    bq,
    bkv,
):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        qcol[...] = (qs_ref[0] * scale).T
        # Eq. 10 with line 11's int8 scale folded into the row max:
        # exp(s − (M − ln 127)) = 127·exp(s − M), whose row max is 127, so
        # the rounded probabilities use the full INT8 range for any Σ
        # (quant(S) with an optimal per-row scale); 1/Σ goes to the output.
        mcol[...] = (m_ref[0] - LN_127).T

    # recompute scores against the mega-tile (lines 9-10); padded keys
    # need no mask here: their V rows are zero (see ``kv_len``)
    s = _scores(qv_ref, kv_ref, qcol, ks_ref)
    if causal:
        i = pl.program_id(1)
        cols = j * bkv + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        rows = i * bq + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        s = jnp.where(rows >= cols, s, NEG_INF)
    pq = jnp.round(jnp.exp(s - mcol[...])).astype(jnp.int8)
    part = jax.lax.dot_general(
        pq, vv_ref[0], (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32
    )
    # f32 accumulate across mega-tiles: per-tile int32 is exact
    # (≤127·127·bkv < 2³¹) and f32 carry avoids overflow at 500k+ contexts.
    acc_ref[...] += part.astype(jnp.float32)

    @pl.when(j == nkv - 1)
    def _fin():
        o_ref[0] = (acc_ref[...] * (1.0 / 127.0) / l_ref[0].T).astype(o_ref.dtype)


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


def _q_rows(x: jnp.ndarray, bq: int) -> jnp.ndarray:
    """[BH, Lq, 1] per-query scalars -> [BH·nq, 1, bq]: one lane-dense row
    per query tile (row-major, so a reshape; no 128-lane padded column)."""
    bh, lq, _ = x.shape
    return x.reshape(bh * (lq // bq), 1, bq)


def _k_rows(x: jnp.ndarray) -> jnp.ndarray:
    """[BHkv, Lk, 1] per-key scales -> [BHkv, 1, Lk], tiled along the lanes."""
    return x.reshape(x.shape[0], 1, x.shape[1])


def _stats_rows(
    qv, qrow, kv, krow, *, causal, scale, bq, bk, interpret, kv_row, kv_len, role
):
    """Stage ① launch over the kernel's row layout (``_q_rows``/``_k_rows``);
    returns ``M`` and ``Σ`` as [BH·nq, 1, bq] rows."""
    bh, lq, dh = qv.shape
    lk = kv.shape[1]
    nq, nk = lq // bq, lk // bk
    return pl.pallas_call(
        functools.partial(
            _stage1_kernel, nk=nk, scale=scale, causal=causal, bq=bq, bk=bk,
            kv_len=kv_len,
        ),
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, dh), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, dh), lambda b, i, j: (kv_row(b), j, 0)),
            pl.BlockSpec((1, 1, bq), lambda b, i, j: (b * nq + i, 0, 0)),
            pl.BlockSpec((1, 1, bk), lambda b, i, j: (kv_row(b), 0, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq), lambda b, i, j: (b * nq + i, 0, 0)),
            pl.BlockSpec((1, 1, bq), lambda b, i, j: (b * nq + i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh * nq, 1, bq), jnp.float32),
            jax.ShapeDtypeStruct((bh * nq, 1, bq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        name=_launch_name(role, "stats"),
    )(qv, kv, qrow, krow)


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
    if kv_len is not None and kv_len >= lk:
        kv_len = None
    m, l = _stats_rows(
        qv, _q_rows(qs, bq), kv, _k_rows(ks), causal=causal, scale=scale, bq=bq,
        bk=bk, interpret=interpret, kv_row=_kv_row_map(bh, kv.shape[0], q_heads, kv_heads),
        kv_len=kv_len, role=role,
    )
    return m.reshape(bh, lq, 1), l.reshape(bh, lq, 1)


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
    Inside, the per-token scalars (qs, ks, M, Σ) travel as lane-dense rows
    (``_q_rows``/``_k_rows``): a [·, L, 1] column would fill a 128-lane
    vreg row, and its DMA move 128× its bytes, for every scalar.  So on
    the chip ``bk`` and ``bkv`` are multiples of 128 or the whole key
    axis, as ``ops.attention_tiles`` gives them: the key-scale row is
    blocked along the lanes.

    **GQA**: when ``q_heads``/``kv_heads`` are given, kv/ks/vv carry only
    ``BHkv = B·kv_heads`` rows and the grid's K/V index maps gather the
    shared head for each query head — no broadcast copy of K/V to the full
    head count ever materializes (``v_scale`` stays per *query* head: it
    is [BH, 1, 1] scalars, not tensor traffic).

    **kv_len**: real key count when L was lane-padded.  Stage ① masks the
    tail columns out of ``M`` and ``Σ``; stage ② does not mask them, so
    the caller must pad **V with zero rows** (as ``ops.two_stage_mha``
    does): a padded key's rounded probability then multiplies an exact
    int8 zero and adds nothing to P·V.

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
    bk = min(bk, lk)
    bkv = min(bkv, lk)
    assert lq % bq == 0 and lk % bk == 0 and lk % bkv == 0, (lq, bq, lk, bk, bkv)
    nq, nkv = lq // bq, lk // bkv
    if kv_len is not None and kv_len >= lk:
        kv_len = None  # nothing padded: skip the mask
    kv_row = _kv_row_map(bh, kv.shape[0], q_heads, kv_heads)
    qrow, krow = _q_rows(qs, bq), _k_rows(ks)

    m, l = _stats_rows(
        qv, qrow, kv, krow, causal=causal, scale=scale, bq=bq, bk=bk,
        interpret=interpret, kv_row=kv_row, kv_len=kv_len, role=role,
    )

    # Stage ②: recompute with mega-tiles, final stats as inputs
    q_spec = pl.BlockSpec((1, 1, bq), lambda b, i, j: (b * nq + i, 0, 0))
    out = pl.pallas_call(
        functools.partial(
            _stage2_kernel, nkv=nkv, scale=scale, causal=causal, bq=bq, bkv=bkv,
        ),
        grid=(bh, nq, nkv),
        in_specs=[
            pl.BlockSpec((1, bq, dh), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bkv, dh), lambda b, i, j: (kv_row(b), j, 0)),
            pl.BlockSpec((1, bkv, dh), lambda b, i, j: (kv_row(b), j, 0)),
            q_spec,
            pl.BlockSpec((1, 1, bkv), lambda b, i, j: (kv_row(b), 0, j)),
            q_spec,
            q_spec,
        ],
        out_specs=pl.BlockSpec((1, bq, dh), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, lq, dh), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((bq, dh), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        name=_launch_name(role, "out"),
    )(qv, kv, vv, qrow, krow, m, l)
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
