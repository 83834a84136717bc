"""Pallas TPU kernels: unified-datapath fusion (paper §IV-B, Fig. 7).

The paper's accelerator claims a single reconfigurable systolic datapath
that executes the *linear* operator and its surrounding *nonlinear* work
(norm statistics, activation functions, rotations, re-quantization) in one
pass — no HBM round-trip between them.  Our unfused flow leaves Pallas
after every ``quant_matmul``, runs dequant → GELU/SiLU → WHT → requantize
in XLA fp32, and re-enters Pallas for the next projection.  These kernels
close that gap:

* :func:`norm_quant` — **prologue**: RMSNorm/LayerNorm statistics (in the
  rotated domain, ``FoldedNorm`` semantics) → optional blocked WHT →
  per-token A8/A4 quantization, one pass.  Emits the int8 values + scales
  the integer matmuls consume directly.

* :func:`fused_matmul` — the integer matmul with a **prologue**
  (norm → WHT → quantize, for fp inputs) and an **epilogue** family:
  dequant-scale → block IDCT → bias → GELU/SiLU → blocked WHT → optional
  re-quantization to INT8/INT4 (per-token scales), all inside the kernel's
  finalize step.

* :func:`fused_ffn` — the **gated-FFN variant**: one Pallas call runs the
  whole FFN layer — norm prologue, shared activation quantization, gate
  *and* up integer matmuls, ``silu(g)·u`` (or GELU), the hidden-side WHT,
  re-quantization, the down integer matmul, IDCT and biases.  One launch
  where the unfused path pays ≥3 matmul launches plus four fp32
  intermediate tensors in HBM.

Tiling: these kernels grid over the token (M) axis only and keep the full
K/N weight panels resident in VMEM — the right trade for serving-size
projections (d_model/d_ff up to a few thousand); the K-tiled
``quant_matmul`` remains the path for very large panels.  Callers pad M to
a lane-friendly multiple (``kernels.ops.lane_tile``) and slice the pad off.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.versaq import _act_fn as _act_rows
from repro.kernels.quant_matmul import unpack_int4_planes

__all__ = ["fused_matmul", "fused_ffn", "norm_quant"]

LANE = 128
UNPACK_ROWS = 256  # packed weight rows unpacked per step of _int_dot
# Scoped VMEM the fused kernels may claim: their weight panels stay
# resident, which outgrows the compiler's 16 MiB default at VGGT widths
# (a v5e core has 128 MiB).
VMEM_LIMIT = 64 * 1024 * 1024


# ---------------------------------------------------------------------------
# in-kernel building blocks (traced jnp on VMEM-resident tiles)
# ---------------------------------------------------------------------------


def _norm_rows(x, kind: str, u, eps: float):
    """FoldedNorm statistics on [r, d] f32 rows (γ/β live in the weights).

    ``rms``: orthonormal rotation preserves ‖x‖₂ so plain x/rms(x) is exact
    in the rotated domain.  ``ln``: mean recovered via ``u = Hᵀ1/d``
    (u: [1, d]), variance from E[x²] − μ² — both rotation-invariant.
    """
    if kind == "rms":
        ms = jnp.mean(x * x, axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(ms + eps)
    d = x.shape[-1]
    mu = jnp.sum(x * u, axis=-1, keepdims=True)
    sq = jnp.mean(x * x, axis=-1, keepdims=True)
    var = sq - mu * mu
    return (x - mu * u * d) * jax.lax.rsqrt(var + eps)


def _wht_rows(x, h, block: int):
    """Blocked WHT along the last axis of [r, d] (same scheme as
    kernels/wht.py: add/sub butterfly across sublane groups + one H_128
    MXU dot; a single small dot for blocks < 128)."""
    r, d = x.shape
    nblk = d // block
    if block >= LANE:
        g = block // LANE
        xv = x.reshape(r, nblk, g, LANE)
        step = 1
        while step < g:
            xv = xv.reshape(r, nblk, g // (2 * step), 2, step, LANE)
            a = xv[:, :, :, 0]
            b = xv[:, :, :, 1]
            xv = jnp.stack([a + b, a - b], axis=3)
            step *= 2
        xv = xv.reshape(r, nblk, g, LANE)
        xv = jnp.einsum("rngl,lm->rngm", xv, h)
        return (xv * (1.0 / math.sqrt(g))).reshape(r, d)
    xv = x.reshape(r, nblk, block)
    xv = jnp.einsum("rnb,bc->rnc", xv, h)
    return xv.reshape(r, d)


def _idct_rows(y, d):
    """Online block IDCT ŷ·D (cancels the offline ·Dᵀ weight transform).

    ``d`` is the block-diagonal IDCT over one lane slice (``I₂ ⊗ D₆₄`` on
    128 lanes, see :func:`_lane_dct`), applied slice by slice: folding the
    64-wide blocks into rows instead is a relayout Mosaic refuses."""
    w = d.shape[0]
    return jnp.concatenate(
        [jnp.dot(y[:, c : c + w], d) for c in range(0, y.shape[1], w)], axis=-1
    )


def _lane_dct(dct, dct_block: int, *widths: int):
    """The [blk, blk] DCT as a block-diagonal matrix over one 128-lane
    slice, when every width it applies to is lane-aligned; else as is."""
    if LANE % dct_block or any(w % LANE for w in widths):
        return dct.astype(jnp.float32)
    return jnp.kron(jnp.eye(LANE // dct_block, dtype=jnp.float32), dct.astype(jnp.float32))


def _quant_rows(x, bits: int):
    """Per-token symmetric quantization (kernel twin of
    ``core.quantize.quantize_per_token``)."""
    qmax = float(2 ** (bits - 1) - 1)
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / qmax
    q = jnp.clip(jnp.round(x / scale), -qmax, qmax)
    return q.astype(jnp.int8), scale


def _int_dot(xv, w_ref, packed: bool):
    """int8 [r, K] × (int8 [K, N] | packed uint8 [K/2, N] ref) -> int32 [r, N].

    Packed layout: original K rows [0, K/2) in low nibbles, [K/2, K) in
    high nibbles — the two nibble planes contract against contiguous
    column halves of the activation, no in-kernel deinterleave.  The
    panel is unpacked ``UNPACK_ROWS`` packed rows at a time, so the int32
    unpack temporaries stay a slice of the panel, not four times it.
    """
    dn = (((1,), (0,)), ((), ()))
    if not packed:
        return jax.lax.dot_general(xv, w_ref[...], dn, preferred_element_type=jnp.int32)
    kp = w_ref.shape[0]
    acc = None
    for c in range(0, kp, UNPACK_ROWS):
        c1 = min(c + UNPACK_ROWS, kp)
        wlo, whi = unpack_int4_planes(w_ref[c:c1, :])
        part = jax.lax.dot_general(
            xv[:, c:c1], wlo, dn, preferred_element_type=jnp.int32
        ) + jax.lax.dot_general(
            xv[:, kp + c : kp + c1], whi, dn, preferred_element_type=jnp.int32
        )
        acc = part if acc is None else acc + part
    return acc


# ---------------------------------------------------------------------------
# prologue kernel: norm -> WHT -> quantize
# ---------------------------------------------------------------------------


def _norm_quant_kernel(*refs, names, cfg):
    r = dict(zip(names, refs))
    x = r["x"][...].astype(jnp.float32)
    if cfg["norm_kind"] is not None:
        u = r["u"][...] if "u" in r else None
        x = _norm_rows(x, cfg["norm_kind"], u, cfg["norm_eps"])
    if cfg["wht_block"] is not None:
        x = _wht_rows(x, r["h_pro"][...], cfg["wht_block"])
    q, s = _quant_rows(x, cfg["a_bits"])
    r["out_q"][...] = q
    r["out_s"][...] = s


@functools.partial(
    jax.jit,
    static_argnames=("norm_kind", "norm_eps", "wht_block", "a_bits", "bm", "interpret"),
)
def norm_quant(
    x: jnp.ndarray,
    norm_u=None,
    h_pro=None,
    *,
    norm_kind: str | None = None,
    norm_eps: float = 1e-6,
    wht_block: int | None = None,
    a_bits: int = 8,
    bm: int = 256,
    interpret: bool = False,
):
    """Fused prologue over [M, D] f32: folded-norm stats → blocked WHT →
    per-token quantize.  Returns (values int8 [M, D], scales f32 [M, 1]).

    ``norm_u``: the LayerNorm mean-recovery vector [D] (``norm_kind="ln"``).
    ``h_pro``: normalized Hadamard [min(wht_block, 128)]² when ``wht_block``.
    """
    m, d = x.shape
    assert m % bm == 0, (m, bm)
    names = ["x"]
    operands = [x.astype(jnp.float32)]
    in_specs = [pl.BlockSpec((bm, d), lambda i: (i, 0))]
    if norm_kind == "ln":
        assert norm_u is not None
        names.append("u")
        operands.append(norm_u.reshape(1, d).astype(jnp.float32))
        in_specs.append(pl.BlockSpec((1, d), lambda i: (0, 0)))
    if wht_block is not None:
        assert h_pro is not None
        hs = h_pro.shape[0]
        names.append("h_pro")
        operands.append(h_pro.astype(jnp.float32))
        in_specs.append(pl.BlockSpec((hs, hs), lambda i: (0, 0)))
    names += ["out_q", "out_s"]
    cfg = dict(norm_kind=norm_kind, norm_eps=norm_eps, wht_block=wht_block, a_bits=a_bits)
    return pl.pallas_call(
        functools.partial(_norm_quant_kernel, names=tuple(names), cfg=cfg),
        grid=(m // bm,),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((bm, d), lambda i: (i, 0)),
            pl.BlockSpec((bm, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, d), jnp.int8),
            jax.ShapeDtypeStruct((m, 1), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",), vmem_limit_bytes=VMEM_LIMIT
        ),
    )(*operands)


# ---------------------------------------------------------------------------
# fused matmul: prologue + integer matmul + epilogue family
# ---------------------------------------------------------------------------


def _fused_matmul_kernel(*refs, names, cfg):
    r = dict(zip(names, refs))
    if cfg["prequant"]:
        xv = r["x"][...]
        xs = r["xs"][...]
    else:
        x = r["x"][...].astype(jnp.float32)
        if cfg["norm_kind"] is not None:
            u = r["u"][...] if "u" in r else None
            x = _norm_rows(x, cfg["norm_kind"], u, cfg["norm_eps"])
        if cfg["pro_wht_block"] is not None:
            x = _wht_rows(x, r["h_pro"][...], cfg["pro_wht_block"])
        xv, xs = _quant_rows(x, cfg["a_bits"])
    acc = _int_dot(xv, r["wv"], cfg["packed"])
    y = acc.astype(jnp.float32) * xs * r["ws"][...]
    if cfg["dct_block"] is not None:
        y = _idct_rows(y, r["dct"][...])
    if "bias" in r:
        y = y + r["bias"][...]
    y = _act_rows(y, cfg["act"])
    if cfg["epi_wht_block"] is not None:
        y = _wht_rows(y, r["h_epi"][...], cfg["epi_wht_block"])
    if cfg["requant_bits"] is not None:
        q, s = _quant_rows(y, cfg["requant_bits"])
        r["out_q"][...] = q
        r["out_s"][...] = s
    else:
        r["out"][...] = y.astype(r["out"].dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "packed", "a_bits", "norm_kind", "norm_eps", "pro_wht_block", "act",
        "epi_wht_block", "requant_bits", "dct_block", "out_dtype", "bm",
        "interpret",
    ),
)
def fused_matmul(
    x: jnp.ndarray,
    wv: jnp.ndarray,
    ws: jnp.ndarray,
    xs=None,
    bias=None,
    norm_u=None,
    h_pro=None,
    h_epi=None,
    dct=None,
    *,
    packed: bool,
    a_bits: int = 8,
    norm_kind: str | None = None,
    norm_eps: float = 1e-6,
    pro_wht_block: int | None = None,
    act: str = "none",
    epi_wht_block: int | None = None,
    requant_bits: int | None = None,
    dct_block: int | None = None,
    out_dtype=jnp.float32,
    bm: int = 128,
    interpret: bool = False,
):
    """One Pallas call: [prologue →] integer matmul → epilogue.

    ``x``: f32 [M, K] (in-kernel prologue: norm → WHT → quantize) or int8
    [M, K] with ``xs`` [M, 1] per-token scales (pre-quantized — e.g. the
    output of :func:`norm_quant` shared across several projections).
    ``wv``/``ws``: int8 [K, N] (or packed uint8 [K/2, N]) + [1, N] scales.

    Epilogue order matches the unfused flow exactly: dequant-scale →
    block IDCT (``dct`` = [blk, blk] DCT matrix) → bias → act →
    blocked WHT → per-token requantization.  Returns f32/``out_dtype``
    [M, N], or ``(values int8 [M, N], scales f32 [M, 1])`` when
    ``requant_bits`` is set.
    """
    m, kdim = x.shape
    n = wv.shape[-1]
    assert m % bm == 0, (m, bm)
    prequant = xs is not None
    names, operands, in_specs = ["x"], [], []
    if prequant:
        assert x.dtype == jnp.int8, x.dtype
        operands.append(x)
    else:
        operands.append(x.astype(jnp.float32))
    in_specs.append(pl.BlockSpec((bm, kdim), lambda i: (i, 0)))
    if prequant:
        names.append("xs")
        operands.append(xs.astype(jnp.float32))
        in_specs.append(pl.BlockSpec((bm, 1), lambda i: (i, 0)))
    else:
        if norm_kind == "ln":
            assert norm_u is not None
            names.append("u")
            operands.append(norm_u.reshape(1, kdim).astype(jnp.float32))
            in_specs.append(pl.BlockSpec((1, kdim), lambda i: (0, 0)))
        if pro_wht_block is not None:
            assert h_pro is not None
            names.append("h_pro")
            operands.append(h_pro.astype(jnp.float32))
            in_specs.append(pl.BlockSpec(h_pro.shape, lambda i: (0, 0)))
    names += ["wv", "ws"]
    operands += [wv, ws.reshape(1, n).astype(jnp.float32)]
    in_specs += [
        pl.BlockSpec(wv.shape, lambda i: (0, 0)),
        pl.BlockSpec((1, n), lambda i: (0, 0)),
    ]
    if dct_block is not None:
        assert dct is not None
        dct = _lane_dct(dct, dct_block, n)
        names.append("dct")
        operands.append(dct)
        in_specs.append(pl.BlockSpec(dct.shape, lambda i: (0, 0)))
    if bias is not None:
        names.append("bias")
        operands.append(bias.reshape(1, n).astype(jnp.float32))
        in_specs.append(pl.BlockSpec((1, n), lambda i: (0, 0)))
    if epi_wht_block is not None:
        assert h_epi is not None
        names.append("h_epi")
        operands.append(h_epi.astype(jnp.float32))
        in_specs.append(pl.BlockSpec(h_epi.shape, lambda i: (0, 0)))
    if requant_bits is not None:
        out_names = ["out_q", "out_s"]
        out_specs = [
            pl.BlockSpec((bm, n), lambda i: (i, 0)),
            pl.BlockSpec((bm, 1), lambda i: (i, 0)),
        ]
        out_shape = [
            jax.ShapeDtypeStruct((m, n), jnp.int8),
            jax.ShapeDtypeStruct((m, 1), jnp.float32),
        ]
    else:
        out_names = ["out"]
        out_specs = pl.BlockSpec((bm, n), lambda i: (i, 0))
        out_shape = jax.ShapeDtypeStruct((m, n), out_dtype)
    cfg = dict(
        prequant=prequant, packed=packed, a_bits=a_bits, norm_kind=norm_kind,
        norm_eps=norm_eps, pro_wht_block=pro_wht_block, act=act,
        epi_wht_block=epi_wht_block, requant_bits=requant_bits,
        dct_block=dct_block,
    )
    return pl.pallas_call(
        functools.partial(
            _fused_matmul_kernel, names=tuple(names + out_names), cfg=cfg
        ),
        grid=(m // bm,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",), vmem_limit_bytes=VMEM_LIMIT
        ),
    )(*operands)


# ---------------------------------------------------------------------------
# fused gated FFN: the whole layer in one launch
# ---------------------------------------------------------------------------


def _fused_ffn_kernel(*refs, names, cfg):
    r = dict(zip(names, refs))
    x = r["x"][...].astype(jnp.float32)
    if cfg["norm_kind"] is not None:
        u = r["u"][...] if "u" in r else None
        x = _norm_rows(x, cfg["norm_kind"], u, cfg["norm_eps"])
    if cfg["pro_wht_block"] is not None:  # unrotated-stream flows
        x = _wht_rows(x, r["h_pro"][...], cfg["pro_wht_block"])
    xv, xs = _quant_rows(x, cfg["a_bits_in"])

    def proj(wn, sn, bn, packed, idct):
        y = _int_dot(xv, r[wn], packed).astype(jnp.float32) * xs * r[sn][...]
        if idct:
            y = _idct_rows(y, r["dct"][...])
        if bn in r:
            y = y + r[bn][...]
        return y

    up = proj("wu", "wus", "bu", cfg["packed_u"], cfg["idct_h"])
    if cfg["gated"]:
        gate = proj("wg", "wgs", "bg", cfg["packed_g"], cfg["idct_h"])
        h = _act_rows(gate, cfg["act"]) * up
    else:
        h = _act_rows(up, cfg["act"])
    if cfg["mid_wht_block"] is not None:
        h = _wht_rows(h, r["h_mid"][...], cfg["mid_wht_block"])
    hq, hs = _quant_rows(h, cfg["a_bits_mid"])
    y = _int_dot(hq, r["wd"], cfg["packed_d"]).astype(jnp.float32)
    y = y * hs * r["wds"][...]
    if cfg["idct_out"]:
        y = _idct_rows(y, r["dct"][...])
    if "bd" in r:
        y = y + r["bd"][...]
    r["out"][...] = y.astype(r["out"].dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "packed_g", "packed_u", "packed_d", "a_bits_in", "a_bits_mid",
        "norm_kind", "norm_eps", "act", "pro_wht_block", "mid_wht_block",
        "idct_h", "idct_out", "dct_block", "out_dtype", "bm", "interpret",
    ),
)
def fused_ffn(
    x: jnp.ndarray,
    wu: jnp.ndarray,
    wus: jnp.ndarray,
    wd: jnp.ndarray,
    wds: jnp.ndarray,
    wg=None,
    wgs=None,
    bg=None,
    bu=None,
    bd=None,
    norm_u=None,
    h_pro=None,
    h_mid=None,
    dct=None,
    *,
    packed_g: bool = False,
    packed_u: bool = False,
    packed_d: bool = False,
    a_bits_in: int = 8,
    a_bits_mid: int = 8,
    norm_kind: str | None = None,
    norm_eps: float = 1e-6,
    act: str = "gelu",
    pro_wht_block: int | None = None,
    mid_wht_block: int | None = None,
    idct_h: bool = False,
    idct_out: bool = False,
    dct_block: int | None = None,
    out_dtype=jnp.float32,
    bm: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """The whole (optionally gated) FFN layer in ONE Pallas call.

    x f32 [M, D] → norm prologue → input blocked WHT (``pro_wht_block``,
    for unrotated-stream flows whose gate/up sites carry the online WHT)
    → per-token A-quant (shared by gate/up) → gate/up integer matmuls
    (+IDCT +bias) → ``act(g)·u`` (or ``act(u)``) → hidden blocked WHT →
    re-quantize at ``a_bits_mid`` → down integer matmul (+IDCT +bias) →
    f32 [M, d_out].

    The unfused path pays ≥3 Pallas launches and materializes four fp32
    [M, d_ff] intermediates in HBM; here everything between the two ends
    of the layer lives in VMEM.
    """
    m, d = x.shape
    dff = wu.shape[-1]
    n_out = wd.shape[-1]
    assert m % bm == 0, (m, bm)
    gated = wg is not None
    names = ["x"]
    operands = [x.astype(jnp.float32)]
    in_specs = [pl.BlockSpec((bm, d), lambda i: (i, 0))]

    def const(name, arr, shape=None):
        names.append(name)
        operands.append(arr)
        in_specs.append(pl.BlockSpec(shape or arr.shape, lambda i: (0, 0)))

    if norm_kind == "ln":
        assert norm_u is not None
        const("u", norm_u.reshape(1, d).astype(jnp.float32))
    if pro_wht_block is not None:
        assert h_pro is not None
        const("h_pro", h_pro.astype(jnp.float32))
    if gated:
        const("wg", wg)
        const("wgs", wgs.reshape(1, dff).astype(jnp.float32))
        if bg is not None:
            const("bg", bg.reshape(1, dff).astype(jnp.float32))
    const("wu", wu)
    const("wus", wus.reshape(1, dff).astype(jnp.float32))
    if bu is not None:
        const("bu", bu.reshape(1, dff).astype(jnp.float32))
    if mid_wht_block is not None:
        assert h_mid is not None
        const("h_mid", h_mid.astype(jnp.float32))
    const("wd", wd)
    const("wds", wds.reshape(1, n_out).astype(jnp.float32))
    if bd is not None:
        const("bd", bd.reshape(1, n_out).astype(jnp.float32))
    if idct_h or idct_out:
        assert dct is not None and dct_block is not None
        const("dct", _lane_dct(dct, dct_block, dff, n_out))
    cfg = dict(
        gated=gated, packed_g=packed_g, packed_u=packed_u, packed_d=packed_d,
        a_bits_in=a_bits_in, a_bits_mid=a_bits_mid, norm_kind=norm_kind,
        norm_eps=norm_eps, act=act, pro_wht_block=pro_wht_block,
        mid_wht_block=mid_wht_block, idct_h=idct_h, idct_out=idct_out,
        dct_block=dct_block,
    )
    return pl.pallas_call(
        functools.partial(_fused_ffn_kernel, names=tuple(names + ["out"]), cfg=cfg),
        grid=(m // bm,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, n_out), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, n_out), out_dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",), vmem_limit_bytes=VMEM_LIMIT
        ),
    )(*operands)
