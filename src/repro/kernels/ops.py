"""jit'd public wrappers around the Pallas kernels.

These are the entry points the rest of the framework uses.  On CPU (this
container) they run in interpret mode for validation; on TPU they compile
to Mosaic.  ``interpret`` defaults from the backend.

Tiling policy: serving token counts (S·(n_special+P), prompt buckets,
odd scene sizes) are rarely multiples of a kernel's tiles.  The matmuls
(``lane_tile``) use an exact divisor tile when an 8-aligned one exists;
otherwise the length is padded to the next multiple of 8 (masked or sliced
off) instead of degrading to tile=1 kernels — a prime-sized dim used to
lower a degenerate one-row-per-step grid.  Attention
(``attention_tiles``) pads its token axes to multiples of 128, the lanes a
score tile's key axis lies on.

Every wrapper records its kernel launches with ``kernels.probe`` so tests
and benchmarks can assert Pallas-call counts (the fused datapath's whole
point is fewer launches).
"""
from __future__ import annotations

import warnings

import jax
import jax.numpy as jnp

from repro.core import transforms
from repro.core.quantize import QTensor, quantize_per_token
from repro.kernels import fused as _fused
from repro.kernels import probe
from repro.kernels import qk_rope as _qk
from repro.kernels import quant_matmul as _qm
from repro.kernels import two_stage_attention as _tsa
from repro.kernels import wht as _wht
from repro.obs import trace as obs_trace

__all__ = [
    "quant_linear_matmul",
    "two_stage_mha",
    "qk_norm_rope",
    "online_wht_2d",
    "fused_linear",
    "fused_ffn_apply",
    "norm_quant_prologue",
    "divisor_tile",
    "lane_tile",
    "matmul_tiles",
    "attention_tiles",
    "matmul_tile_seed",
    "attention_tile_seed",
    "matmul_traffic_bytes",
]

LANE = 8  # sublane granularity the TPU lowerings want tiles aligned to
ATTN_LANE = 128  # a score tile puts its key axis on the 128-lane vreg
ATTN_PAD_FRAC = 0.03  # most padding that buys an attention axis a larger tile


def _default_interpret() -> bool:
    """Interpret mode on the CPU backend (tests), Mosaic on TPU.  Any other
    backend is an error: interpreting there would hide the missing chip."""
    backend = jax.default_backend()
    if backend not in ("cpu", "tpu"):
        raise RuntimeError(
            f"Pallas kernels lower for TPU only (interpret mode on CPU); "
            f"backend {backend!r} has neither"
        )
    return backend == "cpu"


def quant_linear_matmul(
    x: jnp.ndarray,
    wq: QTensor,
    a_bits: int = 8,
    out_dtype=jnp.float32,
    interpret: bool | None = None,
    bm: int | None = None,
    bn: int | None = None,
    bk: int | None = None,
    bm_target: int | None = None,
) -> jnp.ndarray:
    """Quantize activations per-token and run the integer matmul kernel.

    x: [..., K] float -> returns [..., N] ``out_dtype``.  The token dim is
    lane-padded (zero rows, sliced off) when no healthy divisor tile
    exists; K/N are weight dims and use exact divisors.  ``bm`` is an exact
    legacy tile (M padded up to a multiple); ``bm_target`` — what compiled
    ``KernelSchedule`` entries carry — resolves through :func:`lane_tile`
    at trace time, since the token count is not known at compile time.
    """
    interpret = _default_interpret() if interpret is None else interpret
    lead = x.shape[:-1]
    k = x.shape[-1]
    n = wq.shape[-1]
    xq = quantize_per_token(x.reshape(-1, k), a_bits)
    m = xq.values.shape[0]
    bm, mp, bn, bk = matmul_tiles(
        m, k, n, packed=wq.packed, bm=bm, bm_target=bm_target, bn=bn, bk=bk
    )
    xv, xs = xq.values, xq.scale.astype(jnp.float32)
    if mp != m:  # zero rows contribute zero outputs; sliced off below
        xv = jnp.pad(xv, ((0, mp - m), (0, 0)))
        xs = jnp.pad(xs, ((0, mp - m), (0, 0)), constant_values=1.0)
    ws = wq.scale.reshape(1, -1).astype(jnp.float32)
    probe.record(
        "quant_matmul",
        nbytes=matmul_traffic_bytes(mp, k, n, bm=bm, bn=bn, bk=bk, packed=wq.packed),
    )
    y = _qm.quant_matmul(
        xv,
        xs,
        wq.values,
        ws,
        packed=wq.packed,
        out_dtype=out_dtype,
        bm=bm,
        bn=bn,
        bk=bk,
        interpret=interpret,
    )
    return y[:m].reshape(lead + (y.shape[-1],))


def divisor_tile(length: int, target: int) -> int:
    """Largest tile size ≤ ``target`` that divides ``length``.

    The model path serves token counts like S·(n_special + P) that are not
    multiples of the paper's 64/2048 tiles; the kernel requires exact
    divisibility, so serving picks the best-fitting divisor per bucket.
    Prime-ish lengths degrade to tiny tiles — use :func:`lane_tile` on any
    axis that can be padded instead.
    """
    t = min(target, length)
    while length % t:
        t -= 1
    return t


def _aligned_divisor(n: int, target: int, lane: int) -> int:
    """Largest multiple of ``lane`` ≤ target that divides ``n`` (requires
    ``lane | n``)."""
    t = min(target, n)
    t -= t % lane
    while t > lane and n % t:
        t -= lane
    return t


def lane_tile(
    length: int, target: int, lane: int = LANE, warn_frac: float = 0.125
) -> tuple[int, int]:
    """(tile, padded_length): a lane-friendly tile for a paddable axis.

    If a lane-aligned divisor of ``length`` exists the axis stays exact.
    Otherwise the axis is padded to the next lane multiple and tiled with
    a lane-aligned divisor of the padded length — a prime-sized dim gets
    an 8-aligned tile and ≤ 7 pad rows instead of a degenerate tile=1
    kernel.  Warns when the padding overhead exceeds ``warn_frac``.
    """
    if length <= lane:
        return length, length  # tiny axis: one exact block
    padded = -(-length // lane) * lane
    if padded != length and (padded - length) > warn_frac * length:
        warnings.warn(
            f"lane_tile: padding dim {length} -> {padded} "
            f"(+{100.0 * (padded - length) / length:.1f}% > "
            f"{100.0 * warn_frac:.1f}%); consider bucketing this shape",
            stacklevel=2,
        )
    return _aligned_divisor(padded, target, lane), padded


# ---------------------------------------------------------------------------
# tiling policy — the single pad-vs-divide decision point
# ---------------------------------------------------------------------------
#
# Both kernel families used to hand-roll the same choice (exact divisor on
# weight-shaped axes, lane-padding on token-shaped axes) inline.  The two
# resolvers below are now the only place that choice is made; the autotuner
# (core/precision/tuner.py) reuses them as its candidate generator by
# sweeping the *targets* and letting the resolver legalize each candidate.


def matmul_tiles(
    m: int,
    k: int,
    n: int,
    *,
    packed: bool = False,
    bm: int | None = None,
    bm_target: int | None = None,
    bn: int | None = None,
    bk: int | None = None,
    bn_target: int | None = None,
    bk_target: int | None = None,
) -> tuple[int, int, int, int]:
    """Resolve quant-matmul tiles -> ``(bm, m_padded, bn, bk)``.

    ``bm`` is an exact tile (legacy callers; M padded to a multiple of it).
    ``bm_target`` resolves through :func:`lane_tile` exactly like the
    default policy — schedule entries carry targets because M (the token
    count) is runtime-dependent.  ``bn``/``bk`` must divide exactly when
    given; defaults pick the largest divisor under the paper's targets,
    with the packed-int4 layout requiring an even K tile.
    """
    if bm is not None:
        bm = min(bm, m)
        mp = -(-m // bm) * bm
    else:
        bm, mp = lane_tile(m, bm_target or _qm.DEFAULT_BM)
    bn = bn if bn is not None else divisor_tile(n, bn_target or _qm.DEFAULT_BN)
    if bk is None:
        bk = divisor_tile(k, bk_target or _qm.DEFAULT_BK)
    if packed and bk % 2:
        bk = k  # packed layout needs an even K tile; K itself is even
    return bm, mp, bn, bk


def _attention_axis(length: int, target: int) -> int:
    """Padded length of an attention token axis tiled toward ``target``.

    A multiple of 128 (``ATTN_LANE``).  Where that length's best
    128-aligned tile is under half the target (11,008 = 128·2·43 allows
    only 256), a multiple of 512 or 1,024 instead, if it costs ≤ 3% of the
    length.  An axis of at most 128 tokens is one tile, padded to 8.
    """
    if length <= ATTN_LANE:
        return -(-length // LANE) * LANE
    target = max(target, ATTN_LANE)
    padded = -(-length // ATTN_LANE) * ATTN_LANE
    best = _aligned_divisor(padded, target, ATTN_LANE)
    for step in (512, 1024):
        p = -(-length // step) * step
        if best < target // 2 and p - length <= ATTN_PAD_FRAC * length:
            t = _aligned_divisor(p, target, ATTN_LANE)
            if t > best:
                padded, best = p, t
    return padded


def _attention_tile(padded: int, target: int, lane: int = ATTN_LANE) -> int:
    """Largest ``lane``-aligned divisor of ``padded`` ≤ ``target``; a short
    axis is one tile."""
    if padded <= ATTN_LANE:
        return padded
    return _aligned_divisor(padded, max(target, ATTN_LANE), lane)


def attention_tiles(
    lq: int,
    lk: int,
    *,
    bq: int | None = None,
    bk: int | None = None,
    bkv: int | None = None,
    bq_target: int | None = None,
    bk_target: int | None = None,
    bkv_target: int | None = None,
) -> tuple[dict, int, int]:
    """Resolve two-stage attention tiles -> ``({bq, bk, bkv}, lqp, lkp)``.

    Explicit ``bq``/``bk``/``bkv`` must divide exactly (legacy behavior,
    no padding).  ``*_target`` values — the form schedules carry — and the
    T_Q/T_K/T_V defaults pad each token axis once, by the key tile's rule
    (:func:`_attention_axis`, so a self-attention pads both axes alike),
    then take 128-aligned ``bk`` and ``bkv`` (both divide ``lkp``) and an
    8-aligned ``bq`` of the padded lengths.
    """
    tiles: dict[str, int] = {}
    bk_target = bk_target or _tsa.T_K
    if bq is not None:
        tiles["bq"], lqp = bq, lq
    else:
        lqp = _attention_axis(lq, bk_target)
        tiles["bq"] = _attention_tile(lqp, bq_target or _tsa.T_Q, LANE)
    if bk is not None or bkv is not None:
        lkp = lk
        tiles["bk"] = bk if bk is not None else divisor_tile(lk, _tsa.T_K)
        tiles["bkv"] = bkv if bkv is not None else divisor_tile(lk, _tsa.T_V)
    else:
        lkp = _attention_axis(lk, bk_target)
        tiles["bk"] = _attention_tile(lkp, bk_target)
        tiles["bkv"] = _attention_tile(lkp, bkv_target or _tsa.T_V)
    return tiles, lqp, lkp


def matmul_tile_seed(k: int, n: int, *, packed: bool = False, fused: bool = False) -> dict:
    """The heuristic-policy tiles for a weight site, as a schedule entry.

    This is what ``compile_schedule`` records when no tuner is supplied,
    and the seed candidate the autotuner starts from.  ``bn``/``bk`` are
    exact (weight dims are static); ``bm`` stays a target.
    """
    if fused:
        return {"bm_target": FUSED_BM}
    _, _, bn, bk = matmul_tiles(_qm.DEFAULT_BM, k, n, packed=packed)
    return {"bm_target": _qm.DEFAULT_BM, "bn": bn, "bk": bk}


def attention_tile_seed() -> dict:
    """Default two-stage attention tile targets (T_Q/T_K/T_V)."""
    return {"bq_target": _tsa.T_Q, "bk_target": _tsa.T_K, "bkv_target": _tsa.T_V}


def matmul_traffic_bytes(
    mp: int, k: int, n: int, *, bm: int, bn: int, bk: int, packed: bool
) -> int:
    """Modeled HBM bytes moved by one tiled integer-matmul launch.

    Grid is (M/bm, N/bn, K/bk): activations re-stream once per N tile,
    weight panels once per M tile, f32 accumulator written once.  This is
    the CPU-side cost signal the autotuner ranks candidates by when no
    real hardware exists to wall-clock.
    """
    kb = -(-k // 2) if packed else k  # weight K storage bytes per column
    x_bytes = mp * k * (n // bn)
    w_bytes = kb * n * (mp // bm)
    out_bytes = mp * n * 4
    scale_bytes = 4 * (mp * (n // bn) + n * (mp // bm))
    return x_bytes + w_bytes + out_bytes + scale_bytes


def _scale_row_bytes(bh: int, nq: int, nk: int, bq: int, bk: int) -> int:
    """Bytes of the f32 scale rows one two-stage launch DMAs over its
    (bh, nq, nk) grid: three query-side rows of ``bq`` per query tile
    (q scale in; ``M``, ``Σ`` out of stage ①, in to stage ②) and the key
    scale row of ``bk``, fetched every step while the key axis moves and
    once a head when it is one tile (a block is re-read only when its
    index changes)."""
    key_fetches = bh * nq * nk if nk > 1 else bh
    return 4 * (3 * bh * nq * bq + key_fetches * bk)


def _attention_traffic_bytes(bh: int, lqp: int, lkp: int, dh: int, tiles: dict) -> int:
    """Modeled bytes for the two-stage attention pair of launches."""
    bq, bk, bkv = tiles["bq"], tiles["bk"], tiles["bkv"]
    nq = lqp // bq
    # stage ① (stats): Q re-streams per K tile, K per Q tile
    s1 = bh * (lqp * dh * (lkp // bk) + lkp * dh * nq)
    s1 += _scale_row_bytes(bh, nq, lkp // bk, bq, bk)
    # stage ② (PV): Q/V re-stream against the coarser T_V tiling
    s2 = bh * (lqp * dh * (lkp // bkv) + lkp * dh * nq + lqp * dh * 4)
    s2 += _scale_row_bytes(bh, nq, lkp // bkv, bq, bkv)
    return s1 + s2


def two_stage_mha(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = False,
    a_bits: int = 8,
    interpret: bool | None = None,
    role: str | None = None,
    **tile_kw,
) -> jnp.ndarray:
    """Paper-Alg.-1 attention over float [B, H, L, dh] inputs.

    Quantizes Q/K per-token and V per-head to int8, then runs the
    two-stage kernel.  K/V may carry fewer (GQA-shared) heads than Q
    ([B, Hkv, Lk, dh]); shared heads are indexed inside the kernel grid —
    they are never broadcast-copied to the full head count.  Returns
    [B, H, Lq, dh] float32.

    Tile sizes not passed explicitly resolve through
    :func:`attention_tiles`: each token axis is padded to a multiple of
    128, Lq's garbage rows sliced off and Lk's tail keys masked in-kernel
    via ``kv_len``.  Explicitly passed tiles must divide exactly (legacy
    behavior).  ``role`` names the two launches (``two_stage_attention``'s
    ``role``).  Each trace emits one ``attn.tiles`` event (``obs.trace``)
    with the resolved tiles, padded lengths, stage ①'s grid steps, the
    stage ② steps whose body applies a mask (the causal ones; padded keys
    need none there, because K and V are padded with zeros) and the scale
    bytes one stage ② step DMAs, averaged over its grid.
    """
    interpret = _default_interpret() if interpret is None else interpret
    b, h, lq, dh = q.shape
    hkv, lk = k.shape[1], k.shape[2]
    assert h % hkv == 0, (h, hkv)

    tile_kw, lqp, lkp = attention_tiles(lq, lk, **tile_kw)
    # the kernel clamps each tile to its axis
    bq, bk, bkv = min(tile_kw["bq"], lqp), min(tile_kw["bk"], lkp), min(tile_kw["bkv"], lkp)
    nq, nkv = lqp // bq, lkp // bkv
    stage2_steps = b * h * nq * nkv
    obs_trace.emit(
        "attn.tiles", role=role, **tile_kw, lqp=lqp, lkp=lkp,
        stage1_steps=b * h * nq * (lkp // bk),
        stage2_masked_steps=stage2_steps if causal else 0,
        scale_bytes_per_step=_scale_row_bytes(b * h, nq, nkv, bq, bkv) // stage2_steps,
    )

    qf = q.reshape(b * h, lq, dh)
    kf = k.reshape(b * hkv, lk, dh)
    vf = v.reshape(b * hkv, lk, dh)
    if lqp != lq:
        qf = jnp.pad(qf, ((0, 0), (0, lqp - lq), (0, 0)))
    if lkp != lk:
        kf = jnp.pad(kf, ((0, 0), (0, lkp - lk), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, lkp - lk), (0, 0)))
    qq = quantize_per_token(qf, a_bits)
    kq = quantize_per_token(kf, a_bits)
    vmax = jnp.max(jnp.abs(vf), axis=(1, 2), keepdims=True)
    vscale = jnp.maximum(vmax, 1e-8) / 127.0
    vv = jnp.clip(jnp.round(vf / vscale), -127, 127).astype(jnp.int8)
    # v_scale stays per *query* head ([B·H, 1, 1] scalars — not tensor
    # traffic, unlike the old K/V broadcast)
    vscale_q = jnp.repeat(vscale.reshape(b, hkv, 1, 1), h // hkv, axis=1)
    # stage ① + stage ② launches
    probe.record(
        "two_stage_mha", 2,
        nbytes=_attention_traffic_bytes(b * h, lqp, lkp, dh, dict(bq=bq, bk=bk, bkv=bkv)),
    )
    out = _tsa.two_stage_attention(
        qq.values,
        qq.scale.astype(jnp.float32),
        kq.values,
        kq.scale.astype(jnp.float32),
        vv,
        vscale_q.reshape(b * h, 1, 1).astype(jnp.float32),
        causal=causal,
        interpret=interpret,
        q_heads=h if hkv != h else None,
        kv_heads=hkv if hkv != h else None,
        kv_len=lk if lkp != lk else None,
        role=role,
        **tile_kw,
    )
    return out[:, :lq].reshape(b, h, lq, dh)


def qk_norm_rope(
    x: jnp.ndarray,
    cos: jnp.ndarray,
    sin: jnp.ndarray,
    norms: jnp.ndarray,
    *,
    n_heads: int,
    head_dim: int,
    eps: float,
    role: str | None = None,
    interpret: bool | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Per-head LayerNorm → 2D RoPE → per-head WHT of q and k in one launch
    (``kernels/qk_rope.py``).

    ``x`` [N, L, W] holds q and then k in its first 2·n_heads·head_dim
    columns: the fused ``wqkv`` output as it is.  L is a whole number of frames of the
    [T, head_dim] tables' T tokens; token ℓ takes row ℓ mod T.  ``norms``
    [4, head_dim] holds γ_q, β_q, γ_k, β_k.  Returns
    float32 q and k [N, L, n_heads·head_dim].  Each trace emits one
    ``qk_rope.tiles`` event (``obs.trace``) with the block it tiles by:
    T rows, ``cols`` columns, over ``steps`` grid steps."""
    interpret = _default_interpret() if interpret is None else interpret
    n, length, width = x.shape
    t = cos.shape[0]
    hd = n_heads * head_dim
    assert length % t == 0, (length, t)
    bn = next((b for b in _qk.COL_BLOCKS if hd % b == 0), hd)
    frames = n * (length // t)
    obs_trace.emit("qk_rope.tiles", role=role, rows=t, cols=bn, frames=frames,
                   steps=frames * (hd // bn))
    probe.record("qk_norm_rope", nbytes=2 * n * length * hd * (x.dtype.itemsize + 4))
    q, k = _qk.qk_norm_rope(
        x.reshape(frames, t, width), cos, sin, norms,
        n_heads=n_heads, head_dim=head_dim, eps=eps,
        bn=bn, role=role, interpret=interpret,
    )
    return q.reshape(n, length, hd), k.reshape(n, length, hd)


def online_wht_2d(x: jnp.ndarray, interpret: bool | None = None, **kw) -> jnp.ndarray:
    """Pallas blocked WHT along the last axis of [..., d]."""
    interpret = _default_interpret() if interpret is None else interpret
    lead = x.shape[:-1]
    d = x.shape[-1]
    probe.record("wht")
    y = _wht.wht(x.reshape(-1, d), interpret=interpret, **kw)
    return y.reshape(lead + (d,))


# ---------------------------------------------------------------------------
# unified-datapath wrappers (kernels/fused.py)
# ---------------------------------------------------------------------------

FUSED_BM = 256


def _pad_rows(x2: jnp.ndarray, target: int = FUSED_BM) -> tuple[jnp.ndarray, int, int]:
    m = x2.shape[0]
    bm, mp = lane_tile(m, target)
    if mp != m:
        x2 = jnp.pad(x2, ((0, mp - m), (0, 0)))
    return x2, bm, m


def _bm_target(p, default: int = FUSED_BM) -> int:
    """Row-tile target for a fused launch, from the site's schedule tiles."""
    tiles = getattr(p, "tiles", None)
    if tiles:
        return dict(tiles).get("bm_target", default) or default
    return default


def _hadamard_for(block: int | None):
    if block is None:
        return None, None
    return transforms.hadamard_matrix(min(block, 128), dtype=jnp.float32), block


def fused_linear(x, p, out_dtype=jnp.float32, interpret: bool | None = None):
    """One-launch QuantLinear apply: prologue (norm → WHT → quantize) +
    integer matmul + epilogue (IDCT → bias → act → WHT → requant), driven
    by the layer's ``prologue``/``epilogue`` descriptors
    (``core.versaq.QuantLinear``).

    ``x``: float [..., K], or a pre-quantized ``QTensor`` (e.g. from
    :func:`norm_quant_prologue`, shared across several projections).
    Returns float [..., N], or a per-token-scaled ``QTensor`` when the
    epilogue requantizes.
    """
    interpret = _default_interpret() if interpret is None else interpret
    pro, epi = p.prologue, p.epilogue
    prequant = isinstance(x, QTensor)
    xs = None
    if prequant:
        lead = x.values.shape[:-1]
        k = x.values.shape[-1]
        x2 = x.values.reshape(-1, k)
        xs = x.scale.reshape(-1, 1)
        x2, bm, m = _pad_rows(x2, target=_bm_target(p))
        if xs.shape[0] != x2.shape[0]:
            xs = jnp.pad(xs, ((0, x2.shape[0] - m), (0, 0)), constant_values=1.0)
    else:
        lead = x.shape[:-1]
        k = x.shape[-1]
        x2, bm, m = _pad_rows(x.reshape(-1, k), target=_bm_target(p))
    n = p.qw.shape[-1]
    h_pro, pro_block = _hadamard_for(
        transforms.block_size_for(k) if (p.rotate_input and not prequant) else None
    )
    act = epi.act if epi is not None else "none"
    requant = epi.requant_bits if epi is not None else None
    h_epi, epi_block = _hadamard_for(
        transforms.block_size_for(n) if (epi is not None and epi.wht) else None
    )
    dct = transforms.dct_matrix(p.dct_block, dtype=jnp.float32) if p.idct else None
    kb = -(-k // 2) if p.qw.packed else k
    probe.record(
        "fused_matmul",
        nbytes=x2.shape[0] * k + kb * n * (x2.shape[0] // bm) + x2.shape[0] * n * 4,
    )
    out = _fused.fused_matmul(
        x2,
        p.qw.values,
        p.qw.scale.reshape(1, -1),
        xs=xs,
        bias=p.bias,
        norm_u=p.norm_u,
        h_pro=h_pro,
        h_epi=h_epi,
        dct=dct,
        packed=p.qw.packed,
        a_bits=p.a_bits,
        norm_kind=(pro.norm if pro is not None and not prequant else None),
        norm_eps=(pro.eps if pro is not None else 1e-6),
        pro_wht_block=pro_block,
        act=act,
        epi_wht_block=epi_block,
        requant_bits=requant,
        dct_block=(p.dct_block if p.idct else None),
        out_dtype=out_dtype,
        bm=bm,
        interpret=interpret,
    )
    if requant is not None:
        qv, qs = out
        return QTensor(
            values=qv[:m].reshape(lead + (n,)),
            scale=qs[:m].reshape(lead + (1,)),
            bits=requant,
        )
    return out[:m].reshape(lead + (n,))


def fused_ffn_apply(x: jnp.ndarray, f, interpret: bool | None = None) -> jnp.ndarray:
    """The whole gated/plain FFN layer in ONE Pallas launch
    (``core.versaq.FusedFFN``): norm prologue → shared A-quant → gate/up
    int matmuls → act·gate → hidden WHT → requant → down int matmul →
    IDCT/biases.  x: float [..., D] -> [..., d_out]."""
    interpret = _default_interpret() if interpret is None else interpret
    lead = x.shape[:-1]
    d = x.shape[-1]
    wu, wd, wg = f.w_up, f.w_down, f.w_gate
    x2, bm, m = _pad_rows(x.reshape(-1, d), target=_bm_target(wu))
    dff = wu.qw.shape[-1]
    n_out = wd.qw.shape[-1]
    # unrotated-stream flows carry the online WHT on the gate/up inputs
    # (rotate_input equality between gate and up is a fusion precondition)
    h_pro, pro_block = _hadamard_for(
        transforms.block_size_for(d) if wu.rotate_input else None
    )
    h_mid, mid_block = _hadamard_for(
        transforms.block_size_for(dff) if wd.rotate_input else None
    )
    dct = (
        transforms.dct_matrix(wu.dct_block, dtype=jnp.float32)
        if (wu.idct or wd.idct)
        else None
    )
    mp = x2.shape[0]
    members = [wu, wd] + ([wg] if wg is not None else [])
    w_elems = sum(int(w.qw.values.size) for w in members)
    probe.record("fused_ffn", nbytes=mp * d + w_elems * (mp // bm) + mp * n_out * 4)
    y = _fused.fused_ffn(
        x2,
        wu.qw.values,
        wu.qw.scale.reshape(1, -1),
        wd.qw.values,
        wd.qw.scale.reshape(1, -1),
        wg=None if wg is None else wg.qw.values,
        wgs=None if wg is None else wg.qw.scale.reshape(1, -1),
        bg=None if wg is None else wg.bias,
        bu=wu.bias,
        bd=wd.bias,
        norm_u=f.norm_u,
        h_pro=h_pro,
        h_mid=h_mid,
        dct=dct,
        packed_g=bool(wg is not None and wg.qw.packed),
        packed_u=wu.qw.packed,
        packed_d=wd.qw.packed,
        a_bits_in=wu.a_bits,
        a_bits_mid=wd.a_bits,
        norm_kind=f.norm,
        norm_eps=f.norm_eps,
        act=f.act,
        pro_wht_block=pro_block,
        mid_wht_block=mid_block,
        idct_h=wu.idct,
        idct_out=wd.idct,
        dct_block=wu.dct_block,
        bm=bm,
        interpret=interpret,
    )
    return y[:m].reshape(lead + (n_out,))


def norm_quant_prologue(
    x: jnp.ndarray,
    *,
    norm: str | None = None,
    norm_u: jnp.ndarray | None = None,
    eps: float = 1e-6,
    wht: bool = False,
    a_bits: int = 8,
    interpret: bool | None = None,
) -> QTensor:
    """Fused prologue over float [..., D]: folded-norm statistics →
    blocked WHT → per-token quantization, one Pallas launch.  Returns a
    per-token-scaled ``QTensor`` ready for the integer matmul kernels
    (share it across co-located projections, e.g. Q/K/V)."""
    interpret = _default_interpret() if interpret is None else interpret
    lead = x.shape[:-1]
    d = x.shape[-1]
    x2, bm, m = _pad_rows(x.reshape(-1, d))
    h_pro, block = _hadamard_for(transforms.block_size_for(d) if wht else None)
    probe.record("norm_quant")
    qv, qs = _fused.norm_quant(
        x2,
        norm_u=norm_u,
        h_pro=h_pro,
        norm_kind=norm,
        norm_eps=eps,
        wht_block=block,
        a_bits=a_bits,
        bm=bm,
        interpret=interpret,
    )
    return QTensor(
        values=qv[:m].reshape(lead + (d,)),
        scale=qs[:m].reshape(lead + (1,)),
        bits=a_bits,
    )
