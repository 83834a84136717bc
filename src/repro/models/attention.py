"""Attention mixers: GQA (w/ qk-norm) and MLA, with quantized KV caches.

Three entry modes per mixer:
  * ``full``   — training / VGGT forward: attention over the whole sequence
                 (causal flag per call; VGGT global/frame attention is
                 bidirectional, LM training is causal).
  * ``prefill``— like full, but also writes the (int8-quantized) KV cache.
  * ``decode`` — one new token against the cache (paper's serve path; the
                 int8 cache is the activation-quantization idea applied to
                 the most bytes-critical tensor in long-sequence serving).

Per the paper's Stage-2 flow: Q/K get an online per-head WHT after
RoPE/qk-norm when the layer is quantized (scores invariant, distributions
smoothed); V carries an offline per-head Hadamard folded into W_v/W_o.
"""
from __future__ import annotations

from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.quantize import QTensor
from repro.core.versaq import QuantLinear, head_wht
from repro.models import layers as L


class KVCache(NamedTuple):
    """int8 KV cache with per-(token, head) scales.

    k/v: [B, S, Hkv, dh] int8;  k_scale/v_scale: [B, S, Hkv, 1] f32.
    ``length``: [] int32 current fill.
    For MLA the "k" slot stores the compressed c_kv (+ rope key appended
    separately) — see MLAttention.
    """

    k: jnp.ndarray
    v: jnp.ndarray
    k_scale: jnp.ndarray
    v_scale: jnp.ndarray
    length: jnp.ndarray


def _pad_mask(pad_lens: jnp.ndarray, width: int) -> jnp.ndarray:
    """Key-slot validity for LEFT-padded rows: slot s of a row with
    ``pad_lens[b]`` leading pad positions is valid iff ``s >= pad_lens[b]``
    (serving pads prompts on the left so the last real token always sits
    in the last prompt slot).  Shape [B, width] bool."""
    return jnp.arange(width)[None, :] >= pad_lens[:, None]


def roll_kv(cache: KVCache, shift) -> KVCache:
    """Shift every cached token right by ``shift`` slots along the time
    axis (the slot-scheduler's re-alignment primitive: a prompt prefilled
    at bucket width L joins a decode batch at clock T by rolling its rows
    so the last real token lands at slot T-1).  Wrapped-around garbage
    lands in the region ``pad_lens`` masks off, so reads stay token-exact.

    Works on both cache layouts — per-group [B, S, Hkv, d] and stacked
    [G, B, S, Hkv, d] — because the time axis is always third from the
    trailing (head, feature) pair.  ``length`` is left untouched.
    """
    axis = cache.k.ndim - 3
    return cache._replace(
        k=jnp.roll(cache.k, shift, axis=axis),
        v=jnp.roll(cache.v, shift, axis=axis),
        k_scale=jnp.roll(cache.k_scale, shift, axis=axis),
        v_scale=jnp.roll(cache.v_scale, shift, axis=axis),
    )


def _quant_tokens(x: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def _quant_tokens_like(x: jnp.ndarray, dtype) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Quantize for an int8 cache; pass through for a bf16 cache (the
    unquantized baseline in the roofline comparisons)."""
    if dtype == jnp.int8:
        return _quant_tokens(x)
    return x.astype(dtype), jnp.ones(x.shape[:-1] + (1,), jnp.float32)


def init_kv_cache(
    cfg: ModelConfig, batch: int, max_len: int, n_groups: int, kv_dtype=jnp.int8
) -> KVCache:
    """Stacked cache for ``n_groups`` scan groups × per-group attn layers."""
    if cfg.mla:
        kd = cfg.kv_lora_rank + cfg.qk_rope_dim
        k = jnp.zeros((n_groups, batch, max_len, 1, kd), kv_dtype)
        v = jnp.zeros((n_groups, batch, max_len, 1, 1), kv_dtype)  # unused slot
        ks = jnp.zeros((n_groups, batch, max_len, 1, 1), jnp.float32)
        vs = jnp.zeros((n_groups, batch, max_len, 1, 1), jnp.float32)
    else:
        hkv, dh = cfg.n_kv_heads, cfg.head_dim
        k = jnp.zeros((n_groups, batch, max_len, hkv, dh), kv_dtype)
        v = jnp.zeros((n_groups, batch, max_len, hkv, dh), kv_dtype)
        ks = jnp.zeros((n_groups, batch, max_len, hkv, 1), jnp.float32)
        vs = jnp.zeros((n_groups, batch, max_len, hkv, 1), jnp.float32)
    return KVCache(k, v, ks, vs, jnp.zeros((), jnp.int32))


# ---------------------------------------------------------------------------
# GQA
# ---------------------------------------------------------------------------


def init_gqa(key, cfg: ModelConfig, dtype=jnp.float32):
    dh = cfg.head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": L.init_linear(ks[0], cfg.d_model, cfg.n_heads * dh, bias=cfg.attn_bias, dtype=dtype),
        "wk": L.init_linear(ks[1], cfg.d_model, cfg.n_kv_heads * dh, bias=cfg.attn_bias, dtype=dtype),
        "wv": L.init_linear(ks[2], cfg.d_model, cfg.n_kv_heads * dh, bias=cfg.attn_bias, dtype=dtype),
        "wo": L.init_linear(ks[3], cfg.n_heads * dh, cfg.d_model, bias=cfg.attn_bias, dtype=dtype),
    }
    if cfg.qk_norm:
        kind = cfg.qk_norm_kind
        for name in ("q_norm", "k_norm"):
            p[name] = L.init_norm(dh, kind=kind, bias=kind == "ln", dtype=dtype,
                                  eps=cfg.norm_eps)
    return p


def _sdpa(q, k, v, *, causal: bool, q_offset: int | jnp.ndarray = 0, kv_len: Optional[jnp.ndarray] = None,
          kv_mask: Optional[jnp.ndarray] = None):
    """Vanilla SDPA (materializes [Lq,Lk] scores) — ablation baseline.

    q: [B,Lq,H,dh]; k/v: [B,Lk,Hkv,dh]. f32 softmax. GQA broadcast.
    ``kv_mask``: [B, Lk] bool — False keys are excluded (padding-to-bucket
    in the serving engine)."""
    b, lq, h, dh = q.shape
    lk, hkv = k.shape[1], k.shape[2]
    g = h // hkv
    qf = q.reshape(b, lq, hkv, g, dh).astype(jnp.float32)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qf, k.astype(jnp.float32)) / jnp.sqrt(
        jnp.float32(dh)
    )
    if causal:
        rows = jnp.asarray(q_offset) + jnp.arange(lq)[:, None]
        cols = jnp.arange(lk)[None, :]
        s = jnp.where(rows >= cols, s, -1e30)
    if kv_len is not None:  # mask unwritten cache slots
        s = jnp.where(jnp.arange(lk)[None, :] < kv_len, s, -1e30)
    if kv_mask is not None:
        s = jnp.where(kv_mask[:, None, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p, v.astype(jnp.float32))
    return o.reshape(b, lq, h, v.shape[-1])


CHUNK = 1024


def _sdpa_streamed(q, k, v, *, causal: bool, two_stage: bool = False, chunk: int = CHUNK, compute_dtype: str = 'f32',
                   kv_mask: Optional[jnp.ndarray] = None):
    """Streaming attention over KV chunks — never materializes [Lq,Lk].

    ``two_stage=False``: FlashAttention-style single pass carrying
    (m, l, o) with O rescaling.
    ``two_stage=True``: the paper's Alg. 1 — pass ① computes only (m, l),
    pass ② *recomputes* Q·Kᵀ with the final stats and accumulates O with
    no rescaling (trades one extra QKᵀ for the O-carry; on the
    accelerator this is what frees VMEM, and the Pallas kernel
    (kernels/two_stage_attention.py) is the INT8 realization).

    The chunk loop is a Python loop (always unrolled) so dry-run
    cost_analysis counts every chunk — see dryrun.py pass 2.
    """
    b, lq, h, dh = q.shape
    lk, hkv = k.shape[1], k.shape[2]
    dv = v.shape[-1]
    g = h // hkv
    cdt = jnp.bfloat16 if compute_dtype == "bf16" else jnp.float32
    qf = (q.reshape(b, lq, hkv, g, dh) / jnp.sqrt(jnp.float32(dh)).astype(q.dtype)).astype(cdt)
    kf = k.astype(cdt)
    vf = v.astype(cdt)
    n_chunks = max(1, (lk + chunk - 1) // chunk)

    def scores(c0, c1):
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qf, kf[:, c0:c1],
                       preferred_element_type=jnp.float32)
        if causal:
            rows = jnp.arange(lq)[:, None] + (lk - lq)
            cols = c0 + jnp.arange(c1 - c0)[None, :]
            s = jnp.where(rows >= cols, s, -1e30)
        if kv_mask is not None:
            s = jnp.where(kv_mask[:, None, None, None, c0:c1], s, -1e30)
        return s

    def live(c0):  # causal: skip chunks fully above the diagonal
        return (not causal) or (c0 <= (lk - lq) + lq - 1)

    m = jnp.full((b, hkv, g, lq, 1), -1e30, jnp.float32)
    l = jnp.zeros((b, hkv, g, lq, 1), jnp.float32)
    if two_stage:
        # pass ① — statistics only (Eq. 8-9)
        for c in range(n_chunks):
            c0, c1 = c * chunk, min((c + 1) * chunk, lk)
            if not live(c0):
                continue
            s = scores(c0, c1)
            m_new = jnp.maximum(m, s.max(-1, keepdims=True))
            l = l * jnp.exp(m - m_new) + jnp.exp(s - m_new).sum(-1, keepdims=True)
            m = m_new
        # pass ② — recompute with final stats, larger tiles, no rescale
        o = jnp.zeros((b, hkv, g, lq, dv), jnp.float32)
        big = chunk * 2  # paper: Stage-② mega-tiles (T_V > T_K)
        for c in range(max(1, (lk + big - 1) // big)):
            c0, c1 = c * big, min((c + 1) * big, lk)
            if not live(c0):
                continue
            p = jnp.exp(scores(c0, c1) - m)
            o = o + jnp.einsum("bhgqk,bkhd->bhgqd", p.astype(cdt), vf[:, c0:c1],
                               preferred_element_type=jnp.float32)
        o = o / jnp.maximum(l, 1e-30)
    else:
        o = jnp.zeros((b, hkv, g, lq, dv), jnp.float32)
        for c in range(n_chunks):
            c0, c1 = c * chunk, min((c + 1) * chunk, lk)
            if not live(c0):
                continue
            s = scores(c0, c1)
            m_new = jnp.maximum(m, s.max(-1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new)
            l = l * alpha + p.sum(-1, keepdims=True)
            o = o * alpha + jnp.einsum("bhgqk,bkhd->bhgqd", p.astype(cdt), vf[:, c0:c1],
                                       preferred_element_type=jnp.float32)
            m = m_new
        o = o / jnp.maximum(l, 1e-30)
    return jnp.moveaxis(o.reshape(b, hkv * g, lq, dv), 1, 2)


def sdpa_dispatch(cfg, q, k, v, *, causal: bool, q_offset=0, kv_len=None, kv_mask=None):
    impl = getattr(cfg, "attn_impl", "flash")
    if impl == "vanilla" or kv_len is not None:
        # cache-masked paths (prefill-into-cache / decode) use the masked
        # vanilla form; decode scores are [*,1,S] (linear, not quadratic)
        return _sdpa(q, k, v, causal=causal, q_offset=q_offset, kv_len=kv_len, kv_mask=kv_mask)
    return _sdpa_streamed(q, k, v, causal=causal, two_stage=(impl == "two_stage"),
                          compute_dtype=getattr(cfg, "attn_dtype", "f32"), kv_mask=kv_mask)


def _two_stage_kernel_sdpa(q, k, v, *, causal: bool, tiles: tuple | None = None,
                           role: str | None = None):
    """Quantized fast path: the paper's INT8 two-stage Pallas kernel.

    q: [B,Lq,H,dh]; k/v: [B,Lk,Hkv,dh] float (already per-head rotated by
    the VersaQ flow).  Q/K are quantized per token, V per head, inside
    ``kernels.ops.two_stage_mha``; GQA-shared K/V heads are indexed inside
    the kernel grid — never broadcast-copied to the full head count (the
    old copy materialized H/Hkv× the K/V bytes on long sequences).

    Untileable lengths are lane-padded by the wrapper (masked in-kernel
    via ``kv_len``); only truly tiny sequences (< one sublane) fall back
    to the jnp emulation."""
    from repro.kernels import ops as kernel_ops

    lq, lk = q.shape[1], k.shape[1]
    if min(lq, lk) < 8:
        return None
    o = kernel_ops.two_stage_mha(
        jnp.moveaxis(q, 2, 1),
        jnp.moveaxis(k, 2, 1),
        jnp.moveaxis(v, 2, 1),
        causal=causal,
        role=role,
        **(dict(tiles) if tiles else {}),
    )
    return jnp.moveaxis(o, 1, 2)


def _qk_kernel_path(cfg: ModelConfig) -> bool:
    """Published VGGT's q/k work (LayerNorm, 2D RoPE, per-head WHT) runs as
    one Pallas launch on the quantized two-stage path."""
    return (
        cfg.pos == "rope2d"
        and cfg.qk_norm
        and cfg.qk_norm_kind == "ln"
        and cfg.n_kv_heads == cfg.n_heads
        and getattr(cfg, "attn_impl", "flash") == "two_stage"
        and getattr(cfg, "attn_use_kernel", True)
    )


def gqa_attention(
    p: dict,
    cfg: ModelConfig,
    x: jnp.ndarray,
    *,
    causal: bool = True,
    positions: Optional[jnp.ndarray] = None,
    cache: Optional[KVCache] = None,
    mode: str = "full",
    kv_mask: Optional[jnp.ndarray] = None,
    pad_lens: Optional[jnp.ndarray] = None,
    role: Optional[str] = None,
    rope2d: Optional[tuple[jnp.ndarray, jnp.ndarray]] = None,
) -> tuple[jnp.ndarray, Optional[KVCache]]:
    """``role`` is a static label for the two-stage kernel's and the q/k
    kernel's launch names (``kernels/two_stage_attention.py``,
    ``kernels/qk_rope.py``); other paths ignore it.  ``rope2d`` is one
    frame's (cos, sin) [T, head_dim] for ``pos="rope2d"``
    (``layers.rope2d_table``): the sequence is whole frames of T tokens."""
    b, lq, _ = x.shape
    h, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    qkv = None
    if "wqkv" in p:
        # unified datapath: one launch runs the absorbed pre-norm (the
        # caller passed the raw stream — see ``core.versaq.carries_norm``),
        # the shared per-token quantization and all three projections
        quantized = isinstance(p["wqkv"], QuantLinear)
        qkv = L.dense(p["wqkv"], x)
        q, k, v = jnp.split(qkv, [h * dh, (h + hkv) * dh], axis=-1)
        q = q.reshape(b, lq, h, dh)
        k = k.reshape(b, lq, hkv, dh)
        v = v.reshape(b, lq, hkv, dh)
    else:
        quantized = isinstance(p["wq"], QuantLinear)
        q = L.dense(p["wq"], x).reshape(b, lq, h, dh)
        k = L.dense(p["wk"], x).reshape(b, lq, hkv, dh)
        v = L.dense(p["wv"], x).reshape(b, lq, hkv, dh)
    if cfg.pos == "rope2d" and rope2d is None:
        raise ValueError("pos='rope2d' needs the frame's (cos, sin) tables: rope2d=")
    if quantized and _qk_kernel_path(cfg):
        # q/k norm -> 2D RoPE -> per-head WHT in one launch, read straight
        # from the fused site's output (kernels/qk_rope.py)
        from repro.kernels import ops as kernel_ops

        if qkv is None:
            qkv = jnp.concatenate([q.reshape(b, lq, -1), k.reshape(b, lq, -1)], axis=-1)
        qn, kn = p["q_norm"], p["k_norm"]
        q, k = kernel_ops.qk_norm_rope(
            qkv, *rope2d, jnp.stack([qn.g, qn.b, kn.g, kn.b]),
            n_heads=h, head_dim=dh, eps=qn.eps, role=role,
        )
        q = q.reshape(b, lq, h, dh)
        k = k.reshape(b, lq, hkv, dh)
    else:
        if cfg.qk_norm:
            q = L.norm(p["q_norm"], q)
            k = L.norm(p["k_norm"], k)
        if positions is None:
            positions = jnp.arange(lq)[None, :]
        if cfg.pos == "rope":
            cos, sin = L.rope_freqs(dh, cfg.rope_theta, positions)
            q = L.apply_rope(q, cos, sin)
            k = L.apply_rope(k, cos, sin)
        elif cfg.pos == "rope2d":
            q = L.apply_rope2d(q, *rope2d)
            k = L.apply_rope2d(k, *rope2d)
        if quantized:
            # paper Stage 2: post-RoPE online per-head WHT (scores invariant)
            q = head_wht(q)
            k = head_wht(k)
    # V arrives per-head-rotated from the offline W_v fusion.

    if pad_lens is not None:
        # left-padded serving buckets: derive the key mask; exclusive with
        # an explicit kv_mask (VGGT patch masking)
        assert kv_mask is None, "pass either kv_mask or pad_lens, not both"

    if mode == "full" or cache is None:
        if pad_lens is not None:
            kv_mask = _pad_mask(pad_lens, lq)
        o = None
        if (
            quantized
            and getattr(cfg, "attn_impl", "flash") == "two_stage"
            and getattr(cfg, "attn_use_kernel", True)
            and kv_mask is None
        ):
            # W4A8 serving fast path: INT8 Q/K/V through the Pallas kernel
            # (paper Alg. 1); masked (padded-bucket) calls and untileable
            # lengths fall through to the jnp emulation, which supports
            # kv_mask and any L.
            o = _two_stage_kernel_sdpa(
                q, k, v, causal=causal,
                tiles=getattr(cfg, "attn_tiles", None), role=role,
            )
        if o is None:
            o = sdpa_dispatch(cfg, q, k, v, causal=causal, kv_mask=kv_mask)
        new_cache = None
    else:
        # explicit kv_mask is a full/serving-path feature; the cache paths
        # below only support the pad_lens-derived left-pad mask — fail
        # loudly rather than silently attending to padded keys
        assert kv_mask is None, "kv_mask is not supported on prefill/decode cache paths"
        pos0 = cache.length
        kq, ks_ = _quant_tokens_like(k, cache.k.dtype)
        vq, vs_ = _quant_tokens_like(v, cache.v.dtype)
        kc = jax.lax.dynamic_update_slice(cache.k, kq, (0, pos0, 0, 0))
        vc = jax.lax.dynamic_update_slice(cache.v, vq, (0, pos0, 0, 0))
        ksc = jax.lax.dynamic_update_slice(cache.k_scale, ks_, (0, pos0, 0, 0))
        vsc = jax.lax.dynamic_update_slice(cache.v_scale, vs_, (0, pos0, 0, 0))
        new_len = pos0 + lq
        new_cache = KVCache(kc, vc, ksc, vsc, new_len)
        if mode == "prefill" and lq > 1:
            # streaming attention over the freshly-quantized K/V (prefill
            # starts the cache: earlier slots are empty) — O(L·chunk) mem
            kf = kq.astype(jnp.float32) * ks_
            vf = vq.astype(jnp.float32) * vs_
            mask = _pad_mask(pad_lens, lq) if pad_lens is not None else None
            o = sdpa_dispatch(cfg, q, kf, vf, causal=causal, kv_mask=mask)
        else:
            # decode: scores are [*, 1, S] — linear, masked vanilla path;
            # left-pad slots written during a bucketed prefill are masked
            kf = kc.astype(jnp.float32) * ksc
            vf = vc.astype(jnp.float32) * vsc
            mask = _pad_mask(pad_lens, kc.shape[1]) if pad_lens is not None else None
            o = _sdpa(q, kf, vf, causal=causal, q_offset=pos0, kv_len=new_len,
                      kv_mask=mask)
    o = o.reshape(b, lq, h * dh).astype(x.dtype)
    return L.dense(p["wo"], o), new_cache


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2): low-rank compressed KV cache, absorbed decode
# ---------------------------------------------------------------------------


def init_mla(key, cfg: ModelConfig, dtype=jnp.float32):
    ks = jax.random.split(key, 5)
    h = cfg.n_heads
    qd = cfg.qk_nope_dim + cfg.qk_rope_dim
    return {
        "wq": L.init_linear(ks[0], cfg.d_model, h * qd, dtype=dtype),
        "w_kv_down": L.init_linear(ks[1], cfg.d_model, cfg.kv_lora_rank + cfg.qk_rope_dim, dtype=dtype),
        "kv_norm": L.init_norm(cfg.kv_lora_rank, kind="rms", dtype=dtype),
        "w_k_up": L.init_linear(ks[2], cfg.kv_lora_rank, h * cfg.qk_nope_dim, dtype=dtype),
        "w_v_up": L.init_linear(ks[3], cfg.kv_lora_rank, h * cfg.v_head_dim, dtype=dtype),
        "wo": L.init_linear(ks[4], h * cfg.v_head_dim, cfg.d_model, dtype=dtype),
    }


def mla_attention(
    p: dict,
    cfg: ModelConfig,
    x: jnp.ndarray,
    *,
    causal: bool = True,
    positions: Optional[jnp.ndarray] = None,
    cache: Optional[KVCache] = None,
    mode: str = "full",
    pad_lens: Optional[jnp.ndarray] = None,
) -> tuple[jnp.ndarray, Optional[KVCache]]:
    b, lq, _ = x.shape
    h = cfg.n_heads
    dn, dr, dv, rank = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim, cfg.kv_lora_rank
    if positions is None:
        positions = jnp.arange(lq)[None, :]

    q = L.dense(p["wq"], x).reshape(b, lq, h, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    kv = L.dense(p["w_kv_down"], x)
    c_kv, k_rope = kv[..., :rank], kv[..., rank:]
    c_kv = L.norm(p["kv_norm"], c_kv)
    cos, sin = L.rope_freqs(dr, cfg.rope_theta, positions)
    q_rope = L.apply_rope(q_rope, cos, sin)
    k_rope = L.apply_rope(k_rope[..., None, :], cos, sin)[..., 0, :]  # shared across heads

    scale = 1.0 / jnp.sqrt(jnp.float32(dn + dr))
    if mode == "full" or cache is None or (mode == "prefill" and lq > 1):
        # full / prefill: materialize per-token K/V from the fresh c_kv
        # (cheap: [B,L,h,dn]) and run the streaming SDPA; the absorbed
        # compressed-cache path is decode-only (linear scores).
        k_nope = L.dense(p["w_k_up"], c_kv).reshape(b, lq, h, dn)
        v = L.dense(p["w_v_up"], c_kv).reshape(b, lq, h, dv)
        q_eff = jnp.concatenate([q_nope, q_rope], axis=-1)
        k_eff = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope[:, :, None, :], (b, lq, h, dr))], axis=-1
        )
        # pad V head_dim to match q_eff's (dn+dr) contract-free output dim
        mask = _pad_mask(pad_lens, lq) if pad_lens is not None else None
        o = sdpa_dispatch(cfg, q_eff, k_eff, v, causal=causal, kv_mask=mask)
        new_cache = None
        if mode == "prefill" and cache is not None:
            pos0 = cache.length
            ck = jnp.concatenate([c_kv, k_rope], axis=-1)[:, :, None, :]
            ckq, cks = _quant_tokens_like(ck, cache.k.dtype)
            kc = jax.lax.dynamic_update_slice(cache.k, ckq, (0, pos0, 0, 0))
            ksc = jax.lax.dynamic_update_slice(cache.k_scale, cks, (0, pos0, 0, 0))
            new_cache = KVCache(kc, cache.v, ksc, cache.v_scale, pos0 + lq)
    else:
        # absorbed decode: score via cache-domain projection of q
        pos0 = cache.length
        ck = jnp.concatenate([c_kv, k_rope], axis=-1)[:, :, None, :]  # [B,L,1,rank+dr]
        ckq, cks = _quant_tokens_like(ck, cache.k.dtype)
        kc = jax.lax.dynamic_update_slice(cache.k, ckq, (0, pos0, 0, 0))
        ksc = jax.lax.dynamic_update_slice(cache.k_scale, cks, (0, pos0, 0, 0))
        new_len = pos0 + lq
        new_cache = KVCache(kc, cache.v, ksc, cache.v_scale, new_len)
        ckf = (kc.astype(jnp.float32) * ksc)[:, :, 0, :]  # [B,S,rank+dr]
        c_all, krope_all = ckf[..., :rank], ckf[..., rank:]
        wku = p["w_k_up"]["w"] if isinstance(p["w_k_up"], dict) else None
        if wku is None:  # quantized: dequantize the small up-proj for absorption
            wku = p["w_k_up"].qw.dequantize(jnp.float32)
            if p["w_k_up"].idct:
                from repro.core import transforms as _t

                d = _t.dct_matrix(p["w_k_up"].dct_block, dtype=jnp.float32)
                wku = _t.apply_blocked(wku, d, p["w_k_up"].dct_block)
        wku = wku.reshape(rank, h, dn)
        q_lora = jnp.einsum("bqhd,rhd->bqhr", q_nope.astype(jnp.float32), wku.astype(jnp.float32))
        s = (
            jnp.einsum("bqhr,bkr->bhqk", q_lora, c_all)
            + jnp.einsum("bqhd,bkd->bhqk", q_rope.astype(jnp.float32), krope_all)
        ) * scale
        rows = pos0 + jnp.arange(lq)[:, None]
        cols = jnp.arange(c_all.shape[1])[None, :]
        s = jnp.where((rows >= cols) & (cols < new_len), s, -1e30)
        if pad_lens is not None:  # left-pad slots from a bucketed prefill
            s = jnp.where(_pad_mask(pad_lens, c_all.shape[1])[:, None, None, :], s, -1e30)
        att = jax.nn.softmax(s, axis=-1)
        o_lora = jnp.einsum("bhqk,bkr->bqhr", att, c_all)
        wvu = p["w_v_up"]["w"] if isinstance(p["w_v_up"], dict) else None
        if wvu is None:
            wvu = p["w_v_up"].qw.dequantize(jnp.float32)
            if p["w_v_up"].idct:
                from repro.core import transforms as _t

                d = _t.dct_matrix(p["w_v_up"].dct_block, dtype=jnp.float32)
                wvu = _t.apply_blocked(wvu, d, p["w_v_up"].dct_block)
        wvu = wvu.reshape(rank, h, dv)
        o = jnp.einsum("bqhr,rhd->bqhd", o_lora, wvu.astype(jnp.float32))
    o = o.reshape(b, lq, h * dv).astype(x.dtype)
    return L.dense(p["wo"], o), new_cache
