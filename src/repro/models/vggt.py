"""VGGT: Visual Geometry Grounded Transformer (the paper's target model).

Faithful structure per paper §II-B / Fig. 2:

* DINO feature extraction is a STUB frontend — ``input_specs`` provides
  precomputed patch embeddings [B, S, P, d_in] (the paper's quantization
  also targets only the AA module).
* Per-frame special tokens (camera + register) are learned and prepended;
  with ``n_special_sets=2`` (published VGGT) the first frame of a scene
  takes its own set, the reference frame's.
* The **Alternating-Attention** backbone interleaves frame-wise attention
  (tokens reshaped to [B·S, T, C]) and global attention ([B, S·T, C]) —
  the long-sequence global attention is exactly what the paper's two-stage
  tiling (kernels/two_stage_attention.py) accelerates.
* LayerScale (DINOv2-style) on every residual branch — this is the
  LayerScale that paper Eq. 6-7 folds into the output projections.
* ``pos="rope2d"`` (published VGGT): q and k of every block rotate by the
  patch's (row, column) on the frame's patch grid (``layers.rope2d_table``);
  global attention reuses each frame's positions.
* Heads: Camera head (9-DoF pose from the camera token) and a DPT-style
  head (per-patch depth + 3D point map + confidence).

Attention is bidirectional (no causal mask); there is no KV cache —
serving is a single feed-forward pass, per the paper's deployment model.
"""
from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as A
from repro.models import ffn as F
from repro.models import layers as L

N_POSE = 9  # rotation quaternion (4) + translation (3) + focal (2)


def _init_attn_block(key, cfg: ModelConfig, dtype):
    k1, k2 = jax.random.split(key)
    p = {
        "attn_norm": L.init_norm(cfg.d_model, kind="ln", bias=True, dtype=dtype,
                                 eps=cfg.norm_eps),
        "attn": A.init_gqa(k1, cfg, dtype),
        "ffn_norm": L.init_norm(cfg.d_model, kind="ln", bias=True, dtype=dtype,
                                eps=cfg.norm_eps),
        "ffn": F.init_dense_ffn(k2, cfg.d_model, cfg.d_ff, cfg.act, dtype),
        "ls1": jnp.full((cfg.d_model,), cfg.layerscale_init, dtype),
        "ls2": jnp.full((cfg.d_model,), cfg.layerscale_init, dtype),
    }
    return p


def init_params(cfg: ModelConfig, key, dtype=jnp.float32) -> dict:
    assert cfg.vggt
    ks = jax.random.split(key, 8)
    n_groups = cfg.n_layers  # one AA pair per "layer"

    def pair(k):
        ka, kb = jax.random.split(k)
        return {
            "frame": _init_attn_block(ka, cfg, dtype),
            "global": _init_attn_block(kb, cfg, dtype),
        }

    gkeys = jax.random.split(ks[0], n_groups)
    blocks = jax.tree.map(lambda *xs: jnp.stack(xs), *[pair(k) for k in gkeys])
    d = cfg.d_model
    spec_shape = (cfg.n_special_tokens, d)
    if cfg.n_special_sets > 1:
        spec_shape = (cfg.n_special_sets,) + spec_shape
    params: dict[str, Any] = {
        "patch_proj": L.init_linear(ks[1], d, d, bias=True, dtype=dtype),
        "special_tokens": (jax.random.normal(ks[2], spec_shape) * 0.02).astype(dtype),
        "blocks": blocks,
        "final_norm": L.init_norm(d, kind="ln", bias=True, dtype=dtype, eps=cfg.norm_eps),
        "camera_head": {
            "fc1": L.init_linear(ks[3], d, d, bias=True, dtype=dtype),
            "fc2": L.init_linear(ks[4], d, N_POSE, bias=True, dtype=dtype),
        },
        "dpt_head": {
            "fc1": L.init_linear(ks[5], d, d, bias=True, dtype=dtype),
            "fc2": L.init_linear(ks[6], d, 3 + 1 + 1, bias=True, dtype=dtype),  # xyz, depth, conf
        },
    }
    return params


def _block(p, cfg: ModelConfig, x: jnp.ndarray, role: str, kv_mask=None,
           rope2d=None) -> jnp.ndarray:
    """One AA block; ``role`` (``frame`` / ``global``) names its attention
    kernels' launches in compiled HLO and device traces; ``rope2d`` is the
    frame's 2D RoPE tables (``pos="rope2d"``)."""
    # fused sites absorb their pre-norm (unified-datapath prologue)
    h = x if F.carries_norm(p["attn"]) else L.norm(p["attn_norm"], x)
    out, _ = A.gqa_attention(p["attn"], cfg, h, causal=False, mode="full", kv_mask=kv_mask,
                             role=role, rope2d=rope2d)
    x = x + out * p["ls1"].astype(out.dtype) if "ls1" in p else x + out
    h = x if F.carries_norm(p["ffn"]) else L.norm(p["ffn_norm"], x)
    out = F.dense_ffn(p["ffn"], cfg.act, h)
    x = x + out * p["ls2"].astype(out.dtype) if "ls2" in p else x + out
    return x


def token_mask(
    cfg: ModelConfig,
    b: int,
    s: int,
    p_: int,
    patch_mask: jnp.ndarray | None,
    frame_mask: jnp.ndarray | None,
) -> jnp.ndarray | None:
    """[B, S, T] bool validity mask (special tokens valid iff their frame
    is), or None when nothing is padded."""
    if patch_mask is None and frame_mask is None:
        return None
    ns = cfg.n_special_tokens
    pm = (
        jnp.ones((b, s, p_), bool)
        if patch_mask is None
        else patch_mask.astype(bool)
    )
    fm = (
        jnp.ones((b, s), bool)
        if frame_mask is None
        else frame_mask.astype(bool)
    )
    pm = pm & fm[:, :, None]
    spec = jnp.broadcast_to(fm[:, :, None], (b, s, ns))
    return jnp.concatenate([spec, pm], axis=2)


def patch_grid(cfg: ModelConfig, n_patches: int, grid=None) -> tuple[int, int] | None:
    """The (rows, cols) patch grid of a frame: ``grid`` if given, else the
    square one when ``n_patches`` is a perfect square; None where the model
    has no 2D positions.  A 2D-RoPE model refuses a frame it cannot place."""
    if cfg.pos != "rope2d":
        return None
    if grid is None:
        r = math.isqrt(n_patches)
        if r * r != n_patches:
            raise ValueError(
                f"{n_patches} patches a frame is no square grid: pass grid=(rows, cols)"
            )
        grid = (r, r)
    rows, cols = (int(v) for v in grid)
    if rows * cols > n_patches:
        raise ValueError(f"grid {rows}x{cols} holds more than the frame's {n_patches} patches")
    return rows, cols


def special_tokens(params: dict, s: int) -> jnp.ndarray:
    """[S, ns, d] special tokens of a scene's S frames: one set for every
    frame, or ([2, ns, d] params) set 0 for frame 0 and set 1 for the rest
    (published VGGT's ``slice_expand_and_flatten``)."""
    spec = params["special_tokens"]
    if spec.ndim == 2:
        return jnp.broadcast_to(spec, (s,) + spec.shape)
    rest = jnp.broadcast_to(spec[1], (s - 1,) + spec.shape[1:])
    return jnp.concatenate([spec[:1], rest], axis=0)


def forward(
    cfg: ModelConfig,
    params: dict,
    patch_embeds: jnp.ndarray,
    *,
    patch_mask: jnp.ndarray | None = None,
    frame_mask: jnp.ndarray | None = None,
    grid: tuple[int, int] | None = None,
    scan_unroll: bool = False,
    act_sharding=None,
    remat: bool = False,
) -> dict:
    """patch_embeds: [B, S, P, d] (stub DINO features).

    ``grid`` = (rows, cols) places a frame's patches for 2D RoPE
    (``patch_grid``: defaults to the square grid); patches past
    rows·cols (bucket padding) take position (0, 0).

    ``patch_mask`` [B, S, P] / ``frame_mask`` [B, S] (bool) mark padded
    patches/frames added by the serving engine's shape buckets: masked
    tokens are excluded from every attention softmax, so valid-token
    outputs equal the unpadded forward; head outputs at masked positions
    are garbage and must be sliced off by the caller.

    Returns dict with pose [B,S,9], depth [B,S,P], points [B,S,P,3],
    conf [B,S,P], tokens [B,S,T,d].
    """
    b, s, p_, d = patch_embeds.shape
    ns = cfg.n_special_tokens
    x = L.dense(params["patch_proj"], patch_embeds)
    spec = jnp.broadcast_to(special_tokens(params, s), (b, s, ns, d)).astype(x.dtype)
    x = jnp.concatenate([spec, x], axis=2)  # [B, S, T, d], T = ns + P
    t = ns + p_
    rope2d = None
    grid = patch_grid(cfg, p_, grid)
    if grid is not None:
        cos, sin = L.rope2d_table(grid, ns, cfg.head_dim, cfg.rope_theta)
        pad = t - cos.shape[0]  # padded patches: position (0, 0)
        rope2d = (jnp.pad(cos, ((0, pad), (0, 0)), constant_values=1.0),
                  jnp.pad(sin, ((0, pad), (0, 0))))
    tmask = token_mask(cfg, b, s, p_, patch_mask, frame_mask)
    fmask = None if tmask is None else tmask.reshape(b * s, t)
    gmask = None if tmask is None else tmask.reshape(b, s * t)

    def group_body(carry, gp):
        xc = carry  # [B, S, T, d]
        # frame-wise attention (each block's ops carry its role in their
        # op_name metadata)
        xf = xc.reshape(b * s, t, d)
        with jax.named_scope("frame"):
            xf = _block(gp["frame"], cfg, xf, "frame", kv_mask=fmask, rope2d=rope2d)
        xc = xf.reshape(b, s, t, d)
        # global attention over all frames' tokens
        xg = xc.reshape(b, s * t, d)
        with jax.named_scope("global"):
            xg = _block(gp["global"], cfg, xg, "global", kv_mask=gmask, rope2d=rope2d)
        xc = xg.reshape(b, s, t, d)
        if act_sharding is not None:
            xc = jax.lax.with_sharding_constraint(xc, act_sharding)
        return xc, None

    body = group_body
    if remat:
        body = jax.checkpoint(body, prevent_cse=False)
    x, _ = jax.lax.scan(body, x, params["blocks"], unroll=scan_unroll)
    x = L.norm(params["final_norm"], x)

    cam_tok = x[:, :, 0, :]  # [B, S, d]
    ch = params["camera_head"]
    pose = L.dense(ch["fc2"], jnp.tanh(L.dense(ch["fc1"], cam_tok).astype(jnp.float32)).astype(x.dtype))

    patch_tok = x[:, :, ns:, :]
    dh = params["dpt_head"]
    feat = L.gelu(L.dense(dh["fc1"], patch_tok).astype(jnp.float32)).astype(x.dtype)
    out = L.dense(dh["fc2"], feat).astype(jnp.float32)
    points, depth, conf = out[..., :3], out[..., 3], jax.nn.sigmoid(out[..., 4])
    return {
        "pose": pose.astype(jnp.float32),
        "points": points,
        "depth": depth,
        "conf": conf,
        "tokens": x,
    }


def reconstruction_loss(cfg: ModelConfig, params: dict, batch: dict) -> jnp.ndarray:
    """Simple multi-task loss (pose + depth + points) for the training demo."""
    out = forward(cfg, params, batch["patches"])
    lp = jnp.mean((out["pose"] - batch["pose"]) ** 2)
    ld = jnp.mean((out["depth"] - batch["depth"]) ** 2)
    lx = jnp.mean((out["points"] - batch["points"]) ** 2)
    return lp + ld + lx
