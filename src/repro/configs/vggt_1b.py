"""vggt-1b — the paper's own model (VGGT, CVPR'25 [55]).

24 alternating-attention pairs (frame + global per pair), d_model=1024,
16H MHA, d_ff=4096, LayerNorm + LayerScale (DINOv2-style).  The DINO
frontend is a STUB (precomputed patch embeddings); camera + DPT heads on
top.  This is the model the VersaQ-3D quantization and two-stage tiling
were designed for.
"""
from repro.configs.base import ModelConfig, register


@register("vggt-1b")
def config() -> ModelConfig:
    return ModelConfig(
        name="vggt-1b",
        family="vggt",
        n_layers=24,  # AA pairs
        d_model=1024,
        n_heads=16,
        n_kv_heads=16,
        d_ff=4096,
        vocab_size=1,
        norm="ln",
        norm_bias=True,
        act="gelu",
        pos="none",
        vggt=True,
        layerscale=True,
        embed_inputs=True,
        n_special_tokens=5,
        max_seq=65536,
    )


@register("vggt-1b-smoke")
def smoke_config() -> ModelConfig:
    return config().with_(
        name="vggt-smoke",
        n_layers=2,
        d_model=128,
        n_heads=4,
        n_kv_heads=4,
        head_dim=32,
        d_ff=256,
        max_seq=512,
    )


@register("vggt-1b-published")
def published_config() -> ModelConfig:
    """VGGT-1B as released (facebookresearch/vggt, ``vggt/models/aggregator.py``:
    ``Aggregator(embed_dim=1024, depth=24, num_heads=16, mlp_ratio=4,
    num_register_tokens=4, qkv_bias=True, proj_bias=True, ffn_bias=True,
    qk_norm=True, rope_freq=100, init_values=0.01)``).

    Beyond ``vggt-1b``: a LayerNorm over each head's 64 q and k features
    (one γ, β shared by the heads), 2D RoPE with base 100 (a head's first
    32 features rotate by the patch row, the last 32 by the column;
    patches at grid position + 1, special tokens at 0), a first-frame set
    of special tokens (``slice_expand_and_flatten``), exact (erf) GELU
    (``nn.GELU()``), and ``nn.LayerNorm``'s default eps 1e-5 in every
    norm.  The DINO frontend and the heads stay the stubs of ``vggt-1b``.
    """
    return config().with_(
        name="vggt-1b-published",
        norm_eps=1e-5,
        qk_norm=True,
        qk_norm_kind="ln",
        pos="rope2d",
        rope_theta=100.0,
        act="gelu_erf",
        attn_bias=True,
        n_special_sets=2,
        layerscale_init=0.01,
    )


@register("vggt-1b-published-smoke")
def published_smoke_config() -> ModelConfig:
    """``vggt-1b-published`` at CPU-test widths, with the published head
    size (64) so that 2D RoPE keeps its 16 frequencies per axis."""
    return published_config().with_(
        name="vggt-published-smoke",
        n_layers=2,
        d_model=128,
        n_heads=2,
        n_kv_heads=2,
        head_dim=64,
        d_ff=256,
        max_seq=512,
    )
