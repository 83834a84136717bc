"""Work a VGGT forward requires, counted from the model's shapes.

Operations are 2·M·K·N per matmul; attention counts QKᵀ and PV once each
(no recomputation, no padding), whatever a kernel's tiling does.  Bytes
are what each operation must move at least: the int4 weight panels, the
float32 activations a projection reads and writes, and the int8 Q/K/V
with the float32 output of attention.
"""
from __future__ import annotations


def scene_work(c: dict, frames: int, patches: int) -> dict:
    """Per scene: ``*_ops`` and ``*_bytes`` for the projections
    (``matmul``), frame and global attention, and the float layers outside
    the aggregator (``other``: patch projection and heads)."""
    d, f, ns = c["d_model"], c["d_ff"], c["n_special_tokens"]
    hd = c["n_heads"] * c["head_dim"]
    t = ns + patches  # tokens per frame
    n = frames * t  # tokens per scene
    blocks = 2 * c["n_aa_pairs"]
    w_bytes = c["w_bits"] / 8
    kn = d * 3 * hd + hd * d + 2 * d * f  # weights of one block
    matmul_ops = blocks * 2 * n * kn
    # fused wqkv, wo, fused FFN: f32 rows in and out, int4 panel once
    matmul_bytes = blocks * (kn * w_bytes + 4 * n * (d + 3 * hd) + 4 * n * (hd + d) + 8 * n * d)

    def attention(seqs: int, length: int) -> tuple[float, float]:
        ops = seqs * 4 * length * length * hd
        return ops, seqs * length * hd * (3 + 4)

    fo, fb = attention(frames, t)
    go, gb = attention(1, n)
    pairs = c["n_aa_pairs"]
    p = frames * patches
    other = 2 * p * d * d + 2 * frames * d * (d + 9) + 2 * p * d * (d + 5)
    return {
        "matmul_ops": matmul_ops, "matmul_bytes": matmul_bytes,
        "frame_attn_ops": pairs * fo, "frame_attn_bytes": pairs * fb,
        "global_attn_ops": pairs * go, "global_attn_bytes": pairs * gb,
        "other_ops": other,
        "total_ops": matmul_ops + pairs * (fo + go) + other,
    }


def roofline_s(ops: float, nbytes: float, peak: dict) -> float:
    """Least time the chip needs: the larger of compute and memory time."""
    return max(ops / peak["int8_ops"], nbytes / peak["hbm_bytes_per_s"])
