"""kernels layer (``kernels/qk_rope.py``): the least bytes the q/k kernel
must move for the window's scenes, at the device's HBM bandwidth, over the
device time of the ops named ``qk_norm_rope_*`` (``_frame``, ``_global``,
each with XLA's ``.N``), in %.

Per scene the kernel runs in every block (2 per AA pair) over the scene's
S·T tokens, reading q and k as the fused ``wqkv`` site returns them
(float32) and writing float32 q and k: 2 · n_aa_pairs · S·T ·
n_heads·head_dim · 2 (q, k) · (4 + 4) bytes, 8.64 GB for an 8-frame
518-px scene (T = 5 + 1,369).  Its operations (a few hundred per feature)
are not the bound.  Reads nothing where no op has that name."""

KERNELS = ("qk_norm_rope",)


def scene_bytes(c: dict, frames: int, patches: int) -> float:
    tokens = frames * (c["n_special_tokens"] + patches)
    return 2 * c["n_aa_pairs"] * tokens * c["n_heads"] * c["head_dim"] * 2 * (4 + 4)


def read(m):
    if m.trace is None or m.peak is None:
        return None
    t = m.trace.ops_matching(KERNELS)
    if t <= 0:
        return None
    nbytes = len(m.scenes) * scene_bytes(m.config, int(m.traffic["frames"]),
                                         int(m.traffic["patches"]))
    return 100.0 * nbytes / m.peak["hbm_bytes_per_s"] / t
