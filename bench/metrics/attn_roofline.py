"""kernels layer (``kernels/two_stage_attention.py``): the attention work
of the window's scenes (QKᵀ and PV of every frame and global layer,
``work.scene_work``) at the roofline (``work.roofline_s``), over the
device time of the two-stage kernel's ops in the trace, in %.  The ops are
matched by name: the program's Pallas calls become custom calls named
after their jitted wrappers (``two_stage_attention.N``, ``fused_matmul.N``,
``fused_ffn.N``, ``quant_matmul.N``), read from the forward compiled for
a described TPU v5e."""

from bench import work

KERNELS = ("two_stage_attention",)


def read(m):
    if m.trace is None or m.peak is None:
        return None
    t = m.trace.ops_matching(KERNELS)
    if t <= 0:
        return None
    n = len(m.scenes)
    ops = n * (m.work["frame_attn_ops"] + m.work["global_attn_ops"])
    nbytes = n * (m.work["frame_attn_bytes"] + m.work["global_attn_bytes"])
    return 100.0 * work.roofline_s(ops, nbytes, m.peak) / t
