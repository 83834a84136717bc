"""kernels layer (``kernels/two_stage_attention.py``): the global
attention work of the window's scenes (QKᵀ and PV of every global layer,
``work.scene_work``) at the roofline (``work.roofline_s``), over the
device time of the ops the program names ``two_stage_attention_global_*``
(stage ① ``_stats``, stage ② ``_out``, each with XLA's ``.N``), in %.
With ``frame_attn_roofline`` it splits the op set ``attn_roofline``
reads."""

from bench import work

KERNELS = ("two_stage_attention_global",)


def read(m):
    if m.trace is None or m.peak is None:
        return None
    t = m.trace.ops_matching(KERNELS)
    if t <= 0:
        return None
    n = len(m.scenes)
    return 100.0 * work.roofline_s(n * m.work["global_attn_ops"], n * m.work["global_attn_bytes"],
                                   m.peak) / t
