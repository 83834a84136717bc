"""kernels layer (``kernels/fused.py``, ``kernels/quant_matmul.py``): the
projection work of the window's scenes (2·M·K·N of ``wqkv``, ``wo`` and
both FFN matmuls in every block, ``work.scene_work``) at the roofline
(``work.roofline_s``), over the device time of the fused and quantized
matmul kernels' ops in the trace, in %.  The ops are matched by name:
the program's Pallas calls become custom calls named after their jitted
wrappers (``two_stage_attention.N``, ``fused_matmul.N``, ``fused_ffn.N``,
``quant_matmul.N``), read from the forward compiled for a described
TPU v5e."""

from bench import work

KERNELS = ("fused_matmul", "fused_ffn", "quant_matmul")


def read(m):
    if m.trace is None or m.peak is None:
        return None
    t = m.trace.ops_matching(KERNELS)
    if t <= 0:
        return None
    n = len(m.scenes)
    return 100.0 * work.roofline_s(n * m.work["matmul_ops"], n * m.work["matmul_bytes"],
                                   m.peak) / t
