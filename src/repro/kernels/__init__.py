"""Pallas TPU kernels for the perf-critical compute of VersaQ-3D.

- quant_matmul.py: INT8/packed-INT4 MXU matmul (the reconfigurable PE array)
- two_stage_attention.py: paper Alg. 1 (stats pass + recompute pass)
- wht.py: multiplier-free blocked Walsh-Hadamard butterfly
- fused.py: the unified datapath (prologue + int matmul + epilogue, one-launch FFN)
- qk_rope.py: published VGGT's q/k LayerNorm -> 2D RoPE -> per-head WHT, one launch
Each has a jitted wrapper in ops.py and a pure-jnp oracle in ref.py;
validated in interpret mode on CPU, lowered by Mosaic on TPU.
"""
