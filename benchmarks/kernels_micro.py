"""Per-kernel microbenchmarks (interpret mode on CPU: structural metrics
+ small-shape wall time; real perf comes from the TPU lowering)."""
import jax.numpy as jnp
import numpy as np

from benchmarks import common
from repro.core.quantize import quantize_weight
from repro.kernels import ops
from repro.kernels.two_stage_attention import vmem_bytes_two_stage

RNG = np.random.default_rng(0)


def main():
    x = jnp.asarray(RNG.normal(size=(64, 256)), jnp.float32)
    w = jnp.asarray(RNG.normal(size=(256, 128)), jnp.float32)
    for bits in (8, 4):
        wq = quantize_weight(w, bits)
        us = common.timeit(
            lambda: ops.quant_linear_matmul(x, wq, a_bits=8, bm=32, bn=64, bk=128, interpret=True)
        )
        hbm = x.size + wq.values.size + 64 * 128 * 4
        common.emit(f"kernels.quant_matmul.w{bits}", us, f"hbm_bytes={hbm} (w4 halves weight traffic)")
    q = jnp.asarray(RNG.normal(size=(1, 2, 128, 64)), jnp.float32)
    us = common.timeit(lambda: ops.two_stage_mha(q, q, q, causal=False, bq=64, bk=128, bkv=128))
    m = vmem_bytes_two_stage(64, 64, 2048, 64)
    common.emit("kernels.two_stage_mha", us,
                f"vmem_stage1={m['stage1']}B vmem_stage2={m['stage2']}B vs_flash={m['flash_same_tiles']}B")
    xw = jnp.asarray(RNG.normal(size=(32, 1024)), jnp.float32)
    us = common.timeit(lambda: ops.online_wht_2d(xw, br=32))
    common.emit("kernels.wht", us, "multiplier-free butterfly + one 128x128 MXU dot")


if __name__ == "__main__":
    main()
