"""Distribution correctness on 8 fake devices (subprocess):
sharded pjit train/serve step == single-device reference."""
import numpy as np

from tests.helpers import run_with_devices

SHARDED_EQ_SINGLE = """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_config
from repro.models import lm
from repro.optim import adamw
from repro.parallel import sharding
from repro.runtime.trainer import make_train_step

assert len(jax.devices()) == 8, jax.devices()
cfg = get_config("qwen3-14b-smoke").with_(d_model=64, n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128)
key = jax.random.PRNGKey(0)
params = lm.init_params(cfg, key)
opt = adamw.init(params)
batch = {
    "tokens": jax.random.randint(key, (8, 16), 0, cfg.vocab_size),
    "labels": jax.random.randint(key, (8, 16), 0, cfg.vocab_size),
}
step = make_train_step(cfg, adamw.AdamWConfig(lr=1e-3))

# single device
p1, o1, m1 = jax.jit(step)(params, opt, batch)

# 2x4 mesh DP x TP
mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(jax.sharding.AxisType.Auto,) * 2)
with mesh:
    pspec = sharding.make_param_pspecs(params)
    psh = jax.tree.map(lambda s: NamedSharding(mesh, s), pspec, is_leaf=lambda x: isinstance(x, P))
    osh = adamw.AdamWState(step=NamedSharding(mesh, P()),
                           m=jax.tree.map(lambda s: NamedSharding(mesh, s), pspec, is_leaf=lambda x: isinstance(x, P)),
                           v=jax.tree.map(lambda s: NamedSharding(mesh, s), pspec, is_leaf=lambda x: isinstance(x, P)))
    bsh = {"tokens": NamedSharding(mesh, P("data", None)), "labels": NamedSharding(mesh, P("data", None))}
    pjit_step = jax.jit(step, in_shardings=(psh, osh, bsh))
    params_s = jax.device_put(params, psh)
    opt_s = jax.device_put(opt, osh)
    batch_s = jax.device_put(batch, bsh)
    p2, o2, m2 = pjit_step(params_s, opt_s, batch_s)

np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]), rtol=1e-4)
for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-3, atol=5e-3)
print("SHARDED_OK loss", float(m2["loss"]))
"""


def test_sharded_train_step_matches_single_device():
    out = run_with_devices(SHARDED_EQ_SINGLE, n_devices=8)
    assert "SHARDED_OK" in out


QUANT_SERVE_SHARDED = """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_config
from repro.core.model_quant import quantize_lm
from repro.core.versaq import W4A8
from repro.models import lm
from repro.parallel import sharding

cfg = get_config("qwen3-14b-smoke")
key = jax.random.PRNGKey(0)
qp = quantize_lm(cfg, lm.init_params(cfg, key), W4A8)
toks = jax.random.randint(key, (4, 8), 0, cfg.vocab_size)
ref, _ = jax.jit(lambda p, t: lm.forward(cfg, p, t))(qp, toks)

mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(jax.sharding.AxisType.Auto,) * 2)
with mesh:
    pspec = sharding.make_param_pspecs(qp)
    psh = jax.tree.map(lambda s: NamedSharding(mesh, s), pspec, is_leaf=lambda x: isinstance(x, P))
    qp_s = jax.device_put(qp, psh)
    toks_s = jax.device_put(toks, NamedSharding(mesh, P("data", None)))
    got, _ = jax.jit(lambda p, t: lm.forward(cfg, p, t),
                     in_shardings=(psh, NamedSharding(mesh, P("data", None))))(qp_s, toks_s)
# Sharded partial-sum order perturbs pre-quantization activations by ~ulp;
# values sitting on an int8 rounding boundary then flip one quantization
# bin, so a tiny fraction of logits may move by O(one scale step).  Assert
# that structure instead of elementwise tightness (which is flaky).
diff = np.abs(np.asarray(got) - np.asarray(ref))
frac = float((diff > 2e-2).mean())
assert frac < 0.01, ("bin-flip fraction", frac)
assert float(diff.max()) < 0.25, ("max deviation", float(diff.max()))
print("QUANT_SHARD_OK")
"""


def test_quantized_serving_sharded_matches():
    out = run_with_devices(QUANT_SERVE_SHARDED, n_devices=8)
    assert "QUANT_SHARD_OK" in out
