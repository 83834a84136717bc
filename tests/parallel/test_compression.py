"""INT8 error-feedback compressed gradient all-reduce (subprocess, 8 dev)."""
from tests.helpers import run_with_devices

PSUM_CORRECT = """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from jax.experimental.shard_map import shard_map
from repro.parallel.compression import compressed_psum

mesh = jax.make_mesh((8,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))
rng = np.random.default_rng(0)
g_all = jnp.asarray(rng.normal(size=(8, 1000)), jnp.float32)

def spmd(g, e):
    out, e2 = compressed_psum(g[0], "data", 8, e[0])
    return out[None], e2[None]

f = shard_map(spmd, mesh=mesh, in_specs=(P("data"), P("data")),
              out_specs=(P("data"), P("data")), check_rep=False)
err0 = jnp.zeros((8, 1000), jnp.float32)
out, err = f(g_all, err0)
want = g_all.mean(0)
# every device must hold the same mean within int8 resolution
for d in range(8):
    rel = float(jnp.linalg.norm(out[d] - want) / jnp.linalg.norm(want))
    assert rel < 0.03, rel
# error feedback: the residual equals what quantization dropped
assert float(jnp.abs(err).max()) > 0
print("PSUM_OK", rel)

# error feedback compensates over repeated steps: accumulate means
acc_c = jnp.zeros((1000,)); acc_t = jnp.zeros((1000,)); e = err0
for step in range(40):
    g = jnp.asarray(rng.normal(size=(8, 1000)), jnp.float32)
    out, e = f(g, e)
    acc_c = acc_c + out[0]
    acc_t = acc_t + g.mean(0)
rel_acc = float(jnp.linalg.norm(acc_c - acc_t) / jnp.linalg.norm(acc_t))
assert rel_acc < 0.02, rel_acc   # EF keeps the accumulated bias tiny
print("EF_OK", rel_acc)
"""


def test_compressed_psum_correct_and_ef():
    out = run_with_devices(PSUM_CORRECT, n_devices=8)
    assert "PSUM_OK" in out and "EF_OK" in out


DDP_CONVERGES = """
import jax, jax.numpy as jnp
from repro.configs import get_config
from repro.data.pipeline import DataConfig, token_batch
from repro.optim import adamw
from repro.parallel import compression
from repro.runtime.trainer import make_ddp_compressed_step, make_train_step
from repro.models import lm

cfg = get_config("qwen3-14b-smoke").with_(n_layers=2, d_model=32, n_heads=2, n_kv_heads=1, head_dim=16, d_ff=64, vocab_size=32)
key = jax.random.PRNGKey(0)
params = lm.init_params(cfg, key)
opt_cfg = adamw.AdamWConfig(lr=5e-3, warmup_steps=3, total_steps=25)
dc = DataConfig(vocab_size=32, batch=8, seq_len=16)

mesh = jax.make_mesh((8,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))
step_c = make_ddp_compressed_step(cfg, opt_cfg, mesh)
opt = adamw.init(params)
err = compression.init_error_state(params)
p = params
losses = []
for s in range(25):
    b = token_batch(dc, s)
    p, opt, err, m = step_c(p, opt, err, b)
    losses.append(float(m["loss"]))

# baseline (uncompressed, single device)
step_b = jax.jit(make_train_step(cfg, opt_cfg))
p2, opt2 = params, adamw.init(params)
base = []
for s in range(25):
    b = token_batch(dc, s)
    p2, opt2, m = step_b(p2, opt2, b)
    base.append(float(m["loss"]))

assert losses[-1] < losses[0] * 0.9, (losses[0], losses[-1])
# compressed training tracks the uncompressed loss
assert abs(losses[-1] - base[-1]) / base[-1] < 0.15, (losses[-1], base[-1])
print("DDP_OK", losses[-1], base[-1])
"""


def test_ddp_compressed_training_converges():
    out = run_with_devices(DDP_CONVERGES, n_devices=8, timeout=900)
    assert "DDP_OK" in out
