"""GPipe pipeline parallelism demo: pipelined == sequential (subprocess)."""
from tests.helpers import run_with_devices

from repro.parallel.pipeline import bubble_fraction

PIPE = """
import jax, jax.numpy as jnp, numpy as np
from repro.parallel.pipeline import pipeline_apply

mesh = jax.make_mesh((4,), ("pipe",), axis_types=(jax.sharding.AxisType.Auto,))
S, B, D = 4, 8, 16
key = jax.random.PRNGKey(0)
w = jax.random.normal(key, (S, D, D)) * 0.3
x = jax.random.normal(jax.random.PRNGKey(1), (B, D))

def stage_fn(p, h):
    return jnp.tanh(h @ p)

# sequential reference
ref = x
for s in range(S):
    ref = stage_fn(w[s], ref)

got = pipeline_apply(mesh, stage_fn, w, x, n_micro=4)
np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-4, atol=1e-5)
print("PIPE_OK")
"""


def test_pipeline_matches_sequential():
    out = run_with_devices(PIPE, n_devices=4)
    assert "PIPE_OK" in out


def test_bubble_fraction():
    assert abs(bubble_fraction(4, 4) - 3 / 7) < 1e-9
    assert bubble_fraction(4, 28) < 0.1  # enough microbatches amortize
