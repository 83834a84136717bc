"""VGGT-1B as published (``vggt-1b-published``): 2D RoPE, the q/k
LayerNorm, first-frame special tokens and exact GELU, each against a plain
transcription of facebookresearch/vggt (``vggt/layers/rope.py``,
``block.py``, ``attention.py``; ``vggt/models/aggregator.py``), the q/k
kernel against its jnp twin, and the value bias under the per-head
Hadamard."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from scipy.special import erf

from repro.configs import get_config
from repro.core import versaq as V
from repro.core.model_quant import quantize_vggt
from repro.core.precision import PrecisionPlan
from repro.kernels import ops
from repro.models import layers as L
from repro.models import vggt

KEY = jax.random.PRNGKey(3)


def _cfg(**kw):
    return get_config("vggt-1b-published-smoke").with_(**{"n_layers": 1,
                                                          "layerscale_init": 0.2, **kw})


def _drawn(params, key, std=0.1):
    """Every bias, norm γ/β (the q/k norms' too) and special token moved
    off its init, as in trained weights: biases and β non-zero, γ off 1."""
    leaves, tree = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(key, len(leaves))
    moved = [a + std * jax.random.normal(k, a.shape, a.dtype)
             if str(path[-1]) in (".g", ".b", "['b']", "['special_tokens']") else a
             for (path, a), k in zip(leaves, keys)]
    return jax.tree.unflatten(jax.tree.structure(params), moved)


# ---- a plain transcription of the published model --------------------------


def _positions(rows, cols, n_special):
    """aggregator.py: PositionGetter's (y, x) + 1, special tokens at (0, 0)."""
    pos = [(y + 1, x + 1) for y in range(rows) for x in range(cols)]
    return np.asarray([(0, 0)] * n_special + pos, np.float64)


def _rope_1d(tokens, positions, base):
    """rope.py: _compute_frequency_components + _apply_1d_rope, one axis."""
    dim = tokens.shape[-1]
    inv_freq = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    angles = positions[:, None] * inv_freq[None, :]
    angles = np.concatenate([angles, angles], axis=-1)  # [t, dim]
    x1, x2 = tokens[..., : dim // 2], tokens[..., dim // 2 :]
    rotated = np.concatenate([-x2, x1], axis=-1)  # _rotate_features
    return tokens * np.cos(angles)[:, None, :] + rotated * np.sin(angles)[:, None, :]


def _rope_2d(tokens, pos, base):
    """rope.py: RotaryPositionEmbedding2D.forward on [t, H, dh]."""
    v, h = np.split(tokens, 2, axis=-1)
    return np.concatenate([_rope_1d(v, pos[:, 0], base), _rope_1d(h, pos[:, 1], base)], axis=-1)


def _ln(x, g, b, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / np.sqrt(var + eps) * g + b


def _gelu(x):
    return 0.5 * x * (1 + erf(x / math.sqrt(2)))


def _np(a):
    return np.asarray(a, np.float64)


def _block(p, x, pos, valid, cfg):
    """block.py + attention.py on one sequence x [L, d]; ``valid`` [L]
    marks the keys attention may see."""
    eps, nh, dh = cfg.norm_eps, cfg.n_heads, cfg.head_dim
    at = p["attn"]
    h = _ln(x, _np(p["attn_norm"].g), _np(p["attn_norm"].b), eps)
    q, k, v = (h @ _np(at[n]["w"]) + _np(at[n]["b"]) for n in ("wq", "wk", "wv"))
    q, k, v = (u.reshape(len(x), nh, dh) for u in (q, k, v))
    q = _ln(q, _np(at["q_norm"].g), _np(at["q_norm"].b), eps)
    k = _ln(k, _np(at["k_norm"].g), _np(at["k_norm"].b), eps)
    q, k = _rope_2d(q, pos, cfg.rope_theta), _rope_2d(k, pos, cfg.rope_theta)
    s = np.einsum("qhd,khd->hqk", q, k) / math.sqrt(dh)
    s = np.where(valid[None, None, :], s, -np.inf)
    a = np.exp(s - s.max(-1, keepdims=True))
    a /= a.sum(-1, keepdims=True)
    o = np.einsum("hqk,khd->qhd", a, v).reshape(len(x), nh * dh)
    x = x + _np(p["ls1"]) * (o @ _np(at["wo"]["w"]) + _np(at["wo"]["b"]))
    h = _ln(x, _np(p["ffn_norm"].g), _np(p["ffn_norm"].b), eps)
    f = p["ffn"]
    h = _gelu(h @ _np(f["w_up"]["w"]) + _np(f["w_up"]["b"]))
    return x + _np(p["ls2"]) * (h @ _np(f["w_down"]["w"]) + _np(f["w_down"]["b"]))


def _published_tokens(cfg, params, patches, grid, frame_mask):
    """aggregator.py's alternating frame/global blocks over [S, P, d] of one
    scene; returns the final tokens [S, T, d] (before the stub heads)."""
    s, p_, d = patches.shape
    ns = cfg.n_special_tokens
    x = _np(patches) @ _np(params["patch_proj"]["w"]) + _np(params["patch_proj"]["b"])
    sp = _np(params["special_tokens"])  # [2, ns, d]: slice_expand_and_flatten
    spec = np.concatenate([sp[:1], np.repeat(sp[1:2], s - 1, axis=0)])
    x = np.concatenate([spec, x], axis=1)
    t = ns + p_
    pos = _positions(*grid, ns)
    valid = np.repeat(np.asarray(frame_mask, bool), t)
    for layer in range(cfg.n_layers):
        pf, pg = (jax.tree.map(lambda a: a[layer], params["blocks"][r]) for r in ("frame", "global"))
        x = np.stack([_block(pf, x[i], pos, np.ones(t, bool), cfg) for i in range(s)])
        x = _block(pg, x.reshape(s * t, d), np.tile(pos, (s, 1)), valid, cfg).reshape(s, t, d)
    fn = params["final_norm"]
    return _ln(x, _np(fn.g), _np(fn.b), cfg.norm_eps)


# ---- tests -------------------------------------------------------------------


@pytest.mark.parametrize("grid", [(4, 4), (3, 5)])
def test_rope2d_matches_the_published_rotary_embedding(grid):
    """2D RoPE (layers.rope2d_table + apply_rope2d) against rope.py per
    half: row positions on the first half, columns on the second, +1 for
    patches, 0 for the 5 special tokens; a 2-frame sequence reuses the
    frame's positions."""
    ns, dh, h = 5, 64, 3
    t = ns + grid[0] * grid[1]
    x = np.random.default_rng(0).normal(size=(1, 2 * t, h, dh))
    cos, sin = L.rope2d_table(grid, ns, dh, 100.0)
    got = L.apply_rope2d(jnp.asarray(x, jnp.float32), cos, sin)
    pos = _positions(*grid, ns)
    want = np.concatenate([_rope_2d(x[0, f * t : (f + 1) * t], pos, 100.0) for f in (0, 1)])
    np.testing.assert_allclose(np.asarray(got[0]), want, rtol=1e-5, atol=1e-5)
    # special tokens sit at position 0: untouched
    np.testing.assert_allclose(np.asarray(got[0, :ns]), x[0, :ns], atol=1e-6)


@pytest.mark.parametrize("heads,dh", [(2, 64), (4, 64), (4, 32)])
@pytest.mark.parametrize("frames_per_seq", [1, 3], ids=["frame", "global"])
def test_qk_norm_rope_kernel_matches_its_jnp_twin(heads, dh, frames_per_seq):
    """The q/k kernel (interpret mode) against the model's jnp path:
    per-head LayerNorm (γ, β shared by heads) → 2D RoPE → per-head WHT, on
    q and k read from one fused qkv array."""
    rng = np.random.default_rng(heads * dh + frames_per_seq)
    grid, ns = (4, 4), 5
    t = ns + 16
    n = 6 // frames_per_seq
    hd = heads * dh
    qkv = jnp.asarray(rng.normal(size=(n, frames_per_seq * t, 3 * hd)) * 2 + 0.5, jnp.float32)
    norms = jnp.asarray(rng.normal(size=(4, dh)) * 0.3 + np.array([[1], [0], [1], [0]]),
                        jnp.float32)
    cos, sin = L.rope2d_table(grid, ns, dh, 100.0)
    q, k = ops.qk_norm_rope(qkv, cos, sin, norms, n_heads=heads, head_dim=dh, eps=1e-5,
                            role="global", interpret=True)
    for i, got in enumerate((q, k)):
        x = qkv[..., i * hd : (i + 1) * hd].reshape(n, -1, heads, dh)
        norm = V.Norm(g=norms[2 * i], b=norms[2 * i + 1], kind="ln", eps=1e-5)
        want = V.head_wht(L.apply_rope2d(L.norm(norm, x), cos, sin))
        np.testing.assert_allclose(np.asarray(got).reshape(want.shape), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)


def test_float_forward_matches_the_published_model():
    """The float ``vggt.forward`` of ``vggt-1b-published-smoke`` against a
    plain transcription of the published block: QK-norm, 2D RoPE, the
    first frame's special tokens, exact GELU, and a padded frame masked
    out of global attention."""
    cfg = _cfg(n_layers=2)
    params = _drawn(vggt.init_params(cfg, KEY), jax.random.PRNGKey(4))
    s, grid = 3, (4, 4)
    patches = jax.random.normal(jax.random.PRNGKey(5), (1, s, 16, cfg.d_model))
    frame_mask = jnp.asarray([[True, True, False]])
    with jax.default_matmul_precision("highest"):
        got = vggt.forward(cfg, params, patches, frame_mask=frame_mask)["tokens"]
    want = _published_tokens(cfg, params, patches[0], grid, frame_mask[0])
    np.testing.assert_allclose(np.asarray(got[0, :2]), want[:2], rtol=2e-4, atol=2e-4)
    # the first frame's special tokens are its own: one set departs
    one = dict(params, special_tokens=params["special_tokens"][1])
    with jax.default_matmul_precision("highest"):
        other = vggt.forward(cfg, one, patches, frame_mask=frame_mask)["tokens"]
    assert float(jnp.max(jnp.abs(other[0, 0] - got[0, 0]))) > 1e-2


def test_erf_and_exact_gelu_epilogue():
    """``erf_f32`` is within 1.2e-7 of erf; the fused FFN kernel's
    ``gelu_erf`` epilogue matches its jnp emulation."""
    z = np.linspace(-6, 6, 200001).astype(np.float32)
    assert np.abs(np.asarray(V.erf_f32(jnp.asarray(z))) - erf(z.astype(np.float64))).max() < 1.5e-7
    y = jnp.linspace(-5, 5, 1001)
    np.testing.assert_allclose(V._act_fn(y, "gelu_erf"), _gelu(np.asarray(y, np.float64)),
                               atol=1e-6)
    cfg = _cfg()
    params = _drawn(vggt.init_params(cfg, KEY), jax.random.PRNGKey(6))
    fused = quantize_vggt(cfg, params, PrecisionPlan(default="w4a8", fuse=True))
    ffn = jax.tree.map(lambda a: a[0], fused["blocks"]["frame"]["ffn"])
    assert isinstance(ffn, V.FusedFFN) and ffn.act == "gelu_erf"
    x = jax.random.normal(KEY, (40, cfg.d_model))
    kernel = V.apply_ffn(ffn, x)
    emulated = V.apply_ffn(
        dataclasses.replace(ffn, w_up=dataclasses.replace(ffn.w_up, use_kernel=False)), x)
    np.testing.assert_allclose(np.asarray(kernel), np.asarray(emulated), rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("policy", ["w16a16", "w4a8"])
def test_value_bias_rotates_with_wv(policy):
    """With the attention pre-norm's β and the value bias drawn non-zero,
    the quantized program tracks the float model: losslessly at 16 bits
    (the bias takes wv's per-head Hadamard), and within the quantized
    engine's tolerance (rel < 0.25, tests/serving/test_vggt_engine.py)
    at W4A8."""
    cfg = get_config("vggt-1b-smoke").with_(n_layers=1, layerscale_init=0.2, attn_bias=True)
    params = _drawn(vggt.init_params(cfg, KEY), jax.random.PRNGKey(7), std=0.2)
    assert float(jnp.min(jnp.abs(params["blocks"]["frame"]["attn"]["wv"]["b"]))) > 0
    pol = V.QuantPolicy(16, 16) if policy == "w16a16" else V.W4A8
    x = jax.random.normal(KEY, (1, 2, 24, cfg.d_model))
    ref = vggt.forward(cfg, params, x)
    got = vggt.forward(cfg, quantize_vggt(cfg, params, pol), x)
    for k in ("points", "pose"):
        rel = float(jnp.linalg.norm(got[k] - ref[k]) / jnp.linalg.norm(ref[k]))
        assert rel < (5e-3 if policy == "w16a16" else 0.25), (k, rel)
