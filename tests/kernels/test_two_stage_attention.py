"""Two-stage recomputation attention kernel (paper Alg. 1) vs oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.quantize import quantize_per_token
from repro.kernels import ops, ref
from repro.kernels.two_stage_attention import (
    attention_stats,
    two_stage_attention,
    vmem_bytes_two_stage,
)
from repro.obs import trace as obs_trace

RNG = np.random.default_rng(3)


def _qkv(bh, l, dh):
    q = jnp.asarray(RNG.normal(size=(bh, l, dh)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(bh, l, dh)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(bh, l, dh)), jnp.float32)
    return q, k, v


def _quant(q, k, v):
    qq = quantize_per_token(q, 8)
    kq = quantize_per_token(k, 8)
    vs = jnp.max(jnp.abs(v), axis=(1, 2), keepdims=True) / 127.0
    vv = jnp.clip(jnp.round(v / vs), -127, 127).astype(jnp.int8)
    return qq, kq, vv, vs


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize(
    "bh,l,dh,bq,bk,bkv",
    [
        (1, 128, 64, 64, 64, 64),
        (2, 256, 64, 64, 64, 128),
        (1, 256, 128, 64, 64, 256),
        (4, 128, 64, 128, 64, 128),
        (1, 64, 64, 64, 64, 64),  # single tile in every grid dim
        (2, 192, 64, 48, 32, 96),  # mixed non-pow2 tiles, bkv < lk
        (1, 128, 32, 32, 64, 128),  # bq < bk, stage-2 mega-tile == lk
    ],
)
def test_exact_vs_int_oracle(causal, bh, l, dh, bq, bk, bkv):
    q, k, v = _qkv(bh, l, dh)
    qq, kq, vv, vs = _quant(q, k, v)
    want = ref.two_stage_attention_ref(
        qq.values, qq.scale, kq.values, kq.scale, vv, vs, causal=causal
    )
    got = two_stage_attention(
        qq.values, qq.scale.astype(jnp.float32), kq.values,
        kq.scale.astype(jnp.float32), vv, vs.astype(jnp.float32),
        causal=causal, bq=bq, bk=bk, bkv=bkv, interpret=True,
    )
    np.testing.assert_allclose(got, want.astype(jnp.float32), rtol=3e-4, atol=3e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_close_to_fp_attention(causal):
    b, h, l, dh = 1, 2, 256, 64
    q = jnp.asarray(RNG.normal(size=(b, h, l, dh)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(b, h, l, dh)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(b, h, l, dh)), jnp.float32)
    got = ops.two_stage_mha(q, k, v, causal=causal, bq=64, bk=64, bkv=128)
    fp = ref.attention_ref(q, k, v, causal=causal)
    rel = float(jnp.linalg.norm(got - fp) / jnp.linalg.norm(fp))
    assert rel < 0.05, rel  # int8 Q/K/V + int8 probabilities


def test_stats_match_flash_semantics():
    """Stage-① (M, Σ) equals the direct row max / softmax denominator."""
    bh, l, dh = 1, 128, 64
    q, k, v = _qkv(bh, l, dh)
    qq, kq, vv, vs = _quant(q, k, v)
    # run just the kernel's first stage via the public op and compare the
    # implied normalization: o_kernel == oracle already covers Σ; check M
    # indirectly by feeding a spiked row.
    qv = qq.values.at[0, 0].set(127)
    got = two_stage_attention(
        qv, qq.scale.astype(jnp.float32), kq.values, kq.scale.astype(jnp.float32),
        vv, vs.astype(jnp.float32), causal=False, bq=64, bk=64, bkv=64, interpret=True,
    )
    want = ref.two_stage_attention_ref(
        qv, qq.scale, kq.values, kq.scale, vv, vs, causal=False
    )
    np.testing.assert_allclose(got, want.astype(jnp.float32), rtol=3e-4, atol=3e-4)


# ---------------------------------------------------------------------------
# Model-path usage: non-causal global attention over VGGT token counts
# (S·(n_special+P) is not 64-divisible), per-head v_scale, divisor tiles.
# ---------------------------------------------------------------------------


def test_model_path_non_divisible_length_divisor_tiles():
    """ops.two_stage_mha on L = 4·(5+64) = 276 — the serving engine's
    global-attention length, with no 8-aligned divisor tile near 64 — pads
    to a tileable length and stays close to fp."""
    from repro.kernels.ops import divisor_tile

    b, h, l, dh = 1, 2, 276, 32
    assert divisor_tile(l, 64) == 46 and divisor_tile(l, 2048) == 276
    q = jnp.asarray(RNG.normal(size=(b, h, l, dh)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(b, h, l, dh)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(b, h, l, dh)), jnp.float32)
    got = ops.two_stage_mha(q, k, v, causal=False)
    fp = ref.attention_ref(q, k, v, causal=False)
    rel = float(jnp.linalg.norm(got - fp) / jnp.linalg.norm(fp))
    assert rel < 0.05, rel


def test_model_path_per_head_v_scale_applied():
    """Heads with very different V magnitudes must each come back at their
    own scale (the kernel's per-head v_scale multiply)."""
    bh, l, dh = 2, 128, 64
    q, k, v = _qkv(bh, l, dh)
    v = v.at[1].mul(37.0)  # second head's V 37x larger
    qq, kq, vv, vs = _quant(q, k, v)
    assert float(vs[1, 0, 0]) > 30 * float(vs[0, 0, 0])
    want = ref.two_stage_attention_ref(
        qq.values, qq.scale, kq.values, kq.scale, vv, vs, causal=False
    )
    got = two_stage_attention(
        qq.values, qq.scale.astype(jnp.float32), kq.values,
        kq.scale.astype(jnp.float32), vv, vs.astype(jnp.float32),
        causal=False, bq=64, bk=64, bkv=128, interpret=True,
    )
    np.testing.assert_allclose(got, want.astype(jnp.float32), rtol=3e-4, atol=3e-4)


def test_quantized_model_routes_global_attention_through_kernel(monkeypatch):
    """attn_impl="two_stage" + QuantLinear weights must actually hit the
    Pallas kernel wrapper (the serving fast path), and the result must
    stay close to the quantized model under flash attention."""
    import jax

    from repro.configs import get_config
    from repro.core.model_quant import quantize_vggt
    from repro.core.versaq import W4A8
    from repro.kernels import ops as kernel_ops
    from repro.models import vggt

    cfg = get_config("vggt-1b-smoke").with_(
        n_layers=1, d_model=64, n_heads=2, n_kv_heads=2, head_dim=32, d_ff=128,
        layerscale_init=0.2,
    )
    params = vggt.init_params(cfg, jax.random.PRNGKey(0))
    qp = quantize_vggt(cfg, params, W4A8)
    x = jnp.asarray(RNG.normal(size=(1, 2, 11, cfg.d_model)) * 0.3, jnp.float32)

    calls = []
    real = kernel_ops.two_stage_mha

    def spy(*a, **kw):
        calls.append(a[0].shape)
        return real(*a, **kw)

    monkeypatch.setattr(kernel_ops, "two_stage_mha", spy)
    got = vggt.forward(cfg.with_(attn_impl="two_stage"), qp, x)
    # frame [B·S, T] and global [B, S·T] attention, once per AA pair
    assert len(calls) == 2 * cfg.n_layers, calls
    want = vggt.forward(cfg, qp, x)
    rel = float(jnp.linalg.norm(got["points"] - want["points"])
                / jnp.linalg.norm(want["points"]))
    assert rel < 0.15, rel


def test_vmem_model_two_stage_smaller_than_flash():
    """The paper's claim: Stage-② needs no (m, l, rescale) carry, so at the
    same mega-tile size its VMEM working set is below the flash kernel's."""
    m = vmem_bytes_two_stage(bq=64, bk=64, bkv=2048, dh=64)
    assert m["stage1"] < m["flash_same_tiles"]
    assert m["stage2"] <= m["flash_same_tiles"] + 64 * 4  # no rescale carry


# ---------------------------------------------------------------------------
# GQA: shared K/V heads indexed inside the grid (no broadcast copy), and
# lane-padded lengths masked in-kernel via kv_len.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("h,hkv", [(4, 2), (4, 1), (8, 2)])
def test_gqa_shared_kv_heads_match_broadcast(causal, h, hkv):
    """ops.two_stage_mha with Hkv < H == the same call on K/V broadcast to
    the full head count — the kernel gathers the shared head per query
    head instead of materializing the copy."""
    b, l, dh = 2, 128, 64
    q = jnp.asarray(RNG.normal(size=(b, h, l, dh)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(b, hkv, l, dh)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(b, hkv, l, dh)), jnp.float32)
    got = ops.two_stage_mha(q, k, v, causal=causal)
    g = h // hkv
    want = ops.two_stage_mha(
        q, jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1), causal=causal
    )
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_lane_padded_length_masks_tail_keys(causal):
    """Odd / prime L (no healthy divisor tile) is lane-padded; the padded
    tail keys are masked in-kernel (kv_len), so the result matches fp
    attention on the real length."""
    b, h, l, dh = 1, 2, 101, 64  # prime L: old path degraded to tile=1
    q = jnp.asarray(RNG.normal(size=(b, h, l, dh)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(b, h, l, dh)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(b, h, l, dh)), jnp.float32)
    got = ops.two_stage_mha(q, k, v, causal=causal)
    assert got.shape == (b, h, l, dh)
    fp = ref.attention_ref(q, k, v, causal=causal)
    rel = float(jnp.linalg.norm(got - fp) / jnp.linalg.norm(fp))
    assert rel < 0.05, rel


def test_gqa_model_path_no_kv_broadcast():
    """gqa_attention's two_stage fast path serves GQA configs through the
    kernel and matches the jnp emulation."""
    from repro.configs import get_config
    from repro.core.model_quant import quantize_lm
    from repro.models import lm

    cfg = get_config("qwen3-14b-smoke")
    assert cfg.n_kv_heads < cfg.n_heads  # the point of the test
    key = jax.random.PRNGKey(0)
    params = lm.init_params(cfg, key)
    from repro.core.versaq import W4A8

    qp = quantize_lm(cfg, params, W4A8)
    toks = jnp.asarray(RNG.integers(0, cfg.vocab_size, (1, 64)), jnp.int32)
    k_cfg = cfg.with_(attn_impl="two_stage", attn_use_kernel=True)
    e_cfg = cfg.with_(attn_impl="two_stage", attn_use_kernel=False)
    got, _ = lm.forward(k_cfg, qp, toks)
    want, _ = lm.forward(e_cfg, qp, toks)
    rel = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
    assert rel < 0.05, rel


# ---------------------------------------------------------------------------
# Default tiles at VGGT-1B's lengths: a frame (1,369 patches + 5 special
# tokens), two frames (a batched 2-frame scene's global attention), 8 and
# 32 frames, and a short power of two.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "length,lp,bq,bk,bkv",
    [
        (256, 256, 256, 256, 256),
        (1374, 1408, 352, 1408, 1408),
        (2748, 2816, 352, 2816, 2816),
        (10992, 11264, 512, 2816, 2816),
        (43968, 44032, 512, 1024, 1024),
    ],
)
def test_default_tiles_at_vggt_lengths(length, lp, bq, bk, bkv):
    """Each token axis is padded once, to a multiple of 128 (11,008 would
    allow only 256-key tiles, so 10,992 goes to 11,264), by at most 3%;
    key tiles lie on the 128 lanes and every stage ① tile holds at least
    64K scores from 1,024 tokens up."""
    tiles, lqp, lkp = ops.attention_tiles(length, length)
    assert tiles == {"bq": bq, "bk": bk, "bkv": bkv}
    assert lqp == lkp == lp
    assert lp - length <= 0.03 * length
    assert lp % 128 == 0 and bk % 128 == 0 and bkv % 128 == 0 and bq % 8 == 0
    assert lp % bq == 0 and lp % bk == 0 and lp % bkv == 0
    if length >= 1024:
        assert bq * bk >= 64 * 1024


@pytest.mark.parametrize("l", [1374, 2748])
def test_default_tiles_match_fp_and_exact_row_max(l):
    """At a frame's and a 2-frame scene's length the default (padded)
    tiles stay within the fp tolerance, and stage ①'s row max over the
    padded keys equals the row max over the real keys bit for bit."""
    b, h, dh = 1, 2, 64
    q, k, v = (jnp.asarray(RNG.normal(size=(b, h, l, dh)), jnp.float32) for _ in range(3))
    got = ops.two_stage_mha(q, k, v)
    fp = ref.attention_ref(q, k, v, causal=False)
    rel = float(jnp.linalg.norm(got - fp) / jnp.linalg.norm(fp))
    assert rel < 0.05, rel

    tiles, lqp, lkp = ops.attention_tiles(l, l)
    qq = quantize_per_token(q.reshape(b * h, l, dh), 8)
    kq = quantize_per_token(k.reshape(b * h, l, dh), 8)
    qs, ks = qq.scale.astype(jnp.float32), kq.scale.astype(jnp.float32)

    def pad(x, n, fill=0):
        return jnp.pad(x, ((0, 0), (0, n - l), (0, 0)), constant_values=fill)

    m, _ = attention_stats(
        pad(qq.values, lqp), pad(qs, lqp, 1), pad(kq.values, lkp), pad(ks, lkp, 1),
        bq=tiles["bq"], bk=tiles["bk"], kv_len=l, interpret=True,
    )
    s_int = jnp.einsum(
        "bqd,bkd->bqk", qq.values.astype(jnp.int32), kq.values.astype(jnp.int32)
    )
    want = (s_int.astype(jnp.float32) * qs * jnp.swapaxes(ks, 1, 2) * (1.0 / dh**0.5)).max(
        -1, keepdims=True
    )
    np.testing.assert_array_equal(np.asarray(m[:, :l]), np.asarray(want))


def test_attn_tiles_event_once_per_compile():
    """One ``attn.tiles`` event per traced ``two_stage_mha``: the resolved
    tiles, the padded lengths and stage ①'s grid steps; a second call of
    the compiled function emits nothing."""
    b, h, l, dh = 1, 2, 300, 32
    q = jnp.asarray(RNG.normal(size=(b, h, l, dh)), jnp.float32)
    f = jax.jit(lambda q, k, v: ops.two_stage_mha(q, k, v, role="global"))
    tracer = obs_trace.Tracer()
    prev = obs_trace.install(tracer)
    try:
        f(q, q, q)
        f(q, q, q)
    finally:
        obs_trace.install(prev)
    events = [e for e in tracer.recent() if e.phase == "attn.tiles"]
    tiles, lqp, lkp = ops.attention_tiles(l, l)
    steps = b * h * (lqp // tiles["bq"]) * (lkp // tiles["bk"])
    assert [e.labels for e in events] == [
        {"role": "global", **tiles, "lqp": lqp, "lkp": lkp, "stage1_steps": steps}
    ]


def test_vggt_forward_emits_attn_tiles_per_role():
    """A traced VGGT forward emits one ``attn.tiles`` event per role: the
    24-layer scan traces each block's attention once."""
    from repro.configs import get_config
    from repro.core.model_quant import quantize_vggt
    from repro.core.versaq import W4A8
    from repro.models import vggt

    cfg = get_config("vggt-1b-smoke").with_(
        n_layers=2, d_model=64, n_heads=2, n_kv_heads=2, head_dim=32, d_ff=128,
        attn_impl="two_stage",
    )
    qp = quantize_vggt(cfg, vggt.init_params(cfg, jax.random.PRNGKey(0)), W4A8)
    x = jax.ShapeDtypeStruct((1, 3, 11, cfg.d_model), jnp.float32)
    tracer = obs_trace.Tracer()
    prev = obs_trace.install(tracer)
    try:
        jax.eval_shape(lambda p, x: vggt.forward(cfg, p, x), qp, x)
    finally:
        obs_trace.install(prev)
    events = [e.labels for e in tracer.recent() if e.phase == "attn.tiles"]
    t = 11 + cfg.n_special_tokens
    assert sorted((e["role"], e["lqp"]) for e in events) == [
        ("frame", ops.attention_tiles(t, t)[1]),
        ("global", ops.attention_tiles(3 * t, 3 * t)[1]),
    ]
