"""Compile the serving path's Pallas kernels for a TPU v5e chip at VGGT-1B
widths, without a chip.

Each test lowers one kernel with ``interpret=False`` for one chip of a
``v5e:2x2`` topology that is described, not attached, and runs the TPU
compiler on it.  Nothing executes.  This catches what interpret mode
cannot: layouts Mosaic refuses (the fused IDCT epilogue, the int4 unpack)
and kernels that outgrow VMEM — the fused kernels keep their weight
panels resident, so ``FUSED_PANEL_BUDGET`` is checked here against what
the compiler accepts.

Token counts are a VGGT-1B scene's global attention length:
S frames × (1,369 patches + 5 special tokens), for S = 8 and 32, and for
a batch of eight 2-frame scenes.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core.model_quant import FUSED_PANEL_BUDGET, quantize_vggt
from repro.core.quantize import QTensor
from repro.kernels import ops
from repro.launch.specs import ServeSpec
from repro.models import vggt

FRAME_TOKENS = 1369 + 5
SCENE_TOKENS = [8 * FRAME_TOKENS, 32 * FRAME_TOKENS]  # 10,992 and 43,968


@pytest.fixture(scope="module")
def one_chip():
    """One described v5e chip, with JAX's persistent compilation cache off
    (a compile for a described chip is written to the cache but cannot be
    read back without one)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


def _abstract_block(policy: str, one_chip):
    """One global AA block of VGGT-1B prepared for ``policy``, as shapes
    placed on the described chip (the scan-group axis dropped)."""
    cfg = get_config("vggt-1b").with_(n_layers=1)
    plan = ServeSpec.parse(policy).materialize()
    tree = jax.eval_shape(
        lambda: quantize_vggt(cfg, vggt.init_params(cfg, jax.random.PRNGKey(0)), plan)
    )
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype, sharding=one_chip),
        tree["blocks"]["global"],
    )


@pytest.fixture(scope="module")
def w4a8_block(one_chip):
    return _abstract_block("w4a8:fused", one_chip)


def _spec(one_chip, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _compile(fn, *args) -> str:
    """Compile for the described chip; the program must hold the Mosaic
    kernel.  Returns the compiled HLO text."""
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


@pytest.mark.parametrize(
    "batch,length",
    [(1, SCENE_TOKENS[0]), (1, SCENE_TOKENS[1]), (8, FRAME_TOKENS), (8, 2 * FRAME_TOKENS)],
    ids=["global-S8", "global-S32", "frame", "global-S2-batch8"],
)
def test_two_stage_attention_compiles(one_chip, batch, length):
    qkv = _spec(one_chip, (batch, 16, length, 64))
    _compile(
        lambda q, k, v: ops.two_stage_mha(q, k, v, interpret=False), qkv, qkv, qkv
    )


@pytest.mark.parametrize("m", SCENE_TOKENS)
@pytest.mark.parametrize("site", ["wqkv", "wo"])
def test_fused_matmul_compiles(one_chip, w4a8_block, site, m):
    """wqkv: LN prologue + 1024→3072 packed-W4 matmul + IDCT; wo: the
    1024→1024 matmul with its IDCT/bias epilogue."""
    p = w4a8_block["attn"][site]
    assert p.idct and p.qw.packed
    _compile(
        lambda x, p: ops.fused_linear(x, p, interpret=False),
        _spec(one_chip, (m, 1024)), p,
    )


@pytest.mark.parametrize("m", SCENE_TOKENS)
def test_fused_ffn_compiles(one_chip, w4a8_block, m):
    """1024→4096→1024 with gelu, hidden WHT and requant, one launch."""
    _compile(
        lambda x, f: ops.fused_ffn_apply(x, f, interpret=False),
        _spec(one_chip, (m, 1024)), w4a8_block["ffn"],
    )


def test_fused_ffn_at_panel_budget_compiles(one_chip):
    """W8A8 weights make the FFN panel exactly ``FUSED_PANEL_BUDGET``, the
    largest the walker still fuses; Mosaic must accept it."""
    ffn = _abstract_block("w8a8:fused", one_chip)["ffn"]
    panel = ffn.w_up.qw.values.size + ffn.w_down.qw.values.size
    assert panel == FUSED_PANEL_BUDGET
    _compile(
        lambda x, f: ops.fused_ffn_apply(x, f, interpret=False),
        _spec(one_chip, (SCENE_TOKENS[1], 1024)), ffn,
    )


@pytest.mark.parametrize("m", SCENE_TOKENS)
def test_norm_quant_compiles(one_chip, m):
    u = _spec(one_chip, (1024,))
    _compile(
        lambda x, u: ops.norm_quant_prologue(
            x, norm="ln", norm_u=u, wht=True, interpret=False
        ).values,
        _spec(one_chip, (m, 1024)), u,
    )


@pytest.mark.parametrize("m", SCENE_TOKENS)
def test_packed_w4_quant_matmul_compiles(one_chip, m):
    wq = QTensor(
        values=_spec(one_chip, (512, 4096), jnp.uint8),
        scale=_spec(one_chip, (1, 4096)),
        bits=4, packed=True, pack_axis=0,
    )
    _compile(
        lambda x, w: ops.quant_linear_matmul(x, w, interpret=False),
        _spec(one_chip, (m, 1024)), wq,
    )


def test_vggt_forward_names_attention_launches_by_role(one_chip, monkeypatch):
    """A whole W4A8 VGGT-1B forward (one AA pair, two frames) compiled for
    the chip: each block's two-stage launches carry its role and stage,
    no launch keeps the bare ``two_stage_attention`` name, and the fused
    kernels keep theirs."""
    monkeypatch.setattr(ops, "_default_interpret", lambda: False)
    cfg = get_config("vggt-1b").with_(n_layers=1, attn_impl="two_stage")
    plan = ServeSpec.parse("w4a8:fused").materialize()
    tree = jax.eval_shape(
        lambda: quantize_vggt(cfg, vggt.init_params(cfg, jax.random.PRNGKey(0)), plan)
    )
    params = jax.tree.map(lambda a: _spec(one_chip, a.shape, a.dtype), tree)
    text = _compile(
        lambda p, x: vggt.forward(cfg, p, x), params, _spec(one_chip, (1, 2, 1369, 1024))
    )
    launches = {
        re.sub(r"\.\d+$", "", m)
        for m in re.findall(r"%?([\w.]+) = [^\n]*custom_call_target=\"tpu_custom_call\"", text)
    }
    roles = {f"two_stage_attention_{r}_{s}" for r in ("frame", "global") for s in ("stats", "out")}
    assert roles <= launches
    assert "two_stage_attention" not in launches
    assert {"fused_matmul", "fused_ffn"} <= launches
    assert 'op_name="jit(<lambda>)/while/body/closed_call/frame/' in text
    assert 'op_name="jit(<lambda>)/while/body/closed_call/global/' in text


@pytest.mark.parametrize("frames", [8, 16])
def test_qk_norm_rope_compiles(one_chip, frames):
    """Published VGGT's q/k LayerNorm → 2D RoPE → per-head WHT, reading q
    and k from the fused wqkv output of S frames of 1,374 tokens."""
    qkv = _spec(one_chip, (1, frames * FRAME_TOKENS, 3 * 1024))
    table = _spec(one_chip, (FRAME_TOKENS, 64))
    _compile(
        lambda x, c, s, n: ops.qk_norm_rope(x, c, s, n, n_heads=16, head_dim=64, eps=1e-5,
                                            role="global", interpret=False),
        qkv, table, table, _spec(one_chip, (4, 64)),
    )


def test_published_forward_names_qk_launches_by_role(one_chip, monkeypatch):
    """A whole W4A8 forward of VGGT-1B as published (one AA pair, two
    frames) compiled for the chip: the q/k kernel runs in both blocks under
    its role's name, beside the two-stage launches, and the fused FFN's
    exact-GELU epilogue lowers."""
    monkeypatch.setattr(ops, "_default_interpret", lambda: False)
    cfg = get_config("vggt-1b-published").with_(n_layers=1, attn_impl="two_stage")
    plan = ServeSpec.parse("w4a8:fused").materialize()
    tree = jax.eval_shape(
        lambda: quantize_vggt(cfg, vggt.init_params(cfg, jax.random.PRNGKey(0)), plan)
    )
    assert tree["blocks"]["global"]["ffn"].act == "gelu_erf"
    params = jax.tree.map(lambda a: _spec(one_chip, a.shape, a.dtype), tree)
    text = _compile(
        lambda p, x: vggt.forward(cfg, p, x), params, _spec(one_chip, (1, 2, 1369, 1024))
    )
    launches = {
        re.sub(r"\.\d+$", "", m)
        for m in re.findall(r"%?([\w.]+) = [^\n]*custom_call_target=\"tpu_custom_call\"", text)
    }
    assert {"qk_norm_rope_frame", "qk_norm_rope_global"} <= launches
    assert {"two_stage_attention_global_stats", "fused_ffn"} <= launches
