"""Feed-forward mixers: dense GLU/GELU and fine-grained MoE.

MoE is capacity-based with gather/scatter dispatch (no dense one-hot
matmuls, so compiled HLO FLOPs reflect *active* expert compute — this
keeps the roofline's MODEL_FLOPS/HLO_FLOPs ratio honest).  Experts shard
over the ``model`` mesh axis (EP); the combine is a scatter-add that GSPMD
turns into the standard EP all-reduce.  The router runs in f32
(a precision-sensitive nonlinearity, per the paper's BF16-island rule).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.versaq import FusedFFN, apply_ffn, carries_norm
from repro.models import layers as L


def init_dense_ffn(key, d_model: int, d_ff: int, act: str, dtype=jnp.float32):
    ks = jax.random.split(key, 3)
    if act in ("swiglu", "geglu"):
        return {
            "w_gate": L.init_linear(ks[0], d_model, d_ff, dtype=dtype),
            "w_up": L.init_linear(ks[1], d_model, d_ff, dtype=dtype),
            "w_down": L.init_linear(ks[2], d_ff, d_model, dtype=dtype),
        }
    return {
        "w_up": L.init_linear(ks[0], d_model, d_ff, bias=True, dtype=dtype),
        "w_down": L.init_linear(ks[1], d_ff, d_model, bias=True, dtype=dtype),
    }


def dense_ffn(p: dict, act: str, x: jnp.ndarray) -> jnp.ndarray:
    if isinstance(p, FusedFFN):
        # unified datapath: the whole layer (norm prologue when
        # ``carries_norm(p)`` — the caller passes the raw stream —
        # quantize, gate/up/down matmuls, act·gate, WHT, requant) is one
        # Pallas launch; see core/versaq.apply_ffn.
        return apply_ffn(p, x)
    if "w_gate" in p or (not isinstance(p, dict)):
        g = L.dense(p["w_gate"], x)
        u = L.dense(p["w_up"], x)
        h = (L.silu(g) if act == "swiglu" else L.gelu(g)) * u
        return L.dense(p["w_down"], h)
    h = (L.gelu_erf if act == "gelu_erf" else L.gelu)(L.dense(p["w_up"], x))
    return L.dense(p["w_down"], h)


def _ffn_keys(p: dict) -> bool:
    return "w_gate" in p


def init_moe(key, cfg: ModelConfig, dtype=jnp.float32):
    e = cfg.n_experts
    dff = cfg.moe_d_ff or cfg.d_ff
    d = cfg.d_model
    ks = jax.random.split(key, 5)
    glu = cfg.act in ("swiglu", "geglu")
    import math

    s = 1.0 / math.sqrt(d)
    experts = {
        "w_gate": (jax.random.normal(ks[0], (e, d, dff)) * s).astype(dtype),
        "w_up": (jax.random.normal(ks[1], (e, d, dff)) * s).astype(dtype),
        "w_down": (jax.random.normal(ks[2], (e, dff, d)) / math.sqrt(dff)).astype(dtype),
    }
    if not glu:
        experts.pop("w_gate")
    p = {"router": L.init_linear(ks[3], d, e, dtype=dtype), "experts": experts}
    if cfg.n_shared_experts:
        p["shared"] = init_dense_ffn(ks[4], d, cfg.n_shared_experts * dff, cfg.act, dtype=dtype)
    return p


def _moe_block(
    p: dict, cfg: ModelConfig, xt: jnp.ndarray, mask: jnp.ndarray | None = None
) -> jnp.ndarray:
    """Route one block of tokens [tb, d] through the top-k experts.

    ``mask`` [tb] bool marks *real* tokens.  Masked-out tokens (the
    serving engine's LEFT-pad slots) are excluded from routing entirely:
    they consume no expert capacity and contribute nothing to the
    combine, so real tokens keep exactly the slots they would get in the
    unpadded forward.  Capacity is likewise computed from the *real*
    token count (dynamically), matching the unpadded block's static cap
    whenever the real tokens fit one dispatch block.
    """
    tb, d = xt.shape
    e, k = cfg.n_experts, cfg.top_k

    def _cap(n):  # ceil(n·k·cf/e), cf quantized to quarters
        return -(-n * k * int(4 * cfg.capacity_factor) // (4 * e))

    cap = min(max(1, _cap(tb)), tb)  # static: buffer slots
    if mask is None:
        cap_eff = cap
    else:
        # pad tokens must not shrink nor grow capacity: use the formula
        # the unpadded forward would apply to the real-token count (both
        # terms are monotone in n, so cap_eff <= the static cap above)
        n_real = jnp.sum(mask.astype(jnp.int32))
        cap_eff = jnp.minimum(jnp.maximum(1, _cap(n_real)), n_real)

    logits = L.dense(p["router"], xt).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, idx = jax.lax.top_k(probs, k)  # [tb,k]
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)

    # capacity-based slotting: rank of each (token, expert) assignment
    flat_e = idx.reshape(-1)  # [tb*k]
    onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)
    if mask is not None:
        valid = jnp.repeat(mask, k)  # [tb*k]
        onehot = onehot * valid[:, None].astype(jnp.int32)  # pads rank-invisible
    rank = jnp.cumsum(onehot, axis=0) - 1  # running count per expert
    my_rank = jnp.take_along_axis(rank, flat_e[:, None], axis=1)[:, 0]
    keep = my_rank < cap_eff
    if mask is not None:
        keep = keep & valid
    token_id = jnp.repeat(jnp.arange(tb), k)
    slot = jnp.where(keep, my_rank, cap)  # overflow -> scratch slot

    # gather tokens into [e, cap+1, d] (last slot is the overflow bin)
    buf_idx = jnp.full((e, cap + 1), tb, jnp.int32)  # tb == zero pad row
    buf_idx = buf_idx.at[flat_e, slot].set(jnp.where(keep, token_id, tb))
    xt_pad = jnp.concatenate([xt, jnp.zeros((1, d), xt.dtype)], axis=0)
    xe = xt_pad[buf_idx.reshape(-1)].reshape(e, cap + 1, d)[:, :cap, :]

    glu = "w_gate" in p["experts"]

    def expert_mm(wn, xin):
        wexp = p["experts"][wn]
        if isinstance(wexp, jnp.ndarray):  # full-precision stacked experts
            return jnp.einsum("ecd,edf->ecf", xin.astype(jnp.float32), wexp.astype(jnp.float32))
        return jax.vmap(L.dense)(wexp, xin)  # VersaQ-quantized per-expert

    up = expert_mm("w_up", xe)
    if glu:
        g = expert_mm("w_gate", xe)
        h = (L.silu(g) if cfg.act == "swiglu" else L.gelu(g)) * up
    else:
        h = L.gelu(up)
    ye = expert_mm("w_down", h.astype(xt.dtype)).astype(jnp.float32)

    # combine: scatter-add back with gates
    out = jnp.zeros((tb + 1, d), jnp.float32)
    flat_slot_token = buf_idx[:, :cap].reshape(-1)  # [e*cap]
    ye_flat = ye.reshape(-1, d)
    gexp = jnp.zeros((e, cap + 1), jnp.float32)
    gexp = gexp.at[flat_e, slot].set(jnp.where(keep, gate.reshape(-1), 0.0))
    ye_flat = ye_flat * gexp[:, :cap].reshape(-1, 1)
    out = out.at[flat_slot_token].add(ye_flat)
    return out[:tb]


def moe_ffn(
    p: dict,
    cfg: ModelConfig,
    x: jnp.ndarray,
    token_mask: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Top-k routed experts + always-on shared experts (DeepSeekMoE §3).

    Dispatch runs in **token blocks** (``cfg.moe_dispatch_blocks``, auto by
    default): the rank cumsum and gather/scatter stay block-local, so with
    the block dim aligned to DP sharding GSPMD keeps dispatch AND expert
    compute sharded (data × experts) instead of replicating the global
    gather — see EXPERIMENTS.md §Perf (deepseek-moe train hillclimb).
    Block-local capacity also bounds worst-case routing skew.

    ``token_mask`` [B, L] bool marks real tokens; padded slots (bucketed
    serving) are excluded from routing and expert capacity, so real
    tokens route exactly as in the unpadded forward (per dispatch
    block).  Masked slots get only the shared-expert output, which the
    caller discards along with the rest of the padded positions.
    """
    b, l, d = x.shape
    t = b * l
    xt = x.reshape(t, d)
    mt = None if token_mask is None else token_mask.reshape(t).astype(bool)
    nb = cfg.moe_dispatch_blocks or max(1, t // 4096)
    while t % nb:
        nb -= 1
    if nb > 1:
        xb = xt.reshape(nb, t // nb, d)
        if mt is None:
            yb = jax.vmap(lambda xx: _moe_block(p, cfg, xx))(xb)
        else:
            mb = mt.reshape(nb, t // nb)
            yb = jax.vmap(lambda xx, mm: _moe_block(p, cfg, xx, mm))(xb, mb)
        y = yb.reshape(b, l, d).astype(x.dtype)
    else:
        y = _moe_block(p, cfg, xt, mt).reshape(b, l, d).astype(x.dtype)

    if "shared" in p:
        y = y + dense_ffn(p["shared"], cfg.act, x)
    return y


def moe_aux_loss(p: dict, cfg: ModelConfig, x: jnp.ndarray) -> jnp.ndarray:
    """Load-balancing auxiliary loss (Switch-style f·P)."""
    t = x.shape[0] * x.shape[1]
    logits = L.dense(p["router"], x.reshape(t, -1)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    _, idx = jax.lax.top_k(probs, cfg.top_k)
    counts = jnp.zeros((cfg.n_experts,), jnp.float32).at[idx.reshape(-1)].add(1.0)
    f = counts / jnp.maximum(counts.sum(), 1.0)
    pmean = probs.mean(axis=0)
    return cfg.n_experts * jnp.sum(f * pmean)
