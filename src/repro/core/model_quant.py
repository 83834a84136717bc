"""Whole-model VersaQ quantization (the paper's offline pipeline, Fig. 6).

Walks a full-precision parameter tree (models/lm.py or models/vggt.py
structure) and produces the quantized tree:

* the **residual stream is rotated once** at the embedding (E ← E·H, or the
  frontend in_proj / patch_proj gets H fused on its output side; sinusoidal
  position tables get an explicit rotation matrix) and *stays* rotated —
  paper Stage 4's "activations remain in the rotated domain";
* every pre-norm becomes a ``FoldedNorm`` (statistics-only, exact in the
  rotated domain) with its γ/β folded into **every** consumer (q/k/v,
  FFN up/gate, MoE router + shared + routed experts, Mamba in-proj);
* projections are fused per Eq. 7 (``Hᵀ·γ·W·Dᵀ``) and quantized to
  W4/W8 with per-channel scales;
* V/O projections carry the per-head Hadamard pair; LayerScale (VGGT)
  folds into the output projections (Eq. 6's "LayerScale handled
  analogously");
* hidden→down projections get the one mandatory **online** WHT
  (Fig. 5's WHT box);
* precision-sensitive islands stay bf16/f32: router logits, qk-norm,
  RoPE, Mamba Δ/B/C/conv/scan, RWKV decay LoRA + recurrence, all heads.

RWKV is the exception to stream rotation (token-shift lerp is
elementwise in the unrotated basis — DESIGN.md §Arch-applicability):
its stream stays unrotated and every projection uses the online-WHT path.

Baselines: ``method="rtn"`` disables all transforms, ``"quarot"``
disables only the DCT — same walker, same flow.

**Mixed precision**: both walkers accept a
``core.precision.plan.PrecisionPlan`` wherever they accept a uniform
``QuantPolicy``.  Every prepared projection carries a dotted *site* name
(``blocks.l0.mixer.wq``, ``frame.ffn.w_down`` — see ``core/precision``)
and the plan resolves each site to its own level: ``bf16`` sites get the
transform-fused full-precision dict (``prepare_linear_fp`` — they still
consume/produce the rotated stream and keep the V/O Hadamard pair
matched), quantized sites get a per-site ``QuantLinear`` at that level's
``(w_bits, a_bits)``.  Because site preparation depends only on the
site's own level (γ-folds and rotations are method-wide, not
bits-wide), a mixed tree is leaf-for-leaf identical to the uniform tree
of each site's level — the property the precision tests pin down.
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

import dataclasses

from repro.configs.base import ModelConfig
from repro.core import transforms
from repro.core.quantize import QTensor
from repro.core.versaq import (
    Epilogue,
    FoldedNorm,
    FusedFFN,
    Norm,
    Prologue,
    QuantLinear,
    QuantPolicy,
    make_folded_norm,
    prepare_linear,
    prepare_linear_fp,
    rotate_cols,
)
from repro.models import lm

_USE_WHT_METHODS = ("quarot", "versaq")


class _Resolver:
    """Uniform ``QuantPolicy`` or per-site ``PrecisionPlan`` behind one
    interface.  Duck-typed on ``policy_for`` (a plan) vs ``w_bits`` (a
    policy) so ``core.model_quant`` never imports ``core.precision`` (the
    planner imports this module for its proxy-error loop).

    ``fuse`` (plan field) turns on the unified-datapath fusion: dense FFN
    triples become :class:`FusedFFN` (one Pallas launch per layer), Q/K/V
    merge into one prologue-carrying ``wqkv`` site, and output projections
    run their IDCT/bias epilogues in-kernel.  Fusion implies kernel
    routing at the fused sites.

    A compiled ``KernelSchedule`` (``core/precision/compiler.py``, duck-
    typed on ``fuse_decision``) is also accepted: the embedded plan drives
    per-site levels exactly as before, but fusion decisions and kernel
    tiles are *read from the schedule* instead of re-derived inline —
    the walkers stop deciding and start executing."""

    def __init__(self, policy):
        self._schedule = None
        if hasattr(policy, "fuse_decision"):  # compiled KernelSchedule
            self._schedule = policy
            policy = policy.plan
        if hasattr(policy, "policy_for"):  # PrecisionPlan
            self._plan = policy
            self.method = policy.method
            self.fuse = bool(getattr(policy, "fuse", False))
            self.use_kernel = bool(getattr(policy, "use_kernel", False)) or self.fuse
        elif isinstance(policy, QuantPolicy):
            self._plan = None
            self._policy = policy
            self.method = policy.method
            self.fuse = False
            self.use_kernel = False
        else:
            raise TypeError(
                f"policy must be a QuantPolicy or PrecisionPlan, got {type(policy)!r}"
            )

    @property
    def use_wht(self) -> bool:
        return self.method in _USE_WHT_METHODS

    def at(self, site: str) -> Optional[QuantPolicy]:
        """The site's policy; None means bf16 passthrough."""
        if self._plan is None:
            return self._policy
        return self._plan.policy_for(site)

    def tiles_at(self, site: str) -> Optional[tuple]:
        """Compiled kernel tiles for a site (hashable tuple), or None to
        resolve tiles from the heuristic policy at trace time."""
        if self._schedule is None:
            return None
        return self._schedule.tiles_for(site)

    def fuse_decision(self, group: str):
        """None -> no schedule, decide fusion inline (legacy); else a
        ``(fuse: bool, group_entry)`` pair read from the schedule."""
        if self._schedule is None:
            return None
        return self._schedule.fuse_decision(group)


def _vmapped(fn, n_lead: int):
    """vmap ``fn`` over ``n_lead`` stacked leading axes (scan groups,
    experts)."""
    for _ in range(n_lead):
        fn = jax.vmap(fn)
    return fn


def _prep(w, pol: _Resolver, site: str, lead=0, **kw):
    """Per-site prepare (quantized or bf16-fused), vmapped over ``lead``
    stacked leading dims.

    Array kwargs (gamma/beta/bias/out_scale) must carry the same leading
    dims; None kwargs are closed over.
    """
    site_policy = pol.at(site)
    tiles = pol.tiles_at(site)
    arr_keys = [k for k in ("gamma", "beta", "bias", "out_scale") if kw.get(k) is not None]
    static_kw = {k: v for k, v in kw.items() if k not in arr_keys}

    def go(w_, *arrs):
        d = dict(zip(arr_keys, arrs))
        return _prepare_site(w_, pol, site_policy, tiles=tiles, site=site, **static_kw, **d)

    fn = _vmapped(go, lead)
    return fn(w, *[kw[k] for k in arr_keys])


def _prepare_site(w, pol: _Resolver, site_policy, *, out_scale=None, tiles=None, site=None, **kw):
    if out_scale is not None:
        w = w * out_scale[None, :]
        if kw.get("bias") is not None:
            kw["bias"] = kw["bias"] * out_scale
    if site_policy is None:  # bf16 passthrough site
        return prepare_linear_fp(w, use_wht=pol.use_wht, **kw)
    return prepare_linear(
        w, site_policy, use_kernel=pol.use_kernel, tiles=tiles, site=site, **kw
    )


def _fold_fp(w, gamma=None, beta=None, bias=None, rotate_in=False):
    """Fold γ/β/H into a full-precision (non-quantized) consumer — used for
    routers, heads, and the lm_head which stay fp but consume the rotated,
    γ-less norm output."""
    w = w.astype(jnp.float32)
    b = jnp.zeros((w.shape[-1],), jnp.float32) if bias is None else bias.astype(jnp.float32)
    has_b = bias is not None
    if beta is not None:
        b = b + beta.astype(jnp.float32) @ w
        has_b = True
    if gamma is not None:
        w = w * gamma.astype(jnp.float32)[..., :, None]
    if rotate_in:
        blk = transforms.block_size_for(w.shape[-2])
        h = transforms.hadamard_matrix(blk, dtype=jnp.float32)
        d_in = w.shape[-2]
        lead = w.shape[:-2]
        w = w.reshape(lead + (d_in // blk, blk, w.shape[-1]))
        w = jnp.einsum("cb,...bn->...cn", h, w).reshape(lead + (d_in, w.shape[-1]))
    return {"w": w, "b": b if has_b else None}


def _norm_g(n: Norm):
    return n.g


def _norm_b(n: Norm):
    return n.b


# ---------------------------------------------------------------------------
# unified-datapath fusion (kernels/fused.py descriptors)
# ---------------------------------------------------------------------------


# The fused kernels keep their weight panels VMEM-resident (they grid
# over tokens only — kernels/fused.py).  Panels above this budget cannot
# lower on a ~16MB-VMEM TPU core, so such layers stay on the per-site
# K-tiled path; shrinking the fused kernels' token tile doesn't help (the
# weight term dominates), K-tiling them is future work.
FUSED_PANEL_BUDGET = 8 * 1024 * 1024


def _panel_bytes(p: QuantLinear, groups) -> int:
    """Stored bytes of one layer's weight panel (int8/uint8 = 1 B/elem;
    stacked scan groups are sliced to one group per launch)."""
    return int(p.qw.values.size) // (groups or 1)


def _same_mode(parts) -> bool:
    """Sites that can share one kernel launch: all quantized, same
    activation/weight bits, same packing and online-op flags."""
    f = parts[0]
    return all(
        isinstance(p, QuantLinear)
        and p.a_bits == f.a_bits
        and p.qw.bits == f.qw.bits
        and p.qw.packed == f.qw.packed
        and p.idct == f.idct
        and p.dct_block == f.dct_block
        and p.rotate_input == f.rotate_input
        for p in parts
    )


def _zeros_bias(p: QuantLinear):
    return jnp.zeros(p.qw.values.shape[:-2] + (p.qw.values.shape[-1],), jnp.float32)


def _concat_sites(parts, *, prologue=None, norm_u=None, tiles=None) -> QuantLinear:
    """One QuantLinear over the output-concat of separately *prepared*
    sites (e.g. Q/K/V): they consume the same input, so the per-token
    activation quantization is computed once and the matmuls become one
    launch.  Because each site's weights/scales/bias were prepared
    independently and every per-site output width is DCT-block aligned,
    the concatenated site is numerically identical to the per-site flow.
    """
    f = parts[0]
    qw = QTensor(
        values=jnp.concatenate([p.qw.values for p in parts], axis=-1),
        scale=jnp.concatenate([p.qw.scale for p in parts], axis=-1),
        bits=f.qw.bits,
        packed=f.qw.packed,
        pack_axis=f.qw.pack_axis,
    )
    bias = None
    if any(p.bias is not None for p in parts):
        bias = jnp.concatenate(
            [p.bias if p.bias is not None else _zeros_bias(p) for p in parts],
            axis=-1,
        )
    # merged quant-health attribution: "….mixer.wq" -> "….mixer.wqkv"
    site = f.site.rsplit(".", 1)[0] + ".wqkv" if f.site else None
    return dataclasses.replace(
        f, qw=qw, bias=bias, use_kernel=True,
        prologue=prologue, epilogue=Epilogue(), norm_u=norm_u, tiles=tiles,
        site=site,
    )


def _norm_u_for(kind: str, dim: int, groups: int | None):
    """LayerNorm mean-recovery vector for a fused norm prologue (stacked
    for scan groups); None for RMSNorm."""
    u = make_folded_norm(kind, dim).u
    if u is not None and groups is not None:
        u = jnp.broadcast_to(u, (groups, dim))
    return u


def _fuse_qkv(
    mx: dict, mn_kind: str, d_model: int, groups, rotated: bool, decision=None,
    eps: float = 1e-6,
) -> dict:
    """Merge prepared wq/wk/wv into one ``wqkv`` site with a norm→quantize
    prologue, and move wo's IDCT/bias epilogue in-kernel.

    ``decision`` is None for the legacy inline eligibility checks, or the
    resolver's ``(fuse, group_entry)`` pair when a compiled schedule
    already settled the question (the entry carries the ``wo`` epilogue
    flag and the fused launch's tiles)."""
    parts = [mx["wq"], mx["wk"], mx["wv"]]
    tiles = None
    if decision is not None:
        fuse, entry = decision
        if not fuse:
            return mx
        wo_epi = entry.wo_epilogue
        tiles = entry.tiles
    else:
        if not _same_mode(parts):
            return mx  # mixed-precision Q/K/V (or bf16 islands): keep per-site
        if sum(_panel_bytes(p, groups) for p in parts) > FUSED_PANEL_BUDGET:
            return mx  # QKV panel would not fit VMEM-resident: keep per-site
        wo_epi = (
            isinstance(mx["wo"], QuantLinear)
            and _panel_bytes(mx["wo"], groups) <= FUSED_PANEL_BUDGET
        )
    pro = Prologue(norm=mn_kind, eps=eps) if rotated else None
    mx["wqkv"] = _concat_sites(
        parts,
        prologue=pro,
        norm_u=_norm_u_for(mn_kind, d_model, groups) if rotated else None,
        tiles=tiles,
    )
    for name in ("wq", "wk", "wv"):
        del mx[name]
    if wo_epi:
        mx["wo"] = dataclasses.replace(
            mx["wo"], use_kernel=True, epilogue=Epilogue()
        )
    return mx


def _fuse_ffn(
    f: dict, act: str, fn_kind: str, d_model: int, groups, rotated: bool, decision=None,
    eps: float = 1e-6,
):
    """Prepared dense-FFN dict -> :class:`FusedFFN` (one launch per layer)
    when every member site is quantized compatibly; else unchanged.
    ``decision`` as in :func:`_fuse_qkv`."""
    gate, up, down = f.get("w_gate"), f.get("w_up"), f.get("w_down")
    parts = [p for p in (gate, up, down) if p is not None]
    if decision is not None:
        if not decision[0]:
            return f
    else:
        if not all(isinstance(p, QuantLinear) for p in parts):
            return f
        if gate is not None and not _same_mode([gate, up]):
            return f  # gate/up share one quantized input: bits must agree
        if up.dct_block != down.dct_block:
            return f
        if sum(_panel_bytes(p, groups) for p in parts) > FUSED_PANEL_BUDGET:
            return f  # gate+up+down panels would not fit VMEM-resident
    gated_act = "silu" if act == "swiglu" else "gelu"
    return FusedFFN(
        w_up=up,
        w_down=down,
        w_gate=gate,
        norm_u=_norm_u_for(fn_kind, d_model, groups) if rotated else None,
        act=gated_act if gate is not None else ("gelu_erf" if act == "gelu_erf" else "gelu"),
        norm=fn_kind if rotated else None,
        norm_eps=eps,
    )


def quantize_lm(cfg: ModelConfig, params: dict, policy) -> dict:
    """Quantize an lm.py parameter tree with a uniform ``QuantPolicy`` or
    a per-site ``PrecisionPlan``.  Returns a new tree; the forward code is
    unchanged (dispatch happens on leaf types)."""
    pol = _Resolver(policy)
    rotated = pol.use_wht and "rwkv" not in cfg.pattern
    q = dict(params)

    # ---- stream entry: rotate the embedding / frontend output ----
    if rotated:
        emb = params["embed"]["w"].astype(jnp.float32)
        q["embed"] = {"w": rotate_cols(emb)}
        if cfg.embed_inputs and "in_proj" in params:
            ip = params["in_proj"]
            q["in_proj"] = {
                "w": rotate_cols(ip["w"].astype(jnp.float32)),
                "b": rotate_cols(ip["b"][None, :].astype(jnp.float32))[0]
                if ip.get("b") is not None
                else None,
            }
        if cfg.pos == "sincos":
            q["pos_rot"] = transforms.blocked_hadamard_matrix(cfg.d_model, dtype=jnp.float32)

    # ---- prefix layers (not stacked) + scanned groups (stacked) ----
    q["prefix"] = [
        _quantize_layer(
            cfg, lp, lm.mixer_kind(cfg, i), lm.ffn_kind(cfg, i), pol, rotated,
            lead=0, pfx=f"prefix.{i}",
        )
        for i, lp in enumerate(params["prefix"])
    ]
    period = len(cfg.pattern)
    blocks = dict(params["blocks"])
    for j in range(period):
        gi = cfg.first_dense + j
        blocks[f"l{j}"] = _quantize_layer(
            cfg, params["blocks"][f"l{j}"], lm.mixer_kind(cfg, gi), lm.ffn_kind(cfg, gi),
            pol, rotated, lead=1, pfx=f"blocks.l{j}",
        )
    q["blocks"] = blocks

    # ---- final norm + head ----
    fn: Norm = params["final_norm"]
    if rotated:
        q["final_norm"] = make_folded_norm(fn.kind, cfg.d_model)
        if "lm_head" in params:
            q["lm_head"] = _fold_fp(
                params["lm_head"]["w"], gamma=fn.g, beta=fn.b,
                bias=params["lm_head"].get("b"), rotate_in=True,
            )
    return q


def _quantize_layer(cfg, lp, kind, fk, pol: _Resolver, rotated, *, lead, pfx):
    out = dict(lp)
    mn: Norm = lp["mixer_norm"]
    fnm: Norm = lp["ffn_norm"]
    g1 = mn.g if rotated else None
    b1 = mn.b if rotated else None
    g2 = fnm.g if rotated else None
    b2 = fnm.b if rotated else None
    groups = int(mn.g.shape[0]) if lead else None
    if rotated:
        out["mixer_norm"] = _folded(mn.kind, cfg.d_model, groups, mn.eps)
        out["ffn_norm"] = _folded(fnm.kind, cfg.d_model, groups, fnm.eps)
    ls1 = lp.get("ls1")
    ls2 = lp.get("ls2")

    common = dict(rotate_in_offline=rotated, rotate_input_online=not rotated)

    if kind == "attn":
        mx = dict(lp["mixer"])
        if cfg.mla:
            mx["wq"] = _prep(lp["mixer"]["wq"]["w"], pol, f"{pfx}.mixer.wq", lead,
                             gamma=g1, beta=b1,
                             bias=lp["mixer"]["wq"].get("b"), **common)
            # kv_down: rotate the lora columns so the cache lives rotated
            wkv = lp["mixer"]["w_kv_down"]["w"]
            rank = cfg.kv_lora_rank
            kvdown_policy = pol.at(f"{pfx}.mixer.w_kv_down")

            def prep_kvdown(w_, *arrs):
                d = dict(zip([k for k, v in (("gamma", g1), ("beta", b1)) if v is not None], arrs))
                lora, rope = w_[:, :rank], w_[:, rank:]
                if pol.use_wht:
                    lora = rotate_cols(lora)
                w2 = jnp.concatenate([lora, rope], axis=1)
                if kvdown_policy is None:
                    return prepare_linear_fp(w2, use_wht=pol.use_wht, bias=None, **common, **d)
                return prepare_linear(w2, kvdown_policy, bias=None,
                                      use_kernel=pol.use_kernel,
                                      tiles=pol.tiles_at(f"{pfx}.mixer.w_kv_down"),
                                      **common, **d)

            arrs = [a for a in (g1, b1) if a is not None]
            mx["w_kv_down"] = _vmapped(prep_kvdown, lead)(wkv, *arrs)
            kvn: Norm = lp["mixer"]["kv_norm"]
            gkv = kvn.g if pol.use_wht else None
            if pol.use_wht:
                mx["kv_norm"] = _folded("rms", rank, groups)
            mx["w_k_up"] = _prep(lp["mixer"]["w_k_up"]["w"], pol, f"{pfx}.mixer.w_k_up",
                                 lead, gamma=gkv,
                                 rotate_in_offline=pol.use_wht, rotate_input_online=False)
            mx["w_v_up"] = _prep(lp["mixer"]["w_v_up"]["w"], pol, f"{pfx}.mixer.w_v_up",
                                 lead, gamma=gkv,
                                 rotate_in_offline=pol.use_wht, rotate_input_online=False,
                                 head_rot_out=(cfg.n_heads, cfg.v_head_dim))
            mx["wo"] = _prep(lp["mixer"]["wo"]["w"], pol, f"{pfx}.mixer.wo", lead,
                             bias=lp["mixer"]["wo"].get("b"), out_scale=ls1,
                             head_rot_in=(cfg.n_heads, cfg.v_head_dim),
                             rotate_out_offline=rotated)
        else:
            dh = cfg.head_dim
            for name in ("wq", "wk"):
                mx[name] = _prep(lp["mixer"][name]["w"], pol, f"{pfx}.mixer.{name}",
                                 lead, gamma=g1, beta=b1,
                                 bias=lp["mixer"][name].get("b"), **common)
            mx["wv"] = _prep(lp["mixer"]["wv"]["w"], pol, f"{pfx}.mixer.wv", lead,
                             gamma=g1, beta=b1,
                             bias=lp["mixer"]["wv"].get("b"),
                             head_rot_out=(cfg.n_kv_heads, dh), **common)
            mx["wo"] = _prep(lp["mixer"]["wo"]["w"], pol, f"{pfx}.mixer.wo", lead,
                             bias=lp["mixer"]["wo"].get("b"), out_scale=ls1,
                             head_rot_in=(cfg.n_heads, dh),
                             rotate_out_offline=rotated)
            if pol.fuse:
                mx = _fuse_qkv(mx, mn.kind, cfg.d_model, groups, rotated,
                               decision=pol.fuse_decision(f"{pfx}.mixer.wqkv"), eps=mn.eps)
        out["mixer"] = mx
        if ls1 is not None:
            out.pop("ls1", None)
    elif kind == "mamba":
        mx = dict(lp["mixer"])
        mx["w_in"] = _prep(lp["mixer"]["w_in"]["w"], pol, f"{pfx}.mixer.w_in", lead,
                           gamma=g1, beta=b1, **common)
        mx["w_out"] = _prep(lp["mixer"]["w_out"]["w"], pol, f"{pfx}.mixer.w_out", lead,
                            rotate_input_online=True, rotate_out_offline=rotated)
        out["mixer"] = mx  # Δ/B/C/conv/a_log stay fp (bf16 islands)
    elif kind == "rwkv":
        mx = dict(lp["mixer"])
        for name in ("wr", "wk", "wv", "wg", "wo"):
            mx[name] = _prep(lp["mixer"][name]["w"], pol, f"{pfx}.mixer.{name}",
                             lead, rotate_input_online=True)
        out["mixer"] = mx  # mu/decay LoRA/bonus/ln_x stay fp

    # ---- FFN ----
    if fk in ("dense", "dense_inner"):
        f = dict(lp["ffn"])
        for name in ("w_gate", "w_up"):
            if name in lp["ffn"]:
                f[name] = _prep(lp["ffn"][name]["w"], pol, f"{pfx}.ffn.{name}", lead,
                                gamma=g2, beta=b2,
                                bias=lp["ffn"][name].get("b"), **common)
        f["w_down"] = _prep(lp["ffn"]["w_down"]["w"], pol, f"{pfx}.ffn.w_down", lead,
                            bias=lp["ffn"]["w_down"].get("b"), out_scale=ls2,
                            rotate_input_online=True, rotate_out_offline=rotated)
        if pol.fuse:
            f = _fuse_ffn(f, cfg.act, fnm.kind, cfg.d_model, groups, rotated,
                          decision=pol.fuse_decision(f"{pfx}.ffn"), eps=fnm.eps)
        out["ffn"] = f
        if ls2 is not None:
            out.pop("ls2", None)
    elif fk == "moe":
        f = dict(lp["ffn"])
        # router stays fp but must absorb the folded γ/β + rotation
        rt = lp["ffn"]["router"]
        arrs = {k: v for k, v in (("gamma", g2), ("beta", b2), ("bias", rt.get("b"))) if v is not None}
        f["router"] = _vmapped(
            lambda w_, *a: _fold_fp(w_, **dict(zip(arrs.keys(), a)), rotate_in=rotated),
            lead,
        )(rt["w"], *arrs.values())
        ex = lp["ffn"]["experts"]
        nex = dict(ex)
        for name in ("w_gate", "w_up"):
            if name in ex:
                nex[name] = _prep(ex[name], pol, f"{pfx}.ffn.experts.{name}", lead + 1,
                                  gamma=_bcast(g2, cfg.n_experts), beta=_bcast(b2, cfg.n_experts),
                                  **common)
        nex["w_down"] = _prep(ex["w_down"], pol, f"{pfx}.ffn.experts.w_down", lead + 1,
                              rotate_input_online=True, rotate_out_offline=rotated)
        f["experts"] = nex
        if "shared" in lp["ffn"]:
            sh = dict(lp["ffn"]["shared"])
            for name in ("w_gate", "w_up"):
                if name in lp["ffn"]["shared"]:
                    sh[name] = _prep(lp["ffn"]["shared"][name]["w"], pol,
                                     f"{pfx}.ffn.shared.{name}", lead,
                                     gamma=g2, beta=b2, **common)
            sh["w_down"] = _prep(lp["ffn"]["shared"]["w_down"]["w"], pol,
                                 f"{pfx}.ffn.shared.w_down", lead,
                                 rotate_input_online=True, rotate_out_offline=rotated)
            f["shared"] = sh
        out["ffn"] = f
    elif fk == "rwkv_channel":
        f = dict(lp["ffn"])
        f["w_up"] = _prep(lp["ffn"]["w_up"]["w"], pol, f"{pfx}.ffn.w_up", lead,
                          rotate_input_online=True)
        f["w_down"] = _prep(lp["ffn"]["w_down"]["w"], pol, f"{pfx}.ffn.w_down", lead,
                            rotate_input_online=True)
        out["ffn"] = f
    return out


def _bcast(x, n):
    if x is None:
        return None
    return jnp.broadcast_to(x[..., None, :], x.shape[:-1] + (n, x.shape[-1]))


def _folded(kind: str, dim: int, groups: int | None, eps: float = 1e-6) -> FoldedNorm:
    """FoldedNorm whose LN mean-vector ``u`` is stacked for scan groups."""
    fn = make_folded_norm(kind, dim, eps)
    if fn.u is not None and groups is not None:
        fn = FoldedNorm(kind=fn.kind, u=jnp.broadcast_to(fn.u, (groups, dim)), eps=fn.eps)
    return fn


# ---------------------------------------------------------------------------
# VGGT
# ---------------------------------------------------------------------------


def quantize_vggt(cfg: ModelConfig, params: dict, policy) -> dict:
    """Quantize the VGGT tree (models/vggt.py) with a uniform
    ``QuantPolicy`` or a per-site ``PrecisionPlan``: rotated stream via the
    patch projection + rotated special tokens; AA blocks quantized per
    site with LayerScale folded; heads stay fp with final-norm fold.  Each
    norm keeps its own eps; a block's q/k norms (``q_norm``/``k_norm``,
    over head_dim after the projections) stay float ``Norm``s, and the
    value bias takes wv's per-head Hadamard with the weight."""
    pol = _Resolver(policy)
    rotated = pol.use_wht
    q = dict(params)
    if rotated:
        pp = params["patch_proj"]
        q["patch_proj"] = {
            "w": rotate_cols(pp["w"].astype(jnp.float32)),
            "b": rotate_cols(pp["b"][None, :].astype(jnp.float32))[0] if pp.get("b") is not None else None,
        }
        q["special_tokens"] = rotate_cols(params["special_tokens"].astype(jnp.float32))

    def quant_block(bp, pfx):
        an: Norm = bp["attn_norm"]
        fn: Norm = bp["ffn_norm"]
        g1, b1 = (an.g, an.b) if rotated else (None, None)
        g2, b2 = (fn.g, fn.b) if rotated else (None, None)
        common = dict(rotate_in_offline=rotated, rotate_input_online=not rotated)
        nb = dict(bp)
        groups = int(an.g.shape[0])
        if rotated:
            nb["attn_norm"] = _folded("ln", cfg.d_model, groups, an.eps)
            nb["ffn_norm"] = _folded("ln", cfg.d_model, groups, fn.eps)
        at = dict(bp["attn"])
        dh = cfg.head_dim
        for name in ("wq", "wk"):
            at[name] = _prep(bp["attn"][name]["w"], pol, f"{pfx}.attn.{name}", 1,
                             gamma=g1, beta=b1,
                             bias=bp["attn"][name].get("b"), **common)
        at["wv"] = _prep(bp["attn"]["wv"]["w"], pol, f"{pfx}.attn.wv", 1,
                         gamma=g1, beta=b1,
                         bias=bp["attn"]["wv"].get("b"), head_rot_out=(cfg.n_kv_heads, dh), **common)
        at["wo"] = _prep(bp["attn"]["wo"]["w"], pol, f"{pfx}.attn.wo", 1,
                         bias=bp["attn"]["wo"].get("b"),
                         out_scale=bp.get("ls1"), head_rot_in=(cfg.n_heads, dh),
                         rotate_out_offline=rotated)
        if pol.fuse:
            at = _fuse_qkv(at, an.kind, cfg.d_model, groups, rotated,
                           decision=pol.fuse_decision(f"{pfx}.attn.wqkv"), eps=an.eps)
        nb["attn"] = at
        ff = dict(bp["ffn"])
        for name in ("w_gate", "w_up"):
            if name in bp["ffn"]:
                ff[name] = _prep(bp["ffn"][name]["w"], pol, f"{pfx}.ffn.{name}", 1,
                                 gamma=g2, beta=b2,
                                 bias=bp["ffn"][name].get("b"), **common)
        ff["w_down"] = _prep(bp["ffn"]["w_down"]["w"], pol, f"{pfx}.ffn.w_down", 1,
                             bias=bp["ffn"]["w_down"].get("b"), out_scale=bp.get("ls2"),
                             rotate_input_online=True, rotate_out_offline=rotated)
        if pol.fuse:
            ff = _fuse_ffn(ff, cfg.act, fn.kind, cfg.d_model, groups, rotated,
                           decision=pol.fuse_decision(f"{pfx}.ffn"), eps=fn.eps)
        nb["ffn"] = ff
        nb.pop("ls1", None)
        nb.pop("ls2", None)
        return nb

    blocks = dict(params["blocks"])
    blocks["frame"] = quant_block(params["blocks"]["frame"], "frame")
    blocks["global"] = quant_block(params["blocks"]["global"], "global")
    q["blocks"] = blocks

    fn: Norm = params["final_norm"]
    if rotated:
        q["final_norm"] = make_folded_norm("ln", cfg.d_model, fn.eps)
        for head in ("camera_head", "dpt_head"):
            h = dict(params[head])
            h["fc1"] = _fold_fp(params[head]["fc1"]["w"], gamma=fn.g, beta=fn.b,
                                bias=params[head]["fc1"].get("b"), rotate_in=True)
            q[head] = h
    return q
