"""VersaQ-3D quantization flow (paper §III, Fig. 5/6).

Implements the paper's computation flow in JAX, generalized to every
architecture in the assigned pool:

* **Offline weight preparation** (Fig. 6):  ``W_final ← Hᵀ·γ·W·D`` —
  Hadamard on the input side (computational invariance with the rotated
  residual stream, Eq. 4-7), the preceding norm's γ folded in (Eq. 6), the
  DCT on the output side for structural weight preservation (Eq. 7), then
  symmetric W4/W8 quantization with per-output-channel scales.

* **Online activation processing** (Fig. 5): residual stream lives
  permanently in the rotated (WHT) domain; per-token dynamic A4/A8
  quantization before each integer matmul; block IDCT after each matmul to
  cancel the offline DCT; nonlinears (norm stats, RoPE, softmax, GLU,
  router) in bf16 — exactly the paper's Stage-1..4 pipeline.

* **Per-head rotations**: V-projection output / O-projection input carry a
  fused per-head Hadamard (offline, free); Q and K receive an *online*
  per-head WHT after RoPE (paper Stage 2) — scores are invariant because
  (qH)(kH)ᵀ = qkᵀ — which smooths Q/K for INT quantization and makes the
  int8 KV cache accurate.

Conventions (all matrices orthonormal, blocked block-diagonally):
  rotated residual:   x' = x·H            (H = Hᵀ, H·H = I per block)
  DCT domain output:  ŷ = y·Dᵀ  ⇒  online IDCT: y = ŷ·D

Baselines implemented for the paper's comparisons: ``rtn`` (no transforms)
and ``quarot`` (Hadamard only, no DCT).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.core import transforms
from repro.core.quantize import (
    QTensor,
    quantize_per_token,
    quantize_weight,
)

__all__ = [
    "QuantPolicy",
    "QuantLinear",
    "FoldedNorm",
    "Prologue",
    "Epilogue",
    "FusedFFN",
    "apply_linear",
    "apply_norm",
    "apply_ffn",
    "carries_norm",
    "prepare_linear",
    "prepare_linear_fp",
    "online_wht",
    "W4A8",
    "W4A4",
    "W8A8",
]

DCT_BLOCK = 64


@dataclasses.dataclass(frozen=True)
class QuantPolicy:
    """Which bits + which transforms. method ∈ {rtn, quarot, versaq}."""

    w_bits: int = 4
    a_bits: int = 8
    method: str = "versaq"

    @property
    def use_wht(self) -> bool:
        return self.method in ("quarot", "versaq")

    @property
    def use_dct(self) -> bool:
        return self.method == "versaq"

    @property
    def name(self) -> str:
        return f"{self.method}-w{self.w_bits}a{self.a_bits}"


W8A8 = QuantPolicy(8, 8, "versaq")
W4A8 = QuantPolicy(4, 8, "versaq")
W4A4 = QuantPolicy(4, 4, "versaq")


@dataclasses.dataclass(frozen=True)
class Prologue:
    """Unified-datapath prologue descriptor (static, hashable): fold the
    preceding norm's *statistics* into the site's kernel launch.  The norm
    runs in FoldedNorm semantics (γ/β already live in the weights); an
    ``ln`` prologue needs the mean-recovery vector in
    ``QuantLinear.norm_u``.  The site's ``rotate_input`` WHT and the
    activation quantization always join the fused pass."""

    norm: Optional[str] = None  # None | rms | ln
    eps: float = 1e-6


@dataclasses.dataclass(frozen=True)
class Epilogue:
    """Unified-datapath epilogue descriptor (static, hashable): nonlinear
    work emitted inside the kernel's finalize step, after the IDCT/bias
    the site already carries — activation function, blocked WHT toward the
    next consumer, and optional re-quantization to INT8/INT4 (per-token
    scales), which makes the kernel emit integer activations directly."""

    act: str = "none"  # none | gelu | gelu_erf | silu
    wht: bool = False
    requant_bits: Optional[int] = None


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class QuantLinear:
    """A quantized linear layer in the VersaQ flow.

    ``qw`` holds the fully fused+quantized weight.  Static flags describe
    the *online* ops this layer still needs:

    - ``rotate_input``: apply a blocked WHT to x before quantizing (used
      where the producer couldn't be fused, e.g. the FFN hidden -> down
      projection, paper Fig. 5 "WHT" box).
    - ``idct``: apply the block IDCT to the output (cancels the offline D).
    - ``prologue``/``epilogue``: unified-datapath fusion descriptors — with
      ``use_kernel`` set they route the site through the one-launch
      ``kernels.fused`` path (norm → WHT → quantize → int matmul → IDCT →
      bias → act → WHT → requant, all in VMEM); without a kernel the same
      op order runs as the jnp emulation, so numerics don't depend on the
      backend.  ``norm_u`` carries the LayerNorm mean-recovery vector for
      an ``ln`` prologue.
    """

    qw: QTensor
    bias: Optional[jnp.ndarray] = None
    a_bits: int = dataclasses.field(metadata=dict(static=True), default=8)
    rotate_input: bool = dataclasses.field(metadata=dict(static=True), default=False)
    idct: bool = dataclasses.field(metadata=dict(static=True), default=False)
    dct_block: int = dataclasses.field(metadata=dict(static=True), default=DCT_BLOCK)
    # Route the integer matmul through the Pallas kernel
    # (kernels/quant_matmul: int8 MXU path or packed-int4 path) instead of
    # the jnp emulation.  Numerics are identical; the kernel is the TPU hot
    # path, the emulation the portable/autodiff path.
    use_kernel: bool = dataclasses.field(metadata=dict(static=True), default=False)
    prologue: Optional[Prologue] = dataclasses.field(
        metadata=dict(static=True), default=None
    )
    epilogue: Optional[Epilogue] = dataclasses.field(
        metadata=dict(static=True), default=None
    )
    norm_u: Optional[jnp.ndarray] = None
    # Compiled-schedule tiles for the kernel launch, as a hashable
    # ``(("bn", n), ("bk", k), ("bm_target", m))`` tuple (see
    # ``core/precision/compiler.py``).  None = resolve tiles from the
    # heuristic policy at trace time; static, so it never adds a leaf.
    tiles: Optional[tuple] = dataclasses.field(metadata=dict(static=True), default=None)
    # Dotted PrecisionPlan site path ("blocks.l0.ffn.w_down", ...) — the
    # attribution key for quant-health telemetry (obs/quant_health.py).
    # Static: it's an identity, not data, and must survive jit tracing.
    site: Optional[str] = dataclasses.field(metadata=dict(static=True), default=None)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class Norm:
    """Plain (unquantized) norm: γ (+β), kind ∈ {rms, ln}."""

    g: jnp.ndarray
    b: Optional[jnp.ndarray] = None
    kind: str = dataclasses.field(metadata=dict(static=True), default="rms")
    eps: float = dataclasses.field(metadata=dict(static=True), default=1e-6)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class FoldedNorm:
    """Marker for a norm whose γ (and β) were folded into downstream weights.

    The norm *statistics* still run online (bf16), in the rotated domain:

    - RMSNorm: orthonormal rotation preserves ‖x‖₂, so plain x/rms(x) is
      exact in the rotated domain.
    - LayerNorm: the mean is recovered via the precomputed vector
      ``u = Hᵀ1/d`` (nonzero only at block-leading coordinates) and the
      variance from E[x²] − μ², both rotation-invariant.

    β (if any) is folded into the downstream projection bias offline.
    """

    kind: str = dataclasses.field(metadata=dict(static=True), default="rms")
    u: Optional[jnp.ndarray] = None  # Hᵀ1/d for LayerNorm mean recovery
    eps: float = dataclasses.field(metadata=dict(static=True), default=1e-6)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class FusedFFN:
    """A whole (optionally gated) FFN layer fused onto the unified
    datapath: one kernel launch runs norm prologue → shared activation
    quantization → gate/up integer matmuls → ``act(g)·u`` → hidden WHT →
    re-quantization → down integer matmul → IDCT/biases.

    ``norm`` (rms|ln) means the layer *absorbs* its pre-norm: the model
    code passes the raw residual stream and skips the external
    ``apply_norm`` (see :func:`carries_norm`).  ``w_gate`` is None for
    plain (non-GLU) FFNs.  When the member sites are not kernel-routed the
    same op order runs as a jnp emulation — which is also the parity
    reference the fused kernel is tested against.
    """

    w_up: QuantLinear
    w_down: QuantLinear
    w_gate: Optional[QuantLinear] = None
    norm_u: Optional[jnp.ndarray] = None
    act: str = dataclasses.field(metadata=dict(static=True), default="gelu")
    norm: Optional[str] = dataclasses.field(metadata=dict(static=True), default=None)
    norm_eps: float = dataclasses.field(metadata=dict(static=True), default=1e-6)


# ---------------------------------------------------------------------------
# Online ops
# ---------------------------------------------------------------------------


def online_wht(x: jnp.ndarray, block: int | None = None) -> jnp.ndarray:
    """Blocked multiplier-free WHT along the last axis."""
    return transforms.fast_wht(x, block=block)


def _int_matmul(xq: QTensor, wq: QTensor, out_dtype) -> jnp.ndarray:
    """(per-token int) x (per-channel int) -> scaled float.

    jnp fallback path (the Pallas kernel in ``kernels/quant_matmul.py`` is
    the TPU hot path; numerics are identical).  Values are cast to f32
    whose 24-bit mantissa represents every int8 product exactly; f32
    accumulation matches the kernel's int32 accumulate to ~1e-7 relative
    for the K sizes used here.
    """
    xv = xq.values.astype(jnp.float32)
    wv = wq.unpacked_values().astype(jnp.float32)
    acc = jnp.einsum("...k,kn->...n", xv, wv)
    out = acc * xq.scale.astype(jnp.float32) * wq.scale.astype(jnp.float32)
    return out.astype(out_dtype)


def folded_norm_stats(
    xf: jnp.ndarray, kind: str, u: Optional[jnp.ndarray], eps: float
) -> jnp.ndarray:
    """FoldedNorm statistics (γ/β live in the weights) on f32 inputs —
    shared by ``apply_norm``, the fused-path emulations, and the Pallas
    prologue's numerical twin."""
    if kind == "rms":
        ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
        return xf * jax.lax.rsqrt(ms + eps)
    # LayerNorm statistics recovered in the rotated domain
    d = xf.shape[-1]
    mu = jnp.einsum("...d,d->...", xf, u)[..., None]  # mean of unrotated x
    sq = jnp.mean(xf * xf, axis=-1, keepdims=True)  # E[x²] (rotation-invariant)
    var = sq - mu * mu
    # subtract the rotated-domain image of the mean: (μ·1)·H = μ·(1·H) = μ·d·u
    return (xf - mu * u * d) * jax.lax.rsqrt(var + eps)


# erf in float32 from multiplies, adds, one divide and one exp, which Mosaic
# lowers (it has no rule for ``lax.erf``): below |z| = 0.8,
# erf(z) = z·S(z²); above, erf(z) = 1 − t·P(t)·exp(−z²) with t = 1/(1 + z/2).
# S and P are minimax fits to float64 erf; evaluated in float32 the largest
# error over [−6, 6] is 1.2e-7 (XLA's CPU ``lax.erf``: 3.1e-7).
_ERF_SPLIT = 0.8
_ERF_S = (8.925093390446488e-05, -0.0008257350070270952, 0.0052098220061197326,
          -0.02686244245321666, 0.11283741006414373, -0.37612635859611687,
          1.1283791665660137)
_ERF_P = (-0.03642289573924872, 0.1661717279652727, -0.2185697329571003,
          -0.0665956211659792, 0.24644460328725898, 0.06603508625275502,
          0.2852628011858044, 0.2750436284446625, 0.2826369902069534)


def _horner(coeffs, x):
    r = jnp.full_like(x, coeffs[0])
    for c in coeffs[1:]:
        r = r * x + c
    return r


def erf_f32(z: jnp.ndarray) -> jnp.ndarray:
    """erf of float32 ``z`` to within 1.2e-7 (see ``_ERF_S``/``_ERF_P``)."""
    a = jnp.abs(z)
    small = a * _horner(_ERF_S, a * a)
    t = 1.0 / (1.0 + 0.5 * a)
    large = 1.0 - t * _horner(_ERF_P, t) * jnp.exp(-a * a)
    r = jnp.where(a < _ERF_SPLIT, small, large)
    return jnp.where(z < 0, -r, r)


def _act_fn(y: jnp.ndarray, act: str) -> jnp.ndarray:
    if act == "gelu":
        return jax.nn.gelu(y, approximate=True)
    if act == "gelu_erf":  # exact GELU, x·Φ(x)
        return 0.5 * y * (1.0 + erf_f32(y * 0.7071067811865476))
    if act == "silu":
        return jax.nn.silu(y)
    assert act == "none", act
    return y


def carries_norm(p: Any) -> bool:
    """True when a fused site absorbs its pre-norm (the layer code must
    pass the raw residual stream and skip the external ``apply_norm``)."""
    if isinstance(p, FusedFFN):
        return p.norm is not None
    if isinstance(p, dict) and "wqkv" in p:
        p = p["wqkv"]
    return (
        isinstance(p, QuantLinear)
        and p.prologue is not None
        and p.prologue.norm is not None
    )


def _kernel_ready(p: QuantLinear) -> bool:
    return p.use_kernel and p.qw.bits <= 8 and p.a_bits <= 8


def _monitor_quant(p: "QuantLinear", x: jnp.ndarray) -> None:
    """Quant-health tap: observe the activation a site is about to
    quantize (obs/quant_health.py; off by default and free when off).  On
    the fused-kernel path this sees the site *input* — the in-kernel
    norm/WHT run before the actual quantize — so the signal is a proxy
    there; the emulation path observes the exact pre-quant tensor."""
    if p.site is None:
        return
    # local import: obs depends on core.quantize, so core cannot import
    # obs at module scope without a cycle
    from repro.obs import quant_health

    quant_health.monitor(p.site, x, p.a_bits)


def apply_linear(p: Any, x: jnp.ndarray) -> jnp.ndarray:
    """Dispatching linear: plain {"w": ...} dict or QuantLinear.

    A QuantLinear runs per-token activation quantization at its own
    ``a_bits`` and the integer matmul on its own weight format — the
    per-site reconfigurability of the paper's PE array: int8, packed
    int4, or (for sites a PrecisionPlan left at bf16) the plain dict
    path below.  ``use_kernel`` sites route to the Pallas kernel; sites
    with ``prologue``/``epilogue`` descriptors fuse the surrounding
    nonlinear work into that one launch (``kernels.ops.fused_linear``).
    """
    if isinstance(p, QuantLinear):
        dtype = x.dtype
        fused = p.prologue is not None or p.epilogue is not None
        if p.epilogue is not None and p.epilogue.requant_bits is not None:
            raise ValueError(
                "requant epilogues return QTensors — call "
                "kernels.ops.fused_linear directly"
            )
        if fused and _kernel_ready(p):
            from repro.kernels import ops as kernel_ops

            _monitor_quant(p, x)
            return kernel_ops.fused_linear(x, p).astype(dtype)
        if p.prologue is not None and p.prologue.norm is not None:
            x = folded_norm_stats(
                x.astype(jnp.float32), p.prologue.norm, p.norm_u, p.prologue.eps
            ).astype(dtype)
        if p.rotate_input:
            x = online_wht(x)
        _monitor_quant(p, x)
        if _kernel_ready(p):
            from repro.kernels import ops as kernel_ops

            t = dict(p.tiles) if p.tiles else {}
            y = kernel_ops.quant_linear_matmul(
                x,
                p.qw,
                a_bits=p.a_bits,
                out_dtype=jnp.float32,
                bn=t.get("bn"),
                bk=t.get("bk"),
                bm_target=t.get("bm_target"),
            )
        else:
            xq = quantize_per_token(x, p.a_bits)
            y = _int_matmul(xq, p.qw, jnp.float32)
        if p.idct:
            d = transforms.dct_matrix(p.dct_block, dtype=jnp.float32)
            y = transforms.apply_blocked(y, d, p.dct_block)  # ŷ·D cancels offline ·Dᵀ
        if p.bias is not None:
            y = y + p.bias.astype(jnp.float32)
        if p.epilogue is not None:  # emulation twin of the kernel epilogue
            y = _act_fn(y, p.epilogue.act)
            if p.epilogue.wht:
                y = online_wht(y)
        return y.astype(dtype)
    y = jnp.einsum("...k,kn->...n", x, p["w"].astype(x.dtype))
    if p.get("b") is not None:
        y = y + p["b"].astype(x.dtype)
    return y


def apply_ffn(f: FusedFFN, x: jnp.ndarray) -> jnp.ndarray:
    """Apply a :class:`FusedFFN` — one Pallas launch when every member
    site is kernel-routed, else the jnp emulation in the exact same op
    order (the fused kernel's parity reference)."""
    dtype = x.dtype
    members = (f.w_up, f.w_down) + (() if f.w_gate is None else (f.w_gate,))
    if all(_kernel_ready(ql) for ql in members):
        from repro.kernels import ops as kernel_ops

        _monitor_quant(f.w_up, x)  # in-kernel hidden is unobservable
        return kernel_ops.fused_ffn_apply(x, f).astype(dtype)
    if f.norm is not None:
        x = folded_norm_stats(
            x.astype(jnp.float32), f.norm, f.norm_u, f.norm_eps
        ).astype(dtype)
    u = apply_linear(f.w_up, x)
    if f.w_gate is not None:
        h = _act_fn(apply_linear(f.w_gate, x), f.act) * u
    else:
        h = _act_fn(u, f.act)
    return apply_linear(f.w_down, h.astype(dtype)).astype(dtype)


def apply_norm(p: Any, x: jnp.ndarray) -> jnp.ndarray:
    """Dispatching norm: ``Norm`` (plain) or ``FoldedNorm`` (γ folded away)."""
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    if isinstance(p, FoldedNorm):
        return folded_norm_stats(xf, p.kind, p.u, p.eps).astype(dtype)
    if p.kind == "rms":
        ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(ms + p.eps)
    else:
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + p.eps)
    y = y * p.g.astype(jnp.float32)
    if p.b is not None:
        y = y + p.b.astype(jnp.float32)
    return y.astype(dtype)


# ---------------------------------------------------------------------------
# Offline weight preparation (Fig. 6)
# ---------------------------------------------------------------------------


def rotate_rows(w: jnp.ndarray, block: int | None = None) -> jnp.ndarray:
    """W ← Hᵀ·W with blocked Hadamard along the input (row) dim (H = Hᵀ)."""
    blk = block or transforms.block_size_for(w.shape[0])
    h = transforms.hadamard_matrix(blk, dtype=jnp.float32)
    d_in = w.shape[0]
    w = w.reshape(d_in // blk, blk, -1).astype(jnp.float32)
    w = jnp.einsum("cb,kbn->kcn", h, w)
    return w.reshape(d_in, -1)


def rotate_cols(w: jnp.ndarray, block: int | None = None) -> jnp.ndarray:
    """W ← W·H (blocked) along the output dim — leaves outputs rotated."""
    blk = block or transforms.block_size_for(w.shape[-1])
    hb = transforms.hadamard_matrix(blk, dtype=jnp.float32)
    d_out = w.shape[-1]
    lead = w.shape[:-1]
    w = w.reshape(lead + (d_out // blk, blk)).astype(jnp.float32)
    w = jnp.einsum("...kb,bc->...kc", w, hb)
    return w.reshape(lead + (d_out,))


def dct_cols(w: jnp.ndarray, block: int = DCT_BLOCK) -> jnp.ndarray:
    """W ← W·Dᵀ with blocked DCT along the output dim (online IDCT = ·D)."""
    d = transforms.dct_matrix(block, dtype=jnp.float32)
    d_out = w.shape[-1]
    lead = w.shape[:-1]
    w = w.reshape(lead + (d_out // block, block)).astype(jnp.float32)
    w = jnp.einsum("...kb,cb->...kc", w, d)
    return w.reshape(lead + (d_out,))


def _fuse_weight(
    w: jnp.ndarray,
    *,
    use_wht: bool,
    gamma: Optional[jnp.ndarray],
    beta: Optional[jnp.ndarray],
    bias: Optional[jnp.ndarray],
    rotate_in: bool,
    rotate_out_offline: bool,
    head_rot_in: tuple[int, int] | None,
    head_rot_out: tuple[int, int] | None,
    in_block: int | None,
) -> tuple[jnp.ndarray, jnp.ndarray, bool]:
    """Shared offline fusion (Eq. 6/7 minus the DCT): γ/β fold, per-head
    Hadamards, input-side Hᵀ, output-side H.  Returns (w, b, has_bias)."""
    w = w.astype(jnp.float32)
    b = jnp.zeros((w.shape[-1],), jnp.float32) if bias is None else bias.astype(jnp.float32)
    has_bias = bias is not None
    if beta is not None:  # β @ W with the original W
        b = b + beta.astype(jnp.float32) @ w
        has_bias = True
    if gamma is not None:
        w = w * gamma.astype(jnp.float32)[:, None]
    if head_rot_in is not None and use_wht:
        nh, hd = head_rot_in
        w = fold_head_hadamard_in(w, nh, hd)
    if rotate_in and use_wht:
        w = rotate_rows(w, in_block or transforms.block_size_for(w.shape[0]))
    if head_rot_out is not None and use_wht:
        nh, hd = head_rot_out
        w = fold_head_hadamard_out(w, nh, hd)
        b = fold_head_hadamard_out(b[None, :], nh, hd)[0]  # the bias is an output too
    if rotate_out_offline and use_wht:
        w = rotate_cols(w)
        b = rotate_cols(b[None, :])[0]
    return w, b, has_bias


def prepare_linear(
    w: jnp.ndarray,
    policy: QuantPolicy,
    *,
    gamma: Optional[jnp.ndarray] = None,
    beta: Optional[jnp.ndarray] = None,
    bias: Optional[jnp.ndarray] = None,
    rotate_in_offline: bool = False,
    rotate_input_online: bool = False,
    rotate_out_offline: bool = False,
    head_rot_in: tuple[int, int] | None = None,
    head_rot_out: tuple[int, int] | None = None,
    in_block: int | None = None,
    use_kernel: bool = False,
    prologue: Optional[Prologue] = None,
    epilogue: Optional[Epilogue] = None,
    norm_u: Optional[jnp.ndarray] = None,
    tiles: Optional[tuple] = None,
    site: Optional[str] = None,
) -> QuantLinear:
    """Fuse transforms into a [in, out] weight and quantize (Eq. 7).

    ``gamma``/``beta``: the preceding (pre-)norm's element-wise scale/shift,
    folded per Eq. 6 (β contributes ``β @ W`` to the bias, computed on the
    *original* W).
    ``rotate_in_offline``: fuse Hᵀ on the input side (input arrives rotated).
    ``rotate_input_online``: the input can't arrive rotated (e.g. GLU
    hidden); the online WHT runs at apply time and Hᵀ is fused here so the
    pair cancels.
    ``rotate_out_offline``: fuse H on the output side — the output stays in
    the rotated residual domain (paper Stage 4); bias is rotated to match.
    ``head_rot_in``/``head_rot_out``: (n_heads, head_dim) per-head Hadamard
    on the input/output side (V/O projections).
    ``use_kernel``: route this site's matmul through the Pallas kernel.
    ``prologue``/``epilogue``/``norm_u``: unified-datapath fusion
    descriptors carried onto the prepared layer (see :class:`QuantLinear`).
    """
    w, b, has_bias = _fuse_weight(
        w,
        use_wht=policy.use_wht,
        gamma=gamma,
        beta=beta,
        bias=bias,
        rotate_in=rotate_in_offline or rotate_input_online,
        rotate_out_offline=rotate_out_offline,
        head_rot_in=head_rot_in,
        head_rot_out=head_rot_out,
        in_block=in_block,
    )
    idct = False
    if policy.use_dct and w.shape[-1] % DCT_BLOCK == 0:
        w = dct_cols(w, DCT_BLOCK)
        # bias is added AFTER the online IDCT, in the un-DCT'd basis: keep b.
        idct = True
    qw = quantize_weight(w, policy.w_bits)
    return QuantLinear(
        qw=qw,
        bias=b if has_bias else None,
        a_bits=policy.a_bits,
        rotate_input=policy.use_wht and rotate_input_online,
        idct=idct,
        use_kernel=use_kernel,
        prologue=prologue,
        epilogue=epilogue,
        norm_u=norm_u,
        tiles=tiles,
        site=site,
    )


def prepare_linear_fp(
    w: jnp.ndarray,
    *,
    use_wht: bool = True,
    gamma: Optional[jnp.ndarray] = None,
    beta: Optional[jnp.ndarray] = None,
    bias: Optional[jnp.ndarray] = None,
    rotate_in_offline: bool = False,
    rotate_input_online: bool = False,
    rotate_out_offline: bool = False,
    head_rot_in: tuple[int, int] | None = None,
    head_rot_out: tuple[int, int] | None = None,
    in_block: int | None = None,
) -> dict:
    """bf16-passthrough site preparation for mixed-precision plans.

    Same offline fusion as :func:`prepare_linear` — the site must keep
    consuming the rotated residual stream and producing into it, and the
    V/O per-head Hadamard pair must stay matched with its (possibly
    quantized) partner — but no DCT (it only helps quantization) and no
    quantization.  ``rotate_input_online`` is accepted for signature
    parity and *ignored*: with no quantizer between them the online
    WHT/offline Hᵀ pair would cancel exactly, so neither is applied.
    Returns the plain ``{"w", "b"}`` dict ``apply_linear`` dispatches on.
    """
    del rotate_input_online
    w, b, has_bias = _fuse_weight(
        w,
        use_wht=use_wht,
        gamma=gamma,
        beta=beta,
        bias=bias,
        rotate_in=rotate_in_offline,
        rotate_out_offline=rotate_out_offline,
        head_rot_in=head_rot_in,
        head_rot_out=head_rot_out,
        in_block=in_block,
    )
    return {"w": w, "b": b if has_bias else None}


def fold_head_hadamard_out(w: jnp.ndarray, n_heads: int, head_dim: int) -> jnp.ndarray:
    """Fuse a per-head Hadamard on the *output* side: W[:, (h,d)] ← W·H_dh."""
    k = w.shape[0]
    w = w.reshape(k, n_heads, head_dim)
    w = rotate_cols(w)
    return w.reshape(k, n_heads * head_dim)


def fold_head_hadamard_in(w: jnp.ndarray, n_heads: int, head_dim: int) -> jnp.ndarray:
    """Fuse a per-head Hadamard on the *input* side: W[(h,d), :] ← H_dhᵀ·W."""
    hb = transforms.blocked_hadamard_matrix(head_dim, dtype=jnp.float32)
    n = w.shape[-1]
    w = w.reshape(n_heads, head_dim, n).astype(jnp.float32)
    w = jnp.einsum("ed,hdn->hen", hb.T, w)
    return w.reshape(n_heads * head_dim, n)


def head_wht(x: jnp.ndarray) -> jnp.ndarray:
    """Online per-head WHT along head_dim (scores-invariant Q/K smoothing)."""
    return transforms.fast_wht(x)


def make_folded_norm(kind: str, dim: int, eps: float = 1e-6) -> FoldedNorm:
    if kind == "rms":
        return FoldedNorm(kind="rms", u=None, eps=eps)
    # u = Hᵀ1/d: for a normalized blocked Hadamard, column sums are √b at
    # block-leading coordinates and 0 elsewhere.
    b = transforms.block_size_for(dim)
    u = jnp.zeros((dim,), jnp.float32).at[::b].set(jnp.sqrt(float(b)) / dim)
    return FoldedNorm(kind="ln", u=u, eps=eps)
