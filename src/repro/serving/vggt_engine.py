"""Production VGGT serving engine: bucketed jit cache + micro-batched
scene queue + quantized fast path.

The paper's deployment story is *instant* feed-forward reconstruction:
one forward pass per scene, under tight latency budgets.  The naive
``vggt_serve`` re-jits on every call and serves one request at a time;
this engine is the production version:

* **Shape buckets** — requests are keyed on ``(n_frames, n_patches,
  batch)``, and for a 2D-RoPE model (``pos="rope2d"``) on the frames'
  patch grid too; the batch dim is padded up to a bucket size (powers of
  two by default) and each bucket holds exactly one jitted forward, so
  repeated traffic never recompiles.  With ``pad_patches=True`` the patch dim is
  also rounded up (per-frame padding, masked out of every attention
  softmax via ``vggt.forward(patch_mask=...)``) which lets scenes with
  different patch counts share buckets and micro-batches.

* **Micro-batching** — ``enqueue`` parks requests in a per-group queue
  (``serving.batching.MicroBatchQueue``, shared with the LM engine); a
  group is flushed into one forward as soon as it fills ``max_batch``
  scenes, when its oldest request exceeds ``max_wait_s`` (``poll``), or
  explicitly (``flush``).  Results are split back per request, with
  padding rows/patches sliced off.

* **Quantized fast path** — pass ``policy=W4A8`` to serve the
  ``model_quant.quantize_vggt`` weights; with ``attn_impl="two_stage"``
  the long-sequence global attention runs through the INT8 two-stage
  Pallas kernel (``kernels/two_stage_attention.py``, paper Alg. 1) with
  per-token Q/K scales.

* **Stats** — per-bucket compile count, p50/p95 latency and scenes/s via
  :class:`VGGTServeStats` (the shared ``serving.batching`` stats type).
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.model_quant import quantize_vggt
from repro.core.versaq import QuantPolicy
from repro.models import vggt as vggt_mod
from repro.obs import quant_health
from repro.obs import trace as obs_trace
from repro.serving import batching, faults as faults_mod
from repro.serving.batching import (
    BucketStats, NumericFault, QueueFull, next_pow2, pick_bucket,
)

__all__ = ["Bucket", "BucketStats", "VGGTServeStats", "PendingRequest", "VGGTEngine"]

DEFAULT_BATCH_BUCKETS = (1, 2, 4, 8, 16)


@dataclasses.dataclass(frozen=True)
class Bucket(batching.Bucket):
    """One compiled shape: batch is padded up, frames exact, patches
    padded only with ``pad_patches``; per precision tier and, for a
    2D-RoPE model, per patch grid.  Prints as ``b4xs2xp24``
    (``fast:b4xs2xp24`` for a non-default tier, ``b1xs8xp1369xg37x37``
    with a grid)."""

    batch: int
    frames: int
    patches: int
    tier: str = "default"
    grid: Optional[tuple[int, int]] = None  # (rows, cols): identity, not an axis

    AXES = ("b", "s", "p")

    def sizes(self) -> tuple:
        return tuple(v for v in super().sizes() if v is not self.grid)

    def __str__(self) -> str:
        s = super().__str__()
        return s if self.grid is None else f"{s}xg{self.grid[0]}x{self.grid[1]}"


class VGGTServeStats(batching.ServeStats):
    """Per-bucket VGGT serving statistics; ``items`` == scenes (the
    ``scenes``/``padded_scenes`` aliases on the shared type keep the
    feed-forward vocabulary)."""

    unit = "scenes"
    kind = "vggt"


@dataclasses.dataclass
class PendingRequest(batching.PendingRequest):
    """A queued scene batch; ``result()`` is available after the engine
    flushes the request's micro-batch group."""

    scenes: jnp.ndarray  # [b, S, P, d]
    n_patches: int  # real (unpadded) patch count
    tier: str = "default"  # precision tier (engine ``tiers`` key)
    grid: Optional[tuple[int, int]] = None  # patch grid (rows, cols), 2D-RoPE models


class VGGTEngine:
    """Bucketed, micro-batched VGGT serving (see module docstring).

    Synchronous API (single-threaded, deterministic — the async server
    loop, ``serving.server.AsyncServer``, drives ``enqueue``/``poll``):

        eng = VGGTEngine(cfg, params, policy=W4A8, attn_impl="two_stage")
        out = eng.infer(scenes)                  # one request
        reqs = [eng.enqueue(s) for s in many]    # micro-batched
        eng.flush()
        outs = [r.result() for r in reqs]

    Precision tiers (docs/serving.md "Precision tiers"): one engine, many
    quantization levels —

        eng = VGGTEngine(cfg, params, tiers={
            "quality": None, "balanced": W4A8, "fast": mixed_plan,
        })
        out = eng.infer(scenes, tier="fast")
    """

    def __init__(
        self,
        cfg: ModelConfig,
        params: Any,
        *,
        policy: Optional[QuantPolicy] = None,
        schedule: Optional[Any] = None,
        tiers: Optional[dict[str, Any]] = None,
        default_tier: Optional[str] = None,
        attn_impl: Optional[str] = None,
        batch_buckets: tuple[int, ...] = DEFAULT_BATCH_BUCKETS,
        max_batch: Optional[int] = None,
        max_wait_s: float = 0.005,
        pad_patches: bool = False,
        max_pending: Optional[int] = None,
        max_queued_tokens: Optional[int] = None,
        admission: str = "reject",
        degrade: Optional[batching.DegradeConfig | bool] = None,
        faults: Optional[faults_mod.FaultPlan | str] = None,
    ):
        if attn_impl is not None and attn_impl not in ("flash", "two_stage", "vanilla"):
            raise ValueError(
                f"attn_impl={attn_impl!r}: expected flash | two_stage | vanilla"
            )
        self.cfg = cfg.with_(attn_impl=attn_impl) if attn_impl is not None else cfg
        # A compiled KernelSchedule (or a path to one) replaces the
        # implicit policy — see serving.engine.Engine for the contract.
        self.schedule, self._schedule_hash = batching.load_schedule(schedule)
        if self.schedule is not None:
            if policy is not None or tiers is not None:
                raise ValueError(
                    "pass either schedule= or policy=/tiers=, not both"
                )
            policy = self.schedule
            targets = self.schedule.attention_targets()
            if targets:
                self.cfg = self.cfg.with_(attn_tiles=targets)
        # ``tiers``: tier name -> QuantPolicy | PrecisionPlan | None (fp).
        # One engine, many precisions: tier is part of the bucket identity
        # (own jit cache entries + stats rows per tier) and of the queue
        # group key (requests only coalesce within their tier).
        self._tierset = batching.TierSet(
            tiers=tiers, policy=policy, default_tier=default_tier,
            raw_params=params,
            quantize=lambda pol: quantize_vggt(self.cfg, params, pol),
        )
        self.tiers = self._tierset.tiers
        self.default_tier = self._tierset.default_tier
        self.policy = self._tierset.default_policy
        self.batch_buckets = tuple(sorted(batch_buckets))
        self.max_batch = max_batch if max_batch is not None else self.batch_buckets[-1]
        self.max_wait_s = max_wait_s
        self.pad_patches = pad_patches
        self.stats = VGGTServeStats()
        self._fns: dict[tuple, Any] = {}
        self._forwards = 0  # forward ordinal, the batch-level span events' parent id
        # micro-batch queues, one per (frames, bucketed patches) group
        self._queue = batching.MicroBatchQueue(self._run, self.max_batch, max_wait_s)
        # robustness layer (docs/robustness.md): bounded admission,
        # degradation ladder, and the chaos injector — all off by default
        self._admission = batching.AdmissionController(
            max_pending=max_pending, max_queued_tokens=max_queued_tokens,
            policy=admission,
        )
        self._degrade = (
            batching.DegradationController(
                None if degrade is True else degrade, len(self.tiers)
            )
            if degrade else None
        )
        self._injector = (
            faults_mod.FaultInjector(faults) if faults is not None else None
        )

    # ---- tiers -----------------------------------------------------------

    @property
    def params(self) -> Any:
        """The default tier's parameter tree (quantized lazily, like
        every other tier's)."""
        return self._tierset.params(None)

    def tier_params(self, tier: str) -> Any:
        """The tier's (lazily quantized) parameter tree."""
        return self._tierset.params(tier)

    def _tier(self, tier: Optional[str]) -> str:
        return self._tierset.resolve(tier)

    # ---- buckets ---------------------------------------------------------

    def bucket_for(
        self, batch: int, frames: int, patches: int, tier: str = "default", grid=None
    ) -> Bucket:
        b = pick_bucket(self.batch_buckets, batch)
        p = next_pow2(patches) if self.pad_patches else patches
        return Bucket(batch=b, frames=frames, patches=p, tier=tier, grid=grid)

    def _bucket_fn(self, bucket: Bucket, masked: bool):
        """The bucket's jitted forward; cache miss == one compile.

        ``masked`` and unmasked calls are separate graphs (the mask-free
        one keeps the quantized two_stage kernel fast path live), so a
        bucket can own up to two compiles — both counted."""
        key = (bucket, masked, self._schedule_hash)
        fn = self._fns.get(key)
        if fn is None:
            self.stats.bucket(bucket).compiles += 1
            if masked:
                fn = jax.jit(
                    lambda p, x, m: vggt_mod.forward(self.cfg, p, x, patch_mask=m,
                                                     grid=bucket.grid)
                )
            else:
                fn = jax.jit(functools.partial(vggt_mod.forward, self.cfg, grid=bucket.grid))
            self._fns[key] = fn
        return fn

    # ---- request path ----------------------------------------------------

    def _group_key(self, scenes: jnp.ndarray, tier: str, grid=None) -> tuple:
        s, p_ = scenes.shape[1], scenes.shape[2]
        return (tier, s, next_pow2(p_) if self.pad_patches else p_, grid)

    def infer(self, scenes: jnp.ndarray, tier: Optional[str] = None, *, grid=None) -> dict:
        """Serve one request synchronously (still bucket-padded/cached).
        Flushes only this request's group — pending micro-batches of
        other shapes/tiers keep coalescing."""
        req = self.enqueue(scenes, tier=tier, grid=grid)
        if not req.ready:
            self._queue.flush_group(self._group_key(req.scenes, req.tier, req.grid))
        return req.result()

    def enqueue(
        self,
        scenes: jnp.ndarray,
        tier: Optional[str] = None,
        *,
        priority: int = 0,
        deadline_s: Optional[float] = None,
        grid: Optional[tuple[int, int]] = None,
    ) -> PendingRequest:
        """Queue a [b, S, P, d] scene batch; auto-flushes a group the
        moment it reaches ``max_batch`` scenes.  ``tier`` selects the
        precision tier; requests only coalesce within their tier.
        ``grid`` = (rows, cols) places the frames' patches for a 2D-RoPE
        model: it defaults to the square grid where P is a perfect square,
        and a request with neither is refused (``ValueError``); requests
        coalesce only within their grid.  Other models ignore it.
        Higher ``priority`` requests are packed into a flushing
        micro-batch first; a request older than ``deadline_s`` seconds is
        evicted (its ``result()`` raises ``DeadlineExceeded``) instead of
        being served late.

        With admission bounds configured (``max_pending`` /
        ``max_queued_tokens``) an over-full queue raises
        :class:`~repro.serving.batching.QueueFull` (policy "reject") or
        sheds the least-valuable queued requests (policy "shed")."""
        if self._degrade is not None:
            self._degrade.observe(self._queue.pending, self._measured_latency())
        pinned = tier is not None
        tier = self._tier(tier)
        if self._degrade is not None and self._degrade.level and not pinned:
            names = list(self.tiers)
            base = names.index(tier)
            down = min(base + self._degrade.level, len(names) - 1)
            if down != base:
                tier = names[down]
                self.stats.scheduler.degraded_admissions += 1
        scenes = jnp.asarray(scenes)
        if scenes.ndim != 4:
            raise ValueError(f"scenes must be [b, S, P, d], got {scenes.shape}")
        b, _, p_, _ = scenes.shape
        req = PendingRequest(
            scenes=scenes, n_patches=p_, tier=tier,
            priority=priority, deadline_s=deadline_s,
            grid=vggt_mod.patch_grid(self.cfg, p_, grid),
        )
        if self._admission.bounded:
            try:
                victims = self._admission.check(
                    req, self._pending_list(), self._req_tokens,
                    self.stats.scheduler,
                )
            except QueueFull:
                obs_trace.emit("rejected", request=req.req_id, kind="vggt", tier=tier)
                raise
            for v in victims:
                self._queue.remove(v)
                v._fail(QueueFull(
                    "request shed from the pending queue to admit "
                    "higher-priority traffic under overload"
                ))
        if self._injector is not None:
            self._injector.on_enqueue(req)
        obs_trace.emit(
            "enqueue", request=req.req_id, kind="vggt", tier=tier,
            scenes=b, frames=scenes.shape[1], patches=p_, priority=priority,
        )
        self._queue.add(self._group_key(scenes, tier, req.grid), req, b)
        return req

    @property
    def pending(self) -> int:
        """Scene requests waiting in the micro-batch queues."""
        return self._queue.pending

    @property
    def degradation_level(self) -> int:
        """Current ladder level (0 = serving at declared tiers)."""
        return self._degrade.level if self._degrade is not None else 0

    def _pending_list(self) -> list[PendingRequest]:
        return [r for q in self._queue._queues.values() for r, _ in q]

    @staticmethod
    def _req_tokens(r: PendingRequest) -> int:
        """Queued work size for ``max_queued_tokens``: patch tokens
        across the request's scenes and frames."""
        return r.scenes.shape[0] * r.scenes.shape[1] * r.n_patches

    def _measured_latency(self) -> Optional[float]:
        try:
            return self.stats.mean_item_latency_s()
        except ValueError:  # no traffic yet — no latency pressure
            return None

    def _numeric_fault(self, req: PendingRequest) -> None:
        """Quarantine one scene request whose forward outputs went
        non-finite: only this request fails, co-batched scenes deliver."""
        self.stats.scheduler.numeric_faults += 1
        obs_trace.emit(
            "numeric_fault", request=req.req_id, tier=req.tier, stage="forward",
        )
        req._fail(NumericFault(
            f"scene request produced non-finite reconstruction outputs at "
            f"tier {req.tier!r} and was quarantined (co-batched scenes "
            f"are unaffected)"
        ))

    def poll(self) -> int:
        """Evict requests past their deadline, then flush groups whose
        oldest request has waited past ``max_wait_s``.  Returns the
        number of groups flushed."""
        if self._injector is not None:
            self._injector.crash("poll")
            self._injector.sleep("poll")
        if self._degrade is not None:
            self._degrade.observe(self._queue.pending, self._measured_latency())
        self._queue.evict_expired(stats=self.stats.scheduler)
        return self._queue.poll()

    def flush(self) -> None:
        """Flush every pending group (deadline-expired requests are
        evicted first, not served late)."""
        self._queue.evict_expired(stats=self.stats.scheduler)
        self._queue.flush()

    def abort(self, err: Optional[BaseException] = None) -> int:
        """Fail every queued request without serving it (shutdown path)."""
        return self._queue.fail_pending(err or RuntimeError("engine aborted"))

    # ---- micro-batch execution -------------------------------------------

    def _run(self, key: tuple, reqs: list[PendingRequest]) -> None:
        """Serve one micro-batch.  Besides the per-request ``admit`` /
        ``forward`` events, each host phase around the forward is a span
        (``vggt.assemble``, ``vggt.check``, ``vggt.deliver``) with one
        batch-level event carrying this engine's ``forward`` ordinal, the
        ``requests`` served and the ``bucket`` (``vggt.assemble`` also the
        patch ``grid``, ``rows x cols``, or None)."""
        tier, frames, p_bucket, grid = key
        for r in reqs:
            obs_trace.emit(
                "admit", request=r.req_id, tier=tier, frames=frames,
                patches=p_bucket, mid_decode=False,
            )
        self._forwards += 1
        batch = dict(forward=self._forwards, requests=[r.req_id for r in reqs])
        with obs_trace.span("vggt.assemble", **batch) as labels:
            params = self.tier_params(tier)
            n_real = sum(r.scenes.shape[0] for r in reqs)
            bucket = self.bucket_for(n_real, frames, p_bucket, tier, grid)
            labels["bucket"] = batch["bucket"] = str(bucket)
            labels["grid"] = None if grid is None else f"{grid[0]}x{grid[1]}"
            d = reqs[0].scenes.shape[-1]
            dtype = reqs[0].scenes.dtype

            # mask only when some request actually has padded patches: the
            # mask-free graph is cheaper and keeps the quantized two_stage
            # kernel fast path live (it requires kv_mask=None)
            masked = any(r.n_patches < bucket.patches for r in reqs)
            inj = self._injector
            if inj is not None:
                inj.sleep("prefill")  # the forward is VGGT's prefill stage
            parts, mask_parts = [], []
            for r in reqs:
                x = r.scenes
                if inj is not None:
                    v = inj.activation("scene", r.req_id)
                    if v is not None:  # poison one input element of this scene
                        x = x.at[0, 0, 0, 0].add(v)
                if x.shape[2] < bucket.patches:  # pad patch dim (masked)
                    pad = bucket.patches - x.shape[2]
                    x = jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0)))
                parts.append(x)
                if masked:
                    m = jnp.zeros((x.shape[0], frames, bucket.patches), bool)
                    mask_parts.append(m.at[:, :, : r.n_patches].set(True))
            if n_real < bucket.batch:  # pad batch dim with empty scenes
                slack = bucket.batch - n_real
                parts.append(jnp.zeros((slack, frames, bucket.patches, d), dtype))
                if masked:
                    mask_parts.append(jnp.ones((slack, frames, bucket.patches), bool))
            x = jnp.concatenate(parts, axis=0)
            args = (params, x, jnp.concatenate(mask_parts, axis=0)) if masked else (params, x)
            fn = self._bucket_fn(bucket, masked)

        qh0 = quant_health.host_seconds()
        start_ns = time.time_ns()
        t0 = time.perf_counter()
        with obs_trace.span("forward", emit_event=False, bucket=str(bucket)):
            out = fn(*args)
            jax.block_until_ready(out)
        dt = time.perf_counter() - t0
        end_ns = time.time_ns()
        qh_s = quant_health.host_seconds() - qh0

        bs = self.stats.bucket(bucket)
        bs.calls += 1
        bs.items += n_real
        bs.padded_items += bucket.batch - n_real
        bs.total_s += dt
        bs.latencies_s.append(dt)
        for r in reqs:
            obs_trace.emit(
                "forward", request=r.req_id, dur_s=dt, start_ns=start_ns, end_ns=end_ns,
                bucket=str(bucket), tier=tier, scenes=r.scenes.shape[0],
                forward=batch["forward"], quant_health_s=qh_s,
            )

        # per-request finiteness over the real (unpadded) reconstruction
        # outputs, reduced on device and read in one host transfer — a
        # non-finite scene batch fails only its own request
        with obs_trace.span("vggt.check", **batch):
            oks, i0 = [], 0
            for r in reqs:
                b = r.scenes.shape[0]
                ok = jnp.array(True)
                for k in ("pose", "points", "depth", "conf"):
                    a = out[k][i0 : i0 + b]
                    if k != "pose":
                        a = a[:, :, : r.n_patches]
                    ok = jnp.logical_and(ok, jnp.isfinite(a).all())
                oks.append(ok)
                i0 += b
            okh = np.asarray(jnp.stack(oks))

        with obs_trace.span("vggt.deliver", **batch):
            i0 = 0
            ns = self.cfg.n_special_tokens
            for idx, r in enumerate(reqs):
                b = r.scenes.shape[0]
                if okh[idx]:
                    r._deliver(_slice_result(out, i0, b, r.n_patches, ns))
                else:
                    self._numeric_fault(r)
                i0 += b


def _slice_result(out: dict, i0: int, b: int, n_patches: int, ns: int) -> dict:
    """Split one request's rows out of a micro-batched forward, dropping
    padded patches/tokens."""
    return {
        "pose": out["pose"][i0 : i0 + b],
        "points": out["points"][i0 : i0 + b, :, :n_patches],
        "depth": out["depth"][i0 : i0 + b, :, :n_patches],
        "conf": out["conf"][i0 : i0 + b, :, :n_patches],
        "tokens": out["tokens"][i0 : i0 + b, :, : ns + n_patches],
    }
