"""Autotuner + persisted tuning DB backing the kernel-plan compiler.

The compiler (:mod:`repro.core.precision.compiler`) lowers each weight
site to a kernel choice plus tile shapes.  Without a tuner it emits the
seed tiling (the same defaults the implicit path picks); with one, each
distinct ``(shape, dtype, fusion, backend)`` signature is tuned once and
the winner persisted, so re-compiling an already-tuned config performs
**zero** timing runs.

Cost signal is backend-dependent:

* ``interpret`` (CPU) — candidates are *traced* (``jax.eval_shape``)
  through the real kernel wrappers under ``kernels.probe.tracking``; the
  wrappers record modeled HBM traffic for the resolved tiles, and the
  candidate with the fewest bytes wins.  No FLOPs are executed, but every
  candidate evaluation still counts as a timing run for cache accounting.
* anything else (real hardware) — candidates run the actual kernel and
  are ranked by best-of-N wall clock.

Candidate generation reuses the tiling-policy helpers in
:mod:`repro.kernels.ops` (``matmul_tiles`` / ``attention_tiles``), so
every candidate is a legal tiling by construction: targets sweep a small
grid, the policy legalizes them against the concrete shape, and
duplicates collapse.
"""
from __future__ import annotations

import dataclasses
import json
import os
import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from ..quantize import QTensor, quantize_weight
from ...kernels import ops as kernel_ops
from ...kernels import probe

__all__ = ["TuningDB", "Autotuner", "matmul_key", "attention_key"]

DB_VERSION = 1

# Reference token count used when timing matmul candidates — the real M is
# runtime-dependent, so candidates are ranked at a representative size.
TUNE_M = 256
# Reference sequence lengths for attention candidates.
TUNE_LQ = 256
TUNE_LK = 1024

_MATMUL_BM = (128, 256, 512)
_MATMUL_BN = (128, 256, 512)
_MATMUL_BK = (256, 512, 1024)
_FUSED_BM = (128, 256, 512)
_ATTN_BQ = (128, 256, 512)
_ATTN_BK = (256, 512, 1024, 2048, 4096)
_ATTN_BKV = (1024, 2048, 4096)


def matmul_key(
    k: int,
    n: int,
    *,
    w_bits: int,
    a_bits: int,
    packed: bool,
    fused: bool,
    backend: str,
) -> str:
    """DB key for a matmul site: shape x dtype x fusion x backend."""
    return (
        f"quant_matmul|k{k}xn{n}|w{w_bits}a{a_bits}"
        f"|packed{int(packed)}|fused{int(fused)}|{backend}"
    )


def attention_key(head_dim: int, *, backend: str) -> str:
    return f"two_stage_mha|dh{head_dim}|{backend}"


class TuningDB:
    """JSON-file-backed map from tuning key to winning tiles.

    Counts ``hits`` / ``misses`` so tests can assert that a second compile
    of an already-tuned config never re-times anything.
    """

    def __init__(self, path: Optional[str] = None) -> None:
        self.path = path
        self.entries: dict[str, dict] = {}
        self.hits = 0
        self.misses = 0
        self._dirty = False
        if path is not None and os.path.exists(path):
            with open(path) as f:
                blob = json.load(f)
            if blob.get("version") != DB_VERSION:
                raise ValueError(
                    f"tuning DB version {blob.get('version')!r} != {DB_VERSION}"
                )
            self.entries = dict(blob.get("entries", {}))

    def get(self, key: str) -> Optional[dict]:
        entry = self.entries.get(key)
        if entry is None:
            self.misses += 1
        else:
            self.hits += 1
        return entry

    def put(self, key: str, entry: dict) -> None:
        self.entries[key] = entry
        self._dirty = True

    def save(self) -> None:
        if self.path is None or not self._dirty:
            return
        blob = {"version": DB_VERSION, "entries": dict(sorted(self.entries.items()))}
        tmp = self.path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(blob, f, indent=2, sort_keys=True)
            f.write("\n")
        os.replace(tmp, self.path)
        self._dirty = False


@dataclasses.dataclass
class Autotuner:
    """Times candidate tilings and persists winners in a :class:`TuningDB`.

    ``budget`` caps candidates evaluated per site signature.  ``measure``
    is injectable for tests: ``measure(kind, tiles) -> cost`` replaces
    both the traced-bytes and wall-clock paths.
    """

    db: TuningDB
    budget: int = 8
    backend: Optional[str] = None
    measure: Optional[Callable[[str, dict], float]] = None
    timing_runs: int = 0

    def __post_init__(self) -> None:
        if self.backend is None:
            self.backend = (
                "interpret" if jax.default_backend() == "cpu" else jax.default_backend()
            )

    # -- matmul sites ---------------------------------------------------

    def tune_matmul(
        self, k: int, n: int, *, w_bits: int, a_bits: int, packed: bool, fused: bool
    ) -> dict:
        key = matmul_key(
            k, n, w_bits=w_bits, a_bits=a_bits, packed=packed, fused=fused,
            backend=self.backend,
        )
        entry = self.db.get(key)
        if entry is not None:
            return dict(entry["tiles"])
        candidates = self._matmul_candidates(k, n, packed=packed, fused=fused)
        best, cost = self._rank(
            candidates,
            lambda t: self._matmul_cost(t, k, n, w_bits=w_bits, a_bits=a_bits,
                                        packed=packed, fused=fused),
        )
        self.db.put(key, {"tiles": best, "cost": cost, "candidates": len(candidates)})
        return dict(best)

    def _matmul_candidates(self, k: int, n: int, *, packed: bool, fused: bool) -> list[dict]:
        if fused:
            # Fused panels stream the whole weight per M tile; only the
            # token tile target is tunable.
            seeds = [kernel_ops.matmul_tile_seed(k, n, packed=packed, fused=True)]
            seeds += [{"bm_target": t} for t in _FUSED_BM]
            return _dedup(seeds)
        cands = [kernel_ops.matmul_tile_seed(k, n, packed=packed)]
        for bm_t in _MATMUL_BM:
            for bn_t in _MATMUL_BN:
                for bk_t in _MATMUL_BK:
                    _, _, bn, bk = kernel_ops.matmul_tiles(
                        TUNE_M, k, n, packed=packed,
                        bm_target=bm_t, bn_target=bn_t, bk_target=bk_t,
                    )
                    cands.append({"bm_target": bm_t, "bn": bn, "bk": bk})
        return _dedup(cands)

    def _matmul_cost(
        self, tiles: dict, k: int, n: int, *, w_bits: int, a_bits: int,
        packed: bool, fused: bool,
    ) -> float:
        self.timing_runs += 1
        if self.measure is not None:
            return float(self.measure("fused_panel" if fused else "quant_matmul", tiles))
        if fused:
            # One modeled formula (mirrors kernels.ops.fused_linear): the
            # panel re-reads all weight bytes per M tile.
            bm, mp = kernel_ops.lane_tile(TUNE_M, tiles.get("bm_target", kernel_ops.FUSED_BM))
            kb = -(-k // 2) if packed else k
            return float(mp * k + kb * n * (mp // bm) + mp * n * 4)
        if self.backend == "interpret":
            return self._traced_matmul_bytes(tiles, k, n, w_bits=w_bits, a_bits=a_bits,
                                             packed=packed)
        return self._wallclock_matmul(tiles, k, n, w_bits=w_bits, a_bits=a_bits,
                                      packed=packed)

    def _traced_matmul_bytes(
        self, tiles: dict, k: int, n: int, *, w_bits: int, a_bits: int, packed: bool
    ) -> float:
        kstore = k // 2 if packed else k
        vdtype = jnp.uint8 if packed else jnp.int8
        vals = jax.ShapeDtypeStruct((kstore, n), vdtype)
        scale = jax.ShapeDtypeStruct((1, n), jnp.float32)

        def run(v, s):
            wq = QTensor(values=v, scale=s, bits=w_bits, packed=packed,
                         pack_axis=0 if packed else None)
            x = jnp.zeros((TUNE_M, k), jnp.float32)
            return kernel_ops.quant_linear_matmul(
                x, wq, a_bits=a_bits, bn=tiles.get("bn"), bk=tiles.get("bk"),
                bm_target=tiles.get("bm_target"),
            )

        with probe.tracking() as log:
            jax.eval_shape(run, vals, scale)
        return float(log.total_bytes)

    def _wallclock_matmul(
        self, tiles: dict, k: int, n: int, *, w_bits: int, a_bits: int, packed: bool
    ) -> float:
        w = ((jnp.arange(k * n, dtype=jnp.float32) % 13.0) - 6.0).reshape(k, n) / 7.0
        wq = quantize_weight(w, w_bits)
        x = jnp.ones((TUNE_M, k), jnp.float32)

        def run():
            return kernel_ops.quant_linear_matmul(
                x, wq, a_bits=a_bits, bn=tiles.get("bn"), bk=tiles.get("bk"),
                bm_target=tiles.get("bm_target"),
            )

        run().block_until_ready()  # compile outside the timed region
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            run().block_until_ready()
            best = min(best, time.perf_counter() - t0)
        return best

    # -- attention ------------------------------------------------------

    def tune_attention(self, head_dim: int) -> dict:
        key = attention_key(head_dim, backend=self.backend)
        entry = self.db.get(key)
        if entry is not None:
            return dict(entry["tiles"])
        candidates = self._attention_candidates()
        best, cost = self._rank(
            candidates, lambda t: self._attention_cost(t, head_dim)
        )
        self.db.put(key, {"tiles": best, "cost": cost, "candidates": len(candidates)})
        return dict(best)

    def _attention_candidates(self) -> list[dict]:
        cands = [kernel_ops.attention_tile_seed()]
        for bq in _ATTN_BQ:
            for bk in _ATTN_BK:
                for bkv in _ATTN_BKV:
                    cands.append({"bq_target": bq, "bk_target": bk, "bkv_target": bkv})
        return _dedup(cands)

    def _attention_cost(self, tiles: dict, head_dim: int) -> float:
        self.timing_runs += 1
        if self.measure is not None:
            return float(self.measure("two_stage_mha", tiles))
        q = jax.ShapeDtypeStruct((1, 4, TUNE_LQ, head_dim), jnp.float32)
        kv = jax.ShapeDtypeStruct((1, 4, TUNE_LK, head_dim), jnp.float32)

        def run(qq, kk, vv):
            return kernel_ops.two_stage_mha(qq, kk, vv, **tiles)

        if self.backend == "interpret":
            with probe.tracking() as log:
                jax.eval_shape(run, q, kv, kv)
            return float(log.total_bytes)
        qa = jnp.ones(q.shape, q.dtype)
        ka = jnp.ones(kv.shape, kv.dtype)
        run(qa, ka, ka).block_until_ready()
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            run(qa, ka, ka).block_until_ready()
            best = min(best, time.perf_counter() - t0)
        return best

    # -- shared ---------------------------------------------------------

    def _rank(self, candidates: list[dict], cost_fn) -> tuple[dict, float]:
        pool = candidates[: max(1, self.budget)]
        best, best_cost = pool[0], cost_fn(pool[0])
        for cand in pool[1:]:
            c = cost_fn(cand)
            if c < best_cost:
                best, best_cost = cand, c
        return best, best_cost

    def flush(self) -> None:
        self.db.save()


def _dedup(cands: list[dict]) -> list[dict]:
    seen: set[tuple] = set()
    out: list[dict] = []
    for c in cands:
        key = tuple(sorted(c.items()))
        if key not in seen:
            seen.add(key)
            out.append(c)
    return out
