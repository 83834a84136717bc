"""Time two-stage attention's stages on a TPU over a grid of tiles.

    PYTHONPATH=src python benchmarks/attn_tile_sweep.py [--out PATH] [--reps N]

For each attention shape of the VGGT-1B benchmark cells (16 heads of 64;
frame attention over 1,374 tokens a frame, global attention over S·1,374
tokens a scene) it times stage ① (``attention_stats``) alone, then both
stages (``two_stage_attention``), at each tile of a grid and at the tiles
the 8-row resolver of earlier versions chose (``baseline``).  Stage ② is
the difference of the two, at the stage ① tiles that were fastest for
that ``bq``.  Each time is the median of ``--reps`` warm calls closed by
``block_until_ready``: seconds for one layer's launch.  A tile the
compiler refuses is recorded with its error.  The results are written to
``--out`` as JSON, one record a line of stdout as well.

This is how ``T_Q``/``T_K``/``T_V`` in ``kernels/two_stage_attention.py``
were chosen; it needs the chip (it compiles with ``interpret=False``).
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.two_stage_attention import attention_stats, two_stage_attention

FRAME = 1369 + 5
# name -> (rows B·H, real length, padded length, stage ① grid, baseline tiles)
SHAPES = {
    "global-s8": (16, 8 * FRAME, 11264,
                  {"bq": (128, 256, 512), "bk": (256, 512, 1024, 1408)},
                  {"lp": 10992, "bq": 48, "bk": 48, "bkv": 1832}),
    "frame": (128, FRAME, 1408,
              {"bq": (128, 176, 352, 704), "bk": (128, 1408)},
              {"lp": 1376, "bq": 32, "bk": 32, "bkv": 1376}),
    "global-s2b8": (128, 2 * FRAME, 2816,
                    {"bq": (128, 176, 256, 352), "bk": (256, 1408, 2816)},
                    {"lp": 2752, "bq": 64, "bk": 64, "bkv": 1376}),
}
BKV = (1024, 1408, 2048, 2816)
DH = 64


def _inputs(rows: int, lp: int, seed: int = 0):
    rng = np.random.default_rng(seed)

    def i8():
        return jnp.asarray(rng.integers(-127, 128, (rows, lp, DH)), jnp.int8)

    def scale():
        return jnp.asarray(rng.uniform(0.005, 0.02, (rows, lp, 1)), jnp.float32)

    return i8(), scale(), i8(), scale(), i8(), jnp.ones((rows, 1, 1), jnp.float32)


def _time(fn, args, reps: int) -> float:
    jax.block_until_ready(fn(*args))  # compile and warm
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    return statistics.median(times)


def _record(out: list, **rec) -> None:
    out.append(rec)
    print(json.dumps(rec), flush=True)


def _measure(fn, args, reps):
    try:
        return _time(fn, args, reps), None
    except Exception as e:  # a tile Mosaic refuses is a result too
        return None, f"{type(e).__name__}: {str(e).splitlines()[0][:200]}"


def sweep_shape(name: str, reps: int, out: list) -> None:
    rows, length, lp, grid, base = SHAPES[name]
    cases = [("baseline", base["lp"], base["bq"], base["bk"], base["bkv"])]
    cases += [("grid", lp, bq, bk, None) for bq in grid["bq"] for bk in grid["bk"]]
    stage1 = {}
    for kind, lpad, bq, bk, _ in cases:
        qv, qs, kv, ks, _, _ = _inputs(rows, lpad)
        kv_len = length if lpad != length else None
        fn = jax.jit(functools.partial(
            attention_stats, bq=bq, bk=bk, kv_len=kv_len, interpret=False))
        s, err = _measure(fn, (qv, qs, kv, ks), reps)
        scores = rows * lpad * lpad
        stage1[(lpad, bq, bk)] = s
        _record(out, shape=name, stage=1, kind=kind, lp=lpad, bq=bq, bk=bk,
                steps=rows * (lpad // bq) * (lpad // bk), s=s,
                gscores_per_s=None if s is None else scores / s / 1e9, error=err)
    # stage ②: both stages at each bq's fastest stage ① tile, less stage ①
    full = [("baseline", base["lp"], base["bq"], base["bk"], base["bkv"])]
    for bq in grid["bq"]:
        timed = {bk: stage1[(lp, bq, bk)] for bk in grid["bk"]
                 if stage1[(lp, bq, bk)] is not None}
        if not timed:
            continue
        bk = min(timed, key=timed.get)
        full += [("grid", lp, bq, bk, bkv) for bkv in BKV if lp % bkv == 0]
    for kind, lpad, bq, bk, bkv in full:
        args = _inputs(rows, lpad)
        kv_len = length if lpad != length else None
        fn = jax.jit(functools.partial(
            two_stage_attention, bq=bq, bk=bk, bkv=bkv, kv_len=kv_len,
            interpret=False))
        s, err = _measure(fn, args, reps)
        s1 = stage1.get((lpad, bq, bk))
        _record(out, shape=name, stage=2, kind=kind, lp=lpad, bq=bq, bk=bk,
                bkv=bkv, both_s=s,
                s=None if s is None or s1 is None else s - s1, error=err)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default="results/out/attn_tile_sweep.json")
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--shapes", default=",".join(SHAPES))
    args = ap.parse_args()
    if jax.default_backend() != "tpu":
        raise SystemExit("attn_tile_sweep: needs a TPU")
    out: list = []
    for name in args.shapes.split(","):
        sweep_shape(name, args.reps, out)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"device": jax.devices()[0].device_kind, "records": out}, f, indent=1)


if __name__ == "__main__":
    main()
