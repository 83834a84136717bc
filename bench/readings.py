"""Readings of the numbers ``correct`` compares, over many seeds in one
process: what the limits in ``bench/configs`` are set from.

    python3 bench/readings.py --workload <cell> --seeds 1,2,3 --seconds 13 \\
        [--policy w4a4:fused] [--dump DIR] [--config FILE]

Each seed is a whole run of the cell (``run.measure``) at a short window;
``--policy`` serves the program at another precision against the
configuration's reference, which is how the control is read.  Prints one
JSON line per seed: the seed, each compared number, and each scene's
gaps.  ``--dump`` also writes each seed's served and reference answers
(all but the tokens) to ``DIR/<cell>.<policy>.<seed>.npz``; ``--config``
reads the cell's traffic with another configuration file.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import run  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--policy", default=None)
    ap.add_argument("--dump", default=None)
    ap.add_argument("--config", default=None, help="a configuration file to read instead")
    args = ap.parse_args()
    cell = run.load_cell(args.workload)
    if args.config:
        with open(args.config) as f:
            config = json.load(f)
        cell = dataclasses.replace(cell, config=config, **run.modules(config))
    run.configure_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        keep = []
        r = run.measure(cell, seed, args.seconds, False, policy=args.policy, keep=keep)
        print(json.dumps({"seed": seed, "policy": args.policy, "correct": r["correct"],
                          "scenes": r["attempted"],
                          **{k: v["value"] for k, v in r["check"].items()},
                          "metrics": {k: v["value"] for k, v in r["metrics"].items()},
                          "per_scene": [s["gaps"] for s in keep],
                          "run_s": time.perf_counter() - t}),
              flush=True)
        if args.dump:
            os.makedirs(args.dump, exist_ok=True)
            arrays = {f"{j}.{side}.{k}": v for j, s in enumerate(keep)
                      for side in ("got", "ref") for k, v in s[side].items()}
            np.savez_compressed(os.path.join(
                args.dump, f"{cell.config['name']}.{args.workload}.{args.policy or 'sound'}.{seed}.npz"),
                scenes=np.array([s["scene"] for s in keep]), **arrays)
    return 0


if __name__ == "__main__":
    sys.exit(main())
