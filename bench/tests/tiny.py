"""A checkout-shaped tree holding one tiny cell, for the CPU tests: the
real metric readers, peaks and configuration, cut to smoke widths."""
from __future__ import annotations

import json
import os
import shutil

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
CELL = "tiny.t2"


def make_root(path, check_scenes=2, d_model=128) -> str:
    """Write BENCHMARK.json and bench/{configs,traffic,metrics,peaks.json}
    under ``path``, the model ``d_model`` wide (the FFN twice that);
    returns ``path`` as a string."""
    root = str(path)
    os.makedirs(os.path.join(root, "bench", "configs"))
    os.makedirs(os.path.join(root, "bench", "traffic"))
    shutil.copytree(os.path.join(BENCH, "metrics"), os.path.join(root, "bench", "metrics"))
    shutil.copy(os.path.join(BENCH, "peaks.json"), os.path.join(root, "bench", "peaks.json"))
    with open(os.path.join(BENCH, "configs", "vggt-1b-w4a8.json")) as f:
        c = json.load(f)
    c.update(name="tiny", n_aa_pairs=2, d_model=d_model, n_heads=4, head_dim=32,
             d_ff=2 * d_model)
    with open(os.path.join(root, "bench", "configs", "tiny.json"), "w") as f:
        json.dump(c, f)
    traffic = {"loop": "closed", "clients": 2, "frames": 2, "patches": 16, "max_batch": 2,
               "scene_pool": 3, "check_scenes": check_scenes}
    with open(os.path.join(root, "bench", "traffic", "t2.json"), "w") as f:
        json.dump(traffic, f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"] = [dict(bench["configs"][0], name="tiny", file="bench/configs/tiny.json")]
    bench["workloads"] = [{"name": CELL, "config": "tiny", "traffic": "t2", "chips": 1,
                           "why": "CPU test"}]
    for m in bench["per_layer"]:
        m["workloads"] = [CELL]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root
