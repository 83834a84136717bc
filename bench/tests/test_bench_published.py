"""VGGT-1B as published (``vggt-1b-published-w4a8``) runs whole through the
harness on a tiny copy of its configuration on the CPU: ``correct`` with
its own reference, and not where the program leaves out a published form
(2D RoPE, the q/k LayerNorm, the first frame's special tokens)."""
import json
import os
import shutil

import pytest

from bench import run
from bench.tests import tiny

SEED = 2**31 + 29
CONFIG = "vggt-1b-published-w4a8"
MODULES = ("weights_published", "program_published", "reference_published")

# program modules that serve the same weights without one published form
NO_ROPE = '''
from bench import program_published
from bench.program_published import model_config, shutdown, telemetry  # noqa: F401


def serve(config, w, max_batch, policy=None):
    return program_published.program.serve(
        config, w, max_batch, policy,
        to_params=lambda w: program_published.to_params(w, config["norm_eps"]),
        model_config=lambda c: model_config(c).with_(pos="none"))
'''
NO_QK_NORM = NO_ROPE.replace('with_(pos="none")', "with_(qk_norm=False)")
ONE_SPECIAL_SET = '''
from bench import program_published
from bench.program_published import model_config, shutdown, telemetry  # noqa: F401


def serve(config, w, max_batch, policy=None):
    def to_params(w):
        tree = program_published.to_params(w, config["norm_eps"])
        tree["special_tokens"] = tree["special_tokens"][1]
        return tree

    return program_published.program.serve(config, w, max_batch, policy, to_params=to_params,
                                           model_config=model_config)
'''


def _root(tmp_path, program_src=None) -> tuple[str, str]:
    """A tiny tree whose one configuration is the published one at smoke
    widths (published head size 64), with 2-frame scenes on an 8 x 8 patch
    grid; returns (root, cell)."""
    root = tiny.make_root(tmp_path, check_scenes=1000)
    with open(os.path.join(root, "bench", "traffic", "g8.json"), "x") as f:
        json.dump({"loop": "closed", "clients": 2, "frames": 2, "patches": 64,
                   "max_batch": 2, "scene_pool": 3, "check_scenes": 1000}, f)
    for name in MODULES:
        shutil.copy(os.path.join(tiny.BENCH, name + ".py"), os.path.join(root, "bench"))
    with open(os.path.join(tiny.BENCH, "configs", CONFIG + ".json")) as f:
        c = json.load(f)
    # Limits for this size: two AA pairs 128 wide, each branch scaled by
    # LayerScale 0.1, move the stream far less than 24 pairs 1,024 wide, so
    # a departure reads tokens 0.025-0.052 here (under the published-width
    # limit of 0.14) against 0.003 sound; these sit 4-6x above the sound gaps.
    c.update(name="tiny-published", n_aa_pairs=2, d_model=128, n_heads=2, head_dim=64,
             d_ff=256, limits={"tokens": 0.012, "dpt": 0.004, "pose": 0.03})
    if program_src is not None:
        with open(os.path.join(root, "bench", "program_departs.py"), "x") as f:
            f.write(program_src)
        c["program"] = "program_departs"
    with open(os.path.join(root, "bench", "configs", "tiny-published.json"), "x") as f:
        json.dump(c, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append(dict(bench["configs"][0], name="tiny-published",
                                 file="bench/configs/tiny-published.json"))
    cell = "tiny-published.g8"
    bench["workloads"].append({"name": cell, "config": "tiny-published", "traffic": "g8",
                               "chips": 1, "why": "CPU test"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root, cell


def test_published_weights_hold_qk_norms_and_two_special_sets(tmp_path):
    root, name = _root(tmp_path)
    cell = run.load_cell(name, root=root)
    w = cell.weights.init(cell.config, SEED)
    blk = w["blocks"]["frame"]
    for k in ("qn_g", "qn_b", "kn_g", "kn_b"):
        assert blk[k].shape == (2, 64), k
    assert w["special"].shape == (2, 5, 128)
    # nothing is drawn as 0: the pre-norm's beta and the value bias included
    for k in ("ln1_b", "bv", "qn_b", "kn_b"):
        assert float(abs(blk[k]).min()) > 0, k


@pytest.mark.parametrize("program_src,correct", [
    (None, True), (NO_ROPE, False), (NO_QK_NORM, False), (ONE_SPECIAL_SET, False),
], ids=["published", "program_skips_rope", "program_skips_qk_norm", "one_special_set"])
def test_published_configuration_is_correct_only_with_every_published_form(
        tmp_path, program_src, correct):
    root, name = _root(tmp_path, program_src)
    cell = run.load_cell(name, root=root)
    r = run.measure(cell, SEED, 0.5, False, root=root, require_tpu=False)
    assert r["attempted"] >= 2 and r["failed"] == 0
    assert r["correct"] is correct, r["check"]
