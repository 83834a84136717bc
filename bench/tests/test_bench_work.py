"""Work counts at VGGT-1B shapes, and the table of peaks."""
import json
import os

import pytest

from bench import run, work

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _config(name="vggt-1b-w4a8"):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def test_work_of_one_8_frame_scene_at_vggt_1b_shapes():
    """One 8-frame scene of 1,369 patches (10,992 tokens): 13.3 T of
    projections, 11.9 T of global and 1.5 T of frame attention."""
    w = work.scene_work(_config(), frames=8, patches=1369)
    n, t = 8 * 1374, 1374
    assert w["matmul_ops"] == 48 * 2 * n * (1024 * 3072 + 1024 * 1024 + 2 * 1024 * 4096)
    assert w["global_attn_ops"] == 24 * 4 * n * n * 1024
    assert w["frame_attn_ops"] == 24 * 8 * 4 * t * t * 1024
    assert round(w["matmul_ops"] / 1e12, 1) == 13.3
    assert round(w["global_attn_ops"] / 1e12, 1) == 11.9
    assert round(w["frame_attn_ops"] / 1e12, 1) == 1.5
    assert w["total_ops"] > w["matmul_ops"] + w["global_attn_ops"] + w["frame_attn_ops"]


def test_work_scales_with_frames_not_with_batch_padding():
    c = _config()
    one, two = work.scene_work(c, 2, 1369), work.scene_work(c, 4, 1369)
    assert two["matmul_ops"] == 2 * one["matmul_ops"]
    assert two["frame_attn_ops"] == 2 * one["frame_attn_ops"]
    assert two["global_attn_ops"] == 4 * one["global_attn_ops"]


@pytest.mark.parametrize("ops,nbytes,bound", [(393e12, 1.0, "ops"), (1.0, 819e9, "bytes")])
def test_roofline_takes_the_larger_bound(ops, nbytes, bound):
    peak = run.peak_for("TPU v5 lite")
    assert work.roofline_s(ops, nbytes, peak) == pytest.approx(1.0)


def test_peaks_of_v5e_and_unknown_device_kind_raises():
    p = run.peak_for("TPU v5 lite")
    assert (p["int8_ops"], p["bf16_flops"], p["hbm_bytes_per_s"]) == (393e12, 197e12, 819e9)
    with pytest.raises(KeyError, match="not in bench/peaks.json"):
        run.peak_for("cpu")
