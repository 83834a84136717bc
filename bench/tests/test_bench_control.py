"""``correct`` on a tiny cell, on the CPU: a sound run of the program
passes; the control, the program's own next lower precision (W4A4 for the
W4A8 configuration), fails.  The limits are the real configuration's,
set from readings at the cells' own sizes (``bench/readings.py``).  At
d_model 256 the control reads 0.19 in tokens and 0.24-0.29 in pose at
two pairs; at 128 its tokens come as close to the limit as 0.13."""
from bench import run
from bench.tests import tiny

SEED = 2**31 + 11


def test_sound_run_is_correct_and_its_control_is_not(tmp_path):
    root = tiny.make_root(tmp_path, d_model=256)
    cell = run.load_cell(tiny.CELL, root=root)
    sound = run.measure(cell, SEED, 0.5, False, root=root, require_tpu=False)
    assert sound["correct"], sound["check"]
    assert sound["failed"] == 0 and sound["attempted"] >= 2
    assert list(sound)[-1] == "check"
    low = run.measure(cell, SEED, 0.5, False, root=root, require_tpu=False, policy="w4a4:fused")
    assert not low["correct"], low["check"]
