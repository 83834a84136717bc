"""``correct`` comes out false when the timed path is broken underneath:
an answer altered where it is produced, and half of a batch answered with
another scene's rows.  The harness runs whole (set-up, window, check) on
a tiny cell on the CPU, with only the look for a chip skipped."""
import pytest

from bench import run
from bench.tests import tiny

SEED = 2**31 + 13


def _alter_answer(slice_result):
    def bad(out, i0, b, n_patches, ns):
        r = slice_result(out, i0, b, n_patches, ns)
        return dict(r, depth=-r["depth"], tokens=r["tokens"] * 1.5)
    return bad


def _half_batch(slice_result):
    def bad(out, i0, b, n_patches, ns):
        return slice_result(out, 0 if i0 % 2 else i0, b, n_patches, ns)
    return bad


@pytest.mark.parametrize("fault", [_alter_answer, _half_batch])
def test_broken_timed_path_is_not_correct(tmp_path, monkeypatch, fault):
    from repro.serving import vggt_engine

    root = tiny.make_root(tmp_path, check_scenes=1000)
    cell = run.load_cell(tiny.CELL, root=root)
    monkeypatch.setattr(vggt_engine, "_slice_result", fault(vggt_engine._slice_result))
    r = run.measure(cell, SEED, 0.5, False, root=root, require_tpu=False)
    assert r["attempted"] >= 2 and r["failed"] == 0
    assert not r["correct"], r["check"]
