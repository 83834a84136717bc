"""The reduction from a profiler trace to busy time, idle gaps named by
host spans, and summed kernel times."""
import os

import pytest

from bench import xtrace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_union_merges_overlapping_intervals():
    assert xtrace._union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [[0, 3], [5, 8]]


def test_gap_is_named_by_the_innermost_open_span():
    spans = sorted([(0, 100, "bench.wait"), (10, 40, "forward"), (50, 60, "bench.submit")])
    assert xtrace._name_at(20, spans) == "forward"
    assert xtrace._name_at(45, spans) == "bench.wait"
    assert xtrace._name_at(55, spans) == "bench.submit"
    assert xtrace._name_at(150, spans) == "no span"


# A TPU plane with two ops and a host plane with the window and a forward
# span, in nanoseconds: window 1000-6000, ops 1000-3000 and 4000-5000,
# forward 2000-5000.  Busy 3000 of 5000; the gap 3000-4000 lies in the
# forward, the gap 5000-6000 in no span.
SYNTHETIC = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 3000000 duration_ps: 1000000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 4000000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "two_stage_attention" } }
  event_metadata { key: 3 value { id: 3 name: "jit_forward" } } }
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 5000000 }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 3000000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "forward" } } }
"""


def test_reduction_of_a_known_trace():
    from jax.profiler import ProfileData

    r = xtrace.reduce_profile(ProfileData.from_text_proto(SYNTHETIC), "bench.window",
                              ("forward",))
    assert r.n_devices == 1
    assert r.window_s == pytest.approx(5e-6)
    assert r.busy_s == pytest.approx(3e-6)  # the module line is not an op line
    assert r.op_s == pytest.approx({"fusion.1": 2e-6, "two_stage_attention": 1e-6})
    assert r.ops_matching(("two_stage",)) == pytest.approx(1e-6)
    assert [(n, pytest.approx(s)) for n, s in r.gaps] == [("forward", 1e-6), ("no span", 1e-6)]


def test_a_trace_without_the_window_span_is_refused():
    from jax.profiler import ProfileData

    with pytest.raises(ValueError, match="bench.window"):
        xtrace.reduce_profile(ProfileData.from_text_proto(SYNTHETIC.replace(
            '"bench.window"', '"other"')), "bench.window")


def test_kernel_readers_on_the_known_trace():
    """The roofline readers divide the shape-counted work at the roofline
    by the matched ops' device time; the idle reader reads 1 − busy/window."""
    from jax.profiler import ProfileData

    from bench import run, work

    red = xtrace.reduce_profile(ProfileData.from_text_proto(SYNTHETIC), "bench.window")
    cell = run.load_cell("vggt-1b-w4a8.s8-single")
    w = work.scene_work(cell.config, 8, 1369)
    peak = run.peak_for("TPU v5 lite")
    m = run.Measured(config=cell.config, traffic=cell.traffic, peak=peak, work=w,
                     scenes=[{}], window_s=1.0, events=[], stats={}, trace=red)
    attn = work.roofline_s(w["frame_attn_ops"] + w["global_attn_ops"],
                           w["frame_attn_bytes"] + w["global_attn_bytes"], peak)
    assert run.reader("attn_roofline")(m) == pytest.approx(100 * attn / 1e-6)
    assert run.reader("fused_roofline")(m) is None  # no fused op in this trace
    assert run.reader("device_idle_share")(m) == pytest.approx(40.0)


def _recorded():
    """A trace recorded on a TPU v5e ("TPU v5 lite") by a whole harness run
    at smoke widths (2 pairs, d_model 256, 2 frames × 256 patches, batch 2),
    cut to the device's op line and the harness's and program's host spans,
    each op name to its HLO name."""
    import gzip

    from jax.profiler import ProfileData

    with gzip.open(os.path.join(DATA, "tiny_tpu_trace.txt.gz"), "rt") as f:
        return ProfileData.from_text_proto(f.read())


def test_reduction_of_a_recorded_tpu_trace():
    from bench import run

    r = xtrace.reduce_profile(_recorded(), run.WINDOW_SPAN, run.HOST_SPANS)
    assert r.n_devices == 1
    assert r.window_s == pytest.approx(0.318586126)
    assert r.busy_s == pytest.approx(0.118674869)
    assert r.gaps[0] == ("forward", pytest.approx(0.00642675))
    assert {n for n, _ in r.gaps} <= {"forward", "bench.submit", "bench.wait", "no span"}


@pytest.mark.parametrize("metric", ["attn_roofline", "fused_roofline"])
def test_roofline_kernels_are_named_in_a_recorded_tpu_trace(metric):
    """Each roofline reader's name list matches ops the chip ran, all but
    ``quant_matmul``, which only the unfused policies run."""
    import importlib

    kernels = importlib.import_module(f"bench.metrics.{metric}").KERNELS
    r = xtrace.reduce_profile(_recorded(), "bench.window")
    for k in kernels:
        assert (r.ops_matching((k,)) > 0) == (k != "quant_matmul"), k
