"""The readers of the program's host spans, its quant-health counter and
the per-role attention kernels: each on synthetic inputs, each silent
where its input is absent, and the span readers on the events of a tiny
window the program served on the CPU."""
import pytest

from bench import run, work, xtrace
from bench.tests import tiny

SPAN_READERS = ("pre_forward_ms", "post_forward_ms", "quant_health_ms")
ROLE_READERS = ("frame_attn_roofline", "global_attn_roofline")


def _measured(events=(), scenes=("r1", "r2"), trace=None, peak=None, w=None):
    return run.Measured(config={}, traffic={}, peak=peak, work=w or {},
                        scenes=[{"request": r, "frames": 2, "latency_s": 1.0} for r in scenes],
                        window_s=1.0, events=list(events), stats={}, trace=trace)


def _batch(phase, forward, requests, dur_s):
    return {"phase": phase, "forward": forward, "requests": list(requests), "dur_s": dur_s,
            "bucket": "b2xs2xp16"}


# Forward 1 served r1 and r2 (both counted), forward 2 served r3 (not
# counted: after the window's close).
EVENTS = [
    {"phase": "admit", "request": "r1"},
    _batch("vggt.assemble", 1, ["r1", "r2"], 0.010),
    {"phase": "forward", "request": "r1", "dur_s": 2.0, "forward": 1, "quant_health_s": 0.004},
    {"phase": "forward", "request": "r2", "dur_s": 2.0, "forward": 1, "quant_health_s": 0.004},
    _batch("vggt.check", 1, ["r1", "r2"], 0.020),
    _batch("vggt.deliver", 1, ["r1", "r2"], 0.005),
    _batch("vggt.assemble", 2, ["r3"], 0.100),
    {"phase": "forward", "request": "r3", "dur_s": 2.0, "forward": 2, "quant_health_s": 0.5},
    _batch("vggt.check", 2, ["r3"], 0.100),
    _batch("vggt.deliver", 2, ["r3"], 0.100),
]


def test_span_readers_keep_only_forwards_that_served_a_counted_scene():
    m = _measured(EVENTS)
    assert run.reader("pre_forward_ms")(m) == pytest.approx(10.0)
    assert run.reader("post_forward_ms")(m) == pytest.approx(25.0)
    assert run.reader("quant_health_ms")(m) == pytest.approx(4.0)


def test_span_readers_average_over_forwards():
    m = _measured(EVENTS, scenes=("r1", "r3"))
    assert run.reader("pre_forward_ms")(m) == pytest.approx(55.0)
    assert run.reader("post_forward_ms")(m) == pytest.approx((25.0 + 200.0) / 2)
    assert run.reader("quant_health_ms")(m) == pytest.approx((4.0 + 500.0) / 2)


@pytest.mark.parametrize("name", SPAN_READERS)
def test_span_readers_read_nothing_without_the_program_s_spans(name):
    """The events a program without these spans and labels leaves (the
    per-request chain only) read as absent, not as zero."""
    plain = [{"phase": p, "request": "r1", "dur_s": 1.0}
             for p in ("enqueue", "admit", "forward", "complete")]
    assert run.reader(name)(_measured(plain)) is None
    assert run.reader(name)(_measured()) is None
    assert run.reader(name)(_measured(EVENTS, scenes=())) is None


# Device ops of one forward as the program names them: each role's two
# launches, the fused kernels, and a fusion; ns offsets in a 10 us window.
ROLE_TRACE = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 3000000 }
    events { metadata_id: 2 offset_ps: 3000000 duration_ps: 500000 }
    events { metadata_id: 3 offset_ps: 3500000 duration_ps: 4000000 }
    events { metadata_id: 4 offset_ps: 7500000 duration_ps: 1000000 }
    events { metadata_id: 5 offset_ps: 8500000 duration_ps: 700000 }
    events { metadata_id: 6 offset_ps: 9200000 duration_ps: 300000 } }
  event_metadata { key: 1 value { id: 1 name: "two_stage_attention_frame_stats.6" } }
  event_metadata { key: 2 value { id: 2 name: "two_stage_attention_frame_out.6" } }
  event_metadata { key: 3 value { id: 3 name: "two_stage_attention_global_stats.6" } }
  event_metadata { key: 4 value { id: 4 name: "two_stage_attention_global_out.6" } }
  event_metadata { key: 5 value { id: 5 name: "fused_ffn.1" } }
  event_metadata { key: 6 value { id: 6 name: "fusion.3" } } }
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } } }
"""


def _role_measured(text=ROLE_TRACE):
    from jax.profiler import ProfileData

    red = xtrace.reduce_profile(ProfileData.from_text_proto(text), run.WINDOW_SPAN)
    cell = run.load_cell("vggt-1b-w4a8.s8-single")
    w = work.scene_work(cell.config, 8, 1369)
    peak = run.peak_for("TPU v5 lite")
    return _measured(trace=red, peak=peak, w=w, scenes=("r1",)), w, peak


@pytest.mark.parametrize("role,device_s", [("frame", 3.5e-6), ("global", 5e-6)])
def test_role_readers_on_a_known_trace(role, device_s):
    m, w, peak = _role_measured()
    want = 100 * work.roofline_s(w[f"{role}_attn_ops"], w[f"{role}_attn_bytes"], peak) / device_s
    assert run.reader(f"{role}_attn_roofline")(m) == pytest.approx(want)


def test_role_ops_split_the_op_set_attn_roofline_reads():
    """Frame and global device seconds add up to the seconds
    ``attn_roofline`` divides by, on the same trace."""
    import importlib

    m, _, _ = _role_measured()
    split = sum(m.trace.ops_matching(importlib.import_module(f"bench.metrics.{n}").KERNELS)
                for n in ROLE_READERS)
    whole = m.trace.ops_matching(importlib.import_module("bench.metrics.attn_roofline").KERNELS)
    assert split == pytest.approx(whole) == pytest.approx(8.5e-6)
    assert run.reader("attn_roofline")(m) is not None


@pytest.mark.parametrize("name", ROLE_READERS)
def test_role_readers_read_nothing_on_unnamed_kernels_or_no_trace(name):
    """A program whose launches carry no role (bare
    ``two_stage_attention.N``) gives the role readers nothing to read."""
    bare = ROLE_TRACE
    for n, named in enumerate(("frame_stats", "frame_out", "global_stats", "global_out")):
        bare = bare.replace(f"two_stage_attention_{named}.6", f"two_stage_attention.{24 + n}")
    m, _, _ = _role_measured(bare)
    assert run.reader("attn_roofline")(m) is not None
    assert run.reader(name)(m) is None
    assert run.reader(name)(_measured()) is None


def test_span_readers_on_a_window_the_program_served(tmp_path):
    """The program's own events, from two scenes served through the
    benchmark's server on the CPU at smoke widths, give every span reader
    something to read."""
    import jax

    from bench import scenes

    seed = 2**31 + 7
    root = tiny.make_root(tmp_path)
    cell = run.load_cell(tiny.CELL, root=root)
    c, tr, program = cell.config, cell.traffic, cell.program
    srv, _ = program.serve(c, cell.weights.init(c, seed), int(tr["max_batch"]))
    try:
        reqs = [srv.submit(jax.device_put(scenes.scene(tr["frames"], tr["patches"],
                                                       c["d_model"], i, seed)))
                for i in range(2)]
        for r in reqs:
            srv.result(r, timeout=300)
        jax.effects_barrier()
        events = program.telemetry()
    finally:
        program.shutdown(srv)
    m = _measured(events, scenes=[r.req_id for r in reqs])
    for name in SPAN_READERS:
        assert run.reader(name)(m) > 0, name


def _recorded():
    """A trace recorded on a TPU v5e ("TPU v5 lite") by a whole harness run
    at smoke widths (2 pairs, d_model 256, 2 frames × 256 patches, batch 2)
    with the program's role-named attention launches and host phase spans,
    cut to the device's op line and the harness's and program's host
    spans, each op name to its HLO name."""
    import gzip
    import os

    from jax.profiler import ProfileData

    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "tiny_tpu_roles_trace.txt.gz")
    with gzip.open(path, "rt") as f:
        return ProfileData.from_text_proto(f.read())


def test_role_readers_on_a_recorded_tpu_trace():
    """On the chip, each role's two launches carry its name, no launch
    keeps the bare name, and the two roles split ``attn_roofline``'s op
    set exactly."""
    import importlib

    r = xtrace.reduce_profile(_recorded(), run.WINDOW_SPAN)
    attn = sorted(n for n in r.op_s if "two_stage_attention" in n)
    assert attn == ["two_stage_attention_frame_out.6", "two_stage_attention_frame_stats.6",
                    "two_stage_attention_global_out.6", "two_stage_attention_global_stats.6"]
    whole = r.ops_matching(importlib.import_module("bench.metrics.attn_roofline").KERNELS)
    parts = [r.ops_matching(importlib.import_module(f"bench.metrics.{n}").KERNELS)
             for n in ROLE_READERS]
    assert all(p > 0 for p in parts)
    assert sum(parts) == pytest.approx(whole)
    cell = run.load_cell("vggt-1b-w4a8.s8-single")
    m = _measured(trace=r, peak=run.peak_for("TPU v5 lite"), scenes=("r1",),
                  w=work.scene_work(dict(cell.config, n_aa_pairs=2, d_model=256, n_heads=4,
                                         head_dim=32, d_ff=512), 2, 256))
    for name in ROLE_READERS:
        assert 0 < run.reader(name)(m) < 100


def test_recorded_tpu_trace_names_idle_gaps_by_the_program_s_host_spans():
    spans = run.HOST_SPANS + ("vggt.assemble", "vggt.check", "vggt.deliver",
                              "quant_health.observe")
    r = xtrace.reduce_profile(_recorded(), run.WINDOW_SPAN, spans)
    assert r.window_s == pytest.approx(0.328732897)
    assert r.busy_s == pytest.approx(0.116193808)
    named = {n for n, _ in r.gaps}
    assert {"vggt.check", "vggt.deliver", "quant_health.observe"} <= named
