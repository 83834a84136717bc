"""obs unit tests: metrics registry semantics (kinds, labels, renders,
collectors), span tracer ring/JSONL, quant-health sampling, and the
enable_all/disable_all lifecycle."""
import json
import math

import pytest

from repro import obs
from repro.kernels import probe
from repro.obs import metrics, quant_health, trace

# ---------------------------------------------------------------------------
# metrics: counters / gauges / histograms
# ---------------------------------------------------------------------------


def test_counter_inc_set_total_and_labels():
    reg = metrics.Registry()
    c = reg.counter("reqs_total", "requests", ("kind",))
    c.inc(kind="lm")
    c.inc(2, kind="lm")
    c.inc(kind="vggt")
    assert c.value(kind="lm") == 3
    assert c.value(kind="vggt") == 1
    assert c.total() == 4
    c.set_total(10, kind="lm")
    assert c.value(kind="lm") == 10
    with pytest.raises(ValueError):
        c.inc(-1, kind="lm")


def test_label_set_must_match_declaration():
    reg = metrics.Registry()
    c = reg.counter("c_total", "", ("a", "b"))
    with pytest.raises(ValueError):
        c.inc(a="1")  # missing b
    with pytest.raises(ValueError):
        c.inc(a="1", b="2", extra="3")


def test_family_identity_conflicts_raise():
    reg = metrics.Registry()
    reg.counter("thing", "", ("k",))
    with pytest.raises(ValueError):
        reg.gauge("thing", "", ("k",))  # same name, different kind
    with pytest.raises(ValueError):
        reg.counter("thing", "", ("other",))  # same name, different labels
    with pytest.raises(ValueError):
        reg.counter("bad name")  # invalid metric name
    with pytest.raises(ValueError):
        reg.counter("ok_total", "", ("bad-label",))


def test_histogram_buckets_and_renders():
    reg = metrics.Registry()
    h = reg.histogram("lat_seconds", "latency", buckets=(0.01, 0.1, 1.0))
    for v in (0.005, 0.05, 0.05, 5.0):
        h.observe(v)
    assert h.count() == 4
    text = reg.render_prometheus()
    assert '# TYPE lat_seconds histogram' in text
    assert 'lat_seconds_bucket{le="0.01"} 1' in text
    assert 'lat_seconds_bucket{le="0.1"} 3' in text
    assert 'lat_seconds_bucket{le="1"} 3' in text
    assert 'lat_seconds_bucket{le="+Inf"} 4' in text
    assert "lat_seconds_count 4" in text
    assert math.isclose(
        reg.render_json()["lat_seconds"]["series"][0]["sum"], 5.105
    )
    with pytest.raises(ValueError):
        reg.histogram("desc_seconds", buckets=(1.0, 0.5))  # not increasing


def test_prometheus_text_label_escaping_and_format():
    reg = metrics.Registry()
    reg.counter("esc_total", "has \"quotes\"", ("p",)).inc(p='a"b\\c\nd')
    text = reg.render_prometheus()
    assert 'esc_total{p="a\\"b\\\\c\\nd"} 1' in text
    # every non-comment line must be `name{labels} value`
    for line in text.strip().splitlines():
        if not line.startswith("#"):
            name, _, value = line.rpartition(" ")
            assert name and value
            float(value.replace("+Inf", "inf"))


def test_render_json_text_round_trips():
    reg = metrics.Registry()
    reg.gauge("depth", "queue depth", ("kind",)).set(3, kind="lm")
    blob = json.loads(reg.render_json_text())
    assert blob["depth"]["kind"] == "gauge"
    assert blob["depth"]["series"] == [{"labels": {"kind": "lm"}, "value": 3.0}]


def test_collectors_run_at_render_time():
    reg = metrics.Registry()
    pulls = []

    def collector(r):
        pulls.append(1)
        r.gauge("pulled").set(len(pulls))

    reg.register_collector(collector)
    reg.register_collector(collector)  # dedup
    reg.render_prometheus()
    reg.render_json()
    assert pulls == [1, 1]
    assert reg.get("pulled").value() == 2
    reg.unregister_collector(collector)
    reg.render_prometheus()
    assert pulls == [1, 1]


def test_export_kernel_counters():
    reg = metrics.Registry()
    metrics.export_kernel_counters(reg, {"fused_ffn": 3}, {"fused_ffn": 1024})
    assert reg.get("kernel_launches_total").value(kernel="fused_ffn") == 3
    assert reg.get("kernel_modeled_hbm_bytes_total").value(kernel="fused_ffn") == 1024


# ---------------------------------------------------------------------------
# trace: ring buffer, chains, JSONL mirror
# ---------------------------------------------------------------------------


def test_tracer_ring_bounds_and_request_filter():
    tr = trace.Tracer(capacity=4)
    for i in range(10):
        tr.emit("enqueue", request=f"r{i}")
    evs = tr.recent()
    assert len(evs) == 4
    assert [e.request for e in evs] == ["r6", "r7", "r8", "r9"]
    assert [e.request for e in tr.recent(n=2)] == ["r8", "r9"]
    assert [e.phase for e in tr.recent(request="r9")] == ["enqueue"]


def test_tracer_phases_collapse_duplicates_in_order():
    tr = trace.Tracer()
    for phase in ("enqueue", "admit", "prefill", "decode", "decode", "complete"):
        tr.emit(phase, request="r1")
    tr.emit("enqueue", request="r2")
    assert tr.phases("r1") == ["enqueue", "admit", "prefill", "decode", "complete"]
    assert tr.phases("r2") == ["enqueue"]


def test_tracer_jsonl_mirror(tmp_path):
    path = str(tmp_path / "trace.jsonl")
    tr = trace.Tracer(capacity=8, jsonl_path=path)
    tr.emit("enqueue", request="r1", tier="fast")
    tr.emit("complete", request="r1", dur_s=0.5)
    tr.close()
    lines = [json.loads(ln) for ln in open(path)]
    assert [ln["phase"] for ln in lines] == ["enqueue", "complete"]
    assert lines[0]["tier"] == "fast"  # labels merge to the top level
    assert lines[1]["dur_s"] == 0.5
    assert lines[0]["t"] <= lines[1]["t"]  # monotonic ordering


def test_module_emit_is_noop_without_tracer():
    prev = trace.uninstall()
    try:
        assert trace.emit("enqueue", request="r0") is None
        with trace.span("prefill"):  # must not raise either
            pass
    finally:
        trace.install(prev)


def test_install_returns_previous_tracer():
    prev = trace.uninstall()
    try:
        a, b = trace.Tracer(), trace.Tracer()
        assert trace.install(a) is None
        assert trace.install(b) is a
        assert trace.current() is b
        trace.emit("enqueue", request="rx")
        assert len(b.recent()) == 1 and len(a.recent()) == 0
    finally:
        trace.install(prev)


def test_span_emits_duration_event():
    prev = trace.install(trace.Tracer())
    try:
        with trace.span("prefill", request="r7", bucket="b2xl16"):
            pass
        (ev,) = trace.current().recent()
        assert ev.phase == "prefill" and ev.request == "r7"
        assert ev.dur_s >= 0.0
        assert ev.labels == {"bucket": "b2xl16"}
    finally:
        trace.install(prev)


def test_span_event_lines_up_with_its_profiler_annotation(tmp_path):
    """A span's `start_ns`/`end_ns` are on the profiler's clock: its
    TraceAnnotation in the `/host:CPU` plane starts and ends within 1 ms
    of them (plane times are offsets from the trace's start)."""
    import glob
    import os
    import time

    import jax
    from jax.profiler import ProfileData

    prev = trace.install(trace.Tracer())
    try:
        jax.profiler.start_trace(str(tmp_path))
        try:
            with trace.span("vggt.assemble", forward=1):
                time.sleep(0.005)
        finally:
            jax.profiler.stop_trace()
        (ev,) = trace.current().recent()
    finally:
        trace.install(prev)
    assert ev.end_ns - ev.start_ns >= 5_000_000
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    pd = ProfileData.from_file(path)
    env = {k: v for p in pd.planes if p.name == "Task Environment" for k, v in p.stats}
    base = env["profile_start_time"]
    (ann,) = [e for p in pd.planes if p.name == "/host:CPU"
              for line in p.lines for e in line.events if e.name == "vggt.assemble"]
    assert abs(base + ann.start_ns - ev.start_ns) < 1e6
    assert abs(base + ann.end_ns - ev.end_ns) < 1e6


def test_event_start_and_end_ns():
    tr = trace.Tracer()
    ev = tr.emit("enqueue", request="r1")
    assert ev.start_ns == ev.end_ns  # a point event
    d = ev.to_dict()
    assert d["start_ns"] == d["end_ns"] == ev.end_ns
    ev = tr.emit("complete", request="r1", dur_s=0.25)
    assert ev.end_ns - ev.start_ns == 250_000_000
    ev = tr.emit("forward", request="r1", dur_s=0.1, start_ns=5, end_ns=7)
    assert (ev.start_ns, ev.end_ns) == (5, 7)


# ---------------------------------------------------------------------------
# quant_health: host-side sampling
# ---------------------------------------------------------------------------


def test_quant_health_observe_samples_every_nth():
    reg = metrics.Registry()
    quant_health.enable(every=3, registry=reg)
    try:
        for _ in range(7):
            quant_health._observe("blk.wq", 8, 0.125, 2.0, 1)
        # calls 0, 3, 6 sampled
        samples = reg.get("quant_health_samples_total")
        assert samples.value(site="blk.wq", a_bits="8") == 3
        assert reg.get("quant_clip_rate").value(site="blk.wq", a_bits="8") == 0.125
        assert reg.get("quant_overflow_total").value(site="blk.wq", a_bits="8") == 3
        assert quant_health.sites_sampled() == {"blk.wq": 7}
    finally:
        quant_health.disable()
    quant_health._observe("blk.wq", 8, 0.5, 1.0, 0)  # disabled: dropped
    assert quant_health.sites_sampled() == {}


@pytest.mark.parametrize("every", [1, 4, 64])
def test_quant_health_counts_every_callback_and_samples_every_nth(every):
    reg = metrics.Registry()
    quant_health.enable(every=every, registry=reg)
    h0 = quant_health.host_seconds()
    try:
        for _ in range(130):
            quant_health._observe("pair.global.attn.wqkv", 8, 0.1, 3.0, 0)
        lbl = dict(site="pair.global.attn.wqkv", a_bits="8")
        assert reg.get("quant_health_callbacks_total").value(**lbl) == 130
        assert reg.get("quant_health_samples_total").value(**lbl) == -(-130 // every)
        assert quant_health.host_seconds() > h0
    finally:
        quant_health.disable()
    h1 = quant_health.host_seconds()
    quant_health._observe("pair.global.attn.wqkv", 8, 0.1, 3.0, 0)  # disabled
    assert quant_health.host_seconds() == h1


def test_quant_health_enable_validates_every():
    with pytest.raises(ValueError):
        quant_health.enable(every=0)


def test_monitor_is_noop_when_disabled_or_unnamed():
    import jax.numpy as jnp

    quant_health.disable()
    quant_health.monitor("some.site", jnp.ones((2, 4)), 8)  # off: no trace work
    quant_health.enable(every=1, registry=metrics.Registry())
    try:
        quant_health.monitor(None, jnp.ones((2, 4)), 8)  # unnamed site
    finally:
        quant_health.disable()
    assert quant_health.sites_sampled() == {}


# ---------------------------------------------------------------------------
# enable_all / disable_all lifecycle
# ---------------------------------------------------------------------------


def test_enable_all_disable_all_round_trip():
    was_on = obs.enabled()
    obs.disable_all()
    reg = metrics.Registry()
    try:
        tr = obs.enable_all(registry=reg)
        assert obs.enabled()
        assert metrics.live()
        assert quant_health.enabled()
        # trace-time kernel counts are not live telemetry
        assert probe.global_counters() is None
        assert trace.current() is tr
        quant_health._observe("blk.wq", 8, 0.125, 2.0, 0)
        text = reg.render_prometheus()
        assert 'quant_health_callbacks_total{site="blk.wq",a_bits="8"} 1' in text
        assert "kernel_launches_total" not in text
    finally:
        obs.disable_all()
        if was_on:
            obs.enable_all()
    if not was_on:
        assert not obs.enabled()
        assert not metrics.live()
        assert not quant_health.enabled()
        assert probe.global_counters() is None
        assert trace.current() is None
