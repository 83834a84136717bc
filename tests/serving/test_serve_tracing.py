"""Span-chain completeness across the serving stack: one served request
must leave the full enqueue→admit→prefill→decode→complete chain, kernel
launch counters, and per-site quant-health samples — in both LM
scheduler modes and the VGGT engine (docs/observability.md)."""
import functools

import jax
import pytest

from repro.configs import get_config
from repro.core.precision import PrecisionPlan
from repro.core.versaq import W4A8
from repro.kernels import probe
from repro.models import lm, vggt
from repro.obs import metrics as obs_metrics
from repro.obs import quant_health
from repro.obs import trace as obs_trace
from repro.serving.batching import DeadlineExceeded
from repro.serving.engine import Engine
from repro.serving.vggt_engine import VGGTEngine

KEY = jax.random.PRNGKey(0)
TINY = dict(d_model=64, n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128, vocab_size=64)


@functools.lru_cache(maxsize=1)
def _lm_fixture():
    cfg = get_config("qwen3-14b-smoke").with_(**TINY)
    return cfg, lm.init_params(cfg, KEY)


@functools.lru_cache(maxsize=1)
def _vggt_fixture():
    cfg = get_config("vggt-1b-smoke").with_(
        n_layers=1, d_model=64, n_heads=2, n_kv_heads=2, head_dim=32, d_ff=128,
        layerscale_init=0.2,
    )
    return cfg, vggt.init_params(cfg, KEY)


@pytest.fixture
def tracer():
    tr = obs_trace.Tracer(capacity=1024)
    prev = obs_trace.install(tr)
    try:
        yield tr
    finally:
        obs_trace.install(prev)


def _prompt(cfg, n=8, seed=0):
    return jax.random.randint(jax.random.PRNGKey(seed), (n,), 0, cfg.vocab_size)


def test_lm_continuous_span_chain(tracer):
    cfg, params = _lm_fixture()
    eng = Engine(cfg, params, max_len=32, mode="continuous", max_wait_s=0.0)
    req = eng.enqueue(_prompt(cfg), 4)
    while not req.ready:
        eng.poll()
    eng.flush()
    assert tracer.phases(req.req_id) == [
        "enqueue", "admit", "prefill", "decode", "complete",
    ]
    evs = {e.phase: e for e in tracer.recent(request=req.req_id)}
    assert evs["enqueue"].labels["kind"] == "lm"
    assert evs["enqueue"].labels["prompt_len"] == 8
    assert evs["admit"].labels["mid_decode"] is False
    assert evs["prefill"].dur_s > 0
    assert evs["decode"].labels["steps"] == 3  # n_steps - 1 decode steps
    assert evs["complete"].dur_s > 0


def test_lm_bucket_mode_span_chain(tracer):
    cfg, params = _lm_fixture()
    eng = Engine(cfg, params, max_len=32, mode="bucket", max_wait_s=0.0)
    req = eng.enqueue(_prompt(cfg), 3)
    eng.flush()
    assert tracer.phases(req.req_id) == [
        "enqueue", "admit", "prefill", "decode", "complete",
    ]


def test_vggt_span_chain(tracer):
    cfg, params = _vggt_fixture()
    from repro.data.pipeline import scene_batch

    eng = VGGTEngine(cfg, params, max_wait_s=0.0)
    x = jax.numpy.asarray(scene_batch(1, 2, 8, cfg.d_model, 0)["patches"])
    req = eng.enqueue(x)
    eng.flush()
    assert tracer.phases(req.req_id) == [
        "enqueue", "admit", "forward", "complete",
    ]
    evs = {e.phase: e for e in tracer.recent(request=req.req_id)}
    assert evs["enqueue"].labels["kind"] == "vggt"
    assert evs["forward"].dur_s > 0


def test_vggt_host_phases_are_spans_of_one_forward(tracer):
    """One micro-batched forward leaves one batch-level event for each host
    phase around it, all carrying the same forward ordinal and request
    ids, each lying inside the admit → last complete interval; the
    per-request chains stay as they were, and the per-request forward
    events carry the ordinal and the quant-health callbacks' host time."""
    cfg, params = _vggt_fixture()
    from repro.data.pipeline import scene_batch

    reg = obs_metrics.Registry()
    quant_health.enable(every=1, registry=reg)
    try:
        eng = VGGTEngine(cfg, params, policy=W4A8, max_batch=2, max_wait_s=60.0)
        reqs = [eng.enqueue(jax.numpy.asarray(scene_batch(1, 2, 8, cfg.d_model, i)["patches"]))
                for i in range(2)]
        assert all(r.ready for r in reqs)  # the second fills max_batch and flushes
    finally:
        quant_health.disable()
    ids = [r.req_id for r in reqs]
    for rid in ids:
        assert tracer.phases(rid) == ["enqueue", "admit", "forward", "complete"]
    evs = tracer.recent()
    host = {e.phase: e for e in evs if e.phase.startswith("vggt.")}
    assert sorted(host) == ["vggt.assemble", "vggt.check", "vggt.deliver"]
    assert len([e for e in evs if e.phase.startswith("vggt.")]) == 3
    fwd = [e for e in evs if e.phase == "forward"]
    (ordinal,) = {e.labels["forward"] for e in fwd}
    assert all(e.labels["quant_health_s"] > 0 for e in fwd)
    admit0 = min(e.start_ns for e in evs if e.phase == "admit")
    done = max(e.end_ns for e in evs if e.phase == "complete")
    for e in host.values():
        assert e.request is None
        assert e.labels["forward"] == ordinal and e.labels["requests"] == ids
        assert e.labels["bucket"] == fwd[0].labels["bucket"]
        assert admit0 <= e.start_ns <= e.end_ns
        assert e.dur_s * 1e9 <= e.end_ns - e.start_ns + 1e6
    assert host["vggt.assemble"].end_ns <= fwd[0].start_ns
    assert fwd[0].end_ns <= host["vggt.check"].start_ns
    assert host["vggt.check"].end_ns <= host["vggt.deliver"].start_ns <= done
    assert sum(e.dur_s for e in host.values()) + fwd[0].dur_s <= (done - admit0) * 1e-9


def test_evicted_request_chain_ends_in_evicted(tracer):
    cfg, params = _lm_fixture()
    eng = Engine(cfg, params, max_len=32, mode="continuous", max_wait_s=0.0)
    req = eng.enqueue(_prompt(cfg), 4, deadline_s=0.0)
    eng.flush()
    with pytest.raises(DeadlineExceeded):
        req.result()
    phases = tracer.phases(req.req_id)
    assert phases == ["enqueue", "evicted"]
    (ev,) = [e for e in tracer.recent(request=req.req_id) if e.phase == "evicted"]
    assert ev.labels["error"] == "DeadlineExceeded"


def test_quantized_request_records_kernels_and_quant_health(tracer):
    """The acceptance-criteria completeness check: a single request on the
    kernel-routed quantized path yields the full span chain PLUS nonzero
    per-kernel launch counters and per-site quant-health samples."""
    cfg, params = _lm_fixture()
    reg = obs_metrics.Registry()
    counters = probe.enable_global()
    counters.reset()
    quant_health.enable(every=1, registry=reg)
    try:
        eng = Engine(
            cfg, params, max_len=32, mode="continuous", max_wait_s=0.0,
            policy=PrecisionPlan(default="w8a8", use_kernel=True),
        )
        req = eng.enqueue(_prompt(cfg), 4)
        while not req.ready:
            eng.poll()
        eng.flush()
        jax.effects_barrier()  # quant health ships via jax.debug.callback
        assert tracer.phases(req.req_id) == [
            "enqueue", "admit", "prefill", "decode", "complete",
        ]
        assert counters.by_name().get("quant_matmul", 0) > 0
        sites = quant_health.sites_sampled()
        assert any(s.endswith(".wq") for s in sites)
        assert any(".ffn." in s for s in sites)
        assert reg.get("quant_health_samples_total").total() > 0
        assert reg.get("quant_clip_rate") is not None
    finally:
        quant_health.disable()
        probe.disable_global()


def test_plain_policy_sites_survive_quantization(tracer):
    """prepare_linear threads site paths through QuantPolicy quantization
    too (not only PrecisionPlan), so quant health attributes samples when
    serving a uniformly-quantized model."""
    cfg, params = _lm_fixture()
    reg = obs_metrics.Registry()
    quant_health.enable(every=1, registry=reg)
    try:
        eng = Engine(cfg, params, max_len=32, mode="continuous",
                     max_wait_s=0.0, policy=W4A8)
        req = eng.enqueue(_prompt(cfg), 2)
        while not req.ready:
            eng.poll()
        eng.flush()
        jax.effects_barrier()
        assert quant_health.sites_sampled()
    finally:
        quant_health.disable()


def test_vggt_assemble_span_carries_the_patch_grid(tracer):
    """``vggt.assemble`` labels each forward with the frames' patch grid:
    rows x cols for a 2D-RoPE model, None for the reduced one."""
    from repro.data.pipeline import scene_batch

    pub = get_config("vggt-1b-published-smoke").with_(n_layers=1)
    for cfg, params, grid in ((pub, vggt.init_params(pub, jax.random.PRNGKey(0)), "2x8"),
                              (*_vggt_fixture(), None)):
        eng = VGGTEngine(cfg, params, max_batch=1)
        x = jax.numpy.asarray(scene_batch(1, 2, 16, cfg.d_model, 0)["patches"])
        eng.infer(x, grid=(2, 8))
        (ev,) = [e for e in tracer.recent() if e.phase == "vggt.assemble"][-1:]
        assert ev.labels["grid"] == grid
