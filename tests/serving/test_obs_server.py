"""AsyncServer telemetry endpoints: /metrics, /stats, /trace, /healthz
served end-to-end over HTTP against real LM traffic (ephemeral port)."""
import functools
import json
import urllib.error
import urllib.request

import jax
import pytest

from repro import obs
from repro.configs import get_config
from repro.models import lm
from repro.obs import metrics as obs_metrics
from repro.serving.engine import Engine
from repro.serving.server import AsyncServer

KEY = jax.random.PRNGKey(0)
TINY = dict(d_model=64, n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128, vocab_size=64)


@functools.lru_cache(maxsize=1)
def _fixture():
    cfg = get_config("qwen3-14b-smoke").with_(**TINY)
    return cfg, lm.init_params(cfg, KEY)


def _get(addr, path):
    host, port = addr
    with urllib.request.urlopen(f"http://{host}:{port}{path}", timeout=30) as r:
        return r.status, r.headers.get("Content-Type", ""), r.read().decode()


def _parse_prometheus(text):
    """Minimal exposition-format parser: every non-comment line must be
    `name[{labels}] value`; returns {series_name: float}."""
    out = {}
    for line in text.strip().splitlines():
        if line.startswith("#"):
            assert line.startswith(("# HELP ", "# TYPE ")), line
            continue
        name, _, value = line.rpartition(" ")
        assert name, line
        out[name] = float(value.replace("+Inf", "inf"))
    return out


@pytest.fixture
def served():
    """One request served through an AsyncServer with a live metrics
    surface on an ephemeral port."""
    cfg, params = _fixture()
    # the latency histogram lives in the process-wide registry: start it
    # empty, so deliveries of servers that ran earlier in this process
    # do not count here
    hist = obs_metrics.default().get("serve_request_latency_seconds")
    if hist is not None:
        hist.clear()
    eng = Engine(cfg, params, max_len=32, mode="continuous", max_wait_s=0.0)
    srv = AsyncServer(eng, metrics_port=0)
    try:
        with srv:
            prompt = jax.random.randint(KEY, (8,), 0, cfg.vocab_size)
            req = srv.submit(prompt, 4)
            srv.result(req, timeout=300)
            yield srv, req
    finally:
        obs.disable_all()


def test_metrics_endpoint_serves_prometheus_text(served):
    srv, _ = served
    status, ctype, body = _get(srv.metrics_address, "/metrics")
    assert status == 200
    assert ctype.startswith("text/plain")
    series = _parse_prometheus(body)
    # engine stats published at scrape time
    assert series['serve_items_total{kind="lm"}'] == 2  # prefill + decode rows
    assert series['serve_admitted_total{kind="lm"}'] == 1
    assert series['serve_pending_requests{kind="lm"}'] == 0
    assert any(n.startswith("serve_bucket_calls_total{") for n in series)
    # the live request-latency histogram observed the delivery
    assert series["serve_request_latency_seconds_count"] == 1
    assert series['serve_request_latency_seconds_bucket{le="+Inf"}'] == 1


def test_stats_endpoint_serves_summary_json(served):
    srv, _ = served
    status, ctype, body = _get(srv.metrics_address, "/stats")
    assert status == 200 and ctype.startswith("application/json")
    s = json.loads(body)
    assert s["kind"] == "lm"
    assert s["totals"]["items"] == 2
    assert s["scheduler"]["admitted"] == 1
    assert s["pending"] == 0
    assert all("p95_ms" in row for row in s["buckets"].values())


def test_trace_endpoint_serves_span_chain(served):
    srv, req = served
    _, _, body = _get(srv.metrics_address, f"/trace?request={req.req_id}")
    events = json.loads(body)
    phases = list(dict.fromkeys(e["phase"] for e in events))
    assert phases == ["enqueue", "admit", "prefill", "decode", "complete"]
    _, _, body = _get(srv.metrics_address, "/trace?n=2")
    assert len(json.loads(body)) == 2


def test_healthz_and_unknown_path(served):
    srv, _ = served
    status, _, body = _get(srv.metrics_address, "/healthz")
    assert status == 200 and body == "ok\n"
    with pytest.raises(urllib.error.HTTPError) as exc:
        _get(srv.metrics_address, "/nope")
    assert exc.value.code == 404


def test_metrics_port_none_means_no_http_surface():
    cfg, params = _fixture()
    eng = Engine(cfg, params, max_len=32)
    with AsyncServer(eng) as srv:
        assert srv.metrics_address is None


def test_custom_registry_is_used():
    cfg, params = _fixture()
    eng = Engine(cfg, params, max_len=32, mode="continuous", max_wait_s=0.0)
    reg = obs_metrics.Registry()
    default_fam = obs_metrics.default().get("serve_admitted_total")
    before = default_fam.value(kind="lm") if default_fam is not None else None
    try:
        with AsyncServer(eng, metrics_port=0, registry=reg) as srv:
            prompt = jax.random.randint(KEY, (8,), 0, cfg.vocab_size)
            srv.result(srv.submit(prompt, 2), timeout=300)
            _, _, body = _get(srv.metrics_address, "/metrics")
        assert 'serve_admitted_total{kind="lm"} 1' in body
        assert reg.get("serve_admitted_total").value(kind="lm") == 1
        # this engine's stats went to the custom registry, not the default
        default_fam = obs_metrics.default().get("serve_admitted_total")
        after = default_fam.value(kind="lm") if default_fam is not None else None
        assert after == before
    finally:
        obs.disable_all()
