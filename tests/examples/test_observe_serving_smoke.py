"""The observability example (examples/observe_serving.py) must run
end-to-end on CPU — serve quantized traffic with telemetry on, scrape
/metrics, /stats and /trace over HTTP, and close every span chain in
the JSONL mirror."""
import os
import subprocess
import sys

from tests.helpers import REPO


def test_observe_serving_example_smoke():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env.setdefault("JAX_PLATFORMS", "cpu")
    r = subprocess.run(
        [
            sys.executable,
            os.path.join(REPO, "examples", "observe_serving.py"),
            "--requests", "3", "--prompt-len", "12", "--gen", "4",
        ],
        capture_output=True,
        text=True,
        env=env,
        timeout=480,
    )
    assert r.returncode == 0, f"example failed:\nSTDOUT:{r.stdout}\nSTDERR:{r.stderr}"
    assert "scraped /metrics" in r.stdout
    assert "quant_health_callbacks_total" in r.stdout
    assert "kernel_launches_total" not in r.stdout
    assert "chain=enqueue -> admit -> prefill -> decode -> complete" in r.stdout
    assert "observability tour OK" in r.stdout
