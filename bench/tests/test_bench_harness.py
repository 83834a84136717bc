"""The harness finds cells, configurations, traffic and metrics by name,
and refuses to measure without a TPU."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import run
from bench.tests import tiny


def test_every_cell_of_benchmark_json_loads_with_its_metrics():
    with open(os.path.join(tiny.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for w in bench["workloads"]:
        cell = run.load_cell(w["name"])
        assert cell.config["name"] == w["config"]
        assert {m["name"] for m in cell.end_to_end} == {m["name"] for m in bench["end_to_end"]}
        for m in cell.per_layer:
            assert callable(run.reader(m["name"]))
            assert m["moves"] in {e["name"] for e in cell.end_to_end}


def test_new_files_are_picked_up_without_a_code_edit(tmp_path):
    root = tiny.make_root(tmp_path)
    with open(os.path.join(root, "bench", "traffic", "t4.json"), "w") as f:
        json.dump({"loop": "closed", "clients": 4, "frames": 3, "patches": 8,
                   "max_batch": 4, "scene_pool": 4, "check_scenes": 1}, f)
    with open(os.path.join(root, "bench", "metrics", "scenes_done.py"), "w") as f:
        f.write("def read(m):\n    return float(len(m.scenes)) or None\n")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["workloads"].append({"name": "tiny.t4", "config": "tiny", "traffic": "t4",
                               "chips": 1, "why": "new"})
    bench["per_layer"].append({"name": "scenes_done", "unit": "scenes", "better": "higher",
                               "source": "program_counter", "layer": "entry",
                               "moves": "frames_per_s", "workloads": ["tiny.t4"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    cell = run.load_cell("tiny.t4", root=root)
    assert cell.traffic["clients"] == 4 and cell.config["name"] == "tiny"
    assert [m["name"] for m in cell.per_layer] == ["scenes_done"]
    m = run.Measured(config=cell.config, traffic=cell.traffic, peak=None, work={},
                     scenes=[{}] * 3, window_s=1.0, events=[], stats={}, trace=None)
    assert run.reader("scenes_done", root=root)(m) == 3.0
    assert run.layer_metrics(cell, m, root) == {"scenes_done": {"value": 3.0, "unit": "scenes"}}


def test_a_listed_metric_that_reads_nothing_ends_the_run(tmp_path):
    root = tiny.make_root(tmp_path)
    cell = run.load_cell(tiny.CELL, root=root)
    m = run.Measured(config=cell.config, traffic=cell.traffic, peak=None, work={},
                     scenes=[], window_s=1.0, events=[], stats={}, trace=None)
    assert run.reader("device_idle_share", root=root)(m) is None  # no trace to read
    with pytest.raises(run.MissingMetric, match="read nothing in tiny.t2"):
        run.layer_metrics(cell, m, root)


def _bench_cli(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "vggt-1b-w4a8.s8-single",
         "--seed", str(2**31 + 5), "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_measuring_entry_without_a_tpu_exits_nonzero_and_prints_no_result():
    r = _bench_cli(tiny.ROOT)
    assert r.returncode == 3, r.stderr[-2000:]
    assert r.stdout.strip() == ""
    assert "finds no TPU" in r.stderr


def test_entry_with_only_the_benchmark_files_exits_nonzero(tmp_path):
    shutil.copy(os.path.join(tiny.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(tiny.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    r = _bench_cli(tmp_path)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
