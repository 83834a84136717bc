"""A configuration brings its own weights, program and reference modules
as new files, with no edit to a file of ``bench/``: each imports the
shared module and replaces one seam.  The variant here stores q, k and v
as one fused tensor, as published checkpoints do (``attn.qkv``); its
program and reference modules split it again.  The harness runs whole on
a tiny cell on the CPU, with only the look for a chip skipped."""
import json
import os

import pytest

from bench import run
from bench.tests import tiny

SEED = 2**31 + 17

SPLIT = '''
import jax.numpy as jnp


def split(p):
    """One block's fused ``wqkv``/``bqkv`` as the shared ``wq``..``bv``."""
    p = dict(p)
    for fused, names in (("wqkv", ("wq", "wk", "wv")), ("bqkv", ("bq", "bk", "bv"))):
        p.update(zip(names, jnp.split(p.pop(fused), 3, axis=-1)))
    return p
'''

WEIGHTS = '''
import jax.numpy as jnp

from bench import weights


def _block(key, c):
    p = weights._block(key, c)
    p["wqkv"] = jnp.concatenate([p.pop("wq"), p.pop("wk"), p.pop("wv")], axis=-1)
    p["bqkv"] = jnp.concatenate([p.pop("bq"), p.pop("bk"), p.pop("bv")], axis=-1)
    return p


def init(config, seed):
    return weights.init(config, seed, block=_block)
'''

PROGRAM = SPLIT + '''
from bench import program
from bench.program import shutdown, telemetry  # noqa: F401


def to_params(w):
    return program.to_params(dict(w, blocks={k: split(v) for k, v in w["blocks"].items()}))


def serve(config, w, max_batch, policy=None):
    return program.serve(config, w, max_batch, policy, to_params=to_params)
'''

REFERENCE = SPLIT + '''
from bench import reference


class Reference(reference.Reference):
    def _prep_block(self, p):
        return super()._prep_block(split(p))
'''

# the same, but it leaves out LayerScale (as if it were 1)
DEPARTS = SPLIT + '''
from bench import reference


class Reference(reference.Reference):
    def _prep_block(self, p):
        return super()._prep_block(dict(split(p), ls1=0 * p["ls1"] + 1, ls2=0 * p["ls2"] + 1))
'''


def _add_variant(root, reference_src) -> str:
    """Add configuration ``tiny-qkv`` and its cell, writing only new files
    (mode ``x``) and BENCHMARK.json; returns the cell."""
    for name, src in (("weights_alt", WEIGHTS), ("program_alt", PROGRAM),
                      ("reference_alt", reference_src)):
        with open(os.path.join(root, "bench", name + ".py"), "x") as f:
            f.write(src)
    with open(os.path.join(root, "bench", "configs", "tiny.json")) as f:
        c = json.load(f)
    c.update(name="tiny-qkv", weights="weights_alt", program="program_alt",
             reference="reference_alt")
    with open(os.path.join(root, "bench", "configs", "tiny-qkv.json"), "x") as f:
        json.dump(c, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append(dict(bench["configs"][0], name="tiny-qkv",
                                 file="bench/configs/tiny-qkv.json"))
    bench["workloads"].append({"name": "tiny-qkv.t2", "config": "tiny-qkv", "traffic": "t2",
                               "chips": 1, "why": "CPU test"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return "tiny-qkv.t2"


@pytest.mark.parametrize("reference_src,correct", [(REFERENCE, True), (DEPARTS, False)],
                         ids=["agrees", "reference_departs"])
def test_a_configuration_added_as_new_files_runs_its_own_modules(tmp_path, reference_src,
                                                                 correct):
    root = tiny.make_root(tmp_path, check_scenes=1000)
    cell = run.load_cell(_add_variant(root, reference_src), root=root)
    for kind in run.MODULES:
        assert getattr(cell, kind).__file__ == os.path.join(root, "bench", kind + "_alt.py")
    w = cell.weights.init(cell.config, SEED)
    assert "wqkv" in w["blocks"]["frame"] and "wq" not in w["blocks"]["frame"]
    r = run.measure(cell, SEED, 0.5, False, root=root, require_tpu=False)
    assert r["attempted"] >= 2 and r["failed"] == 0
    assert r["correct"] is correct, r["check"]
