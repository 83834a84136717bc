"""Dry-run machinery smoke tests on a small fake mesh (subprocess):
make_cell lowers + compiles for each shape kind, and the roofline
extraction returns sane terms."""
from tests.helpers import run_with_devices

from repro.launch.roofline_util import collective_bytes


CELL = """
import jax
from repro.configs import get_config
from repro.launch import specs, roofline_util as ru

mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(jax.sharding.AxisType.Auto,) * 2)
cfg = get_config("qwen3-14b-smoke").with_(d_model=256, n_heads=8, n_kv_heads=2, head_dim=32, d_ff=512)

import dataclasses
for shape_name, bs, seq in (("train_4k", 8, 64), ("prefill_32k", 4, 128), ("decode_32k", 8, 128)):
    sh = dataclasses.replace(specs.SHAPES[shape_name], batch=bs, seq=seq)
    specs.SHAPES[shape_name] = sh
    with mesh:
        cell = specs.make_cell(cfg, shape_name, mesh, unroll=True)
        compiled = jax.jit(cell.fn, in_shardings=cell.in_shardings).lower(*cell.args).compile()
        res = ru.extract(compiled)
    assert res["flops_per_dev"] > 0, shape_name
    assert res["hbm_bytes_per_dev"] > 0, shape_name
    assert res["coll_bytes_per_dev"] > 0, shape_name  # TP always communicates
    print("CELL_OK", shape_name, res["dominant"])
"""


def test_cells_lower_compile_and_extract():
    out = run_with_devices(CELL, n_devices=8, timeout=900)
    assert out.count("CELL_OK") == 3


def test_collective_parser():
    hlo = """
  %all-gather.1 = f32[256,128]{1,0} all-gather(%x), replica_groups=[4,4]<=[16], dimensions={0}
  %all-reduce.2 = bf16[64]{0} all-reduce(%y), replica_groups=[2,8]<=[16]
  %rs = f32[32,16]{1,0} reduce-scatter(%z), replica_groups={{0,1,2,3}}, dimensions={0}
  %other = f32[8]{0} add(%a, %b)
"""
    res = collective_bytes(hlo)
    ag = 256 * 128 * 4 * (3 / 4)
    ar = 2 * 64 * 2 * (7 / 8)
    rs = 32 * 16 * 4 * 3
    assert abs(res["per_kind"]["all-gather"] - ag) < 1
    assert abs(res["per_kind"]["all-reduce"] - ar) < 1
    assert abs(res["per_kind"]["reduce-scatter"] - rs) < 1
    assert res["count"]["all-gather"] == 1


def test_applicability_rules():
    from repro.configs import get_config
    from repro.launch.specs import applicable

    ok, _ = applicable(get_config("qwen3-14b"), "long_500k")
    assert not ok
    ok, _ = applicable(get_config("jamba-v0.1-52b"), "long_500k")
    assert ok
    ok, _ = applicable(get_config("rwkv6-1.6b"), "long_500k")
    assert ok
    for shape in ("train_4k", "prefill_32k", "decode_32k"):
        ok, _ = applicable(get_config("qwen3-14b"), shape)
        assert ok
