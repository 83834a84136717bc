"""VGGTEngine: bucket-cache reuse, padding correctness, micro-batch
split/merge, and the quantized fast path."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.versaq import W4A8
from repro.data.pipeline import scene_batch
from repro.models import vggt
from repro.serving.vggt_engine import Bucket, VGGTEngine

KEY = jax.random.PRNGKey(0)


@functools.lru_cache(maxsize=1)
def _fixture():
    cfg = get_config("vggt-1b-smoke").with_(
        n_layers=1, d_model=64, n_heads=2, n_kv_heads=2, head_dim=32, d_ff=128,
        layerscale_init=0.2,
    )
    return cfg, vggt.init_params(cfg, KEY)


def _scenes(n, frames=2, patches=24, seed=0):
    cfg, _ = _fixture()
    return jnp.asarray(scene_batch(n, frames, patches, cfg.d_model, seed)["patches"])


def test_bucket_cache_reuse_no_recompile():
    """A second request with an already-seen (frames, patches, batch)
    bucket must not compile anything new."""
    cfg, params = _fixture()
    eng = VGGTEngine(cfg, params, batch_buckets=(2, 4))
    eng.infer(_scenes(2, seed=0))
    assert eng.stats.compiles == 1
    eng.infer(_scenes(2, seed=1))  # same bucket -> warm
    assert eng.stats.compiles == 1
    assert eng.stats.calls == 2
    # batch 3 pads into the same b4 bucket as batch 4
    eng.infer(_scenes(3, seed=2))
    eng.infer(_scenes(4, seed=3))
    assert eng.stats.compiles == 2
    b4 = eng.stats.buckets[Bucket(4, 2, 24)]
    assert b4.compiles == 1 and b4.calls == 2 and b4.padded_scenes == 1
    # a genuinely new shape compiles exactly once more
    eng.infer(_scenes(2, frames=3, seed=4))
    assert eng.stats.compiles == 3


def test_batch_padding_matches_unpadded_forward():
    cfg, params = _fixture()
    eng = VGGTEngine(cfg, params, batch_buckets=(4,))
    scenes = _scenes(3, seed=7)
    got = eng.infer(scenes)
    want = vggt.forward(cfg, params, scenes)
    for k in ("pose", "points", "depth", "conf"):
        np.testing.assert_allclose(got[k], want[k], rtol=1e-5, atol=1e-5)


def test_patch_padding_masked_matches_unpadded_forward():
    """pad_patches rounds P up to the bucket and masks the padding out of
    every attention softmax — valid outputs must match the unpadded run."""
    cfg, params = _fixture()
    eng = VGGTEngine(cfg, params, batch_buckets=(2,), pad_patches=True)
    scenes = _scenes(2, patches=20, seed=8)
    got = eng.infer(scenes)
    want = vggt.forward(cfg, params, scenes)
    assert got["points"].shape == want["points"].shape  # padding sliced off
    for k in ("pose", "points", "depth", "conf"):
        np.testing.assert_allclose(got[k], want[k], rtol=2e-4, atol=2e-4)


def test_microbatch_split_merge_roundtrip():
    """Coalesced requests run as ONE forward and each caller gets exactly
    its own scenes back."""
    cfg, params = _fixture()
    eng = VGGTEngine(cfg, params, batch_buckets=(4,), max_batch=4)
    parts = [_scenes(1, seed=10), _scenes(2, seed=11), _scenes(1, seed=12)]
    reqs = [eng.enqueue(s) for s in parts]
    # 1+2+1 == max_batch -> auto-flushed on the last enqueue
    assert all(r.ready for r in reqs)
    assert eng.stats.calls == 1 and eng.stats.scenes == 4
    for s, r in zip(parts, reqs):
        want = vggt.forward(cfg, params, s)
        got = r.result()
        assert got["points"].shape == want["points"].shape
        np.testing.assert_allclose(got["points"], want["points"], rtol=1e-5, atol=1e-5)


def test_mixed_patch_counts_coalesce_with_masking():
    cfg, params = _fixture()
    eng = VGGTEngine(cfg, params, pad_patches=True, max_batch=8)
    a, b = _scenes(2, patches=24, seed=13), _scenes(2, patches=17, seed=14)
    ra, rb = eng.enqueue(a), eng.enqueue(b)
    eng.flush()
    assert eng.stats.calls == 1  # one shared (frames=2, p32) bucket
    for s, r in ((a, ra), (b, rb)):
        want = vggt.forward(cfg, params, s)
        np.testing.assert_allclose(r.result()["points"], want["points"],
                                   rtol=2e-4, atol=2e-4)


def test_poll_flushes_after_deadline():
    cfg, params = _fixture()
    eng = VGGTEngine(cfg, params, max_batch=8, max_wait_s=0.0)
    req = eng.enqueue(_scenes(1, seed=15))
    assert not req.ready
    assert eng.poll() == 1
    assert req.ready


def test_infer_flushes_only_its_own_group():
    """A synchronous infer must not drain unrelated half-full queues."""
    cfg, params = _fixture()
    eng = VGGTEngine(cfg, params, max_batch=8)
    pending = eng.enqueue(_scenes(1, frames=3, seed=20))
    eng.infer(_scenes(1, frames=2, seed=21))
    assert not pending.ready  # other group keeps coalescing
    eng.flush()
    assert pending.ready


def test_failed_microbatch_delivers_error_to_all_owners():
    cfg, params = _fixture()
    eng = VGGTEngine(cfg, params, max_batch=8)
    good = eng.enqueue(_scenes(1, seed=22))
    bad = eng.enqueue(jnp.zeros((1, 2, 24, cfg.d_model + 1)))  # wrong d_model
    with pytest.raises(Exception):
        eng.flush()
    assert good.ready and bad.ready
    with pytest.raises(RuntimeError, match="micro-batch failed"):
        good.result()


def test_oversize_request_served_alone():
    cfg, params = _fixture()
    eng = VGGTEngine(cfg, params, batch_buckets=(1, 2), max_batch=2)
    scenes = _scenes(3, seed=16)
    got = eng.infer(scenes)
    want = vggt.forward(cfg, params, scenes)
    np.testing.assert_allclose(got["points"], want["points"], rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("attn_impl", [None, "two_stage"])
def test_w4a8_engine_tracks_fp(attn_impl):
    """The quantized engine (jnp int emulation and the INT8 Pallas-kernel
    fast path) must track fp32 within the tolerance of the existing quant
    tests (tests/test_system.py uses rel < 0.25)."""
    cfg, params = _fixture()
    fp = VGGTEngine(cfg, params, batch_buckets=(2,))
    q = VGGTEngine(cfg, params, policy=W4A8, attn_impl=attn_impl, batch_buckets=(2,))
    scenes = _scenes(2, seed=17)
    ref = fp.infer(scenes)
    got = q.infer(scenes)
    rel = float(jnp.linalg.norm(got["points"] - ref["points"])
                / jnp.linalg.norm(ref["points"]))
    assert rel < 0.25, rel


def test_two_stage_kernel_close_to_quantized_flash():
    """Routing the quantized model's attention through the INT8 two-stage
    kernel only changes attention numerics (int8 Q/K/V + int8 probs)."""
    cfg, params = _fixture()
    flash = VGGTEngine(cfg, params, policy=W4A8, batch_buckets=(2,))
    ts = VGGTEngine(cfg, params, policy=W4A8, attn_impl="two_stage", batch_buckets=(2,))
    scenes = _scenes(2, seed=18)
    a = flash.infer(scenes)
    b = ts.infer(scenes)
    rel = float(jnp.linalg.norm(a["points"] - b["points"])
                / jnp.linalg.norm(a["points"]))
    assert rel < 0.15, rel


@functools.lru_cache(maxsize=1)
def _published_fixture():
    cfg = get_config("vggt-1b-published-smoke").with_(n_layers=1, layerscale_init=0.2)
    return cfg, vggt.init_params(cfg, KEY)


def _published_scenes(patches, seed):
    cfg, _ = _published_fixture()
    return jnp.asarray(scene_batch(1, 2, patches, cfg.d_model, seed)["patches"])


def test_patch_grid_is_part_of_the_bucket_for_2d_rope():
    """A 2D-RoPE model keys buckets (and micro-batch groups) on the patch
    grid: the square default and an explicit 2 x 8 grid of the same 16
    patches are two compiles with different answers; the reduced model's
    buckets carry no grid."""
    cfg, params = _published_fixture()
    eng = VGGTEngine(cfg, params, max_batch=4)
    x = _published_scenes(16, seed=30)
    square = eng.infer(x)
    wide = eng.infer(x, grid=(2, 8))
    assert set(eng.stats.buckets) == {Bucket(1, 2, 16, grid=(4, 4)), Bucket(1, 2, 16, grid=(2, 8))}
    assert str(Bucket(1, 2, 16, grid=(4, 4))) == "b1xs2xp16xg4x4"
    assert eng.stats.compiles == 2
    assert float(jnp.max(jnp.abs(square["points"] - wide["points"]))) > 1e-4
    np.testing.assert_allclose(square["points"], vggt.forward(cfg, params, x)["points"],
                               rtol=1e-5, atol=1e-5)
    a = eng.enqueue(x)
    b = eng.enqueue(x, grid=(2, 8))
    assert not a.ready and not b.ready and eng.pending == 2  # separate groups
    eng.flush()
    assert eng.stats.calls == 4
    red_cfg, red_params = _fixture()
    red = VGGTEngine(red_cfg, red_params, batch_buckets=(2,))
    red.infer(_scenes(2, seed=31), grid=(4, 6))
    assert set(red.stats.buckets) == {Bucket(2, 2, 24)}


def test_2d_rope_request_without_a_grid_is_refused():
    """24 patches a frame are no square: a 2D-RoPE model refuses the
    request unless it names its grid."""
    cfg, params = _published_fixture()
    eng = VGGTEngine(cfg, params)
    x = _published_scenes(24, seed=32)
    with pytest.raises(ValueError, match="grid"):
        eng.enqueue(x)
    with pytest.raises(ValueError, match="grid"):
        eng.enqueue(x, grid=(5, 5))  # more patches than the frame has
    assert eng.pending == 0
    out = eng.infer(x, grid=(4, 6))
    assert out["points"].shape == (1, 2, 24, 3)
