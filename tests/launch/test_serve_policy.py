"""REGRESSION: launch.serve._policy parsed bit-widths by string index
(args.policy[1] / args.policy[3]) — w4a16 mis-parsed as a_bits=1 and any
malformed string crashed with an IndexError or produced garbage bits."""
import argparse

import pytest

from repro.core.versaq import QuantPolicy
from repro.launch.serve import _policy


def _args(policy, method="versaq"):
    return argparse.Namespace(policy=policy, method=method)


def test_fp_is_none():
    assert _policy(_args("fp")) is None


def test_single_digit_bits():
    assert _policy(_args("w4a8")) == QuantPolicy(4, 8, "versaq")
    assert _policy(_args("w4a4", method="rtn")) == QuantPolicy(4, 4, "rtn")


def test_multi_digit_bits():
    # the old string-index parse read a_bits='1' out of 'w4a16'
    assert _policy(_args("w4a16")) == QuantPolicy(4, 16, "versaq")
    assert _policy(_args("w8a16")) == QuantPolicy(8, 16, "versaq")


def test_case_and_whitespace_tolerant():
    assert _policy(_args(" W4A8 ")) == QuantPolicy(4, 8, "versaq")


@pytest.mark.parametrize("bad", ["w4", "a8", "w4b8", "4a8", "w4a", "quux",
                                 "w4a8x", "", "wXaY"])
def test_malformed_policy_raises(bad):
    with pytest.raises(ValueError, match="policy"):
        _policy(_args(bad))


def _targs(tiers, method="versaq"):
    return argparse.Namespace(tiers=tiers, method=method)


def test_tiers_none_passthrough():
    from repro.launch.serve import _tiers

    assert _tiers(_targs(None), None, None) is None
    assert _tiers(_targs(""), None, None) is None


def test_tiers_parse_fp_and_uniform():
    from repro.launch.serve import _tiers

    t = _tiers(_targs("quality=fp, balanced=W4A8"), None, None)
    assert t == {"quality": None, "balanced": QuantPolicy(4, 8, "versaq")}


def test_tiers_parse_plan_runs_planner():
    import jax

    from repro.configs import get_config
    from repro.core.precision import PrecisionPlan
    from repro.launch.serve import _tiers
    from repro.models import vggt

    cfg = get_config("vggt-1b-smoke")
    params = vggt.init_params(cfg, jax.random.PRNGKey(0))
    t = _tiers(_targs("fast=plan"), cfg, params)
    assert isinstance(t["fast"], PrecisionPlan)
    assert t["fast"].name == "fast"


@pytest.mark.parametrize("bad", ["fast", "=w4a8", "fast=", "fast=w4b8"])
def test_tiers_malformed_raises(bad):
    from repro.launch.serve import _tiers

    with pytest.raises(ValueError):
        _tiers(_targs(bad), None, None)


@pytest.mark.parametrize("flags,fails", [
    ([], True),
    (["--faults", "nan@scene:req=0"], False),
    (["--max-pending", "2"], False),
    (["--deadline-s", "1"], False),
])
def test_failed_request_exits_nonzero_unless_expected(monkeypatch, flags, fails):
    """A request that fails makes the launcher exit non-zero, except under
    the flags whose point is to make requests fail."""
    import sys

    from repro.launch import serve

    monkeypatch.setattr(serve, "configure_compile_cache", lambda: None)
    monkeypatch.setattr(serve, "serve_vggt", lambda cfg, args: (3, 4))
    monkeypatch.setattr(sys, "argv", ["serve", "--arch", "vggt-1b-smoke", *flags])
    if fails:
        with pytest.raises(SystemExit, match="1 of 4 requests failed"):
            serve.main()
    else:
        serve.main()
