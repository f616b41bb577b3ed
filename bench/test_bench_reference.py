"""The benchmark's float32 reference, reached through each configuration's
family module, against the program, on the CPU at a tiny size: one MoE
layer against the program's dense oracle (``moe_forward_ref``), the whole
forward against the program's full forward, the 2T-Drop calibration
against the program's ``prepare``, and the 2T forward against the
program's forward on the prepared weights."""
from __future__ import annotations

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))

from bench import reference, weights  # noqa: E402
from bench.test_bench_tiny import TINY, TINY_COARSE  # noqa: E402

TOL = 2e-4          # float32 at HIGHEST precision on both sides


@pytest.fixture(scope="module", params=["fine", "coarse"])
def setup(request):
    from bench import run as R
    from bench import system
    cfg = TINY if request.param == "fine" else TINY_COARSE
    fam = R.family(cfg)
    s = fam.sizes(cfg)
    mc = fam.model_config("tiny", cfg, s)
    params = weights.make(fam.layout(s), 5)
    system.check_tree(mc, params)
    return fam, s, mc, params


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def test_moe_layer_matches_program_oracle(setup):
    from repro.core import moe
    fam, s, mc, params = setup
    h = jax.random.normal(jax.random.key(1), (40, s["d"]), jnp.float32)
    layer = jax.tree.map(lambda a: a[1], _f32(params["blocks"]["moe"]))
    with jax.default_matmul_precision("highest"):
        want = moe.moe_forward_ref(layer, h, mc)
    got = reference.moe_layer(h, params["blocks"]["moe"], 1, None, None,
                              s=s, two_t=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=TOL,
                               rtol=TOL)


def test_forward_matches_program(setup):
    from repro.models import transformer
    from repro.serving.engine import exact_moe_dist
    fam, s, mc, params = setup
    tokens = np.random.default_rng(0).integers(0, s["vocab"], 37)
    with jax.default_matmul_precision("highest"):
        logits = np.asarray(transformer.forward(
            _f32(params), {"tokens": jnp.asarray(tokens)[None]}, mc,
            dist=exact_moe_dist(None)))[0]
    mx, tl, _, am = fam.Reference(params, s).rows(tokens, tokens)
    np.testing.assert_allclose(mx, logits.max(-1), atol=TOL, rtol=TOL)
    np.testing.assert_allclose(
        tl, np.take_along_axis(logits, tokens[:, None], -1)[:, 0],
        atol=TOL, rtol=TOL)
    assert (am == logits.argmax(-1)).mean() > 0.95


def _prepared(fam, s, mc, params, calib):
    from bench import system
    mix = {"policy": {"name": "per_layer", "drop_target": 0.25,
                      "delta": 0.05}}
    policy = system.policy_of(mc, mix)
    prepared, dist = system.apply_policy(mc, _f32(params), policy,
                                         jnp.asarray(calib))
    # capacity for every pair, as the engine serves
    dist = dataclasses.replace(dist, policy=dataclasses.replace(
        dist.policy, exact_capacity=True))
    ref2t = fam.calibrate_2t(params, s, list(calib), p=2,
                             importance="abs_gate", drop_target=0.25,
                             delta=0.05)
    return prepared, dist, ref2t


def test_2t_calibration_matches_program(setup):
    fam, s, mc, params = setup
    toks = np.random.default_rng(1).integers(0, s["vocab"], 64)
    calib = fam.Reference(params, s).calibration([toks])
    with jax.default_matmul_precision("highest"):
        prepared, _, ref2t = _prepared(fam, s, mc, params, calib)
    np.testing.assert_allclose(
        np.asarray(prepared["blocks"]["moe"]["thresholds"]),
        np.asarray(ref2t["thresholds"]), rtol=1e-6)
    # the program's major sub-expert 2e holds exactly the reference's
    # major neurons of expert e (as a set: the order within may differ)
    w1 = np.asarray(_f32(params)["blocks"]["moe"]["w1"])
    w1p = np.asarray(prepared["blocks"]["moe"]["w1"])
    major = np.asarray(ref2t["major"])
    for layer in range(s["layers"]):
        for e in range(s["experts"]):
            want = np.sort(w1[layer, e][:, major[layer, e]], axis=1)
            got = np.sort(w1p[layer, 2 * e], axis=1)
            np.testing.assert_array_equal(got, want)
    assert major.sum() == s["layers"] * s["experts"] * s["f"] // 2


def test_2t_forward_matches_program(setup):
    from repro.models import transformer
    fam, s, mc, params = setup
    toks = np.random.default_rng(1).integers(0, s["vocab"], 64)
    calib = fam.Reference(params, s).calibration([toks])
    tokens = np.random.default_rng(2).integers(0, s["vocab"], 45)
    with jax.default_matmul_precision("highest"):
        prepared, dist, ref2t = _prepared(fam, s, mc, params, calib)
        logits = np.asarray(transformer.forward(
            prepared, {"tokens": jnp.asarray(tokens)[None]}, mc,
            dist=dist))[0]
    mx, tl, _, _ = fam.Reference(params, s, ref2t).rows(tokens, tokens)
    np.testing.assert_allclose(mx, logits.max(-1), atol=TOL, rtol=TOL)
    np.testing.assert_allclose(
        tl, np.take_along_axis(logits, tokens[:, None], -1)[:, 0],
        atol=TOL, rtol=TOL)
    # and dropping changed something: the plain forward differs
    mx0, _, _, _ = fam.Reference(params, s).rows(tokens, tokens)
    assert np.abs(mx0 - mx).max() > 10 * TOL
