"""Hypothesis property-based tests on the system's invariants."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import hypothesis.strategies as st
from hypothesis import given

from repro.core import drop, gating, load_aware, moe, partition
from repro.models.layers import split_params


@st.composite
def moe_shapes(draw):
    d = draw(st.sampled_from([16, 32, 48]))
    e = draw(st.sampled_from([4, 8, 16]))
    f = draw(st.sampled_from([8, 16, 32]))
    k = draw(st.integers(1, min(4, e)))
    p = draw(st.sampled_from([2, 4]))
    seed = draw(st.integers(0, 2 ** 16))
    renorm = draw(st.booleans())
    return d, e, f, k, p, seed, renorm


def _make(d, e, f, seed):
    from repro.configs.base import ModelConfig
    cfg = ModelConfig(arch_id="prop", family="moe", source="", n_layers=1,
                      d_model=d, n_heads=2, n_kv_heads=2, d_ff=f,
                      vocab_size=64, n_experts=e, top_k=1, d_expert=f)
    key = jax.random.PRNGKey(seed)
    params, _ = split_params(moe.make_moe_params(key, cfg))
    x = jax.random.normal(jax.random.fold_in(key, 1), (24, d)) * 0.5
    return cfg, params, x


@given(moe_shapes())
def test_complete_transform_invariant(shapes):
    """∀ (shapes, P): complete transformation preserves outputs (Eq. 11)."""
    d, e, f, k, p, seed, renorm = shapes
    cfg, params, x = _make(d, e, f, seed)
    cfg = dataclasses.replace(cfg, top_k=k, router_norm_topk=renorm)
    y0 = moe.moe_forward_ref(params, x, cfg)
    pc = partition.complete_transform(params, p)
    cfg_p = dataclasses.replace(cfg, n_experts=e * p, top_k=k * p,
                                d_expert=f // p)
    yc = moe.moe_forward_ref(pc, x, cfg_p)
    np.testing.assert_allclose(np.asarray(y0), np.asarray(yc), atol=1e-4)


@given(moe_shapes())
def test_partial_transform_invariant(shapes):
    """∀ (shapes, P): partial transformation + Eq. 12 routing expansion
    preserves outputs (Eq. 13)."""
    d, e, f, k, p, seed, renorm = shapes
    cfg, params, x = _make(d, e, f, seed)
    cfg = dataclasses.replace(cfg, top_k=k, router_norm_topk=renorm)
    y0 = moe.moe_forward_ref(params, x, cfg)
    pp = partition.partial_transform(params, p)
    r = gating.route(x, params["wg"], k, renorm)
    pairs = drop.expand_pairs_2t(r.idx, r.combine, r.norm_score, p, -1., -1.)
    yp = moe.moe_forward_ref(pp, x, cfg, pairs=pairs)
    np.testing.assert_allclose(np.asarray(y0), np.asarray(yp), atol=1e-4)


@given(st.integers(0, 2 ** 16),
       st.floats(0.0, 0.5), st.floats(0.0, 0.4))
def test_two_t_keep_monotone(seed, t_major, gap):
    """Raising either threshold can only drop MORE pairs, and the kept set
    of 2T at (t, t) equals 1T at t."""
    t_minor = t_major + gap
    key = jax.random.PRNGKey(seed)
    s = jax.random.uniform(key, (64, 4))
    idx = jax.random.randint(jax.random.fold_in(key, 1), (64, 4), 0, 8)
    c = jnp.ones((64, 4))
    p1 = drop.expand_pairs_2t(idx, c, s, 2, t_major, t_minor)
    p2 = drop.expand_pairs_2t(idx, c, s, 2, t_major + 0.05, t_minor + 0.05)
    assert bool((p2.keep <= p1.keep).all())


@given(st.integers(0, 2 ** 16), st.integers(2, 8),
       st.floats(0.01, 0.5))
def test_load_aware_threshold_bounds(seed, n_dev, t_max):
    """Step-down thresholds are in [0, t_max] and increase with load."""
    loads = jax.random.uniform(jax.random.PRNGKey(seed), (n_dev,),
                               minval=0.0, maxval=100.0)
    t = load_aware.step_down_thresholds(loads, t_max)
    assert float(t.min()) >= 0.0 and float(t.max()) <= t_max + 1e-6
    order = jnp.argsort(loads)
    ts = np.asarray(t)[np.asarray(order)]
    assert np.all(np.diff(ts) >= -1e-6)


@given(st.integers(0, 2 ** 16))
def test_dispatch_agrees_with_ref_property(seed):
    cfg, params, x = _make(32, 8, 16, seed)
    cfg = dataclasses.replace(cfg, top_k=2)
    y0 = moe.moe_forward_ref(params, x, cfg)
    y1 = moe.moe_forward_dispatch(params, x, cfg, capacity_factor=8.0)
    np.testing.assert_allclose(np.asarray(y0), np.asarray(y1), atol=1e-4)


@given(st.integers(0, 2 ** 16), st.floats(0.0, 0.6), st.floats(0.0, 0.3))
def test_two_t_modes_partition_exactly(seed, t_major, gap):
    """∀ scores/thresholds: MODE_DROP / MODE_MAJOR / MODE_FULL are mutually
    exclusive AND exhaustive — every pair lands in exactly one mode, and each
    mode's membership matches its defining predicate (paper §4.2)."""
    t_minor = t_major + gap
    key = jax.random.PRNGKey(seed)
    s = jax.random.uniform(key, (96, 4))
    modes = np.asarray(drop.two_t_modes(s, t_major, t_minor))
    s = np.asarray(s)
    in_drop = modes == drop.MODE_DROP
    in_major = modes == drop.MODE_MAJOR
    in_full = modes == drop.MODE_FULL
    # exhaustive: no pair escapes the three modes
    assert np.all(in_drop | in_major | in_full)
    # mutually exclusive: exactly one mode per pair
    assert np.all(in_drop.astype(int) + in_major.astype(int)
                  + in_full.astype(int) == 1)
    # each region matches its defining predicate (strict > keeps on both
    # boundaries, matching one_t_keep — see core.drop module docstring)
    np.testing.assert_array_equal(in_full, s > t_minor)
    np.testing.assert_array_equal(in_major, (s > t_major) & (s <= t_minor))
    np.testing.assert_array_equal(in_drop, s <= t_major)
    # the expanded sub-expert keep mask realizes the modes: majors kept for
    # mode>=1, minors kept only for mode 2
    idx = jax.random.randint(jax.random.fold_in(key, 1), (96, 4), 0, 8)
    pairs = drop.expand_pairs_2t(idx, jnp.ones((96, 4)), jnp.asarray(s), 2,
                                 t_major, t_minor)
    keep = np.asarray(pairs.keep).reshape(96, 4, 2)
    np.testing.assert_array_equal(keep[:, :, 0], ~in_drop)
    np.testing.assert_array_equal(keep[:, :, 1], in_full)


@given(st.integers(0, 2 ** 16), st.sampled_from([2, 4]))
def test_one_t_drop_at_zero_keeps_everything(seed, p):
    """1T-Drop with T¹=0 never drops: normalized gating scores are strictly
    positive, so `score > 0` holds for every routed pair."""
    key = jax.random.PRNGKey(seed)
    logits = jax.random.normal(key, (64, 8))
    probs = jax.nn.softmax(logits, axis=-1)
    score, idx = jax.lax.top_k(probs, 4)
    pairs = drop.expand_pairs_1t(idx, score, score, p, 0.0)
    assert bool(pairs.keep.all())
    assert float(drop.drop_rate(pairs)) == 0.0
    assert np.all(np.asarray(pairs.modes) == drop.MODE_FULL)


@given(st.integers(0, 2 ** 16), st.floats(0.0, 0.3))
def test_drop_rate_flops_proportionality(seed, t1):
    """Paper Fig 10: the fraction of dropped token-(sub)expert computations
    equals the fraction of expert FLOPs saved (tensor-granular dropping)."""
    key = jax.random.PRNGKey(seed)
    s = jax.random.uniform(key, (128, 4))
    s = s / s.sum(-1, keepdims=True)
    idx = jax.random.randint(jax.random.fold_in(key, 1), (128, 4), 0, 8)
    c = jnp.ones((128, 4))
    pairs = drop.expand_pairs_2t(idx, c, s, 2, t1 - 0.01, t1 + 0.01)
    dr = float(drop.drop_rate(pairs))
    fs = float(drop.flops_saved_fraction(pairs.modes))
    assert abs(dr - fs) < 1e-5
