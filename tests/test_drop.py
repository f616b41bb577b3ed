"""Paper §4.1-4.2: 1T/2T token-expert dropping semantics."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import drop, gating, moe, reconstruct


def test_one_t_zero_threshold_keeps_all(rng):
    s = jax.random.uniform(rng, (32, 8), minval=1e-3)
    assert bool(drop.one_t_keep(s, 0.0).all())


def test_one_t_monotone_in_threshold(rng):
    s = jax.random.uniform(rng, (64, 8))
    rates = [float(1 - drop.one_t_keep(s, t).mean())
             for t in (0.0, 0.05, 0.1, 0.2, 0.5)]
    assert rates == sorted(rates)


def test_two_t_equal_thresholds_is_one_t(rng):
    """Paper Table 2 note: T2_major == T2_minor degenerates to 1T-Drop —
    EXACTLY, including at score == T. The scores here contain the
    threshold value itself to pin the boundary: 1T keeps strictly-above
    (``one_t_keep``: score > t), so degenerate 2T must too."""
    t = 0.12
    s = jax.random.uniform(rng, (64, 8))
    s = s.at[0, 0].set(t)                    # exact boundary score
    modes = drop.two_t_modes(s, t, t)
    keep1 = drop.one_t_keep(s, t)
    np.testing.assert_array_equal(np.asarray(modes == drop.MODE_FULL),
                                  np.asarray(keep1))
    # the degenerate band (t, t] is empty: no pair may sit in MAJOR-only
    assert not bool((modes == drop.MODE_MAJOR).any())
    # boundary score drops on both paths
    assert int(modes[0, 0]) == drop.MODE_DROP
    assert not bool(keep1[0, 0])


def test_two_t_boundary_scores(rng):
    """Band boundaries are strict > keeps: score == t_major drops, score ==
    t_minor stays MAJOR-only (consistent with ``threshold_to_drop_rate``
    counting score <= t as dropped)."""
    tm, tn = 0.05, 0.1
    s = jnp.array([[tm, tn, tm - 1e-6, tn + 1e-6]])
    modes = np.asarray(drop.two_t_modes(s, tm, tn))[0]
    np.testing.assert_array_equal(
        modes, [drop.MODE_DROP, drop.MODE_MAJOR, drop.MODE_DROP,
                drop.MODE_FULL])


def test_two_t_degeneracy_property(rng):
    """Property: for random thresholds t, 2T(t, t) keep masks (both halves)
    equal the 1T expansion bit for bit — on scores salted with exact
    threshold values."""
    for seed in range(5):
        k1, k2 = jax.random.split(jax.random.fold_in(rng, seed))
        t = float(jax.random.uniform(k1, ()))
        s = jax.random.uniform(k2, (32, 4))
        s = s.at[0, :2].set(t)               # exact boundary scores
        idx = jnp.tile(jnp.arange(4)[None], (32, 1))
        combine = jnp.full((32, 4), 0.25)
        p2 = drop.expand_pairs_2t(idx, combine, s, 2, t, t)
        p1 = drop.expand_pairs_1t(idx, combine, s, 2, t)
        np.testing.assert_array_equal(np.asarray(p2.keep),
                                      np.asarray(p1.keep))
        np.testing.assert_array_equal(np.asarray(p2.modes),
                                      np.asarray(p1.modes))


def test_two_t_mode_bands(rng):
    s = jnp.array([[0.01, 0.08, 0.2]])
    modes = drop.two_t_modes(s, 0.05, 0.1)
    np.testing.assert_array_equal(np.asarray(modes)[0], [0, 1, 2])


def test_expand_pairs_major_minor_masks():
    idx = jnp.array([[2]])
    combine = jnp.array([[0.6]])
    for score, exp_keep in [(0.2, [True, True]),      # full
                            (0.08, [True, False]),    # major only
                            (0.01, [False, False])]:  # dropped
        pairs = drop.expand_pairs_2t(idx, combine, jnp.array([[score]]),
                                     2, 0.05, 0.1)
        np.testing.assert_array_equal(np.asarray(pairs.keep)[0], exp_keep)
        np.testing.assert_array_equal(np.asarray(pairs.idx)[0], [4, 5])
        np.testing.assert_allclose(np.asarray(pairs.combine)[0], [0.6, 0.6])


def test_drop_rate_and_flops_saved(rng):
    idx = jnp.zeros((100, 1), jnp.int32)
    combine = jnp.ones((100, 1))
    score = jnp.linspace(0, 1, 100)[:, None]
    pairs = drop.expand_pairs_2t(idx, combine, score, 2, 0.25, 0.75)
    # ~25% fully dropped, ~50% major-only, ~25% full
    fs = float(drop.flops_saved_fraction(pairs.modes))
    assert 0.4 < fs < 0.6
    dr = float(drop.drop_rate(pairs))
    assert 0.4 < dr < 0.6


def test_threshold_drop_rate_map_monotone(rng):
    s = jax.random.uniform(rng, (256, 8))
    ts = jnp.linspace(0, 1, 11)
    rates = np.asarray(drop.threshold_to_drop_rate(s, ts))
    assert np.all(np.diff(rates) >= 0)
    assert rates[0] <= 0.01 and rates[-1] >= 0.99


def test_2t_reconstruct_less_error_than_1t(rng, moe_cfg, moe_params,
                                           calib_x):
    """The paper's central accuracy claim (Table 2), as an output-error
    statement: at matched FLOPs savings, 2T with reconstruction approximates
    the full model better than 1T.

    Random-init routers produce nearly-uniform top-k scores, so we sharpen
    the gate (x20) to get a realistic score spread, put T¹ at the median
    normalized score, and choose the 2T band (T¹-g, T¹+g) symmetric around
    it — by construction both policies then save ~the same FLOPs."""
    params = dict(moe_params)
    params["wg"] = moe_params["wg"] * 20.0
    x = calib_x[:64]
    y_full = moe.moe_forward_ref(params, x, moe_cfg)
    r = gating.route(x, params["wg"], moe_cfg.top_k,
                     moe_cfg.router_norm_topk)
    rec = reconstruct.partition_and_reconstruct(params, x, moe_cfg, p=2)

    t1 = float(jnp.quantile(r.norm_score, 0.5))
    gap = float(jnp.quantile(r.norm_score, 0.6)) - t1
    pairs_1t = drop.expand_pairs_1t(r.idx, r.combine, r.norm_score, 2, t1)
    pairs_2t = drop.expand_pairs_2t(r.idx, r.combine, r.norm_score, 2,
                                    t1 - gap, t1 + gap)
    rate1 = float(drop.drop_rate(pairs_1t))
    rate2 = float(drop.drop_rate(pairs_2t))
    assert abs(rate1 - rate2) < 0.1, (rate1, rate2)
    y1 = moe.moe_forward_ref(rec, x, moe_cfg, pairs=pairs_1t)
    y2 = moe.moe_forward_ref(rec, x, moe_cfg, pairs=pairs_2t)
    e1 = float(jnp.mean((y1 - y_full) ** 2))
    e2 = float(jnp.mean((y2 - y_full) ** 2))
    assert e2 <= e1 * 1.05, f"2T ({e2}) should not be worse than 1T ({e1})"


def test_calibration_dtypes_pinned_under_x64(rng):
    """Regression for the f32-explicit calibration math: even under
    jax_enable_x64 (where bool-means and Python-float thresholds would
    silently promote) every calibration output stays float32. The lint's
    calib/threshold entry traces the same guarantee statically."""
    scores = jax.random.uniform(rng, (16, 8), dtype=jnp.float32)
    with jax.enable_x64(True):
        t = drop.calibrate_threshold(scores, 0.3)
        rates = drop.threshold_to_drop_rate(scores, [0.05, 0.1, 0.2])
        per_layer = drop.calibrate_per_layer_thresholds([scores, scores],
                                                        0.25)
    assert t.dtype == jnp.float32
    assert rates.dtype == jnp.float32
    assert per_layer.dtype == jnp.float32
    assert per_layer.shape == (2, 2)
