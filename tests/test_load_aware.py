"""Paper §4.3: load-aware thresholding in EP."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import load_aware


def test_device_loads():
    hist = jnp.arange(8)           # 8 experts
    loads = load_aware.device_loads(hist, 2)   # 4 devices
    np.testing.assert_array_equal(np.asarray(loads), [1, 5, 9, 13])


def test_step_down_rule():
    loads = jnp.array([10., 20., 30., 40.])    # ideal = 25
    t = load_aware.step_down_thresholds(loads, t_max=0.1)
    np.testing.assert_allclose(np.asarray(t),
                               [0.1 * 10 / 25, 0.1 * 20 / 25, 0.1, 0.1],
                               rtol=1e-6)


def test_overloaded_devices_get_t_max():
    loads = jnp.array([100., 1., 1., 1.])
    t = load_aware.step_down_thresholds(loads, 0.2)
    np.testing.assert_allclose(float(t[0]), 0.2, rtol=1e-6)
    assert np.all(np.asarray(t[1:]) < 0.02)


def test_pair_thresholds_follow_device(rng):
    loads = jnp.array([10., 40.])              # dev1 overloaded
    idx = jnp.array([[0, 3]])                  # expert 0 -> dev0, 3 -> dev1
    t_major, t_minor = load_aware.pair_thresholds(idx, loads, 2, t_max=0.1)
    assert float(t_major[0, 0]) < float(t_major[0, 1])
    np.testing.assert_allclose(np.asarray(t_minor - t_major), 0.02,
                               atol=1e-6)


def test_load_aware_drops_less_at_same_makespan(rng):
    """Core §4.3 property: vs. a uniform T_max threshold, step-down
    thresholds drop FEWER pairs while the post-drop makespan (max device
    load) does not exceed the uniform policy's."""
    D, E_per, T, K = 4, 4, 4096, 2
    E = D * E_per
    k1, k2 = jax.random.split(rng)
    # skewed routing: device 0 heavily loaded
    logits = jax.random.normal(k1, (T, E)) + jnp.where(
        jnp.arange(E) < E_per, 1.5, 0.0)
    from repro.core import gating
    r = gating.top_k_routing(logits, K, renorm=True)
    hist = gating.expert_histogram(r.idx, E)
    loads = load_aware.device_loads(hist, E_per)
    t_max = 0.45

    dev_of = r.idx // E_per
    # uniform threshold policy
    keep_uniform = r.norm_score > t_max
    # load-aware step-down policy
    t_dev = load_aware.step_down_thresholds(loads, t_max)
    keep_la = r.norm_score > t_dev[dev_of]

    def post_loads(keep):
        h = gating.expert_histogram(r.idx, E, keep=keep)
        return load_aware.device_loads(h, E_per)

    ms_uniform = float(load_aware.makespan(post_loads(keep_uniform)))
    ms_la = float(load_aware.makespan(post_loads(keep_la)))
    dropped_uniform = float(1 - keep_uniform.mean())
    dropped_la = float(1 - keep_la.mean())
    assert dropped_la < dropped_uniform
    assert ms_la <= ms_uniform * 1.02


def test_load_aware_dtypes_pinned_under_x64():
    """Regression for the f32-explicit histogram math: an int histogram
    divided/averaged without the explicit casts would promote to f64 under
    jax_enable_x64 (the lint's calib/load_aware entry checks the trace)."""
    with jax.enable_x64(True):
        hist = jnp.arange(8, dtype=jnp.int32)
        loads = load_aware.device_loads(hist, 2)
        ts = load_aware.step_down_thresholds(loads, 0.12)
    assert loads.dtype == jnp.float32
    assert ts.dtype == jnp.float32
