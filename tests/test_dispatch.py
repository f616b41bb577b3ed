"""Sort-based mode-ordered dispatch (core.dispatch): equivalence with the
one-hot-cumsum oracle, bit-exact moe_forward_dispatch behaviour, and the
counts_major wiring into the dual-sparse kernel on the dispatch and S-ETP
production paths."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import hypothesis.strategies as st
from hypothesis import given

from repro.core import dispatch as D
from repro.core import drop, gating, moe, setp
from repro.core.policy import TwoTDrop
from repro.kernels import ops as kops


# ---------------------------------------------------------------------------
# Property: sort_dispatch == cumsum_dispatch (plans, buffers, overflow)
# ---------------------------------------------------------------------------

@st.composite
def dispatch_cases(draw):
    n = draw(st.sampled_from([1, 7, 64, 300, 1024]))
    g = draw(st.sampled_from([1, 3, 8, 32]))
    cap = draw(st.sampled_from([1, 4, 16, 64]))
    keep_p = draw(st.floats(0.0, 1.0))
    major_p = draw(st.floats(0.0, 1.0))
    with_modes = draw(st.booleans())
    seed = draw(st.integers(0, 2 ** 16))
    return n, g, cap, keep_p, major_p, with_modes, seed


@given(dispatch_cases())
def test_sort_matches_cumsum_oracle(case):
    n, g, cap, keep_p, major_p, with_modes, seed = case
    key = jax.random.PRNGKey(seed)
    ks = jax.random.split(key, 4)
    group = jax.random.randint(ks[0], (n,), 0, g)
    keep = jax.random.bernoulli(ks[1], keep_p, (n,))
    major = (jax.random.bernoulli(ks[2], major_p, (n,)) & keep) \
        if with_modes else None
    a = D.sort_dispatch(group, keep, n_groups=g, capacity=cap,
                        major_only=major)
    b = D.cumsum_dispatch(group, keep, n_groups=g, capacity=cap,
                          major_only=major)
    for name in ("perm", "group_offsets", "counts_full", "counts_major",
                 "group", "slot", "overflow"):
        np.testing.assert_array_equal(
            np.asarray(getattr(a, name)), np.asarray(getattr(b, name)),
            err_msg=f"{name} diverges (case {case})")
    # buffer construction: gather (new) == repeat+scatter (old), bit for bit
    x = jax.random.normal(ks[3], (n, 5))
    np.testing.assert_array_equal(
        np.asarray(D.gather_rows(x, a, cap)),
        np.asarray(D.scatter_rows(x, b, cap)))
    # overflow is exactly the kept pairs beyond per-group capacity
    hist = np.bincount(np.asarray(group)[np.asarray(keep)], minlength=g)
    assert int(a.overflow) == int(np.maximum(hist - cap, 0).sum())


def test_mode_ordering_full_rows_first():
    """MAJOR-only pairs seat after every FULL pair of their group, each in
    arrival order — the row layout the dual-sparse kernel requires."""
    group = jnp.asarray([0, 0, 0, 0, 1, 0])
    keep = jnp.asarray([True, True, True, True, True, False])
    major = jnp.asarray([True, False, True, False, False, False])
    plan = D.sort_dispatch(group, keep, n_groups=2, capacity=8,
                           major_only=major)
    # group 0 buffer: FULL pairs 1,3 then MAJOR-only pairs 0,2
    np.testing.assert_array_equal(np.asarray(plan.perm[:4]), [1, 3, 0, 2])
    np.testing.assert_array_equal(np.asarray(plan.counts_full), [2, 1])
    np.testing.assert_array_equal(np.asarray(plan.counts_major), [2, 0])
    np.testing.assert_array_equal(np.asarray(plan.slot), [2, 0, 3, 1, 0, 8])


# ---------------------------------------------------------------------------
# moe_forward_dispatch is bit-exact vs the pre-sort scatter implementation
# ---------------------------------------------------------------------------

def _old_scatter_dispatch(params, x, cfg, pairs, capacity):
    """The pre-sort moe_forward_dispatch math (one-hot cumsum slotting,
    jnp.repeat + scatter buffers), kept as the bit-exactness oracle."""
    T, d = x.shape
    E = params["w1"].shape[0]
    K = pairs.idx.shape[1]
    plan = D.cumsum_dispatch(pairs.idx, pairs.keep, n_groups=E,
                             capacity=capacity)
    buf = D.scatter_rows(x, plan, capacity, index_div=K)
    out_buf = moe.expert_ffn(params["w1"], params["w3"], params["w2"], buf)
    gathered = D.unpermute(out_buf, plan)
    w = (pairs.combine * pairs.keep.astype(pairs.combine.dtype)).reshape(-1)
    y = (gathered * w[:, None].astype(gathered.dtype))
    y = y.reshape(T, K, d).sum(axis=1).astype(x.dtype)
    return y + moe._shared_out(params, x), plan.overflow


@pytest.mark.parametrize("capacity", [4, 64])
def test_dispatch_bit_exact_vs_cumsum_path(rng, moe_cfg, moe_params,
                                           capacity):
    """At EQUAL capacity the sort-based forward must reproduce the old
    cumsum/scatter forward bit for bit — same seats, same drops, same sums
    — including under capacity overflow."""
    x = jax.random.normal(rng, (64, moe_cfg.d_model)) * 0.5
    pairs = moe.route_plain(moe_params, x, moe_cfg)
    y_new, of_new = moe.moe_forward_dispatch(
        moe_params, x, moe_cfg, pairs=pairs, capacity=capacity,
        return_overflow=True)
    y_old, of_old = _old_scatter_dispatch(moe_params, x, moe_cfg, pairs,
                                          capacity)
    np.testing.assert_array_equal(np.asarray(y_new), np.asarray(y_old))
    assert int(of_new) == int(of_old)


# ---------------------------------------------------------------------------
# counts_major reaches the kernel in production (dispatch path)
# ---------------------------------------------------------------------------

def _spying_grouped_swiglu(record):
    orig = kops.grouped_swiglu

    def spy(x, w1, w3, w2, counts_full=None, counts_major=None, **kw):
        def cb(cf, cm):
            record.append((np.asarray(cf), np.asarray(cm)))
        if counts_major is not None:
            jax.debug.callback(cb, counts_full, counts_major)
        return orig(x, w1, w3, w2, counts_full, counts_major, **kw)
    return spy


def _two_t_setup(rng, moe_cfg, moe_params, calib_x):
    """Prepared 2T params + thresholds that actually produce mode-1 pairs
    (router sharpened so normalized scores spread)."""
    from benchmarks.common import sharp_router_params
    params = sharp_router_params(moe_params)
    pol = TwoTDrop(partition_p=2, use_kernel=True)
    prepared, _ = pol.prepare(params, moe_cfg, calib_x)
    r = gating.route(calib_x, params["wg"], moe_cfg.top_k,
                     moe_cfg.router_norm_topk)
    t1 = float(jnp.quantile(r.norm_score, 0.35))
    pol = dataclasses.replace(pol, t_major=t1 - 0.02, t_minor=t1 + 0.02)
    pairs = pol.route(prepared, calib_x, moe_cfg)
    modes = np.asarray(pairs.modes)
    assert (modes == drop.MODE_MAJOR).sum() > 0, \
        "setup must yield MAJOR-only pairs"
    return prepared, pol, pairs


def test_counts_major_reaches_kernel_dispatch_path(rng, moe_cfg, moe_params,
                                                   calib_x, monkeypatch):
    """A 2t policy with use_kernel=True on the dispatch path must hand the
    kernel mode-ordered ORIGINAL-expert buffers with nonzero counts_major,
    skip >0 minor-half tiles, and stay exact vs the dense reference."""
    prepared, pol, pairs = _two_t_setup(rng, moe_cfg, moe_params, calib_x)
    record = []
    monkeypatch.setattr(kops, "grouped_swiglu", _spying_grouped_swiglu(record))
    T = calib_x.shape[0]
    # fused_pipeline=False pins the buffer-kernel path this test spies on
    # (auto would pick the fused pipeline here, which never calls
    # grouped_swiglu)
    y, overflow = moe.moe_forward_dispatch(
        prepared, calib_x, moe_cfg, pairs=pairs, capacity=T,
        use_kernel=True, return_overflow=True,
        mode_grouped=pol.kernel_mode_grouping, fused_pipeline=False)
    y_ref = moe.moe_forward_ref(prepared, calib_x, moe_cfg, pairs=pairs)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=1e-4)
    assert int(overflow) == 0
    assert record, "kernel was never invoked with counts_major"
    cf, cm = record[-1]
    assert cm.sum() > 0, "no MAJOR-only rows reached the kernel"
    # the paper's §4.2 cash-in: whole minor-half MXU tiles never issued
    from benchmarks.bench_kernel_skip import tile_skip_fraction
    f_full = prepared["w1"].shape[-1] * 2
    skip = tile_skip_fraction(cf, cm, T, f_full, block_c=32, block_f=32)
    assert skip > 0.0


def test_fused_kernel_halves_dispatched_pairs(rng, moe_cfg, moe_params,
                                              calib_x):
    """Mode grouping dispatches one row per ORIGINAL pair: the fused plan
    seats at most half the rows of the sub-expert plan at P=2."""
    prepared, pol, pairs = _two_t_setup(rng, moe_cfg, moe_params, calib_x)
    E_sub = prepared["w1"].shape[0]
    sub_plan = D.sort_dispatch(pairs.idx, pairs.keep, n_groups=E_sub,
                               capacity=calib_x.shape[0])
    fused = D.fuse_sub_pairs(pairs, 2)
    fused_plan = D.sort_dispatch(fused.group, fused.keep,
                                 n_groups=E_sub // 2,
                                 capacity=calib_x.shape[0],
                                 major_only=fused.major_only)
    assert int(fused_plan.counts.sum()) < int(sub_plan.counts.sum())


# ---------------------------------------------------------------------------
# counts_major reaches the kernel on the S-ETP path + overflow accounting
# ---------------------------------------------------------------------------

def _one_dev_mesh():
    from repro.launch.mesh import make_host_mesh
    return make_host_mesh(1)


def test_counts_major_reaches_kernel_setp_path(rng, moe_cfg, moe_params,
                                               calib_x, monkeypatch):
    """The S-ETP shard_map body must order each local sub-expert's buffer
    FULL-first/MAJOR-only-second and pass counts_major to the kernel, while
    matching the dense reference."""
    prepared, pol, pairs = _two_t_setup(rng, moe_cfg, moe_params, calib_x)
    # fused_pipeline=False pins the buffer-kernel path this test spies on
    # (auto would pick the fused pipeline here, which never calls
    # grouped_swiglu)
    pol = dataclasses.replace(pol, fused_pipeline=False)
    record = []
    monkeypatch.setattr(kops, "grouped_swiglu", _spying_grouped_swiglu(record))
    mesh = _one_dev_mesh()
    placed = setp.place_params_strided(prepared, 1)
    x3 = calib_x[:64].reshape(1, 64, -1)
    y, overflow = setp.setp_moe_forward(
        placed, x3, moe_cfg, mesh, policy=pol, cap_factor=4.0,
        local_cap_factor=4.0, wire_dtype=jnp.float32, return_overflow=True)
    pairs64 = pol.route(prepared, calib_x[:64], moe_cfg)
    y_ref = moe.moe_forward_ref(prepared, calib_x[:64], moe_cfg,
                                pairs=pairs64)
    np.testing.assert_allclose(np.asarray(y[0]), np.asarray(y_ref),
                               atol=2e-4, rtol=1e-4)
    assert int(overflow) == 0
    assert record, "kernel was never invoked with counts_major on S-ETP"
    cf, cm = record[-1]
    assert cm.sum() > 0, "no MAJOR-only rows reached the S-ETP kernel"


def test_setp_overflow_counter_surfaces(rng, moe_cfg, moe_params, calib_x):
    """Starving the S-ETP capacities must report overflow > 0 (previously
    invisible on this path); ample capacity reports exactly 0."""
    pol = TwoTDrop(partition_p=2, t_major=-1.0, t_minor=-1.0)
    prepared, pol = pol.prepare(moe_params, moe_cfg, calib_x)
    placed = setp.place_params_strided(prepared, 1)
    mesh = _one_dev_mesh()
    x3 = calib_x[:64].reshape(1, 64, -1)
    _, of0 = setp.setp_moe_forward(placed, x3, moe_cfg, mesh, policy=pol,
                                   cap_factor=4.0, local_cap_factor=4.0,
                                   return_overflow=True)
    assert int(of0) == 0
    y, of1 = setp.setp_moe_forward(placed, x3, moe_cfg, mesh, policy=pol,
                                   cap_factor=4.0, local_cap_factor=0.05,
                                   cap_multiple=1, return_overflow=True)
    assert int(of1) > 0
    assert bool(jnp.isfinite(y).all())


# ---------------------------------------------------------------------------
# Fused sub-expert kernel mode vs merged-weight oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("E,C,d,f,P,bc,bf", [
    (2, 32, 32, 64, 2, 16, 16),
    (3, 17, 16, 48, 2, 8, 8),        # C not block-aligned
    (2, 16, 16, 64, 4, 8, 8),        # P = 4
    (1, 8, 8, 24, 2, 8, 8),          # sub width not block-aligned (padding)
])
def test_kernel_p_factor_matches_merged_weights(rng, E, C, d, f, P, bc, bf):
    """p_factor indexing must equal physically re-merging the partitioned
    weights into full-width experts."""
    from repro.core import partition
    from repro.kernels import ref as kref
    ks = jax.random.split(rng, 6)
    x = jax.random.normal(ks[0], (E, C, d)) * 0.5
    w1 = jax.random.normal(ks[1], (E, d, f)) * 0.1
    w3 = jax.random.normal(ks[2], (E, d, f)) * 0.1
    w2 = jax.random.normal(ks[3], (E, f, d)) * 0.1
    cf = jax.random.randint(ks[4], (E,), 0, C // 2 + 1)
    cm = jax.random.randint(ks[5], (E,), 0, C // 2 + 1)
    sub = partition.partial_transform({"w1": w1, "w3": w3, "w2": w2}, P)
    got = kops.grouped_swiglu(x, sub["w1"], sub["w3"], sub["w2"], cf, cm,
                              p_factor=P, block_c=bc, block_f=bf)
    # oracle: full-width weights with the minor region starting at the
    # first sub-expert boundary
    want = kref.grouped_swiglu_ref(x, w1, w3, w2, cf, cm,
                                   n_minor_start=f // P)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_kernel_explicit_n_minor_start_disables_split(rng):
    """n_minor_start == f treats every neuron as MAJOR: counts_major rows
    compute the full group (the S-ETP local-buffer contract)."""
    E, C, d, f = 2, 16, 16, 32
    ks = jax.random.split(rng, 4)
    x = jax.random.normal(ks[0], (E, C, d)) * 0.5
    w1 = jax.random.normal(ks[1], (E, d, f)) * 0.1
    w3 = jax.random.normal(ks[2], (E, d, f)) * 0.1
    w2 = jax.random.normal(ks[3], (E, f, d)) * 0.1
    cf = jnp.asarray([3, 0])
    cm = jnp.asarray([5, 7])
    got = kops.grouped_swiglu(x, w1, w3, w2, cf, cm, n_minor_start=f,
                              block_c=8, block_f=16)
    want = kops.grouped_swiglu(x, w1, w3, w2, cf + cm, None,
                               block_c=8, block_f=16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)
