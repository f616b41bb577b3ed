"""Streamed fused-pipeline kernel: property coverage vs the buffer-path
oracle and the resident variant.

The streamed kernel (scalar-prefetch SMEM maps, x/out in HBM behind
double-buffered DMA) shares math and accumulation order with the resident
variant it replaced, so the two must agree BIT-FOR-BIT on every layout;
both match the buffer path to tolerance only (per-token K-sum order
differs). Property sweep covers ragged ``T % block_c != 0``, empty
experts, P in {1, 2}, and capacity-overflow pressure — plus a pinned
representative grid naming each edge (hypothesis, 20 examples).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, strategies as st

from repro.core import dispatch as D
from repro.core import gating, moe
from repro.core.policy import TwoTDrop, make_policy
from repro.kernels import ops as kops


def _check_case(seed: int, T: int, E: int, P: int, K: int, block_c: int,
                cap: int, hot: bool = False):
    """One property case: random routing + weights on a (possibly ragged,
    overflowing, or mostly-empty) layout. Streamed must equal resident
    bit-for-bit and match the buffer-path kernel oracle."""
    d, fsub = 16, 32
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    w1 = jax.random.normal(ks[0], (E * P, d, fsub)) * 0.1
    w3 = jax.random.normal(ks[1], (E * P, d, fsub)) * 0.1
    w2 = jax.random.normal(ks[2], (E * P, fsub, d)) * 0.1
    x = jax.random.normal(ks[3], (T, d))
    hi = max(1, E // 4) if hot else E      # hot: most experts stay empty
    group = jax.random.randint(ks[4], (T, K), 0, hi)
    keep = jax.random.bernoulli(ks[5], 0.85, (T, K))
    wts = jax.random.uniform(ks[6], (T, K))
    major = (jax.random.bernoulli(ks[7], 0.3, (T, K)) & keep) \
        if P > 1 else None
    plan = D.sort_dispatch(group, keep, n_groups=E, capacity=cap,
                           major_only=major)
    w = wts * keep
    cf, cm = plan.kernel_counts(cap)
    tok_s, w_s = D.sorted_pair_arrays(plan, w, index_div=K, pad=block_c)
    nms = None if P > 1 else fsub

    # oracle: buffer path (gather -> grouped_swiglu -> unpermute + combine)
    buf = D.gather_rows(x, plan, cap, index_div=K)
    out_buf = kops.grouped_swiglu(buf, w1, w3, w2, counts_full=cf,
                                  counts_major=cm, p_factor=P,
                                  n_minor_start=nms, block_c=block_c,
                                  block_f=32)
    gathered = D.unpermute(out_buf, plan)
    y_ref = (gathered * w.reshape(-1)[:, None]).reshape(T, K, d).sum(1)

    args = (x, w1, w3, w2, plan.group_offsets, cf, cm, tok_s, w_s)
    kw = dict(capacity=cap, p_factor=P, n_minor_start=nms,
              block_c=block_c, block_f=32)
    y_s = kops.fused_moe_pipeline(*args, streamed=True, **kw)
    y_r = kops.fused_moe_pipeline(*args, streamed=False, **kw)
    assert (np.asarray(y_s) == np.asarray(y_r)).all(), (
        f"streamed DMA staging perturbed bits vs resident variant "
        f"(T={T} E={E} P={P} K={K} block_c={block_c} cap={cap})")
    np.testing.assert_allclose(np.asarray(y_s), np.asarray(y_ref),
                               atol=1e-4)


# (seed, T, E, P, K, block_c, cap, hot) — each row names the edge it pins
GRID = [
    (1, 37, 4, 1, 2, 8, 12, False),    # ragged T, overflow pressure
    (2, 40, 3, 1, 2, 16, 48, False),   # ragged expert count, ample cap
    (3, 6, 8, 1, 1, 8, 8, True),       # T < block_c + most experts empty
    (4, 64, 4, 2, 2, 16, 24, False),   # P=2 mode-grouped, overflow
    (5, 33, 4, 2, 2, 8, 64, False),    # P=2 ragged, no overflow
    (6, 128, 8, 2, 2, 32, 16, True),   # P=2 hot experts, heavy overflow
]


@pytest.mark.parametrize("seed,T,E,P,K,block_c,cap,hot", GRID)
def test_streamed_property_grid(seed, T, E, P, K, block_c, cap, hot):
    _check_case(seed, T, E, P, K, block_c, cap, hot)


@st.composite
def streamed_cases(draw):
    # T sampled from a pinned ragged/aligned set (not a free range): the
    # interpret kernels recompile per distinct static shape, so a bounded
    # shape vocabulary keeps the sweep's wall-clock sane via jit caching
    return (draw(st.integers(0, 2 ** 16)),          # seed
            draw(st.sampled_from([5, 13, 37, 40, 64])),  # T, mostly ragged
            draw(st.sampled_from([4, 8])),          # E
            draw(st.sampled_from([1, 2])),          # P
            draw(st.integers(1, 2)),                # K
            draw(st.sampled_from([8, 16])),         # block_c
            draw(st.sampled_from([8, 64])),         # capacity
            draw(st.booleans()))                    # hot (empty experts)


@given(streamed_cases())
def test_streamed_property_sweep(case):
    _check_case(*case)


# ---------------------------------------------------------------------------
# Production fused layout: streamed vs resident at the dispatch level
# ---------------------------------------------------------------------------

def _prod_setup(moe_cfg, moe_params, calib_x):
    from benchmarks.common import sharp_router_params
    params = sharp_router_params(moe_params)
    pol = TwoTDrop(partition_p=2, use_kernel=True, fused_pipeline=True)
    prepared, _ = pol.prepare(params, moe_cfg, calib_x)
    r = gating.route(calib_x, params["wg"], moe_cfg.top_k,
                     moe_cfg.router_norm_topk)
    t1 = float(jnp.quantile(r.norm_score, 0.35))
    pol = dataclasses.replace(pol, t_major=t1 - 0.02, t_minor=t1 + 0.02)
    return prepared, pol, pol.route(prepared, calib_x, moe_cfg)


@pytest.mark.parametrize("capacity", [None, 8])   # ample / overflowing
def test_streamed_equals_resident_production_layout(moe_cfg, moe_params,
                                                    calib_x, capacity):
    prepared, pol, pairs = _prod_setup(moe_cfg, moe_params, calib_x)
    cap = capacity or calib_x.shape[0]
    y_s, ov_s = moe.moe_forward_dispatch(
        prepared, calib_x, moe_cfg, pairs=pairs, capacity=cap,
        fused_pipeline=True, mode_grouped=True, return_overflow=True)
    y_r, ov_r = moe.moe_forward_dispatch(
        prepared, calib_x, moe_cfg, pairs=pairs, capacity=cap,
        fused_pipeline=True, fused_streamed=False, mode_grouped=True,
        return_overflow=True)
    assert (np.asarray(y_s) == np.asarray(y_r)).all()
    assert int(ov_s) == int(ov_r)
    if capacity is not None:
        assert int(ov_s) > 0    # the pressure case must actually overflow


# ---------------------------------------------------------------------------
# Auto heuristic: default-on selection + no retrace on threshold change
# ---------------------------------------------------------------------------

def test_prefer_fused_pipeline_table():
    """TPU: always fused (the streamed kernel's VMEM working set is
    T-independent). CPU interpret: fused iff the buffer path would also
    run interpreted kernels (BENCH_moe_pipeline.json trajectory). Any other
    backend cannot lower the TPU kernel and takes the buffer path."""
    assert D.prefer_fused_pipeline(8192, 64, backend="tpu")
    assert not D.prefer_fused_pipeline(1, 4, backend="gpu")
    assert D.prefer_fused_pipeline(8192, 4, use_kernel=True, backend="cpu")
    assert not D.prefer_fused_pipeline(8192, 4, use_kernel=False,
                                       backend="cpu")
    assert not D.prefer_fused_pipeline(64, 8, backend="cpu")


def test_auto_hint_no_retrace_on_threshold_change(moe_cfg, moe_params,
                                                  calib_x):
    """fused_pipeline=None resolves INSIDE jit from static shape/backend
    facts only — flipping traced threshold leaves must not retrace."""
    prepared, pol, _ = _prod_setup(moe_cfg, moe_params, calib_x)
    pol = dataclasses.replace(pol, fused_pipeline=None)
    traces = []

    @jax.jit
    def fwd(params, x, policy):
        traces.append(1)
        pairs = policy.route(params, x, moe_cfg)
        return moe.moe_forward_dispatch(
            params, x, moe_cfg, pairs=pairs, capacity=x.shape[0],
            use_kernel=True, mode_grouped=policy.kernel_mode_grouping,
            fused_pipeline=policy.fused_pipeline)

    x = calib_x[:32]
    fwd(prepared, x, pol)
    assert len(traces) == 1
    moved = dataclasses.replace(pol, t_major=pol.t_major + 0.01,
                                t_minor=pol.t_minor + 0.01)
    fwd(prepared, x, moved)
    assert len(traces) == 1, "threshold change must not retrace"


# ---------------------------------------------------------------------------
# Metrics counters ride unchanged through the streamed path
# ---------------------------------------------------------------------------

def test_metrics_counters_parity_fused_vs_buffer(moe_cfg, moe_params,
                                                 calib_x):
    """kept_full/kept_major/dropped_pairs come from the routing (shared),
    but overflow_pairs and the expert_load histogram flow through the
    dispatch path — the streamed fused path must report the same stats
    dict as the buffer path on the production fused layout."""
    from benchmarks.common import sharp_router_params
    from repro.launch.mesh import make_host_mesh
    from repro.models import transformer as TF
    params = sharp_router_params(moe_params)
    pol = make_policy("2t", moe_cfg.dualsparse, use_kernel=True,
                      fused_pipeline=True)
    prepared, pol_f = pol.prepare(params, moe_cfg, calib_x)
    pol_b = dataclasses.replace(pol_f, fused_pipeline=False)
    x = calib_x[:64].reshape(1, 64, moe_cfg.d_model)
    mesh = make_host_mesh(1)

    def stats_for(policy):
        dist = TF.DistContext(mesh=mesh, moe_impl="dispatch", policy=policy)
        y, _, stats = TF._moe_forward(prepared, x, moe_cfg, dist,
                                      collect=True)
        return y, stats

    y_f, st_f = stats_for(pol_f)
    y_b, st_b = stats_for(pol_b)
    for key in ("kept_full", "kept_major", "dropped_pairs",
                "overflow_pairs"):
        assert int(st_f[key]) == int(st_b[key]), key
    np.testing.assert_array_equal(np.asarray(st_f["expert_load"]),
                                  np.asarray(st_b["expert_load"]))
    np.testing.assert_allclose(np.asarray(y_f), np.asarray(y_b), atol=1e-4)
