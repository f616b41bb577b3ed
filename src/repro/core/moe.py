"""MoE layer: params, exact dense reference, and the capacity-based
dispatch path used inside jit/shard_map.

Three forward paths, all fixed-shape / jit-safe:

  * ``moe_forward_ref``       — computes every expert for every token and
    combines with (possibly dropped) weights. Exact oracle, O(T·E) compute.
  * ``moe_forward_dispatch``  — sort-based capacity dispatch
    (``core.dispatch``): gather tokens into (E, C, d) buffers in
    mode-ordered arrival order, batched expert GEMMs, gather back. This is
    the per-device body of S-ETP and the host of the Pallas kernel; under a
    partitioned drop policy with ``use_kernel`` it groups by ORIGINAL
    expert so the dual-sparse kernel skips minor-half MXU tiles.
  * shard_map S-ETP lives in ``core.setp``.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ..models.layers import normal
from . import dispatch as dispatch_mod
from . import gating
from .drop import SubExpertPairs, expand_pairs_2t, MODE_FULL


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------

def make_moe_params(key, cfg, d_expert: Optional[int] = None,
                    n_experts: Optional[int] = None):
    """Param tree (wrapped in Param leaves with logical axes)."""
    d = cfg.d_model
    E = n_experts if n_experts is not None else cfg.n_experts
    f = d_expert if d_expert is not None else cfg.d_expert
    ks = jax.random.split(key, 5)
    p = {
        "wg": normal(ks[0], (d, E), ("embed", None)),
        "w1": normal(ks[1], (E, d, f), ("expert", "embed", "expert_ffn")),
        "w3": normal(ks[2], (E, d, f), ("expert", "embed", "expert_ffn")),
        "w2": normal(ks[3], (E, f, d), ("expert", "expert_ffn", "embed")),
    }
    if cfg.n_shared_experts:
        fs = cfg.n_shared_experts * f
        km = jax.random.split(ks[4], 3)
        p["shared"] = {
            "w1": normal(km[0], (d, fs), ("embed", "ffn")),
            "w3": normal(km[1], (d, fs), ("embed", "ffn")),
            "w2": normal(km[2], (fs, d), ("ffn", "embed")),
        }
    return p


def expert_ffn(w1, w3, w2, x):
    """Batched SwiGLU over experts: x (E, C, d) -> (E, C, d)."""
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", x, w1))
    h = h * jnp.einsum("ecd,edf->ecf", x, w3)
    return jnp.einsum("ecf,efd->ecd", h, w2)


def _shared_out(params, x):
    if "shared" not in params:
        return 0.0
    s = params["shared"]
    h = jax.nn.silu(x @ s["w1"]) * (x @ s["w3"])
    return h @ s["w2"]


# ---------------------------------------------------------------------------
# Routing helpers
# ---------------------------------------------------------------------------

def route_dualsparse(params, x, cfg, *, thresholds=None) -> SubExpertPairs:
    """Routing incl. partial-transformation expansion and 2T-Drop keep mask.

    ``thresholds``: optional (t_major, t_minor) override — each entry may be
    scalar or per-token (T,) for load-aware thresholding.
    Requires params already partial-transformed with cfg.dualsparse.partition_p.
    """
    ds = cfg.dualsparse
    r = gating.route(x, params["wg"], cfg.top_k, cfg.router_norm_topk)
    if thresholds is not None:
        t_major, t_minor = thresholds
    elif "thresholds" in params:
        # per-layer calibrated thresholds (beyond-paper, §5.3.3 future work);
        # stored in the param tree so layer scans slice them automatically
        t_major, t_minor = params["thresholds"][0], params["thresholds"][1]
    else:
        t_major, t_minor = ds.t_major, ds.t_minor
    return expand_pairs_2t(r.idx, r.combine, r.norm_score,
                           ds.partition_p, t_major, t_minor)


def aux_loss_for(params, x, cfg):
    """Switch-style load-balance auxiliary loss for this MoE layer."""
    r = gating.route(x, params["wg"], cfg.top_k, cfg.router_norm_topk)
    E = params["wg"].shape[1]
    return gating.load_balance_aux_loss(r.probs, r.idx, E)


def route_plain(params, x, cfg, n_experts=None) -> SubExpertPairs:
    """Routing with no partition/drop (P=1, keep everything)."""
    E = n_experts if n_experts is not None else params["wg"].shape[1]
    k = cfg.top_k if E == cfg.n_experts else cfg.top_k * (E // cfg.n_experts)
    r = gating.route(x, params["wg"], k, cfg.router_norm_topk)
    return SubExpertPairs(idx=r.idx, combine=r.combine,
                          keep=jnp.ones_like(r.idx, dtype=bool),
                          modes=jnp.full_like(r.idx, MODE_FULL))


# ---------------------------------------------------------------------------
# Reference forward (exact, dense over experts)
# ---------------------------------------------------------------------------

def moe_forward_ref(params, x, cfg, pairs: Optional[SubExpertPairs] = None):
    """Dense oracle: every expert computed for every token.

    x: (T, d). If ``pairs`` is given, combine weights/keep masks come from it
    (sub-expert ids index params' expert axis).
    """
    E = params["w1"].shape[0]
    if pairs is None:
        pairs = route_plain(params, x, cfg, n_experts=E)
    # all-expert outputs: (E, T, d)
    h = jax.nn.silu(jnp.einsum("td,edf->etf", x, params["w1"]))
    h = h * jnp.einsum("td,edf->etf", x, params["w3"])
    outs = jnp.einsum("etf,efd->etd", h, params["w2"])
    w = pairs.combine * pairs.keep.astype(pairs.combine.dtype)   # (T, K')
    sel = jax.nn.one_hot(pairs.idx, E, dtype=w.dtype) * w[..., None]
    y = jnp.einsum("tke,etd->td", sel, outs).astype(x.dtype)
    return y + _shared_out(params, x)


# ---------------------------------------------------------------------------
# Capacity-based dispatch forward (production per-device path)
# ---------------------------------------------------------------------------

def capacity_for(n_tokens: int, k_eff: int, n_experts: int,
                 capacity_factor: float = 1.25, multiple: int = 8) -> int:
    cap = int(capacity_factor * n_tokens * k_eff / n_experts)
    return max(multiple, (cap + multiple - 1) // multiple * multiple)


def dispatch_indices(pairs: SubExpertPairs, n_experts: int, capacity: int):
    """Compute per-pair (expert, slot) coordinates via the sort-based plan
    (``core.dispatch``). Dropped pairs and over-capacity pairs get
    slot == capacity (out of range, discarded).

    Returns ``(flat_e, slot, overflow)`` where ``overflow`` is the scalar
    count of KEPT pairs silently discarded because their expert's capacity
    was exhausted — the quantity a deployment must watch (an overflow drop
    is an accuracy loss the drop policy never sanctioned)."""
    plan = dispatch_mod.dispatch_plan(pairs.idx, pairs.keep,
                                      n_groups=n_experts, capacity=capacity)
    return plan.group, plan.slot, plan.overflow


def _pairs_partition_p(pairs: SubExpertPairs) -> int:
    """Partial-transformation factor encoded in an expanded pair list
    (``modes`` is per ORIGINAL pair, ``idx`` per sub-expert pair)."""
    Kp = pairs.idx.shape[1]
    K = pairs.modes.shape[1]
    return Kp // K if K and Kp % K == 0 else 1


def _sub_pair_overflow(plan, pairs: SubExpertPairs, fused, capacity: int):
    """Capacity-overflow drops of an ORIGINAL-expert (fused) plan counted in
    the canonical unit: SUB-expert pairs. A fused row stands for every kept
    half of its original pair (P when FULL, 1 when MAJOR-only), so counting
    overflowed fused rows 1:1 — as this path used to — under-reports by up
    to P-1 sub-pairs per drop and is incomparable with the sub-pair dispatch
    path and ``_setp_body`` (``engine.overflow_pairs`` mixes units)."""
    T, K = fused.group.shape
    p = pairs.idx.shape[1] // K
    kept_halves = pairs.keep.reshape(T, K, p).sum(-1).astype(jnp.int32)
    overflowed = fused.keep.reshape(-1) & (plan.slot.reshape(-1) >= capacity)
    return jnp.sum(jnp.where(overflowed, kept_halves.reshape(-1), 0))


def _fused_kernel_dispatch(params, x, cfg, pairs: SubExpertPairs, p: int,
                           capacity: int):
    """Original-expert-granularity dispatch for the dual-sparse kernel: one
    row per (token, ORIGINAL expert) pair — halving dispatched pairs at P=2
    — mode-ordered FULL-first/MAJOR-only-second, with ``counts_major``
    driving the kernel's minor-half tile skipping (paper §4.2). Exact
    w.r.t. the sub-expert path under partial transformation (Eq. 13).
    Overflow is reported in SUB-pair units (see ``_sub_pair_overflow``)."""
    from ..kernels import ops as kops
    T, d = x.shape
    E = params["w1"].shape[0] // p
    fused = dispatch_mod.fuse_sub_pairs(pairs, p)
    K = fused.group.shape[1]
    plan = dispatch_mod.dispatch_plan(fused.group, fused.keep,
                                      n_groups=E, capacity=capacity,
                                      major_only=fused.major_only)
    buf = dispatch_mod.gather_rows(x, plan, capacity, index_div=K)
    cf, cm = plan.kernel_counts(capacity)
    out_buf = kops.grouped_swiglu(buf, params["w1"], params["w3"],
                                  params["w2"], counts_full=cf,
                                  counts_major=cm, p_factor=p)
    gathered = dispatch_mod.unpermute(out_buf, plan)            # (T*K, d)
    w = (fused.combine * fused.keep.astype(fused.combine.dtype)).reshape(-1)
    y = (gathered * w[:, None].astype(gathered.dtype))
    overflow = _sub_pair_overflow(plan, pairs, fused, capacity)
    return y.reshape(T, K, d).sum(axis=1), overflow


def _fused_pipeline_block(block_c: int, capacity: int) -> int:
    return min(block_c, capacity)


EXPERT_WEIGHTS = ("w1", "w3", "w2")


def fused_pipeline_on(fused_pipeline: Optional[bool], n_tokens: int,
                      n_experts: int, use_kernel: bool = False) -> bool:
    """Resolve the ``fused_pipeline`` hint: ``None`` defers to
    ``core.dispatch.prefer_fused_pipeline`` (per shape and backend)."""
    if fused_pipeline is None:
        return dispatch_mod.prefer_fused_pipeline(n_tokens, n_experts,
                                                  use_kernel=use_kernel)
    return bool(fused_pipeline)


def reads_layer_stack(w1_shape, n_tokens: int, *,
                      fused_pipeline: Optional[bool] = None,
                      use_kernel: bool = False) -> bool:
    """Whether the MoE forward can hand its kernel the layer-stacked
    expert weights (``w1_shape`` is ``(L, Es, d, f)``) and a layer index
    instead of one layer's slice. Only the streamed fused kernel indexes a
    layer itself, and only where ``f`` needs no padding to its 128-wide
    neuron tiles: padding would copy the whole stack on every call."""
    f = w1_shape[-1]
    return f % min(128, f) == 0 and fused_pipeline_on(
        fused_pipeline, n_tokens, w1_shape[-3], use_kernel)


def _fused_pipeline_dispatch(params, x, cfg, pairs: SubExpertPairs, p: int,
                             capacity: int, mode_grouped: bool,
                             block_c: int = 128, block_f: int = 128,
                             streamed: bool = True, layer=None):
    """The single fused Pallas pipeline (ROADMAP item 4): the kernel
    consumes the DispatchPlan directly — sort permutation + segment counts
    — gathering token rows from the flat (T, d) array, running the
    mode-ordered grouped SwiGLU with minor-half tile skipping, and
    scatter-accumulating combine-weighted outputs per token. Eliminates
    both HBM round-trips of the buffer path (the gather-built
    (E, capacity, d) buffer the kernel re-reads, and the unpermute
    read-back); that path remains as the bit-exactness oracle.

    ``mode_grouped`` (P > 1): one row per ORIGINAL pair, weights fused at
    kernel level via ``p_factor`` BlockSpec indexing. Otherwise rows are
    sub-expert pairs against the weights' native expert axis. Overflow is
    reported in SUB-pair units on both layouts.

    ``layer``: the expert weights are layer-stacked and the kernel reads
    that layer of them in place."""
    from ..kernels import ops as kops
    T, d = x.shape
    bc = _fused_pipeline_block(block_c, capacity)
    n_sub = params["w1"].shape[-3]
    if mode_grouped and p > 1:
        E = n_sub // p
        fused = dispatch_mod.fuse_sub_pairs(pairs, p)
        K = fused.group.shape[1]
        plan = dispatch_mod.dispatch_plan(fused.group, fused.keep,
                                          n_groups=E, capacity=capacity,
                                          major_only=fused.major_only)
        w = fused.combine * fused.keep.astype(fused.combine.dtype)
        overflow = _sub_pair_overflow(plan, pairs, fused, capacity)
        p_factor, n_minor_start = p, None
    else:
        E = n_sub
        K = pairs.idx.shape[1]
        plan = dispatch_mod.dispatch_plan(pairs.idx, pairs.keep,
                                          n_groups=E, capacity=capacity)
        w = pairs.combine * pairs.keep.astype(pairs.combine.dtype)
        overflow = plan.overflow
        p_factor, n_minor_start = 1, params["w1"].shape[-1]
    tok_sorted, w_sorted = dispatch_mod.sorted_pair_arrays(
        plan, w, index_div=K, pad=bc)
    cf, cm = plan.kernel_counts(capacity)
    y = kops.fused_moe_pipeline(
        x, params["w1"], params["w3"], params["w2"], plan.group_offsets,
        cf, cm, tok_sorted, w_sorted, capacity=capacity, p_factor=p_factor,
        n_minor_start=n_minor_start, block_c=block_c, block_f=block_f,
        streamed=streamed, layer=layer)
    return y, overflow


def moe_forward_dispatch(params, x, cfg, pairs: Optional[SubExpertPairs] = None,
                         capacity_factor: float = 1.25,
                         capacity: Optional[int] = None,
                         use_kernel: bool = False,
                         return_overflow: bool = False,
                         mode_grouped: bool = False,
                         fused_pipeline: Optional[bool] = None,
                         fused_streamed: bool = True, layer=None):
    """Sort-based gather -> batched expert GEMM -> gather back. Exact w.r.t.
    the reference whenever no token exceeds capacity.

    With ``use_kernel`` the batched GEMM is the Pallas dualsparse kernel.
    Under a partitioned drop policy (P > 1), ``mode_grouped=True``
    (``SparsityPolicy.kernel_mode_grouping`` supplies it in production)
    additionally groups pairs by ORIGINAL expert so 2T-Drop's MAJOR-only
    rows sort after the FULL rows and ``counts_major`` lets the kernel skip
    minor-half MXU tiles — the §4.2 saving, live in production. Mode
    grouping requires a mode-monotone keep mask (a kept minor half implies
    a kept major half — true of every registered policy); it is opt-in
    (default off) so hand-built pair lists that violate the invariant keep
    the exact per-sub-pair semantics. Without the kernel a jnp einsum
    computes full sub-experts (minor-half skipping then only reduces
    *dispatched* pairs: the minor sub-expert of a mode-1 token is simply
    never dispatched).

    ``fused_pipeline`` (``SparsityPolicy.fused_pipeline`` supplies it in
    production) routes through the single fused streamed Pallas kernel —
    dispatch gather, grouped SwiGLU, and weighted combine in one launch,
    with no (E, capacity, d) HBM buffer and no unpermute read-back, and a
    VMEM working set independent of T (pair maps in scalar-prefetch SMEM,
    x/out in HBM behind double-buffered DMA). ``None`` (the default)
    resolves per shape/backend via
    ``core.dispatch.prefer_fused_pipeline`` — fused everywhere on
    TPU, fused iff ``use_kernel`` on CPU interpret. The buffer path
    below stays as its bit-exactness oracle. ``fused_streamed=False``
    selects the whole-array-resident kernel variant (identical math and
    accumulation order — bit-exact vs streamed; bench/debug knob only).

    ``return_overflow``: also return the scalar count of kept pairs dropped
    by capacity overflow (see ``dispatch_indices``). Always in sub-pair
    units, on every path.

    ``layer``: ``params``' expert weights are the layer-stacked
    ``(L, Es, d, f)`` arrays and the streamed fused kernel reads that
    layer of them in place. Only for calls ``reads_layer_stack`` admits;
    every other path takes one layer's weights.
    """
    T, d = x.shape
    E = params["w1"].shape[-3]
    if pairs is None:
        pairs = route_plain(params, x, cfg, n_experts=E)
    K = pairs.idx.shape[1]
    if capacity is None:
        capacity = capacity_for(T, K, E, capacity_factor)

    p = _pairs_partition_p(pairs)
    if fused_pipeline_on(fused_pipeline, T, E, use_kernel):
        y, overflow = _fused_pipeline_dispatch(
            params, x, cfg, pairs, p, capacity,
            mode_grouped=mode_grouped and p > 1, streamed=fused_streamed,
            layer=layer)
        out = y.astype(x.dtype) + _shared_out(params, x)
        return (out, overflow) if return_overflow else out

    if use_kernel and mode_grouped and p > 1:
        y, overflow = _fused_kernel_dispatch(params, x, cfg, pairs, p,
                                             capacity)
        out = y.astype(x.dtype) + _shared_out(params, x)
        return (out, overflow) if return_overflow else out

    plan = dispatch_mod.dispatch_plan(pairs.idx, pairs.keep,
                                      n_groups=E, capacity=capacity)
    buf = dispatch_mod.gather_rows(x, plan, capacity, index_div=K)

    if use_kernel:
        from ..kernels import ops as kops
        cf, cm = plan.kernel_counts(capacity)
        out_buf = kops.grouped_swiglu(buf, params["w1"], params["w3"],
                                      params["w2"], counts_full=cf,
                                      counts_major=cm,
                                      n_minor_start=params["w1"].shape[-1])
    else:
        out_buf = expert_ffn(params["w1"], params["w3"], params["w2"], buf)

    gathered = dispatch_mod.unpermute(out_buf, plan)            # (T*K, d)
    w = (pairs.combine * pairs.keep.astype(pairs.combine.dtype)).reshape(-1)
    y = (gathered * w[:, None].astype(gathered.dtype))
    y = y.reshape(T, K, d).sum(axis=1)
    out = y.astype(x.dtype) + _shared_out(params, x)
    return (out, plan.overflow) if return_overflow else out
