"""Soft Expert-Tensor Parallelism (paper §3.3) and the ETP baseline.

S-ETP = partial transformation + plain EP. Each original expert is split into
P sub-experts; sub-experts are placed *strided* across the EP axis
(sub-expert ``id`` lives on device ``id % D``), so the P halves of one expert
sit on different devices — the tensor-parallel memory/compute split — while
the communication pattern stays a single AlltoAll each way (Fig. 5b).

The ETP baseline (Fig. 5a) shards whole experts over an ``ep`` sub-axis and
each expert's d_ff over a ``tp`` sub-axis, paying AlltoAll+AllGather on
dispatch and ReduceScatter+AlltoAll on return.

Both are shard_map bodies in plain JAX (jax.lax collectives). Load-aware
thresholding (§4.3) costs one psum of a (D,) histogram.

All seating (device-level and local-expert-level) runs on the shared
sort-based dispatch substrate (``core.dispatch``): stable argsort keys,
segment-histogram counts, gather-built buffers — no dense one-hot cumsum,
no ``jnp.repeat`` of the token block. Local buffers are mode-ordered
(FULL rows first, MAJOR-only rows second; the flag rides in the low bit of
the AlltoAll id payload) so ``counts_full``/``counts_major`` feed the
dual-sparse kernel, and capacity-overflow drops are counted and psum'd out
of the body (``setp_moe_forward(return_overflow=True)``).
"""
from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from . import dispatch as dispatch_mod
from . import drop as drop_mod
from . import gating, moe as moe_mod


# ---------------------------------------------------------------------------
# Placement
# ---------------------------------------------------------------------------

def to_strided_order(w, n_dev: int):
    """Reorder the leading (sub-)expert axis from id-order to placement order
    so that a contiguous shard_map shard holds device d's sub-experts.

    id = loc * D + d  ->  placed[d * L + loc] = w[id]."""
    Ep = w.shape[0]
    L = Ep // n_dev
    return w.reshape(L, n_dev, *w.shape[1:]).swapaxes(0, 1).reshape(w.shape)


def place_params_strided(params: Dict, n_dev: int) -> Dict:
    out = dict(params)
    for k in ("w1", "w3", "w2"):
        out[k] = to_strided_order(params[k], n_dev)
    return out


# ---------------------------------------------------------------------------
# S-ETP shard_map body
# ---------------------------------------------------------------------------

def _ceil_mult(x: float, m: int = 8) -> int:
    return max(m, int(np.ceil(x / m) * m)) if m > 1 else max(1, int(np.ceil(x)))


def _setp_body(wg, w1, w3, w2, x_loc, *, cfg, n_dev: int, axis: str,
               token_axes: tuple, policy, thresholds=None,
               cap_factor: float, local_cap_factor: float,
               cap_multiple: int = 8, wire_dtype=jnp.bfloat16,
               tokens_on_axis: bool = True, collect_stats: bool = False):
    """Per-device S-ETP MoE. x_loc: (B_l, S_l, d). Experts already
    partial-transformed (E*P sub-experts when ``policy.partition_p > 1``)
    and strided-placed; this device holds w1/w3/w2 slices of L = E*P/D
    sub-experts. The ``policy`` decides the keep mask over expanded
    sub-expert pairs; a load-aware policy additionally costs one psum of
    the (D,) pre-drop device histogram. ``thresholds``: optional per-layer
    calibrated (2,) pair threaded through the shard_map (replicated).

    ``policy.exact_capacity`` sizes both seatings for the worst case
    instead of by capacity factors, so no kept pair can overflow: a token
    sends at most ``min(K*P, L)`` sub-pairs to one device, and a local
    sub-expert receives at most one pair per token from each of the
    ``n_dev`` sources."""
    p_factor = policy.partition_p
    use_kernel = policy.use_kernel
    Bl, Sl, d = x_loc.shape
    xt = x_loc.reshape(-1, d)
    T = xt.shape[0]
    L = w1.shape[0]                              # local sub-experts
    # whole-body compute dtype == wire dtype: keeps the AlltoAll in bf16
    # (a convert adjacent to the collective gets hoisted across it by the
    # algebraic simplifier, silently doubling interconnect bytes)
    w1 = w1.astype(wire_dtype)
    w3 = w3.astype(wire_dtype)
    w2 = w2.astype(wire_dtype)

    r = gating.route(xt, wg, cfg.top_k, cfg.router_norm_topk)
    K = cfg.top_k

    # --- partial transformation of the routing (Eq. 12) + 2T keep mask ---
    sub = jnp.arange(p_factor, dtype=r.idx.dtype)
    sub_idx = (r.idx[:, :, None] * p_factor + sub).reshape(T, K * p_factor)
    combine = jnp.repeat(r.combine[:, :, None], p_factor, axis=2)
    combine = combine.reshape(T, K * p_factor)
    dev_of = sub_idx % n_dev
    loc_of = sub_idx // n_dev
    score = jnp.repeat(r.norm_score[:, :, None], p_factor, axis=2)
    score = score.reshape(T, K * p_factor)
    is_major = (sub_idx % p_factor) == 0 if p_factor > 1 else \
        jnp.ones_like(sub_idx, dtype=bool)

    loads = None
    if policy.needs_loads:
        # pre-drop load histogram per EP device — one psum (O(N) segment
        # histogram; no dense one-hot). Sum over the expert axis ONLY when
        # tokens are actually sharded over it (prefill/train); on decode
        # steps (S == 1) the token block is REPLICATED over the expert axis,
        # and psum'ing the identical per-device histograms would multiply
        # every load by n_dev — skewing load-aware thresholds toward
        # uniform-looking (capped) ratios.
        loads = dispatch_mod.group_histogram(dev_of, n_dev,
                                             dtype=jnp.float32)
        for ax in token_axes + ((axis,) if tokens_on_axis else ()):
            loads = jax.lax.psum(loads, ax)
    keep = policy.sub_pair_keep(score, is_major, sub_idx, cfg, n_dev=n_dev,
                                loads=loads, thresholds=thresholds)

    stats = None
    if collect_stats:
        # routing-time metrics (pre-dispatch): kept-pair histogram over the
        # GLOBAL sub-expert ids plus mode-attributed keep/drop counts. Like
        # ``loads`` above, psum over the expert axis only when tokens are
        # sharded over it — on decode the token block is replicated there
        # and summing identical copies would multiply every count by n_dev.
        hist = dispatch_mod.group_histogram(sub_idx, L * n_dev, mask=keep)
        kf, km, dr = drop_mod.sub_pair_outcome_counts(keep, p_factor)
        for ax in token_axes + ((axis,) if tokens_on_axis else ()):
            hist, kf, km, dr = jax.lax.psum((hist, kf, km, dr), ax)
        stats = {"expert_load": hist, "kept_full": kf, "kept_major": km,
                 "dropped_pairs": dr}

    Kp = K * p_factor
    if policy.exact_capacity:
        cap = _ceil_mult(T * min(Kp, L), cap_multiple)
    else:
        cap = _ceil_mult(cap_factor * T * Kp / n_dev, cap_multiple)

    # --- dispatch: sort-based seating per destination device ---
    # MAJOR-only flags ride to the owning device (low bit of the id
    # payload) so its local buffers can be mode-ordered for the kernel.
    mflag = dispatch_mod.major_only_flags(keep, p_factor)
    plan_dev = dispatch_mod.sort_dispatch(dev_of, keep,
                                          n_groups=n_dev, capacity=cap)
    # bf16 on the wire: halves AlltoAll traffic; experts compute from bf16
    # activations (standard practice) while the combine stays in x dtype.
    send_x = dispatch_mod.gather_rows(xt.astype(wire_dtype), plan_dev, cap,
                                      index_div=Kp)
    payload = loc_of * 2 + mflag.astype(loc_of.dtype)
    send_e = dispatch_mod.gather_rows(payload.reshape(-1), plan_dev, cap,
                                      fill=-1)

    # --- the S-ETP collective: ONE AlltoAll each way (Fig. 5b) ---
    recv_x = jax.lax.all_to_all(send_x, axis, 0, 0, tiled=False)
    recv_e = jax.lax.all_to_all(send_e, axis, 0, 0, tiled=False)

    # --- local grouped expert FFN (mode-ordered buffers) ---
    rx = recv_x.reshape(n_dev * cap, d)
    re2 = recv_e.reshape(-1)
    valid = re2 >= 0
    loc = jnp.where(valid, re2 // 2, 0)
    mfl = valid & ((re2 & 1) == 1)
    if policy.exact_capacity:
        c2 = _ceil_mult(n_dev * T, cap_multiple)
    else:
        c2 = _ceil_mult(local_cap_factor * n_dev * cap / L, cap_multiple)
    plan_loc = dispatch_mod.sort_dispatch(loc, valid, n_groups=L,
                                          capacity=c2, major_only=mfl)
    fused = getattr(policy, "fused_pipeline", None)
    if fused is None:
        # auto: same per-shape/backend heuristic as the dispatch path
        fused = dispatch_mod.prefer_fused_pipeline(rx.shape[0], L,
                                                   use_kernel=use_kernel)
    if fused:
        # single fused Pallas pipeline: the kernel gathers received rows
        # straight through plan_loc.perm, runs the grouped SwiGLU, and
        # scatters back per received row — no (L, c2, d) buffer, no
        # unpermute. Validity rides as the combine weight (1 kept / 0 pad),
        # replacing the ``* valid`` mask of the buffer path.
        from ..kernels import ops as kops
        cf, cm = plan_loc.kernel_counts(c2)
        bc = min(128, c2)
        tok_s, w_s = dispatch_mod.sorted_pair_arrays(
            plan_loc, valid.astype(jnp.float32), pad=bc)
        out_tok = kops.fused_moe_pipeline(
            rx, w1, w3, w2, plan_loc.group_offsets, cf, cm, tok_s, w_s,
            capacity=c2, n_minor_start=w1.shape[-1],
            block_c=bc).astype(wire_dtype)
    else:
        buf = dispatch_mod.gather_rows(rx, plan_loc, c2)
        if use_kernel:
            from ..kernels import ops as kops
            cf, cm = plan_loc.kernel_counts(c2)
            # each local group IS one sub-expert (the halves of an original
            # expert live on different devices — that is the S-ETP split),
            # so no minor-half neuron region exists locally: counts_major
            # tracks the mode ordering and pads tile-skip row validity only.
            out_buf = kops.grouped_swiglu(buf, w1, w3, w2, counts_full=cf,
                                          counts_major=cm,
                                          n_minor_start=w1.shape[-1])
        else:
            out_buf = moe_mod.expert_ffn(w1, w3, w2, buf)
        out_tok = dispatch_mod.unpermute(out_buf, plan_loc).astype(wire_dtype)
        out_tok = out_tok * valid[:, None].astype(out_tok.dtype)

    # --- return AlltoAll + combine on the source device ---
    back = jax.lax.all_to_all(out_tok.reshape(n_dev, cap, d), axis, 0, 0)
    back = jnp.pad(back, ((0, 0), (0, 1), (0, 0)))
    out_pair = back[plan_dev.group, plan_dev.slot]               # (T*Kp, d)
    flat_keep = keep.reshape(-1)
    w = (combine.reshape(-1) * flat_keep.astype(combine.dtype))
    y = (out_pair * w[:, None].astype(out_pair.dtype)).reshape(T, Kp, d).sum(1)
    # kept pairs silently discarded by capacity overflow, globally summed:
    # device-level seating + local-expert-level seating on this shard
    overflow = plan_dev.overflow + plan_loc.overflow
    for ax in token_axes + (axis,):
        overflow = jax.lax.psum(overflow, ax)
    y = y.reshape(Bl, Sl, d).astype(x_loc.dtype)
    if collect_stats:
        stats["overflow_pairs"] = overflow
        return y, stats
    return y, overflow


def _spec_uses_axis(spec, axis: str) -> bool:
    """Whether a PartitionSpec shards any dimension over ``axis`` — i.e.
    whether the per-shard token block is a distinct slice along it (vs
    replicated, as on decode steps)."""
    for entry in spec:
        if entry == axis:
            return True
        if isinstance(entry, (tuple, list)) and axis in entry:
            return True
    return False


def setp_moe_forward(params: Dict, x, cfg, mesh: Mesh, *,
                     expert_axis: str = "model", policy=None,
                     cap_factor: float = 1.15, local_cap_factor: float = 1.25,
                     cap_multiple: int = 8, wire_dtype=jnp.bfloat16,
                     x_spec: Optional[P] = None,
                     return_overflow: bool = False,
                     return_stats: bool = False):
    """S-ETP MoE layer under a ``SparsityPolicy`` (default ``NoDrop``).
    params' experts must already be prepared by the SAME policy
    (``policy.prepare(...)``: partial transformation + reconstruction for
    drop policies) AND strided-placed via
    ``place_params_strided(params, mesh.shape[expert_axis])``.

    x: (B, S, d) — batch sharded over (pod, data), seq sharded over
    ``expert_axis`` so the AlltoAll happens within each data-parallel group.

    ``return_overflow``: also return the GLOBAL (psum'd, replicated) count
    of kept token/sub-expert pairs silently discarded by device-level or
    local-expert-level capacity overflow — the unsanctioned accuracy loss a
    deployment must watch, previously invisible on this path.

    The policy's ``exact_capacity`` hint (``exact_moe`` in the engines)
    replaces ``cap_factor``/``local_cap_factor`` by worst-case capacities:
    no overflow, so outputs do not depend on co-batched traffic.

    ``return_stats``: instead return ``(y, stats)`` where stats is the
    ``repro.obs`` per-layer dict (kept-pair ``expert_load`` histogram over
    global sub-expert ids plus kept_full/kept_major/dropped_pairs/
    overflow_pairs int32 scalars), all globally psum'd and replicated.
    Supersedes ``return_overflow`` when both are set.
    """
    if policy is None:
        from .policy import NoDrop
        policy = NoDrop()
    n_dev = mesh.shape[expert_axis]
    token_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    if x_spec is None:
        from ..distributed.sharding import batch_spec
        # shard seq over the expert axis when divisible (prefill/train);
        # decode steps (S == 1) keep seq replicated.
        seq_ax = expert_axis if x.shape[1] % n_dev == 0 else None
        x_spec = batch_spec(x.shape[0], mesh, extra=(seq_ax, None))
    body = functools.partial(
        _setp_body, cfg=cfg, n_dev=n_dev, axis=expert_axis,
        token_axes=token_axes, policy=policy,
        cap_factor=cap_factor, local_cap_factor=local_cap_factor,
        cap_multiple=cap_multiple, wire_dtype=wire_dtype,
        tokens_on_axis=_spec_uses_axis(x_spec, expert_axis),
        collect_stats=return_stats)

    # per-layer calibrated thresholds ride through the shard_map replicated
    has_th = "thresholds" in params
    args = [params["wg"], params["w1"], params["w3"], params["w2"]]
    in_specs = [P(), P(expert_axis), P(expert_axis), P(expert_axis)]
    if has_th:
        args.append(params["thresholds"])
        in_specs.append(P())
    args.append(x)
    in_specs.append(x_spec)

    def fn(wg, w1, w3, w2, *rest):
        if has_th:
            th, xx = rest
        else:
            th, (xx,) = None, rest
        return body(wg, w1, w3, w2, xx, thresholds=th)

    if return_stats:
        aux_spec = {"expert_load": P(), "kept_full": P(), "kept_major": P(),
                    "dropped_pairs": P(), "overflow_pairs": P()}
    else:
        aux_spec = P()
    y, aux = jax.shard_map(
        fn, mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=(x_spec, aux_spec), check_vma=False,
    )(*args)
    if "shared" in params:
        s = params["shared"]
        h = jax.nn.silu(x @ s["w1"]) * (x @ s["w3"])
        y = y + h @ s["w2"]
    if return_stats:
        return y, aux
    return (y, aux) if return_overflow else y


# ---------------------------------------------------------------------------
# ETP baseline (Fig. 5a): EP over `ep` axis, TP over `tp` axis
# ---------------------------------------------------------------------------

def _etp_body(wg, w1, w3, w2, x_loc, *, cfg, n_ep: int, n_tp: int,
              cap_factor: float, local_cap_factor: float):
    """w1/w3: (E_loc, d, f/tp); w2: (E_loc, f/tp, d). Tokens sharded over ep
    (and replicated over tp). Pattern: AlltoAll(ep) + AllGather(tp) dispatch,
    partial FFN, ReduceScatter(tp) + AlltoAll(ep) return."""
    Bl, Sl, d = x_loc.shape
    xt = x_loc.reshape(-1, d)
    T = xt.shape[0]
    L = w1.shape[0]
    r = gating.route(xt, wg, cfg.top_k, cfg.router_norm_topk)
    K = cfg.top_k
    dev_of = r.idx // L
    loc_of = r.idx % L
    cap = _ceil_mult(cap_factor * T * K / n_ep)
    plan_dev = dispatch_mod.sort_dispatch(dev_of, n_groups=n_ep,
                                          capacity=cap)
    send_x = dispatch_mod.gather_rows(xt, plan_dev, cap, index_div=K)
    send_e = dispatch_mod.gather_rows(loc_of.reshape(-1), plan_dev, cap,
                                      fill=-1)

    # dispatch: AlltoAll over ep ...
    recv_x = jax.lax.all_to_all(send_x, "ep", 0, 0)
    recv_e = jax.lax.all_to_all(send_e, "ep", 0, 0)
    # ... + AllGather over tp (each tp rank computed routing for its own
    # token shard; expert compute needs the full token set of the ep group)
    recv_x = jax.lax.all_gather(recv_x, "tp", tiled=False)      # (tp, nev, cap, d)
    recv_e = jax.lax.all_gather(recv_e, "tp", tiled=False)
    rx = recv_x.reshape(-1, d)
    re = recv_e.reshape(-1)
    valid = re >= 0
    n_recv = rx.shape[0]
    c2 = _ceil_mult(local_cap_factor * n_recv / L)
    plan_loc = dispatch_mod.sort_dispatch(jnp.where(valid, re, 0), valid,
                                          n_groups=L, capacity=c2)
    buf = dispatch_mod.gather_rows(rx, plan_loc, c2)
    out_buf = moe_mod.expert_ffn(w1, w3, w2, buf)     # partial over f/tp
    out_tok = dispatch_mod.unpermute(out_buf, plan_loc)
    out_tok = out_tok * valid[:, None].astype(rx.dtype)
    out_tok = out_tok.reshape(n_tp, n_ep, cap, d)
    # return: ReduceScatter over tp (sum partial FFN outputs, keep own shard)
    out_own = jax.lax.psum_scatter(out_tok, "tp", scatter_dimension=0,
                                   tiled=False)                  # (nev, cap, d)
    back = jax.lax.all_to_all(out_own, "ep", 0, 0)
    back = jnp.pad(back, ((0, 0), (0, 1), (0, 0)))
    out_pair = back[plan_dev.group, plan_dev.slot]
    w = r.combine.reshape(-1)
    y = (out_pair * w[:, None].astype(out_pair.dtype)).reshape(T, K, d).sum(1)
    return y.reshape(Bl, Sl, d).astype(x_loc.dtype)


def etp_moe_forward(params: Dict, x, cfg, mesh: Mesh, *,
                    ep_axis: str = "ep", tp_axis: str = "tp",
                    cap_factor: float = 1.3, local_cap_factor: float = 2.0):
    """ETP baseline. Expert weights sharded (expert over ep, d_expert over tp);
    tokens sharded over ep, replicated over tp."""
    n_ep, n_tp = mesh.shape[ep_axis], mesh.shape[tp_axis]
    body = functools.partial(_etp_body, cfg=cfg, n_ep=n_ep, n_tp=n_tp,
                             cap_factor=cap_factor,
                             local_cap_factor=local_cap_factor)
    x_spec = P(ep_axis, None, None)
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(), P(ep_axis, None, tp_axis), P(ep_axis, None, tp_axis),
                  P(ep_axis, tp_axis, None), x_spec),
        out_specs=x_spec, check_vma=False,
    )(params["wg"], params["w1"], params["w3"], params["w2"], x)
