"""The entry-point registry: every public computation surface, traced
across a matrix of representative configs.

An entry owns a lazy ``trace()`` producing ``Artifacts``: a closed jaxpr
(always, for traceable entries), compiled HLO text (when the entry opts
in — compilation costs seconds, tracing milliseconds), and/or static
``KernelSpec`` objects (spec-only entries need no tracing at all). Entry
``meta`` carries the per-entry pass parameters: forbidden buffer shapes,
collective budgets, VMEM budget overrides, the x64-probe flag.

Families (glob-friendly names):
  dispatch/<policy>/T<n>   single-device MoE forward, dispatch path
  pipeline/{buffer,fused}  capacity-buffer oracle vs fused Pallas pipeline
  setp/<policy>            shard_map S-ETP forward (needs >= 2 devices)
  obs/dispatch_metrics/<policy>    metrics-collecting MoE layer forward
  engine/{prefill_insert,decode}   continuous-batching jitted steps
  engine/{chunk_insert,paged_decode,prefix_hit_insert}  paged-KV steps
  calib/{threshold,load_aware}     calibration math probed under x64
  kernel/<name>/<scenario>         production-scale KernelSpecs (no trace)
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp


@dataclasses.dataclass
class Artifacts:
    jaxpr: Any = None                 # ClosedJaxpr
    hlo: Optional[str] = None         # compiled module text
    kernel_specs: Tuple = ()          # KernelSpec objects


@dataclasses.dataclass
class LintEntry:
    name: str
    meta: Dict[str, Any]
    _trace: Callable[[], Artifacts]
    _cache: Optional[Artifacts] = None

    def trace(self) -> Artifacts:
        if self._cache is None:
            self._cache = self._trace()
        return self._cache


def _sds(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


def _abstract_moe_params(cfg, p: int, *, per_layer_thresholds: bool = False):
    """ShapeDtypeStruct param dict of one prepared MoE layer: partial
    transformation splits each expert's f neurons into p sub-experts."""
    d, E, f = cfg.d_model, cfg.n_experts, cfg.d_expert
    assert f % p == 0
    params = {
        "wg": _sds((d, E)),
        "w1": _sds((E * p, d, f // p)),
        "w3": _sds((E * p, d, f // p)),
        "w2": _sds((E * p, f // p, d)),
    }
    if per_layer_thresholds:
        params["thresholds"] = _sds((2,))
    return params


def _jaxpr_and_hlo(fn, args, *, want_hlo: bool) -> Artifacts:
    jaxpr = jax.make_jaxpr(fn)(*args)
    hlo = None
    if want_hlo:
        hlo = jax.jit(fn).lower(*args).compile().as_text()
    return Artifacts(jaxpr=jaxpr, hlo=hlo)


# ---------------------------------------------------------------------------
# Entry builders
# ---------------------------------------------------------------------------

def _dispatch_entry(cfg, policy_name: str, T: int, *,
                    want_hlo: bool) -> LintEntry:
    from ..core import moe as moe_mod
    from ..core.policy import make_policy

    kw = {"use_kernel": True} if policy_name in ("2t",) else {}
    policy = make_policy(policy_name, cfg.dualsparse, **kw)
    p = policy.partition_p
    params = _abstract_moe_params(
        cfg, p, per_layer_thresholds=(policy_name == "per_layer"))
    x = _sds((T, cfg.d_model))

    def fn(params, x):
        pairs = policy.route(params, x, cfg)
        return moe_mod.moe_forward_dispatch(
            params, x, cfg, pairs,
            capacity_factor=policy.capacity_factor,
            use_kernel=policy.use_kernel,
            mode_grouped=policy.kernel_mode_grouping,
            fused_pipeline=policy.fused_pipeline)

    return LintEntry(
        name=f"dispatch/{policy_name}/T{T}",
        meta={"x64_probe": False, "hbm_baseline": want_hlo},
        _trace=lambda: _jaxpr_and_hlo(fn, (params, x), want_hlo=want_hlo))


def _pipeline_entries(cfg, T: int) -> List[LintEntry]:
    from ..core import moe as moe_mod
    from ..core.policy import make_policy

    policy = make_policy("2t", cfg.dualsparse, use_kernel=True)
    p = policy.partition_p
    params = _abstract_moe_params(cfg, p)
    x = _sds((T, cfg.d_model))
    # mode-grouped kernel paths group by ORIGINAL expert (same geometry as
    # benchmarks/bench_moe_pipeline.py, whose CI assertion this pass
    # absorbs)
    E = cfg.n_experts
    capacity = moe_mod.capacity_for(T, cfg.top_k, E, policy.capacity_factor)

    def make_fn(fused: bool):
        def fn(params, x):
            pairs = policy.route(params, x, cfg)
            return moe_mod.moe_forward_dispatch(
                params, x, cfg, pairs, capacity=capacity,
                use_kernel=not fused,
                mode_grouped=policy.kernel_mode_grouping,
                fused_pipeline=fused)
        return fn

    d = cfg.d_model
    forbidden = [(E, capacity, d)]
    bc = min(128, capacity)
    cap_padded = (capacity + bc - 1) // bc * bc
    if cap_padded != capacity:
        forbidden.append((E, cap_padded, d))
    buffer_entry = LintEntry(
        name=f"pipeline/buffer/T{T}",
        meta={"hbm_baseline": True, "require_shapes": forbidden[:1]},
        _trace=lambda: _jaxpr_and_hlo(make_fn(False), (params, x),
                                      want_hlo=True))
    fused_entry = LintEntry(
        name=f"pipeline/fused/T{T}",
        meta={"forbid_shapes": forbidden,
              "hbm_less_than": f"pipeline/buffer/T{T}",
              "hbm_baseline": True},
        _trace=lambda: _jaxpr_and_hlo(make_fn(True), (params, x),
                                      want_hlo=True))
    return [buffer_entry, fused_entry]


def _setp_entry(cfg, policy_name: str, n_dev: int) -> LintEntry:
    from ..core.policy import make_policy
    from ..core.setp import setp_moe_forward
    from ..launch.mesh import make_host_mesh

    policy = make_policy(policy_name, cfg.dualsparse)
    p = policy.partition_p
    params = _abstract_moe_params(cfg, p)
    B, S = 2, 8
    x = _sds((B, S, cfg.d_model))
    mesh = make_host_mesh(model=n_dev)

    def fn(params, x):
        return setp_moe_forward(params, x, cfg, mesh, policy=policy,
                                return_overflow=True)

    # the S-ETP invariant: ONE dispatch AlltoAll + ONE return AlltoAll per
    # layer; psums only for overflow (+ the load histogram when the policy
    # needs it); never an all-gather of the token block
    n_psum = 2 + (1 if policy.needs_loads else 0)
    budget = {"all-to-all": 2, "all-reduce": n_psum, "all-gather": 0}
    return LintEntry(
        name=f"setp/{policy_name}",
        meta={"collective_budget": budget, "hbm_baseline": True},
        _trace=lambda: _jaxpr_and_hlo(fn, (params, x), want_hlo=True))


def _engine_entries() -> List[LintEntry]:
    from ..configs import get_config
    from ..models import model as M
    from ..obs import metrics_spec
    from ..serving.engine import ContinuousBatchingEngine

    cfg = get_config("qwen3-moe-30b-a3b").reduced()
    params, _ = M.abstract_params_and_axes(cfg)
    n_slots, lp = 2, 16

    def build(which: str):
        def trace():
            eng = ContinuousBatchingEngine(cfg, params, n_slots=n_slots,
                                           max_prompt_len=lp,
                                           max_new_tokens=8)
            cache = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                eng._cache)
            policy = eng._base_policy
            if which == "prefill_insert":
                fn = eng._prefill_insert.__wrapped__
                args = (params, _sds((1, lp), jnp.int32),
                        _sds((), jnp.int32), _sds((), jnp.int32),
                        cache, policy)
            else:
                fn = eng._decode.__wrapped__
                args = (params, _sds((n_slots, 1), jnp.int32), cache,
                        _sds((n_slots,), jnp.bool_), policy)
            return Artifacts(jaxpr=jax.make_jaxpr(fn)(*args))
        return trace

    # engines default to metrics=True, so both steps trace with the
    # MetricsState seam in the cache. The expert-load histogram leaf must
    # be a traced ARGUMENT (counter values change every step — a captured
    # constant would retrace per decode), and the jaxpr-hostsync pass
    # proves the seam adds no host callbacks to the hot path.
    spec = metrics_spec(cfg, params)
    metrics_leaf = [[list(spec), "int32"]] if spec else []
    return [LintEntry(name=f"engine/{which}",
                      meta={"traced_leaves": metrics_leaf},
                      _trace=build(which))
            for which in ("prefill_insert", "decode")]


def _obs_dispatch_entry(cfg, policy_name: str, T: int, *,
                        want_hlo: bool) -> LintEntry:
    """The metrics-collecting MoE layer forward (``_moe_forward`` with
    ``collect=True``): same routing and dispatch as ``dispatch/<policy>``
    plus the per-layer obs stats dict. The pass set proves the seam costs
    no host syncs and no extra capacity buffers; hbm_baseline tracks its
    (small, int32) memory footprint."""
    from ..core.policy import make_policy
    from ..models import transformer
    from ..models.transformer import DistContext

    kw = {"use_kernel": True} if policy_name in ("2t",) else {}
    policy = make_policy(policy_name, cfg.dualsparse, **kw)
    p = policy.partition_p
    params = _abstract_moe_params(
        cfg, p, per_layer_thresholds=(policy_name == "per_layer"))
    B, S = 2, 32
    x = _sds((B, S, cfg.d_model))
    dist = DistContext(mesh=None, moe_impl="dispatch", policy=policy)

    def fn(params, x):
        y, _, stats = transformer._moe_forward(params, x, cfg, dist,
                                               collect=True)
        return y, stats

    return LintEntry(
        name=f"obs/dispatch_metrics/{policy_name}",
        meta={"x64_probe": False, "hbm_baseline": want_hlo},
        _trace=lambda: _jaxpr_and_hlo(fn, (params, x), want_hlo=want_hlo))


def _paged_engine_entries(*, want_hlo: bool) -> List[LintEntry]:
    """The paged serving engine's jitted steps. All three carry a
    ``traced_leaves`` check on the page-table array: slot->page indirection
    must enter the step as a TRACED argument, never a captured constant —
    a constant page table re-hashes into a new executable on every
    allocator churn (page reuse, prefix hit, eviction), silently
    recompiling per admission."""
    from ..configs import get_config
    from ..models import model as M
    from ..serving.paged import PagedEngine

    cfg = get_config("qwen3-moe-30b-a3b").reduced()
    params, _ = M.abstract_params_and_axes(cfg)
    n_slots, lp, chunk, ps = 2, 16, 8, 4

    def build(which: str, hlo: bool):
        def trace():
            eng = PagedEngine(cfg, params, n_slots=n_slots, page_size=ps,
                              chunk_size=chunk, max_prompt_len=lp,
                              max_new_tokens=8)
            cache = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                eng._cache)
            pt = _sds((n_slots, eng.pages_per_slot), jnp.int32)
            policy = eng._base_policy
            if which == "paged_decode":
                fn = eng._decode.__wrapped__
                args = (params, _sds((n_slots, 1), jnp.int32), cache,
                        _sds((n_slots,), jnp.bool_), pt, policy)
            else:
                # chunk_insert and prefix_hit_insert share ONE jitted step:
                # a prefix hit only changes the traced ``start`` scalar and
                # page-table values, so admission after a hit reuses the
                # cold-path executable — both entries lock that contract.
                fn = eng._chunk_insert.__wrapped__
                args = (params, _sds((1, chunk), jnp.int32),
                        _sds((), jnp.int32), _sds((), jnp.int32),
                        _sds((), jnp.int32), cache, pt, policy)
            return _jaxpr_and_hlo(fn, args, want_hlo=hlo)
        return trace

    pt_shape = [n_slots, -(-(lp + 8) // ps)]
    entries = []
    for which in ("chunk_insert", "paged_decode", "prefix_hit_insert"):
        # prefix_hit_insert shares chunk_insert's executable — skip its
        # (duplicate) compile and keep it as a jaxpr-only contract entry
        hlo = want_hlo and which != "prefix_hit_insert"
        meta = {"traced_leaves": [[pt_shape, "int32"]],
                # single-device serving steps must stay collective-free: an
                # all-gather of the page pool would defeat paging entirely
                "collective_budget": {"all-gather": 0, "all-to-all": 0},
                "hbm_baseline": hlo}
        entries.append(LintEntry(name=f"engine/{which}", meta=meta,
                                 _trace=build(which, hlo)))
    return entries


def _calib_entries(cfg) -> List[LintEntry]:
    """Calibration math, traced under jax_enable_x64: f32-explicit code
    stays clean, weak-type-dependent code lights the dtype pass up. These
    entries justify the f32 pinning in core.drop / core.load_aware."""
    from ..core import drop as drop_mod
    from ..core import load_aware

    def trace_threshold():
        scores = _sds((256, cfg.top_k))
        with jax.enable_x64(True):
            def fn(scores):
                t = drop_mod.calibrate_threshold(scores, 0.25)
                rates = drop_mod.threshold_to_drop_rate(
                    scores, [0.05, 0.1, 0.2])
                per_layer = drop_mod.calibrate_per_layer_thresholds(
                    [scores, scores], 0.25)
                return t, rates, per_layer
            return Artifacts(jaxpr=jax.make_jaxpr(fn)(scores))

    def trace_load_aware():
        hist = _sds((cfg.n_experts,), jnp.int32)
        idx = _sds((64, cfg.top_k), jnp.int32)
        with jax.enable_x64(True):
            def fn(hist, idx):
                loads = load_aware.device_loads(hist, 2)
                t_dev = load_aware.step_down_thresholds(loads, 0.12)
                tm, tn = load_aware.pair_thresholds(idx, loads, 2, 0.12)
                return t_dev, tm, tn, load_aware.makespan(loads)
            return Artifacts(jaxpr=jax.make_jaxpr(fn)(hist, idx))

    return [
        LintEntry(name="calib/threshold", meta={"x64_probe": True},
                  _trace=trace_threshold),
        LintEntry(name="calib/load_aware", meta={"x64_probe": True},
                  _trace=trace_load_aware),
    ]


def _kernel_spec_entries() -> List[LintEntry]:
    """Production-scale static specs (qwen3-moe-30b-a3b dims, bf16): no
    tracing, pure geometry — the checks a TPU deployment needs before any
    hardware exists in the loop."""
    from ..core.moe import capacity_for
    from ..kernels import (fused_moe_pipeline_kernel_spec,
                           grouped_swiglu_kernel_spec)

    d, f, E, top_k, P = 2048, 768, 128, 8, 2
    fsub = f // P

    def gs_trace():
        cap = capacity_for(4096, top_k * P, E * P, 1.25)
        return Artifacts(kernel_specs=(grouped_swiglu_kernel_spec(
            E, cap, d, fsub, dtype=jnp.bfloat16, p_factor=1),))

    def fused_trace(T, *, d=d, f=fsub, E=E, top_k=top_k, n_layers=48):
        # production fused path at P>1 is mode-grouped: ONE pair per
        # (token, original expert), so the scalar-prefetch maps carry
        # T*top_k entries (+ one block of padding) — half the sub-pair
        # layout at P=2, which is what keeps them inside the SMEM budget
        # at prefill scale. The layer scans launch it on the layer-stacked
        # weights (published depth; the layer index rides in SMEM).
        def trace():
            cap = capacity_for(T, top_k * P, E, 2.0)
            n_pairs = T * top_k + 128
            return Artifacts(kernel_specs=(fused_moe_pipeline_kernel_spec(
                T, d, f, E, n_pairs, capacity=cap, dtype=jnp.bfloat16,
                p_factor=P, n_layers=n_layers),))
        return trace

    return [
        LintEntry(name="kernel/grouped_swiglu/prod", meta={},
                  _trace=gs_trace),
        LintEntry(name="kernel/fused_pipeline/prod_decode", meta={},
                  _trace=fused_trace(256)),
        # prefill scale is CLEAN since the streamed rewrite: pair maps in
        # scalar-prefetch SMEM, x/out in ANY memory behind double-buffered
        # DMA, so the VMEM working set no longer grows with T (the old
        # resident layout blew the budget here ~6x and was suppressed in
        # lint_baseline.json — the suppression is deleted and a regression
        # test keeps the unstreamed spec failing)
        LintEntry(name="kernel/fused_pipeline/prod_prefill", meta={},
                  _trace=fused_trace(8192)),
        # wide-model prefill: Mixtral-class dims (d=4096, 64 experts,
        # top_k=2) — the acceptance shape for the streamed residency model
        LintEntry(name="kernel/fused_pipeline/prefill_8k_wide", meta={},
                  _trace=fused_trace(8192, d=4096, f=14336 // P, E=64,
                                     top_k=2, n_layers=32)),
    ]


# ---------------------------------------------------------------------------
# The matrix
# ---------------------------------------------------------------------------

def build_entries(*, include_hlo: bool = True,
                  include_engine: bool = True) -> List[LintEntry]:
    """The full entry matrix for this machine. S-ETP entries appear only
    when the process sees >= 2 devices (the CLI forces 8 host devices;
    in-process test runs on the single-device default skip them).

    ``include_hlo=False`` keeps every entry jaxpr/spec-only (fast path for
    tests); ``include_engine=False`` skips the two transformer-sized
    traces."""
    from ..configs import get_config

    cfg = get_config("olmoe-lite").reduced()
    entries: List[LintEntry] = []
    for pol in ("none", "1t", "2t", "load_aware", "per_layer"):
        entries.append(_dispatch_entry(cfg, pol, 64,
                                       want_hlo=include_hlo))
    entries.append(_dispatch_entry(cfg, "2t", 256, want_hlo=False))
    entries.append(_obs_dispatch_entry(cfg, "2t", 64,
                                       want_hlo=include_hlo))
    if include_hlo:
        entries.extend(_pipeline_entries(cfg, 64))
    if include_hlo and len(jax.devices()) >= 2:
        n_dev = 4 if len(jax.devices()) % 4 == 0 else 2
        for pol in ("2t", "load_aware"):
            entries.append(_setp_entry(cfg, pol, n_dev))
    if include_engine:
        entries.extend(_engine_entries())
        entries.extend(_paged_engine_entries(want_hlo=include_hlo))
    entries.extend(_calib_entries(cfg))
    entries.extend(_kernel_spec_entries())
    return entries
