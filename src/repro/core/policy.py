"""First-class sparsity policies: ONE pluggable object per deployment
scenario instead of scattered booleans.

A ``SparsityPolicy`` owns the three coupled decisions the paper makes:

  (a) **param preparation** — ``prepare(params, cfg, calib_x)``: partial
      transformation factor, neuron-importance reconstruction, and threshold
      calibration (absorbing ``transform_params_for_dualsparse``);
  (b) **routing** — ``route(params, x, cfg, *, loads=None)``: which
      token/(sub-)expert pairs to compute (absorbing the
      ``route_plain`` / ``route_dualsparse`` / ``expand_pairs_*`` selection
      and the ``params["thresholds"]`` side-channel);
  (c) **execution hints** — kernel choice, dispatch capacity factor, and
      exact-capacity mode for batch-composition-invariant serving.

Policies are frozen dataclasses registered as JAX pytrees: threshold
*values* are leaves (so a policy can be passed as a jit argument and its
values changed per call — or per request/slot — without retracing), while
structural knobs (partition factor, importance metric, kernel/capacity
hints) are static aux data. The registry maps CLI names to classes:

    none | 1t | 2t | load_aware | per_layer

Everything downstream — ``DistContext``, ``setp_moe_forward``, the model's
``_moe_forward``, both serving engines, the launchers, and the benchmarks —
consumes policies instead of booleans.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple, Type

import jax
import jax.numpy as jnp

from . import drop as drop_mod
from . import gating
from . import moe as moe_mod


# ---------------------------------------------------------------------------
# Pytree registration: dynamic (threshold) fields are leaves, the rest aux
# ---------------------------------------------------------------------------

POLICIES: Dict[str, Type["SparsityPolicy"]] = {}


def register_policy(name: str):
    """Class decorator: register under ``name`` and make the class a pytree
    whose ``_dynamic`` fields are children (traced) and whose remaining
    dataclass fields are static aux data (retrace on change)."""
    def deco(cls):
        cls.name = name
        POLICIES[name] = cls
        dyn = tuple(cls._dynamic)
        static = tuple(f.name for f in dataclasses.fields(cls)
                       if f.name not in dyn)
        # introspection hooks for repro.lint's retrace-hazard pass: the
        # exact field partition the pytree flatten uses
        cls._pytree_dynamic = dyn
        cls._pytree_static = static

        def flatten(p):
            return (tuple(getattr(p, n) for n in dyn),
                    tuple(getattr(p, n) for n in static))

        def unflatten(aux, children):
            kw = dict(zip(static, aux))
            kw.update(zip(dyn, children))
            return cls(**kw)

        jax.tree_util.register_pytree_node(cls, flatten, unflatten)
        return cls
    return deco


def _bt(t, score):
    """Broadcast a threshold against a (T, K') score block: scalars pass
    through, per-token (T,) vectors gain a pair axis."""
    t = jnp.asarray(t)
    return t[:, None] if t.ndim == 1 else t


# ---------------------------------------------------------------------------
# Base class
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SparsityPolicy:
    """Base policy. Subclasses list their traced fields in ``_dynamic``."""

    # --- static structure (pytree aux data) ---
    partition_p: int = 1            # partial-transformation factor P
    importance: str = "abs_gate"    # neuron-importance metric (§4.2b)
    reconstruction: bool = True     # reorder neurons before partition
    # --- execution hints (static) ---
    use_kernel: bool = False        # Pallas grouped kernel on expert GEMMs
    fused_pipeline: Optional[bool] = None   # single fused (streamed) Pallas
    #                                 dispatch->FFN->combine kernel (no
    #                                 (E, C, d) HBM buffer, no unpermute
    #                                 read-back). None = auto: resolved per
    #                                 shape/backend at trace time by
    #                                 core.dispatch.prefer_fused_pipeline
    #                                 (TPU: always fused; CPU interpret:
    #                                 fused iff use_kernel). True/False
    #                                 force the choice.
    capacity_factor: float = 2.0    # dispatch-path expert capacity factor
    exact_capacity: bool = False    # worst-case capacities (capacity = T on
    #                                 the dispatch path): no overflow drop
    #                                 ever, so MoE outputs are
    #                                 batch-invariant
    drop_target: Optional[float] = None   # calibrate thresholds in prepare()

    _dynamic: Tuple[str, ...] = ()
    name = "base"
    needs_loads = False             # setp body must psum a load histogram

    @property
    def kernel_mode_grouping(self) -> bool:
        """Execution hint: with ``use_kernel`` on the dispatch path, group
        pairs by ORIGINAL expert in mode order (FULL rows first, MAJOR-only
        rows second) so ``counts_major`` reaches the dual-sparse kernel and
        minor-half MXU tiles are skipped (paper §4.2). Sound for any policy
        whose keep mask is mode-monotone (a kept minor half implies a kept
        major half) — true of every registered drop policy."""
        return self.partition_p > 1

    # -- (a) param preparation ------------------------------------------

    def prepare_layer(self, moe_params: Dict, cfg, calib_x=None, *,
                      n_ep_devices: int = 0) -> Dict:
        """One MoE layer's param dict -> prepared dict (partition +
        reconstruction + strided EP placement)."""
        out = moe_params
        if self.partition_p > 1:
            if calib_x is None:
                raise ValueError(f"{self.name}: prepare needs calibration "
                                 "activations to profile neuron importance")
            if self.reconstruction:
                from . import reconstruct
                out = reconstruct.partition_and_reconstruct(
                    out, calib_x, cfg, p=self.partition_p,
                    method=self.importance)
            else:
                from . import partition
                out = partition.partial_transform(out, self.partition_p)
        if n_ep_devices:
            from . import setp
            out = setp.place_params_strided(out, n_ep_devices)
        return out

    def prepare(self, params: Dict, cfg, calib_x=None, *,
                n_ep_devices: int = 0) -> Tuple[Dict, "SparsityPolicy"]:
        """Prepare a full model param tree (or a bare MoE layer dict).

        Returns ``(prepared_params, calibrated_policy)`` — the returned
        policy has thresholds calibrated to ``drop_target`` when set."""
        if "blocks" in params:
            blocks = params["blocks"]
            if "moe" not in blocks:
                return params, self
            new_moe = jax.vmap(lambda mp: self.prepare_layer(
                mp, cfg, calib_x, n_ep_devices=n_ep_devices))(blocks["moe"])
            out = dict(params)
            out["blocks"] = {**blocks, "moe": new_moe}
            wg = new_moe["wg"]                          # (L, d, E)
            return out, self._calibrated(wg, cfg, calib_x)
        if "wg" not in params:
            return params, self
        new = self.prepare_layer(params, cfg, calib_x,
                                 n_ep_devices=n_ep_devices)
        return new, self._calibrated(new["wg"][None], cfg, calib_x)

    def _calib_scores(self, wg_stack, cfg, calib_x):
        """Pooled normalized gating scores over all layers' routers."""
        def one(wg):
            return gating.route(calib_x, wg, cfg.top_k,
                                cfg.router_norm_topk).norm_score
        return jax.vmap(one)(wg_stack)

    def _calibrated(self, wg_stack, cfg, calib_x) -> "SparsityPolicy":
        """Override in subclasses that support ``drop_target``."""
        return self

    def calibrate(self, prepared_params: Dict, cfg,
                  calib_x) -> "SparsityPolicy":
        """Calibrate this policy's thresholds to ``drop_target`` against
        already-prepared params, WITHOUT re-running the (expensive) param
        preparation — for sweeping thresholds over one prepared model."""
        if "blocks" in prepared_params:
            wg = prepared_params["blocks"]["moe"]["wg"]
        else:
            wg = prepared_params["wg"][None]
        return self._calibrated(wg, cfg, calib_x)

    # -- (b) routing -----------------------------------------------------

    def route(self, params: Dict, x, cfg, *,
              loads=None) -> drop_mod.SubExpertPairs:
        raise NotImplementedError

    def sub_pair_keep(self, score, is_major, sub_idx, cfg, *, n_dev: int = 1,
                      loads=None, thresholds=None):
        """Keep mask over already-expanded (T, K*P) sub-expert pairs — the
        form the S-ETP shard_map body needs (it expands routing itself so
        the AlltoAll layout stays fused). ``loads``: (n_dev,) pre-drop
        histogram when ``needs_loads``; ``thresholds``: per-layer (2,)
        calibrated pair when the params carry one."""
        raise NotImplementedError

    # -- helpers ---------------------------------------------------------

    def per_token(self, batch: int, seq: int) -> "SparsityPolicy":
        """Expand per-row (B,) threshold leaves to per-token (B*S,) so a
        per-slot/per-request policy broadcasts over a flattened (B*S, d)
        token block. Scalar leaves pass through."""
        if seq == 1:
            return self

        def f(leaf):
            a = jnp.asarray(leaf)
            return jnp.repeat(a, seq) if a.ndim == 1 else leaf
        return jax.tree_util.tree_map(f, self)

    def dispatch_capacity(self, n_tokens: int) -> Optional[int]:
        """Exact-capacity hint: pin dispatch capacity to the token count so
        no pair can overflow-drop (each token selects a sub-expert at most
        once, so capacity == T is always sufficient)."""
        return n_tokens if self.exact_capacity else None


# ---------------------------------------------------------------------------
# Concrete policies
# ---------------------------------------------------------------------------

@register_policy("none")
@dataclasses.dataclass(frozen=True)
class NoDrop(SparsityPolicy):
    """No partition, no dropping: the plain top-k MoE layer."""
    partition_p: int = 1
    _dynamic: Tuple[str, ...] = ()

    def route(self, params, x, cfg, *, loads=None):
        return moe_mod.route_plain(params, x, cfg)

    def sub_pair_keep(self, score, is_major, sub_idx, cfg, *, n_dev=1,
                      loads=None, thresholds=None):
        return jnp.ones_like(score, dtype=bool)

    @classmethod
    def from_config(cls, ds, drop_target=None, **kw):
        return cls(**kw)


@register_policy("1t")
@dataclasses.dataclass(frozen=True)
class OneTDrop(SparsityPolicy):
    """1T-Drop (§4.1): drop a token-expert pair entirely when its normalized
    gating score is below T¹ — with partition, both halves go together."""
    partition_p: int = 2
    t_drop: float = 0.08
    _dynamic: Tuple[str, ...] = ("t_drop",)

    def route(self, params, x, cfg, *, loads=None):
        r = gating.route(x, params["wg"], cfg.top_k, cfg.router_norm_topk)
        return drop_mod.expand_pairs_1t(r.idx, r.combine, r.norm_score,
                                        self.partition_p, self.t_drop)

    def sub_pair_keep(self, score, is_major, sub_idx, cfg, *, n_dev=1,
                      loads=None, thresholds=None):
        return score > _bt(self.t_drop, score)

    def _calibrated(self, wg_stack, cfg, calib_x):
        if self.drop_target is None:
            return self
        scores = self._calib_scores(wg_stack, cfg, calib_x)
        t = drop_mod.calibrate_threshold(scores, self.drop_target)
        return dataclasses.replace(self, t_drop=float(t))

    @classmethod
    def from_config(cls, ds, drop_target=None, **kw):
        return cls(partition_p=ds.partition_p, importance=ds.importance,
                   t_drop=ds.t_drop, drop_target=drop_target, **kw)


@register_policy("2t")
@dataclasses.dataclass(frozen=True)
class TwoTDrop(SparsityPolicy):
    """2T-Drop (§4.2): below T²_major drop both halves, between compute the
    reconstructed MAJOR half only, above T²_minor compute the full expert."""
    partition_p: int = 2
    t_major: float = 0.07
    t_minor: float = 0.09
    _dynamic: Tuple[str, ...] = ("t_major", "t_minor")

    def _pair_thresholds(self, r, params, cfg, loads):
        return self.t_major, self.t_minor

    def route(self, params, x, cfg, *, loads=None):
        r = gating.route(x, params["wg"], cfg.top_k, cfg.router_norm_topk)
        tm, tn = self._pair_thresholds(r, params, cfg, loads)
        return drop_mod.expand_pairs_2t(r.idx, r.combine, r.norm_score,
                                        self.partition_p, tm, tn)

    def sub_pair_keep(self, score, is_major, sub_idx, cfg, *, n_dev=1,
                      loads=None, thresholds=None):
        # strict > on BOTH thresholds (matching one_t_keep's boundary), so
        # t_major == t_minor degenerates 2T -> 1T exactly, incl. at the
        # boundary score.
        return jnp.where(is_major, score > _bt(self.t_major, score),
                         score > _bt(self.t_minor, score))

    def _calibrated(self, wg_stack, cfg, calib_x, delta: float = 0.05):
        if self.drop_target is None:
            return self
        # calibrate in RATE space (band = ±delta drop rate around the
        # target) so flops saved == target regardless of the score spread:
        # saved = (t-δ) + ½·2δ = target.
        scores = self._calib_scores(wg_stack, cfg, calib_x)
        tm = drop_mod.calibrate_threshold(
            scores, max(self.drop_target - delta, 0.0))
        tn = drop_mod.calibrate_threshold(
            scores, min(self.drop_target + delta, 1.0))
        return dataclasses.replace(self, t_major=float(tm), t_minor=float(tn))

    @classmethod
    def from_config(cls, ds, drop_target=None, **kw):
        return cls(partition_p=ds.partition_p, importance=ds.importance,
                   t_major=ds.t_major, t_minor=ds.t_minor,
                   drop_target=drop_target, **kw)


@register_policy("load_aware")
@dataclasses.dataclass(frozen=True)
class LoadAwareTwoT(SparsityPolicy):
    """2T-Drop with load-aware thresholding (§4.3): each EP device's
    threshold steps down with its load ratio, so lightly-loaded devices
    drop less — the makespan (max device load) sets the step time anyway.

    ``n_devices`` models the EP layout on the single-device dispatch path
    (contiguous expert blocks, as in ``core.load_aware``); the S-ETP body
    passes its real strided device mapping instead. With ``loads`` uniform
    (or ``n_devices == 1``) this is exactly ``TwoTDrop(t_max - t_gap,
    t_max + t_gap)``."""
    partition_p: int = 2
    n_devices: int = 1
    t_max: float = 0.12
    t_gap: float = 0.01
    _dynamic: Tuple[str, ...] = ("t_max", "t_gap")
    needs_loads = True

    def _t1(self, score, loads, dev_of):
        """Per-pair stepped-down T¹ = t_max * min(load_ratio, 1)[device]."""
        loads = loads.astype(jnp.float32)
        ratio = loads / jnp.maximum(jnp.mean(loads), 1e-9)
        factor = jnp.minimum(ratio, 1.0)
        return _bt(self.t_max, score) * factor[dev_of]

    def route(self, params, x, cfg, *, loads=None):
        r = gating.route(x, params["wg"], cfg.top_k, cfg.router_norm_topk)
        E = params["wg"].shape[1]
        per_dev = max(E // self.n_devices, 1)
        if loads is None:
            hist = gating.expert_histogram(r.idx, E)
            from . import load_aware
            loads = load_aware.device_loads(hist, per_dev)
        t1 = self._t1(r.norm_score, loads, r.idx // per_dev)
        gap = _bt(self.t_gap, r.norm_score)
        return drop_mod.expand_pairs_2t(
            r.idx, r.combine, r.norm_score, self.partition_p,
            jnp.maximum(t1 - gap, 0.0), t1 + gap)

    def sub_pair_keep(self, score, is_major, sub_idx, cfg, *, n_dev=1,
                      loads=None, thresholds=None):
        if loads is None:
            raise ValueError("LoadAwareTwoT.sub_pair_keep needs the psum'd "
                             "per-device load histogram")
        t1 = self._t1(score, loads, sub_idx % n_dev)   # strided placement
        gap = _bt(self.t_gap, score)
        return jnp.where(is_major, score > jnp.maximum(t1 - gap, 0.0),
                         score > t1 + gap)

    @classmethod
    def from_config(cls, ds, drop_target=None, **kw):
        return cls(partition_p=ds.partition_p, importance=ds.importance,
                   t_max=ds.t_max, t_gap=(ds.t_minor - ds.t_major) / 2,
                   drop_target=drop_target, **kw)


@register_policy("per_layer")
@dataclasses.dataclass(frozen=True)
class PerLayerCalibrated2T(SparsityPolicy):
    """Beyond-paper (§5.3.3 future work): per-layer (T²_major, T²_minor)
    calibrated so EVERY layer hits ``drop_target`` on its own router's
    score distribution (Fig 12: a global T over-drops in deep layers).
    Thresholds live in the param tree as ``moe["thresholds"]`` (2,) per
    layer, so layer scans slice them automatically."""
    partition_p: int = 2
    drop_target: Optional[float] = 0.25
    delta: float = 0.05
    _dynamic: Tuple[str, ...] = ()

    def prepare_layer(self, moe_params, cfg, calib_x=None, *,
                      n_ep_devices: int = 0):
        out = super().prepare_layer(moe_params, cfg, calib_x,
                                    n_ep_devices=n_ep_devices)
        r = gating.route(calib_x, moe_params["wg"], cfg.top_k,
                         cfg.router_norm_topk)
        target = self.drop_target if self.drop_target is not None else 0.25
        tm = drop_mod.calibrate_threshold(
            r.norm_score, max(target - self.delta, 0.0))
        tn = drop_mod.calibrate_threshold(
            r.norm_score, min(target + self.delta, 1.0))
        out = dict(out)
        out["thresholds"] = jnp.stack([tm, tn])
        return out

    def _layer_thresholds(self, params=None, thresholds=None):
        th = thresholds if thresholds is not None else \
            (params or {}).get("thresholds")
        if th is None:
            raise ValueError("per_layer policy: params carry no "
                             "'thresholds' — run policy.prepare() first")
        return th[0], th[1]

    def route(self, params, x, cfg, *, loads=None):
        r = gating.route(x, params["wg"], cfg.top_k, cfg.router_norm_topk)
        tm, tn = self._layer_thresholds(params)
        return drop_mod.expand_pairs_2t(r.idx, r.combine, r.norm_score,
                                        self.partition_p, tm, tn)

    def sub_pair_keep(self, score, is_major, sub_idx, cfg, *, n_dev=1,
                      loads=None, thresholds=None):
        tm, tn = self._layer_thresholds(thresholds=thresholds)
        return jnp.where(is_major, score > tm, score > tn)

    @classmethod
    def from_config(cls, ds, drop_target=None, **kw):
        return cls(partition_p=ds.partition_p, importance=ds.importance,
                   drop_target=0.25 if drop_target is None else drop_target,
                   **kw)


# ---------------------------------------------------------------------------
# Registry helpers
# ---------------------------------------------------------------------------

def make_policy(name: str, ds=None, *, drop_target: Optional[float] = None,
                **kw) -> SparsityPolicy:
    """Build a registered policy from a ``DualSparseConfig`` (or defaults).

    ``name``: none | 1t | 2t | load_aware | per_layer. Extra kwargs
    (``use_kernel=``, ``exact_capacity=``, ...) override execution hints."""
    if name not in POLICIES:
        raise KeyError(f"unknown sparsity policy {name!r}; registered: "
                       f"{sorted(POLICIES)}")
    if ds is None:
        from ..configs.base import DualSparseConfig
        ds = DualSparseConfig()
    return POLICIES[name].from_config(ds, drop_target=drop_target, **kw)


def default_policy() -> SparsityPolicy:
    return NoDrop()


def registered_policies() -> Dict[str, Type[SparsityPolicy]]:
    """Snapshot of the policy registry (name -> class). ``repro.lint``
    iterates this to audit every policy's static/traced field split."""
    return dict(POLICIES)
