"""Jaxpr-level passes: dtype promotion, host syncs, policy retrace hazards.

All three inspect traces, never run computation, so they are cheap and
deterministic. The shared equation walker recurses into every sub-jaxpr a
higher-order primitive carries (pjit, scan, while, cond, shard_map,
pallas_call, custom_vjp, ...) by structurally scanning ``eqn.params`` for
Jaxpr/ClosedJaxpr values — robust to new primitives without a registry.
"""
from __future__ import annotations

import dataclasses
from typing import Iterator, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.extend import core as jcore

from .findings import Finding, Severity

# avals with these dtype names are silent-upcast hazards: nothing in this
# repo wants f64/c128 math, so their presence means a Python scalar or an
# x64-context promotion leaked into a hot path. Integers are NOT flagged
# (i64 shape math is benign and jit-invisible).
_BAD_DTYPES = ("float64", "complex128")

# primitives that force a host round-trip / side channel inside a step
_HOST_PRIMS = ("pure_callback", "io_callback", "debug_callback",
               "debug_print", "callback", "infeed", "outfeed")
# debug output: a WARNING (it stalls the stream, but changes no result)
_DEBUG_PRIMS = ("debug_callback", "debug_print")


def _subjaxprs(params) -> Iterator[jcore.Jaxpr]:
    """Yield every Jaxpr found structurally inside an eqn's params."""
    for v in params.values():
        stack = [v]
        while stack:
            x = stack.pop()
            if isinstance(x, jcore.ClosedJaxpr):
                yield x.jaxpr
            elif isinstance(x, jcore.Jaxpr):
                yield x
            elif isinstance(x, (tuple, list)):
                stack.extend(x)
            elif isinstance(x, dict):
                stack.extend(x.values())


def iter_eqns(jaxpr) -> Iterator[jcore.JaxprEqn]:
    """Depth-first over all equations, sub-jaxprs included."""
    if isinstance(jaxpr, jcore.ClosedJaxpr):
        jaxpr = jaxpr.jaxpr
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _subjaxprs(eqn.params):
            yield from iter_eqns(sub)


def _aval_dtype_name(aval) -> Optional[str]:
    dt = getattr(aval, "dtype", None)
    return None if dt is None else np.dtype(dt).name


def check_dtype_promotion(jaxpr, entry: str) -> List[Finding]:
    """Flag f64/c128 result avals and explicit converts into them.

    Run the traced function under ``jax.enable_x64(True)`` when
    probing for *latent* promotions: code that is f32-explicit stays clean,
    code that leans on weak-type defaults lights up."""
    out: List[Finding] = []
    seen = set()
    for eqn in iter_eqns(jaxpr):
        for var in eqn.outvars:
            name = _aval_dtype_name(var.aval)
            if name in _BAD_DTYPES:
                key = (eqn.primitive.name, name)
                if key in seen:
                    continue
                seen.add(key)
                out.append(Finding(
                    "jaxpr-dtype", "f64-upcast", Severity.ERROR, entry,
                    f"'{eqn.primitive.name}' produces {name} "
                    f"{getattr(var.aval, 'shape', ())}",
                    "pin the computation to f32 explicitly (astype / "
                    "dtype=) — under jax_enable_x64 this silently doubles "
                    "memory traffic and falls off the MXU fast path"))
        if eqn.primitive.name == "convert_element_type":
            new = np.dtype(eqn.params.get("new_dtype", np.float32)).name
            src = _aval_dtype_name(eqn.invars[0].aval) \
                if eqn.invars else None
            if new in _BAD_DTYPES and src not in _BAD_DTYPES:
                key = ("convert", src, new)
                if key not in seen:
                    seen.add(key)
                    out.append(Finding(
                        "jaxpr-dtype", "explicit-upcast", Severity.ERROR,
                        entry, f"explicit convert {src} -> {new}",
                        "remove the upcast or make it f32"))
    return out


def check_host_sync(jaxpr, entry: str) -> List[Finding]:
    """Flag host-callback/transfer primitives inside a jitted entry point:
    each one serializes the device stream against Python."""
    out: List[Finding] = []
    counts = {}
    for eqn in iter_eqns(jaxpr):
        if eqn.primitive.name in _HOST_PRIMS:
            counts[eqn.primitive.name] = counts.get(eqn.primitive.name,
                                                    0) + 1
    for prim, n in sorted(counts.items()):
        sev = Severity.WARNING if prim in _DEBUG_PRIMS else Severity.ERROR
        out.append(Finding(
            "jaxpr-hostsync", prim, sev, entry,
            f"{n}x '{prim}' inside the traced entry point",
            "host callbacks stall the accelerator pipeline every step; "
            "strip debug prints / move the side channel out of the jit"))
    return out


def check_traced_leaves(jaxpr, entry: str, leaves) -> List[Finding]:
    """Indirection arrays (page tables and friends) must enter a jitted
    step as TRACED arguments. ``leaves`` is a list of (shape, dtype-name)
    specs from the entry's meta; each must match an invar of the traced
    jaxpr. A spec matching only a captured CONSTANT is the retrace hazard
    this pass exists for: the constant's VALUE is baked into the
    executable, so every allocator churn (page reuse, prefix hit,
    eviction) silently recompiles the step."""
    out: List[Finding] = []
    if isinstance(jaxpr, jcore.ClosedJaxpr):
        consts = [(tuple(np.shape(c)), np.dtype(
            getattr(c, "dtype", type(c))).name) for c in jaxpr.consts]
        jaxpr = jaxpr.jaxpr
    else:
        consts = [(tuple(v.aval.shape), _aval_dtype_name(v.aval))
                  for v in jaxpr.constvars]
    invars = [(tuple(v.aval.shape), _aval_dtype_name(v.aval))
              for v in jaxpr.invars]
    for spec in leaves:
        shape, dtype = tuple(spec[0]), str(spec[1])
        if (shape, dtype) in invars:
            continue
        if (shape, dtype) in consts:
            out.append(Finding(
                "jaxpr-traced-leaves", "leaf-captured-constant",
                Severity.ERROR, entry,
                f"{dtype}{list(shape)} leaf is a captured constant, not a "
                f"traced argument",
                "pass the array into the jitted step as an argument — as a "
                "closure constant its value hashes into the jit cache key "
                "and every page-table update recompiles"))
        else:
            out.append(Finding(
                "jaxpr-traced-leaves", "leaf-missing", Severity.ERROR,
                entry, f"no {dtype}{list(shape)} invar in the traced step",
                "the entry's traced_leaves meta no longer matches the "
                "step's signature — update the registry entry"))
    return out


# ---------------------------------------------------------------------------
# Retrace-hazard audit of the SparsityPolicy registry (global pass)
# ---------------------------------------------------------------------------

def check_policy_retrace(policies=None) -> List[Finding]:
    """Cross-check every registered policy's pytree static/traced split.

    Hazards flagged:
      * a static (aux-data) field holding a jax/numpy array — its VALUE is
        hashed into the jit cache key, so every new threshold array
        retraces (and arrays make the aux tuple unhashable under jit);
      * any unhashable static field value (lists, dicts, sets);
      * a ``_dynamic`` name that is not a dataclass field (the flatten
        would raise AttributeError at dispatch time);
      * a dynamic leaf that cannot become a jnp array (it could never ride
        through shard_map / donated buffers).
    """
    if policies is None:
        from ..core.policy import registered_policies
        policies = registered_policies()
    from ..configs.base import DualSparseConfig
    out: List[Finding] = []
    ds = DualSparseConfig()
    for name, cls in sorted(policies.items()):
        entry = f"policy/{name}"
        fields = {f.name for f in dataclasses.fields(cls)}
        dyn = tuple(getattr(cls, "_pytree_dynamic", cls._dynamic))
        static = tuple(getattr(cls, "_pytree_static",
                               tuple(f for f in fields if f not in dyn)))
        for d in dyn:
            if d not in fields:
                out.append(Finding(
                    "policy-retrace", "dynamic-not-a-field", Severity.ERROR,
                    entry, f"_dynamic lists {d!r} but the dataclass has no "
                    f"such field"))
        try:
            pol = cls.from_config(ds)
        except Exception as e:  # noqa: BLE001 — report, don't crash the lint
            out.append(Finding(
                "policy-retrace", "from-config-failed", Severity.ERROR,
                entry, f"from_config(DualSparseConfig()) raised "
                f"{type(e).__name__}: {e}"))
            continue
        aux_vals = []
        for s in static:
            v = getattr(pol, s, None)
            if isinstance(v, (jnp.ndarray, np.ndarray)):
                out.append(Finding(
                    "policy-retrace", "traced-value-hashed", Severity.ERROR,
                    entry, f"static field {s!r} holds an array — its value "
                    f"becomes part of the jit cache key",
                    "move the field into _dynamic so it is a traced leaf"))
                continue
            aux_vals.append((s, v))
        try:
            hash(tuple(v for _, v in aux_vals))
        except TypeError:
            bad = [s for s, v in aux_vals
                   if not _hashable(v)]
            out.append(Finding(
                "policy-retrace", "unhashable-static", Severity.ERROR,
                entry, f"static field(s) {bad} are unhashable — the policy "
                f"cannot be a jit argument at all",
                "use tuples/frozen values for static structure, or list "
                "the field in _dynamic"))
        leaves, _ = jax.tree_util.tree_flatten(pol)
        if len(leaves) != len(dyn):
            out.append(Finding(
                "policy-retrace", "leaf-count-mismatch", Severity.ERROR,
                entry, f"tree_flatten yields {len(leaves)} leaves but "
                f"_dynamic lists {len(dyn)} fields"))
        for fname, leaf in zip(dyn, leaves):
            try:
                jnp.asarray(leaf)
            except Exception:  # noqa: BLE001
                out.append(Finding(
                    "policy-retrace", "untraceable-leaf", Severity.ERROR,
                    entry, f"dynamic field {fname!r} = {leaf!r} cannot "
                    f"become a jax array"))
    return out


def _hashable(v) -> bool:
    try:
        hash(v)
        return True
    except TypeError:
        return False
