"""Model dispatch: init/abstract params, train/prefill/serve step builders.

This is the public API surface used by tests, examples, benchmarks, and the
launchers. Family routing:

  dense | moe | ssm | hybrid | vlm  -> models.transformer
  audio                              -> models.whisper (enc-dec)
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from . import layers as L
from . import transformer, whisper
from .transformer import DistContext


def _mod(cfg: ModelConfig):
    return whisper if cfg.family == "audio" else transformer


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------

def init_params_and_axes(rng, cfg: ModelConfig, dtype=jnp.float32):
    """Returns (params, axes) trees. dtype applied to all floating leaves.

    The tree is drawn under one ``jit`` with the cast inside, so XLA fuses
    each leaf's draw into its cast: no float32 copy of a bf16 tree, and no
    per-layer trees beside their stacked copy. Peak device memory is the
    returned tree itself."""
    def build(key):
        params, _ = L.split_params(_mod(cfg).make_model_params(key, cfg))
        return jax.tree.map(lambda a: a.astype(dtype), params)

    _, axes = abstract_params_and_axes(cfg)
    return jax.jit(build)(rng), axes


def init_params(rng, cfg: ModelConfig, dtype=jnp.float32):
    return init_params_and_axes(rng, cfg, dtype)[0]


def abstract_params_and_axes(cfg: ModelConfig, dtype=jnp.float32):
    """ShapeDtypeStruct params (no allocation) + axes tree, for dry-runs.

    Param's axes ride in the treedef (aux data), so eval_shape of the Param
    tree preserves them without materializing anything."""
    tree = jax.eval_shape(lambda k: _mod(cfg).make_model_params(k, cfg),
                          jax.random.PRNGKey(0))
    params, axes = L.split_params(tree)
    if dtype != jnp.float32:
        params = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, dtype), params)
    return params, axes


def transform_params_for_dualsparse(params, cfg: ModelConfig, calib_x,
                                    n_ep_devices: int = 0,
                                    target_drop_rate: Optional[float] = None):
    """DEPRECATED shim over the ``SparsityPolicy`` API: equivalent to
    ``make_policy("2t" | "per_layer", cfg.dualsparse).prepare(...)[0]``.
    Prefer building a policy (``repro.core.policy``) and calling its
    ``prepare`` — that also returns the calibrated policy object that the
    rest of the stack (DistContext, engines, CLI) consumes."""
    import warnings
    warnings.warn(
        "transform_params_for_dualsparse is deprecated; build a policy via "
        "repro.core.policy.make_policy and call policy.prepare(...) instead",
        DeprecationWarning, stacklevel=2)
    from ..core.policy import make_policy
    ds = cfg.dualsparse
    if not (cfg.is_moe and ds.enabled):
        return params
    name = "per_layer" if target_drop_rate is not None else "2t"
    pol = make_policy(name, ds, drop_target=target_drop_rate)
    return pol.prepare(params, cfg, calib_x, n_ep_devices=n_ep_devices)[0]


# ---------------------------------------------------------------------------
# Loss / steps
# ---------------------------------------------------------------------------

def cross_entropy(logits, targets):
    logits = logits.astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return -jnp.mean(ll)


def loss_fn(params, batch, cfg: ModelConfig, *, window: int = 0,
            dist: Optional[DistContext] = None, aux_coef: float = 0.0):
    """Cross entropy (+ Switch-style MoE load-balance aux when aux_coef>0)."""
    if aux_coef and cfg.is_moe and cfg.family != "audio":
        logits, aux = _mod(cfg).forward(params, batch, cfg, window=window,
                                        dist=dist, with_aux=True)
        return cross_entropy(logits, batch["targets"]) + aux_coef * aux
    logits = _mod(cfg).forward(params, batch, cfg, window=window, dist=dist)
    return cross_entropy(logits, batch["targets"])


def make_train_step(cfg: ModelConfig, optimizer, *, window: int = 0,
                    dist: Optional[DistContext] = None,
                    aux_coef: float = 0.0):
    """(params, opt_state, batch) -> (params, opt_state, loss)."""
    def step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch, cfg,
                                                  window=window, dist=dist,
                                                  aux_coef=aux_coef)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = jax.tree.map(lambda p, u: p + u, params, updates)
        return params, opt_state, loss
    return step


def make_prefill_step(cfg: ModelConfig, *, cache_len: int = 0, window: int = 0,
                      dist: Optional[DistContext] = None,
                      cache_dtype=None, metrics: bool = True):
    """batch -> (logits (B,S,vocab), populated decode cache)."""
    import jax.numpy as _jnp
    cd = cache_dtype if cache_dtype is not None else _jnp.bfloat16
    # whisper (audio) caches have no MoE metrics seam
    kw = {} if cfg.family == "audio" else {"metrics": metrics}
    def step(params, batch):
        return _mod(cfg).prefill(params, batch, cfg, cache_len=cache_len,
                                 window=window, dist=dist, cache_dtype=cd,
                                 **kw)
    return step


def make_serve_step(cfg: ModelConfig, *, window: int = 0,
                    dist: Optional[DistContext] = None):
    """(params, token (B,1), cache) -> (logits, cache) — ONE new token."""
    def step(params, token, cache):
        return _mod(cfg).decode_step(params, token, cache, cfg,
                                     window=window, dist=dist)
    return step


def context_len_for(cfg: ModelConfig, prompt_len: int, new_tokens: int) -> int:
    """KV capacity needed to prefill ``prompt_len`` tokens (plus any stub
    frontend prefix) and then generate ``new_tokens``."""
    prefix = cfg.n_frontend_tokens if cfg.frontend == "vision" else 0
    return prompt_len + prefix + new_tokens


def init_cache(cfg: ModelConfig, batch: int, context_len: int, *,
               window: int = 0, dtype=jnp.bfloat16,
               per_slot_pos: bool = False, metrics_spec=None):
    kw: Dict[str, Any] = {}
    if cfg.family != "audio":
        kw["metrics_spec"] = metrics_spec
    if per_slot_pos:
        return _mod(cfg).init_cache(cfg, batch, context_len, window=window,
                                    dtype=dtype, per_slot_pos=True, **kw)
    return _mod(cfg).init_cache(cfg, batch, context_len, window=window,
                                dtype=dtype, **kw)


def abstract_cache(cfg: ModelConfig, batch: int, context_len: int, *,
                   window: int = 0, dtype=jnp.bfloat16):
    return jax.eval_shape(
        lambda: init_cache(cfg, batch, context_len, window=window,
                           dtype=dtype))


# ---------------------------------------------------------------------------
# Input construction (concrete); abstract variants live in launch.dryrun
# ---------------------------------------------------------------------------

def make_batch(rng, cfg: ModelConfig, batch: int, seq: int, kind: str,
               dtype=jnp.float32):
    """Concrete random batch for smoke tests / examples."""
    ks = jax.random.split(rng, 3)
    out: Dict[str, Any] = {
        "tokens": jax.random.randint(ks[0], (batch, seq), 0, cfg.vocab_size),
    }
    if kind == "train":
        out["targets"] = jax.random.randint(ks[1], (batch, seq), 0,
                                            cfg.vocab_size)
    if cfg.frontend == "vision":
        out["frontend"] = jax.random.normal(
            ks[2], (batch, cfg.n_frontend_tokens, cfg.d_model), dtype) * 0.1
    if cfg.frontend == "audio":
        out["audio_embeds"] = jax.random.normal(
            ks[2], (batch, cfg.n_frontend_tokens, cfg.d_model), dtype) * 0.1
    return out
