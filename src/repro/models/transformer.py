"""Decoder-only transformer stack covering the dense / moe / ssm / hybrid /
vlm families, with jax.lax.scan over stacked layer params.

Three entry modes per model:
  * train/prefill forward over a full sequence (blockwise attention),
  * single-token decode against a cache (dict-of-arrays, stacked over layers).

Distribution is injected via ``DistContext`` — when present, the MoE layer
uses the S-ETP shard_map path (paper §3.3) and activations get sharding
constraints; when absent everything is single-device pure JAX (tests).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core import drop as drop_mod
from ..core import gating
from ..core import moe as moe_mod
from ..core import setp as setp_mod
from ..obs import MetricsState, ObsCache
from . import attention as attn
from . import layers as L
from . import mamba2 as mm
from .layers import normal, ones


@dataclasses.dataclass(frozen=True)
class DistContext:
    """How to distribute the forward pass.

    MoE sparsity is configured by ONE object: ``policy`` (a
    ``core.policy.SparsityPolicy``; ``None`` means ``NoDrop``). The policy
    owns routing (which pairs to compute), the drop thresholds, and the
    execution hints (kernel choice, dispatch capacity factor, exact
    capacity for batch-composition-invariant serving). Params must have
    been prepared by the SAME policy (``policy.prepare``)."""
    mesh: Mesh
    moe_impl: str = "setp"        # "setp" (shard_map AlltoAll EP) | "gspmd"
    policy: Optional[Any] = None  # SparsityPolicy; None == NoDrop
    remat: bool = False           # activation checkpointing on blocks
    remat_policy: str = "none"    # none | dots — jax.checkpoint policy

    def constrain(self, x, spec: P):
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, spec))


def _maybe_constrain(x, dist: Optional[DistContext], spec):
    if dist is None:
        return x
    from ..distributed.sharding import batch_spec
    return dist.constrain(x, batch_spec(x.shape[0], dist.mesh, extra=spec))


def _residual_spec(dist: Optional[DistContext], seq_len: int,
                   family: str = "dense"):
    """Sequence parallelism: keep the (B, S, d) residual stream sharded over
    the model axis along S whenever it divides — norms/projections are
    per-token, attention context-parallelizes its q-blocks along the same
    boundaries, and the S-ETP MoE wants exactly this layout. Re-replicating
    between layers costs an all-gather of the full residual per layer.

    NOT for ssm/hybrid: the Mamba causal conv + chunk scan recur along S,
    so a seq-sharded residual forces halo exchanges/permutes every layer
    (measured: zamba2 train collectives 1.9 -> 4.7 s). Those families keep
    the batch-only layout."""
    if dist is None or family in ("ssm", "hybrid"):
        return (None, None)
    model_n = dist.mesh.shape.get("model", 1)
    if model_n > 1 and seq_len % model_n == 0 and seq_len // model_n >= 128:
        return ("model", None)
    return (None, None)


# ---------------------------------------------------------------------------
# Block params
# ---------------------------------------------------------------------------

def make_block_params(key, cfg):
    """One decoder block (pre-norm). Families:
    dense/vlm: attn + mlp; moe: attn + moe; ssm: mamba only."""
    ks = jax.random.split(key, 4)
    if cfg.family == "ssm":
        return {"ln1": ones((cfg.d_model,), ("embed",)),
                "mamba": mm.make_mamba2_params(ks[0], cfg)}
    p: Dict[str, Any] = {"ln1": ones((cfg.d_model,), ("embed",)),
                         "ln2": ones((cfg.d_model,), ("embed",))}
    if cfg.attn_kind == "mla":
        p["attn"] = attn.make_mla_params(ks[0], cfg)
    else:
        p["attn"] = attn.make_gqa_params(ks[0], cfg)
    if cfg.is_moe:
        p["moe"] = moe_mod.make_moe_params(ks[1], cfg)
    else:
        p["mlp"] = L.make_mlp_params(ks[1], cfg.d_model, cfg.d_ff, cfg.mlp_kind)
    return p


def make_hybrid_params(key, cfg):
    """Zamba2-style: stacked mamba blocks + ONE shared attention block
    (attn + its own mlp) applied every ``attn_every`` layers."""
    k1, k2 = jax.random.split(key)
    mamba_cfg = cfg
    stacked = L.stack_layer_params(
        k1, cfg.n_layers,
        lambda k: {"ln1": ones((cfg.d_model,), ("embed",)),
                   "mamba": mm.make_mamba2_params(k, cfg)})
    ks = jax.random.split(k2, 3)
    shared = {
        "ln1": ones((cfg.d_model,), ("embed",)),
        "attn": attn.make_gqa_params(ks[0], cfg),
        "ln2": ones((cfg.d_model,), ("embed",)),
        "mlp": L.make_mlp_params(ks[1], cfg.d_model, cfg.d_ff, cfg.mlp_kind),
    }
    return {"mamba_blocks": stacked, "shared_attn": shared}


# ---------------------------------------------------------------------------
# Block forward
# ---------------------------------------------------------------------------

def _attn_forward(p, x, positions, cfg, *, window: int, dist,
                  capture_cap: int = 0, cache_dtype=jnp.bfloat16):
    """capture_cap > 0: also return the populated decode cache."""
    if cfg.attn_kind == "mla":
        if capture_cap:
            return attn.mla_prefill_attention(p, x, positions, cfg,
                                              window=window, cap=capture_cap,
                                              cache_dtype=cache_dtype,
                                              dist=dist)
        return attn.mla_attention(p, x, positions, cfg, window=window,
                                  dist=dist)
    if capture_cap:
        return attn.gqa_prefill_attention(p, x, positions, cfg,
                                          window=window, cap=capture_cap,
                                          cache_dtype=cache_dtype, dist=dist)
    return attn.gqa_attention(p, x, positions, cfg, window=window,
                              dist=dist)


def _policy_of(dist: Optional[DistContext]):
    if dist is not None and dist.policy is not None:
        return dist.policy
    from ..core.policy import NoDrop
    return NoDrop()


def _moe_forward(p, x, cfg, dist: Optional[DistContext], aux: bool = False,
                 collect: bool = False, layer=None):
    """MoE layer forward under ``dist.policy`` (default ``NoDrop``).

    Returns ``(y, aux_loss, overflow)``: aux_loss is None unless ``aux``
    (training); overflow is the scalar count of token-expert pairs dropped
    by dispatch-capacity overflow (on the setp/shard_map path this is the
    psum'd global count across device-level and local-expert seating).

    ``collect``: the third return is instead the per-layer ``repro.obs``
    stats dict (kept-pair expert_load histogram over sub-expert ids plus
    kept_full/kept_major/dropped_pairs/overflow_pairs) — same routing,
    bit-identical ``y``.

    ``layer``: ``p``'s expert weights are the whole layer-stacked arrays
    (see ``_expert_stack_xs``) and this is the layer to run."""
    B, S, d = x.shape
    aux_val = None
    if aux:
        aux_val = moe_mod.aux_loss_for(p, x.reshape(-1, d), cfg)
    policy = _policy_of(dist)
    if dist is not None and dist.moe_impl == "setp":
        if collect:
            y, stats = setp_mod.setp_moe_forward(p, x, cfg, dist.mesh,
                                                 policy=policy,
                                                 return_stats=True)
            return y, aux_val, stats
        y, overflow = setp_mod.setp_moe_forward(p, x, cfg, dist.mesh,
                                                policy=policy,
                                                return_overflow=True)
        return y, aux_val, overflow
    xt = x.reshape(-1, d)
    # per-request/per-slot threshold leaves come in shaped (B,): expand them
    # to per-token so routing broadcasts over the flattened (B*S, d) block
    policy = policy.per_token(B, S)
    with jax.named_scope("route"):
        pairs = policy.route(p, xt, cfg)
    # exact capacity: one expert receives at most one pair per token, so
    # capacity == T guarantees zero overflow drops at any load skew
    y, overflow = moe_mod.moe_forward_dispatch(
        p, xt, cfg, pairs=pairs, capacity_factor=policy.capacity_factor,
        capacity=policy.dispatch_capacity(xt.shape[0]),
        use_kernel=policy.use_kernel, return_overflow=True,
        mode_grouped=policy.kernel_mode_grouping,
        fused_pipeline=getattr(policy, "fused_pipeline", None), layer=layer)
    if collect:
        n_sub = p["w1"].shape[-3]
        p_factor = pairs.idx.shape[1] // pairs.modes.shape[1]
        kf, km, dr = drop_mod.sub_pair_outcome_counts(pairs.keep, p_factor)
        stats = {"expert_load": gating.expert_histogram(pairs.idx, n_sub,
                                                        keep=pairs.keep),
                 "kept_full": kf, "kept_major": km, "dropped_pairs": dr,
                 "overflow_pairs": overflow}
        return y.reshape(B, S, d), aux_val, stats
    return y.reshape(B, S, d), aux_val, overflow


def _ffn_block(bp, x, cfg, dist, collect_stats, layer=None):
    """ln2 + MoE (or dense MLP) + residual, under the ``moe`` (``mlp``)
    named scope. Returns (x, moe_overflow or obs stats dict)."""
    overflow = jnp.zeros((), jnp.int32)
    with jax.named_scope("moe" if "moe" in bp else "mlp"):
        h = L.rms_norm(x, bp["ln2"], cfg.norm_eps)
        if "moe" in bp:
            y, _, overflow = _moe_forward(bp["moe"], h, cfg, dist,
                                          collect=collect_stats, layer=layer)
            x = x + y
        else:
            x = x + L.apply_mlp(bp["mlp"], h, cfg.mlp_kind)
    return x, overflow


def block_forward(bp, x, positions, cfg, *, window: int = 0,
                  dist: Optional[DistContext] = None, capture_cap: int = 0,
                  cache_dtype=jnp.bfloat16, with_aux: bool = False,
                  collect_stats: bool = False, layer=None):
    """Full-sequence block forward (train / prefill). With capture_cap the
    return is (x, cache_layer, moe_overflow) for the prefill->decode
    handoff (with ``collect_stats`` the third slot is the per-layer obs
    stats dict instead); with_aux returns (x, load-balance aux loss) for
    MoE training."""
    no_overflow = jnp.zeros((), jnp.int32)
    if cfg.family == "ssm" or "mamba" in bp:
        h = L.rms_norm(x, bp["ln1"], cfg.norm_eps)
        if capture_cap:
            y, st = mm.mamba2_forward(bp["mamba"], h, cfg, return_state=True)
            return x + y, st, no_overflow
        x = x + mm.mamba2_forward(bp["mamba"], h, cfg)
        return (x, jnp.zeros(())) if with_aux else x
    cache_layer = None
    with jax.named_scope("attention"):
        h = L.rms_norm(x, bp["ln1"], cfg.norm_eps)
        if capture_cap:
            y, cache_layer = _attn_forward(bp["attn"], h, positions, cfg,
                                           window=window, dist=dist,
                                           capture_cap=capture_cap,
                                           cache_dtype=cache_dtype)
            x = x + y
        else:
            x = x + _attn_forward(bp["attn"], h, positions, cfg,
                                  window=window, dist=dist)
    if with_aux and "moe" in bp:
        with jax.named_scope("moe"):
            h = L.rms_norm(x, bp["ln2"], cfg.norm_eps)
            y, aux, _ = _moe_forward(bp["moe"], h, cfg, dist, aux=True)
        return x + y, aux
    x, overflow = _ffn_block(bp, x, cfg, dist, collect_stats, layer)
    if with_aux:
        return x, jnp.zeros(())
    return (x, cache_layer, overflow) if capture_cap else x


def block_decode(bp, x, cache_layer, pos, cfg, *, window: int = 0,
                 dist: Optional[DistContext] = None, layout=None,
                 page_table=None, write_mask=None, read_len=None,
                 collect_stats: bool = False, layer=None):
    """One-token decode. cache_layer is this layer's cache dict slice.
    Returns (x, cache_layer, moe_overflow) — or the per-layer obs stats
    dict in the third slot under ``collect_stats``. ``layout``/
    ``page_table``/``write_mask`` select the KV storage (see
    gqa_decode_attention)."""
    no_overflow = jnp.zeros((), jnp.int32)
    if cfg.family == "ssm" or "mamba" in bp:
        h = L.rms_norm(x, bp["ln1"], cfg.norm_eps)
        st = mm.MambaState(cache_layer["conv"], cache_layer["ssm"])
        y, st = mm.mamba2_decode(bp["mamba"], h, st, cfg)
        return x + y, {"conv": st.conv, "ssm": st.ssm}, no_overflow
    with jax.named_scope("attention"):
        h = L.rms_norm(x, bp["ln1"], cfg.norm_eps)
        if cfg.attn_kind == "mla":
            y, cache_layer = attn.mla_decode_attention(
                bp["attn"], h, cache_layer, pos, cfg, window)
        else:
            y, cache_layer = attn.gqa_decode_attention(
                bp["attn"], h, cache_layer, pos, cfg, window,
                layout=layout, page_table=page_table, write_mask=write_mask,
                read_len=read_len)
        x = x + y
    x, overflow = _ffn_block(bp, x, cfg, dist, collect_stats, layer)
    return x, cache_layer, overflow


# ---------------------------------------------------------------------------
# Model params
# ---------------------------------------------------------------------------

def make_model_params(key, cfg):
    k_emb, k_blocks, k_fin = jax.random.split(key, 3)
    p: Dict[str, Any] = {
        "embed": L.make_embed_params(k_emb, cfg.vocab_size, cfg.d_model,
                                     cfg.tie_embeddings),
        "final_norm": ones((cfg.d_model,), ("embed",)),
    }
    if cfg.family == "hybrid":
        p.update(make_hybrid_params(k_blocks, cfg))
    else:
        p["blocks"] = L.stack_layer_params(
            k_blocks, cfg.n_layers, lambda k: make_block_params(k, cfg))
    if cfg.frontend:
        # stub frontends provide embeddings directly; a linear projector
        # adapts them to d_model (the one real parameter of the stub).
        p["frontend_proj"] = normal(k_fin, (cfg.d_model, cfg.d_model),
                                    ("embed", None))
    return p


# ---------------------------------------------------------------------------
# Stack forward (scan over layers)
# ---------------------------------------------------------------------------

def _positions_for(cfg, B, S, offset=0):
    pos = jnp.arange(S, dtype=jnp.int32)[None, :] + offset
    pos = jnp.broadcast_to(pos, (B, S))
    if cfg.mrope_sections:
        # stub M-RoPE positions: text-style (t == h == w); real VLM inputs
        # may pass explicit (3,B,S) grids via batch["positions"]
        pos = jnp.broadcast_to(pos[None], (3, B, S))
    return pos


def _expert_stack_xs(blocks, x, dist: Optional[DistContext], *,
                     split: bool = True):
    """``(xs, rejoin)`` for a scan over the stacked ``blocks``: ``rejoin``
    turns the body's slice of ``xs`` into ``(block params, layer)``.

    Where each layer's MoE runs the streamed fused kernel, which reads its
    layer straight from the layer-stacked expert weights
    (``moe.reads_layer_stack``), the expert stacks leave ``xs`` — scanning
    them would slice, i.e. copy, every stack on every call — and are
    closed over whole, with the layer index in ``xs`` instead. Elsewhere
    (``split`` False, no MoE, S-ETP, any other MoE path) ``xs`` is
    ``blocks`` and the layer is None."""
    moe = blocks.get("moe")
    policy = _policy_of(dist)
    if not (split and moe is not None
            and (dist is None or dist.moe_impl != "setp")
            and moe_mod.reads_layer_stack(
                moe["w1"].shape, x.shape[0] * x.shape[1],
                fused_pipeline=getattr(policy, "fused_pipeline", None),
                use_kernel=policy.use_kernel)):
        return blocks, lambda bp: (bp, None)
    stacks = {k: moe[k] for k in moe_mod.EXPERT_WEIGHTS}
    rest = {k: v for k, v in moe.items() if k not in stacks}

    def rejoin(xs):
        bp, layer = xs
        return {**bp, "moe": {**bp["moe"], **stacks}}, layer
    layers = jnp.arange(moe["w1"].shape[0], dtype=jnp.int32)
    return ({**blocks, "moe": rest}, layers), rejoin


def stack_forward(params, x, positions, cfg, *, window: int = 0,
                  dist: Optional[DistContext] = None, capture_cap: int = 0,
                  cache_dtype=jnp.bfloat16, with_aux: bool = False,
                  metrics: bool = True):
    """x: (B,S,d) -> (B,S,d) through all blocks. With capture_cap also
    returns the layer-stacked decode cache (prefill); with_aux returns
    (x, summed MoE load-balance aux loss).

    ``metrics`` (MoE + capture only): the captured cache carries a
    ``"metrics"`` MetricsState (per-layer expert-load histograms + sub-pair
    outcome counters) instead of the legacy ``"moe_overflow"`` scalar;
    decode steps accumulate into it on device."""
    if cfg.family == "hybrid":
        out = _hybrid_forward(params, x, positions, cfg, window=window,
                              dist=dist, capture_cap=capture_cap,
                              cache_dtype=cache_dtype)
        return (out, jnp.zeros(())) if with_aux else out

    collect = bool(metrics and capture_cap and cfg.is_moe)
    fwd = functools.partial(block_forward, cfg=cfg, window=window, dist=dist,
                            capture_cap=capture_cap, cache_dtype=cache_dtype,
                            with_aux=with_aux, collect_stats=collect)
    if dist is not None and dist.remat and not capture_cap:
        policy = None
        if dist.remat_policy == "dots":
            policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        fwd = jax.checkpoint(fwd, policy=policy)

    res_spec = _residual_spec(dist, x.shape[1], cfg.family)
    # only the prefill splits off the expert stacks: a forward without
    # capture may be differentiated, and its stacks' gradients stay
    # per-layer slices
    xs, rejoin = _expert_stack_xs(params["blocks"], x, dist,
                                  split=bool(capture_cap))

    def body(h, xs):
        bp, layer = rejoin(xs)
        h = _maybe_constrain(h, dist, res_spec)
        if capture_cap:
            h2, cl, of = fwd(bp, h, positions, layer=layer)
            return h2, (cl, of)
        out = fwd(bp, h, positions)
        if with_aux:
            return out
        return out, None

    x, caches = jax.lax.scan(body, x, xs)
    if capture_cap:
        layers, ofs = caches
        cache = ObsCache({"layers": layers})
        if collect:
            # scan stacked the per-layer stats dicts to (n_layers, ...)
            cache["metrics"] = MetricsState.from_stacked(ofs)
        else:
            cache["moe_overflow"] = jnp.sum(ofs)
        return x, cache
    if with_aux:
        return x, jnp.sum(caches)
    return x


def _hybrid_forward(params, x, positions, cfg, *, window: int = 0,
                    dist: Optional[DistContext] = None, capture_cap: int = 0,
                    cache_dtype=jnp.bfloat16):
    """Zamba2: shared attention block before every ``attn_every``-th mamba
    layer; mamba segments run under scan, attention occurrences are a python
    loop over the (small) number of groups so FLOPs are exact."""
    n = cfg.n_layers
    every = cfg.attn_every
    n_occ = (n + every - 1) // every
    shared = params["shared_attn"]
    attn_caches = []
    mamba_caches = []

    mamba_fwd = functools.partial(block_forward, cfg=cfg, dist=dist,
                                  capture_cap=capture_cap,
                                  cache_dtype=cache_dtype)
    if dist is not None and dist.remat and not capture_cap:
        mamba_fwd = jax.checkpoint(mamba_fwd)

    def mamba_body(h, bp):
        if capture_cap:
            h2, st, _ = mamba_fwd(bp, h, positions)
            return h2, st
        return mamba_fwd(bp, h, positions), None

    for occ in range(n_occ):
        lo, hi = occ * every, min((occ + 1) * every, n)
        h = L.rms_norm(x, shared["ln1"], cfg.norm_eps)
        if capture_cap:
            y, ac = attn.gqa_prefill_attention(shared["attn"], h, positions,
                                               cfg, window=window,
                                               cap=capture_cap,
                                               cache_dtype=cache_dtype)
            attn_caches.append(ac)
            x = x + y
        else:
            x = x + attn.gqa_attention(shared["attn"], h, positions, cfg,
                                       window=window)
        h = L.rms_norm(x, shared["ln2"], cfg.norm_eps)
        x = x + L.apply_mlp(shared["mlp"], h, cfg.mlp_kind)
        seg = jax.tree.map(lambda a: a[lo:hi], params["mamba_blocks"])
        x, segc = jax.lax.scan(mamba_body, x, seg)
        if capture_cap:
            mamba_caches.append(segc)
    if capture_cap:
        cache = ObsCache({
            "mamba": jax.tree.map(lambda *xs: jnp.concatenate(xs, 0),
                                  *mamba_caches),
            "attn": jax.tree.map(lambda *xs: jnp.stack(xs), *attn_caches),
            "moe_overflow": jnp.zeros((), jnp.int32),
        })
        return x, cache
    return x


def stack_decode(params, x, cache, pos, cfg, *, window: int = 0,
                 dist: Optional[DistContext] = None, layout=None,
                 page_table=None, write_mask=None, read_len=None):
    """One-token decode through all blocks. cache: layer-stacked dict."""
    if cfg.family == "hybrid":
        return _hybrid_decode(params, x, cache, pos, cfg, window=window,
                              dist=dist)

    # static gate: whether stats flow is decided by the cache's pytree
    # STRUCTURE (the "metrics" key), never by leaf values — so metric
    # value churn can't retrace
    collect = "metrics" in cache
    blocks, rejoin = _expert_stack_xs(params["blocks"], x, dist)

    def body(h, xs):
        bp, cl = xs
        bp, layer = rejoin(bp)
        h, cl, of = block_decode(bp, h, cl, pos, cfg, window=window,
                                 dist=dist, layout=layout,
                                 page_table=page_table,
                                 write_mask=write_mask, read_len=read_len,
                                 collect_stats=collect, layer=layer)
        return h, (cl, of)

    x, (new_layers, ofs) = jax.lax.scan(
        body, x, (blocks, cache["layers"]))
    new = ObsCache({"layers": new_layers})
    if collect:                   # device-side accumulation, no host sync
        new["metrics"] = cache["metrics"].accumulate(ofs)
    elif "moe_overflow" in cache:  # legacy running total across steps
        new["moe_overflow"] = cache["moe_overflow"] + jnp.sum(ofs)
    return x, new


def _hybrid_decode(params, x, cache, pos, cfg, *, window: int = 0,
                   dist: Optional[DistContext] = None):
    n, every = cfg.n_layers, cfg.attn_every
    n_occ = (n + every - 1) // every
    shared = params["shared_attn"]
    new_attn = {"k": [], "v": []}
    mamba_cache = cache["mamba"]
    new_mamba = []

    def mamba_body(h, xs):
        bp, cl = xs
        h, cl, _ = block_decode(bp, h, cl, pos, cfg, dist=dist)
        return h, cl

    for occ in range(n_occ):
        lo, hi = occ * every, min((occ + 1) * every, n)
        h = L.rms_norm(x, shared["ln1"], cfg.norm_eps)
        acache = {"k": cache["attn"]["k"][occ], "v": cache["attn"]["v"][occ]}
        y, acache = attn.gqa_decode_attention(shared["attn"], h, acache, pos,
                                              cfg, window)
        x = x + y
        new_attn["k"].append(acache["k"])
        new_attn["v"].append(acache["v"])
        h = L.rms_norm(x, shared["ln2"], cfg.norm_eps)
        x = x + L.apply_mlp(shared["mlp"], h, cfg.mlp_kind)
        seg_p = jax.tree.map(lambda a: a[lo:hi], params["mamba_blocks"])
        seg_c = jax.tree.map(lambda a: a[lo:hi], mamba_cache)
        x, seg_c = jax.lax.scan(mamba_body, x, (seg_p, seg_c))
        new_mamba.append(seg_c)
    new_cache = ObsCache({
        "mamba": jax.tree.map(lambda *xs: jnp.concatenate(xs, 0), *new_mamba),
        "attn": {"k": jnp.stack(new_attn["k"]), "v": jnp.stack(new_attn["v"])},
    })
    if "moe_overflow" in cache:
        new_cache["moe_overflow"] = cache["moe_overflow"]
    return x, new_cache


# ---------------------------------------------------------------------------
# Top-level forwards
# ---------------------------------------------------------------------------

def embed_inputs(params, batch, cfg, offset=0):
    """Token embeddings (+ stub frontend embeddings prepended for vlm/audio
    decoder-only archs). Returns (x, positions, n_prefix)."""
    tokens = batch["tokens"]
    B, S = tokens.shape
    x = L.embed(params["embed"], tokens)
    n_prefix = 0
    if cfg.frontend == "vision" and "frontend" in batch:
        fe = batch["frontend"] @ params["frontend_proj"]
        x = jnp.concatenate([fe.astype(x.dtype), x], axis=1)
        n_prefix = fe.shape[1]
    positions = batch.get("positions")
    if positions is None:
        positions = _positions_for(cfg, B, x.shape[1], offset)
    return x, positions, n_prefix


def forward(params, batch, cfg, *, window: int = 0,
            dist: Optional[DistContext] = None, with_aux: bool = False):
    """Full-sequence forward -> logits (B, S, vocab) over the token part.
    with_aux additionally returns the summed MoE load-balance loss."""
    x, positions, n_prefix = embed_inputs(params, batch, cfg)
    x = _maybe_constrain(x, dist, _residual_spec(dist, x.shape[1],
                                                 cfg.family))
    aux = jnp.zeros(())
    if with_aux:
        x, aux = stack_forward(params, x, positions, cfg, window=window,
                               dist=dist, with_aux=True)
    else:
        x = stack_forward(params, x, positions, cfg, window=window,
                          dist=dist)
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    if n_prefix:
        x = x[:, n_prefix:]
    logits = L.unembed(params["embed"], x)
    if dist is not None:
        logits = _maybe_constrain(logits, dist, (None, "model"))
    return (logits, aux) if with_aux else logits


def prefill(params, batch, cfg, *, cache_len: int = 0, window: int = 0,
            dist: Optional[DistContext] = None, cache_dtype=jnp.bfloat16,
            metrics: bool = True):
    """Prefill: full forward AND populated decode cache.

    Returns (logits (B,S,vocab), cache) with cache["pos"] set past the
    prompt (including any frontend prefix). ``metrics``: MoE caches carry
    a ``"metrics"`` MetricsState (see ``repro.obs``) instead of the legacy
    ``"moe_overflow"`` scalar."""
    x, positions, n_prefix = embed_inputs(params, batch, cfg)
    S_total = x.shape[1]
    cap = max(cache_len, S_total) if not window else \
        min(cache_len if cache_len else S_total, window)
    x = _maybe_constrain(x, dist, _residual_spec(dist, S_total, cfg.family))
    x, cache = stack_forward(params, x, positions, cfg, window=window,
                             dist=dist, capture_cap=cap,
                             cache_dtype=cache_dtype, metrics=metrics)
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    if n_prefix:
        x = x[:, n_prefix:]
    logits = L.unembed(params["embed"], x)
    cache["pos"] = jnp.asarray(S_total, jnp.int32)
    return logits, cache


def decode_step(params, token, cache, cfg, *, window: int = 0,
                dist: Optional[DistContext] = None, layout=None,
                page_table=None, write_mask=None, read_len=None):
    """token: (B,1) -> (logits (B,1,vocab), new cache). cache carries 'pos' —
    a scalar shared by the batch (synchronized decode) or a (B,) vector of
    per-slot positions (continuous batching over ragged requests).

    ``layout``/``page_table`` select the KV storage: with a ``PagedLayout``
    the cache holds one page pool per layer and ``page_table`` (B, P) int32
    maps each slot's logical pages to physical ones. ``write_mask`` (B,)
    suppresses KV writes for inactive slots (their pos still advances; the
    engine owns per-slot positions)."""
    pos = cache["pos"]
    with jax.named_scope("embed"):
        x = L.embed(params["embed"], token)
    x, new_cache = stack_decode(params, x, cache, pos, cfg, window=window,
                                dist=dist, layout=layout,
                                page_table=page_table, write_mask=write_mask,
                                read_len=read_len)
    with jax.named_scope("lm_head"):
        x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = L.unembed(params["embed"], x)
    new_cache["pos"] = pos + 1
    return logits, new_cache


# ---------------------------------------------------------------------------
# Chunked prefill (one slot, fixed-shape chunks)
# ---------------------------------------------------------------------------

def chunk_block(bp, x, cache_layer, slot, start, valid_len, cfg, *,
                layout, page_table=None, read_len=None,
                dist: Optional[DistContext] = None,
                collect_stats: bool = False, layer=None):
    """One block over a (1,C,d) prompt chunk of a single slot, appending its
    K/V into the decode cache. Returns (x, cache_layer, moe_overflow) —
    obs stats dict in the third slot under ``collect_stats``."""
    with jax.named_scope("attention"):
        h = L.rms_norm(x, bp["ln1"], cfg.norm_eps)
        y, cache_layer = attn.gqa_chunk_attention(
            bp["attn"], h, cache_layer, slot, start, valid_len, cfg,
            layout=layout, page_table=page_table, read_len=read_len)
        x = x + y
    x, overflow = _ffn_block(bp, x, cfg, dist, collect_stats, layer)
    return x, cache_layer, overflow


def chunk_step(params, tokens, slot, start, valid_len, cache, cfg, *,
               layout, page_table=None, read_len=None,
               dist: Optional[DistContext] = None):
    """Advance ONE slot's prompt by a fixed-size chunk.

    tokens: (1, C) prompt tokens at absolute positions start..start+C-1
    (rows >= ``valid_len`` are padding: their K/V writes are dropped and
    their logits are garbage the caller must ignore). Returns
    (logits (1, C, vocab), new cache) with cache['pos'][slot] advanced to
    start + valid_len.

    C is static; slot/start/valid_len are traced scalars — one jit serves
    every chunk of every prompt. Only gqa-attention, non-windowed families
    support chunked prefill (ssm/hybrid state and MLA latent caches have no
    per-slot chunk insert)."""
    assert cfg.family not in ("ssm", "hybrid") and cfg.attn_kind != "mla", \
        "chunked prefill requires gqa attention"
    slot = jnp.asarray(slot, jnp.int32)
    start = jnp.asarray(start, jnp.int32)
    valid_len = jnp.asarray(valid_len, jnp.int32)
    with jax.named_scope("embed"):
        x = L.embed(params["embed"], tokens)

    collect = "metrics" in cache  # static structural gate, as stack_decode
    blocks, rejoin = _expert_stack_xs(params["blocks"], x, dist)

    def body(h, xs):
        bp, cl = xs
        bp, layer = rejoin(bp)
        h, cl, of = chunk_block(bp, h, cl, slot, start, valid_len, cfg,
                                layout=layout, page_table=page_table,
                                read_len=read_len, dist=dist,
                                collect_stats=collect, layer=layer)
        return h, (cl, of)

    x, (new_layers, ofs) = jax.lax.scan(
        body, x, (blocks, cache["layers"]))
    with jax.named_scope("lm_head"):
        x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = L.unembed(params["embed"], x)
    new_cache = ObsCache({"layers": new_layers,
                          "pos": cache["pos"].at[slot].set(start + valid_len)})
    if collect:
        new_cache["metrics"] = cache["metrics"].accumulate(ofs)
    elif "moe_overflow" in cache:
        new_cache["moe_overflow"] = cache["moe_overflow"] + jnp.sum(ofs)
    return logits, new_cache


# ---------------------------------------------------------------------------
# Cache init
# ---------------------------------------------------------------------------

def init_cache(cfg, batch: int, context_len: int, *, window: int = 0,
               dtype=jnp.bfloat16, per_slot_pos: bool = False,
               metrics_spec=None):
    """Layer-stacked decode cache. ``context_len`` is the KV capacity
    (== window when windowed). ``per_slot_pos`` makes cache['pos'] a (B,)
    vector so each batch slot decodes at its own ragged position.
    ``metrics_spec``: an (n_layers, n_sub_experts) pair (see
    ``repro.obs.metrics_spec``) — the cache then carries a zeroed
    ``"metrics"`` MetricsState instead of the legacy ``"moe_overflow"``
    scalar, and decode steps accumulate obs stats into it."""
    cap = min(window, context_len) if window else context_len
    hd = cfg.resolved_head_dim

    def one_attn():
        return attn.ContiguousLayout(window).init(batch, cap, cfg.n_kv_heads,
                                                  hd, dtype)

    def one_mamba():
        st = mm.init_mamba_state(batch, cfg, jnp.float32)
        return {"conv": st.conv, "ssm": st.ssm}

    if cfg.family == "hybrid":
        n_occ = (cfg.n_layers + cfg.attn_every - 1) // cfg.attn_every
        cache = {
            "mamba": jax.tree.map(
                lambda *xs: jnp.stack(xs),
                *[one_mamba() for _ in range(cfg.n_layers)]),
            "attn": jax.tree.map(
                lambda *xs: jnp.stack(xs),
                *[one_attn() for _ in range(n_occ)]),
        }
    elif cfg.family == "ssm":
        cache = {"layers": jax.tree.map(
            lambda *xs: jnp.stack(xs),
            *[one_mamba() for _ in range(cfg.n_layers)])}
    elif cfg.attn_kind == "mla":
        cache = {"layers": jax.tree.map(
            lambda *xs: jnp.stack(xs),
            *[attn.init_mla_cache(batch, cap, cfg, dtype)
              for _ in range(cfg.n_layers)])}
    else:
        cache = {"layers": jax.tree.map(
            lambda *xs: jnp.stack(xs),
            *[one_attn() for _ in range(cfg.n_layers)])}
    cache = ObsCache(cache)
    cache["pos"] = jnp.zeros((batch,) if per_slot_pos else (), jnp.int32)
    if metrics_spec is not None:
        cache["metrics"] = MetricsState.zeros(*metrics_spec)
    else:
        # legacy: running count of token-expert pairs dropped by
        # dispatch-capacity overflow (accumulated by decode steps)
        cache["moe_overflow"] = jnp.zeros((), jnp.int32)
    return cache


def init_paged_cache(cfg, n_pages: int, page_size: int, n_slots: int, *,
                     dtype=jnp.bfloat16, metrics_spec=None):
    """Layer-stacked PAGED decode cache: one (n_pages, page_size, Hkv, D)
    pool per layer, shared by all slots through a per-slot page table the
    engine owns (the same logical->physical mapping applies to every
    layer). Physical page 0 is reserved as the write sink for retired
    slots. cache['pos'] is always per-slot (n_slots,)."""
    assert cfg.family not in ("ssm", "hybrid") and cfg.attn_kind != "mla", \
        "paged KV requires gqa attention"
    layout = attn.PagedLayout(page_size)
    hd = cfg.resolved_head_dim
    cache = ObsCache({"layers": jax.tree.map(
        lambda *xs: jnp.stack(xs),
        *[layout.init(n_pages, cfg.n_kv_heads, hd, dtype)
          for _ in range(cfg.n_layers)])})
    cache["pos"] = jnp.zeros((n_slots,), jnp.int32)
    if metrics_spec is not None:
        cache["metrics"] = MetricsState.zeros(*metrics_spec)
    else:
        cache["moe_overflow"] = jnp.zeros((), jnp.int32)
    return cache
