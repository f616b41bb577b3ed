"""Serving engines for the DualSparse-MoE inference system (paper §4).

Both engines implement the unified request API (``serving.api``:
``submit()`` / ``step()`` / ``drain()``) and share the jitted model steps:

``ServingEngine`` — the synchronized-batch baseline: requests are grouped to
a common (padded) prompt length, prefilled in one jitted call, then decoded
together with ONE shared absolute position. One ``step()`` serves one convoy
batch to completion. This is the exact setting of the paper's efficiency
evaluation (fixed 500-token prompts, 100 output tokens, §5.3.2) and is kept
as the benchmark baseline.

``ContinuousBatchingEngine`` — slot-based continuous batching for heavy
heterogeneous traffic: a fixed number of decode *slots* (the batch dimension
of one jitted decode step), an admission queue, per-slot absolute positions
and ragged KV handling (cache["pos"] is a (n_slots,) vector), per-request
EOS/budget retirement that frees slots mid-decode for waiting requests, and
a jitted fixed-shape prefill-insert so slot churn never retraces. One
``step()`` is one admit+decode scheduler iteration.

MoE sparsity is configured by ONE ``SparsityPolicy`` on the DistContext
(``core.policy``: none/1t/2t/load_aware/per_layer); requests may override
threshold values per request via ``GenerationConfig.policy`` (same policy
family) — the continuous engine stacks per-slot threshold leaves into the
jitted decode step, so mixed-threshold traffic co-decodes without retrace.

Request isolation: with ``exact_moe`` (continuous default) the MoE dispatch
capacity is set so no token-expert pair is ever dropped by overflow, making
each request's tokens independent of what else happens to be co-batched —
greedy outputs are bit-identical to a synchronized run of the same
requests. Overflow drops that do occur (non-exact deployments) are counted
and surfaced via ``engine.overflow_pairs``.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs.base import ModelConfig
from ..core.policy import NoDrop, SparsityPolicy
from ..models import model as M
from ..models import transformer
from ..models.transformer import DistContext
from ..obs import MetricsSnapshot, metrics_spec
from .api import EngineBase, GenerationConfig, Request, Result  # noqa: F401


def merge_policy_override(base: Optional[SparsityPolicy],
                          override: SparsityPolicy) -> SparsityPolicy:
    """Graft a per-request override's threshold LEAVES onto the engine base
    policy's static hints (exact_capacity, capacity_factor, ...): requests
    choose values, the deployment keeps its execution guarantees. Raises
    when the override is a different policy family."""
    if base is None:
        return override
    if type(override) is not type(base):
        raise ValueError(
            f"per-request policy must match the engine's policy family "
            f"{base.name!r} (got {override.name!r}); only threshold values "
            f"may differ")
    leaves = jax.tree_util.tree_flatten(override)[0]
    base_leaves, treedef = jax.tree_util.tree_flatten(base)
    assert len(leaves) == len(base_leaves)   # same class => same dynamics
    return jax.tree_util.tree_unflatten(treedef, leaves)


def exact_moe_dist(dist: Optional[DistContext]) -> DistContext:
    """A DistContext whose dispatch-path MoE never drops a token-expert pair
    by capacity overflow (capacity == T), making outputs
    batch-composition-invariant. The existing sparsity policy is preserved
    with its ``exact_capacity`` hint set; no policy means NoDrop + exact
    capacity."""
    if dist is not None:
        pol = dist.policy if dist.policy is not None else NoDrop()
        return dataclasses.replace(
            dist, policy=dataclasses.replace(pol, exact_capacity=True))
    from ..launch.mesh import make_host_mesh
    return DistContext(mesh=make_host_mesh(1), moe_impl="dispatch",
                       policy=NoDrop(exact_capacity=True))


def place_like_steps(tree, dist: Optional[DistContext]):
    """Commit an eagerly built engine cache to the placement its jitted
    steps return. With a DistContext the model's sharding constraints give
    every step output a NamedSharding on ``dist.mesh``, while a cache built
    outside jit is single-device; JAX keys its trace cache on that
    difference, so the first donated cache would trace (and, on a chip,
    compile) every step twice."""
    if dist is None:
        return tree
    return jax.device_put(tree, NamedSharding(dist.mesh, P()))


class ServingEngine(EngineBase):
    """Synchronized-batch engine around jitted prefill/serve steps."""

    def __init__(self, cfg: ModelConfig, params, *, batch_size: int = 8,
                 max_prompt_len: int = 512, max_new_tokens: int = 128,
                 window: int = 0, pad_token: int = 0,
                 dist: Optional[DistContext] = None,
                 exact_moe: bool = False, cache_dtype=jnp.bfloat16,
                 metrics: bool = True, trace: bool = False):
        super().__init__(metrics=metrics, trace=trace)
        self.cfg = cfg
        self.params = params
        self.batch_size = batch_size
        self.window = window
        self.pad_token = pad_token
        if exact_moe and cfg.is_moe:
            dist = exact_moe_dist(dist)
        self.dist = dist
        # device-resident obs MetricsState summed over served batches (one
        # lazy add per batch, drained only by engine.metrics()); None until
        # the first metrics-enabled batch finishes
        self._dev_metrics = None
        ctx = M.context_len_for(cfg, max_prompt_len, max_new_tokens)
        self.context_len = ctx
        # trace counters: incremented only when jit actually (re)traces
        self.prefill_traces = 0
        self.decode_traces = 0

        # the sparsity policy is a jit ARGUMENT (pytree): per-call overrides
        # with the same structure change only threshold leaves -> no retrace
        def prefill_step(params, batch, policy):
            self.prefill_traces += 1
            d = dist if (dist is None or policy is None) else \
                dataclasses.replace(dist, policy=policy)
            return M.make_prefill_step(cfg, cache_len=ctx, window=window,
                                       dist=d, cache_dtype=cache_dtype,
                                       metrics=metrics)(params, batch)

        def serve_step(params, token, cache, policy):
            self.decode_traces += 1
            d = dist if (dist is None or policy is None) else \
                dataclasses.replace(dist, policy=policy)
            return M.make_serve_step(cfg, window=window,
                                     dist=d)(params, token, cache)

        self._prefill = jax.jit(prefill_step)
        self._serve = jax.jit(serve_step)
        self.max_prompt_len = max_prompt_len

    def _policy_for(self, gen: GenerationConfig) -> Optional[SparsityPolicy]:
        base = self.dist.policy if self.dist is not None else None
        if gen.policy is None:
            return base
        if self.dist is None:
            raise ValueError("per-request policy override needs a "
                             "DistContext-backed engine (MoE dispatch path)")
        # keep the engine's execution hints (e.g. exact_moe's exact
        # capacity); the request only chooses threshold values
        return merge_policy_override(base, gen.policy)

    def _make_batch(self, prompts: List[np.ndarray]) -> Dict[str, jax.Array]:
        """Right-align (left-pad) prompts to the common max length so every
        real token sits at the end — causal attention then gives each request
        a correct suffix context (pads influence only via their K/V, which we
        accept for pad-light batches; equal-length prompts are exact)."""
        L = max(len(p) for p in prompts)
        toks = np.full((len(prompts), L), self.pad_token, np.int32)
        for i, p in enumerate(prompts):
            toks[i, L - len(p):] = p
        batch = {"tokens": jnp.asarray(toks)}
        if self.cfg.frontend == "vision":
            batch["frontend"] = jnp.zeros(
                (len(prompts), self.cfg.n_frontend_tokens, self.cfg.d_model))
        if self.cfg.frontend == "audio":
            batch["audio_embeds"] = jnp.zeros(
                (len(prompts), self.cfg.n_frontend_tokens, self.cfg.d_model))
        return batch

    # -- unified request API --------------------------------------------

    def _validate(self, req: Request) -> None:
        self._policy_for(req.gen)        # raises on family mismatch

    def _ready(self) -> bool:
        """Convoy semantics: wait for a full batch while more traffic is
        still arriving; a flush (``run``/end of trace) serves partials."""
        if not self._queue:
            return False
        return self._flush or len(self._queue) >= self.batch_size

    @staticmethod
    def _policy_sig(gen: GenerationConfig):
        if gen.policy is None:
            return None
        return (type(gen.policy),
                tuple(float(l) for l in
                      jax.tree_util.tree_flatten(gen.policy)[0]))

    def _trace_count(self) -> int:
        return self.prefill_traces + self.decode_traces

    def _device_metrics(self):
        return self._dev_metrics

    def _metrics_hook(self, snap: MetricsSnapshot) -> None:
        snap.gauge("repro_engine_batch_size", self.batch_size)

    def _step(self) -> bool:
        """Serve ONE convoy batch to completion: pop up to ``batch_size``
        queued requests (cut early at a per-request policy-override change —
        the policy is one jit argument per batch), prefill them together,
        decode with per-request EOS/budget/sampling. Returns True while more
        requests are queued."""
        if not self._queue:
            return False
        batch = [self._queue.popleft()]
        sig = self._policy_sig(batch[0][1].gen)
        while (len(batch) < self.batch_size and self._queue
               and self._policy_sig(self._queue[0][1].gen) == sig):
            batch.append(self._queue.popleft())
        self._run_batch(batch)
        return bool(self._queue)

    def _run_batch(self, batch: List[Tuple[int, Request]]) -> None:
        uids = [u for u, _ in batch]
        gens = [r.gen for _, r in batch]
        B = len(batch)
        b = self._make_batch([r.prompt for _, r in batch])
        policy = self._policy_for(gens[0])
        for u in uids:
            res = self._results[u]
            res.admitted_s = res.prefill_start_s = self._now()
        with self.tracer.span("prefill", batch=B):
            logits, cache = self._prefill(self.params, b, policy)
        last = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
        done = np.zeros(B, bool)
        max_steps = max(g.max_new_tokens for g in gens)
        t0 = time.perf_counter()
        with self.tracer.span("decode_loop", batch=B):
            for step in range(max_steps):
                last_np = np.asarray(last)
                for i in range(B):
                    if done[i]:
                        continue
                    self._record_token(uids[i], int(last_np[i, 0]))
                    res = self._results[uids[i]]
                    if (last_np[i, 0] == gens[i].eos_token
                            or len(res.tokens) >= gens[i].max_new_tokens):
                        done[i] = True
                if done.all():
                    break
                with self.tracer.span("decode", batch=B):
                    logits, cache = self._serve(self.params, last, cache,
                                                policy)
                last = self._next_tokens(logits, gens, uids, step)
        t_decode = time.perf_counter() - t0
        # drain the batch's device metrics into the engine accumulator with
        # ONE lazy device-side add — no host transfer until .metrics()
        m = cache.get("metrics") if isinstance(cache, dict) else None
        if m is not None:
            self._dev_metrics = m if self._dev_metrics is None \
                else self._dev_metrics + m
        now = self._now()
        for u in uids:
            self._results[u].decode_s = t_decode
            self._results[u].finished_s = now
            self.tracer.instant("retire", uid=u)

    @property
    def overflow_pairs(self) -> int:
        """Total MoE capacity-overflow drops across every batch served
        (reads the device-resident obs MetricsState — one scalar
        transfer, no per-step sync)."""
        if self._dev_metrics is None:
            return 0
        return int(self._dev_metrics.overflow_pairs)

    def _next_tokens(self, logits, gens, uids, step):
        greedy = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
        if all(g.temperature == 0 for g in gens):
            return greedy
        greedy_np = np.asarray(greedy)
        toks = np.empty((len(gens), 1), np.int32)
        for i, g in enumerate(gens):
            if g.temperature > 0:
                key = jax.random.fold_in(
                    jax.random.fold_in(jax.random.PRNGKey(g.seed),
                                       uids[i]), step)
                toks[i, 0] = int(jax.random.categorical(
                    key, logits[i, -1] / g.temperature))
            else:
                toks[i, 0] = greedy_np[i, 0]
        return jnp.asarray(toks)


# ---------------------------------------------------------------------------
# Continuous batching
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class _SlotState:
    uid: int
    gen: GenerationConfig
    n_emitted: int = 0


class ContinuousBatchingEngine(EngineBase):
    """Slot-based continuous-batching engine.

    * ``n_slots`` decode slots form the fixed batch dimension of ONE jitted
      decode step; admission/retirement never changes traced shapes, so slot
      churn never retraces (see ``decode_traces`` / ``prefill_traces``).
    * Prompts are right-padded to ``max_prompt_len`` and prefilled one
      request at a time by a jitted *prefill-insert* that writes the new
      request's KV (and its first greedy token) into a free slot of the
      shared ragged cache; ``cache["pos"]`` holds per-slot absolute
      positions, so requests at different depths decode together.
    * A request retires on EOS or budget exhaustion, immediately freeing its
      slot for the next queued request — mid-decode admission.

    Right-padding is exact for causal attention (pad K/V sits *after* every
    real token and is masked by per-slot validity until overwritten by
    decoded tokens); sliding-window (ring) caches would break that layout,
    so ``window`` is not supported here.

    For MoE models ``exact_moe=True`` (default) pins dispatch capacity to
    the token count so expert overflow can never silently drop a pair —
    request outputs are then independent of co-batched traffic and greedy
    tokens match a synchronized run bit-for-bit.
    """

    def __init__(self, cfg: ModelConfig, params, *, n_slots: int = 8,
                 max_prompt_len: int = 512, max_new_tokens: int = 128,
                 pad_token: int = 0, dist: Optional[DistContext] = None,
                 exact_moe: bool = True, cache_dtype=jnp.bfloat16,
                 metrics: bool = True, trace: bool = False):
        if cfg.family in ("audio", "ssm", "hybrid"):
            # ssm/hybrid: the Mamba recurrence runs over trailing pad tokens
            # during right-padded prefill and pollutes the captured decode
            # state — attention's per-slot validity masking has no recurrent
            # analog, so these families need chunked prefill (ROADMAP).
            raise NotImplementedError(
                f"continuous batching supports attention-based decoder-only "
                f"families, not {cfg.family!r}")
        super().__init__(metrics=metrics, trace=trace)
        self.cfg = cfg
        self.params = params
        self.n_slots = n_slots
        self.pad_token = pad_token
        self.max_prompt_len = max_prompt_len
        self.max_new_tokens = max_new_tokens
        if exact_moe and cfg.is_moe:
            dist = exact_moe_dist(dist)
        self.dist = dist
        self.context_len = M.context_len_for(cfg, max_prompt_len,
                                             max_new_tokens)
        self._prefix = (cfg.n_frontend_tokens if cfg.frontend == "vision"
                        else 0)
        # Per-slot sparsity policies: the base policy's threshold leaves are
        # stacked into (n_slots,) vectors and passed to the jitted decode as
        # a pytree ARGUMENT, so requests with per-request threshold
        # overrides (GenerationConfig.policy, same family) co-decode in one
        # fixed-shape step — values change, nothing retraces.
        self._base_policy = dist.policy if dist is not None else None
        self._policy_treedef = None
        if self._base_policy is not None:
            leaves, treedef = jax.tree_util.tree_flatten(self._base_policy)
            try:
                base = np.asarray([float(l) for l in leaves], np.float32)
            except (TypeError, ValueError):
                base = None        # non-scalar leaves: no per-slot stacking
            if base is not None:
                self._policy_treedef = treedef
                self._base_leaves = base
                self._slot_pol = np.tile(base[:, None], (1, n_slots))
        # trace counters: incremented only when jit actually (re)traces
        self.prefill_traces = 0
        self.decode_traces = 0
        ctx_len = self.context_len

        def prefill_insert(params, tokens, valid_len, slot, cache, policy):
            self.prefill_traces += 1
            d = dist if (dist is None or policy is None) else \
                dataclasses.replace(dist, policy=policy)
            batch = {"tokens": tokens}
            if cfg.frontend == "vision":
                batch["frontend"] = jnp.zeros(
                    (1, cfg.n_frontend_tokens, cfg.d_model))
            logits, small = transformer.prefill(
                params, batch, cfg, cache_len=ctx_len, dist=d,
                cache_dtype=cache_dtype, metrics=metrics)
            last = jax.lax.dynamic_index_in_dim(logits[0], valid_len - 1,
                                                axis=0, keepdims=False)
            first_tok = jnp.argmax(last).astype(jnp.int32)
            # per-slot KV layers are batch-inserted; the engine-wide obs
            # seam ("metrics" / legacy "moe_overflow") merges additively
            small.pop("pos")
            m_small = small.pop("metrics", None)
            of_small = small.pop("moe_overflow", None)
            skip = ("pos", "metrics", "moe_overflow")
            rest = {k: v for k, v in cache.items() if k not in skip}
            small = dict(small)      # match rest's plain-dict treedef

            def ins(big, sm):
                start = (0, slot) + (0,) * (big.ndim - 2)
                return jax.lax.dynamic_update_slice(
                    big, sm.astype(big.dtype), start)

            new = transformer.ObsCache(jax.tree.map(ins, rest, small))
            new["pos"] = cache["pos"].at[slot].set(
                self._prefix + valid_len)
            if "metrics" in cache:
                new["metrics"] = cache["metrics"] + m_small \
                    if m_small is not None else cache["metrics"]
            elif "moe_overflow" in cache:
                new["moe_overflow"] = cache["moe_overflow"] + (
                    of_small if of_small is not None else 0)
            return first_tok, new

        def decode(params, tokens, cache, active, policy):
            self.decode_traces += 1
            d = dist if (dist is None or policy is None) else \
                dataclasses.replace(dist, policy=policy)
            logits, new = transformer.decode_step(params, tokens, cache, cfg,
                                                  dist=d)
            # inactive slots hold their position (their writes land on a
            # fixed, fully-overwritten-on-admit slot — harmless by design)
            new["pos"] = jnp.where(active, new["pos"], cache["pos"])
            greedy = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
            return logits[:, -1], greedy, new

        # the engine discards the previous cache on every call, so both steps
        # donate it — decode updates one token row in place instead of
        # copying the whole (n_layers, n_slots, context_len, ...) cache
        self._prefill_insert = jax.jit(prefill_insert, donate_argnums=(4,))
        self._decode = jax.jit(decode, donate_argnums=(2,))
        spec = metrics_spec(cfg, params) if metrics else None
        self._cache = place_like_steps(
            M.init_cache(cfg, n_slots, self.context_len, per_slot_pos=True,
                         dtype=cache_dtype, metrics_spec=spec), dist)
        self._slots: List[Optional[_SlotState]] = [None] * n_slots
        self._last = np.full((n_slots, 1), pad_token, np.int32)
        self._active = np.zeros((n_slots,), bool)
        # scheduler stats
        self.n_admitted = 0
        self.n_retired = 0
        self.max_concurrency = 0
        self.decode_steps = 0

    # -- unified request API --------------------------------------------

    def _validate(self, req: Request) -> None:
        if len(np.asarray(req.prompt)) > self.max_prompt_len:
            raise ValueError(
                f"prompt length {len(np.asarray(req.prompt))} exceeds engine "
                f"max_prompt_len {self.max_prompt_len}")
        if req.gen.max_new_tokens > self.max_new_tokens:
            raise ValueError(
                f"request max_new_tokens {req.gen.max_new_tokens} "
                f"exceeds engine budget {self.max_new_tokens}")
        if req.gen.policy is not None:
            if self._policy_treedef is None:
                raise ValueError(
                    "per-request policy override requires an engine built "
                    "with a scalar-threshold base policy (DistContext.policy)")
            # same family required; static hints (exact capacity etc.) stay
            # the engine's — only the override's threshold leaves are used
            merge_policy_override(self._base_policy, req.gen.policy)

    def _has_work(self) -> bool:
        return bool(self._queue) or bool(self._active.any())

    # -- scheduling primitives ------------------------------------------

    def _request_leaves(self, gen: GenerationConfig):
        """Validated threshold leaves for a request (base values when the
        request carries no override)."""
        if gen.policy is None:
            return self._base_leaves
        leaves, treedef = jax.tree_util.tree_flatten(gen.policy)
        return np.asarray([float(l) for l in leaves], np.float32)

    def _stacked_policy(self):
        """The per-slot policy pytree for one decode step (threshold leaves
        shaped (n_slots,)), or None when the base DistContext's policy is
        used as a closure constant."""
        if self._policy_treedef is None:
            return None
        return jax.tree_util.tree_unflatten(
            self._policy_treedef,
            [jnp.asarray(row) for row in self._slot_pol])

    def _retire(self, slot: int):
        st = self._slots[slot]
        self._results[st.uid].finished_s = self._now()
        self.tracer.instant("retire", uid=st.uid, slot=slot,
                            n_tokens=st.n_emitted)
        self._slots[slot] = None
        self._active[slot] = False
        self._last[slot, 0] = self.pad_token
        if self._policy_treedef is not None:
            self._slot_pol[:, slot] = self._base_leaves
        self.n_retired += 1

    def _admit(self) -> int:
        """Move queued requests into free slots (jitted prefill-insert each).
        Returns the number admitted. A request whose first token already
        terminates it (eos / budget 1 reached) retires immediately."""
        admitted = 0
        for slot in range(self.n_slots):
            if not self._queue:
                break
            if self._slots[slot] is not None:
                continue
            uid, req = self._queue.popleft()
            toks = np.full((1, self.max_prompt_len), self.pad_token, np.int32)
            toks[0, :len(req.prompt)] = req.prompt
            req_policy = None
            if self._policy_treedef is not None:
                leaves = self._request_leaves(req.gen)
                self._slot_pol[:, slot] = leaves
                req_policy = jax.tree_util.tree_unflatten(
                    self._policy_treedef, [jnp.asarray(l) for l in leaves])
            res = self._results[uid]
            res.admitted_s = res.prefill_start_s = self._now()
            with self.tracer.span("prefill_insert", uid=uid, slot=slot,
                                  prompt_len=len(req.prompt)):
                first, self._cache = self._prefill_insert(
                    self.params, jnp.asarray(toks),
                    jnp.asarray(len(req.prompt), jnp.int32),
                    jnp.asarray(slot, jnp.int32), self._cache, req_policy)
                first = int(first)
            self._slots[slot] = _SlotState(uid=uid, gen=req.gen)
            self._active[slot] = True
            self._last[slot, 0] = first
            self._emit(slot, first)
            admitted += 1
            self.n_admitted += 1
        self.max_concurrency = max(self.max_concurrency,
                                   int(self._active.sum()))
        return admitted

    def _emit(self, slot: int, token: int):
        """Record one generated token for the slot's request; retire on EOS
        or budget exhaustion (mirrors the synchronized engine: the EOS token
        itself is emitted, then the request stops)."""
        st = self._slots[slot]
        self._record_token(st.uid, token)
        st.n_emitted += 1
        if token == st.gen.eos_token or st.n_emitted >= st.gen.max_new_tokens:
            self._retire(slot)

    def _step(self) -> bool:
        """One scheduler iteration: admit waiting requests into free slots,
        then run one batched decode step over all active slots. Returns True
        while there is (or may be) work left."""
        self._admit()
        if not self._active.any():
            return bool(self._queue)
        with self.tracer.span("decode", batch=int(self._active.sum())):
            logits, greedy, self._cache = self._decode(
                self.params, jnp.asarray(self._last), self._cache,
                jnp.asarray(self._active), self._stacked_policy())
        self.decode_steps += 1
        greedy_np = np.asarray(greedy)
        need_sampling = any(st is not None and st.gen.temperature > 0
                            for st in self._slots)
        logits_np = np.asarray(logits) if need_sampling else None
        for slot in range(self.n_slots):
            st = self._slots[slot]
            if st is None:
                continue
            if st.gen.temperature > 0:
                key = jax.random.fold_in(
                    jax.random.fold_in(jax.random.PRNGKey(st.gen.seed),
                                       st.uid), st.n_emitted)
                tok = int(jax.random.categorical(
                    key, jnp.asarray(logits_np[slot]) / st.gen.temperature))
            else:
                tok = int(greedy_np[slot])
            self._last[slot, 0] = tok
            self._emit(slot, tok)
        return True

    def reset_stats(self):
        """Zero the scheduler statistics (after a warmup run, say). Trace
        counters are deliberately kept: warmup compiles are still traces."""
        self.n_admitted = self.n_retired = 0
        self.max_concurrency = 0
        self.decode_steps = 0

    # -- observability hooks (EngineBase) -------------------------------

    def _trace_count(self) -> int:
        return self.prefill_traces + self.decode_traces

    def _device_metrics(self):
        if isinstance(self._cache, dict):
            return self._cache.get("metrics")
        return None

    def _metrics_hook(self, snap) -> None:
        snap.gauge("repro_engine_slots", float(self.n_slots))
        snap.gauge("repro_engine_free_slots", float(self.free_slots))
        snap.counter("repro_engine_decode_steps_total",
                     float(self.decode_steps))
        snap.counter("repro_requests_admitted_total", float(self.n_admitted))
        snap.counter("repro_requests_retired_total", float(self.n_retired))

    @property
    def overflow_pairs(self) -> int:
        """Total token-expert pairs silently dropped by capacity overflow
        since engine construction (0 under ``exact_moe`` on either MoE
        path; a setp-backed engine counts its psum'd device-level and
        local-expert overflow). The
        counter rides in the decode cache, so reading it costs one scalar
        transfer — no per-step sync."""
        m = self._device_metrics()
        if m is not None:
            return int(m.overflow_pairs)
        if isinstance(self._cache, dict) and "moe_overflow" in self._cache:
            return int(dict.__getitem__(self._cache, "moe_overflow"))
        return 0

    @property
    def free_slots(self) -> int:
        return int(self.n_slots - self._active.sum())

    @property
    def queued(self) -> int:
        return len(self._queue)
