"""Paged-KV serving engine: block-granular KV cache + chunked prefill +
prefix caching (paper §4, ROADMAP serving item).

The KV cache is ONE physical page pool per layer (``PagedLayout``); each
decode slot owns a row of a host-side *page table* mapping logical page
index -> physical page. The table is passed to the jitted steps as a traced
int32 array, so page churn (allocation, reuse, eviction) changes VALUES,
never shapes — nothing retraces.

Three mechanisms ride on the indirection:

* **Chunked prefill** — a prompt advances ``chunk_size`` tokens per engine
  ``step()`` through a jitted fixed-shape ``chunk_insert``, interleaved with
  decode for already-active slots: long prompts no longer stall token
  generation for everyone else. Attention reads are trimmed to the same
  static width the monolithic prefill uses (``read_len=max_prompt_len``), so
  chunked logits are bit-identical to one-shot prefill.
* **Prefix caching** — filled prompt pages are registered under a hash of
  (prompt prefix tokens, policy thresholds); a later request with the same
  prefix maps the cached physical pages into its page table (refcounted,
  zero-copy) and starts prefill after them. The last prompt token is always
  recomputed (hits are capped at ``h*ps <= plen-1``) so first-token logits
  exist. Unreferenced cached pages park in an LRU and are evicted only when
  the free list runs dry.
* **Page-0 write sink** — page 0 is never allocated; masked/inactive writes
  are redirected past the pool (``mode="drop"``) or land on page 0, and
  reads beyond a slot's position are validity-masked, so stale data is
  never observed.

Bit-exactness contract (tested): with ``exact_moe`` and a float32 cache,
greedy tokens match ``ContinuousBatchingEngine`` bit-for-bit — decode reads
trim to the contiguous engine's ``context_len`` and chunk reads to its
padded prompt width, keeping every softmax reduction the same static width.
"""
from __future__ import annotations

import dataclasses
import hashlib
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ModelConfig
from ..models import attention as attn
from ..models import transformer
from ..models.transformer import DistContext
from ..obs import MetricsSnapshot, metrics_spec
from .api import EngineBase, GenerationConfig, Request
from .engine import exact_moe_dist, merge_policy_override, place_like_steps


class PageAllocator:
    """Refcounted physical-page allocator with a prefix-cache directory.

    Page 0 is reserved as the write sink for inactive slots and is never
    handed out. A page is in exactly one of three states: *free* (on the
    free stack), *held* (refcount > 0), or *parked* (refcount 0 but still
    registered in the prefix cache — reusable via ``acquire_cached`` and
    evictable in LRU order when the free stack empties)."""

    def __init__(self, n_pages: int):
        self.n_pages = n_pages
        self._free: List[int] = list(range(n_pages - 1, 0, -1))
        self._ref = np.zeros(n_pages, np.int32)
        self._cached: Dict[bytes, int] = {}    # prefix key -> physical page
        self._page_key: Dict[int, bytes] = {}  # reverse map
        self._lru: Dict[int, int] = {}         # parked page -> last-use tick
        self._tick = 0
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def available(self) -> int:
        return len(self._free) + len(self._lru)

    # page-state census (page 0, the write sink, is never handed out and
    # is excluded from all three states)
    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def n_parked(self) -> int:
        return len(self._lru)

    @property
    def n_held(self) -> int:
        return self.n_pages - 1 - self.n_free - self.n_parked

    def alloc(self) -> int:
        """Take a fresh page (refcount 1), evicting the LRU-oldest parked
        cached page if the free stack is empty."""
        if self._free:
            page = self._free.pop()
        else:
            page = min(self._lru, key=self._lru.get)
            del self._lru[page]
            del self._cached[self._page_key.pop(page)]
            self.evictions += 1
        self._ref[page] = 1
        return page

    def lookup(self, key: bytes) -> Optional[int]:
        return self._cached.get(key)

    def acquire_cached(self, key: bytes) -> int:
        """Take a reference on the cached page for ``key`` (prefix hit)."""
        page = self._cached[key]
        self._ref[page] += 1
        self._lru.pop(page, None)
        self.hits += 1
        return page

    def register(self, key: bytes, page: int) -> None:
        """Publish a filled, held page under a prefix key. First writer
        wins: an existing registration (same content by construction) is
        kept; a page can carry at most one key."""
        if key in self._cached or page in self._page_key:
            return
        self._cached[key] = page
        self._page_key[page] = key

    def release(self, page: int) -> None:
        """Drop one reference; at zero the page parks (if registered) or
        returns to the free stack."""
        self._ref[page] -= 1
        assert self._ref[page] >= 0
        if self._ref[page] == 0:
            if page in self._page_key:
                self._tick += 1
                self._lru[page] = self._tick
            else:
                self._free.append(page)


@dataclasses.dataclass
class _SlotState:
    uid: int
    gen: GenerationConfig
    prompt: np.ndarray
    n_pages: int                      # page-table entries this slot holds
    next_start: int = 0               # next prompt token to prefill
    prefilling: bool = True
    n_emitted: int = 0


class PagedEngine(EngineBase):
    """Paged-KV continuous-batching engine with chunked prefill and prefix
    caching. Speaks the unified ``submit()``/``step()``/``drain()`` API;
    with ``exact_moe`` + float32 cache its greedy tokens are bit-identical
    to ``ContinuousBatchingEngine`` for the same requests."""

    def __init__(self, cfg: ModelConfig, params, *, n_slots: int = 8,
                 page_size: int = 16, chunk_size: int = 64,
                 max_prompt_len: int = 512, max_new_tokens: int = 128,
                 n_pages: Optional[int] = None, pad_token: int = 0,
                 dist: Optional[DistContext] = None, exact_moe: bool = True,
                 cache_dtype=jnp.bfloat16, prefix_cache: bool = True,
                 metrics: bool = True, trace: bool = False):
        if (cfg.family in ("audio", "ssm", "hybrid")
                or cfg.attn_kind == "mla" or cfg.frontend):
            raise NotImplementedError(
                "paged serving supports GQA attention decoder-only text "
                "models (chunked prefill has no recurrent-state or "
                "frontend-token analog yet)")
        super().__init__(metrics=metrics, trace=trace)
        self.cfg = cfg
        self.params = params
        self.n_slots = n_slots
        self.page_size = page_size
        self.chunk_size = chunk_size
        self.pad_token = pad_token
        self.max_prompt_len = max_prompt_len
        self.max_new_tokens = max_new_tokens
        self.prefix_cache = prefix_cache
        if exact_moe and cfg.is_moe:
            dist = exact_moe_dist(dist)
        self.dist = dist
        # one slot's worth of logical pages covers prompt + decode budget;
        # the decode read is trimmed to exactly the contiguous engine's
        # context_len so both engines reduce over the same static width
        self.context_len = max_prompt_len + max_new_tokens
        self.pages_per_slot = -(-self.context_len // page_size)
        if n_pages is None:
            n_pages = 1 + n_slots * self.pages_per_slot
        self.n_pages = n_pages
        self._alloc = PageAllocator(n_pages)
        self._layout = attn.PagedLayout(page_size)
        self._page_table = np.zeros((n_slots, self.pages_per_slot), np.int32)
        self._cache = place_like_steps(transformer.init_paged_cache(
            cfg, n_pages, page_size, n_slots, dtype=cache_dtype,
            metrics_spec=metrics_spec(cfg, params) if metrics else None),
            dist)
        self._slots: List[Optional[_SlotState]] = [None] * n_slots
        self._last = np.full((n_slots, 1), pad_token, np.int32)
        self._active = np.zeros((n_slots,), bool)

        # per-slot policy stacking (same scheme as the continuous engine)
        self._base_policy = dist.policy if dist is not None else None
        self._policy_treedef = None
        if self._base_policy is not None:
            leaves, treedef = jax.tree_util.tree_flatten(self._base_policy)
            try:
                base = np.asarray([float(l) for l in leaves], np.float32)
            except (TypeError, ValueError):
                base = None
            if base is not None:
                self._policy_treedef = treedef
                self._base_leaves = base
                self._slot_pol = np.tile(base[:, None], (1, n_slots))

        # trace counters: incremented only when jit actually (re)traces
        self.chunk_traces = 0
        self.decode_traces = 0
        layout = self._layout
        mpl = max_prompt_len
        ctx = self.context_len

        def chunk_insert(params, tokens, slot, start, valid_len, cache,
                         page_table, policy):
            self.chunk_traces += 1
            d = dist if (dist is None or policy is None) else \
                dataclasses.replace(dist, policy=policy)
            logits, new = transformer.chunk_step(
                params, tokens, slot, start, valid_len, cache, cfg,
                layout=layout, page_table=page_table, read_len=mpl, dist=d)
            with jax.named_scope("lm_head"):
                last = jax.lax.dynamic_index_in_dim(
                    logits[0], valid_len - 1, axis=0, keepdims=False)
                first = jnp.argmax(last).astype(jnp.int32)
            return first, new

        def decode(params, tokens, cache, active, page_table, policy):
            self.decode_traces += 1
            d = dist if (dist is None or policy is None) else \
                dataclasses.replace(dist, policy=policy)
            logits, new = transformer.decode_step(
                params, tokens, cache, cfg, dist=d, layout=layout,
                page_table=page_table, write_mask=active, read_len=ctx)
            new["pos"] = jnp.where(active, new["pos"], cache["pos"])
            with jax.named_scope("lm_head"):
                greedy = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
            return logits[:, -1], greedy, new

        self._chunk_insert = jax.jit(chunk_insert, donate_argnums=(5,))
        self._decode = jax.jit(decode, donate_argnums=(2,))

        # scheduler stats
        self.n_admitted = 0
        self.n_retired = 0
        self.max_concurrency = 0
        self.decode_steps = 0
        self.chunk_steps = 0              # jitted chunk_insert invocations
        self.prefill_tokens = 0           # prompt tokens actually prefilled

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
            merge_policy_override(self._base_policy, req.gen.policy)

    def _has_work(self) -> bool:
        return bool(self._queue) or any(s is not None for s in self._slots)

    # -- prefix-cache keys ----------------------------------------------

    def _policy_bytes(self, gen: GenerationConfig) -> bytes:
        """KV content depends on MoE routing thresholds (earlier layers'
        MoE feeds later layers' K/V), so the policy is part of the key."""
        if self._policy_treedef is None:
            return b""
        return self._request_leaves(gen).tobytes()

    def _prefix_key(self, prompt: np.ndarray, n_tokens: int,
                    gen: GenerationConfig) -> bytes:
        h = hashlib.sha256()
        h.update(np.ascontiguousarray(prompt[:n_tokens]).tobytes())
        h.update(self._policy_bytes(gen))
        return h.digest()

    # -- policy stacking (same scheme as the continuous engine) ----------

    def _request_leaves(self, gen: GenerationConfig):
        if gen.policy is None:
            return self._base_leaves
        leaves, _ = jax.tree_util.tree_flatten(gen.policy)
        return np.asarray([float(l) for l in leaves], np.float32)

    def _stacked_policy(self):
        if self._policy_treedef is None:
            return None
        return jax.tree_util.tree_unflatten(
            self._policy_treedef,
            [jnp.asarray(row) for row in self._slot_pol])

    def _slot_policy(self, gen: GenerationConfig):
        if self._policy_treedef is None:
            return None
        return jax.tree_util.tree_unflatten(
            self._policy_treedef,
            [jnp.asarray(l) for l in self._request_leaves(gen)])

    # -- admission / retirement ------------------------------------------

    def _admit(self) -> int:
        """FIFO admission with head-of-line blocking: a request enters a
        free slot only if the allocator can cover its FULL page demand
        (prompt + decode budget), after prefix-cache reuse. Hit pages map
        straight into the slot's page table; prefill starts after them."""
        admitted = 0
        for slot in range(self.n_slots):
            if not self._queue:
                break
            if self._slots[slot] is not None:
                continue
            uid, req = self._queue[0]
            plen = len(req.prompt)
            ps = self.page_size
            need_total = -(-(plen + req.gen.max_new_tokens) // ps)
            # longest run of cached full prompt pages, capped so the last
            # prompt token is recomputed (its logits emit the first token)
            hit_keys: List[bytes] = []
            if self.prefix_cache:
                h = 1
                while h * ps <= plen - 1:
                    key = self._prefix_key(req.prompt, h * ps, req.gen)
                    if self._alloc.lookup(key) is None:
                        break
                    hit_keys.append(key)
                    h += 1
            if self._alloc.available() < need_total - len(hit_keys):
                break                      # head-of-line: keep FIFO order
            self._queue.popleft()
            pages = [self._alloc.acquire_cached(k) for k in hit_keys]
            # hit rate is over lookup-eligible prompt pages (h*ps <= plen-1)
            self._alloc.misses += max(0, (plen - 1) // ps - len(hit_keys))
            pages += [self._alloc.alloc()
                      for _ in range(need_total - len(hit_keys))]
            row = np.zeros(self.pages_per_slot, np.int32)
            row[:len(pages)] = pages
            self._page_table[slot] = row
            if self._policy_treedef is not None:
                self._slot_pol[:, slot] = self._request_leaves(req.gen)
            start = len(hit_keys) * ps
            self._slots[slot] = _SlotState(
                uid=uid, gen=req.gen, prompt=req.prompt, n_pages=len(pages),
                next_start=start)
            self._cache["pos"] = self._cache["pos"].at[slot].set(start)
            self._results[uid].admitted_s = self._now()
            admitted += 1
            self.n_admitted += 1
        return admitted

    def _retire(self, slot: int):
        st = self._slots[slot]
        self._results[st.uid].finished_s = self._now()
        self.tracer.instant("retire", uid=st.uid, slot=slot,
                            n_tokens=st.n_emitted)
        for page in self._page_table[slot]:
            if page:
                self._alloc.release(int(page))
        self._page_table[slot] = 0
        self._slots[slot] = None
        self._active[slot] = False
        self._last[slot, 0] = self.pad_token
        if self._policy_treedef is not None:
            self._slot_pol[:, slot] = self._base_leaves
        self.n_retired += 1

    def _emit(self, slot: int, token: int):
        st = self._slots[slot]
        self._record_token(st.uid, token)
        st.n_emitted += 1
        if token == st.gen.eos_token or st.n_emitted >= st.gen.max_new_tokens:
            self._retire(slot)

    # -- prefill / decode ------------------------------------------------

    def _advance_prefill(self) -> bool:
        """Advance exactly ONE prefilling slot by ONE chunk (fixed-shape
        jitted step — the per-step prompt work is bounded by chunk_size).
        On the final chunk the slot activates for decode, its first greedy
        token is emitted, and its filled prompt pages are registered in the
        prefix cache."""
        slot = next((i for i, s in enumerate(self._slots)
                     if s is not None and s.prefilling), None)
        if slot is None:
            return False
        st = self._slots[slot]
        plen = len(st.prompt)
        start = st.next_start
        valid = min(self.chunk_size, plen - start)
        toks = np.full((1, self.chunk_size), self.pad_token, np.int32)
        toks[0, :valid] = st.prompt[start:start + valid]
        res = self._results[st.uid]
        if res.prefill_start_s is None:
            res.prefill_start_s = self._now()
        with self.tracer.span("prefill_chunk", uid=st.uid, slot=slot,
                              start=start, n_tokens=valid):
            first, self._cache = self._chunk_insert(
                self.params, jnp.asarray(toks), jnp.asarray(slot, jnp.int32),
                jnp.asarray(start, jnp.int32), jnp.asarray(valid, jnp.int32),
                self._cache, jnp.asarray(self._page_table),
                self._slot_policy(st.gen))
        self.chunk_steps += 1
        self.prefill_tokens += valid
        st.next_start = start + valid
        if st.next_start < plen:
            return True
        with self.tracer.span("readback", uid=st.uid):
            first = int(first)
        # prefill complete: publish full prompt pages, activate for decode
        with self.tracer.span("emit", uid=st.uid):
            if self.prefix_cache:
                ps = self.page_size
                for h in range(1, plen // ps + 1):
                    self._alloc.register(
                        self._prefix_key(st.prompt, h * ps, st.gen),
                        int(self._page_table[slot, h - 1]))
            st.prefilling = False
            self._active[slot] = True
            self._last[slot, 0] = first
            self._emit(slot, first)
            self.max_concurrency = max(self.max_concurrency,
                                       int(self._active.sum()))
        return True

    def _step(self) -> bool:
        """One scheduler iteration: admit queued requests into free slots,
        advance one prefilling slot by one chunk, then one batched decode
        step over all active slots. Returns True while work may remain.
        Each phase is a span: ``admit``, ``prefill_chunk`` (input upload
        and dispatch), ``decode`` (the same), ``readback`` (waiting for the
        step's tokens, and logits when sampling), ``emit`` (tokens,
        sampling, retirement)."""
        with self.tracer.span("admit"):
            self._admit()
        self._advance_prefill()
        if not self._active.any():
            return self._has_work()
        with self.tracer.span("decode", batch=int(self._active.sum())):
            logits, greedy, self._cache = self._decode(
                self.params, jnp.asarray(self._last), self._cache,
                jnp.asarray(self._active), jnp.asarray(self._page_table),
                self._stacked_policy())
        self.decode_steps += 1
        with self.tracer.span("readback"):
            greedy_np = np.asarray(greedy)
            need_sampling = any(st is not None and not st.prefilling
                                and st.gen.temperature > 0
                                for st in self._slots)
            logits_np = np.asarray(logits) if need_sampling else None
        with self.tracer.span("emit"):
            for slot in range(self.n_slots):
                st = self._slots[slot]
                if st is None or st.prefilling:
                    continue
                if st.gen.temperature > 0:
                    key = jax.random.fold_in(
                        jax.random.fold_in(jax.random.PRNGKey(st.gen.seed),
                                           st.uid), st.n_emitted)
                    tok = int(jax.random.categorical(
                        key,
                        jnp.asarray(logits_np[slot]) / st.gen.temperature))
                else:
                    tok = int(greedy_np[slot])
                self._last[slot, 0] = tok
                self._emit(slot, tok)
        return True

    def decode_hlo(self) -> str:
        """Optimized HLO text of the jitted decode step at the engine's
        current shapes — for auditing what the step runs on the device
        (e.g. that the MoE kernel lowered natively). Each instruction's
        ``metadata={op_name=...}`` carries the model's named scopes
        (``embed``, ``attention``, ``moe``, ``lm_head``)."""
        return self._decode.lower(
            self.params, jnp.asarray(self._last), self._cache,
            jnp.asarray(self._active), jnp.asarray(self._page_table),
            self._stacked_policy()).compile().as_text()

    def chunk_hlo(self) -> str:
        """Optimized HLO text of the jitted prefill-chunk step, as
        ``decode_hlo``."""
        i32 = jnp.asarray(0, jnp.int32)
        return self._chunk_insert.lower(
            self.params, jnp.zeros((1, self.chunk_size), jnp.int32), i32,
            i32, i32, self._cache, jnp.asarray(self._page_table),
            self._slot_policy(GenerationConfig())).compile().as_text()

    # -- stats -----------------------------------------------------------

    @property
    def prefix_hits(self) -> int:
        return self._alloc.hits

    @property
    def prefix_misses(self) -> int:
        return self._alloc.misses

    @property
    def prefix_hit_rate(self) -> float:
        tot = self._alloc.hits + self._alloc.misses
        return self._alloc.hits / tot if tot else 0.0

    @property
    def overflow_pairs(self) -> int:
        m = self._device_metrics()
        if m is not None:
            return int(m.overflow_pairs)
        if isinstance(self._cache, dict) and "moe_overflow" in self._cache:
            return int(dict.__getitem__(self._cache, "moe_overflow"))
        return 0

    @property
    def free_slots(self) -> int:
        return sum(s is None for s in self._slots)

    @property
    def queued(self) -> int:
        return len(self._queue)

    # -- observability hooks (EngineBase) --------------------------------

    def _trace_count(self) -> int:
        return self.chunk_traces + self.decode_traces

    def _device_metrics(self):
        if isinstance(self._cache, dict):
            return self._cache.get("metrics")
        return None

    def _metrics_hook(self, snap: MetricsSnapshot) -> None:
        snap.counter("repro_prefix_cache_total", float(self._alloc.hits),
                     event="hit")
        snap.counter("repro_prefix_cache_total", float(self._alloc.misses),
                     event="miss")
        snap.counter("repro_prefix_cache_total", float(self._alloc.evictions),
                     event="eviction")
        snap.gauge("repro_page_pool_pages", float(self._alloc.n_free),
                   state="free")
        snap.gauge("repro_page_pool_pages", float(self._alloc.n_held),
                   state="held")
        snap.gauge("repro_page_pool_pages", float(self._alloc.n_parked),
                   state="parked")
        snap.gauge("repro_engine_slots", float(self.n_slots))
        snap.gauge("repro_engine_free_slots", float(self.free_slots))
        snap.counter("repro_engine_decode_steps_total",
                     float(self.decode_steps))
        snap.counter("repro_engine_chunk_steps_total",
                     float(self.chunk_steps))
        snap.counter("repro_requests_admitted_total", float(self.n_admitted))
        snap.counter("repro_requests_retired_total", float(self.n_retired))

    def reset_stats(self):
        """Zero scheduler statistics (trace counters are kept: warmup
        compiles are still traces; allocator hit/miss counters are kept:
        the prefix cache's state survives across runs)."""
        self.n_admitted = self.n_retired = 0
        self.max_concurrency = 0
        self.decode_steps = 0
        self.chunk_steps = 0
        self.prefill_tokens = 0
