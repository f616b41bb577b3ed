"""Paged-KV serving: chunked prefill bit-exactness vs monolithic prefill,
paged-vs-contiguous engine bit-identity under mixed-length traffic, prefix
caching (bit-exact hits that skip prefill work), page churn without
retracing, the unified request API, and the deprecated KV-cache shims.

Bit-exactness here means EQUAL ARRAYS, not tolerances: the paged engine's
attention reads are trimmed to the same static reduction widths the
contiguous engines use, and exact-capacity MoE makes tokens independent of
co-batched traffic — so a float32 cache reproduces greedy tokens exactly.
"""
import dataclasses
import re
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import moe as moe_mod
from repro.models import attention as A
from repro.models import model as M
from repro.models import transformer as T
from repro.serving import (ContinuousBatchingEngine, Engine, GenerationConfig,
                           PagedEngine, Request, ServingEngine,
                           exact_moe_dist)


@pytest.fixture(scope="module")
def served():
    cfg = get_config("mixtral-8x7b-lite")
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _prompts(cfg, lens, mults=(7, 11, 13, 17, 5, 3)):
    return [np.asarray((np.arange(L) * m) % cfg.vocab_size)
            for L, m in zip(lens, mults)]


# ---------------------------------------------------------------------------
# Chunked prefill == monolithic prefill, bitwise
# ---------------------------------------------------------------------------

def test_chunked_prefill_bitwise_equals_monolithic(served):
    """chunk_step over 5-token chunks reproduces the monolithic prefill's
    logits EXACTLY (==, not allclose) on both layouts, provided the chunk
    attention reads are trimmed (read_len) to the monolithic width — the
    softmax reduction width is part of XLA's numerics."""
    cfg, params = served
    dist = exact_moe_dist(None)
    plen, cap, chunk = 12, 20, 5
    prompt = np.asarray((np.arange(plen) * 7) % cfg.vocab_size, np.int32)
    logits_m, _ = T.prefill(params, {"tokens": jnp.asarray(prompt[None])},
                            cfg, cache_len=cap, dist=dist,
                            cache_dtype=jnp.float32)
    logits_m = np.asarray(logits_m[0])

    def run_chunks(layout, cache, page_table=None):
        rows = []
        for start in range(0, plen, chunk):
            valid = min(chunk, plen - start)
            toks = np.zeros((1, chunk), np.int32)
            toks[0, :valid] = prompt[start:start + valid]
            lg, cache = T.chunk_step(params, jnp.asarray(toks), 1, start,
                                     valid, cache, cfg, layout=layout,
                                     page_table=page_table, read_len=plen,
                                     dist=dist)
            rows.append(np.asarray(lg[0, :valid]))
        return np.concatenate(rows, 0)

    cont = run_chunks(A.ContiguousLayout(),
                      T.init_cache(cfg, 2, cap, dtype=jnp.float32,
                                   per_slot_pos=True))
    assert (cont == logits_m).all()

    ps = 4
    ppslot = -(-cap // ps)
    pt = np.zeros((2, ppslot), np.int32)
    pt[1] = np.arange(1, 1 + ppslot)
    paged = run_chunks(A.PagedLayout(ps),
                       T.init_paged_cache(cfg, 1 + 2 * ppslot, ps, 2,
                                          dtype=jnp.float32),
                       page_table=jnp.asarray(pt))
    assert (paged == logits_m).all()


# ---------------------------------------------------------------------------
# Paged engine == contiguous engines, bitwise
# ---------------------------------------------------------------------------

def test_paged_engine_matches_continuous_mixed_traffic(served):
    """Mixed-length prompts through the paged engine (chunked prefill, page
    indirection, slot churn) produce greedy tokens bit-identical to the
    contiguous continuous-batching engine."""
    cfg, params = served
    lens = [12, 5, 9, 3, 7]
    prompts = _prompts(cfg, lens)
    gen = GenerationConfig(max_new_tokens=6)
    cont = ContinuousBatchingEngine(cfg, params, n_slots=3, max_prompt_len=16,
                                    max_new_tokens=8,
                                    cache_dtype=jnp.float32)
    ref = cont.generate(prompts, gen)
    paged = PagedEngine(cfg, params, n_slots=3, page_size=4, chunk_size=5,
                        max_prompt_len=16, max_new_tokens=8,
                        cache_dtype=jnp.float32)
    got = paged.generate(prompts, gen)
    assert [r.tokens for r in got] == [r.tokens for r in ref]
    assert paged.n_admitted == paged.n_retired == len(prompts)


def test_paged_engine_matches_synchronized_equal_lengths(served):
    """Acceptance check against the paper-baseline synchronized engine:
    equal-length prompts (its exact regime) decode to the same greedy
    tokens, while no engine step advances a prompt by more than one chunk."""
    cfg, params = served
    L, new, chunk = 12, 5, 5
    prompts = _prompts(cfg, [L] * 4)
    gen = GenerationConfig(max_new_tokens=new)
    sync = ServingEngine(cfg, params, batch_size=4, max_prompt_len=L,
                         max_new_tokens=new, exact_moe=True,
                         cache_dtype=jnp.float32)
    ref = sync.generate(prompts, gen)
    paged = PagedEngine(cfg, params, n_slots=2, page_size=4, chunk_size=chunk,
                        max_prompt_len=L, max_new_tokens=new,
                        cache_dtype=jnp.float32)
    uids = [paged.submit(p, gen) for p in prompts]
    before = 0
    while paged.step():
        # chunked-prefill bound: one step never advances prompts by more
        # than one chunk of prefill work
        assert paged.prefill_tokens - before <= chunk
        before = paged.prefill_tokens
    assert [paged.result(u).tokens for u in uids] == [r.tokens for r in ref]


# ---------------------------------------------------------------------------
# Prefix cache
# ---------------------------------------------------------------------------

def test_prefix_cache_hit_bitwise_and_skips_prefill_work(served):
    """A warm request sharing a cached prefix reuses filled pages: tokens
    stay bit-identical to the cold run while chunk invocations and prefilled
    token counts drop (the shared prefix is never recomputed)."""
    cfg, params = served
    prompts = _prompts(cfg, [12, 5, 9])
    gen = GenerationConfig(max_new_tokens=5)
    paged = PagedEngine(cfg, params, n_slots=2, page_size=4, chunk_size=5,
                        max_prompt_len=16, max_new_tokens=8,
                        cache_dtype=jnp.float32)
    cold = paged.generate(prompts, gen)
    cold_chunks, cold_tokens = paged.chunk_steps, paged.prefill_tokens
    assert paged.prefix_hits == 0
    paged.reset_stats()
    warm = paged.generate(prompts, gen)
    assert [r.tokens for r in warm] == [r.tokens for r in cold]
    assert paged.prefix_hits > 0
    assert paged.chunk_steps < cold_chunks
    assert paged.prefill_tokens < cold_tokens


def test_prefix_cache_recomputes_last_prompt_token(served):
    """A prompt whose length is an exact page multiple caps its prefix hit
    at plen-1 tokens: the final page is recomputed so the first-token logits
    exist, and outputs still match the cold run bitwise."""
    cfg, params = served
    prompt = _prompts(cfg, [8])[0]          # exactly 2 pages of 4
    gen = GenerationConfig(max_new_tokens=4)
    paged = PagedEngine(cfg, params, n_slots=1, page_size=4, chunk_size=4,
                        max_prompt_len=8, max_new_tokens=4,
                        cache_dtype=jnp.float32)
    cold = paged.generate([prompt], gen)[0].tokens
    warm_start = paged.prefill_tokens
    warm = paged.generate([prompt], gen)[0].tokens
    assert warm == cold
    # only the first page (4 tokens) may be reused; the last page holding
    # the final prompt token is prefilled again
    assert paged.prefill_tokens - warm_start == 4
    assert paged.prefix_hits == 1


def test_prefix_cache_off_never_hits(served):
    cfg, params = served
    prompt = _prompts(cfg, [8])[0]
    gen = GenerationConfig(max_new_tokens=3)
    paged = PagedEngine(cfg, params, n_slots=1, page_size=4, chunk_size=4,
                        max_prompt_len=8, max_new_tokens=4,
                        prefix_cache=False, cache_dtype=jnp.float32)
    a = paged.generate([prompt], gen)[0].tokens
    b = paged.generate([prompt], gen)[0].tokens
    assert a == b
    assert paged.prefix_hits == 0 and paged.prefix_hit_rate == 0.0


# ---------------------------------------------------------------------------
# Fixed shapes: page churn never retraces
# ---------------------------------------------------------------------------

def test_page_churn_and_prefix_reuse_never_retrace(served):
    """Slot churn, page reallocation, prefix hits, and LRU eviction all only
    change page-table VALUES — the jitted chunk-insert and decode steps
    trace exactly once."""
    cfg, params = served
    paged = PagedEngine(cfg, params, n_slots=2, page_size=4, chunk_size=5,
                        max_prompt_len=12, max_new_tokens=6,
                        n_pages=1 + 2 * 5,   # tight pool: forces eviction
                        cache_dtype=jnp.float32)
    gen = GenerationConfig(max_new_tokens=4)
    paged.generate(_prompts(cfg, [12, 7, 9, 12]), gen)
    assert (paged.chunk_traces, paged.decode_traces) == (1, 1)
    paged.generate(_prompts(cfg, [12, 9, 5]), gen)   # warm + evictions
    assert (paged.chunk_traces, paged.decode_traces) == (1, 1)


# ---------------------------------------------------------------------------
# Unified request API
# ---------------------------------------------------------------------------

def test_unified_api_across_engines(served):
    """All three engines satisfy the Engine protocol and serve the same
    submit()/step()/drain() lifecycle; drain returns submission order."""
    cfg, params = served
    prompts = _prompts(cfg, [8, 6])
    gen = GenerationConfig(max_new_tokens=3)
    kw = dict(max_prompt_len=8, max_new_tokens=4)
    engines = [ServingEngine(cfg, params, batch_size=2, **kw),
               ContinuousBatchingEngine(cfg, params, n_slots=2, **kw),
               PagedEngine(cfg, params, n_slots=2, page_size=4,
                           chunk_size=4, **kw)]
    for eng in engines:
        assert isinstance(eng, Engine)
        u0 = eng.submit(prompts[0], gen)
        u1 = eng.submit(Request(prompt=prompts[1], gen=gen))
        res = eng.drain()
        assert [r.uid for r in res] == [u0, u1]
        assert all(len(r.tokens) == 3 for r in res)
        assert eng.drain() == []            # nothing new since last drain
        assert eng.result(u0).tokens == res[0].tokens


def test_paged_timed_admission(served):
    cfg, params = served
    prompts = _prompts(cfg, [8, 8])
    arrivals = [(0.0, prompts[0], GenerationConfig(max_new_tokens=3)),
                (0.05, prompts[1], GenerationConfig(max_new_tokens=3))]
    eng = PagedEngine(cfg, params, n_slots=2, page_size=4, chunk_size=4,
                      max_prompt_len=8, max_new_tokens=4)
    res = eng.generate_timed(arrivals)
    assert [r.submitted_s for r in res] == [0.0, 0.05]
    assert all(len(r.tokens) == 3 for r in res)
    assert all(r.finished_s >= r.submitted_s for r in res)


@pytest.mark.parametrize("engine", ["paged", "continuous", "synchronized"])
def test_lifecycle_stamps_are_ordered(served, engine):
    """submitted <= admitted <= prefill_start <= first_token for every
    request; with one slot, the second request waits behind the first."""
    cfg, params = served
    if engine == "paged":
        eng = PagedEngine(cfg, params, n_slots=1, page_size=4, chunk_size=4,
                          max_prompt_len=16, max_new_tokens=4)
    elif engine == "continuous":
        eng = ContinuousBatchingEngine(cfg, params, n_slots=1,
                                       max_prompt_len=16, max_new_tokens=4)
    else:
        eng = ServingEngine(cfg, params, batch_size=1, max_prompt_len=16,
                            max_new_tokens=4)
    res = eng.generate(_prompts(cfg, [14, 5, 9]),
                       GenerationConfig(max_new_tokens=3))
    for r in res:
        assert (r.submitted_s <= r.admitted_s <= r.prefill_start_s
                <= r.first_token_s <= r.finished_s)
    first, second = res[0], res[1]
    assert second.prefill_start_s - second.submitted_s > 0
    assert second.admitted_s >= first.finished_s


def test_paged_prefill_starts_at_first_chunk(served):
    """A paged request is admitted when it gets a slot and pages, and its
    prefill starts when its first chunk is dispatched: a short prompt
    admitted beside a long one waits for the lane."""
    cfg, params = served
    eng = PagedEngine(cfg, params, n_slots=2, page_size=4, chunk_size=4,
                      max_prompt_len=16, max_new_tokens=4)
    res = eng.generate(_prompts(cfg, [14, 5]),
                       GenerationConfig(max_new_tokens=2))
    long_, short = res
    assert short.admitted_s < long_.first_token_s <= short.prefill_start_s


@pytest.mark.parametrize("program", ["decode", "chunk"])
def test_compiled_steps_carry_model_scopes(served, program):
    """Each compiled step names its parts in the op_name metadata, so a
    profiler trace can be split by model layer."""
    cfg, params = served
    eng = PagedEngine(cfg, params, n_slots=2, page_size=4, chunk_size=4,
                      max_prompt_len=16, max_new_tokens=4)
    text = eng.decode_hlo() if program == "decode" else eng.chunk_hlo()
    parts = {part for name in re.findall(r'op_name="([^"]*)"', text)
             for part in name.split("/")}
    assert {"embed", "attention", "moe", "route", "lm_head"} <= parts


# ---------------------------------------------------------------------------
# The fused kernel reads its layer straight from the layer-stacked experts
# ---------------------------------------------------------------------------

def _fused_dist(cfg, params, p):
    """Prepared params and a dist whose MoE runs the streamed fused kernel
    at partition p: NoDrop at p=1, calibrated mode-grouped 2T-Drop at p=2
    (so MAJOR-only rows exist and skip minor tiles)."""
    from repro.core.policy import NoDrop, make_policy
    from repro.launch.mesh import make_host_mesh
    hints = dict(use_kernel=True, fused_pipeline=True)
    pol = NoDrop(**hints) if p == 1 else \
        make_policy("2t", cfg.dualsparse, drop_target=0.25, **hints)
    calib = jax.random.normal(jax.random.PRNGKey(1), (64, cfg.d_model))
    prepared, pol = pol.prepare(params, cfg, calib)
    assert prepared["blocks"]["moe"]["w1"].shape[1] == cfg.n_experts * p
    return prepared, T.DistContext(mesh=make_host_mesh(1),
                                   moe_impl="dispatch", policy=pol)


def _serve_recorded(cfg, params, dist):
    """Serve mixed prompts through a paged engine, recording every decode
    call's logits and greedy tokens and every chunk call's first token;
    also one prompt chunk's logits from the chunk program's model step.
    Returns (records, chunk logits, final cache)."""
    eng = PagedEngine(cfg, params, dist=dist, n_slots=2, page_size=4,
                      chunk_size=4, max_prompt_len=12, max_new_tokens=4,
                      cache_dtype=jnp.float32)
    seen = []
    decode, chunk = eng._decode, eng._chunk_insert

    def record(fn, n_out):
        def call(*args):
            out = fn(*args)
            seen.append([np.asarray(o) for o in out[:n_out]])
            return out
        return call
    eng._decode, eng._chunk_insert = record(decode, 2), record(chunk, 1)
    res = eng.generate(_prompts(cfg, [9, 5, 7]),
                       GenerationConfig(max_new_tokens=3))
    seen.append([np.asarray(r.tokens) for r in res])

    ps, n_pages = 4, 4
    pt = jnp.asarray(np.arange(1, 1 + 2 * n_pages).reshape(2, n_pages))
    toks = jnp.asarray(_prompts(cfg, [4])[0][None], jnp.int32)
    step = jax.jit(lambda prm, c: T.chunk_step(
        prm, toks, 0, 0, 4, c, cfg, layout=A.PagedLayout(ps),
        page_table=pt, read_len=12, dist=eng.dist)[0])
    logits = step(params, T.init_paged_cache(cfg, 1 + 2 * n_pages, ps, 2,
                                             dtype=jnp.float32))
    return seen, np.asarray(logits), jax.tree.map(np.asarray, eng._cache)


@pytest.mark.parametrize("p", [1, 2], ids=["p1", "p2_mode_grouped"])
def test_stacked_expert_weights_bitwise_equal_layer_slices(served, p,
                                                           monkeypatch):
    """The fused kernel reading (stack, layer) serves the same bits as the
    kernel fed each layer's slice of the stacks: decode logits and greedy
    tokens, chunk logits and first tokens, served tokens, and the final
    KV pools and counters."""
    cfg, params = served
    prepared, dist = _fused_dist(cfg, params, p)
    stacked = _serve_recorded(cfg, prepared, dist)
    with monkeypatch.context() as m:
        m.setattr(moe_mod, "reads_layer_stack", lambda *a, **k: False)
        sliced = _serve_recorded(cfg, prepared, dist)
    for got, want in zip(jax.tree.leaves(stacked), jax.tree.leaves(sliced),
                         strict=True):
        assert got.shape == want.shape and (got == want).all()


_EXPERT_SLICE = re.compile(
    r"= \w+\[(\d+),(\d+),(\d+)\]\{[^}]*\} .*dynamic.slice")


def _expert_slices(text, w1_shape):
    """Instructions of compiled HLO text that dynamic-slice one layer's
    whole expert weight, (Es, d, f) or (Es, f, d), out of its stack."""
    _, Es, d, f = w1_shape
    whole = {(Es, d, f), (Es, f, d)}
    return [line for line in text.splitlines()
            if (m := _EXPERT_SLICE.search(line))
            and tuple(map(int, m.groups())) in whole]


@pytest.mark.parametrize("fused", [True, False], ids=["fused", "einsum"])
def test_expert_stacks_sliced_only_off_the_fused_path(served, fused):
    """Both compiled serving programs: with the fused kernel no
    instruction slices a layer's expert weights out of the stacks (the
    kernel indexes the layer itself); on the einsum path the scan's
    per-layer slices are still there."""
    cfg, params = served
    prepared, dist = _fused_dist(cfg, params, 2)
    if not fused:
        dist = dataclasses.replace(dist, policy=dataclasses.replace(
            dist.policy, use_kernel=False, fused_pipeline=False))
    eng = PagedEngine(cfg, prepared, dist=dist, n_slots=2, page_size=4,
                      chunk_size=4, max_prompt_len=12, max_new_tokens=4)
    w1_shape = prepared["blocks"]["moe"]["w1"].shape
    for text in (eng.decode_hlo(), eng.chunk_hlo()):
        found = _expert_slices(text, w1_shape)
        assert (not found) if fused else len(found) >= 3, found


def test_paged_rejects_oversized_and_unsupported(served):
    cfg, params = served
    eng = PagedEngine(cfg, params, n_slots=1, page_size=4, chunk_size=4,
                      max_prompt_len=8, max_new_tokens=4)
    with pytest.raises(ValueError):
        eng.submit(np.arange(9), GenerationConfig(max_new_tokens=2))
    with pytest.raises(ValueError):
        eng.submit(np.arange(4), GenerationConfig(max_new_tokens=5))


# ---------------------------------------------------------------------------
# Deprecated KV-cache shims
# ---------------------------------------------------------------------------

def test_deprecated_kv_shims_warn_and_match_layout(served):
    """init_kv_cache / build_cache_from_seq / _cache_slot warn
    DeprecationWarning and return bit-equal results to the KVCacheLayout
    replacements they delegate to."""
    del served
    rng = np.random.default_rng(0)
    k = jnp.asarray(rng.normal(size=(2, 6, 2, 4)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, 6, 2, 4)), jnp.float32)
    layout = A.ContiguousLayout()

    with pytest.warns(DeprecationWarning):
        old = A.init_kv_cache(2, 8, 2, 4, dtype=jnp.float32)
    new = layout.init(2, 8, 2, 4, dtype=jnp.float32)
    assert all((old[x] == new[x]).all() for x in ("k", "v"))

    with pytest.warns(DeprecationWarning):
        old = A.build_cache_from_seq(k, v, 8, dtype=jnp.float32)
    new = layout.from_seq(k, v, 8, dtype=jnp.float32)
    assert all((old[x] == new[x]).all() for x in ("k", "v"))

    with pytest.warns(DeprecationWarning):
        old = A._cache_slot(jnp.asarray(11), 8, window=4)
    assert old == A.ContiguousLayout(4).slot_index(jnp.asarray(11), 8)

    # no warning on the supported surface
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        layout.init(2, 8, 2, 4, dtype=jnp.float32)
        A.kv_cache_insert(new, k[:, :1], v[:, :1], jnp.asarray(0))
