"""repro.obs: traced on-device metrics, span tracing, and the export layer.

Covers the three contracts the observability seam must keep:
  * outputs are BIT-IDENTICAL with metrics on vs off (dispatch, fused
    pipeline, and S-ETP paths; engine greedy tokens);
  * counter-value changes never retrace the jitted decode step;
  * the export surface round-trips (Prometheus text, Chrome-trace JSON)
    and the legacy ``cache["moe_overflow"]`` read warns but still works.
"""
import json
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.policy import make_policy
from repro.models import model as M
from repro.models.transformer import DistContext
from repro.obs import (MetricsState, ObsCache, MetricsSnapshot,
                       SpanTracer, metrics_spec, parse_prometheus,
                       render_prometheus)
from repro.serving import (ContinuousBatchingEngine, GenerationConfig,
                           PagedEngine, Request, ServingEngine)


@pytest.fixture(scope="module")
def served():
    cfg = get_config("mixtral-8x7b-lite")
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _prompts(cfg, lens, mults=(7, 11, 13, 17, 5)):
    return [np.asarray((np.arange(L) * m) % cfg.vocab_size)
            for L, m in zip(lens, mults)]


# ---------------------------------------------------------------------------
# MetricsState / ObsCache pytree mechanics
# ---------------------------------------------------------------------------

def test_metrics_state_pytree_roundtrip():
    s = MetricsState.zeros(3, 8)
    leaves, treedef = jax.tree_util.tree_flatten(s)
    assert len(leaves) == 5
    s2 = jax.tree_util.tree_unflatten(treedef, leaves)
    assert isinstance(s2, MetricsState)
    assert s2.expert_load.shape == (3, 8)
    total = s + s2
    assert int(total.total_pairs) == 0


def test_obs_cache_is_registered_pytree():
    c = ObsCache({"b": jnp.ones(2), "a": jnp.zeros(3)})
    leaves, treedef = jax.tree_util.tree_flatten(c)
    c2 = jax.tree_util.tree_unflatten(treedef, leaves)
    assert isinstance(c2, ObsCache)
    assert sorted(c2) == ["a", "b"]
    # treedef must be stable across rebuilds — retrace hazard otherwise
    assert jax.tree_util.tree_structure(c2) == treedef


def test_metrics_spec_shapes(served):
    cfg, params = served
    spec = metrics_spec(cfg, params)
    assert spec is not None
    n_layers, n_sub = spec
    assert n_layers == cfg.n_layers
    # NoDrop default: no partition, sub-experts == experts
    assert n_sub == cfg.n_experts
    dense = get_config("qwen2-7b").reduced()
    assert metrics_spec(dense, {}) is None


# ---------------------------------------------------------------------------
# Bit-identity + counter consistency on the model paths
# ---------------------------------------------------------------------------

def _dispatch_dist(cfg, *, fused=False):
    from repro.launch.mesh import make_host_mesh
    policy = make_policy("2t", cfg.dualsparse, use_kernel=not fused,
                         fused_pipeline=fused)
    return policy, DistContext(mesh=make_host_mesh(1), moe_impl="dispatch",
                               policy=policy)


def test_prefill_bit_identical_and_counters_consistent(served):
    cfg, params = served
    batch = {"tokens": jnp.asarray(_prompts(cfg, [12])[0])[None, :]}
    on = M.make_prefill_step(cfg, cache_len=16, metrics=True)
    off = M.make_prefill_step(cfg, cache_len=16, metrics=False)
    logits_on, cache_on = on(params, batch)
    logits_off, cache_off = off(params, batch)
    assert jnp.array_equal(logits_on, logits_off)
    m = cache_on["metrics"]
    assert isinstance(m, MetricsState)
    assert "metrics" not in cache_off and "moe_overflow" in cache_off
    # every routed pair is kept, dropped, or was never kept (NoDrop: all
    # kept as FULL, nothing dropped); histogram counts kept pairs only
    T = batch["tokens"].shape[1]
    total = T * cfg.top_k * cfg.n_layers
    assert int(m.total_pairs) == total
    assert int(m.dropped_pairs) == 0 and int(m.kept_major) == 0
    assert int(m.expert_load.sum()) == int(m.kept_full + m.kept_major)
    assert m.expert_load.shape == (cfg.n_layers, cfg.n_experts)


def test_policy_paths_bit_identical_with_metrics(served):
    """2T-Drop via the dispatch path and the fused Pallas pipeline: the
    collect branch must not perturb the forward value."""
    cfg, params = served
    x = jnp.asarray(_prompts(cfg, [10])[0])[None, :]
    for fused in (False, True):
        policy, dist = _dispatch_dist(cfg, fused=fused)
        outs = {}
        for metrics in (True, False):
            step = M.make_prefill_step(cfg, cache_len=12, dist=dist,
                                       metrics=metrics)
            logits, cache = step(params, {"tokens": x})
            outs[metrics] = logits
        assert jnp.array_equal(outs[True], outs[False]), f"fused={fused}"


def test_setp_stats_match_overflow_path(moe_cfg, moe_params, calib_x):
    """S-ETP with return_stats: y bit-identical to the overflow-only call,
    stats internally consistent, overflow scalar equal on both calls."""
    from repro.core.setp import setp_moe_forward
    from repro.launch.mesh import make_host_mesh
    cfg = moe_cfg
    policy = make_policy("2t", cfg.dualsparse)
    params, policy = policy.prepare(moe_params, cfg, calib_x)
    mesh = make_host_mesh(1)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 8, cfg.d_model))
    y_ref, overflow = setp_moe_forward(params, x, cfg, mesh, policy=policy,
                                       return_overflow=True)
    y, stats = setp_moe_forward(params, x, cfg, mesh, policy=policy,
                                return_stats=True)
    assert jnp.array_equal(y, y_ref)
    assert int(stats["overflow_pairs"]) == int(overflow)
    T = x.shape[0] * x.shape[1]
    P = policy.partition_p
    kept = int(stats["kept_full"] + stats["kept_major"])
    assert kept + int(stats["dropped_pairs"]) == T * cfg.top_k * P
    assert int(stats["expert_load"].sum()) == kept


# ---------------------------------------------------------------------------
# Engines: identity, accumulation, no-retrace, migration
# ---------------------------------------------------------------------------

def test_engines_bit_identical_with_metrics(served):
    cfg, params = served
    prompts = _prompts(cfg, [6, 10, 8])
    gen = GenerationConfig(max_new_tokens=5)
    tokens = {}
    for metrics in (True, False):
        eng = ContinuousBatchingEngine(cfg, params, n_slots=2,
                                       max_prompt_len=12, max_new_tokens=6,
                                       cache_dtype=jnp.float32,
                                       metrics=metrics)
        tokens[metrics] = [r.tokens for r in eng.generate(prompts, gen)]
    assert tokens[True] == tokens[False]


def test_decode_never_retraces_on_counter_values(served):
    """The structural gate: metric VALUES change every step; the cache
    treedef (including the ObsCache wrapper and MetricsState leaves) must
    not, so the decode executable is hit exactly once."""
    cfg, params = served
    eng = ContinuousBatchingEngine(cfg, params, n_slots=2,
                                   max_prompt_len=12, max_new_tokens=16,
                                   metrics=True)
    for p in _prompts(cfg, [6, 10, 8, 5]):
        eng.submit(Request(prompt=p, gen=GenerationConfig(max_new_tokens=12)))
    before = None
    while eng.step():
        if before is None:
            before = int(eng._cache["metrics"].total_pairs)
    after = int(eng._cache["metrics"].total_pairs)
    assert after > before          # counters really accumulated
    assert eng.decode_traces == 1
    assert eng.prefill_traces == 1


def test_overflow_pairs_migration_and_deprecation(served):
    cfg, params = served
    eng = ContinuousBatchingEngine(cfg, params, n_slots=2,
                                   max_prompt_len=12, max_new_tokens=6,
                                   metrics=True)
    eng.generate(_prompts(cfg, [6, 8]), GenerationConfig(max_new_tokens=3))
    assert eng.overflow_pairs == int(eng._cache["metrics"].overflow_pairs)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        legacy = eng._cache["moe_overflow"]
    assert any(issubclass(x.category, DeprecationWarning) for x in w)
    assert int(legacy) == eng.overflow_pairs
    # metrics=False keeps the legacy scalar, no warning
    eng2 = ContinuousBatchingEngine(cfg, params, n_slots=2,
                                    max_prompt_len=12, max_new_tokens=6,
                                    metrics=False)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        assert int(eng2._cache["moe_overflow"]) == 0


def test_paged_engine_metrics_and_page_gauges(served):
    cfg, params = served
    eng = PagedEngine(cfg, params, n_slots=2, page_size=4, chunk_size=8,
                      max_prompt_len=12, max_new_tokens=6, metrics=True)
    eng.generate(_prompts(cfg, [9, 9, 6]), GenerationConfig(max_new_tokens=4))
    snap = eng.metrics()
    states = {s: snap.gauges[f'repro_page_pool_pages{{state="{s}"}}']
              for s in ("free", "held", "parked")}
    assert sum(states.values()) == eng.n_pages - 1
    assert snap.counters['repro_prefix_cache_total{event="hit"}'] \
        == eng.prefix_hits
    assert eng.chunk_traces == 1 and eng.decode_traces == 1


def test_engine_timing_and_request_latency(served):
    cfg, params = served
    eng = ContinuousBatchingEngine(cfg, params, n_slots=2,
                                   max_prompt_len=12, max_new_tokens=8,
                                   metrics=True)
    res = eng.generate(_prompts(cfg, [6, 10, 8]),
                       GenerationConfig(max_new_tokens=6))
    t = eng.timing
    assert t["compile_steps"] >= 1 and t["steady_steps"] >= 1
    assert t["compile_s"] > t["steady_step_s"] > 0
    for r in res:
        assert r.ttft_s is not None and r.tpot_s is not None
        assert 0 < r.ttft_s <= r.latency_s
    snap = eng.metrics()
    h = snap.histograms["repro_request_ttft_seconds"]
    assert h.count == len(res)


# ---------------------------------------------------------------------------
# Span tracer / Chrome trace
# ---------------------------------------------------------------------------

def test_chrome_trace_valid_json_with_nested_spans(tmp_path):
    tr = SpanTracer(enabled=True)
    with tr.span("outer", kind="test"):
        with tr.span("inner"):
            tr.instant("tick", n=1)
    path = tmp_path / "trace.json"
    tr.write_chrome_trace(str(path))
    doc = json.loads(path.read_text())
    evs = doc["traceEvents"]
    by_name = {e["name"]: e for e in evs}
    assert by_name["tick"]["ph"] == "i"
    outer, inner = by_name["outer"], by_name["inner"]
    assert outer["ph"] == inner["ph"] == "X"
    # inner nests fully within outer on the timeline
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]
    assert outer["args"]["kind"] == "test"


def test_paged_step_emits_phase_spans_in_order(served):
    """One paged step's phases, in the order they open: the step, then
    admission, the last prefill chunk with its token readback and emit,
    then the batched decode with its readback and emit."""
    cfg, params = served
    eng = PagedEngine(cfg, params, n_slots=2, page_size=4, chunk_size=4,
                      max_prompt_len=12, max_new_tokens=4, trace=True)
    uid = eng.submit(np.arange(6, dtype=np.int32) % cfg.vocab_size,
                     GenerationConfig(max_new_tokens=3))
    eng.step()                         # first chunk: nothing to decode yet
    n0 = len(eng.tracer.events())
    eng.step()                         # last chunk, then decode
    evs = sorted((e for e in eng.tracer.events()[n0:] if e["ph"] == "X"),
                 key=lambda e: (e["ts"], -e["dur"]))
    assert [e["name"] for e in evs] == [
        "step", "admit", "prefill_chunk", "readback", "emit", "decode",
        "readback", "emit"]
    by = {e["name"]: e["args"] for e in evs}
    assert by["prefill_chunk"]["uid"] == uid
    assert by["prefill_chunk"]["start"] == 4
    assert by["decode"] == {"batch": 1}
    step = evs[0]
    for e in evs[1:]:
        assert step["ts"] <= e["ts"]
        assert e["ts"] + e["dur"] <= step["ts"] + step["dur"]


def test_spans_reach_the_profiler_with_the_buffer_off(served, tmp_path):
    """Engine spans are profiler annotations whether or not the Chrome
    buffer records; with ``trace=False`` (the default) it stays empty."""
    import glob
    from jax.profiler import ProfileData
    cfg, params = served
    eng = PagedEngine(cfg, params, n_slots=2, page_size=4, chunk_size=4,
                      max_prompt_len=12, max_new_tokens=4)
    eng.generate(_prompts(cfg, [3]), GenerationConfig(max_new_tokens=2))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        eng.generate(_prompts(cfg, [6, 3]), GenerationConfig(max_new_tokens=2))
    assert eng.tracer.events() == []
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0]
    names = {e.name for plane in ProfileData.from_file(path).planes
             for line in plane.lines for e in line.events}
    assert {"engine_step", "engine_admit", "engine_prefill_chunk",
            "engine_decode", "engine_readback", "engine_emit",
            "engine_submit", "engine_retire"} <= names


def test_disabled_tracer_records_nothing(served):
    cfg, params = served
    eng = ContinuousBatchingEngine(cfg, params, n_slots=2,
                                   max_prompt_len=12, max_new_tokens=6,
                                   metrics=False)
    eng.generate(_prompts(cfg, [6, 8]), GenerationConfig(max_new_tokens=3))
    assert eng.tracer.events() == []


# ---------------------------------------------------------------------------
# Export: Prometheus exposition + JSON lines + schema validator
# ---------------------------------------------------------------------------

def test_prometheus_round_trip():
    snap = MetricsSnapshot()
    snap.counter("repro_moe_subpairs_total", 42, outcome="kept_full")
    snap.counter("repro_moe_subpairs_total", 7, outcome="dropped")
    snap.gauge("repro_queue_depth", 3)
    snap.histogram("repro_request_latency_seconds", [0.002, 0.3, 0.3, 12.0])
    text = render_prometheus(snap)
    back = parse_prometheus(text)
    assert back.counters == snap.counters
    assert back.gauges == snap.gauges
    h0 = snap.histograms["repro_request_latency_seconds"]
    h1 = back.histograms["repro_request_latency_seconds"]
    assert h0.counts == h1.counts and h0.sum == pytest.approx(h1.sum)
    # render is deterministic and self-consistent
    assert render_prometheus(back) == text


def test_metrics_server_scrape(served):
    import urllib.request
    cfg, params = served
    eng = ServingEngine(cfg, params, metrics=True)
    eng.generate(_prompts(cfg, [6]), GenerationConfig(max_new_tokens=3))
    from repro.obs import MetricsServer
    srv = MetricsServer(eng.metrics, port=0).start()
    try:
        with urllib.request.urlopen(srv.url) as resp:
            assert resp.status == 200
            text = resp.read().decode()
    finally:
        srv.stop()
    snap = parse_prometheus(text)
    assert snap.counters['repro_requests_total{state="finished"}'] == 1


def test_serving_engine_row_schema_requires_timing():
    from repro.lint.bench_schema import SERVING_ENGINE_ROW, _check_keys
    row = {"engine": "paged", "requests": 4, "tokens": 16,
           "throughput_tok_s": 10.0, "wall_s": 1.6}
    errs = _check_keys(row, SERVING_ENGINE_ROW, "engines[0]")
    assert any("compile_s" in e for e in errs)
    assert any("steady_step_s" in e for e in errs)
