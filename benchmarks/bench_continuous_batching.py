"""Continuous vs synchronized batching under heterogeneous Poisson traffic.

Requests arrive as a Poisson process with mixed prompt and output lengths —
the regime where synchronized batching loses throughput to convoy effects
(every request in a batch waits for the longest one) and continuous batching
keeps slots busy via mid-decode admission.

Reports, per engine: token throughput, mean/p95 request latency, and the
slot-utilization statistics of the continuous scheduler.

    PYTHONPATH=src python benchmarks/bench_continuous_batching.py \
        --requests 12 --slots 4 --rate 2.0
"""
from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import numpy as np

from repro.configs import get_config
from repro.data.pipeline import SyntheticLM
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model as M
from repro.serving import (ContinuousBatchingEngine, GenerationConfig,
                           PagedEngine, ServingEngine)


def make_traffic(cfg, n_requests, rate_hz, prompt_lens, out_lens, seed=0):
    """Poisson arrivals with prompt/output lengths cycled from the mixes."""
    rng = np.random.RandomState(seed)
    src = SyntheticLM(cfg.vocab_size, seed=seed)
    key = jax.random.PRNGKey(seed)
    t = 0.0
    traffic = []
    for i in range(n_requests):
        t += rng.exponential(1.0 / rate_hz)
        pl = prompt_lens[i % len(prompt_lens)]
        ol = out_lens[i % len(out_lens)]
        prompt = np.asarray(src.sample_batch(
            jax.random.fold_in(key, i), 1, pl)["tokens"][0])
        traffic.append((t, prompt, GenerationConfig(max_new_tokens=ol)))
    return traffic


def lat_stats(lats):
    lats = np.asarray(lats)
    return float(lats.mean()), float(np.percentile(lats, 95))


def timing_line(eng):
    """compile-vs-steady split from the engine's step classifier — steps
    that (re)traced a jit are compile, the rest are steady state; a tok/s
    headline that mixes the two misstates both."""
    t = eng.timing
    return (f"timing: compile={t['compile_s']:.2f}s "
            f"({t['compile_steps']} traced steps) "
            f"steady_step={t['steady_step_s'] * 1e3:.2f}ms "
            f"over {t['steady_steps']} steps")


def _warm_sync(eng, cfg, batch_size, max_prompt):
    """Compile prefill/serve at the shapes the traffic will hit (a chunk's
    padded length is its longest prompt, so warm at max_prompt). Retraces on
    odd-shaped partial chunks remain — a genuine synchronized-engine cost."""
    prompts = [np.zeros(max_prompt, np.int32)] * batch_size
    eng.generate(prompts, GenerationConfig(max_new_tokens=1))


def run_sync(cfg, params, traffic, batch_size, max_prompt, max_new):
    """Synchronized baseline under the same arrival process (the paper's
    §5.3.2 setting, extended with arrival-time accounting). The engine's own
    convoy scheduler does the waiting: ``_ready()`` holds a batch until it
    fills (or the trace is exhausted), and per-request budgets/EOS are
    honored inside the decode loop — no driver-side chunking needed."""
    # exact_moe matches the continuous engine's dispatch setting so the
    # headline ratio measures scheduling, not a capacity handicap
    eng = ServingEngine(cfg, params, batch_size=batch_size,
                        max_prompt_len=max_prompt, max_new_tokens=max_new,
                        exact_moe=True)
    _warm_sync(eng, cfg, batch_size, max_prompt)
    t0 = time.perf_counter()
    res = eng.generate_timed(traffic)
    wall = time.perf_counter() - t0
    tokens = sum(len(r.tokens) for r in res)
    latencies = [r.latency_s for r in res]
    return tokens / wall, latencies, wall, eng


def _run_timed(eng, traffic, max_prompt):
    """Warm (compile at the traffic's fixed shapes), reset stats, replay."""
    eng.generate([np.zeros(max_prompt, np.int32)],
                 GenerationConfig(max_new_tokens=1))
    eng.reset_stats()
    t0 = time.perf_counter()
    res = eng.generate_timed(traffic)
    wall = time.perf_counter() - t0
    tokens = sum(len(r.tokens) for r in res)
    latencies = [r.latency_s for r in res]
    return tokens / wall, latencies, wall, eng


def run_continuous(cfg, params, traffic, slots, max_prompt, max_new):
    eng = ContinuousBatchingEngine(cfg, params, n_slots=slots,
                                   max_prompt_len=max_prompt,
                                   max_new_tokens=max_new)
    return _run_timed(eng, traffic, max_prompt)


def run_paged(cfg, params, traffic, slots, max_prompt, max_new,
              page_size, chunk_size):
    eng = PagedEngine(cfg, params, n_slots=slots, page_size=page_size,
                      chunk_size=chunk_size, max_prompt_len=max_prompt,
                      max_new_tokens=max_new)
    return _run_timed(eng, traffic, max_prompt)


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mixtral-8x7b-lite")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--rate", type=float, default=2.0,
                    help="Poisson arrival rate (requests/s)")
    ap.add_argument("--prompt-lens", default="8,24,48")
    ap.add_argument("--out-lens", default="4,12,24")
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--chunk-size", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    cfg = get_config(args.arch)
    params = M.init_params(jax.random.PRNGKey(args.seed), cfg)
    prompt_lens = [int(x) for x in args.prompt_lens.split(",")]
    out_lens = [int(x) for x in args.out_lens.split(",")]
    max_prompt, max_new = max(prompt_lens), max(out_lens)
    traffic = make_traffic(cfg, args.requests, args.rate, prompt_lens,
                           out_lens, args.seed)
    span = traffic[-1][0]
    print(f"# {args.requests} requests over {span:.2f}s "
          f"(rate {args.rate}/s), prompts {prompt_lens}, outputs {out_lens}")

    tps_c, lat_c, wall_c, eng = run_continuous(
        cfg, params, traffic, args.slots, max_prompt, max_new)
    m, p95 = lat_stats(lat_c)
    print(f"continuous  ({args.slots} slots): {tps_c:6.1f} tok/s  "
          f"latency mean {m:.2f}s p95 {p95:.2f}s  wall {wall_c:.2f}s")
    from common import moe_overflow
    print(f"  scheduler: admitted={eng.n_admitted} "
          f"decode_steps={eng.decode_steps} "
          f"max_concurrency={eng.max_concurrency} "
          f"traces(prefill={eng.prefill_traces}, decode={eng.decode_traces}) "
          f"moe_overflow={moe_overflow(eng)}")
    print(f"  {timing_line(eng)}")

    tps_p, lat_p, wall_p, peng = run_paged(
        cfg, params, traffic, args.slots, max_prompt, max_new,
        args.page_size, args.chunk_size)
    m, p95 = lat_stats(lat_p)
    print(f"paged       ({args.slots} slots): {tps_p:6.1f} tok/s  "
          f"latency mean {m:.2f}s p95 {p95:.2f}s  wall {wall_p:.2f}s")
    print(f"  scheduler: admitted={peng.n_admitted} "
          f"chunk_steps={peng.chunk_steps} "
          f"decode_steps={peng.decode_steps} "
          f"prefix_hit_rate={peng.prefix_hit_rate:.2f} "
          f"traces(chunk={peng.chunk_traces}, decode={peng.decode_traces}) "
          f"moe_overflow={moe_overflow(peng)}")
    print(f"  {timing_line(peng)}")

    tps_s, lat_s, wall_s, seng = run_sync(cfg, params, traffic, args.slots,
                                          max_prompt, max_new)
    m, p95 = lat_stats(lat_s)
    print(f"synchronized (B={args.slots})  : {tps_s:6.1f} tok/s  "
          f"latency mean {m:.2f}s p95 {p95:.2f}s  wall {wall_s:.2f}s")
    print(f"  {timing_line(seng)}")
    print(f"# continuous/synchronized throughput: {tps_c / tps_s:.2f}x, "
          f"mean-latency: {lat_stats(lat_c)[0] / lat_stats(lat_s)[0]:.2f}x")


if __name__ == "__main__":
    main()
