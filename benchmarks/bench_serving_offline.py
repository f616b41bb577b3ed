"""Offline serving throughput: paged vs contiguous KV, plus a prefix-cache
hit-rate sweep (MLPerf-offline style — every request is available at t=0,
the engine drains the backlog, throughput = generated tokens / wall time).

Two sections:
  * ``engines`` — the same mixed-length workload through the contiguous
    continuous-batching engine and the paged engine (chunked prefill +
    page-table indirection); with exact MoE both emit bit-identical greedy
    tokens, so the delta is pure scheduling/layout cost.
  * ``prefix_sweep`` — workloads whose prompts share a leading prefix of
    varying fraction; the paged engine's prefix cache maps shared pages
    instead of recomputing them. Reports hit rate and prefill work skipped.

Emits ``BENCH_serving_offline.json`` (repo root by default; flat,
overwritten per run) validated against ``repro.lint.bench_schema``.

    PYTHONPATH=src python -m benchmarks.bench_serving_offline [--smoke]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import numpy as np

from repro.configs import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model as M
from repro.serving import (ContinuousBatchingEngine, GenerationConfig,
                           PagedEngine)


def make_prompts(cfg, n, lens, *, shared_frac=0.0, seed=0):
    """Mixed-length prompts; ``shared_frac`` of each prompt (from the left)
    is a common prefix across all requests of the same length class."""
    rng = np.random.RandomState(seed)
    shared = rng.randint(0, cfg.vocab_size, max(lens)).astype(np.int32)
    out = []
    for i in range(n):
        L = lens[i % len(lens)]
        p = rng.randint(0, cfg.vocab_size, L).astype(np.int32)
        k = int(L * shared_frac)
        p[:k] = shared[:k]
        out.append(p)
    return out


def drain_timed(eng, prompts, gen):
    """Submit everything up front, drain, return (tok/s, tokens, wall)."""
    for p in prompts:
        eng.submit(p, gen)
    t0 = time.perf_counter()
    res = eng.drain()
    wall = time.perf_counter() - t0
    tokens = sum(len(r.tokens) for r in res)
    return tokens / wall, tokens, wall


def run(smoke: bool = False, out_path: str | None = None) -> dict:
    cfg = get_config("mixtral-8x7b-lite")
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    if smoke:
        n_req, lens, new, slots = 6, (8, 16), 4, 2
        page, chunk = 4, 8
        sweep_fracs = (0.0, 1.0)
    else:
        n_req, lens, new, slots = 24, (16, 48, 96), 16, 4
        page, chunk = 16, 32
        sweep_fracs = (0.0, 0.25, 0.5, 0.75, 1.0)
    max_prompt = max(lens)
    gen = GenerationConfig(max_new_tokens=new)
    kw = dict(max_prompt_len=max_prompt, max_new_tokens=new)
    warm = [np.zeros(max_prompt, np.int32)]
    warm_gen = GenerationConfig(max_new_tokens=1)

    # -- engine comparison ------------------------------------------------
    prompts = make_prompts(cfg, n_req, lens)
    engine_rows = []
    for name in ("contiguous", "paged"):
        if name == "contiguous":
            eng = ContinuousBatchingEngine(cfg, params, n_slots=slots, **kw)
        else:
            eng = PagedEngine(cfg, params, n_slots=slots, page_size=page,
                              chunk_size=chunk, **kw)
        eng.generate(warm, warm_gen)       # compile outside the timed drain
        eng.reset_stats()
        tps, tokens, wall = drain_timed(eng, prompts, gen)
        timing = eng.timing
        row = {"engine": name, "requests": n_req, "tokens": tokens,
               "throughput_tok_s": round(tps, 2), "wall_s": round(wall, 4),
               "compile_s": round(timing["compile_s"], 4),
               "steady_step_s": round(timing["steady_step_s"], 6)}
        engine_rows.append(row)
        print(f"{name:11s}: {tps:8.1f} tok/s  ({tokens} tokens, "
              f"{wall:.2f}s wall, compile {row['compile_s']:.2f}s, "
              f"steady step {row['steady_step_s'] * 1e3:.2f}ms)")

    # -- prefix-cache hit-rate sweep -------------------------------------
    sweep_rows = []
    for frac in sweep_fracs:
        eng = PagedEngine(cfg, params, n_slots=slots, page_size=page,
                          chunk_size=chunk, **kw)
        eng.generate(warm, warm_gen)
        eng.reset_stats()
        sp = make_prompts(cfg, n_req, lens, shared_frac=frac, seed=1)
        tps, tokens, wall = drain_timed(eng, sp, gen)
        row = {"shared_prefix_frac": frac,
               "hit_rate": round(eng.prefix_hit_rate, 4),
               "throughput_tok_s": round(tps, 2),
               "chunk_steps": eng.chunk_steps,
               "prefill_tokens": eng.prefill_tokens}
        sweep_rows.append(row)
        print(f"prefix {frac:4.2f}: hit_rate {row['hit_rate']:.2f}  "
              f"{tps:8.1f} tok/s  chunks {eng.chunk_steps}  "
              f"prefilled {eng.prefill_tokens}")

    payload = {
        "bench": "serving_offline",
        "unit": "tok/s",
        "note": "offline (backlog-drain) serving throughput, paged vs "
                "contiguous KV, and the paged engine's prefix-cache sweep "
                "(hit rate + prefill work vs shared-prefix fraction); "
                "greedy tokens are bit-identical across engines under "
                "exact MoE",
        "host": {"backend": jax.default_backend(),
                 "devices": jax.device_count()},
        "smoke": smoke,
        "engines": engine_rows,
        "prefix_sweep": sweep_rows,
    }
    out = out_path or os.path.join(os.path.dirname(__file__), "..",
                                   "BENCH_serving_offline.json")
    from repro.lint.bench_schema import validate_serving_bench
    schema_errs = validate_serving_bench(payload)
    assert not schema_errs, (
        "refusing to write a malformed BENCH_serving_offline.json: "
        + "; ".join(schema_errs))
    with open(out, "w") as f:
        json.dump(payload, f, indent=2)
        f.write("\n")
    print(f"# wrote {os.path.abspath(out)}")
    return payload


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny workload (CI end-to-end check)")
    ap.add_argument("--out", default=None, help="JSON output path")
    args = ap.parse_args()
    run(smoke=args.smoke, out_path=args.out)


if __name__ == "__main__":
    main()
