"""Observability overhead gate: metrics-on vs metrics-off decode throughput.

The traced on-device metrics seam (``repro.obs.MetricsState`` riding in the
decode cache) is designed to be almost free — a handful of int32 adds and
one small histogram per MoE layer, no host syncs, no retraces. This bench
measures exactly that claim on the continuous-batching engine's steady-state
decode step and GATES it: the non-smoke run asserts the relative decode-time
overhead stays within ``MAX_OVERHEAD_FRAC`` (5%).

Method: build two engines over the same params — one with ``metrics=True``,
one with ``metrics=False`` — warm both (compile excluded), then time N
steady decode steps each under ``jax.block_until_ready``. Greedy tokens are
asserted bit-identical between the two runs first, so the timing compares
the same computation ± the metrics seam.

Emits/APPENDS to ``BENCH_obs_overhead.json`` (repo root by default): the
file holds a ``runs`` list — one entry per invocation — validated against
``repro.lint.bench_schema.validate_obs_bench``.

    PYTHONPATH=src python -m benchmarks.bench_obs_overhead [--smoke]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.lint.bench_schema import validate_obs_bench
from repro.models import model as M
from repro.serving import ContinuousBatchingEngine, GenerationConfig, Request

MAX_OVERHEAD_FRAC = 0.05


def _make_engine(cfg, params, *, metrics, n_slots, max_prompt, max_new):
    return ContinuousBatchingEngine(
        cfg, params, n_slots=n_slots, max_prompt_len=max_prompt,
        max_new_tokens=max_new, cache_dtype=jnp.float32, metrics=metrics)


def _fill_slots(eng, cfg, n_slots, max_prompt, budget, seed=0):
    """Admit one long-budget request per slot so the timed loop below is
    pure steady-state decode at full occupancy."""
    rng = np.random.RandomState(seed)
    for i in range(n_slots):
        prompt = rng.randint(0, cfg.vocab_size, max_prompt - 1).astype(
            np.int32)
        eng.submit(Request(prompt=prompt,
                           gen=GenerationConfig(max_new_tokens=budget)))
    eng.step()                        # admits everything + 1 decode step
    assert eng.free_slots == 0


def _time_decode(eng, n_steps):
    """Mean wall time of one batched decode step over n_steps steps."""
    jax.block_until_ready(eng._cache)
    t0 = time.perf_counter()
    for _ in range(n_steps):
        eng.step()
    jax.block_until_ready(eng._cache)
    return (time.perf_counter() - t0) / n_steps


def _identical_tokens(cfg, params, *, n_slots, max_prompt, max_new):
    """Greedy tokens of a small workload must not depend on the metrics
    seam — otherwise the timing below compares different computations."""
    outs = []
    rng = np.random.RandomState(7)
    prompts = [rng.randint(0, cfg.vocab_size, max_prompt // 2).astype(
        np.int32) for _ in range(n_slots + 1)]
    for m in (True, False):
        eng = _make_engine(cfg, params, metrics=m, n_slots=n_slots,
                           max_prompt=max_prompt, max_new=max_new)
        res = eng.generate(prompts, GenerationConfig(max_new_tokens=4))
        outs.append([r.tokens for r in res])
    assert outs[0] == outs[1], "metrics seam changed greedy tokens"


def run(smoke: bool = False, out_path: str | None = None) -> dict:
    cfg = get_config("mixtral-8x7b-lite")
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    if smoke:
        n_slots, max_prompt, steps, repeats = 2, 16, 8, 1
    else:
        n_slots, max_prompt, steps, repeats = 4, 32, 48, 3
    max_new = steps * (repeats + 2)

    _identical_tokens(cfg, params, n_slots=n_slots, max_prompt=max_prompt,
                      max_new=8)

    per_mode = {}
    decode_steps = 0
    for m in (True, False):
        eng = _make_engine(cfg, params, metrics=m, n_slots=n_slots,
                           max_prompt=max_prompt, max_new=max_new)
        _fill_slots(eng, cfg, n_slots, max_prompt, budget=max_new)
        _time_decode(eng, 2)          # warm: everything traced by now
        assert eng.decode_traces == 1, "steady loop retraced"
        # best-of-repeats: scheduler noise is one-sided
        best = min(_time_decode(eng, steps) for _ in range(repeats))
        per_mode[m] = best
        decode_steps += eng.decode_steps
    t_on, t_off = per_mode[True], per_mode[False]
    overhead = (t_on - t_off) / t_off
    tok_s_on = n_slots / t_on
    tok_s_off = n_slots / t_off
    row = {
        "engine": "continuous", "decode_steps": decode_steps,
        "decode_us_on": round(t_on * 1e6, 2),
        "decode_us_off": round(t_off * 1e6, 2),
        "tok_s_on": round(tok_s_on, 2), "tok_s_off": round(tok_s_off, 2),
        "overhead_frac": round(overhead, 4),
    }
    print(f"decode step: metrics-on {row['decode_us_on']:.0f}us "
          f"({tok_s_on:.1f} tok/s)  metrics-off {row['decode_us_off']:.0f}us "
          f"({tok_s_off:.1f} tok/s)  overhead {overhead * 100:+.2f}%")
    if not smoke:
        assert overhead <= MAX_OVERHEAD_FRAC, (
            f"metrics seam costs {overhead:.1%} of a decode step "
            f"(budget {MAX_OVERHEAD_FRAC:.0%})")

    run_entry = {
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "host": {"backend": jax.default_backend(),
                 "devices": jax.device_count()},
        "smoke": smoke,
        "rows": [row],
    }
    out = out_path or os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "BENCH_obs_overhead.json")
    payload = {
        "bench": "obs_overhead",
        "unit": "us_per_decode_step",
        "note": "steady-state decode step time of the continuous-batching "
                "engine with the traced on-device metrics seam "
                "(cache['metrics']) enabled vs disabled; greedy tokens are "
                "asserted bit-identical first; non-smoke runs gate "
                "overhead_frac <= 0.05",
        "runs": [],
    }
    if os.path.exists(out):
        try:
            with open(out) as f:
                old = json.load(f)
            if isinstance(old.get("runs"), list):
                payload["runs"] = old["runs"]
        except (json.JSONDecodeError, OSError):
            pass
    payload["runs"].append(run_entry)
    schema_errs = validate_obs_bench(payload)
    assert not schema_errs, (
        "refusing to write a malformed BENCH_obs_overhead.json: "
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
                    help="tiny workload, no overhead gate (CI check)")
    ap.add_argument("--out", default=None, help="JSON output path")
    args = ap.parse_args()
    run(smoke=args.smoke, out_path=args.out)


if __name__ == "__main__":
    main()
