"""Dispatch substrate benchmark: sort-based vs one-hot-cumsum seating.

Times ONE dispatch step (plan + buffer materialization — the quantity every
MoE layer pays before its expert GEMMs) for both implementations over a
T x E grid, plus the mode-ordered 2T variant with its analytic MXU
tile-skip fraction (what ``counts_major`` buys the dual-sparse kernel).

Emits ``BENCH_dispatch.json`` (repo root by default) so the perf trajectory
of this path is tracked across PRs, and CSV rows for ``benchmarks.run``.

Usage:
    PYTHONPATH=src python -m benchmarks.bench_dispatch [--smoke] [--out F]
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import numpy as np

from repro.core import dispatch as D
from repro.launch.compile_cache import enable_compile_cache

from .common import Row, time_fn

K = 8
D_MODEL = 64
FULL_SWEEP = [(T, E) for T in (256, 1024, 4096, 16384)
              for E in (8, 64, 256)]
SMOKE_SWEEP = [(256, 8), (1024, 64)]
# mode-ordered cases: fraction of kept pairs that are MAJOR-only / dropped
MODE_CASES = [(0.0, 0.0), (0.3, 0.1)]


def _case(T: int, E: int, major_frac: float, drop_frac: float, seed: int = 0):
    key = jax.random.PRNGKey(seed)
    ks = jax.random.split(key, 4)
    idx = jax.random.randint(ks[0], (T, K), 0, E)
    x = jax.random.normal(ks[1], (T, D_MODEL))
    keep = ~jax.random.bernoulli(ks[2], drop_frac, (T, K))
    major = jax.random.bernoulli(ks[3], major_frac, (T, K)) & keep
    cap = max(8, int(np.ceil(1.25 * T * K / E / 8)) * 8)
    return idx, x, keep, major, cap


def _dispatch_step(plan_fn, build_fn, E: int, cap: int):
    def step(idx, x, keep, major):
        plan = plan_fn(idx, keep, n_groups=E, capacity=cap, major_only=major)
        return build_fn(x, plan, cap, index_div=K), plan.overflow
    return jax.jit(step)


def run(smoke: bool = False, out_path: str | None = None) -> list[Row]:
    sweep = SMOKE_SWEEP if smoke else FULL_SWEEP
    iters = 3 if smoke else 7
    rows: list[Row] = []
    results = []
    for T, E in sweep:
        for major_frac, drop_frac in (MODE_CASES[:1] if smoke else MODE_CASES):
            idx, x, keep, major, cap = _case(T, E, major_frac, drop_frac)
            t_cum = time_fn(
                _dispatch_step(D.cumsum_dispatch, D.scatter_rows, E, cap),
                idx, x, keep, major, iters=iters, warmup=1)
            t_sort = time_fn(
                _dispatch_step(D.sort_dispatch, D.gather_rows, E, cap),
                idx, x, keep, major, iters=iters, warmup=1)
            plan = D.sort_dispatch(idx, keep, n_groups=E, capacity=cap,
                                   major_only=major)
            skip = _tile_skip(plan, cap) if major_frac > 0 else 0.0
            tag = f"dispatch/T{T}_E{E}_maj{major_frac:.1f}"
            rows.append((f"{tag}/cumsum", t_cum, ""))
            rows.append((f"{tag}/sort", t_sort,
                         f"speedup={t_cum / t_sort:.2f}x "
                         f"tile_skip={skip:.3f}"))
            results.append({
                "T": T, "E": E, "K": K, "d": D_MODEL, "capacity": cap,
                "major_frac": major_frac, "drop_frac": drop_frac,
                "cumsum_us": t_cum, "sort_us": t_sort,
                "speedup": t_cum / t_sort, "tile_skip_fraction": skip,
            })
    payload = {
        "bench": "dispatch",
        "unit": "us_per_dispatch_step",
        "note": "plan + buffer materialization; sort-based vs dense "
                "one-hot cumsum (core.dispatch)",
        "host": {"backend": jax.default_backend(),
                 "devices": jax.device_count()},
        "smoke": smoke,
        "rows": results,
    }
    out_path = out_path or os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "BENCH_dispatch.json")
    from repro.lint.bench_schema import validate_dispatch_bench
    schema_errs = validate_dispatch_bench(payload)
    assert not schema_errs, (
        "refusing to write a malformed BENCH_dispatch.json: "
        + "; ".join(schema_errs))
    with open(out_path, "w") as f:
        json.dump(payload, f, indent=2)
        f.write("\n")
    return rows


def _tile_skip(plan: D.DispatchPlan, cap: int, f: int = 256,
               block_c: int = 128, block_f: int = 128) -> float:
    """Analytic fraction of (token-block x neuron-block) MXU tiles the
    dual-sparse kernel never issues for these counts (see
    bench_kernel_skip.tile_skip_fraction; f/2 is the minor boundary)."""
    from .bench_kernel_skip import tile_skip_fraction
    cf, cm = (np.asarray(a) for a in plan.kernel_counts(cap))
    return float(tile_skip_fraction(cf, cm, cap, f,
                                    block_c=min(block_c, cap),
                                    block_f=min(block_f, f)))


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sweep for CI (seconds, not minutes)")
    ap.add_argument("--out", default=None, help="JSON output path")
    args = ap.parse_args()
    t0 = time.time()
    rows = run(smoke=args.smoke, out_path=args.out)
    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")
    print(f"# dispatch bench done in {time.time() - t0:.1f}s")


if __name__ == "__main__":
    main()
