"""Fused MoE pipeline benchmark + HBM-elimination assertion.

Compares the production buffer path (gather_rows -> grouped_swiglu ->
unpermute + combine) against the single fused Pallas pipeline
(``fused_pipeline=True``: the kernel consumes the DispatchPlan directly) on
the same 2T-routed layer, and — the part CI gates on — lowers both to HLO
and asserts via ``launch.hlo_analysis`` that the fused path materializes NO
``(E, capacity, d)`` intermediate buffer (the two HBM round-trips the fused
kernel exists to eliminate; see README "Dispatch architecture").

Timings on this CPU container run the kernels in interpret mode, so the
µs numbers track *plan/dispatch overhead*, not MXU economics — the HLO
bytes/shape accounting is the backend-independent signal. Full runs add
prefill-scale rows (T=4096/8192) that compare buffer vs resident-fused vs
streamed-fused and gate streamed <= buffer; streamed must match resident
bit-for-bit at every scale.

Emits/APPENDS to ``BENCH_moe_pipeline.json`` (repo root by default): the
file holds a ``runs`` list — one entry per invocation — so the trajectory
accumulates across PRs instead of overwriting. Schema documented in README.

Usage:
    PYTHONPATH=src python -m benchmarks.bench_moe_pipeline [--smoke] [--out F]
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax

from repro.configs import get_config
from repro.core import moe as moe_mod
from repro.core import policy as policy_mod
from repro.launch import hlo_analysis
from repro.launch.compile_cache import enable_compile_cache
from repro.lint.bench_schema import validate_pipeline_bench
from repro.lint.hlo_passes import capacity_buffer_count
from repro.models.layers import split_params

from .common import Row, rel_err, sharp_router_params, time_fn

# Full runs include prefill-scale rows: at T >= PREFILL_T the resident
# fused kernel would need the whole (T, d) activation + f32 accumulator in
# VMEM, so these rows are the ones that exercise (and gate) the streamed
# HBM<->VMEM DMA rewrite. Interpret mode makes them slow — iters drops to
# PREFILL_ITERS there.
FULL_TOKENS = [128, 256, 4096, 8192]
SMOKE_TOKENS = [64]
PREFILL_T = 4096
PREFILL_ITERS = 2


def _setup(seed: int = 0):
    cfg = get_config("olmoe-lite").reduced()
    key = jax.random.PRNGKey(seed)
    params, _ = split_params(moe_mod.make_moe_params(key, cfg))
    params = sharp_router_params(params)
    policy = policy_mod.make_policy("2t", cfg.dualsparse, use_kernel=True)
    calib = jax.random.normal(jax.random.fold_in(key, 1), (96, cfg.d_model))
    params, policy = policy.prepare(params, cfg, calib)
    return cfg, params, policy


def _paths(cfg, params, policy, T: int):
    """(buffer_fn, fused_fn, resident_fn, x, capacity) — jitted, same
    routing inside. ``fused_fn`` is the streamed kernel (the production
    default); ``resident_fn`` is the whole-array-resident variant it
    replaced, kept as the bit-exactness yardstick for the DMA machinery."""
    E = params["w1"].shape[0] // policy.partition_p
    capacity = moe_mod.capacity_for(T, cfg.top_k, E, policy.capacity_factor)

    def run(x, fused: bool, streamed: bool = True):
        pairs = policy.route(params, x, cfg)
        return moe_mod.moe_forward_dispatch(
            params, x, cfg, pairs=pairs, capacity=capacity,
            use_kernel=not fused, mode_grouped=policy.kernel_mode_grouping,
            fused_pipeline=fused, fused_streamed=streamed,
            return_overflow=True)

    x = jax.random.normal(jax.random.PRNGKey(T), (T, cfg.d_model))
    buffer_fn = jax.jit(lambda x: run(x, False))
    fused_fn = jax.jit(lambda x: run(x, True))
    resident_fn = jax.jit(lambda x: run(x, True, streamed=False))
    return buffer_fn, fused_fn, resident_fn, x, capacity


def run(smoke: bool = False, out_path: str | None = None) -> list[Row]:
    cfg, params, policy = _setup()
    E = params["w1"].shape[0] // policy.partition_p
    d = cfg.d_model
    rows: list[Row] = []
    results = []
    for T in (SMOKE_TOKENS if smoke else FULL_TOKENS):
        iters = PREFILL_ITERS if T >= PREFILL_T else (2 if smoke else 5)
        buffer_fn, fused_fn, resident_fn, x, capacity = _paths(
            cfg, params, policy, T)

        yb, ovb = buffer_fn(x)
        yf, ovf = fused_fn(x)
        yr, ovr = resident_fn(x)
        # streamed and resident share math and accumulation order; the DMA
        # staging must not perturb a single bit.
        assert (yf == yr).all() and int(ovf) == int(ovr), (
            f"streamed kernel diverged from resident variant at T={T}")
        err = rel_err(yf, yb)
        assert err <= 1e-6, f"fused path diverged from oracle: rel_err={err}"
        assert int(ovb) == int(ovf), (
            f"overflow units differ: buffer={int(ovb)} fused={int(ovf)}")

        hlo_b = buffer_fn.lower(x).compile().as_text()
        hlo_f = fused_fn.lower(x).compile().as_text()
        nb = capacity_buffer_count(hlo_b, E, capacity, d)
        nf = capacity_buffer_count(hlo_f, E, capacity, d)
        assert nb > 0, (
            f"buffer path shows no (E={E}, C={capacity}, d={d}) "
            "intermediate — the assertion target moved; update the bench")
        assert nf == 0, (
            f"REGRESSION: fused path materializes {nf} (E={E}, "
            f"C={capacity}, d={d}) capacity buffer(s) — the HBM round-trip "
            "the fused pipeline exists to eliminate is back")
        cb = hlo_analysis.analyze_hlo(hlo_b)
        cf = hlo_analysis.analyze_hlo(hlo_f)

        t_buf = time_fn(buffer_fn, x, iters=iters, warmup=1)
        t_fus = time_fn(fused_fn, x, iters=iters, warmup=1)
        t_res = time_fn(resident_fn, x, iters=iters, warmup=1)
        if T >= PREFILL_T:
            assert t_fus <= t_buf, (
                f"REGRESSION: streamed fused pipeline slower than buffer "
                f"path at prefill scale T={T}: fused={t_fus:.0f}us "
                f"buffer={t_buf:.0f}us")
        tag = f"moe_pipeline/T{T}_E{E}_cap{capacity}"
        rows.append((f"{tag}/buffer", t_buf,
                     f"hbm_bytes={cb.hbm_bytes:.0f} cap_bufs={nb}"))
        rows.append((f"{tag}/fused", t_fus,
                     f"hbm_bytes={cf.hbm_bytes:.0f} cap_bufs=0 "
                     f"rel_err={err:.2e}"))
        rows.append((f"{tag}/resident", t_res, "bit-exact vs fused"))
        results.append({
            "T": T, "E": E, "d": d, "f": cfg.d_expert,
            "K": cfg.top_k, "P": policy.partition_p, "capacity": capacity,
            "buffer_us": t_buf, "fused_us": t_fus, "resident_us": t_res,
            "streamed": True,
            "buffer_hbm_bytes": cb.hbm_bytes, "fused_hbm_bytes": cf.hbm_bytes,
            "buffer_capacity_buffers": nb, "fused_capacity_buffers": nf,
            "rel_err_vs_oracle": err, "overflow_pairs": int(ovb),
        })

    run_entry = {
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "host": {"backend": jax.default_backend(),
                 "devices": jax.device_count()},
        "smoke": smoke,
        "rows": results,
    }
    out_path = out_path or os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "BENCH_moe_pipeline.json")
    payload = {
        "bench": "moe_pipeline",
        "unit": "us_per_layer_forward",
        "note": "buffer path (gather_rows -> grouped_swiglu -> unpermute) "
                "vs single fused Pallas pipeline (fused_us = streamed "
                "kernel; resident_us = whole-array-resident variant, "
                "bit-exact vs streamed); capacity_buffers counts "
                "(E, capacity, d)-shaped HLO instructions (must be 0 on "
                "the fused path); interpret-mode timings on CPU",
        "runs": [],
    }
    if os.path.exists(out_path):
        try:
            with open(out_path) as f:
                old = json.load(f)
            if isinstance(old.get("runs"), list):
                payload["runs"] = old["runs"]
        except (json.JSONDecodeError, OSError):
            pass
    payload["runs"].append(run_entry)
    schema_errs = validate_pipeline_bench(payload)
    assert not schema_errs, (
        "refusing to write a malformed BENCH_moe_pipeline.json: "
        + "; ".join(schema_errs))
    with open(out_path, "w") as f:
        json.dump(payload, f, indent=2)
        f.write("\n")
    return rows


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="single tiny shape for CI (seconds)")
    ap.add_argument("--out", default=None, help="JSON output path")
    args = ap.parse_args()
    t0 = time.time()
    rows = run(smoke=args.smoke, out_path=args.out)
    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")
    print(f"# moe_pipeline bench done in {time.time() - t0:.1f}s")


if __name__ == "__main__":
    main()
