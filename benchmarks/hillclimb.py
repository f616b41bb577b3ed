"""Perf-iteration harness: recompile one (arch x shape) with experimental
overrides and print the roofline terms + collective breakdown.

    PYTHONPATH=src python -m benchmarks.hillclimb --arch qwen3-moe-30b-a3b \
        --shape train_4k [--no-remat] [--moe-impl gspmd] ...

Each run = one hypothesis->change->measure cycle for EXPERIMENTS.md §Perf.
"""
import os
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=512")
os.environ["JAX_PLATFORMS"] = "cpu"     # placeholder devices, never a chip

import argparse
import dataclasses
import json
import time

import jax

from repro.configs import INPUT_SHAPES, get_config
from repro.launch import mesh as mesh_mod
from repro.launch.hlo_analysis import analyze_hlo, roofline_terms
from repro.launch import dryrun as dr


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--remat-policy", default=None)
    ap.add_argument("--moe-impl", default=None, choices=["setp", "gspmd"])
    ap.add_argument("--no-dualsparse", action="store_true")
    ap.add_argument("--dump-hlo", default=None)
    ap.add_argument("--label", default="exp")
    args = ap.parse_args()

    cfg = get_config(args.arch)
    shape = INPUT_SHAPES[args.shape]
    mesh = mesh_mod.make_production_mesh(multi_pod=False)

    # monkey-patch build_dist with overrides
    orig = dr.build_dist

    def patched(cfg_, kind, mesh_):
        d = orig(cfg_, kind, mesh_)
        kw = {}
        if args.no_remat:
            kw["remat"] = False
        if args.remat_policy:
            kw["remat_policy"] = args.remat_policy
        if args.moe_impl:
            kw["moe_impl"] = args.moe_impl
        if args.no_dualsparse:
            kw["dualsparse"] = False
            kw["load_aware"] = False
        return dataclasses.replace(d, **kw) if kw else d

    dr.build_dist = patched
    t0 = time.time()
    a, sh, step = dr.abstract_state(cfg, shape, mesh, cfg.dualsparse.enabled)
    jitted = jax.jit(step, in_shardings=sh)
    with jax.set_mesh(mesh):
        comp = jitted.lower(*a).compile()
    c = analyze_hlo(comp.as_text())
    try:
        ma = comp.memory_analysis()
        temp = ma.temp_size_in_bytes
        if shape.kind != "train":
            # remove the CPU FloatNormalization f32-weight-copy artifact
            temp = max(temp - 2 * dr._per_device_param_bytes(a[0], sh[0]), 0)
        traffic = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                   + 2 * temp)
    except Exception:
        traffic, temp = 0, 0
    rt = roofline_terms(c.flops, traffic, c.collective_bytes, 1,
                        peak_flops=mesh_mod.PEAK_FLOPS_BF16,
                        hbm_bw=mesh_mod.HBM_BW, ici_bw=mesh_mod.ICI_BW)
    if args.dump_hlo:
        open(args.dump_hlo, "w").write(comp.as_text())
    print(json.dumps({
        "label": args.label, "arch": args.arch, "shape": args.shape,
        "compile_s": round(time.time() - t0, 1),
        "flops": c.flops, "hbm_traffic": traffic, "temp_bytes": temp,
        "coll_bytes": c.collective_bytes,
        "by_kind": c.bytes_by_kind, "count_by_kind": c.count_by_kind,
        "roofline": rt,
    }, indent=1))


if __name__ == "__main__":
    main()
