"""Benchmark harness — one module per paper table/figure.

Usage:  PYTHONPATH=src python -m benchmarks.run [--only fig9,table2]
Output: ``name,us_per_call,derived`` CSV rows.
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback

from repro.launch.compile_cache import enable_compile_cache

from .common import print_rows

BENCHES = [
    ("table1", "benchmarks.bench_table1_partition"),
    ("table2", "benchmarks.bench_table2_drop"),
    ("table3", "benchmarks.bench_table3_related"),
    ("fig9", "benchmarks.bench_fig9_setp"),
    ("fig10", "benchmarks.bench_fig10_speedup"),
    ("fig11", "benchmarks.bench_fig11_load_aware"),
    ("fig12", "benchmarks.bench_fig12_thresholds"),
    ("dispatch", "benchmarks.bench_dispatch"),
    ("importance", "benchmarks.bench_importance"),
    ("kernel_skip", "benchmarks.bench_kernel_skip"),
    ("roofline", "benchmarks.bench_roofline"),
]


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated bench keys to run")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None

    print("name,us_per_call,derived")
    failures = 0
    for key, modname in BENCHES:
        if only and key not in only:
            continue
        t0 = time.time()
        try:
            mod = __import__(modname, fromlist=["run"])
            rows = mod.run()
            print_rows(rows)
            print(f"# {key} done in {time.time() - t0:.1f}s", flush=True)
        except Exception:
            failures += 1
            print(f"# {key} FAILED:", file=sys.stderr)
            traceback.print_exc()
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
