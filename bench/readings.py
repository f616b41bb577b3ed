#!/usr/bin/env python3
"""Readings that a cell's correctness limits are set from.

    python bench/readings.py --workload <cell> --seeds 101,102,... [--seconds S]

For every seed, in one process: a full run of the cell (set-up, the
window, the check against the float32 reference), and on the same sample
of served tokens the control, the reference computed in float8 (e4m3):
what a change to that precision would read. The control goes through the
same check, against the cell's limits, and has to come out not correct.
One JSON line per seed with both sides' gap statistics (``bench/run.py``
``GAP_STATS``) and verdicts, then per statistic the lower reading (the
largest the program gave), the upper reading (the smallest the control
gave) and their ratio, and whether every program run was correct and
every control run not. The benchmark's own runs never run the control.
Needs the cell's chips.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import run as R  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=None,
                    help="window per seed (default: BENCHMARK.json's)")
    args = ap.parse_args()
    cell = R.load_cell(args.workload)
    devices = R.require_chips(cell.chips)
    R.enable_compile_cache()
    with open(os.path.join(R.ROOT, "BENCHMARK.json")) as fh:
        seconds = args.seconds or json.load(fh)["run_seconds"]
    prog, ctrl, verdicts = [], [], []
    for seed in (int(x) for x in args.seeds.split(",")):
        res = R.run(cell, seed, seconds, False, devices, control=True,
                    t_start=time.perf_counter(),
                    log=lambda *a, **k: print(*a, **k, flush=True))
        prog.append(res["program_gaps"])
        ctrl.append(res["control_gaps"])
        print(json.dumps({"seed": seed, "program": res["program_gaps"],
                          "control": res["control_gaps"],
                          "tokens": res["served_tokens"],
                          "correct": res["correct"],
                          "control_correct": res["control_correct"],
                          "metrics": {k: v["value"] for k, v in
                                      res["metrics"].items()}}), flush=True)
        verdicts.append((res["correct"], res["control_correct"]))
        gc.collect()
    summary = {"workload": cell.name, "seeds": args.seeds}
    for name in R.GAP_STATS:
        lower = max(p[name] for p in prog)
        upper = min(c[name] for c in ctrl)
        summary[name] = {"lower": lower, "upper": upper,
                         "ratio": upper / lower if lower else None}
    summary["program_correct"] = all(p for p, _ in verdicts)
    summary["control_not_correct"] = not any(c for _, c in verdicts)
    print(json.dumps(summary))


if __name__ == "__main__":
    main()
