#!/usr/bin/env python3
"""Find the knee of an open-loop cell: the highest arrival rate it
sustains.

    python bench/sweep.py --workload <cell> --seed <n> --rates 1,2,3 [--seconds S]

One set-up, then one window per rate, in order, with the engine drained
between them. Per rate: time to first token (median, p90, and p90 of the
first and second half of the arrivals), p95 gap between tokens, tokens
per second completed, and the requests still queued when the window
closed. Past the knee the queue grows through the window, so the second
half's p90 runs away from the first's. A cell's fixed rate is chosen once
from this, at about four fifths of the knee, and written into
``bench/cells/<cell>.json``; the benchmark never searches for a rate.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import run as R  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    args = ap.parse_args()
    cell = R.load_cell(args.workload)
    R.require_chips(cell.chips)
    R.enable_compile_cache()
    from bench import system
    from bench.traffic.generator import generate
    from bench.traffic.loops import Loop

    b = R.build(cell, args.seed)
    for rate in (float(r) for r in args.rates.split(",")):
        reqs = generate(cell.mix, args.seed, args.seconds, b.s["vocab"],
                        rate_per_s=rate)
        win = Loop(b.eng, system.gen_for).run_open(
            reqs, args.seconds, cell.mix["grace_s"])
        queued = b.eng.queued
        arrived = sorted(win.in_window(), key=lambda s: s.due_s)
        ttft = np.array([(s.stamps[0] if s.stamps else win.end_s) - s.due_s
                         for s in arrived])
        half = len(ttft) // 2
        gaps = [y - x for s in win.served for x, y in zip(s.stamps, s.stamps[1:])
                if y <= win.seconds]
        print(json.dumps({
            "rate_per_s": rate, "arrived": len(arrived),
            "queued_at_close": queued,
            "ttft_p50_ms": 1e3 * float(np.percentile(ttft, 50)),
            "ttft_p90_ms": 1e3 * float(np.percentile(ttft, 90)),
            "ttft_p90_first_half_ms": 1e3 * float(np.percentile(ttft[:half], 90)),
            "ttft_p90_second_half_ms": 1e3 * float(np.percentile(ttft[half:], 90)),
            "itl_p95_ms": 1e3 * float(np.percentile(gaps, 95)),
            "out_tok_s": win.tokens_in_window() / win.seconds,
            "late_ms_max": 1e3 * max(win.late_s)}), flush=True)
        b.eng.drain()


if __name__ == "__main__":
    main()
