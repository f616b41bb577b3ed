"""Seeded traffic, and the device guard of the entry point."""
from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np

from bench.traffic.generator import generate, lengths

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _mix(name):
    with open(os.path.join(ROOT, "bench", "traffic", name + ".json")) as fh:
        return json.load(fh)


def test_same_seed_same_inputs_and_every_seed_the_same_work():
    mix = _mix("chat-2t")
    big = 2**33 + 5
    a = generate(mix, big, 40, 1000, rate_per_s=1.5)
    b = generate(mix, big, 40, 1000, rate_per_s=1.5)
    c = generate(mix, 7, 40, 1000, rate_per_s=1.5)
    assert [x.arrival_s for x in a] == [x.arrival_s for x in b]
    assert all((x.prompt == y.prompt).all() for x, y in zip(a, b))
    assert len(a) == len(c) == 60
    # one schedule for every seed: the same sizes and gaps in one order
    for f in (lambda r: len(r.prompt), lambda r: r.n_out,
              lambda r: r.arrival_s):
        assert list(map(f, a)) == list(map(f, c))
    # the quantile grid in the schedule seed's shuffled order
    plen = np.array([len(r.prompt) for r in a])
    assert (np.sort(plen) == lengths(mix["prompt_len"], 60)).all()
    assert (plen != np.sort(plen)).any()
    # exponential gaps: mean 1/rate, spread as wide as the mean
    gaps = np.diff([0.0] + [r.arrival_s for r in a])
    assert abs(gaps.mean() * 1.5 - 1) < 0.05
    assert 0.8 < gaps.std() / gaps.mean() < 1.2
    assert a[-1].arrival_s < 40
    other = dict(mix, schedule_seed=mix["schedule_seed"] + 1)
    assert [len(r.prompt) for r in generate(other, big, 40, 1000,
                                            rate_per_s=1.5)] != plen.tolist()
    assert a[0].prompt.tolist() != c[0].prompt.tolist()
    assert generate(mix, big + 2**32, 40, 1000, rate_per_s=1.5)[0].prompt \
        .tolist() != a[0].prompt.tolist()


def test_lengths_follow_the_mix():
    spec = {"dist": "lognormal", "median": 1024, "sigma": 1.0, "min": 64,
            "max": 4096}
    v = lengths(spec, 1001)
    assert v.min() >= 64 and v.max() == 4096
    assert abs(np.median(v) - 1024) <= 2
    u = lengths({"dist": "uniform", "min": 512, "max": 2048}, 1000)
    assert u.min() == 512 and u.max() == 2048


def test_closed_loop_clients_share_the_queue():
    mix = _mix("decode-long")
    reqs = generate(mix, 3, 40, 1000)
    assert len(reqs) == mix["clients"] * mix["requests_per_client"]
    per = np.bincount([r.client for r in reqs])
    assert (per == mix["requests_per_client"]).all()


def test_no_chip_no_result():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "qwen3-30b-a3b.chat-2t", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=120)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr
