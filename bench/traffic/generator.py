"""Seeded request generation from a traffic mix's data file.

Every seed gets the same schedule: the same prompt lengths, answer lengths
and inter-arrival gaps, in the same order. Each is read off a fixed
quantile grid of the mix's distribution, so a run holds them in their
proportions, and shuffled by a permutation drawn from the mix's own
``schedule_seed``, so arrivals cluster as independent exponential gaps
do: the schedule is one draw of a Poisson process, the same in every run.
Only the token ids (and the weights) come from the run's seed. A schedule
drawn from the run's seed was tried first: at 0.8 of the knee the queue a
chat cell builds depends on which long prompts arrive together, and the
time to first token then spread by 19% between seeds against 3% between
two runs of one seed. With one schedule the spread between seeds is the
system's.
"""
from __future__ import annotations

import dataclasses
import math
import statistics
from typing import Dict, List

import numpy as np


@dataclasses.dataclass
class Request:
    arrival_s: float          # scheduled send time (open loop); 0 = on demand
    prompt: np.ndarray        # int32 token ids
    n_out: int                # tokens to generate
    client: int = 0           # closed loop: the client that sends it


def _grid(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(spec: Dict, n: int) -> np.ndarray:
    """n lengths on the quantile grid of ``spec`` (sorted ascending)."""
    q = _grid(n)
    if spec["dist"] == "lognormal":
        z = np.array([statistics.NormalDist().inv_cdf(x) for x in q])
        vals = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    elif spec["dist"] == "uniform":
        vals = spec["min"] + q * (spec["max"] - spec["min"] + 1)
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.floor(vals), spec["min"], spec["max"]).astype(np.int64)


def poisson_gaps(rate: float, n: int) -> np.ndarray:
    """n exponential inter-arrival gaps (mean 1/rate) on the quantile grid."""
    return -np.log1p(-_grid(n)) / rate


def rng(seed: int, stream: int) -> np.random.Generator:
    """Independent numpy generator number ``stream`` of ``seed``."""
    return np.random.default_rng(np.random.SeedSequence(seed,
                                                        spawn_key=(stream,)))


# one permutation stream of the schedule seed per shuffled quantity
STREAMS = {"prompt": 0, "output": 1, "gap": 2}


def shuffled(values: np.ndarray, schedule_seed: int, what: str) -> np.ndarray:
    """``values`` in the order of the schedule's permutation for ``what``."""
    return rng(schedule_seed, STREAMS[what]).permutation(values)


def generate(mix: Dict, seed: int, seconds: float, vocab: int, *,
             rate_per_s: float = 0.0) -> List[Request]:
    """The requests of one run: open loop, ``rate_per_s * seconds``
    arrivals scheduled inside the window; closed loop, a queue of
    ``requests_per_client`` per client (clients take them in order)."""
    if mix["loop"] == "open":
        if rate_per_s <= 0:
            raise ValueError("an open-loop mix needs the cell's rate_per_s")
        n = int(rate_per_s * seconds)
    elif mix["loop"] == "closed":
        n = mix["clients"] * mix["requests_per_client"]
    else:
        raise ValueError(f"unknown loop {mix['loop']!r}")
    if n < 1:
        raise ValueError("the mix yields no request in this window")
    sched = mix["schedule_seed"]
    plen = shuffled(lengths(mix["prompt_len"], n), sched, "prompt")
    nout = shuffled(lengths(mix["output_len"], n), sched, "output")
    toks = rng(seed, 1)
    prompts = [toks.integers(0, vocab, int(p)).astype(np.int32) for p in plen]
    if mix["loop"] == "open":
        arrivals = np.cumsum(shuffled(poisson_gaps(rate_per_s, n), sched,
                                      "gap"))
        return [Request(float(a), p, int(o))
                for a, p, o in zip(arrivals, prompts, nout)]
    c = mix["clients"]
    return [Request(0.0, p, int(o), client=i % c)
            for i, (p, o) in enumerate(zip(prompts, nout))]
