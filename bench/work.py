"""Operations and bytes the served work needs, from shapes and counts.

Counted is the work the routing asks for, never what today's kernels do:
attention over each sequence's real length (not the static read width),
expert products only for kept (sub-)pairs, the LM head only for tokens
whose logits are used, weight bytes only for the (sub-)experts some token
of a call is routed to. A faster kernel therefore reads a higher share,
and no share can pass 100%.

A step record (one engine step, built by the benchmark while tracing):

    wall_s       host seconds of the step
    decode_keys  per decode token, the keys its attention reads
    chunk        None or (start, valid, final) of the prefill chunk
    live         (layers, sub-experts) bool: routed to in this step
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np


def proj_flops(s: Dict) -> float:
    """Q/K/V/O projections, one token, one layer."""
    return 2.0 * s["d"] * s["hd"] * (2 * s["hq"] + 2 * s["hkv"])


def core_flops(s: Dict, keys: float) -> float:
    """QK^T and PV of one query over ``keys`` keys, one layer."""
    return 4.0 * s["hq"] * s["hd"] * keys


def router_flops(s: Dict) -> float:
    return 2.0 * s["d"] * s["experts"]


def moe_flops(s: Dict, kept_share: float) -> float:
    """Expert SwiGLU products of one token, one layer, when ``kept_share``
    of its sub-pairs are kept (1 without dropping)."""
    return s["top_k"] * kept_share * 6.0 * s["d"] * s["f"]


def head_flops(s: Dict) -> float:
    return 2.0 * s["d"] * s["vocab"]


def sub_expert_bytes(s: Dict, p: int) -> float:
    """bf16 bytes of one (sub-)expert's three matrices."""
    return 3.0 * s["d"] * (s["f"] // p) * 2


def chunk_keys(start: int, valid: int) -> float:
    """Keys read by the ``valid`` queries of a chunk starting at ``start``."""
    return valid * start + valid * (valid + 1) / 2.0


def step_tokens(rec: Dict) -> int:
    return len(rec["decode_keys"]) + (rec["chunk"][1] if rec["chunk"] else 0)


def model_flops(s: Dict, steps: Sequence[Dict], kept_share: float) -> float:
    """Needed model operations of ``steps``."""
    L = s["layers"]
    per_token = proj_flops(s) + router_flops(s) + moe_flops(s, kept_share)
    total = 0.0
    for rec in steps:
        keys = float(sum(rec["decode_keys"]))
        heads = len(rec["decode_keys"])
        if rec["chunk"]:
            start, valid, final = rec["chunk"]
            keys += chunk_keys(start, valid)
            heads += int(final)
        total += L * (step_tokens(rec) * per_token + core_flops(s, keys))
        total += heads * head_flops(s)
    return total


def moe_needed(s: Dict, p: int, steps: Sequence[Dict],
               kept_share: float) -> Dict[str, float]:
    """Operations and bytes the MoE layers of ``steps`` need: the kept
    sub-pairs' products; each step's live (sub-)experts' weights (read
    once for the step's calls together, a lower bound), and every token's
    row in and out."""
    flops = sum(step_tokens(r) for r in steps) * s["layers"] \
        * moe_flops(s, kept_share)
    weights = sum(float(np.sum(r["live"])) for r in steps) \
        * sub_expert_bytes(s, p)
    rows = sum(step_tokens(r) for r in steps) * s["layers"] * s["d"] * 2 * 2
    return {"flops": flops, "bytes": weights + rows}


def roofline_share(flops: float, nbytes: float, seconds: float,
                   peaks: Dict) -> float:
    """Least time the chip could take over ``seconds``, in percent."""
    least = max(flops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds


def kept_share(counts: List[int]) -> float:
    """Kept sub-pairs over all sub-pairs, from (kept_full, kept_major,
    dropped)."""
    kf, km, dr = counts
    total = kf + km + dr
    return (kf + km) / total if total else 1.0
