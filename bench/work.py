"""Operations and bytes the served work needs, from shapes and counts.

Counted is the work the routing asks for, never what today's kernels do:
attention over each sequence's real length (not the static read width),
expert products only for kept (sub-)pairs, the LM head only for tokens
whose logits are used, weight bytes only for the (sub-)experts some token
of a call is routed to. A faster kernel therefore reads a higher share,
and no share can pass 100%.

The counts that depend on the architecture (projections, attention,
experts, head) are each family's (``bench/families``: ``model_flops``,
``moe_needed``); the pieces here are shared.

A step record (one engine step, built by the benchmark while tracing):

    wall_s       host seconds of the step
    decode_keys  per decode token, the keys its attention reads
    chunk        None or (start, valid, final) of the prefill chunk
    live         (layers, sub-experts) bool: routed to in this step
"""
from __future__ import annotations

from typing import Dict, List


def chunk_keys(start: int, valid: int) -> float:
    """Keys read by the ``valid`` queries of a chunk starting at ``start``."""
    return valid * start + valid * (valid + 1) / 2.0


def step_tokens(rec: Dict) -> int:
    return len(rec["decode_keys"]) + (rec["chunk"][1] if rec["chunk"] else 0)


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
