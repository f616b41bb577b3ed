"""Reduction of a JAX profiler trace (``.xplane.pb``) to what the
per-layer metrics read.

On a TPU the trace holds one plane per chip (``/device:TPU:<n>``) whose
``XLA Ops`` line has one event per operation run on the chip (its name is
the HLO instruction's text) and whose ``XLA Modules`` line has one event
per program run (``jit_<function>(<fingerprint>)``); and a host plane
(``/host:CPU``) whose ``python`` line holds the profiler annotations the
benchmark and the engine write (``bench_*``, ``engine_*``). All events
share one clock.
"""
from __future__ import annotations

import collections
import dataclasses
import gzip
import re
from typing import Dict, List, Tuple

Interval = Tuple[int, int]          # [start_ns, end_ns)

PALLAS = "tpu_custom_call"          # HLO custom-call target of a Pallas kernel
SPAN_PREFIXES = ("bench_", "engine_")


@dataclasses.dataclass
class Trace:
    ops: Dict[str, List[Tuple[str, int, int]]]       # chip -> (name, t0, t1)
    modules: Dict[str, List[Tuple[str, int, int]]]   # chip -> (name, t0, t1)
    spans: List[Tuple[str, int, int]]                # host annotations


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as fh:
            pd = ProfileData.from_serialized_xspace(fh.read())
    else:
        pd = ProfileData.from_file(path)
    ops, modules, spans = {}, {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            chip = plane.name.split(":")[-1]
            for line in plane.lines:
                if line.name in ("XLA Ops", "XLA Modules"):
                    evs = [(e.name, int(e.start_ns),
                            int(e.start_ns + e.duration_ns))
                           for e in line.events]
                    (ops if line.name == "XLA Ops" else modules)[chip] = evs
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIXES):
                        spans.append((e.name, int(e.start_ns),
                                      int(e.start_ns + e.duration_ns)))
    return Trace(ops, modules, spans)


def loop_window(tr: Trace) -> Interval:
    """From the first to the last of the benchmark loop's own spans."""
    b = [(t0, t1) for n, t0, t1 in tr.spans if n.startswith("bench_")]
    if not b:
        raise ValueError("trace holds no bench_* span")
    return min(t0 for t0, _ in b), max(t1 for _, t1 in b)


def union(intervals, window: Interval) -> List[Interval]:
    """Disjoint sorted union of ``intervals`` clipped to ``window``."""
    w0, w1 = window
    out: List[Interval] = []
    for t0, t1 in sorted((max(a, w0), min(b, w1)) for a, b in intervals):
        if t1 <= t0:
            continue
        if out and t0 <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], t1))
        else:
            out.append((t0, t1))
    return out


def busy_ns(tr: Trace, window: Interval) -> float:
    """Nanoseconds in which some operation ran, averaged over the chips."""
    if not tr.ops:
        return 0.0
    per = [sum(b - a for a, b in union([(t0, t1) for _, t0, t1 in evs],
                                       window))
           for evs in tr.ops.values()]
    return sum(per) / len(per)


def gaps(tr: Trace, window: Interval) -> List[Interval]:
    """Idle intervals of the first chip inside ``window``."""
    chip = sorted(tr.ops)[0]
    busy = union([(t0, t1) for _, t0, t1 in tr.ops[chip]], window)
    out, at = [], window[0]
    for t0, t1 in busy:
        if t0 > at:
            out.append((at, t0))
        at = max(at, t1)
    if at < window[1]:
        out.append((at, window[1]))
    return out


def span_at(tr: Trace, t: int) -> str:
    """The innermost host annotation open at time ``t``."""
    best = None
    for name, t0, t1 in tr.spans:
        if t0 <= t < t1 and (best is None or t1 - t0 < best[1]):
            best = (name, t1 - t0)
    return best[0] if best else "no annotation"


SHORT_GAP_NS = 10_000


def idle_by_span(tr: Trace, window: Interval) -> Dict[str, float]:
    """Idle seconds of the chip, by what the host was doing mid-gap; gaps
    under 10 us (between the operations of one program) are pooled."""
    out: Dict[str, float] = collections.defaultdict(float)
    for t0, t1 in gaps(tr, window):
        label = ("gaps under 10us" if t1 - t0 < SHORT_GAP_NS
                 else span_at(tr, (t0 + t1) // 2))
        out[label] += (t1 - t0) * 1e-9
    return dict(out)


_FINGERPRINT = re.compile(r"\(\d+\)$")


def programs(tr: Trace, window: Interval) -> Dict[str, Tuple[float, int]]:
    """Per program (``jit_decode`` ...): (device seconds, calls), first
    chip, for runs that start inside ``window``."""
    chip = sorted(tr.modules)[0]
    out: Dict[str, List] = collections.defaultdict(lambda: [0.0, 0])
    for name, t0, t1 in tr.modules[chip]:
        if window[0] <= t0 < window[1]:
            acc = out[_FINGERPRINT.sub("", name)]
            acc[0] += (t1 - t0) * 1e-9
            acc[1] += 1
    return {k: (v[0], v[1]) for k, v in out.items()}


def op_name(text: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``; a Pallas
    kernel's custom call is tagged with its target."""
    head = text.split(" = ", 1)[0].lstrip("%")
    return f"{head} [{PALLAS}]" if PALLAS in text else head


def kernel_ns(tr: Trace, window: Interval) -> Tuple[float, int]:
    """(device ns, calls) of Pallas kernels (custom calls targeting
    ``tpu_custom_call``) starting inside ``window``, first chip."""
    chip = sorted(tr.ops)[0]
    ns, n = 0.0, 0
    for name, t0, t1 in tr.ops[chip]:
        if PALLAS in name and window[0] <= t0 < window[1]:
            ns += t1 - t0
            n += 1
    return ns, n


CONTAINERS = ("while", "conditional", "call")


def top_ops(tr: Trace, window: Interval, k: int = 10):
    """The ``k`` operations that took the most device time. Loops and
    calls are left out: their events span the operations inside them."""
    chip = sorted(tr.ops)[0]
    acc: Dict[str, float] = collections.defaultdict(float)
    for name, t0, t1 in tr.ops[chip]:
        head = op_name(name)
        if head.split(".")[0] in CONTAINERS:
            continue
        if window[0] <= t0 < window[1]:
            acc[head] += (t1 - t0) * 1e-9
    return sorted(acc.items(), key=lambda kv: -kv[1])[:k]
