"""Device time by the model's named scopes, and the program's per-request
lifecycle stamps: what the scheduler and model-layer readers read.

The served step names its parts with ``jax.named_scope`` (``embed``,
``attention``, ``moe``, ``lm_head``); each compiled instruction carries
the scope path in ``metadata={op_name=...}`` of the program's optimized
HLO (``PagedEngine.decode_hlo()`` / ``chunk_hlo()``). The profiler's
``XLA Ops`` events carry only the instruction's text, so an op is mapped
to its scope through the ``(program, instruction) -> op_name`` map of the
program whose ``XLA Modules`` interval holds it. The layer scan's own
per-layer slices of its stacked inputs and updates of its stacked outputs
lie directly in the loop body, under no model scope
(``.../while/body/dynamic_slice``, ``squeeze``, ``dynamic_update_slice``;
a fused slice takes the name of its ``squeeze``): ``scan_copy``. An
instruction XLA inserted (a copy to an aliased output, say) carries no
op_name: ``unnamed``.
"""
from __future__ import annotations

import bisect
import collections
import re
from typing import Dict, List, Optional, Tuple

import numpy as np

from bench import xplane

SCOPES = ("embed", "attention", "moe", "lm_head")
SCAN_COPY = "scan_copy"
SCAN_OPS = ("dynamic_slice", "squeeze", "dynamic_update_slice")
UNSCOPED = "unscoped"              # a named op outside every model scope
UNNAMED = "unnamed"                # no op_name: inserted by XLA
OTHER = "other programs"           # an op of a program with no map

_HEADER = re.compile(r"^HloModule (\S+?),")
_INSTR = re.compile(r'^\s*(?:ROOT )?%?(\S+) = .*metadata=\{op_name="([^"]*)"')


def op_names(hlo_text: str) -> Tuple[str, Dict[str, str]]:
    """(module name, instruction -> op_name) of an optimized HLO text."""
    lines = hlo_text.splitlines()
    m = _HEADER.match(lines[0]) if lines else None
    if m is None:
        raise ValueError("not an HLO module text")
    names = {}
    for line in lines[1:]:
        hit = _INSTR.match(line)
        if hit:
            names[hit.group(1)] = hit.group(2)
    return m.group(1), names


def scope_of(op_name: str) -> str:
    """The outermost model scope on an op_name path, else ``scan_copy``
    for the layer scan's own slices and updates, else ``unscoped``."""
    parts = op_name.split("/")
    for p in parts:
        if p in SCOPES:
            return p
    if parts[-3:-1] == ["while", "body"] and parts[-1] in SCAN_OPS:
        return SCAN_COPY
    return UNSCOPED


def device_by_scope(tr: xplane.Trace, window: xplane.Interval,
                    maps: Dict[str, Dict[str, str]]
                    ) -> Dict[str, Dict[str, float]]:
    """Device seconds per program and scope of the ops that start inside
    ``window`` on the first chip; ``maps`` is program -> instruction ->
    op_name. Loops and calls are left out, as in ``xplane.top_ops``."""
    chip = sorted(tr.ops)[0]
    mods = sorted((t0, t1, xplane._FINGERPRINT.sub("", n))
                  for n, t0, t1 in tr.modules.get(chip, []))
    starts = [m[0] for m in mods]
    out: Dict[str, Dict[str, float]] = collections.defaultdict(
        lambda: collections.defaultdict(float))
    for text, t0, t1 in tr.ops[chip]:
        if not window[0] <= t0 < window[1]:
            continue
        head = text.split(" = ", 1)[0].lstrip("%")
        if head.split(".")[0] in xplane.CONTAINERS:
            continue
        i = bisect.bisect_right(starts, t0) - 1
        prog = mods[i][2] if i >= 0 and t0 < mods[i][1] else OTHER
        names = maps.get(prog)
        if names is None:
            scope = OTHER
        elif head not in names:
            scope = UNNAMED
        else:
            scope = scope_of(names[head])
        out[prog][scope] += (t1 - t0) * 1e-9
    return {p: dict(v) for p, v in out.items()}


def totals(by_program: Dict[str, Dict[str, float]]) -> Dict[str, float]:
    """Seconds per scope, summed over programs."""
    out: Dict[str, float] = collections.defaultdict(float)
    for by_scope in by_program.values():
        for scope, secs in by_scope.items():
            out[scope] += secs
    return dict(out)


def request_stamps(engine, uids) -> List[Dict[str, Optional[float]]]:
    """The engine's lifecycle stamps of requests ``uids`` (engine clock),
    each with ``read_s``: the engine clock when they were read."""
    now = engine._now()
    out = []
    for uid in uids:
        r = engine.result(uid)
        out.append({"submitted_s": r.submitted_s, "admitted_s": r.admitted_s,
                    "prefill_start_s": r.prefill_start_s,
                    "first_token_s": r.first_token_s, "read_s": now})
    return out


# -- what the readers share ---------------------------------------------------

def ms_per_step(ctx, scope: str):
    """Device milliseconds per traced engine step under ``scope``. The
    trace's scope seconds are ``ctx.scopes`` (absent: nothing to read)."""
    secs = getattr(ctx, "scopes", None)
    if not secs or not ctx.steps:
        return None
    return 1e3 * secs.get(scope, 0.0) / len(ctx.steps)


def _p90_ms(values: List[float]):
    return 1e3 * float(np.percentile(values, 90)) if values else None


def queue_wait_p90_ms(ctx):
    """p90 over the window's requests (``ctx.requests``) of submission to
    the first prefill dispatch; a request never started counts the time it
    waited."""
    reqs = getattr(ctx, "requests", None) or []
    return _p90_ms([(r["prefill_start_s"] if r["prefill_start_s"] is not None
                     else r["read_s"]) - r["submitted_s"] for r in reqs])


def prefill_p90_ms(ctx):
    """p90 over the window's started requests of the first prefill
    dispatch to the first token; one without a token counts the time so
    far."""
    reqs = getattr(ctx, "requests", None) or []
    return _p90_ms([(r["first_token_s"] if r["first_token_s"] is not None
                     else r["read_s"]) - r["prefill_start_s"]
                    for r in reqs if r["prefill_start_s"] is not None])
