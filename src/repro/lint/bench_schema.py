"""Schema validation for the checked-in benchmark trajectory files.

``BENCH_dispatch.json`` / ``BENCH_serving_offline.json`` (flat, overwritten
per run) and ``BENCH_moe_pipeline.json`` (append-only ``runs`` trajectory)
are consumed by CI gates and the README tables; a malformed append silently
corrupts them. The bench scripts call these validators before writing, and
the lint runs them over the repo's checked-in copies.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, List

from .findings import Finding, Severity

# required keys and their types; numeric fields accept int or float
_NUM = (int, float)

HOST = {"backend": str, "devices": int}

DISPATCH_TOP = {"bench": str, "unit": str, "note": str, "host": dict,
                "smoke": bool, "rows": list}
DISPATCH_ROW = {"T": int, "E": int, "K": int, "d": int, "capacity": int,
                "major_frac": _NUM, "drop_frac": _NUM, "cumsum_us": _NUM,
                "sort_us": _NUM, "speedup": _NUM,
                "tile_skip_fraction": _NUM}

PIPELINE_TOP = {"bench": str, "unit": str, "note": str, "runs": list}
PIPELINE_RUN = {"timestamp": str, "host": dict, "smoke": bool,
                "rows": list}
PIPELINE_ROW = {"T": int, "E": int, "d": int, "f": int, "K": int, "P": int,
                "capacity": int, "buffer_us": _NUM, "fused_us": _NUM,
                "buffer_hbm_bytes": _NUM, "fused_hbm_bytes": _NUM,
                "buffer_capacity_buffers": int, "fused_capacity_buffers": int,
                "rel_err_vs_oracle": _NUM, "overflow_pairs": int}
# added by the streamed-kernel PR; optional so pre-existing trajectory runs
# stay valid. fused_us is the STREAMED kernel from that PR on; resident_us
# is the whole-array-resident variant it replaced.
PIPELINE_ROW_OPTIONAL = {"resident_us": _NUM, "streamed": bool}


SERVING_TOP = {"bench": str, "unit": str, "note": str, "host": dict,
               "smoke": bool, "engines": list, "prefix_sweep": list}
SERVING_ENGINE_ROW = {"engine": str, "requests": int, "tokens": int,
                      "throughput_tok_s": _NUM, "wall_s": _NUM,
                      "compile_s": _NUM, "steady_step_s": _NUM}
SERVING_SWEEP_ROW = {"shared_prefix_frac": _NUM, "hit_rate": _NUM,
                     "throughput_tok_s": _NUM, "chunk_steps": int,
                     "prefill_tokens": int}


def _check_keys(obj: Dict, schema: Dict, where: str,
                optional: Dict = None) -> List[str]:
    errs = []
    if not isinstance(obj, dict):
        return [f"{where}: expected an object, got {type(obj).__name__}"]
    items = list(schema.items()) + [
        (k, t) for k, t in (optional or {}).items() if k in obj]
    for key, typ in items:
        if key not in obj:
            errs.append(f"{where}: missing key {key!r}")
        elif typ is int and isinstance(obj[key], bool):
            errs.append(f"{where}: {key!r} is a bool, expected int")
        elif not isinstance(obj[key], typ):
            want = typ[0].__name__ if isinstance(typ, tuple) \
                else typ.__name__
            errs.append(f"{where}: {key!r} is "
                        f"{type(obj[key]).__name__}, expected {want}")
    return errs


def validate_dispatch_bench(doc: Dict) -> List[str]:
    """Errors in a BENCH_dispatch.json document (empty list == valid)."""
    errs = _check_keys(doc, DISPATCH_TOP, "top-level")
    if isinstance(doc.get("host"), dict):
        errs += _check_keys(doc["host"], HOST, "host")
    for i, row in enumerate(doc.get("rows", []) or []):
        errs += _check_keys(row, DISPATCH_ROW, f"rows[{i}]")
    return errs


def validate_pipeline_bench(doc: Dict) -> List[str]:
    """Errors in a BENCH_moe_pipeline.json document (append-only runs)."""
    errs = _check_keys(doc, PIPELINE_TOP, "top-level")
    for i, run in enumerate(doc.get("runs", []) or []):
        errs += _check_keys(run, PIPELINE_RUN, f"runs[{i}]")
        if not isinstance(run, dict):
            continue
        if isinstance(run.get("host"), dict):
            errs += _check_keys(run["host"], HOST, f"runs[{i}].host")
        for j, row in enumerate(run.get("rows", []) or []):
            errs += _check_keys(row, PIPELINE_ROW, f"runs[{i}].rows[{j}]",
                                optional=PIPELINE_ROW_OPTIONAL)
    return errs


def validate_serving_bench(doc: Dict) -> List[str]:
    """Errors in a BENCH_serving_offline.json document (flat, overwritten).
    ``engines`` must cover both KV layouts; ``prefix_sweep`` rows carry the
    paged engine's hit-rate/throughput curve."""
    errs = _check_keys(doc, SERVING_TOP, "top-level")
    if isinstance(doc.get("host"), dict):
        errs += _check_keys(doc["host"], HOST, "host")
    names = set()
    for i, row in enumerate(doc.get("engines", []) or []):
        errs += _check_keys(row, SERVING_ENGINE_ROW, f"engines[{i}]")
        if isinstance(row, dict):
            names.add(row.get("engine"))
    if doc.get("engines") and not {"contiguous", "paged"} <= names:
        errs.append("engines: must include both 'contiguous' and 'paged' "
                    f"rows (got {sorted(n for n in names if n)})")
    for i, row in enumerate(doc.get("prefix_sweep", []) or []):
        errs += _check_keys(row, SERVING_SWEEP_ROW, f"prefix_sweep[{i}]")
        if isinstance(row, dict) and isinstance(row.get("hit_rate"), _NUM) \
                and not 0.0 <= row["hit_rate"] <= 1.0:
            errs.append(f"prefix_sweep[{i}]: hit_rate "
                        f"{row['hit_rate']} outside [0, 1]")
    return errs


_VALIDATORS = {
    "BENCH_dispatch.json": validate_dispatch_bench,
    "BENCH_moe_pipeline.json": validate_pipeline_bench,
    "BENCH_serving_offline.json": validate_serving_bench,
}


def check_bench_files(repo_root) -> List[Finding]:
    """Lint pass over the repo's checked-in bench files. Absent files are
    fine (fresh clone before any bench run); malformed ones ERROR."""
    out: List[Finding] = []
    root = Path(repo_root)
    for name, validate in _VALIDATORS.items():
        path = root / name
        entry = f"bench/{name}"
        if not path.exists():
            continue
        try:
            doc = json.loads(path.read_text())
        except json.JSONDecodeError as e:
            out.append(Finding("bench-schema", "invalid-json",
                               Severity.ERROR, entry, f"unparseable: {e}"))
            continue
        for err in validate(doc):
            out.append(Finding(
                "bench-schema", "schema", Severity.ERROR, entry, err,
                "the bench script should have refused this append — fix "
                "the writer, not just the file"))
    return out
