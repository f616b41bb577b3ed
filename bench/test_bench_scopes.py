"""Device time by model scope, and the scheduler and model-layer readers:
on synthetic input, and on a small trace recorded on a TPU v5e with the
compiled programs' op_name maps (``bench/record_scope_fixture.py``:
Qwen3-30B-A3B's widths at 2 layers through ``PagedEngine``, three
requests on two slots)."""
from __future__ import annotations

import json
import os

import numpy as np
import pytest

from bench import run as R
from bench import scopes, xplane

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")

HLO = """HloModule jit_decode, is_scheduled=true, entry_computation_layout={(f32[2]{0})->f32[2]{0}}

%fused_computation (param_0: f32[2]) -> f32[2] {
  %param_0 = f32[2]{0} parameter(0)
  ROOT %tanh.1 = f32[2]{0} tanh(%param_0), metadata={op_name="jit(decode)/while/body/closed_call/attention/tanh"}
}

ENTRY %main.9 (p: f32[2]) -> f32[2] {
  %p = f32[2]{0} parameter(0), metadata={op_name="p"}
  %dynamic-slice_bitcast_fusion = f32[8,8]{1,0} fusion(%p), kind=kLoop, calls=%fc, metadata={op_name="jit(decode)/while/body/dynamic_slice" source_file="a.py" source_line=3}
  %fused_moe_pipeline.8 = f32[2]{0} custom-call(%p), custom_call_target="tpu_custom_call", metadata={op_name="jit(decode)/while/body/closed_call/moe/fused_moe_pipeline"}
  ROOT %fusion.2 = f32[2]{0} fusion(%p), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(decode)/while/body/closed_call/attention/tanh"}
}
"""


def test_op_names_reads_module_and_instructions():
    module, names = scopes.op_names(HLO)
    assert module == "jit_decode"
    assert names["dynamic-slice_bitcast_fusion"] == \
        "jit(decode)/while/body/dynamic_slice"
    assert names["fused_moe_pipeline.8"].endswith("/moe/fused_moe_pipeline")
    assert names["fusion.2"].endswith("/attention/tanh")
    assert names["p"] == "p"
    with pytest.raises(ValueError):
        scopes.op_names("not hlo")


@pytest.mark.parametrize("op_name,scope", [
    ("jit(decode)/while/body/closed_call/attention/dot_general",
     "attention"),
    ("jit(decode)/while/body/closed_call/moe/route/top_k", "moe"),
    ("jit(chunk_insert)/lm_head/argmax", "lm_head"),
    ("jit(decode)/embed/gather", "embed"),
    ("jit(decode)/while/body/dynamic_slice", "scan_copy"),
    ("jit(decode)/while/body/dynamic_update_slice", "scan_copy"),
    ("jit(decode)/while/body/squeeze", "scan_copy"),
    ("jit(decode)/while/body/closed_call/dynamic_slice", "unscoped"),
    ("jit(decode)/dynamic_slice", "unscoped"),
    ("jit(decode)/while/body/add", "unscoped"),
    ("", "unscoped"),
])
def test_scope_of(op_name, scope):
    assert scopes.scope_of(op_name) == scope


def test_device_by_scope_maps_ops_through_their_program():
    ops = [("%while.1 = (f32[2]) while(%t)", 0, 90),            # container
           ("%fusion.2 = f32[2]{0} fusion(%p)", 10, 30),       # attention
           ("%fused_moe_pipeline.8 = f32[2]{0} custom-call(%p)", 30, 60),
           ("%dynamic-slice_bitcast_fusion = f32[8,8] fusion(%p)", 60, 70),
           ("%copy.3 = f32[2]{0} copy(%p)", 70, 75),           # no op_name
           ("%fusion.2 = f32[2]{0} fusion(%p)", 210, 220),     # other program
           ("%fusion.2 = f32[2]{0} fusion(%p)", 400, 500)]     # outside
    tr = xplane.Trace(ops={"0": ops},
                      modules={"0": [("jit_decode(123)", 0, 100),
                                     ("jit_copy(7)", 200, 300)]},
                      spans=[])
    _, names = scopes.op_names(HLO)
    got = scopes.device_by_scope(tr, (0, 350), {"jit_decode": names})
    assert got["jit_decode"] == pytest.approx(
        {"attention": 20e-9, "moe": 30e-9, "scan_copy": 10e-9,
         "unnamed": 5e-9})
    assert got["jit_copy"] == pytest.approx({scopes.OTHER: 10e-9})
    assert scopes.totals(got)["attention"] == pytest.approx(20e-9)


def _ctx(**extra):
    cell = R.load_cell("qwen3-30b-a3b.chat-2t")
    ctx = R.Context(family=cell.family, s=cell.family.sizes(cell.config),
                    p=1, peaks={"bf16_flops": 1.0, "hbm_bytes_per_s": 1.0},
                    steps=[{}] * 4, counts=[0, 0, 0], programs={},
                    kernel=(0.0, 0), busy_s=0.0, window_s=0.0)
    for k, v in extra.items():
        setattr(ctx, k, v)
    return ctx


def _req(sub, start, first, read=10.0):
    return {"submitted_s": sub, "admitted_s": sub, "prefill_start_s": start,
            "first_token_s": first, "read_s": read}


REQUESTS = [_req(0.0, 0.5, 1.0), _req(1.0, 1.1, 3.1), _req(2.0, 4.0, 4.5),
            _req(3.0, 3.2, None), _req(9.0, None, None)]
WAITS = [0.5, 0.1, 2.0, 0.2, 1.0]          # the last never started: 10 - 9
PREFILLS = [0.5, 2.0, 0.5, 6.8]            # the fourth has no token yet


@pytest.mark.parametrize("metric,extra,want", [
    ("attention_ms.chat", {"scopes": {"attention": 0.008, "moe": 0.04}},
     2.0),
    ("moe_ms.chat", {"scopes": {"attention": 0.008, "moe": 0.04}}, 10.0),
    ("scan_copy_ms.chat", {"scopes": {"scan_copy": 0.1}}, 25.0),
    ("scan_copy_ms.chat", {"scopes": {"moe": 0.1}}, 0.0),
    ("queue_wait_p90_ms.chat", {"requests": REQUESTS},
     1e3 * np.percentile(WAITS, 90)),
    ("prefill_p90_ms.chat", {"requests": REQUESTS},
     1e3 * np.percentile(PREFILLS, 90)),
])
def test_reader_values(metric, extra, want):
    assert R.reader(metric)(_ctx(**extra)) == pytest.approx(want)


@pytest.fixture(scope="module")
def recorded():
    tr = xplane.load(os.path.join(FIXTURES, "engine_scopes.xplane.pb.gz"))
    with open(os.path.join(FIXTURES, "engine_scopes.op_names.json")) as fh:
        maps = json.load(fh)
    return tr, maps, xplane.loop_window(tr)


def test_recorded_decode_time_lies_under_model_scopes(recorded):
    tr, maps, win = recorded
    assert set(maps) == {"jit_decode", "jit_chunk_insert"}
    by = scopes.device_by_scope(tr, win, maps)
    for prog in maps:
        assert {"embed", "attention", "moe", "lm_head",
                "scan_copy"} <= set(by[prog])
    decode = by["jit_decode"]
    named = sum(v for k, v in decode.items()
                if k in scopes.SCOPES + (scopes.SCAN_COPY,))
    assert named >= 0.95 * sum(decode.values())


def test_recorded_idle_gaps_name_engine_phases(recorded):
    tr, _, win = recorded
    idle = xplane.idle_by_span(tr, win)
    phases = {k for k in idle if k.startswith("engine_")}
    assert {"engine_admit", "engine_prefill_chunk", "engine_emit"} & phases
    assert idle.get("bench_step", 0.0) < 0.1 * sum(idle.values())
