"""A tiny cell for the CPU tests: the Qwen3-MoE layout at toy widths, a
short open-loop mix, and a run of the whole harness on it."""
from __future__ import annotations

import os
import sys

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

TINY = {
    "source": "toy widths for tests", "hidden_size": 64,
    "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "moe_intermediate_size": 32,
    "num_experts": 4, "num_experts_per_tok": 2, "norm_topk_prob": True,
    "vocab_size": 128, "rope_theta": 10000.0, "rms_norm_eps": 1e-6,
    "dualsparse": {"partition_p": 2, "importance": "abs_gate",
                   "t_drop": 0.3, "t_major": 0.29, "t_minor": 0.31,
                   "t_max": 0.12},
}

# the Mixtral layout at toy widths: 8 coarse experts, top-2, 4 query heads
# per KV head
TINY_COARSE = {
    "source": "toy widths for tests", "hidden_size": 128,
    "num_hidden_layers": 2, "num_attention_heads": 8,
    "num_key_value_heads": 2, "intermediate_size": 96,
    "num_local_experts": 8, "num_experts_per_tok": 2, "vocab_size": 128,
    "rope_theta": 1e6, "rms_norm_eps": 1e-5,
    "dualsparse": TINY["dualsparse"],
}


def tiny_cell(policy: str = "none", loop: str = "open",
              widest_gap: float = 1.0):
    from bench import run as R
    mix = {
        "schedule_seed": 1, "loop": loop,
        "prompt_len": {"dist": "lognormal", "median": 24, "sigma": 0.5,
                       "min": 4, "max": 60},
        "output_len": {"dist": "uniform", "min": 8, "max": 24},
        "policy": ({"name": "per_layer", "drop_target": 0.25,
                    "delta": 0.05} if policy == "2t" else {"name": "none"}),
        "engine": {"n_slots": 4, "page_size": 8, "chunk_size": 16,
                   "max_prompt_len": 64, "max_new_tokens": 24},
        "calibration": {"prompts": 2, "prompt_len": 32},
        "check": {"sample_requests": 6}, "grace_s": 30,
        "clients": 4, "requests_per_client": 400, "stagger_s": 0.01,
    }
    e2e = [{"name": n, "unit": u} for n, u in (
        ("ttft_p90_ms", "ms"), ("itl_p95_ms", "ms"),
        ("offline_tok_s", "tokens/s"), ("setup_s", "s"))]
    return R.Cell(name="tiny", chips=1, config_name="tiny", config=TINY,
                  traffic_name="tiny", mix=mix,
                  fixed={"rate_per_s": 6.0,
                         "limits": {"widest_gap": widest_gap}},
                  end_to_end=e2e, per_layer=[])


@pytest.mark.parametrize("policy,loop", [("none", "closed"), ("2t", "open")])
def test_tiny_run_is_correct(policy, loop):
    from bench import run as R
    res = R.run(tiny_cell(policy, loop, widest_gap=TINY_LIMIT), 2**33 + 7,
                3.0, False, jax.devices(), log=lambda *a, **k: None)
    assert res["correct"], res["check"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"ttft_p90_ms", "itl_p95_ms",
                                   "offline_tok_s", "setup_s"}
    assert all(v["value"] > 0 for v in res["metrics"].values())


# The tiny cell's limit, set as the cells' are (CPU, 5 seeds x 2 policies):
# sound runs read at most 1.8e-3 (bf16 program vs float32 reference), the
# float8 control at least 6.3e-3, the planted faults 0.42 and more.
TINY_LIMIT = 4.5e-3
