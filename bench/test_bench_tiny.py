"""A tiny cell for the CPU tests: the Qwen3-MoE layout at toy widths, a
short open-loop mix, and a run of the whole harness on it."""
from __future__ import annotations

import hashlib
import os
import sys

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

TINY = {
    "source": "toy widths for tests", "model_type": "qwen3_moe",
    "hidden_size": 64,
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
    "source": "toy widths for tests", "model_type": "mixtral",
    "hidden_size": 128,
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
                  end_to_end=e2e, per_layer=[], family=R.family(TINY))


@pytest.mark.parametrize("policy,loop", [("none", "closed"), ("2t", "open")])
def test_tiny_run_is_correct(policy, loop):
    from bench import run as R
    res = R.run(tiny_cell(policy, loop, widest_gap=TINY_LIMIT), 2**33 + 7,
                3.0, False, jax.devices(), log=lambda *a, **k: None)
    assert res["correct"], res["check"]
    # a cell whose policy drops none holds the dropped pairs at 0
    assert ("dropped_pairs" in res["check"]) == (policy == "none")
    assert res["check"].get("dropped_pairs", {"value": 0})["value"] == 0
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"ttft_p90_ms", "itl_p95_ms",
                                   "offline_tok_s", "setup_s"}
    assert all(v["value"] > 0 for v in res["metrics"].values())


# The tiny cell's limit, set as the cells' are (CPU, 5 seeds x 2 policies):
# sound runs read at most 1.8e-3 (bf16 program vs float32 reference), the
# float8 control at least 6.3e-3, the planted faults 0.42 and more.
TINY_LIMIT = 4.5e-3


# sha256 of every leaf's bf16 bits, in the tree's order, as the weights
# were drawn before the layouts moved into bench/families: the same tree,
# the same fold_in index per leaf, the same bits
WEIGHT_DIGESTS = {
    ("TINY", 5):
        "2ac441e09fcb84ec2a58a4a94ad0cee385d95f40df2bb37dc891e9fb198e9bc9",
    ("TINY", 2**33 + 5):
        "af1bcd53e814cbb9eb35fcc10d8f2f8f29ab3e7fb8542ab0f48ab28ab14f486c",
    ("TINY_COARSE", 5):
        "138ec2b502284d581a71c3d05336f57b0627f3b6092c59e92628bf62cc7891e0",
    ("TINY_COARSE", 2**33 + 5):
        "1a5b5e5f2ba515a2e20dd55f275f73b46a0501713439558ca8e826b1c270b0f5",
}


@pytest.mark.parametrize("name,seed", sorted(WEIGHT_DIGESTS))
def test_weights_keep_their_bits(name, seed):
    import numpy as np
    from bench import run as R
    from bench import weights
    cfg = {"TINY": TINY, "TINY_COARSE": TINY_COARSE}[name]
    fam = R.family(cfg)
    params = weights.make(fam.layout(fam.sizes(cfg)), seed)
    h = hashlib.sha256()
    for leaf in jax.tree.leaves(params):
        h.update(np.asarray(leaf).view(np.uint16).tobytes())
    assert h.hexdigest() == WEIGHT_DIGESTS[name, seed]
