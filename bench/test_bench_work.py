"""Operation and byte counts of needed work, against shapes worked by hand,
through the Qwen3-MoE family's counts and the shared ones of
``bench/work.py``."""
from __future__ import annotations

import numpy as np
import pytest

from bench import run as R
from bench import work

FAM = R.family({"model_type": "qwen3_moe"})
QWEN = FAM.sizes({"hidden_size": 2048, "num_hidden_layers": 4,
              "num_attention_heads": 32, "num_key_value_heads": 4,
              "head_dim": 128, "moe_intermediate_size": 768,
              "num_experts": 128, "num_experts_per_tok": 8,
              "vocab_size": 151936, "rope_theta": 1e6,
              "rms_norm_eps": 1e-6, "dualsparse": {"partition_p": 2}})
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def test_per_token_counts():
    # q 2048x4096, k and v 2048x512 each, o 4096x2048: 2 flops a MAC
    assert FAM.proj_flops(QWEN) == 2 * (2048 * 4096 * 2 + 2048 * 512 * 2)
    assert FAM.core_flops(QWEN, 10) == 4 * 32 * 128 * 10
    assert FAM.router_flops(QWEN) == 2 * 2048 * 128
    # 8 experts x 3 matrices of 2048 x 768, all kept / half of them kept
    assert FAM.moe_flops(QWEN, 1.0) == 8 * 3 * 2 * 2048 * 768
    assert FAM.moe_flops(QWEN, 0.5) == 4 * 3 * 2 * 2048 * 768
    assert FAM.head_flops(QWEN) == 2 * 2048 * 151936
    assert FAM.sub_expert_bytes(QWEN, 1) == 3 * 2048 * 768 * 2
    assert FAM.sub_expert_bytes(QWEN, 2) == 3 * 2048 * 384 * 2


def test_chunk_keys():
    # queries at 5, 6, 7 read 6, 7, 8 keys
    assert work.chunk_keys(5, 3) == 21
    assert work.chunk_keys(0, 1) == 1


def test_model_flops_of_one_step():
    rec = {"wall_s": 0.05, "decode_keys": [100, 200], "chunk": (16, 4, True),
           "live": np.zeros((4, 128), bool)}
    per_token = (FAM.proj_flops(QWEN) + FAM.router_flops(QWEN)
                 + FAM.moe_flops(QWEN, 1.0))
    keys = 300 + (17 + 18 + 19 + 20)
    want = (4 * (6 * per_token + FAM.core_flops(QWEN, keys))
            + 3 * FAM.head_flops(QWEN))
    assert FAM.model_flops(QWEN, [rec], 1.0) == pytest.approx(want)


def test_moe_needed_and_roofline():
    live = np.zeros((4, 256), bool)
    live[:, :10] = True                       # 10 live sub-experts a layer
    rec = {"wall_s": 0.05, "decode_keys": [1] * 3, "chunk": None,
           "live": live}
    need = FAM.moe_needed(QWEN, 2, [rec], 0.75)
    assert need["flops"] == 3 * 4 * FAM.moe_flops(QWEN, 0.75)
    assert need["bytes"] == (40 * FAM.sub_expert_bytes(QWEN, 2)
                             + 3 * 4 * 2048 * 2 * 2)
    share = work.roofline_share(need["flops"], need["bytes"], 1e-3, PEAKS)
    assert share == pytest.approx(100 * need["bytes"] / 819e9 / 1e-3)
    assert work.kept_share([6, 2, 2]) == 0.8
