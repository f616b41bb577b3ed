"""BENCHMARK.json and the data files it names: every cell finds its
configuration, traffic mix, fixed numbers and family module, every
per-layer metric its reader, and a reader with nothing to read returns
nothing. A new family is a new file and nothing else."""
from __future__ import annotations

import glob
import json
import os
import shutil

import pytest

from bench import run as R

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "BENCHMARK.json")) as _fh:
    SPEC = json.load(_fh)
CELLS = [w["name"] for w in SPEC["workloads"]]
# every reader, also those of metrics no cell of BENCHMARK.json reports yet
METRICS = sorted(os.path.basename(p)[:-3] for p in glob.glob(
    os.path.join(ROOT, "bench", "metrics", "*.*.py")))


@pytest.mark.parametrize("name", CELLS)
def test_cell_loads(name):
    cell = R.load_cell(name)
    s = cell.family.sizes(cell.config)   # refuses a split it cannot make
    assert s["vocab"] > 0 and list(_leaves(cell.family.layout(s)))
    assert cell.fixed["limits"]
    assert set(cell.fixed["limits"]) <= set(R.GAP_STATS)
    if cell.mix["loop"] == "open":
        assert cell.fixed["rate_per_s"] > 0
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    assert all(m["moves"] in {e["name"] for e in cell.end_to_end}
               for m in cell.per_layer)
    for key in cell.config["reduced"]:
        assert key in cell.config


def test_every_per_layer_metric_has_a_reader():
    assert {m["name"] for m in SPEC["per_layer"]} <= set(METRICS)


@pytest.mark.parametrize("name,key,value,match", [
    ("qwen3-30b-a3b.chat-2t", "moe_intermediate_size", 769, "expert width"),
    ("mixtral-8x7b.chat-2t", "intermediate_size", 14337, "expert width"),
    ("qwen3-30b-a3b.chat-2t", "num_key_value_heads", 5, "query heads"),
    ("mixtral-8x7b.chat-2t", "num_key_value_heads", 5, "query heads"),
], ids=["qwen3_moe-width", "mixtral-width", "qwen3_moe-heads",
        "mixtral-heads"])
def test_sizes_refuse_a_split_the_program_cannot_make(name, key, value,
                                                      match):
    """The family's sizes hold what the program's layout needs: the
    expert width splits into the configuration's ``partition_p``
    sub-experts, and the query heads group over the KV heads."""
    cell = R.load_cell(name)
    with pytest.raises(ValueError, match=match):
        cell.family.sizes({**cell.config, key: value})


@pytest.mark.parametrize("metric", METRICS)
def test_reader_finds_nothing_in_an_empty_trace(metric):
    cell = R.load_cell(CELLS[0])
    ctx = R.Context(family=cell.family, s=cell.family.sizes(cell.config), p=1,
                    peaks={"bf16_flops": 1.0, "hbm_bytes_per_s": 1.0},
                    steps=[], counts=[0, 0, 0], programs={},
                    kernel=(0.0, 0), busy_s=0.0, window_s=0.0)
    assert R.reader(metric)(ctx) is None


def test_weights_layout_counts_the_published_parameters():
    for name, want in (("qwen3-30b-a3b.chat-2t", 3_114_813_440),  # 4 of 48
                       ("mixtral-8x7b.chat-2t", 3_164_688_384)):  # 2 of 32
        cell = R.load_cell(name)
        n = 0
        for shape, _ in _leaves(cell.family.layout(
                cell.family.sizes(cell.config))):
            size = 1
            for x in shape:
                size *= x
            n += size
        assert n == want, name


def _leaves(tree):
    for v in tree.values():
        if isinstance(v, dict):
            yield from _leaves(v)
        else:
            yield v


def _copy_spec(root, name: str, model_type: str):
    """A checkout under ``root`` with the benchmark's spec and data files
    and one cell, ``name``, whose configuration names ``model_type``."""
    src = R.load_cell("qwen3-30b-a3b.chat-2t")
    for sub in ("configs", "traffic", "cells"):
        shutil.copytree(os.path.join(ROOT, "bench", sub),
                        os.path.join(root, "bench", sub))
    with open(os.path.join(root, "bench", "configs", "toy.json"), "w") as fh:
        json.dump({**src.config, "model_type": model_type}, fh)
    shutil.copy(os.path.join(ROOT, "bench", "cells",
                             "qwen3-30b-a3b.chat-2t.json"),
                os.path.join(root, "bench", "cells", name + ".json"))
    spec = {**SPEC,
            "configs": SPEC["configs"] + [
                {"name": "toy", "source": "test", "file":
                 "bench/configs/toy.json", "reduced": [], "why": "test"}],
            "workloads": SPEC["workloads"] + [
                {"name": name, "config": "toy", "traffic": "chat-2t",
                 "chips": 1, "why": "test"}]}
    with open(os.path.join(root, "BENCHMARK.json"), "w") as fh:
        json.dump(spec, fh)


def test_a_new_family_is_a_new_file(tmp_path):
    before = {p: os.path.getmtime(p) for p in glob.glob(
        os.path.join(ROOT, "bench", "**", "*.py"), recursive=True)}
    _copy_spec(tmp_path, "toy.chat", "toy_moe")
    os.makedirs(tmp_path / "bench" / "families")
    shutil.copy(os.path.join(ROOT, "bench", "families", "qwen3_moe.py"),
                tmp_path / "bench" / "families" / "toy_moe.py")
    cell = R.load_cell("toy.chat", root=str(tmp_path))
    assert cell.family.__file__ == str(tmp_path / "bench" / "families"
                                       / "toy_moe.py")
    s = cell.family.sizes(cell.config)
    assert s["layers"] == 4 and s["experts"] == 128
    assert len(list(_leaves(cell.family.layout(s)))) == 13
    mc = cell.family.model_config(cell.config_name, cell.config, s)
    assert (mc.n_layers, mc.d_model, mc.n_experts) == (4, 2048, 128)
    after = {p: os.path.getmtime(p) for p in glob.glob(
        os.path.join(ROOT, "bench", "**", "*.py"), recursive=True)}
    assert after == before


def test_an_unknown_family_names_the_file_it_looked_for(tmp_path):
    _copy_spec(tmp_path, "toy.chat", "no_such_family")
    want = str(tmp_path / "bench" / "families" / "no_such_family.py")
    with pytest.raises(SystemExit, match=want):
        R.load_cell("toy.chat", root=str(tmp_path))
