"""BENCHMARK.json and the data files it names: every cell finds its
configuration, traffic mix and fixed numbers, every per-layer metric its
reader, and a reader with nothing to read returns nothing."""
from __future__ import annotations

import glob
import json
import os

import pytest

from bench import run as R
from bench.weights import layout, sizes

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
    s = sizes(cell.config)
    assert s["d"] % s["hq"] == 0 and s["f"] % 2 == 0
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


@pytest.mark.parametrize("metric", METRICS)
def test_reader_finds_nothing_in_an_empty_trace(metric):
    ctx = R.Context(s=sizes(R.load_cell(CELLS[0]).config), p=1,
                    peaks={"bf16_flops": 1.0, "hbm_bytes_per_s": 1.0},
                    steps=[], counts=[0, 0, 0], programs={},
                    kernel=(0.0, 0), busy_s=0.0, window_s=0.0)
    assert R.reader(metric)(ctx) is None


def test_weights_layout_counts_the_published_parameters():
    s = sizes(R.load_cell("qwen3-30b-a3b.chat-2t").config)
    n = 0
    for shape, _ in _leaves(layout(s)):
        size = 1
        for x in shape:
            size *= x
        n += size
    assert n == 3_114_813_440          # 4 of the 48 layers


def _leaves(tree):
    for v in tree.values():
        if isinstance(v, dict):
            yield from _leaves(v)
        else:
            yield v
