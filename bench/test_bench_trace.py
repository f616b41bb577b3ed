"""The trace reduction on a small trace recorded on a TPU v5e
(``bench/record_fixture.py``: a tiny MoE model through ``PagedEngine``,
8 decode and 3 prefill-chunk steps under the benchmark's annotations)."""
from __future__ import annotations

import os

import pytest

from bench import xplane

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixtures", "engine_trace.xplane.pb.gz")


@pytest.fixture(scope="module")
def tr():
    return xplane.load(FIXTURE)


def test_planes_and_spans(tr):
    assert list(tr.ops) == ["0"] and list(tr.modules) == ["0"]
    names = {n for n, _, _ in tr.spans}
    assert {"bench_step", "bench_submit", "engine_decode",
            "engine_prefill_chunk"} <= names


def test_busy_and_idle_add_up(tr):
    win = xplane.loop_window(tr)
    length = win[1] - win[0]
    busy = xplane.busy_ns(tr, win)
    idle = sum(b - a for a, b in xplane.gaps(tr, win))
    assert 0 < busy < length
    assert busy + idle == pytest.approx(length)
    by_span = xplane.idle_by_span(tr, win)
    assert sum(by_span.values()) == pytest.approx(idle * 1e-9)
    assert "bench_step" in by_span


def test_union_merges_overlaps():
    got = xplane.union([(5, 9), (0, 3), (2, 4), (8, 12), (20, 30)], (1, 25))
    assert got == [(1, 4), (5, 12), (20, 25)]


def test_programs_and_kernels(tr):
    win = xplane.loop_window(tr)
    progs = xplane.programs(tr, win)
    assert progs["jit_decode"][1] == 8
    assert progs["jit_chunk_insert"][1] == 3
    assert all(s > 0 for s, _ in progs.values())
    ns, calls = xplane.kernel_ns(tr, win)
    assert calls > 0 and 0 < ns < sum(s for s, _ in progs.values()) * 1e9
    top = xplane.top_ops(tr, win)
    assert 0 < len(top) <= 10
    assert not any(n.startswith("while") for n, _ in top)
    assert xplane.op_name('%fusion.3 = f32[2]{0} fusion(f32[2]{0} %x)') \
        == "fusion.3"
