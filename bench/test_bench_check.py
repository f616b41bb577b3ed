"""The correctness check at a tiny size on the CPU: the whole run with the
chip guard skipped, with the served path broken underneath it, must come
out not correct; so must the float8 control on the same served tokens."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

from bench import run as R
from bench import system
from bench.test_bench_tiny import TINY_LIMIT, tiny_cell


def _plant(eng, fault: str) -> None:
    """Break the engine's jitted steps: ``token``, every decoded token
    altered where it is produced; ``state``, both steps hand back the cache
    they were given (no K/V written, no position advanced)."""
    if fault == "token":
        step = eng._decode

        def decode(*a):
            logits, greedy, cache = step(*a)
            return logits, (greedy + 1) % logits.shape[-1], cache
        eng._decode = decode
    elif fault == "state":
        chunk, step = eng._chunk_insert, eng._decode

        def copy(tree):
            return jax.tree.map(jnp.copy, tree)

        def chunk_insert(*a):
            first, _ = chunk(*a[:5], copy(a[5]), *a[6:])
            return first, a[5]

        def decode(params, tokens, cache, *a):
            logits, greedy, _ = step(params, tokens, copy(cache), *a)
            return logits, greedy, cache
        eng._chunk_insert, eng._decode = chunk_insert, decode
    else:
        raise ValueError(f"unknown fault {fault!r}")


# the fault, and the gap statistic the cell's limit names; the cases of
# the widest gap keep the fault's name as their id
BROKEN = [pytest.param(f, stat, id=f if stat == "widest_gap" else
                       f"{f}-{stat}")
          for stat in ("widest_gap", "mean_gap") for f in ("token", "state")]


@pytest.mark.parametrize("fault,stat", BROKEN)
def test_broken_served_path_is_not_correct(fault, stat, monkeypatch):
    """A mean is at most the widest gap, so sound runs pass the tiny limit
    under either statistic."""
    build = system.engine

    def broken(*a, **k):
        eng = build(*a, **k)
        _plant(eng, fault)
        return eng
    monkeypatch.setattr(system, "engine", broken)
    cell = tiny_cell("none", "open", TINY_LIMIT)
    cell.fixed = {**cell.fixed, "limits": {stat: TINY_LIMIT}}
    res = R.run(cell, 31, 3.0, False, jax.devices(),
                log=lambda *a, **k: None)
    assert not res["correct"], res["check"]
    assert res["check"][stat]["value"] > TINY_LIMIT


def test_dropping_under_a_no_drop_cell_is_not_correct(monkeypatch):
    """The program prepared for 2T-Drop where the cell's policy drops
    none: the dropped pairs, held at 0, fail the run whatever the gaps
    read."""
    prepare = system.apply_policy
    cell = tiny_cell("none", "open", TINY_LIMIT)
    two_t = tiny_cell("2t").mix

    def dropping(mc, params, policy, calib):
        fam = cell.family
        s = fam.sizes(cell.config)
        calib = fam.Reference(params, s).calibration(
            R.calib_tokens(two_t, 31, s["vocab"]))
        return prepare(mc, params, system.policy_of(mc, two_t),
                       jnp.asarray(calib))
    monkeypatch.setattr(system, "apply_policy", dropping)
    res = R.run(cell, 31, 3.0, False, jax.devices(),
                log=lambda *a, **k: None)
    assert not res["correct"], res["check"]
    assert res["check"]["dropped_pairs"]["value"] > 0


def test_control_reads_above_the_limit():
    res = R.run(tiny_cell("2t", "open", TINY_LIMIT), 3, 3.0, False,
                jax.devices(), control=True, log=lambda *a, **k: None)
    assert res["correct"], res["check"]
    ctrl = res["control_gaps"]["widest_gap"]
    assert ctrl > TINY_LIMIT
    assert ctrl > 3 * res["program_gaps"]["widest_gap"]
    # the same check that passes the program fails the control
    assert not res["control_correct"], res["control_check"]
    assert res["control_check"]["widest_gap"]["value"] == ctrl
