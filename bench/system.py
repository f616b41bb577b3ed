"""The system under test: the served program, built for one cell.

This module and the family modules (``bench/families``), which turn a
configuration file into the program's ``ModelConfig``, are the only ones
of the benchmark that import the program (``src/repro``). This one checks
that the benchmark's weights have the tree the program takes, applies the
cell's sparsity policy (for 2T-Drop: partition, reconstruction and
per-layer thresholds, calibrated by the program on the calibration
activations the benchmark hands it), and builds the ``PagedEngine`` whose
``submit``/``step`` the timed window drives.
"""
from __future__ import annotations

import os
import sys
from typing import Dict, Optional

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if os.path.join(ROOT, "src") not in sys.path:
    sys.path.insert(0, os.path.join(ROOT, "src"))

from repro.configs.base import ModelConfig  # noqa: E402
from repro.core.policy import make_policy  # noqa: E402
from repro.launch.mesh import make_host_mesh  # noqa: E402
from repro.models import model as M  # noqa: E402
from repro.models.transformer import DistContext  # noqa: E402
from repro.serving import GenerationConfig, PagedEngine  # noqa: E402


def check_tree(mc: ModelConfig, params) -> None:
    """The weights must have exactly the tree and shapes the program
    takes."""
    want, _ = M.abstract_params_and_axes(mc, jnp.bfloat16)
    got = jax.tree.map(lambda a: (a.shape, a.dtype), params)
    exp = jax.tree.map(lambda a: (a.shape, a.dtype), want)
    if jax.tree.structure(got) != jax.tree.structure(exp) or got != exp:
        raise ValueError("benchmark weights do not match the program's "
                         f"parameter tree: {got} vs {exp}")


def policy_of(mc: ModelConfig, mix: Dict):
    pol = mix["policy"]
    if pol["name"] == "none":
        return None
    if pol["name"] != "per_layer":
        raise ValueError(f"policy {pol['name']!r} is not benchmarked")
    return make_policy("per_layer", mc.dualsparse,
                       drop_target=pol["drop_target"], delta=pol["delta"])


def apply_policy(mc: ModelConfig, params, policy, calib) -> tuple:
    """Prepare the weights for ``policy`` with one calibration block per
    layer (``calib`` (L, T, d) float32). Returns (params, dist)."""
    if policy is None:
        return params, None
    moe = jax.jit(jax.vmap(lambda m, c: policy.prepare_layer(m, mc, c)))(
        params["blocks"]["moe"], calib)
    params = {**params, "blocks": {**params["blocks"], "moe": moe}}
    return params, DistContext(mesh=make_host_mesh(1), moe_impl="dispatch",
                               policy=policy)


def engine(mc: ModelConfig, params, dist: Optional[DistContext],
           mix: Dict) -> PagedEngine:
    return PagedEngine(mc, params, dist=dist, **mix["engine"])


def gen_for(n_out: int) -> GenerationConfig:
    return GenerationConfig(max_new_tokens=int(n_out))
