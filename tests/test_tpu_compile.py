"""Ahead-of-time compiles of the MoE kernels for a described TPU v5e, at the
published Qwen3-30B-A3B widths (d_model 2048, 128 experts, top-8, d_expert
768; partitioned P=2 into sub-experts of 384).

Nothing runs: the TPU compiler checks what interpret mode cannot (tile
alignment of every slice and DMA, SMEM/VMEM residency, scalar indexing).
The topology is described inside a fixture, never at import time, so every
test worker collects the same tests and only the worker given this file
loads the TPU compiler.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.dualsparse_ffn import (fused_moe_pipeline_pallas,
                                          grouped_swiglu_pallas)

D_MODEL, N_EXPERTS, TOP_K, D_EXPERT = 2048, 128, 8, 768


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2, with the persistent compilation
    cache off (a described chip's entries cannot be read back)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _sds(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _weights(sharding, p, n_layers=0):
    n_sub, f = N_EXPERTS * p, D_EXPERT // p
    stack = (n_layers,) if n_layers else ()
    return (_sds(sharding, stack + (n_sub, D_MODEL, f)),
            _sds(sharding, stack + (n_sub, D_MODEL, f)),
            _sds(sharding, stack + (n_sub, f, D_MODEL)))


@pytest.mark.parametrize(
    "n_tokens,p,n_layers", [(8, 1, 0), (128, 2, 0), (8, 1, 4), (128, 2, 4)],
    ids=["decode_p1", "prefill_chunk_p2", "decode_p1_stacked",
         "prefill_chunk_p2_stacked"])
def test_streamed_fused_pipeline_compiles(one_chip, n_tokens, p, n_layers):
    """The serving default: exact capacity (capacity == T), P=1 with the
    minor-half split off, or mode-grouped P=2 with minor-half skipping.
    Stacked: the weights are a 4-layer stack and the kernel reads a traced
    layer of it (layer index in SMEM, weight blocks squeeze the layer
    axis)."""
    block_c = min(128, n_tokens)
    n_pairs = n_tokens * TOP_K + block_c
    args = (_sds(one_chip, (n_tokens, D_MODEL)),
            *_weights(one_chip, p, n_layers),
            *(_sds(one_chip, (N_EXPERTS,), jnp.int32) for _ in range(3)),
            _sds(one_chip, (n_pairs,), jnp.int32),
            _sds(one_chip, (n_pairs,), jnp.float32))
    n_minor_start = None if p > 1 else D_EXPERT

    def fused(*a, layer=None):
        return fused_moe_pipeline_pallas(
            *a, capacity=n_tokens, p_factor=p, n_minor_start=n_minor_start,
            layer=layer, interpret=False)

    layer = _sds(one_chip, (), jnp.int32) if n_layers else None
    compiled = jax.jit(fused).lower(*args, layer=layer).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_grouped_swiglu_compiles(one_chip):
    """The buffer path's kernel, mode-grouped at P=2 over (E, C, d)
    capacity buffers of a 128-token prefill chunk."""
    capacity, p = 128, 2
    args = (_sds(one_chip, (N_EXPERTS, capacity, D_MODEL)),
            *_weights(one_chip, p),
            _sds(one_chip, (N_EXPERTS,), jnp.int32),
            _sds(one_chip, (N_EXPERTS,), jnp.int32))

    def grouped(*a):
        return grouped_swiglu_pallas(*a, p_factor=p, interpret=False)

    compiled = jax.jit(grouped).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
