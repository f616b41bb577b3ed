"""Jit'd public wrappers for the Pallas kernels.

The kernels lower natively on a TPU backend and run in ``interpret=True``
(the kernel body executes in Python, validating block logic exactly) on the
CPU backend. Any other backend is refused rather than silently interpreted.
"""
from __future__ import annotations

import functools

import jax

from .dualsparse_ffn import (fused_moe_pipeline_kernel_spec,
                             fused_moe_pipeline_pallas,
                             grouped_swiglu_kernel_spec,
                             grouped_swiglu_pallas)
from . import ref

__all__ = ["fused_moe_pipeline", "grouped_swiglu", "grouped_swiglu_ref",
           "fused_moe_pipeline_kernel_spec", "grouped_swiglu_kernel_spec",
           "fused_moe_pipeline_pallas", "grouped_swiglu_pallas"]


def _interpret() -> bool:
    """True on CPU (interpreter), False on TPU (native lowering); raises on
    any other backend — these are TPU kernels, and interpreting them on an
    accelerator would hide the device behind a Python loop."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(f"Pallas TPU kernels cannot run on backend {backend!r}")


@functools.partial(jax.jit, static_argnames=("capacity", "p_factor",
                                             "n_minor_start", "block_c",
                                             "block_f", "streamed"))
def fused_moe_pipeline(x, w1, w3, w2, group_offsets, counts_full,
                       counts_major, tok_sorted, combine_sorted,
                       capacity: int, p_factor: int = 1, n_minor_start=None,
                       block_c: int = 128, block_f: int = 128,
                       streamed: bool = True, layer=None):
    """Fused dispatch -> grouped SwiGLU -> weighted combine in ONE Pallas
    kernel: gathers token rows from the flat (T, d) activation array
    through the sort permutation, runs the mode-ordered dual-sparse FFN
    (minor-half MXU tiles of MAJOR-only rows skipped), and
    scatter-accumulates combine-weighted outputs per token — no
    (E, capacity, d) HBM buffer, no unpermute read-back.

    ``streamed=True`` (default): pair maps in scalar-prefetch SMEM, x/out
    in ANY (HBM) memory with explicit double-buffered DMA, so the VMEM
    working set is independent of T (prefill-safe). ``streamed=False``
    keeps the whole-array-resident PR-6 layout (bit-identical output,
    CPU interpreter only). With ``layer`` (traced scalar) the weights are
    the layer-stacked arrays and the kernel reads that layer in place.
    See kernels.dualsparse_ffn.fused_moe_pipeline_pallas for the
    contract; ``core.dispatch.sorted_pair_arrays`` builds the pair maps."""
    return fused_moe_pipeline_pallas(
        x, w1, w3, w2, group_offsets, counts_full, counts_major,
        tok_sorted, combine_sorted, capacity=capacity, p_factor=p_factor,
        n_minor_start=n_minor_start, block_c=block_c, block_f=block_f,
        streamed=streamed, layer=layer, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("p_factor", "n_minor_start",
                                             "block_c", "block_f"))
def grouped_swiglu(x, w1, w3, w2, counts_full=None, counts_major=None,
                   p_factor: int = 1, n_minor_start=None,
                   block_c: int = 128, block_f: int = 128):
    """Grouped SwiGLU expert FFN (optionally with 2T-Drop counts).

    x: (E, C, d) -> (E, C, d). ``p_factor > 1`` fuses partial-transformed
    sub-expert weights back into full-width experts by BlockSpec indexing so
    MAJOR-only rows skip the minor sub-experts' tiles; ``n_minor_start``
    overrides the minor-half boundary (pass the full width to disable the
    split). See kernels.ref / kernels.dualsparse_ffn for exact semantics."""
    return grouped_swiglu_pallas(
        x, w1, w3, w2, counts_full, counts_major,
        p_factor=p_factor, n_minor_start=n_minor_start,
        block_c=block_c, block_f=block_f, interpret=_interpret())


grouped_swiglu_ref = ref.grouped_swiglu_ref
