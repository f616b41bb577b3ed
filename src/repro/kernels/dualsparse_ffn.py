"""Pallas TPU kernel: grouped SwiGLU expert FFN with dual-sparse block
skipping (the TPU adaptation of the paper's §4.2 Triton kernel).

Design (see DESIGN.md §3):
  * tokens are pre-sorted per expert buffer: FULL-mode rows first, then
    MAJOR-only rows, then padding. Neurons are pre-reconstructed so the
    MAJOR half occupies d_ff slots [0, f/2).
  * grid = (E, C/block_c, f/block_f); the f axis is innermost and
    accumulates into the (block_c, d) output tile resident in VMEM.
  * a (token-block, neuron-block) pair is SKIPPED with ``pl.when`` whenever
    no row of the block needs that neuron half:
        neuron block in MINOR half -> valid rows = counts_full[e]
        neuron block in MAJOR half -> valid rows = counts_full[e]+counts_major[e]
    so 2T-Drop's computation dropping becomes whole MXU tiles never issued —
    the tensor-granular saving the paper argues is what real hardware can
    actually cash in (vs. fine-grained sparsity).
  * within a partially-valid block, rows are masked by an iota compare
    (VPU-cheap) for exactness.

Block shapes default to (128, 128) — MXU-aligned; d (the contraction /
output width) stays whole per tile so each grid step is one
(block_c × d) @ (d × block_f) MXU matmul pair + one (block_c × block_f) @
(block_f × d) accumulation.

VMEM working set per step ≈ (block_c·d + 2·d·block_f + block_f·d +
block_c·d) · bytes — e.g. d=2048, blocks 128/128, bf16: ≈ 2.6 MB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .specs import BlockUse, KernelSpec, dtype_name


def _row_view(d: int):
    """``(R, L)`` slab shape of one d-wide activation row in the streamed
    kernel: lane-dense ``L = 128`` whenever d allows it. Holding x and out
    as ``(T, R, L)`` keeps the token axis leading and untiled, so one token
    row is a whole-tile DMA at any offset instead of a one-row slice of an
    (8|16, 128)-tiled ``(T, d)`` array, which the TPU compiler refuses."""
    lanes = 128 if d % 128 == 0 else d
    return d // lanes, lanes


def _resolve_blocks(C: int, f: int, p_factor: int,
                    n_minor_start: int | None, block_c: int, block_f: int):
    """Shared geometry: clamp blocks to the logical dims, pad to block
    multiples, resolve the minor-half boundary. Returns a meta dict both
    kernel specs embed and both launches consume."""
    block_c = min(block_c, C)
    block_f = min(block_f, f)
    pad_c, pad_f = (-C) % block_c, (-f) % block_f
    Cp, fp = C + pad_c, f + pad_f
    nf_sub = fp // block_f              # f-blocks per sub-expert
    n_f = p_factor * nf_sub             # f-blocks over the virtual width
    if n_minor_start is None:
        if p_factor > 1:
            n_minor_start = fp          # everything past sub-expert 0
        else:
            n_minor_start = f // 2 if f % 2 == 0 else f
    return dict(block_c=block_c, block_f=block_f, pad_c=pad_c, pad_f=pad_f,
                Cp=Cp, fp=fp, nf_sub=nf_sub, n_f=n_f,
                n_minor_start=n_minor_start, p_factor=p_factor)


def grouped_swiglu_kernel_spec(E: int, C: int, d: int, f: int, *,
                               dtype=jnp.float32, p_factor: int = 1,
                               n_minor_start: int | None = None,
                               block_c: int = 128,
                               block_f: int = 128) -> KernelSpec:
    """Static launch description of ``grouped_swiglu_pallas`` for logical
    shapes x: (E, C, d), w1/w3: (E*p_factor, d, f), w2: (E*p_factor, f, d).
    The launch derives its grid/blocks from this spec, so the
    ``repro.lint`` Pallas passes analyze exactly what runs."""
    g = _resolve_blocks(C, f, p_factor, n_minor_start, block_c, block_f)
    dt = dtype_name(dtype)
    blocks = (
        BlockUse("counts_full", (E,), "int32", "in", streamed=False,
                 control=True, space="smem"),
        BlockUse("counts_major", (E,), "int32", "in", streamed=False,
                 control=True, space="smem"),
        BlockUse("x", (1, g["block_c"], d), dt, "in"),
        BlockUse("w1", (1, d, g["block_f"]), dt, "in"),
        BlockUse("w3", (1, d, g["block_f"]), dt, "in"),
        BlockUse("w2", (1, g["block_f"], d), dt, "in"),
        BlockUse("out", (1, g["block_c"], d), "float32", "out"),
    )
    grid = (E, g["Cp"] // g["block_c"], g["n_f"])
    meta = dict(g, E=E, C=C, d=d, f=f, virtual_f=g["fp"] * p_factor)
    return KernelSpec("grouped_swiglu", grid, blocks, meta)


def fused_moe_pipeline_kernel_spec(T: int, d: int, f: int, E: int,
                                   n_pairs_padded: int, *,
                                   capacity: int, dtype=jnp.float32,
                                   p_factor: int = 1,
                                   n_minor_start: int | None = None,
                                   block_c: int = 128,
                                   block_f: int = 128,
                                   streamed: bool = True,
                                   n_layers: int = 0) -> KernelSpec:
    """Static launch description of ``fused_moe_pipeline_pallas``.

    ``streamed=True`` (production): the per-pair maps ride in SMEM via
    scalar prefetch, x and the f32 output live in ANY (HBM) memory as
    ``(T, R, L)`` token-row slabs (``_row_view``), and
    VMEM holds only the revolving weight tiles plus the double-buffered
    (block_c, d) gather tiles and two f32 staging tiles — the working set
    is independent of T, so the 16 MB budget holds at prefill scale.

    ``streamed=False`` (resident): the original PR-6 layout with the whole
    (T, d) activation/output arrays VMEM-resident — kept as the
    bit-exactness oracle for the streamed kernel, the bench comparison
    point, and the lint negative test (it MUST blow the VMEM budget at
    prefill scale).

    ``n_layers > 0`` (streamed only): the stacked launch. The weights are
    the layer-stacked ``(n_layers, E*p_factor, d, f)`` arrays, the
    ``layer`` index rides first in SMEM, and each weight block squeezes
    the layer axis (a leading 1 here, ``pl.squeezed`` in the launch): the
    same tiles, read from one layer of the stack in place."""
    g = _resolve_blocks(capacity, f, p_factor, n_minor_start,
                        block_c, block_f)
    dt = dtype_name(dtype)
    map_space = "smem" if streamed else "vmem"
    layer_axis = (1,) if n_layers else ()
    blocks = [
        BlockUse("layer", (1,), "int32", "in", streamed=False,
                 control=True, space="smem"),
    ] if n_layers else []
    blocks += [
        BlockUse("group_offsets", (E,), "int32", "in", streamed=False,
                 control=True, space=map_space),
        BlockUse("counts_full", (E,), "int32", "in", streamed=False,
                 control=True, space=map_space),
        BlockUse("counts_major", (E,), "int32", "in", streamed=False,
                 control=True, space=map_space),
        BlockUse("tok_sorted", (n_pairs_padded,), "int32", "in",
                 streamed=False, control=True, space=map_space),
        BlockUse("combine_sorted", (n_pairs_padded,), "float32", "in",
                 streamed=False, control=True, space=map_space),
    ]
    if streamed:
        row = _row_view(d)
        blocks += [
            BlockUse("x", (T,) + row, dt, "in", streamed=False,
                     space="any", dma_buffers=2),
            BlockUse("w1", layer_axis + (1, d, g["block_f"]), dt, "in"),
            BlockUse("w3", layer_axis + (1, d, g["block_f"]), dt, "in"),
            BlockUse("w2", layer_axis + (1, g["block_f"], d), dt, "in"),
            BlockUse("out", (T,) + row, "float32", "out", streamed=False,
                     space="any", dma_buffers=1),
            BlockUse("x_tiles", (2 * g["block_c"],) + row, dt, "scratch"),
            BlockUse("acc_scratch", (g["block_c"],) + row, "float32",
                     "scratch"),
            BlockUse("out_stage", (g["block_c"],) + row, "float32",
                     "scratch"),
        ]
    else:
        assert not n_layers, "the stacked launch is streamed only"
        blocks += [
            BlockUse("x", (T, d), dt, "in", streamed=False),
            BlockUse("w1", (1, d, g["block_f"]), dt, "in"),
            BlockUse("w3", (1, d, g["block_f"]), dt, "in"),
            BlockUse("w2", (1, g["block_f"], d), dt, "in"),
            BlockUse("out", (T, d), "float32", "out", streamed=False),
            BlockUse("x_scratch", (g["block_c"], d), dt, "scratch"),
            BlockUse("acc_scratch", (g["block_c"], d), "float32", "scratch"),
        ]
    grid = (E, g["Cp"] // g["block_c"], g["n_f"])
    meta = dict(g, E=E, C=capacity, d=d, f=f, T=T, capacity=capacity,
                n_pairs_padded=n_pairs_padded, virtual_f=g["fp"] * p_factor,
                streamed=streamed, n_layers=n_layers)
    return KernelSpec("fused_moe_pipeline", grid, tuple(blocks), meta)


def _kernel(counts_full_ref, counts_major_ref,   # (E,) control, SMEM
            x_ref, w1_ref, w3_ref, w2_ref, out_ref, *,
            block_c: int, block_f: int, n_minor_start: int):
    e = pl.program_id(0)
    c = pl.program_id(1)
    f = pl.program_id(2)

    cf = counts_full_ref[e]
    cm = counts_major_ref[e]
    row0 = c * block_c
    # a block is live iff any of its neurons is needed by any of its rows:
    # blocks containing major neurons serve cf+cm rows, minor-only blocks cf.
    has_major = f * block_f < n_minor_start
    live = row0 < jnp.where(has_major, cf + cm, cf)

    @pl.when(f == 0)
    def _init():
        out_ref[0] = jnp.zeros_like(out_ref[0])

    @pl.when(live)
    def _compute():
        x = x_ref[0]                                   # (block_c, d)
        w1 = w1_ref[0]                                 # (d, block_f)
        w3 = w3_ref[0]
        w2 = w2_ref[0]                                 # (block_f, d)
        h = jax.nn.silu(jnp.dot(x, w1, preferred_element_type=jnp.float32))
        h = h * jnp.dot(x, w3, preferred_element_type=jnp.float32)
        rows = row0 + jax.lax.broadcasted_iota(jnp.int32, (block_c, 1), 0)
        # per-neuron validity handles f/2 not aligned to block_f exactly
        nids = f * block_f + jax.lax.broadcasted_iota(jnp.int32, (1, block_f), 1)
        valid_rows = jnp.where(nids < n_minor_start, cf + cm, cf)  # (1, bf)
        h = jnp.where(rows < valid_rows, h, 0.0)
        out_ref[0] += jnp.dot(h.astype(w2.dtype), w2,
                              preferred_element_type=jnp.float32
                              ).astype(out_ref.dtype)


def grouped_swiglu_pallas(x, w1, w3, w2, counts_full=None, counts_major=None,
                          *, p_factor: int = 1,
                          n_minor_start: int | None = None,
                          block_c: int = 128, block_f: int = 128,
                          interpret: bool = True):
    """See kernels.ref.grouped_swiglu_ref for semantics.

    x: (E, C, d); w1/w3: (E*p_factor, d, f); w2: (E*p_factor, f, d)
    -> (E, C, d).

    ``p_factor > 1`` — **fused sub-expert mode**: the weights are a
    partial-transformed layer (``core.partition``: sub-expert ``e*P + j``
    holds neuron slice j of original expert e). The grid's f axis walks the
    *virtual* concatenated width ``P*f`` of each original expert and the
    BlockSpec index map picks the owning sub-expert's slice — the fused
    full-width expert is reassembled by pure indexing, with zero weight
    copies. Sub-expert 0 is the reconstructed MAJOR half, so
    ``n_minor_start`` lands on the first sub-expert boundary and 2T-Drop's
    MAJOR-only rows (``counts_major``) skip every tile of sub-experts 1..P-1.

    ``n_minor_start`` — first neuron (virtual coordinate when fused) that
    belongs to the MINOR half. Defaults: ``f // 2`` at ``p_factor == 1``
    (pre-reconstructed full-width weights), the sub-expert width when fused.
    Pass the full width explicitly to disable the minor-half split (e.g. the
    S-ETP local buffers, where each group IS a single sub-expert and
    ``counts_major`` only tracks the row-mode ordering).

    ``interpret=True`` executes the kernel body in Python on CPU; on TPU
    pass interpret=False.
    """
    E, C, d = x.shape
    Es, _, f = w1.shape
    assert Es == E * p_factor, (
        f"weights carry {Es} sub-experts; buffers have {E} groups x "
        f"p_factor {p_factor}")
    if counts_full is None:
        counts_full = jnp.full((E,), C, jnp.int32)
    if counts_major is None:
        counts_major = jnp.zeros((E,), jnp.int32)
    spec = grouped_swiglu_kernel_spec(
        E, C, d, f, dtype=x.dtype, p_factor=p_factor,
        n_minor_start=n_minor_start, block_c=block_c, block_f=block_f)
    g = spec.meta
    block_c, block_f = g["block_c"], g["block_f"]
    pc, pf = g["pad_c"], g["pad_f"]
    Cp, nf_sub = g["Cp"], g["nf_sub"]
    n_minor_start = g["n_minor_start"]
    grid = spec.grid
    # pad C / per-sub-expert f to block multiples (padded neuron columns are
    # zero in w1/w3 => silu(0)*0 == 0 contribution through zero w2 rows)
    if pc:
        x = jnp.pad(x, ((0, 0), (0, pc), (0, 0)))
    if pf:
        w1 = jnp.pad(w1, ((0, 0), (0, 0), (0, pf)))
        w3 = jnp.pad(w3, ((0, 0), (0, 0), (0, pf)))
        w2 = jnp.pad(w2, ((0, 0), (0, pf), (0, 0)))

    kernel = functools.partial(
        _kernel, block_c=block_c, block_f=block_f,
        n_minor_start=n_minor_start)

    # the (E,) counts ride in SMEM via scalar prefetch: the kernel reads
    # them at a dynamic expert index, which a VMEM vector cannot serve
    def x_map(e, c, f, *_refs):
        return (e, c, 0)

    def w13_map(e, c, f, *_refs):
        return (e * p_factor + f // nf_sub, 0, f % nf_sub)

    def w2_map(e, c, f, *_refs):
        return (e * p_factor + f // nf_sub, f % nf_sub, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_c, d), x_map),
            pl.BlockSpec((1, d, block_f), w13_map),
            pl.BlockSpec((1, d, block_f), w13_map),
            pl.BlockSpec((1, block_f, d), w2_map),
        ],
        out_specs=pl.BlockSpec((1, block_c, d), x_map),
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((E, Cp, d), jnp.float32),
        interpret=interpret,
    )(counts_full.astype(jnp.int32), counts_major.astype(jnp.int32),
      x, w1, w3, w2)
    return out[:, :C].astype(x.dtype)


# ---------------------------------------------------------------------------
# Fused dispatch -> expert FFN -> combine pipeline (ROADMAP item 4)
# ---------------------------------------------------------------------------

def _fused_pipeline_kernel(offs_ref, cf_ref, cm_ref,      # (E,) control
                           tok_ref, wc_ref,               # (N_pad,) pair maps
                           x_ref, w1_ref, w3_ref, w2_ref, out_ref,
                           x_scr, acc_scr, *,
                           block_c: int, block_f: int, n_minor_start: int,
                           n_f: int):
    """One grid step = one (expert, row-block, neuron-block) tile.

    Instead of reading a pre-gathered (E, capacity, d) buffer, the kernel
    walks the sort permutation directly: the row block's sorted positions
    are ``offs[e] + row0 .. + block_c`` (contiguous by construction of
    ``DispatchPlan.perm``), ``tok_ref`` maps each sorted position to its
    source row of the flat (T, d) activation array, and ``wc_ref`` carries
    the pair's combine weight. Token rows are gathered once per row block
    (at f == 0) into VMEM scratch, the mode-ordered grouped SwiGLU runs
    with the same minor-half tile skipping as ``_kernel``, and the
    combine-weighted output rows are scatter-accumulated straight into the
    (T, d) output — no capacity buffer, no unpermute read-back.
    """
    e = pl.program_id(0)
    c = pl.program_id(1)
    f = pl.program_id(2)

    cf = cf_ref[e]
    cm = cm_ref[e]
    row0 = c * block_c
    any_rows = row0 < cf + cm                     # some row needs SOME tile
    has_major = f * block_f < n_minor_start
    live = row0 < jnp.where(has_major, cf + cm, cf)
    start = offs_ref[e] + row0

    @pl.when((e == 0) & (c == 0) & (f == 0))
    def _init_out():
        out_ref[...] = jnp.zeros(out_ref.shape, out_ref.dtype)

    @pl.when((f == 0) & any_rows)
    def _gather():
        acc_scr[...] = jnp.zeros(acc_scr.shape, acc_scr.dtype)

        def body(j, _):
            tok = tok_ref[start + j]
            x_scr[pl.ds(j, 1), :] = x_ref[pl.ds(tok, 1), :]
            return 0
        jax.lax.fori_loop(0, block_c, body, 0)

    @pl.when(live)
    def _compute():
        x = x_scr[...]                                 # (block_c, d)
        w1 = w1_ref[0]                                 # (d, block_f)
        w3 = w3_ref[0]
        w2 = w2_ref[0]                                 # (block_f, d)
        h = jax.nn.silu(jnp.dot(x, w1, preferred_element_type=jnp.float32))
        h = h * jnp.dot(x, w3, preferred_element_type=jnp.float32)
        rows = row0 + jax.lax.broadcasted_iota(jnp.int32, (block_c, 1), 0)
        nids = f * block_f + jax.lax.broadcasted_iota(jnp.int32, (1, block_f), 1)
        valid_rows = jnp.where(nids < n_minor_start, cf + cm, cf)  # (1, bf)
        h = jnp.where(rows < valid_rows, h, 0.0)
        acc_scr[...] += jnp.dot(h.astype(w2.dtype), w2,
                                preferred_element_type=jnp.float32)

    @pl.when((f == n_f - 1) & any_rows)
    def _scatter():
        def body(j, _):
            tok = tok_ref[start + j]
            w = jnp.where(row0 + j < cf + cm, wc_ref[start + j], 0.0)
            out_ref[pl.ds(tok, 1), :] += \
                w * acc_scr[pl.ds(j, 1), :].astype(out_ref.dtype)
            return 0
        jax.lax.fori_loop(0, block_c, body, 0)


def _fused_pipeline_streamed_kernel(
        offs_ref, cf_ref, cm_ref, tok_ref, wc_ref,   # scalar prefetch (SMEM)
        x_hbm, w1_ref, w3_ref, w2_ref, out_hbm,      # ANY + revolving VMEM
        x_tiles, acc_scr, stage, gather_sem, rw_sem, *,
        T: int, d: int, block_c: int, block_f: int, n_minor_start: int,
        n_f: int, n_c: int, n_blocks: int, E: int):
    """Streamed variant: VMEM holds only the revolving weight tiles plus
    ``x_tiles`` (2 x (block_c, d) — double-buffered gather destination),
    ``acc_scr`` and one f32 staging tile. The pair maps arrive through
    scalar prefetch (SMEM), x and out stay in ANY (HBM) memory and every
    touch is an explicit ``make_async_copy``.

    Every activation row is held as a ``(R, L)`` slab (``_row_view``): the
    token axis is a leading, untiled dimension, so a single token row is a
    whole-tile DMA at any offset — the TPU's (8|16, 128) tiling never sees
    a one-row slice.

      * gather — the row block of the NEXT (e, c) pair is DMA'd from
        x into the other half of ``x_tiles`` while the current block
        computes (classic double buffering keyed on the linear block
        index ``lin = e*n_c + c``; start and wait reconstruct identical
        per-row descriptors so the semaphore balances).
      * scatter — at each block's last f step, out rows are
        read-modify-written one row at a time through ``stage`` row 0
        (sequential per-row RMW keeps duplicate tokens exact).
      * init — grid step (0, 0, 0) zeroes out by DMA-ing a zeroed staging
        tile across the T rows before any scatter can read them.

    Arithmetic (accumulation order included) is identical to the resident
    kernel, so streamed == resident bit-exactly; only the residency and
    data movement differ.
    """
    e = pl.program_id(0)
    c = pl.program_id(1)
    f = pl.program_id(2)
    lin = e * n_c + c                             # linear (e, c) block index
    slot = jax.lax.rem(lin, 2)

    cf = cf_ref[e]
    cm = cm_ref[e]
    row0 = c * block_c
    any_rows = row0 < cf + cm                     # some row needs SOME tile
    has_major = f * block_f < n_minor_start
    live = row0 < jnp.where(has_major, cf + cm, cf)
    start = offs_ref[e] + row0

    def gather_dma(row, dst_slot, j):
        # one token row: x[tok] -> x_tiles[dst_slot*block_c + j]
        return pltpu.make_async_copy(
            x_hbm.at[pl.ds(row, 1)],
            x_tiles.at[pl.ds(dst_slot * block_c + j, 1)],
            gather_sem.at[dst_slot])

    def start_block_gather(blk, dst_slot):
        blk_start = offs_ref[blk // n_c] + (blk % n_c) * block_c

        def body(j, _):
            gather_dma(tok_ref[blk_start + j], dst_slot, j).start()
            return 0
        jax.lax.fori_loop(0, block_c, body, 0)

    def wait_block_gather(blk, dst_slot):
        blk_start = offs_ref[blk // n_c] + (blk % n_c) * block_c

        def body(j, _):
            gather_dma(tok_ref[blk_start + j], dst_slot, j).wait()
            return 0
        jax.lax.fori_loop(0, block_c, body, 0)

    @pl.when((lin == 0) & (f == 0))
    def _init_out():
        # Zero the (T, d) HBM accumulator by staging a zeroed tile; the
        # in-step waits order every zero write before the first scatter.
        stage[...] = jnp.zeros(stage.shape, stage.dtype)

        if T >= block_c:                 # static: loop body traces eagerly
            def zbody(k, _):
                cp = pltpu.make_async_copy(
                    stage, out_hbm.at[pl.ds(k * block_c, block_c)], rw_sem)
                cp.start()
                cp.wait()
                return 0
            jax.lax.fori_loop(0, T // block_c, zbody, 0)
        tail = T % block_c
        if tail:
            cp = pltpu.make_async_copy(
                stage.at[pl.ds(0, tail)],
                out_hbm.at[pl.ds(T - tail, tail)], rw_sem)
            cp.start()
            cp.wait()

    @pl.when(f == 0)
    def _dma_phase():
        # warm-up: the very first live block gathers for itself
        @pl.when((lin == 0) & any_rows)
        def _():
            start_block_gather(lin, slot)

        @pl.when(any_rows)
        def _():
            wait_block_gather(lin, slot)
            acc_scr[...] = jnp.zeros(acc_scr.shape, acc_scr.dtype)

        # steady state: prefetch the NEXT block's rows into the other slot
        nxt = lin + 1
        e1 = jnp.minimum(nxt // n_c, E - 1)       # clamp: nxt may be past end
        nxt_any = (nxt % n_c) * block_c < cf_ref[e1] + cm_ref[e1]

        @pl.when((nxt < n_blocks) & nxt_any)
        def _():
            start_block_gather(nxt, 1 - slot)

    @pl.when(live)
    def _compute():
        x = x_tiles[pl.ds(slot * block_c, block_c)].reshape(block_c, d)
        w1 = w1_ref[0]                                   # (d, block_f)
        w3 = w3_ref[0]
        w2 = w2_ref[0]                                   # (block_f, d)
        h = jax.nn.silu(jnp.dot(x, w1, preferred_element_type=jnp.float32))
        h = h * jnp.dot(x, w3, preferred_element_type=jnp.float32)
        rows = row0 + jax.lax.broadcasted_iota(jnp.int32, (block_c, 1), 0)
        nids = f * block_f + jax.lax.broadcasted_iota(jnp.int32, (1, block_f), 1)
        valid_rows = jnp.where(nids < n_minor_start, cf + cm, cf)  # (1, bf)
        h = jnp.where(rows < valid_rows, h, 0.0)
        acc_scr[...] += jnp.dot(h.astype(w2.dtype), w2,
                                preferred_element_type=jnp.float32
                                ).reshape(acc_scr.shape)

    @pl.when((f == n_f - 1) & any_rows)
    def _scatter():
        # sequential per-row RMW through stage row 0: duplicate tokens in
        # one block stay exact because each row's write completes before
        # the next row's read starts.
        def body(j, _):
            tok = tok_ref[start + j]
            w = jnp.where(row0 + j < cf + cm, wc_ref[start + j], 0.0)
            rd = pltpu.make_async_copy(out_hbm.at[pl.ds(tok, 1)],
                                       stage.at[pl.ds(0, 1)], rw_sem)
            rd.start()
            rd.wait()
            stage[0] = stage[0] + w * acc_scr[j]
            wr = pltpu.make_async_copy(stage.at[pl.ds(0, 1)],
                                       out_hbm.at[pl.ds(tok, 1)], rw_sem)
            wr.start()
            wr.wait()
            return 0
        jax.lax.fori_loop(0, block_c, body, 0)


def fused_moe_pipeline_pallas(x, w1, w3, w2, group_offsets, counts_full,
                              counts_major, tok_sorted, combine_sorted, *,
                              capacity: int, p_factor: int = 1,
                              n_minor_start: int | None = None,
                              block_c: int = 128, block_f: int = 128,
                              streamed: bool = True, layer=None,
                              interpret: bool = True):
    """Fused dispatch -> grouped SwiGLU -> weighted combine (one kernel).

    x: (T, d) flat token activations; w1/w3: (E*p_factor, d, f);
    w2: (E*p_factor, f, d) -> (T, d).

    ``group_offsets``/``counts_full``/``counts_major``: (E,) from a
    ``DispatchPlan`` (counts already clamped to ``capacity``, see
    ``DispatchPlan.kernel_counts``). ``tok_sorted``: (N',) source row of
    the flat activation array per SORTED pair position (``plan.perm``
    divided by the pair fan-out); ``combine_sorted``: (N',) combine weight
    (zero for dropped pairs) in the same order. Both must be padded with
    ``block_c`` trailing entries (token 0, weight 0) so the final row
    block's slice stays in range — ``core.dispatch.sorted_pair_arrays``
    builds them.

    Semantics match the three-step oracle
    ``gather_rows -> grouped_swiglu -> unpermute + combine`` to fp
    tolerance: the same rows are computed (capacity clamping included) and
    each kept pair contributes ``combine * f_e(x_tok)`` to its token's
    output row; only the float accumulation order differs.

    ``p_factor`` / ``n_minor_start`` follow ``grouped_swiglu_pallas``: the
    f axis walks the virtual concatenated width of partitioned sub-expert
    weights and MAJOR-only rows skip every minor-half tile.

    ``streamed=True`` (default, production): pair maps ride in SMEM via
    ``pltpu.PrefetchScalarGridSpec`` scalar prefetch, x/out live in ANY
    (HBM) memory, and every row touch is an explicit double-buffered
    ``pltpu.make_async_copy`` — the VMEM working set is independent of T.
    ``streamed=False`` keeps the original whole-array-resident layout
    (the streamed kernel's bit-exactness oracle and the lint negative
    test; interpret mode only). Both produce identical bits;
    ``interpret=True`` validates the block/skip/DMA logic on CPU.

    ``layer`` (a traced int32 scalar; streamed only): w1/w3/w2 are the
    layer-stacked ``(L, E*p_factor, d, f)`` / ``(L, E*p_factor, f, d)``
    arrays and the kernel reads layer ``layer``'s tiles straight from
    them — the index rides in scalar prefetch and the weight blocks squeeze
    the layer axis — so a layer scan hands over the whole stacks instead
    of a sliced copy. Same tiles, same order, same bits. ``f`` must be a
    multiple of ``block_f``: padding would copy the whole stack per call.
    """
    T, d = x.shape
    Es, _, f = w1.shape[-3:]
    n_layers = 0 if layer is None else w1.shape[0]
    E = group_offsets.shape[0]
    assert Es == E * p_factor, (
        f"weights carry {Es} sub-experts; plan has {E} groups x "
        f"p_factor {p_factor}")
    assert capacity >= 1
    assert tok_sorted.shape == combine_sorted.shape
    Np = tok_sorted.shape[0]
    spec = fused_moe_pipeline_kernel_spec(
        T, d, f, E, Np, capacity=capacity, dtype=x.dtype,
        p_factor=p_factor, n_minor_start=n_minor_start,
        block_c=block_c, block_f=block_f, streamed=streamed,
        n_layers=n_layers)
    g = spec.meta
    block_c, block_f = g["block_c"], g["block_f"]
    pf, nf_sub, n_f = g["pad_f"], g["nf_sub"], g["n_f"]
    n_minor_start = g["n_minor_start"]
    grid = spec.grid
    if not streamed and not interpret:
        raise NotImplementedError(
            "the resident fused kernel (streamed=False) is an interpret-mode "
            "oracle only; its one-row VMEM slices do not lower on TPU")
    if n_layers and pf:
        raise ValueError(f"stacked weights need f % block_f == 0 (f={f}, "
                         f"block_f={block_f}); slice the layer instead")
    if pf:
        w1 = jnp.pad(w1, ((0, 0), (0, 0), (0, pf)))
        w3 = jnp.pad(w3, ((0, 0), (0, 0), (0, pf)))
        w2 = jnp.pad(w2, ((0, 0), (0, pf), (0, 0)))

    operands = (group_offsets.astype(jnp.int32),
                counts_full.astype(jnp.int32),
                counts_major.astype(jnp.int32),
                tok_sorted.astype(jnp.int32),
                combine_sorted.astype(jnp.float32), x, w1, w3, w2)

    if streamed:
        n_c = grid[1]
        R, L = _row_view(d)
        kernel = functools.partial(
            _fused_pipeline_streamed_kernel, T=T, d=d, block_c=block_c,
            block_f=block_f, n_minor_start=n_minor_start, n_f=n_f,
            n_c=n_c, n_blocks=E * n_c, E=E)
        prefetch = operands[:5]
        layer_block = ()
        if n_layers:
            # the layer index is prefetched first and read by the weight
            # index maps only; the kernel body never sees it
            prefetch = (jnp.reshape(layer, (1,)).astype(jnp.int32),
                        *prefetch)
            layer_block = (pl.squeezed,)
            body = kernel

            def kernel(layer_ref, *refs):
                return body(*refs)

        def at_layer(refs):
            return (refs[0][0],) if n_layers else ()

        # index maps receive the scalar-prefetch refs as trailing args
        def w13_map(e, c, f, *refs):
            return at_layer(refs) + (e * p_factor + f // nf_sub, 0,
                                     f % nf_sub)

        def w2_map(e, c, f, *refs):
            return at_layer(refs) + (e * p_factor + f // nf_sub,
                                     f % nf_sub, 0)

        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=grid,
            in_specs=[
                pl.BlockSpec(memory_space=pl.ANY),           # x (HBM)
                pl.BlockSpec(layer_block + (1, d, block_f), w13_map),
                pl.BlockSpec(layer_block + (1, d, block_f), w13_map),
                pl.BlockSpec(layer_block + (1, block_f, d), w2_map),
            ],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),     # out (HBM)
            scratch_shapes=[
                pltpu.VMEM((2 * block_c, R, L), x.dtype),    # gather tiles
                pltpu.VMEM((block_c, R, L), jnp.float32),    # output accum
                pltpu.VMEM((block_c, R, L), jnp.float32),    # zero/RMW stage
                pltpu.SemaphoreType.DMA((2,)),               # per-slot gather
                pltpu.SemaphoreType.DMA,                     # zero + RMW
            ],
        )
        out = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((T, R, L), jnp.float32),
            interpret=interpret,
            name="fused_moe_pipeline",
        )(*prefetch, x.reshape(T, R, L), *operands[6:])
        return out.reshape(T, d).astype(x.dtype)

    kernel = functools.partial(
        _fused_pipeline_kernel, block_c=block_c, block_f=block_f,
        n_minor_start=n_minor_start, n_f=n_f)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((E,), lambda e, c, f: (0,)),        # group_offsets
            pl.BlockSpec((E,), lambda e, c, f: (0,)),        # counts_full
            pl.BlockSpec((E,), lambda e, c, f: (0,)),        # counts_major
            pl.BlockSpec((Np,), lambda e, c, f: (0,)),       # tok_sorted
            pl.BlockSpec((Np,), lambda e, c, f: (0,)),       # combine_sorted
            pl.BlockSpec((T, d), lambda e, c, f: (0, 0)),    # x (whole)
            pl.BlockSpec((1, d, block_f),
                         lambda e, c, f: (e * p_factor + f // nf_sub, 0,
                                          f % nf_sub)),
            pl.BlockSpec((1, d, block_f),
                         lambda e, c, f: (e * p_factor + f // nf_sub, 0,
                                          f % nf_sub)),
            pl.BlockSpec((1, block_f, d),
                         lambda e, c, f: (e * p_factor + f // nf_sub,
                                          f % nf_sub, 0)),
        ],
        out_specs=pl.BlockSpec((T, d), lambda e, c, f: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((T, d), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((block_c, d), x.dtype),               # gathered rows
            pltpu.VMEM((block_c, d), jnp.float32),           # output accum
        ],
        interpret=interpret,
        name="fused_moe_pipeline",
    )(*operands)
    return out.astype(x.dtype)
