"""Sort-based, mode-ordered MoE dispatch (the shared fast substrate).

Every capacity-dispatch site in this repo — ``moe_forward_dispatch``, the
S-ETP shard_map body (device-level and local-expert-level slotting), and the
ETP baseline — reduces to the same problem: seat N flat (token, group) pairs
into fixed ``(G, capacity)`` buffers, preserving arrival order, dropping
pairs the routing policy discarded and counting pairs that overflow their
group's capacity.

The historical implementation materialized a dense ``one_hot(group, G)``
matrix and ran a ``cumsum`` down the pair axis — O(N·G) memory traffic for
what is an argsort problem. This module replaces it:

  * **argsort** a composite key ``(group, is_major_only, arrival)``; JAX's
    sort is stable, so a key of just ``group*2 + is_major_only`` (dropped
    pairs pushed past every group) keeps arrival order within each bucket
    for free — same slots as the cumsum path, bit for bit.
  * per-bucket counts come from a ``segment_sum`` histogram (O(N)) and group
    start offsets from one tiny (G,) ``cumsum`` — no (N, G) intermediate.
  * buffers are built by **gather** straight from the token array through
    ``perm`` (``gather_rows``), eliminating both the ``jnp.repeat(x, K)``
    materialization and the scatter of the old path.

**Mode ordering** is what finally feeds the dual-sparse kernel: with 2T-Drop
(paper §4.2) a pair is either FULL (both halves) or MAJOR-only. Passing the
major-only flag as the middle key sorts each group's buffer FULL-rows-first /
MAJOR-only-rows-second *by construction*, which is exactly the row layout
``kernels.dualsparse_ffn`` requires to skip whole minor-half MXU tiles —
``counts_full`` / ``counts_major`` fall out of the same histogram.

``cumsum_dispatch`` keeps the dense one-hot reference as an oracle for the
equivalence tests and ``benchmarks/bench_dispatch.py``.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np


class DispatchPlan(NamedTuple):
    """Seating plan for N flat pairs into (G, capacity) buffers.

    All per-pair arrays are in the ORIGINAL flat-pair order; ``perm`` /
    ``group_offsets`` describe the sorted (buffer) order.
    """
    perm: jax.Array           # (N,) flat-pair ids in buffer order:
    #                           grouped by group, FULL rows first, then
    #                           MAJOR-only rows, then all dropped pairs
    group_offsets: jax.Array  # (G,) start of each group's run inside perm
    counts_full: jax.Array    # (G,) kept FULL-mode rows per group (unclamped)
    counts_major: jax.Array   # (G,) kept MAJOR-only rows per group
    group: jax.Array          # (N,) destination group (clipped to [0, G))
    slot: jax.Array           # (N,) buffer row; == capacity when the pair is
    #                           dropped (by policy or by capacity overflow)
    overflow: jax.Array       # ()  kept pairs discarded by capacity overflow

    @property
    def counts(self) -> jax.Array:
        """Kept rows per group (FULL + MAJOR-only), unclamped."""
        return self.counts_full + self.counts_major

    def kernel_counts(self, capacity: int):
        """(counts_full, counts_major) clamped so full+major <= capacity —
        the row-validity arrays ``kernels.ops.grouped_swiglu`` consumes."""
        cf = jnp.minimum(self.counts_full, capacity)
        total = jnp.minimum(self.counts_full + self.counts_major, capacity)
        return cf, total - cf


def group_histogram(ids, n_groups: int, *, mask=None, dtype=jnp.int32):
    """O(N) histogram of ``ids`` over [0, n_groups) via segment_sum —
    replaces the dense ``one_hot(ids, G).sum(...)`` hot spots. ``mask``
    drops pairs (their id value may then be arbitrary, even negative)."""
    flat = ids.reshape(-1)
    if mask is not None:
        flat = jnp.where(mask.reshape(-1), flat, n_groups)
    data = jnp.ones(flat.shape, dtype)
    return jax.ops.segment_sum(data, flat, num_segments=n_groups + 1,
                               indices_are_sorted=False)[:n_groups]


def sort_dispatch(group, keep=None, *, n_groups: int, capacity: int,
                  major_only=None) -> DispatchPlan:
    """Build a DispatchPlan by stable argsort of ``(group, mode, arrival)``.

    group: (N,) destination group per flat pair (values outside [0, G) are
        tolerated only where ``keep`` is False).
    keep: (N,) bool — pairs the routing policy kept (None = all).
    major_only: (N,) bool — kept pairs that compute only the MAJOR neuron
        half (2T mode 1); they sort AFTER the FULL rows of their group so the
        dual-sparse kernel can skip minor-half tiles. None = no mode split.

    Slots are identical to the one-hot-cumsum path (``cumsum_dispatch``) bit
    for bit: stability of the sort preserves arrival order within each
    (group, mode) bucket, so ranks coincide with running counts.
    """
    group = group.reshape(-1)
    N = group.shape[0]
    G = n_groups
    if keep is None:
        keep = jnp.ones((N,), bool)
    else:
        keep = keep.reshape(-1)
    if major_only is None:
        major_only = jnp.zeros((N,), bool)
    else:
        major_only = major_only.reshape(-1) & keep

    # composite key: 2 buckets per group (FULL=0 / MAJOR-only=1), dropped
    # pairs past everything. Stable argsort => arrival order within buckets.
    bucket = jnp.where(keep, group * 2 + major_only.astype(group.dtype),
                       2 * G)
    perm = jnp.argsort(bucket, stable=True)

    counts2 = group_histogram(bucket, 2 * G)                     # (2G,)
    counts_full = counts2[0::2]
    counts_major = counts2[1::2]
    group_counts = counts_full + counts_major
    group_offsets = jnp.cumsum(group_counts) - group_counts      # exclusive

    # rank of each flat pair in sorted order -> slot within its group
    inv = jnp.zeros((N,), jnp.int32).at[perm].set(
        jnp.arange(N, dtype=jnp.int32))
    g_clip = jnp.clip(group, 0, G - 1)
    slot = inv - group_offsets[g_clip]
    overflow = jnp.sum((keep & (slot >= capacity)).astype(jnp.int32))
    slot = jnp.where(keep, jnp.minimum(slot, capacity), capacity)
    return DispatchPlan(perm=perm, group_offsets=group_offsets,
                        counts_full=counts_full, counts_major=counts_major,
                        group=g_clip, slot=slot, overflow=overflow)


def gather_rows(values, plan: DispatchPlan, capacity: int, *,
                index_div: int = 1, fill=0):
    """Materialize the (G, capacity, ...) buffers by GATHERING through the
    plan — no ``jnp.repeat`` of the token block, no scatter.

    values: (M, ...) source rows; flat pair ``i`` reads row
    ``i // index_div`` (pass ``index_div=K`` to read token ``i // K`` for a
    (T, K)-shaped pair list directly from the (T, d) token array).
    Rows beyond a group's kept count are ``fill``.
    """
    N = plan.perm.shape[0]
    G = plan.group_offsets.shape[0]
    pos = plan.group_offsets[:, None] + jnp.arange(capacity)[None, :]
    valid = jnp.arange(capacity)[None, :] < \
        jnp.minimum(plan.counts, capacity)[:, None]              # (G, C)
    src = plan.perm[jnp.clip(pos, 0, N - 1)]                     # (G, C)
    out = values[src // index_div if index_div > 1 else src]
    mask = valid.reshape(G, capacity, *((1,) * (out.ndim - 2)))
    return jnp.where(mask, out, jnp.asarray(fill, out.dtype))


def unpermute(out_buf, plan: DispatchPlan):
    """Read each flat pair's output row back from the (G, C, ...) buffer.
    Dropped/overflowed pairs (slot == capacity) read a zero pad row."""
    padded = jnp.pad(out_buf, ((0, 0), (0, 1)) +
                     ((0, 0),) * (out_buf.ndim - 2))
    return padded[plan.group, plan.slot]


def sorted_pair_arrays(plan: DispatchPlan, weights, *, index_div: int = 1,
                       pad: int = 0):
    """(tok_sorted, weight_sorted) for the fused Pallas MoE pipeline
    (``kernels.dualsparse_ffn.fused_moe_pipeline_pallas``).

    tok_sorted[i] is the source row (flat pair id // ``index_div``) of the
    i-th SORTED pair position; weight_sorted[i] its combine weight (pass
    ``combine * keep`` so dropped pairs carry weight 0). Both O(N) — the
    only per-pair state the fused kernel needs, replacing the
    (G, capacity, d) gathered buffer entirely. ``pad`` appends that many
    (row 0, weight 0) entries so the kernel's final row-block slice stays
    in range (pass its ``block_c``)."""
    src = plan.perm // index_div if index_div > 1 else plan.perm
    w = weights.reshape(-1)[plan.perm]
    if pad:
        src = jnp.pad(src, (0, pad))
        w = jnp.pad(w, (0, pad))
    return src.astype(jnp.int32), w


def prefer_cumsum_dispatch(n_pairs: int, n_groups: int,
                           backend: Optional[str] = None) -> bool:
    """Per-shape dispatch heuristic (ROADMAP): the sort substrate wins
    almost everywhere, but on CPU the dense one-hot cumsum is still faster
    for FEW groups at LARGE pair counts — O(N*G) with G<=8 is one cheap
    vectorized pass, while a stable argsort of ~1e4+ keys pays its
    O(N log N) in scalar compares (BENCH_dispatch.json: T=1024..4096/E=8
    runs 0.68-0.86x). Both build bit-identical plans, so the choice is pure
    performance. Accelerators always sort (the dense one-hot is an (N, G)
    HBM-traffic bomb there)."""
    if backend is None:
        backend = jax.default_backend()
    return backend == "cpu" and n_groups <= 8 and n_pairs >= 8192


def prefer_fused_pipeline(n_tokens: int, n_groups: int, *,
                          use_kernel: bool = False,
                          backend: Optional[str] = None) -> bool:
    """Per-shape fused-vs-buffer heuristic (mirrors
    ``prefer_cumsum_dispatch``): should the MoE forward run the streamed
    fused dispatch->FFN->combine Pallas pipeline instead of the
    gather->grouped-FFN->unpermute buffer path?

    On TPU the streamed kernel is the default at EVERY token count: its
    VMEM working set is independent of T (pair maps in SMEM, x/out in HBM
    with double-buffered DMA) and it never materializes the
    (E, capacity, d) buffer. On CPU the kernels run in interpret mode,
    where the fused kernel beats the interpreted buffer-path Pallas FFN
    but loses to the pure-XLA einsum the non-kernel policies use — so fused
    follows ``use_kernel`` there. Any other backend has no lowering for
    the TPU kernel and takes the buffer path. All paths agree to fp
    tolerance; the choice is performance only."""
    if backend is None:
        backend = jax.default_backend()
    del n_tokens, n_groups          # today's rule is shape-independent;
    #                                 the signature keeps per-shape tuning
    #                                 open without call-site churn
    if backend == "tpu":
        return True
    return backend == "cpu" and use_kernel


def dispatch_plan(group, keep=None, *, n_groups: int, capacity: int,
                  major_only=None, backend: Optional[str] = None
                  ) -> DispatchPlan:
    """Shape-dispatched planner: ``sort_dispatch`` or ``cumsum_dispatch``
    by ``prefer_cumsum_dispatch`` — bit-identical output either way."""
    n_pairs = int(np.prod(group.shape))
    fn = cumsum_dispatch if prefer_cumsum_dispatch(n_pairs, n_groups,
                                                   backend) else sort_dispatch
    return fn(group, keep, n_groups=n_groups, capacity=capacity,
              major_only=major_only)


# ---------------------------------------------------------------------------
# Dense one-hot cumsum reference (the pre-sort implementation, kept as the
# oracle for equivalence tests and the bench_dispatch baseline)
# ---------------------------------------------------------------------------

def cumsum_dispatch(group, keep=None, *, n_groups: int, capacity: int,
                    major_only=None) -> DispatchPlan:
    """O(N·G) reference: dense one-hot + cumsum running counts. Mode
    ordering is two-phase (FULL ranks first, MAJOR-only ranks offset by the
    group's FULL count) so slots match ``sort_dispatch`` exactly."""
    group = group.reshape(-1)
    N = group.shape[0]
    G = n_groups
    if keep is None:
        keep = jnp.ones((N,), bool)
    else:
        keep = keep.reshape(-1)
    if major_only is None:
        major_only = jnp.zeros((N,), bool)
    else:
        major_only = major_only.reshape(-1) & keep
    g_clip = jnp.clip(group, 0, G - 1)

    def running(mask):
        onehot = jax.nn.one_hot(g_clip, G, dtype=jnp.int32)
        onehot = onehot * mask[:, None].astype(jnp.int32)
        pos = jnp.cumsum(onehot, axis=0) - onehot                # (N, G)
        return (jnp.take_along_axis(pos, g_clip[:, None], axis=1)[:, 0],
                onehot.sum(axis=0))

    full_mask = keep & ~major_only
    pos_f, counts_full = running(full_mask)
    pos_m, counts_major = running(major_only)
    true_slot = jnp.where(major_only, counts_full[g_clip] + pos_m, pos_f)
    overflow = jnp.sum((keep & (true_slot >= capacity)).astype(jnp.int32))
    slot = jnp.where(keep, jnp.minimum(true_slot, capacity), capacity)

    group_counts = counts_full + counts_major
    group_offsets = jnp.cumsum(group_counts) - group_counts
    # perm via scatter of each kept pair into its sorted position (the
    # UNclamped rank — overflowed pairs still occupy a unique position);
    # dropped pairs fill the tail in arrival order
    drop = (~keep).astype(jnp.int32)
    rank_drop = jnp.cumsum(drop) - drop
    sorted_pos = jnp.where(keep, group_offsets[g_clip] + true_slot,
                           jnp.sum(group_counts) + rank_drop)
    perm = jnp.zeros((N,), jnp.int32).at[sorted_pos].set(
        jnp.arange(N, dtype=jnp.int32))
    return DispatchPlan(perm=perm, group_offsets=group_offsets,
                        counts_full=counts_full, counts_major=counts_major,
                        group=g_clip, slot=slot, overflow=overflow)


def scatter_rows(values, plan: DispatchPlan, capacity: int, *,
                 index_div: int = 1, fill=0):
    """Reference buffer construction of the pre-sort path: repeat + scatter
    into a (G, capacity+1, ...) buffer (row ``capacity`` is the discard
    row). Used by tests/benchmarks to pin gather_rows equivalence."""
    N = plan.group.shape[0]
    src = jnp.arange(N) // index_div if index_div > 1 else jnp.arange(N)
    rows = values[src]                                           # repeat
    G = plan.group_offsets.shape[0]
    buf = jnp.full((G, capacity + 1) + values.shape[1:], fill, values.dtype)
    buf = buf.at[plan.group, plan.slot].set(rows)
    return buf[:, :capacity]


# ---------------------------------------------------------------------------
# Mode helpers: original-expert ("fused") grouping for the dual-sparse kernel
# ---------------------------------------------------------------------------

def major_only_flags(keep, p: int):
    """Per-sub-pair MAJOR-only flags from an expanded (T, K*P) keep mask.

    Sub-expert 0 of an original pair is the MAJOR half; a pair is MAJOR-only
    when its major half is kept but every minor half is dropped (2T mode 1).
    Requires mode-monotone keeps (a kept minor implies a kept major), which
    every registered drop policy satisfies. Returns (T, K*P) bool with the
    flag on the major sub-pair only."""
    if p <= 1:
        return jnp.zeros_like(keep, dtype=bool)
    T, Kp = keep.shape
    k3 = keep.reshape(T, Kp // p, p)
    flag3 = jnp.zeros_like(k3)
    flag3 = flag3.at[..., 0].set(k3[..., 0] & ~k3[..., 1:].any(-1))
    return flag3.reshape(T, Kp)


class FusedGroups(NamedTuple):
    """Original-expert-granularity view of an expanded sub-pair list."""
    group: jax.Array       # (T, K) original expert per pair
    keep: jax.Array        # (T, K) any half kept
    major_only: jax.Array  # (T, K) only the major half kept
    combine: jax.Array     # (T, K) combine weight (shared by the halves)


def fuse_sub_pairs(pairs, p: int) -> FusedGroups:
    """Collapse a (T, K*P) sub-expert pair list to (T, K) ORIGINAL-expert
    groups for the fused dual-sparse kernel: one dispatched row per original
    pair (halving traffic at P=2), FULL vs MAJOR-only decided by which
    halves the policy kept. Exact under partial transformation (Eq. 13):
    the combine weight is shared and sub-expert outputs add, so
    c·(f_major + f_minor) == c·f_full and c·f_major is the mode-1 row the
    kernel computes by skipping minor-half tiles."""
    T, Kp = pairs.idx.shape
    K = Kp // p
    idx3 = pairs.idx.reshape(T, K, p)
    keep3 = pairs.keep.reshape(T, K, p)
    comb3 = pairs.combine.reshape(T, K, p)
    return FusedGroups(
        group=idx3[..., 0] // p,
        keep=keep3.any(-1),
        major_only=keep3[..., 0] & ~keep3[..., 1:].any(-1),
        combine=comb3[..., 0],
    )
