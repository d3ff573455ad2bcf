"""Worker-granularity expansion strategies — Atos's task/data-parallel blend.

Atos workers come in two flavours (paper section 3.2/3.3):

  * warp-sized worker, no intra-worker load balancing  -> ``expand_per_item``
  * CTA-sized worker + load-balancing search [Merrill/Baxter] inside the
    worker                                             -> ``expand_merge_path``

``expand_per_item`` assigns each popped task (a CSR row) to one lane-group
and pads the neighbor loop to ``max_degree`` — fast when degree variance is
low (mesh-like graphs), wasteful when it is high (scale-free graphs),
*exactly* the warp-worker behaviour measured in the paper.

``expand_merge_path`` flattens the wavefront's total neighbor work with a
vectorized *load-balancing search*: work item k binary-searches the exclusive
scan of the popped rows' degrees to find its source row.  Every lane receives
one unit of work regardless of degree skew — the paper's data-parallel LB,
retargeted at the 8x128 VPU.

The expansion schedule is a swappable component (DESIGN.md section 9): the
``backend`` argument dispatches ``expand_merge_path`` either to the jnp
implementation in this module (the bit-exact reference) or to the Pallas TPU
kernel with explicit VMEM BlockSpec tiling (``repro/kernels/frontier_expand``
— compiled on TPU, interpret mode elsewhere).  Both produce identical
outputs; the choice is pure performance and is searched by the server
autotuner (``server/autotune.py``).
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from .backend import STREAM, resolve_backend


def searchsorted_right(sorted_arr: jax.Array, values: jax.Array) -> jax.Array:
    """Vectorized upper_bound: index of first element > value.

    jnp.searchsorted is available but we keep an explicit branchless binary
    search so the Pallas kernel and the reference share the exact schedule.
    """
    n = sorted_arr.shape[0]
    lo = jnp.zeros(values.shape, jnp.int32)
    hi = jnp.full(values.shape, n, jnp.int32)
    bits = max(1, (n).bit_length())
    for _ in range(bits):
        mid = (lo + hi) // 2
        go_right = sorted_arr[jnp.clip(mid, 0, n - 1)] <= values
        lo = jnp.where(go_right & (mid < hi), mid + 1, lo)
        hi = jnp.where(go_right, hi, jnp.minimum(hi, mid))
    return lo


def owner_of_units(scan: jax.Array, budget: int) -> jax.Array:
    """``searchsorted_right(scan, arange(budget))`` for a non-decreasing
    ``scan`` of non-negative ints: the number of scan entries <= k, as one
    scatter-add of ones at the scan values and a prefix sum over the
    budget (no per-unit search; sorted indices, so no scatter sort)."""
    marks = jnp.zeros((budget,), jnp.int32).at[scan].add(
        1, mode="drop", indices_are_sorted=True)
    return jnp.cumsum(marks)


def adjacency_of(graph) -> Tuple[jax.Array, jax.Array, object]:
    """``(row_ptr, cols, overlay)`` of a canonical or slotted graph.

    The uniform unpacking for algorithm bodies: a canonical
    :class:`~repro.graph.csr.CSRGraph` yields its flat ``col_idx`` and
    ``overlay=None``; a :class:`~repro.graph.slotted.SlottedView` yields
    its slab slots plus the :class:`~repro.graph.slotted.Overlay` needed
    by :func:`gather_neighbors`.  ``row_ptr`` is canonical either way, so
    every degree-sum consumer (LBS, chunking, budgets) is representation
    agnostic.
    """
    overlay = getattr(graph, "overlay", None)
    if overlay is None:
        return graph.row_ptr, graph.col_idx, None
    return graph.row_ptr, graph.slab_col, overlay


def gather_neighbors(row_ptr: jax.Array, cols: jax.Array, src: jax.Array,
                     edge: jax.Array, overlay=None) -> jax.Array:
    """Neighbor id at flat canonical edge index ``edge`` of row ``src``.

    ``overlay=None`` is the canonical CSR flat gather.  With an
    :class:`~repro.graph.slotted.Overlay`, the within-row offset
    ``edge - row_ptr[src]`` reads the row's slab prefix while below
    ``slab_len[src]`` and its overlay tail beyond — both sorted with the
    prefix strictly below the tail, so the result is bit-identical to the
    canonical gather on the same edge set.  Broadcasts over any matching
    ``src``/``edge`` shape (flat LBS work lists and [n, max_degree] padded
    loops alike).
    """
    if overlay is None:
        return cols[jnp.clip(edge, 0, cols.shape[0] - 1)]
    off = edge - row_ptr[src]
    s_len = overlay.slab_len[src]
    s_idx = overlay.slab_ptr[src] + off
    s_val = cols[jnp.clip(s_idx, 0, cols.shape[0] - 1)]
    o_idx = overlay.ovl_ptr[src] + off - s_len
    o_val = overlay.ovl_col[jnp.clip(o_idx, 0,
                                     overlay.ovl_col.shape[0] - 1)]
    return jnp.where(off < s_len, s_val, o_val)


class Expansion(NamedTuple):
    """Flattened (source, neighbor) work units for one wavefront."""

    src: jax.Array        # [W] source row per work unit (chunk member)
    nbr: jax.Array        # [W] neighbor / column id
    owner: jax.Array      # [W] index into the popped wavefront of the source
    valid: jax.Array      # [W] bool
    total: jax.Array      # scalar int32 — true number of work units


def chunk_degrees(heads: jax.Array, widths, valid: jax.Array,
                  row_ptr: jax.Array) -> jax.Array:
    """Degree-sum of each ``[head, head + width)`` chunk (0 where invalid).

    ``widths=None`` is the single-row case (degree of ``head``), kept as
    the exact pre-granularity expression so G = 1 traces are unchanged.
    """
    safe = jnp.where(valid, heads, 0)
    if widths is None:
        return jnp.where(valid, row_ptr[safe + 1] - row_ptr[safe], 0)
    n = row_ptr.shape[0] - 1
    end = jnp.clip(safe + jnp.asarray(widths, jnp.int32), 0, n)
    return jnp.where(valid, row_ptr[end] - row_ptr[safe], 0)


def chunk_row_of(row_ptr: jax.Array, head: jax.Array, rank: jax.Array,
                 widths, max_width: int) -> jax.Array:
    """Source row of within-chunk edge offset ``rank`` in ``[head, head+w)``.

    The second, intra-chunk level of the load-balancing search: the LBS
    distributes work units across *chunks* by degree-sum; this locates each
    unit's member row by a ``max_width``-round compare-count against the
    chunk's local row offsets — O(G) broadcast compares, no gather-heavy
    binary search, the same VPU-friendly shape as the Pallas LBS kernel's
    owner count (``kernels/frontier_expand``).  ``max_width <= 1`` is the
    identity.  The ``j < width`` guard matters on device-local CSR slices
    (shard/partition.py): row_ptr entries past the chunk's block are not
    monotone there, so rows outside the chunk must never be counted.
    """
    if max_width <= 1:
        return head
    n = row_ptr.shape[0] - 1
    widths = jnp.asarray(widths, jnp.int32)
    base = row_ptr[head]
    local = jnp.zeros(head.shape, jnp.int32)
    for j in range(1, max_width):
        before = row_ptr[jnp.clip(head + j, 0, n)] - base
        local = local + ((j < widths) & (before <= rank)).astype(jnp.int32)
    return jnp.clip(head + local, 0, jnp.maximum(n - 1, 0))


def expand_merge_path(
    items: jax.Array,
    valid: jax.Array,
    row_ptr: jax.Array,
    col_idx: jax.Array,
    work_budget: int,
    backend: str = "jnp",
    widths: jax.Array | None = None,
    max_width: int = 1,
    overlay=None,
) -> Expansion:
    """CTA-style expansion: load-balancing search over the wavefront.

    items[i] is a vertex id (or EMPTY).  ``work_budget`` is the static upper
    bound on sum(degree(items)) processed per wavefront; excess work units are
    masked out (the caller sizes the budget; tests assert no truncation for
    the configured fetch sizes).

    ``backend`` selects the LBS implementation: ``"jnp"`` runs the reference
    below, ``"pallas"`` dispatches to the TPU kernel
    (``kernels/frontier_expand/ops.frontier_expand``), ``"auto"`` picks by
    hardware.  Outputs are bit-identical across backends (tested).

    With ``widths`` (and its static bound ``max_width``), item ``i`` is a
    *chunk* of ``widths[i]`` consecutive rows headed at ``items[i]``
    (core/task.py): the LBS balances over chunk degree-sums and each work
    unit's true source row is recovered by :func:`chunk_row_of`, so a
    coarse-grained wavefront still spreads its neighbor work evenly across
    every lane — the paper's granularity x load-balancing composition.
    """
    if backend == STREAM:
        # internal megakernel value (checked before resolve_backend, which
        # rejects it): the same LBS schedule, but neighbor slices are
        # DMA-streamed HBM->VMEM inside the fused drain kernel
        # (kernels/drain_loop/csr_stream; imported lazily — it imports
        # Expansion and the schedule helpers from this module)
        from ..kernels.drain_loop.csr_stream import expand_stream

        return expand_stream(items, valid, row_ptr, col_idx, work_budget,
                             widths=widths, max_width=max_width,
                             overlay=overlay)
    if resolve_backend(backend) == "pallas":
        # imported lazily: kernels/ imports Expansion from this module
        from ..kernels.frontier_expand.ops import frontier_expand

        return frontier_expand(items, valid, row_ptr, col_idx, work_budget,
                               widths=widths, max_width=max_width,
                               overlay=overlay)
    safe = jnp.where(valid, items, 0)
    deg = chunk_degrees(items, widths, valid, row_ptr)
    scan = jnp.cumsum(deg)                       # inclusive scan of degrees
    total = scan[-1] if scan.shape[0] > 0 else jnp.int32(0)

    k = jnp.arange(work_budget, dtype=jnp.int32)
    owner = owner_of_units(scan, work_budget)    # which popped item owns unit k
    owner = jnp.clip(owner, 0, items.shape[0] - 1)
    excl = scan - deg                            # exclusive scan
    rank = k - excl[owner]                       # edge offset within the chunk
    head = safe[owner]
    src = (head if widths is None else
           chunk_row_of(row_ptr, head, rank, widths[owner], max_width))
    in_range = k < total
    # per-unit reads index the wavefront-sized tables, not the [n] row_ptr
    edge = (row_ptr[safe] - excl)[owner] + k
    nbr = gather_neighbors(row_ptr, col_idx, src, edge, overlay=overlay)
    return Expansion(
        src=jnp.where(in_range, src, 0),
        nbr=jnp.where(in_range, nbr, 0),
        owner=jnp.where(in_range, owner, 0),
        valid=in_range,
        total=total,
    )


def expand_per_item(
    items: jax.Array,
    valid: jax.Array,
    row_ptr: jax.Array,
    col_idx: jax.Array,
    max_degree: int,
    overlay=None,
) -> Expansion:
    """Warp-style expansion: one padded neighbor loop per popped item.

    Produces a [n_items * max_degree] work list; lanes beyond a row's true
    degree are masked (idle lanes = the warp-worker load imbalance the paper
    measures on scale-free graphs).
    """
    safe = jnp.where(valid, items, 0)
    deg = jnp.where(valid, row_ptr[safe + 1] - row_ptr[safe], 0)
    j = jnp.arange(max_degree, dtype=jnp.int32)
    edge = row_ptr[safe][:, None] + j[None, :]          # [n, max_degree]
    in_range = j[None, :] < deg[:, None]
    nbr = gather_neighbors(row_ptr, col_idx,
                           jnp.broadcast_to(safe[:, None], edge.shape),
                           edge, overlay=overlay)
    src = jnp.broadcast_to(safe[:, None], nbr.shape)
    owner = jnp.broadcast_to(
        jnp.arange(items.shape[0], dtype=jnp.int32)[:, None], nbr.shape
    )
    return Expansion(
        src=jnp.where(in_range, src, 0).reshape(-1),
        nbr=jnp.where(in_range, nbr, 0).reshape(-1),
        owner=jnp.where(in_range, owner, 0).reshape(-1),
        valid=in_range.reshape(-1),
        total=jnp.sum(deg),
    )
