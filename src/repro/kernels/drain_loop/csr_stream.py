"""Double-buffered DMA of CSR row slices — the megakernel's expansion feed.

Outside the megernel, ``expand_merge_path`` gathers each work unit's
neighbor id straight out of the full ``col_idx`` array; inside a resident
kernel the CSR lives in HBM and the win comes from *streaming* exactly the
row slices the claimed chunks need into VMEM, with the copy for chunk
``i+1`` in flight while chunk ``i``'s slice is being written out — the
classic two-deep DMA pipeline.

``stream_row_slices`` is that pipeline: for each popped chunk it issues
``make_async_copy(col_idx[start_i : start_i + budget]) -> scratch[slot]``
against a ``[2, budget]`` VMEM scratch and a 2-lane DMA semaphore, waits
the previous slot, and lands the slice in row ``i`` of the output.

``expand_stream`` is the merge-path expansion rebuilt on top of it: the
degree scan, owner search, and intra-chunk row recovery are shared with
``core.frontier`` (imported, not copied), and only the neighbor gather
changes — ``nbr[k] = slices[owner_k, k - excl[owner_k]]``.  The merge-path
layout makes the two gathers *provably identical*: work unit ``k``'s edge
index is ``row_ptr[head_owner] + rank`` with ``rank < budget``, i.e. it
always falls inside its owner's streamed slice.  Dispatched as the
internal ``backend="stream"`` value of ``expand_merge_path``
(core/backend.py), which the runtime selects for megakernel bodies.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core.backend import resolve_interpret
from ...core.frontier import (Expansion, chunk_degrees, chunk_row_of,
                              searchsorted_right)
from ...graph.slotted import SLAB_SLACK

_N_BUFFERS = 2  # double buffering: one slice landing, one in flight


def _stream_kernel(n_items, budget, starts_ref, hbm_ref, out_ref,
                   scratch, sem):
    """Copy ``hbm[starts[i] : starts[i]+budget]`` into ``out[i]`` for every
    ``i``, two DMAs deep.  ``starts`` rides in SMEM (scalar loop bounds),
    ``hbm_ref`` stays unblocked in ANY/HBM — only the slices touch VMEM.
    ``n_items`` is static and positive: ``stream_row_slices`` short-circuits
    an empty wavefront before the launch, so the prologue DMA below never
    reads ``starts_ref[0]`` out of bounds."""

    def dma(slot, i):
        return pltpu.make_async_copy(
            hbm_ref.at[pl.ds(starts_ref[i], budget)],
            scratch.at[slot], sem.at[slot])

    dma(0, 0).start()

    def body(i, carry):
        slot = jax.lax.rem(i, _N_BUFFERS)

        @pl.when(i + 1 < n_items)
        def _():
            dma(jax.lax.rem(i + 1, _N_BUFFERS), i + 1).start()

        dma(slot, i).wait()
        out_ref[pl.ds(i, 1), :] = scratch[slot].reshape(1, budget)
        return carry

    jax.lax.fori_loop(0, n_items, body, 0)


def stream_row_slices(col_idx: jax.Array, starts: jax.Array, budget: int,
                      *, interpret=None) -> jax.Array:
    """``[n_items, budget]`` — ``col_idx[starts[i] : starts[i]+budget]``
    per item, streamed HBM→VMEM through the double-buffered pipeline.

    ``col_idx`` is padded by ``budget`` zeros so a slice starting near the
    tail never reads out of bounds (padding lanes are masked off by the
    caller's ``in_range``); DMA lengths must be static on TPU, only the
    starts may be dynamic.
    """
    n_items = int(starts.shape[0])
    if n_items == 0:
        # static: no items, no launch — the kernel's prologue DMA would
        # read starts_ref[0] out of bounds (and a zero-row output block
        # cannot be padded at all)
        return jnp.zeros((0, budget), col_idx.dtype)
    padded = jnp.concatenate(
        [col_idx, jnp.zeros((budget,), col_idx.dtype)])
    starts = jnp.clip(jnp.asarray(starts, jnp.int32), 0, col_idx.shape[0])
    return pl.pallas_call(
        functools.partial(_stream_kernel, n_items, budget),
        out_shape=jax.ShapeDtypeStruct((n_items, budget), col_idx.dtype),
        in_specs=[pl.BlockSpec(memory_space=pltpu.MemorySpace.SMEM),
                  pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY)],
        out_specs=pl.BlockSpec(memory_space=pltpu.MemorySpace.VMEM),
        scratch_shapes=[pltpu.VMEM((_N_BUFFERS, budget), col_idx.dtype),
                        pltpu.SemaphoreType.DMA((_N_BUFFERS,))],
        interpret=resolve_interpret(interpret),
    )(starts, padded)


def expand_stream(
    items: jax.Array,
    valid: jax.Array,
    row_ptr: jax.Array,
    col_idx: jax.Array,
    work_budget: int,
    widths: jax.Array | None = None,
    max_width: int = 1,
    overlay=None,
    *,
    interpret=None,
) -> Expansion:
    """Merge-path expansion over DMA-streamed row slices.

    Bit-identical to the jnp reference in ``core.frontier``: the LBS
    schedule (degree scan, owner search, chunk-row recovery) is the shared
    code, and for every in-range work unit ``rank = k - excl[owner]``
    satisfies ``rank < deg_owner <= budget`` — the streamed slice
    ``col_idx[row_ptr[head_owner] :+ budget]`` therefore contains exactly
    the edge the flat gather would read.  Out-of-range lanes are zeroed on
    both paths.

    Traffic note: because DMA lengths must be static, every popped item
    streams a FULL ``work_budget``-length slice — ``n_items x
    work_budget`` elements per expansion regardless of the chunks' actual
    degrees, so on low-degree frontiers the streamed byte volume can
    exceed the flat gather's touched footprint by a large factor.  The
    roofline section of ``benchmarks/bench_megakernel.py`` accounts for
    this term explicitly (DESIGN.md §14).
    """
    safe = jnp.where(valid, items, 0)
    deg = chunk_degrees(items, widths, valid, row_ptr)
    scan = jnp.cumsum(deg)
    total = scan[-1] if scan.shape[0] > 0 else jnp.int32(0)

    k = jnp.arange(work_budget, dtype=jnp.int32)
    owner = searchsorted_right(scan, k)
    owner = jnp.clip(owner, 0, items.shape[0] - 1)
    excl = scan - deg
    rank = k - excl[owner]
    head = safe[owner]
    src = (head if widths is None else
           chunk_row_of(row_ptr, head, rank, widths[owner], max_width))
    in_range = k < total
    if overlay is None:
        slices = stream_row_slices(col_idx, row_ptr[safe], work_budget,
                                   interpret=interpret)
        nbr = slices[owner, jnp.clip(rank, 0, work_budget - 1)]
    else:
        # Slotted graph (graph/slotted.py): ``col_idx`` is the flat slab
        # array.  A chunk's slab span is bounded by the slab-slack
        # invariant: sum(cap_r) <= 4 * sum(max(1, deg_r)) <= 4 *
        # (degree_sum + width) <= 4 * (work_budget + max_width), so one
        # static-length DMA per chunk starting at ``slab_ptr[head]`` covers
        # every member row's slab.  The extra over-fetch (4x on top of the
        # full-budget slice above) is the price of in-place commits; the
        # overlay tail is tiny and compaction-bounded, so it reads straight
        # from its own flat array instead of the stream.
        slab_budget = SLAB_SLACK * (work_budget + max_width)
        slices = stream_row_slices(col_idx, overlay.slab_ptr[safe],
                                   slab_budget, interpret=interpret)
        edge = row_ptr[head] + rank
        off = edge - row_ptr[src]
        s_idx = overlay.slab_ptr[src] + off - overlay.slab_ptr[head]
        s_val = slices[owner, jnp.clip(s_idx, 0, slab_budget - 1)]
        o_idx = overlay.ovl_ptr[src] + off - overlay.slab_len[src]
        o_val = overlay.ovl_col[jnp.clip(o_idx, 0,
                                         overlay.ovl_col.shape[0] - 1)]
        nbr = jnp.where(off < overlay.slab_len[src], s_val, o_val)
    return Expansion(
        src=jnp.where(in_range, src, 0),
        nbr=jnp.where(in_range, nbr, 0),
        owner=jnp.where(in_range, owner, 0),
        valid=in_range,
        total=total,
    )
