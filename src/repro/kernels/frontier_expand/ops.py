"""jit'd public wrapper: full CSR wavefront expansion via the LBS kernel.

Call paths (wired by the backend layer, ``core/backend.py``):

  * ``core/frontier.expand_merge_path(..., backend="pallas"|"auto")``
    dispatches here — which makes this kernel the hot path of the
    merge-path strategy in ``algorithms/bfs.py`` and
    ``algorithms/pagerank.py``, of every server job built from their
    runtime program factories (``server/jobs.JobRegistry.build``), and of
    any autotuner candidate with ``SchedulerConfig(backend="pallas")``.
  * ``benchmarks/bench_kernels.py`` times it against the jnp reference and
    emits the comparison to ``BENCH_kernels.json``.

``interpret=None`` defers to :func:`repro.core.backend.resolve_interpret`:
compiled on TPU, interpreter elsewhere — a real-TPU run never silently
interprets.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ...core.backend import resolve_interpret
from ...core.frontier import (Expansion, chunk_degrees, chunk_row_of,
                              gather_neighbors)
from .kernel import lbs_pallas


@functools.partial(jax.jit,
                   static_argnames=("budget", "interpret", "max_width"))
def frontier_expand(items, valid, row_ptr, col_idx, budget: int,
                    interpret: bool | None = None,
                    widths=None, max_width: int = 1,
                    overlay=None) -> Expansion:
    """Drop-in replacement for ``core.frontier.expand_merge_path`` that runs
    the merge-path search as a Pallas TPU kernel.

    Bit-identical to the reference by construction (same masking, same
    owner/rank definitions) — asserted by ``tests/test_kernels.py`` and,
    end-to-end, by the backend-parity tests in ``tests/test_algorithms.py``.

    Chunked wavefronts (``widths`` + static ``max_width``; core/task.py)
    feed the kernel the *chunk degree-sum* scan — the LBS itself is
    granularity-agnostic, it balances whatever scan it is given — and each
    work unit's member row is recovered afterwards by the shared
    :func:`~repro.core.frontier.chunk_row_of` compare-count (O(max_width)
    broadcast compares, the same VPU shape as the kernel's owner count), so
    both backends stay bit-identical at every granularity.
    """
    interpret = resolve_interpret(interpret)
    safe = jnp.where(valid, items, 0)
    deg = chunk_degrees(items, widths, valid, row_ptr)
    scan = jnp.cumsum(deg)
    total = scan[-1] if scan.shape[0] > 0 else jnp.int32(0)

    owner, rank = lbs_pallas(scan, budget, interpret=interpret)
    owner = jnp.clip(owner, 0, items.shape[0] - 1)
    head = safe[owner]
    src = (head if widths is None else
           chunk_row_of(row_ptr, head, rank, widths[owner], max_width))
    k = jnp.arange(budget, dtype=jnp.int32)
    in_range = k < total
    edge = row_ptr[safe][owner] + rank
    # the LBS kernel only computes (owner, rank); the gather lives out here,
    # so a slotted graph just swaps the flat read for the two-level one
    nbr = gather_neighbors(row_ptr, col_idx, src, edge, overlay=overlay)
    return Expansion(
        src=jnp.where(in_range, src, 0),
        nbr=jnp.where(in_range, nbr, 0),
        owner=jnp.where(in_range, owner, 0),
        valid=in_range,
        total=total,
    )
