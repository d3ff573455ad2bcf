"""jit'd public wrapper: global stream compaction via the Pallas tile kernel.

Call paths (wired by the backend layer, ``core/backend.py``):

  * ``core/queue.TaskQueue.push(..., backend="pallas"|"auto")`` uses
    :func:`compact` as its slot-reservation engine — which makes this kernel
    the push hot path of the scheduler (``core/scheduler.wavefront_step``),
    of every ``MultiQueue`` lane the task server drives
    (``server/engine.TaskServer``), and of any autotuner candidate with
    ``SchedulerConfig(backend="pallas")``.  All three case-study algorithms
    (BFS / PageRank / coloring) push through it under that config.
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
from .kernel import TILE, compact_tiles_pallas


@functools.partial(jax.jit, static_argnames=("interpret",))
def compact(items: jax.Array, mask: jax.Array,
            interpret: bool | None = None):
    """([N], [N]bool) -> ([N] compacted-then-zeros, count) — kernel-backed.

    Stable (order-preserving) and bit-identical to
    ``kernels/queue_compact/ref.compact_ref`` — asserted per-tile by
    ``tests/test_kernels.py`` and end-to-end against ``TaskQueue``'s
    prefix-sum reservation by ``tests/test_backend.py``.
    """
    interpret = resolve_interpret(interpret)
    n = items.shape[0]
    local, counts = compact_tiles_pallas(items, mask, interpret=interpret)
    n_tiles = local.shape[0]
    tile_offs = jnp.cumsum(counts) - counts            # phase 2: global stitch
    # element (t, j) for j < counts[t] lands at tile_offs[t] + j; the rest
    # add 0 at the next tile's first slot, so the indices stay sorted and
    # no sort precedes the scatter
    j = jnp.arange(TILE, dtype=jnp.int32)
    live = j[None, :] < counts[:, None]
    dst = tile_offs[:, None] + jnp.minimum(j[None, :], counts[:, None])
    out = jnp.zeros((n_tiles * TILE,), jnp.int32).at[dst.reshape(-1)].add(
        jnp.where(live, local, 0).reshape(-1), mode="drop",
        indices_are_sorted=True)
    return out[:n], jnp.sum(counts)
