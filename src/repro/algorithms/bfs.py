"""BFS case study — BSP Dijkstra (Alg 1) vs. speculative relaxed-barrier BFS (Alg 2).

BSP BFS is level-synchronous: the frontier at depth d is fully expanded
behind a barrier before depth d+1 starts, so every vertex is first reached on
a shortest path (zero overwork).  Speculative BFS pops a *wavefront* of
vertices from the Atos queue; because the queue mixes depths, a vertex may be
reached first via a non-shortest path and later re-relaxed — the paper's
concurrency-vs-overwork trade.  Both produce exact shortest hop distances.

GPU->TPU adaptation: ``atomicMin(&neighbor.dist, ...)`` becomes a vectorized
``dist.at[nbr].min(cand)`` scatter-min over the wavefront's expanded edges
(order-independent, deterministic).  "Was my relaxation the winner?" is
answered by comparing against the pre-scatter value — the same information
CUDA's atomicMin returns.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp

from ..core import (STATE_COUNTERS, ChunkCodec, SchedulerConfig, WorkCounter,
                    chunk_degrees, adjacency_of, chunk_seeds, coalesce_chunks,
                    expand_merge_path, expand_per_item, flatten_chunks)
from ..graph.csr import CSRGraph
from ..obs import Trace
from ..obs.spans import APPLY, EXPAND, PUSH
from ..runtime.program import AtosProgram, ProgramContext
from ..runtime.programs import reject_unknown_params
from .common import chunking_for, default_work_budget, max_degree_of

INF = jnp.int32(0x7FFFFFFF)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class BFSState:
    dist: jax.Array
    counter: WorkCounter


# --------------------------------------------------------------------- BSP
@partial(jax.jit, static_argnums=(2,))
def _bsp_level(graph: CSRGraph, carry, max_degree: int):
    """One level-synchronous step over a dense frontier mask."""
    dist, frontier, level, work = carry
    deg = graph.row_ptr[1:] - graph.row_ptr[:-1]
    # expand every frontier vertex, padded to max_degree (data-parallel flat)
    vids = jnp.arange(graph.num_vertices, dtype=jnp.int32)
    j = jnp.arange(max_degree, dtype=jnp.int32)
    edge = graph.row_ptr[:-1][:, None] + j[None, :]
    in_row = j[None, :] < deg[:, None]
    active = in_row & frontier[:, None]
    nbr = graph.col_idx[jnp.clip(edge, 0, graph.num_edges - 1)]
    cand = jnp.where(active, level + 1, INF)
    new_dist = dist.at[jnp.where(active, nbr, 0)].min(
        jnp.where(active, cand, INF), mode="drop"
    )
    new_frontier = new_dist < dist  # improved this level
    work = work + jnp.sum(active.astype(jnp.int32))
    return new_dist, new_frontier, level + 1, work


def bfs_bsp(graph: CSRGraph, source: int, max_levels: int | None = None):
    """Level-synchronous BFS; host loop per level = discrete BSP kernels."""
    n = graph.num_vertices
    max_degree = int(jnp.max(graph.degrees()))
    dist = jnp.full((n,), INF, jnp.int32).at[source].set(0)
    frontier = jnp.zeros((n,), bool).at[source].set(True)
    level = jnp.int32(0)
    work = jnp.int32(0)
    max_levels = max_levels or n
    levels = 0
    frontier_sizes = []
    while bool(jnp.any(frontier)) and levels < max_levels:
        frontier_sizes.append(int(jnp.sum(frontier)))
        dist, frontier, level, work = _bsp_level(
            graph, (dist, frontier, level, work), max_degree
        )
        levels += 1
    return dist, {"levels": levels, "work": int(work),
                  "frontier_sizes": frontier_sizes}


# ------------------------------------------------------------- speculative
def init_state(graph: CSRGraph, source: int) -> BFSState:
    """Job-parameterized initial state: dist=INF except the source."""
    n = graph.num_vertices
    return BFSState(
        dist=jnp.full((n,), INF, jnp.int32).at[source].set(0),
        counter=WorkCounter.zero(),
    )


def make_wavefront_fn(graph: CSRGraph, strategy: str, work_budget: int,
                      max_degree: int, backend: str = "jnp",
                      codec: ChunkCodec | None = None,
                      split_threshold: int | None = None,
                      owner_block: int | None = None,
                      formation_row_ptr=None):
    """Reusable speculative-BFS wavefront body.

    Closed over the graph only — the returned ``f(items, valid, state)`` is a
    pure :data:`~repro.core.scheduler.WavefrontFn`, so it can drive a
    single-tenant run (``bfs_speculative``) or serve as one tenant's
    expansion logic inside the multi-job task server (``repro.server``).

    ``backend`` selects the merge-path LBS implementation (jnp reference vs
    the Pallas kernel) — outputs are bit-identical either way (DESIGN.md
    section 9).

    ``codec`` makes the body chunk-aware (DESIGN.md section 12): popped
    tasks decode to ``(head, width)`` row runs, the merge-path LBS balances
    chunk degree-*sums*, and improved neighbors are re-coalesced into
    chunks at push time (bounded by ``split_threshold`` and the shard
    ``owner_block``; ``formation_row_ptr`` is the *global* row_ptr — pushed
    vertices may be remote, so formation degree sums cannot come from a
    device-local CSR slice).  The identity codec (G = 1) reproduces the
    single-vertex body bit-for-bit.
    """
    codec = codec or ChunkCodec(1)
    g = codec.granularity
    form_rp = graph.row_ptr if formation_row_ptr is None else formation_row_ptr

    rp, cols, overlay = adjacency_of(graph)

    def f(items, valid, state: BFSState):
        with jax.named_scope(EXPAND):
            safe = jnp.where(valid, items, 0)
            heads, widths = codec.decode(safe)
            if strategy == "merge_path":  # CTA worker: task+data-parallel LB
                ex = expand_merge_path(heads, valid, rp, cols,
                                       work_budget, backend=backend,
                                       widths=widths, max_width=g,
                                       overlay=overlay)
                # chunks whose rows spill past the work budget are re-queued
                # whole (progress is guaranteed: budget >= max_degree >= any
                # formed chunk's degree-sum, so the first popped task always
                # expands fully).
                deg = chunk_degrees(heads, widths, valid, graph.row_ptr)
                excl = jnp.cumsum(deg) - deg
                truncated = valid & (excl + deg > work_budget)
            else:                         # warp worker: task-parallel only
                flat_v, flat_valid, _ = flatten_chunks(heads, widths, valid,
                                                       g)
                ex = expand_per_item(flat_v, flat_valid, rp, cols,
                                     max_degree, overlay=overlay)
                truncated = jnp.zeros_like(valid)
            # edges owned by truncated chunks are excluded entirely: the
            # chunk is re-queued whole and will relax+push on re-expansion
            # (if we relaxed the prefix now but suppressed its pushes, the
            # re-expansion would see "no improvement" and the neighbor would
            # never be enqueued).  (per_item never truncates; its ex.owner
            # indexes the flattened per-vertex lanes, so the mask below is
            # the chunk one only on the merge_path branch.)
            live = (ex.valid & ~truncated[ex.owner]
                    if strategy == "merge_path" else ex.valid)
        with jax.named_scope(APPLY):
            # one-row chunks: a unit's source is its popped head, so its
            # distance comes from the wavefront-sized table
            src_dist = (state.dist[heads][ex.owner]
                        if strategy == "merge_path" and g == 1
                        else state.dist[ex.src])
            cand = jnp.where(live, src_dist + 1, INF)
            before = state.dist[ex.nbr]
            tgt = jnp.where(live, ex.nbr, 0)
            new_dist = state.dist.at[tgt].min(jnp.where(live, cand, INF),
                                              mode="drop")
            improved = live & (cand < before)
            # within-wavefront dedup (beyond-paper): several lanes may
            # improve the same neighbor; only the winning relaxation needs
            # to requeue it.  On the GPU this would need extra atomics; in
            # the deterministic wavefront a scatter-min over lane ids is
            # free and cuts overwork.
            n = state.dist.shape[0]
            lanes = jnp.arange(ex.nbr.shape[0], dtype=jnp.int32)
            first_lane = jnp.full((n,), ex.nbr.shape[0], jnp.int32).at[
                jnp.where(improved, ex.nbr, n)
            ].min(jnp.where(improved, lanes, ex.nbr.shape[0]), mode="drop")
            improved &= first_lane[ex.nbr] == lanes
            counter = state.counter.add(jnp.sum(jnp.where(
                valid & ~truncated, widths, 0)))
            if strategy == "merge_path":
                counter = counter.add_units(ex.total, work_budget)
        with jax.named_scope(PUSH):
            # improved (deduplicated) neighbors re-coalesce into chunks;
            # truncated chunks are re-queued whole, unchanged.
            out_new, new_mask, n_splits = coalesce_chunks(
                ex.nbr, improved, codec, form_rp,
                split_threshold=split_threshold, owner_block=owner_block)
            counter = counter.add_splits(n_splits)
            out_items = jnp.concatenate([out_new,
                                         jnp.where(truncated, items, 0)])
            out_mask = jnp.concatenate([new_mask, truncated])
        return out_items, out_mask, BFSState(dist=new_dist, counter=counter)

    return f


def make_program(graph: CSRGraph, cfg: SchedulerConfig, *,
                 queue_capacity: int | None = None,
                 **params) -> AtosProgram:
    """Speculative BFS as **one** :class:`AtosProgram` — the single
    definition every execution policy (single/fused/sharded x
    persistent/discrete) drains unchanged (DESIGN.md section 11).

    ``params``: ``source`` (init-only), ``strategy`` (merge_path |
    per_item), ``work_budget``.  Static bounds (budget, max degree) come
    from the global graph so a sharded run traces the identical body on
    every device; ``dist`` merges by ``pmin`` — the exact union of all
    relaxations — and the work counter by delta-psum.  ``cfg.granularity``
    sets the chunk width G (DESIGN.md section 12): tasks are packed
    ``(head, width)`` row runs, routed and stolen by their head vertex, and
    the seed is a width-1 chunk.
    """
    source = int(params.pop("source", 0))
    strategy = params.pop("strategy", "merge_path")
    work_budget = params.pop("work_budget", None)
    reject_unknown_params("bfs", params)
    n = graph.num_vertices
    max_degree = max_degree_of(graph)
    budget = default_work_budget(graph, cfg.wavefront, work_budget,
                                 max_degree=max_degree)
    codec, threshold, owner_block = chunking_for(
        graph, cfg, budget if strategy == "merge_path" else None)

    def make_body(local_graph: CSRGraph, ctx: ProgramContext):
        return make_wavefront_fn(local_graph, strategy, budget, max_degree,
                                 backend=ctx.backend, codec=codec,
                                 split_threshold=threshold,
                                 owner_block=owner_block,
                                 formation_row_ptr=graph.row_ptr)

    def dirty_seeds(applied, state):
        from ..stream.incremental import bfs_dirty_seeds  # lazy: stream layer

        return bfs_dirty_seeds(applied, state, codec=codec,
                               split_threshold=threshold,
                               owner_block=owner_block)

    return AtosProgram(
        name="bfs",
        init=lambda: (init_state(graph, source),
                      jnp.asarray(chunk_seeds([source], codec,
                                              graph.row_ptr))),
        make_body=make_body,
        result=lambda s: s.dist,
        merge={"dist": "pmin", "counter": "work_counter"},
        task_vertex=codec.head,
        task_width=codec.width,
        counters=STATE_COUNTERS,
        ideal_work=n,
        default_queue_capacity=queue_capacity or max(4 * n, 1024),
        dirty_seeds=dirty_seeds,
    )


def bfs_speculative(
    graph: CSRGraph,
    source: int,
    cfg: SchedulerConfig,
    strategy: str = "merge_path",
    work_budget: int | None = None,
    queue_capacity: int | None = None,
    trace: Trace | None = None,
) -> Tuple[jax.Array, dict]:
    """Relaxed-barrier BFS on the Atos scheduler.

    Thin driver over :func:`repro.runtime.execute`: builds the BFS
    :class:`AtosProgram` and drains it under ``cfg``'s resolved execution
    policy.  ``strategy``: "merge_path" (CTA-style) or "per_item"
    (warp-style).  Under the sharded topology (``cfg.num_shards > 1`` or
    ``topology="sharded"``) distances are bit-identical to the
    single-device run.  ``trace`` (an :class:`~repro.obs.Trace`) collects
    one row per round (per device when sharded).
    """
    from ..runtime import execute  # lazy: runtime.api imports this module

    program = make_program(graph, cfg, queue_capacity=queue_capacity,
                           source=source, strategy=strategy,
                           work_budget=work_budget)
    state, _, info = execute(program, graph, cfg,
                             queue_capacity=queue_capacity, trace=trace)
    return state.dist, info
