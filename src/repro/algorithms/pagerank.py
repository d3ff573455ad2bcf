"""PageRank case study — BSP push (Alg 3) vs. asynchronous push (Alg 4).

Residual ("push") PageRank: every vertex holds (rank, residue).  Processing a
vertex harvests its residue into its rank and pushes ``lambda * res / deg`` to
each out-neighbor's residue.  Converged when all residues <= eps; the result
solves  pr = (1-lambda)*1 + lambda * A^T D^{-1} pr  to within eps*deg slack.

PageRank is *naturally unordered* (Dijkstra's don't-care non-determinism):
relaxing the barrier never produces wrong answers, only a different
propagation schedule.  The paper shows the async schedule does *less* total
work because high-residue hubs get re-processed promptly instead of once per
global sweep — our work counters reproduce that (benchmarks/bench_table4).

GPU->TPU adaptation: ``atomicExch(residue+v, 0)`` = gather residues then
scatter zeros (the wavefront pops each vertex at most once — duplicates in
the wavefront are de-duplicated by keeping the first occurrence, which is
what the atomic exchange guarantees on the GPU); ``atomicAdd`` = scatter-add.
Algorithm 4's "exclusively reserve Check_Size vertices" rotating re-scan is a
per-wavefront rotating window driven by a cursor in the state.
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core import (ChunkCodec, SchedulerConfig, WorkCounter, adjacency_of,
                    chunk_degrees, chunk_seeds, coalesce_chunks,
                    expand_merge_path, flatten_chunks)
from ..graph.csr import CSRGraph
from ..runtime.program import AtosProgram, ProgramContext
from ..runtime.programs import reject_unknown_params
from .common import chunking_for, default_work_budget, max_degree_of


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class PRState:
    rank: jax.Array       # f32 [n]
    residue: jax.Array    # f32 [n]
    in_queue: jax.Array   # bool [n] — presence bit (see adaptation note)
    check_cursor: jax.Array  # int32 — Alg 4 rotating re-scan cursor
    counter: WorkCounter


# Adaptation note (recorded in DESIGN.md): Alg 4 tolerates duplicate queue
# entries because a duplicate pop's atomicExch harvests zero residue (a
# no-op).  In the deterministic wavefront queue, duplicates instead flood the
# ring buffer (the checker re-finds hot vertices every rotation), so we
# de-duplicate at *push* time with a presence bit — the observable schedule
# (each vertex re-processed while residue > eps) is identical, queue pressure
# is bounded by n.


def _push_wavefront(graph: CSRGraph, damping: float, work_budget: int,
                    backend: str = "jnp", codec: ChunkCodec | None = None):
    """Shared core: harvest residues of popped chunks, push to neighbors.

    Chunk-aware (DESIGN.md section 12): a popped task is a ``(head, width)``
    run of rows (core/task.py); the whole chunk is harvested or re-queued
    as a unit, the LBS balances chunk degree-sums, and every expanded edge's
    contribution reads its true member row's residue/degree.  The identity
    codec (G = 1) is the original per-vertex core.
    """
    codec = codec or ChunkCodec(1)
    g = codec.granularity

    def push(items, valid, state: PRState):
        n = state.rank.shape[0]
        k = items.shape[0]
        safe = jnp.where(valid, items, 0)
        heads, widths = codec.decode(safe)
        # de-duplicate within the wavefront (atomicExch semantics): keep the
        # first occurrence of each chunk head.  Chunks never overlap (the
        # presence bit gates every enqueue per vertex), so head identity is
        # chunk identity.
        order = jnp.arange(k, dtype=jnp.int32)
        first_idx = jnp.full((n,), k, jnp.int32)
        first_idx = first_idx.at[heads].min(jnp.where(valid, order, k),
                                            mode="drop")
        is_first = valid & (first_idx[heads] == order)

        # chunks spilling past the work budget are not harvested; they are
        # re-queued whole (same discipline as speculative BFS; formation
        # caps every chunk's degree-sum at the budget, so the first popped
        # task always expands fully).
        deg = chunk_degrees(heads, widths, is_first, graph.row_ptr)
        excl = jnp.cumsum(deg) - deg
        truncated = is_first & (excl + deg > work_budget)
        process = is_first & ~truncated

        # harvest: dense mask avoids duplicate-index scatter hazards
        flat_v, flat_valid, flat_owner = flatten_chunks(heads, widths,
                                                        valid, g)
        proc_flat = flat_valid & process[flat_owner]
        popped = jnp.zeros((n,), bool).at[
            jnp.where(proc_flat, flat_v, n)
        ].set(True, mode="drop")
        rank = state.rank + jnp.where(popped, state.residue, 0.0)
        residue = jnp.where(popped, 0.0, state.residue)
        # popped vertices leave the queue; truncated ones stay (re-queued)
        trunc_flat = flat_valid & truncated[flat_owner]
        trunc_mask = jnp.zeros((n,), bool).at[
            jnp.where(trunc_flat, flat_v, n)
        ].set(True, mode="drop")
        in_queue = jnp.where(popped & ~trunc_mask, False, state.in_queue)

        rp, cols, overlay = adjacency_of(graph)
        ex = expand_merge_path(heads, process, rp, cols,
                               work_budget, backend=backend,
                               widths=widths, max_width=g, overlay=overlay)
        # per-edge contribution from the edge's true source row: ex.src is
        # the chunk member owning the edge, its residue read pre-harvest.
        # One-row chunks share it per popped task: computed on the
        # wavefront, then read per edge from that [k] table.
        src = heads if g == 1 else ex.src
        row_deg = jnp.maximum(
            graph.row_ptr[src + 1] - graph.row_ptr[src], 1
        ).astype(jnp.float32)
        res_src = jnp.where(popped[src], state.residue[src], 0.0)
        contrib = damping * res_src / row_deg
        contrib = jnp.where(ex.valid,
                            contrib[ex.owner] if g == 1 else contrib, 0.0)
        residue = residue.at[jnp.where(ex.valid, ex.nbr, 0)].add(contrib,
                                                                 mode="drop")
        counter = state.counter.add(jnp.sum(jnp.where(process, widths, 0)))
        return residue, rank, in_queue, counter, truncated

    return push


def pagerank_bsp(
    graph: CSRGraph,
    damping: float = 0.85,
    eps: float = 1e-6,
    max_iters: int = 1000,
    trace: list | None = None,
) -> Tuple[jax.Array, dict]:
    """Alg 3: process the whole frontier (all residues > eps) per sweep."""
    n = graph.num_vertices
    deg = jnp.maximum(graph.degrees(), 1).astype(jnp.float32)
    edge_src = _edge_sources(graph)  # host-side, hoisted out of the jit

    @jax.jit
    def sweep(rank, residue):
        active = residue > eps
        res = jnp.where(active, residue, 0.0)
        rank = rank + res
        residue = jnp.where(active, 0.0, residue)
        # dense edge-parallel push: for every edge (u -> v) add contribution
        contrib_per_v = damping * res / deg
        adds = contrib_per_v[edge_src]
        residue = residue.at[graph.col_idx].add(adds)
        return rank, residue, jnp.sum(active.astype(jnp.int32))

    rank = jnp.zeros((n,), jnp.float32)
    residue = jnp.full((n,), 1.0 - damping, jnp.float32)
    iters, work = 0, 0
    while iters < max_iters:
        if not bool(jnp.any(residue > eps)):
            break
        rank, residue, nactive = sweep(rank, residue)
        work += int(nactive)
        iters += 1
        if trace is not None:
            trace.append(int(nactive))
    return rank, {"iters": iters, "work": work}


_EDGE_SRC_CACHE: dict = {}


def _edge_sources(graph: CSRGraph) -> jax.Array:
    """[m] source vertex of every CSR edge (cached per graph identity)."""
    key = id(graph.row_ptr)
    if key not in _EDGE_SRC_CACHE:
        import numpy as np

        rp = np.asarray(graph.row_ptr)
        src = np.repeat(np.arange(graph.num_vertices, dtype=np.int32),
                        np.diff(rp))
        _EDGE_SRC_CACHE[key] = jnp.asarray(src)
    return _EDGE_SRC_CACHE[key]


def init_state(graph: CSRGraph, damping: float = 0.85,
               seed_count: int | None = None) -> Tuple[PRState, jax.Array]:
    """Job-parameterized initial state + the seed tasks that prime the queue.

    Every vertex starts with residue ``1 - damping``; the first ``seed_count``
    vertices (default: all) are pre-enqueued, the rest are found by the
    rotating re-scan.
    """
    n = graph.num_vertices
    n_seed = n if seed_count is None else min(n, seed_count)
    state = PRState(
        rank=jnp.zeros((n,), jnp.float32),
        residue=jnp.full((n,), 1.0 - damping, jnp.float32),
        in_queue=jnp.arange(n, dtype=jnp.int32) < n_seed,
        check_cursor=jnp.int32(0),
        counter=WorkCounter.zero(),
    )
    return state, jnp.arange(n_seed, dtype=jnp.int32)


def make_wavefront_fns(
    graph: CSRGraph,
    wavefront: int,
    n_check: int,
    damping: float = 0.85,
    eps: float = 1e-6,
    work_budget: int | None = None,
    backend: str = "jnp",
    check_block=None,
    max_degree: int | None = None,
    codec: ChunkCodec | None = None,
    split_threshold: int | None = None,
    owner_block: int | None = None,
    formation_row_ptr=None,
):
    """Reusable async-PageRank wavefront bodies: ``(f, on_empty, stop)``.

    ``wavefront`` sizes ``on_empty``'s padding (it must emit a full-width
    wavefront), ``n_check`` is the rotating re-scan window.  All three
    returned callables are pure and job-parameterized, shared by the
    single-tenant driver (``pagerank_async``) and the task server.
    ``backend`` selects the merge-path LBS implementation (DESIGN.md §9).

    ``check_block=(start, length)`` restricts the rotating re-scan to one
    contiguous vertex block — the sharded driver passes each device its
    owned block so re-scan tasks are born on their owner and the presence
    bit stays single-writer (DESIGN.md section 10).  Both values may be
    traced scalars (they derive from ``lax.axis_index`` under shard_map).
    ``max_degree`` must then be passed explicitly (precomputed from the
    global graph): the budget's progress-guarantee floor cannot concretize
    the device-local CSR slice inside the trace.

    ``codec`` (+ ``split_threshold``/``owner_block``/``formation_row_ptr``,
    see :func:`~repro.algorithms.common.chunking_for`) makes the bodies
    chunk-aware: the rotating re-scan's over-eps vertices — a naturally
    run-heavy stream — coalesce into ``(head, width)`` chunk tasks at push
    time (DESIGN.md section 12).
    """
    n = graph.num_vertices
    work_budget = default_work_budget(graph, wavefront, work_budget,
                                      max_degree=max_degree)
    codec = codec or ChunkCodec(1)
    form_rp = (graph.row_ptr if formation_row_ptr is None
               else formation_row_ptr)
    push = _push_wavefront(graph, damping, work_budget, backend=backend,
                           codec=codec)
    n_check = min(n_check, n)
    if check_block is None:
        block_start, block_len = jnp.int32(0), jnp.int32(n)
    else:
        block_start = jnp.asarray(check_block[0], jnp.int32)
        block_len = jnp.asarray(check_block[1], jnp.int32)

    def scan_window(cursor):
        """Next ``n_check`` ids of the rotating block scan + validity.

        Lanes past the block length are masked off (a short or empty block
        — the last shards of an uneven partition — must not rescan other
        owners' vertices, and must never enqueue one vertex twice in one
        window)."""
        j = jnp.arange(n_check, dtype=jnp.int32)
        ids = block_start + (cursor + j) % jnp.maximum(block_len, 1)
        return jnp.where(j < block_len, ids, 0), j < block_len

    def chunk_window(check_ids, over):
        """Coalesce the window's over-eps vertices into chunk tasks."""
        return coalesce_chunks(check_ids, over, codec, form_rp,
                               split_threshold=split_threshold,
                               owner_block=owner_block)

    def f(items, valid, state: PRState):
        residue, rank, in_queue, counter, truncated = push(items, valid, state)
        # rotating residual re-scan (Alg 4 lines 11-14): each wavefront checks
        # the next n_check vertices and enqueues those above eps that are not
        # already queued (presence bit — see adaptation note above).
        check_ids, in_window = scan_window(state.check_cursor)
        over = in_window & (residue[check_ids] > eps) & ~in_queue[check_ids]
        in_queue = in_queue.at[jnp.where(over, check_ids, n)].set(
            True, mode="drop")
        out_scan, scan_mask, n_splits = chunk_window(check_ids, over)
        counter = counter.add_splits(n_splits)
        new_state = PRState(rank=rank, residue=residue, in_queue=in_queue,
                            check_cursor=state.check_cursor + n_check,
                            counter=counter)
        out = jnp.concatenate([out_scan, jnp.where(truncated, items, 0)])
        mask = jnp.concatenate([scan_mask, truncated])
        return out, mask, new_state

    def on_empty(state: PRState):
        check_ids, in_window = scan_window(state.check_cursor)
        over = (in_window & (state.residue[check_ids] > eps)
                & ~state.in_queue[check_ids])
        in_queue = state.in_queue.at[jnp.where(over, check_ids, n)].set(
            True, mode="drop")
        out_scan, scan_mask, n_splits = chunk_window(check_ids, over)
        new_state = dataclasses.replace(
            state, in_queue=in_queue,
            check_cursor=state.check_cursor + n_check,
            counter=state.counter.add_splits(n_splits),
        )
        pad = jnp.zeros((wavefront,), jnp.int32)
        return (jnp.concatenate([out_scan, pad]),
                jnp.concatenate([scan_mask, jnp.zeros((wavefront,), bool)]),
                new_state)

    def stop(state: PRState):
        # converged when nothing is above eps anywhere (O(n) reduce per
        # wavefront — measured as part of the scheduler's fixed cost).
        return jnp.max(state.residue) <= eps

    return f, on_empty, stop


def make_program(graph: CSRGraph, cfg: SchedulerConfig, *,
                 queue_capacity: int | None = None,
                 **params) -> AtosProgram:
    """Async push PageRank as **one** :class:`AtosProgram` (DESIGN.md §11).

    ``params``: ``damping``, ``eps``, ``check_size``, ``work_budget``,
    ``seed_count``.  The program declares ``empty_means_done=False`` — the
    rotating rescan legally refills a drained queue, so only ``stop``
    (max residue <= eps) ends the drain; this replaces the old implicit
    "``on_empty`` is set, ignore queue size" inference.  Under the sharded
    topology the body's rescan window is restricted to the device's owned
    vertex block (``ctx.shard``), residue/rank merge by delta-psum, the
    presence bit by or-delta, and the cursor — advanced by the same
    constant on every device — stays collective-free.
    """
    from ..shard.partition import block_size  # lazy: shard imports runtime

    damping = float(params.pop("damping", 0.85))
    eps = float(params.pop("eps", 1e-6))
    check_size = int(params.pop("check_size", 64))
    work_budget = params.pop("work_budget", None)
    seed_count = params.pop("seed_count", None)
    reject_unknown_params("pagerank", params)
    n = graph.num_vertices
    max_degree = max_degree_of(graph)
    budget = default_work_budget(graph, cfg.wavefront, work_budget,
                                 max_degree=max_degree)
    codec, threshold, owner_block = chunking_for(graph, cfg, budget)
    n_check = min(cfg.num_workers * check_size, n)
    # the rescan blocks must match the partitioner's ownership map exactly,
    # or rescan tasks are born off-owner and break the single-writer merges
    blk = block_size(n, cfg.num_shards)
    fns_cache: dict = {}

    def _fns(local_graph: CSRGraph, ctx: ProgramContext):
        chunk_kw = dict(codec=codec, split_threshold=threshold,
                        owner_block=owner_block,
                        formation_row_ptr=graph.row_ptr)
        if ctx.sharded:
            # traced shard index — rebuild inside the shard_map, no caching
            start = jnp.asarray(ctx.shard, jnp.int32) * blk
            check_block = (start, jnp.clip(jnp.int32(n) - start, 0, blk))
            return make_wavefront_fns(
                local_graph, ctx.wavefront, n_check=n_check, damping=damping,
                eps=eps, work_budget=budget, backend=ctx.backend,
                check_block=check_block, max_degree=max_degree, **chunk_kw)
        # body / on_empty / stop share one closure build per host context
        key = (id(local_graph.row_ptr), ctx.wavefront, ctx.backend)
        if key not in fns_cache:
            fns_cache[key] = (local_graph, make_wavefront_fns(
                local_graph, ctx.wavefront, n_check=n_check, damping=damping,
                eps=eps, work_budget=budget, backend=ctx.backend,
                max_degree=max_degree, **chunk_kw))
        return fns_cache[key][1]

    # stop reads only the (merged, replicated) state — build it once on the
    # host from the global graph; bodies are rebuilt per execution context.
    _, _, stop = _fns(graph, ProgramContext(cfg.wavefront, cfg.num_workers,
                                            cfg.backend))

    if seed_count is None:
        cap = queue_capacity or max(8 * n, 1024)
        seed_count = min(n, max(1, cap // 2))

    def dirty_seeds(applied, state):
        from ..stream.incremental import pagerank_dirty_seeds  # lazy

        return pagerank_dirty_seeds(applied, state, damping=damping,
                                    eps=eps, codec=codec,
                                    split_threshold=threshold,
                                    owner_block=owner_block)

    def init():
        state, seeds = init_state(graph, damping, seed_count=seed_count)
        # the dense seed frontier is the coarsening jackpot: consecutive
        # vertex ids pack into maximal chunks (bounded by the split
        # threshold and shard blocks), so the warm-up rounds shrink ~G-fold
        return state, jnp.asarray(chunk_seeds(
            np.asarray(seeds), codec, graph.row_ptr,
            split_threshold=threshold, owner_block=owner_block))

    return AtosProgram(
        name="pagerank",
        init=init,
        make_body=lambda g, ctx: _fns(g, ctx)[0],
        make_on_empty=lambda g, ctx: _fns(g, ctx)[1],
        result=lambda s: s.rank,
        stop=stop,
        empty_means_done=False,
        merge={"rank": "sum_delta", "residue": "sum_delta",
               "in_queue": "or_delta", "check_cursor": "replicated",
               "counter": "work_counter"},
        task_vertex=codec.head,
        task_width=codec.width,
        work=lambda s: s.counter.work,
        splits=lambda s: s.counter.splits,
        ideal_work=n,
        default_queue_capacity=queue_capacity or max(8 * n, 1024),
        dirty_seeds=dirty_seeds,
    )


def pagerank_async(
    graph: CSRGraph,
    cfg: SchedulerConfig,
    damping: float = 0.85,
    eps: float = 1e-6,
    check_size: int = 64,
    work_budget: int | None = None,
    queue_capacity: int | None = None,
    trace: list | None = None,
) -> Tuple[jax.Array, dict]:
    """Alg 4: queue-driven asynchronous PageRank on the Atos scheduler.

    Thin driver over :func:`repro.runtime.execute`.  Under the sharded
    topology each shard's rotating re-scan covers its owned vertex block,
    residue deltas merge by psum every round, and ranks match the
    single-device schedule within the usual ``eps * deg`` slack.
    """
    from ..runtime import execute  # lazy: runtime.api imports this module

    program = make_program(graph, cfg, queue_capacity=queue_capacity,
                           damping=damping, eps=eps, check_size=check_size,
                           work_budget=work_budget)
    state, _, info = execute(program, graph, cfg,
                             queue_capacity=queue_capacity, trace=trace)
    info["max_residue"] = float(jnp.max(state.residue))
    return state.rank, info


def pagerank_reference(graph: CSRGraph, damping: float = 0.85,
                       iters: int = 200) -> jax.Array:
    """Dense power iteration oracle: pr = (1-d)*1 + d*A^T D^{-1} pr."""
    n = graph.num_vertices
    deg = jnp.maximum(graph.degrees(), 1).astype(jnp.float32)
    edge_src = _edge_sources(graph)
    pr = jnp.full((n,), 1.0 - damping, jnp.float32)
    for _ in range(iters):
        contrib = damping * pr / deg
        pr = jnp.full((n,), 1.0 - damping, jnp.float32).at[graph.col_idx].add(
            contrib[edge_src]
        )
    return pr
