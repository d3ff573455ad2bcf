"""Graph-coloring case study — BSP speculative greedy (Alg 5) vs. relaxed (Alg 6).

Both variants use *speculative greedy* coloring [Gebremedhin-Manne]: assign
each vertex the minimum color not used by its neighbors (reading possibly
stale neighbor colors), then detect conflicts and re-color.  The BSP variant
barriers between the assign and detect phases; the relaxed variant fuses them
in one uberkernel — task sign distinguishes assign (+) from detect (-),
exactly Alg 6's encoding (we use +v+1 / -(v+1) so vertex 0 is signable).

Speculation cost: adjacent vertices colored in the same wavefront read each
other's *stale* colors and may pick the same color -> conflict -> recolor.
The paper shows this is driven by "consecutive queue entries are neighbors"
(meaningful vertex IDs); we reproduce their 6.4 permutation experiment.

GPU->TPU adaptations (DESIGN.md):
  * conflict tie-break — Alg 5/6 re-add any vertex that sees its color on a
    neighbor; on the GPU, timing asymmetry breaks color-pick symmetry, but a
    deterministic lockstep wavefront would livelock (both endpoints forever
    re-pick the same color).  We use the standard ID tie-break: the
    higher-ID endpoint re-colors.  Same fixed point, guaranteed progress.
  * forbidden-color bitset — CUDA builds a shared-memory forbidden array per
    vertex; we scatter the wavefront's merge-path-expanded neighbor colors
    into a [wavefront, max_colors] table and take argmin (vectorizes over
    the 8x128 VPU).
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp

from ..core import (STATE_COUNTERS, ChunkCodec, SchedulerConfig, WorkCounter,
                    adjacency_of, chunk_degrees, chunk_seeds, coalesce_chunks,
                    expand_merge_path, fitting_units, flatten_chunks,
                    gather_neighbors, owner_broadcast)
from ..graph.csr import CSRGraph
from ..obs import Trace
from ..obs.spans import APPLY, EXPAND, PUSH
from ..runtime.program import AtosProgram, ProgramContext
from ..runtime.programs import reject_unknown_params
from .common import chunking_for, default_work_budget, max_degree_of


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ColorState:
    colors: jax.Array   # int32 [n], -1 = uncolored
    counter: WorkCounter  # assign tasks processed (Table 4 unit: ratio vs n)
    #: rounds whose assigns built the full-palette forbidden table (some
    #: lane had every one of the first ``FIRST_COLORS`` colors taken)
    wide_picks: jax.Array = dataclasses.field(
        default_factory=lambda: jnp.int32(0))


def _gather_neighbor_colors(graph, vids, valid, max_degree):
    """[w, max_degree] neighbor colors, -1 padded."""
    rp, cols, overlay = adjacency_of(graph)
    safe = jnp.where(valid, vids, 0)
    deg = jnp.where(valid, rp[safe + 1] - rp[safe], 0)
    j = jnp.arange(max_degree, dtype=jnp.int32)
    edge = rp[safe][:, None] + j[None, :]
    in_row = j[None, :] < deg[:, None]
    nbr = gather_neighbors(rp, cols,
                           jnp.broadcast_to(safe[:, None], edge.shape),
                           edge, overlay=overlay)
    return nbr, in_row


#: width of the first forbidden-color table; wider palettes are only
#: scanned in the rounds where some lane has every color below it taken
FIRST_COLORS = 1024


def _first_free_color(lane, nbr_colors, live, num_lanes, max_colors):
    """Per lane: smallest color in [0, max_colors) on none of its live
    edges, and whether the full-palette table was built for it.

    The forbidden table is built by one scatter over the edges — O(edges +
    lanes x colors), never the [lanes, edges, colors] one-hot, which at a
    Graph500 hub degree would not fit any device.  The table first spans
    only ``FIRST_COLORS`` colors; the full ``max_colors`` table is built
    only when a lane finds all of those taken, so the answer is the same.
    """
    seen = live & (nbr_colors >= 0)

    def first_free(width):
        forbidden = jnp.zeros((num_lanes, width), jnp.bool_).at[
            jnp.where(seen, lane, num_lanes), jnp.where(seen, nbr_colors, 0)
        ].set(True, mode="drop")
        return (jnp.argmin(forbidden, axis=1).astype(jnp.int32),  # 1st False
                jnp.all(forbidden, axis=1))

    if max_colors <= FIRST_COLORS:
        return first_free(max_colors)[0], jnp.bool_(False)
    pick, full = first_free(FIRST_COLORS)
    wide = jnp.any(full)
    return jax.lax.cond(wide, lambda: first_free(max_colors)[0],
                        lambda: pick), wide


def _min_free_color(colors, nbr, in_row, max_colors):
    """Per row: smallest color in [0, max_colors) unused by valid neighbors."""
    nbr_colors = jnp.where(in_row, colors[nbr], -1)          # [w, d]
    onehot = jax.nn.one_hot(nbr_colors, max_colors, dtype=jnp.bool_)
    forbidden = jnp.any(onehot, axis=1)                      # [w, c]
    return jnp.argmin(forbidden, axis=1).astype(jnp.int32)   # first False


def _priority(v):
    """Deterministic pseudo-random priority (Gebremedhin-Manne symmetry
    breaking).  A pure ID tie-break serializes lattice graphs into diagonal
    waves under the deterministic wavefront; hashing restores the O(log n)
    expected rounds the paper's GPU timing noise provides for free."""
    h = (v.astype(jnp.uint32) * jnp.uint32(2654435761)) ^ jnp.uint32(0x9E3779B9)
    h = (h ^ (h >> 13)) * jnp.uint32(0x85EBCA6B)
    return h ^ (h >> 16)


def _loses(v, u):
    """Does ``v`` yield to neighbor ``u``?  Total order (hash, id) — id
    breaks the (rare) hash collisions."""
    pv, pu = _priority(v), _priority(u)
    return (pu < pv) | ((pu == pv) & (u < v))


def _conflicts(colors, vids, valid, nbr, in_row):
    """Does v share a color with a higher-priority neighbor? (v recolors)."""
    safe = jnp.where(valid, vids, 0)
    my = colors[safe]
    clash = in_row & (colors[nbr] == my[:, None]) & \
        _loses(safe[:, None], nbr) & (my[:, None] >= 0)
    return jnp.any(clash, axis=1) & valid


def coloring_bsp(
    graph: CSRGraph,
    max_iters: int = 10000,
    trace: list | None = None,
) -> Tuple[jax.Array, dict]:
    """Alg 5: assign-all / barrier / detect-all, double buffered."""
    n = graph.num_vertices
    max_degree = int(jnp.max(graph.degrees()))
    max_colors = max_degree + 1

    @jax.jit
    def assign(colors, frontier):
        vids = jnp.arange(n, dtype=jnp.int32)
        nbr, in_row = _gather_neighbor_colors(graph, vids, frontier, max_degree)
        pick = _min_free_color(colors, nbr, in_row, max_colors)
        return jnp.where(frontier, pick, colors)

    @jax.jit
    def detect(colors, frontier):
        vids = jnp.arange(n, dtype=jnp.int32)
        nbr, in_row = _gather_neighbor_colors(graph, vids, frontier, max_degree)
        return _conflicts(colors, vids, frontier, nbr, in_row)

    colors = jnp.full((n,), -1, jnp.int32)
    frontier = jnp.ones((n,), bool)
    iters, work = 0, 0
    while iters < max_iters and bool(jnp.any(frontier)):
        fsize = int(jnp.sum(frontier))
        colors = assign(colors, frontier)
        frontier = detect(colors, frontier)
        work += fsize
        iters += 1
        if trace is not None:
            trace.append(fsize)
    return colors, {"iters": iters, "work": work}


def init_state(graph: CSRGraph,
               codec: ChunkCodec | None = None,
               owner_block: int | None = None,
               split_threshold: int | None = None
               ) -> Tuple["ColorState", jax.Array]:
    """Job-parameterized initial state + seed tasks (an assign per vertex).

    With a coarse ``codec`` the every-vertex frontier packs into maximal
    ``(head, width)`` chunks — one assign-chunk task per run — encoded with
    the usual +(task + 1) sign convention (DESIGN.md section 12).
    """
    import numpy as np

    n = graph.num_vertices
    state = ColorState(colors=jnp.full((n,), -1, jnp.int32),
                       counter=WorkCounter.zero())
    if codec is None or codec.granularity == 1:
        return state, jnp.arange(1, n + 1, dtype=jnp.int32)
    chunks = chunk_seeds(np.arange(n), codec, graph.row_ptr,
                         split_threshold=split_threshold,
                         owner_block=owner_block)
    return state, jnp.asarray(chunks) + 1


def make_wavefront_fn(graph: CSRGraph, work_budget: int,
                      fused: bool = True,
                      max_degree: int | None = None,
                      backend: str = "jnp",
                      codec: ChunkCodec | None = None,
                      split_threshold: int | None = None,
                      owner_block: int | None = None,
                      formation_row_ptr=None):
    """Reusable fused assign/detect uberkernel body (Alg 6).

    Task encoding: +(task+1) = assign, -(task+1) = detect, where ``task``
    is a packed ``(head, width)`` chunk code (core/task.py; the raw vertex
    id at granularity 1, reproducing the classic ±(v+1) scheme
    bit-for-bit).  An assign chunk colors ``width`` consecutive vertices
    and queues one detect chunk for the same run; conflicted vertices
    re-coalesce into new assign chunks.  A wavefront mixes both kinds (and
    multiple speculation depths).  The returned ``f`` is a pure WavefrontFn
    shared by the single-tenant driver (``coloring_async``) and the task
    server.

    Both phases read the wavefront's neighbors through one merge-path
    expansion of at most ``work_budget`` edges (the BFS discipline):
    chunks whose rows spill past the budget are re-queued whole and
    unchanged, so per-round work follows the degrees actually popped, not
    ``wavefront x max_degree``.  Progress is guaranteed because the budget
    is at least the largest chunk degree-sum.  ``backend`` selects the LBS
    implementation, with bit-identical colors either way.

    ``fused=False`` makes phase B read the *pre-wavefront* colors instead of
    phase A's same-wavefront commits.  The sharded driver (repro/shard)
    needs this: remote assigns from the same epoch are invisible anyway, so
    uniform epoch-start reads keep detection independent of which shard a
    task ran on — detection is merely deferred one epoch, never lost
    (DESIGN.md section 10).  ``max_degree`` may be passed explicitly when
    the body is built inside a traced context (a shard_map) where the
    device-local CSR slice cannot be concretized.
    """
    n = graph.num_vertices
    if max_degree is None:
        max_degree = int(jnp.max(graph.degrees()))
    max_colors = max_degree + 1
    codec = codec or ChunkCodec(1)
    g = codec.granularity
    form_rp = (graph.row_ptr if formation_row_ptr is None
               else formation_row_ptr)
    rp, cols, overlay = adjacency_of(graph)

    def f(items, valid, state: ColorState):
        with jax.named_scope(EXPAND):
            is_assign = valid & (items > 0)
            is_detect = valid & (items < 0)
            codes = jnp.where(is_assign, items - 1, -items - 1)
            codes = jnp.where(valid, codes, 0)
            heads, widths = codec.decode(codes)
            scan = jnp.cumsum(chunk_degrees(heads, widths, valid,
                                            graph.row_ptr))
            truncated = valid & (scan > work_budget)
            ex = expand_merge_path(heads, valid, rp, cols, work_budget,
                                   backend=backend, widths=widths,
                                   max_width=g, overlay=overlay)
            # truncation is a suffix of the wavefront: the units of the
            # chunks that fit are a prefix of the lanes
            units = jnp.arange(work_budget, dtype=jnp.int32)
            live = ex.valid & (units < fitting_units(scan, work_budget))
            # explode chunk tasks into their member vertices: lane kind
            # (assign vs detect) is a chunk property, vertices are per
            # member; each edge's lane is its source row's slot in that
            # layout (a one-row chunk's lane is its task's).  A unit's kind
            # is its task's sign: live units belong to valid tasks.
            vids, flat_valid, owner = flatten_chunks(heads, widths, valid, g)
            flat_run = flat_valid & ~truncated[owner]
            flat_assign = flat_run & is_assign[owner]
            flat_detect = flat_run & is_detect[owner]
            if g == 1:
                kind, = owner_broadcast(ex.excl, work_budget, items)
                lane = ex.owner
            else:
                kind, head = owner_broadcast(ex.excl, work_budget, items,
                                             heads)
                lane = ex.owner * g + (ex.src - head)
            num_lanes = vids.shape[0]

        with jax.named_scope(APPLY):
            # ---- phase A: assigns (all reads see pre-wavefront colors =
            # stale speculation, exactly the GPU race the paper analyzes)
            pick, wide = _first_free_color(
                lane, state.colors[ex.nbr], live & (kind > 0),
                num_lanes, max_colors)
            # duplicate assign tasks for one vertex cannot exist (1 assign
            # -> 1 detect -> at most 1 re-assign, and chunk members are
            # distinct), so this scatter has unique targets
            colors = state.colors.at[jnp.where(flat_assign, vids, n)].set(
                jnp.where(flat_assign, pick, 0), mode="drop")

            # ---- phase B: detects run on post-assign colors of THIS
            # wavefront (uberkernel fusion: later tasks see earlier tasks'
            # commits).  The unfused variant reads epoch-start colors so
            # detection is identical no matter which device processed the
            # wavefront (shard parity).
            detect_colors = colors if fused else state.colors
            if g == 1:
                my, = owner_broadcast(ex.excl, work_budget,
                                      detect_colors[heads])
            else:
                my = detect_colors[ex.src]
            clash = (live & (kind < 0) & (my >= 0)
                     & (detect_colors[ex.nbr] == my) & _loses(ex.src, ex.nbr))
            bad = jnp.zeros((num_lanes,), jnp.bool_).at[
                jnp.where(clash, lane, num_lanes)].set(True, mode="drop")
            bad &= flat_detect
            counter = state.counter.add(jnp.sum(flat_assign.astype(
                jnp.int32))).add_units(ex.total, work_budget)

        with jax.named_scope(PUSH):
            # conflicted vertices re-coalesce into assign chunks (identity
            # at G = 1: each bad vertex re-assigns alone, exactly the old
            # stream); truncated chunks are re-queued whole, unchanged.
            re_assign, re_mask, n_splits = coalesce_chunks(
                vids, bad, codec, form_rp, split_threshold=split_threshold,
                owner_block=owner_block)
            done_assign = is_assign & ~truncated
            out = jnp.concatenate([
                jnp.where(done_assign, -(codes + 1), 0),  # assign -> detect
                jnp.where(re_mask, re_assign + 1, 0),     # conflict -> again
                jnp.where(truncated, items, 0),
            ])
            mask = jnp.concatenate([done_assign, re_mask, truncated])
            counter = counter.add_splits(n_splits)
        return out, mask, ColorState(
            colors=colors, counter=counter,
            wide_picks=state.wide_picks + wide.astype(jnp.int32))

    return f


def make_program(graph: CSRGraph, cfg: SchedulerConfig, *,
                 queue_capacity: int | None = None,
                 **params) -> AtosProgram:
    """Speculative greedy coloring as **one** :class:`AtosProgram`
    (DESIGN.md section 11).

    The context picks the body variant: the single/fused topologies run the
    fused assign/detect uberkernel (Alg 6), the sharded topology the
    unfused one (detects read epoch-start colors), so results never depend
    on which device a same-epoch neighbor assign ran on.  Tasks are
    sign-encoded ±(task+1) chunk codes; ownership and occupancy follow the
    decoded chunk head/width (``task_vertex``/``task_width``).  Colors are
    single-writer per round, so both state fields merge by delta-psum.

    ``params``: ``dirty`` picks the streaming (repro/stream) incremental
    rule — ``"conflicts"`` (default) keeps carried colors and recolors only
    the losing endpoints of inserted same-colored edges (valid coloring,
    minimal work, but a *different* valid coloring than a from-scratch
    drain); ``"recolor"`` disables the rule, so delta batches trigger the
    conservative full reseed (bit-identical to from-scratch).
    ``work_budget`` caps the edges one wavefront expands, as for BFS and
    PageRank (default: :func:`~repro.algorithms.common.default_work_budget`).
    """
    dirty = params.pop("dirty", "conflicts")
    work_budget = params.pop("work_budget", None)
    reject_unknown_params("coloring", params)
    if dirty not in ("conflicts", "recolor"):
        raise ValueError(
            f"coloring dirty mode must be 'conflicts' or 'recolor', "
            f"got {dirty!r}")
    n = graph.num_vertices
    max_degree = max_degree_of(graph)
    budget = default_work_budget(graph, cfg.wavefront, work_budget,
                                 max_degree=max_degree)
    codec, threshold, owner_block = chunking_for(graph, cfg, budget)

    def make_body(local_graph: CSRGraph, ctx: ProgramContext):
        return make_wavefront_fn(local_graph, budget, fused=not ctx.sharded,
                                 max_degree=max_degree, backend=ctx.backend,
                                 codec=codec,
                                 split_threshold=threshold,
                                 owner_block=owner_block,
                                 formation_row_ptr=graph.row_ptr)

    def natural_code(t):
        return jnp.abs(jnp.asarray(t, jnp.int32)) - 1

    def conflict_seeds(applied, state):
        from ..stream.incremental import coloring_dirty_seeds  # lazy

        return coloring_dirty_seeds(applied, state, codec=codec,
                                    split_threshold=threshold,
                                    owner_block=owner_block)

    return AtosProgram(
        name="coloring",
        init=lambda: init_state(graph, codec, owner_block, threshold),
        make_body=make_body,
        result=lambda s: s.colors,
        merge={"colors": "sum_delta", "counter": "work_counter",
               "wide_picks": "sum_delta"},
        task_vertex=lambda t: codec.head(natural_code(t)),
        task_width=lambda t: codec.width(natural_code(t)),
        counters={**STATE_COUNTERS,
                  "wide_picks": lambda s: s.wide_picks},
        ideal_work=n,
        default_queue_capacity=queue_capacity or max(4 * n, 1024),
        dirty_seeds=conflict_seeds if dirty == "conflicts" else None,
    )


def coloring_async(
    graph: CSRGraph,
    cfg: SchedulerConfig,
    queue_capacity: int | None = None,
    trace: Trace | None = None,
) -> Tuple[jax.Array, dict]:
    """Alg 6: fused assign/detect uberkernel on the Atos queue.

    Thin driver over :func:`repro.runtime.execute`.  The sharded topology
    uses the *unfused* body (detects read epoch-start colors), so a
    full-width sharded run produces bit-identical colors for every shard
    count, including 1 (tested in tests/test_shard.py).
    """
    from ..runtime import execute  # lazy: runtime.api imports this module

    program = make_program(graph, cfg, queue_capacity=queue_capacity)
    state, _, info = execute(program, graph, cfg,
                             queue_capacity=queue_capacity, trace=trace)
    return state.colors, info


def validate_coloring(graph: CSRGraph, colors) -> bool:
    """Proper coloring: no edge joins two same-colored vertices; all
    colored.  Entries of ``col_idx`` past ``row_ptr[n]`` (spare capacity)
    are not edges."""
    import numpy as np

    c = np.asarray(colors)
    if (c < 0).any():
        return False
    rp = np.asarray(graph.row_ptr)
    ci = np.asarray(graph.col_idx)[:rp[-1]]
    src = np.repeat(np.arange(graph.num_vertices), np.diff(rp))
    return bool((c[src] != c[ci]).all())
