"""Persistent and discrete sharded drivers — one Atos drain, many devices.

Mirrors ``core/scheduler.py`` across a device mesh: the 1-D ``("shard",)``
ring, or — with ``cfg.mesh_shape = (rows, cols)`` — a 2-D ``("row", "col")``
mesh whose exchange is dimension-ordered per axis (DESIGN.md §16).  Each
device carries a queue replica (a 2-lane :class:`~repro.core.queue.
MultiQueue`: owned tasks + freshly stolen ones) and a full-size state replica
that is authoritative for its vertex block and reconciled every round by the
program's declarative merge spec (``runtime/program.build_merge``).  One
**round** is, in lockstep on every device:

  1. *deliver*  — (overlap mode only) push the previous round's staged
                  exchange arrivals into the LOCAL lane;
  2. *steal*    — occupancy-skew-triggered ring donation (shard/steal.py);
  3. *pop*      — one ``num_workers x fetch_size`` wavefront, stolen first;
  4. *body*     — the algorithm's existing wavefront fn on the local CSR
                  slice via the backend layer (runs even when the pop is
                  empty: a zero-valid wavefront is a no-op for BFS/coloring
                  and exactly the ``on_empty`` re-scan for PageRank);
  5. *exchange* — owner-split + per-axis all-to-all routing
                  (shard/exchange.py), optionally delta-compressed;
                  arrivals are pushed immediately (strict,
                  ``defer_rounds=0`` — bit-for-bit the historical schedule)
                  or staged for step 1 of the *next* round
                  (``defer_rounds=1`` — the double-buffered overlap: the
                  collective's latency hides behind the next round's
                  expansion of already-delivered tasks.  Legal under Atos
                  semantics: tasks are idempotent re-checks, so delaying
                  delivery one round changes the schedule, never the
                  fixpoint);
  6. *merge*    — replica reconciliation (pmin / delta-psum);
  7. *stop*     — ``psum`` the replica sizes *plus staged arrivals*: no
                  device exits while any device still has live or staged
                  work, and converged-but-idle devices keep serving
                  collectives until the global predicate fires.

``persistent_run_sharded`` wraps the whole drain in a ``shard_map``-wrapped
``lax.while_loop`` (zero host round-trips — the multi-device persistent
kernel); ``discrete_run_sharded`` dispatches one jitted sharded round per
host-loop iteration and can trace per-round exchange volume and occupancy
for the benchmarks.  Both honor ``SchedulerConfig``: ``num_shards`` picks
the mesh width, ``mesh_shape`` folds it 2-D, ``persistent`` picks the
driver, ``backend`` threads through to the kernels exactly as in the
single-device path.  On either driver a ``max_rounds`` (or ``stop``) exit
flushes the staging buffer back into the queue so segmented callers (the
streaming snapshot layer) never lose staged tasks.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..core.queue import EMPTY, MultiQueue, TaskQueue
from ..core.scheduler import QueueOps, SchedulerConfig, wavefront_step
from ..graph.csr import CSRGraph
from ..launch.mesh import make_shard_mesh, make_shard_mesh2d
from ..obs import Trace, stacked_rings, unstack_ring
from ..obs.spans import EXCHANGE
from ..runtime.program import AtosProgram, ProgramContext, build_merge
from .exchange import (LANE_LOCAL, NUM_LANES, delivered_width, pop_wavefront,
                       route_tasks)
from .partition import (ShardedCSR, owner_of, partition_graph,
                        place_partition, split_seeds)
from .steal import rebalance

AXIS = "shard"


def _shard_context(cfg: SchedulerConfig, shard, axes=AXIS) -> ProgramContext:
    """Context for building the body inside the shard_map trace.

    ``axes`` is the mesh axis name — the 1-D ``"shard"`` string or the 2-D
    ``("row", "col")`` tuple; jax collectives accept either form.
    """
    return ProgramContext(wavefront=cfg.wavefront,
                          num_workers=cfg.num_workers, backend=cfg.backend,
                          shard=shard, num_shards=cfg.num_shards,
                          axis_name=axes, granularity=cfg.granularity)


class ShardCounters(NamedTuple):
    """Per-device round accounting (int32 scalars inside the loop)."""

    rounds: jax.Array         # uniform by construction
    items: jax.Array          # valid tasks this device popped
    sent: jax.Array           # distinct tasks shipped to other owners
    route_dropped: jax.Array  # remote tasks lost to a narrow route buffer
    donated: jax.Array        # tasks this device donated to its successor
    stolen_run: jax.Array     # stolen tasks this device executed
    steal_rounds: jax.Array   # rounds the (uniform) steal trigger fired
    mis_routed: jax.Array     # popped tasks that violated ownership
    sent_row: jax.Array       # cross-device payload ints, row-axis hop
    sent_col: jax.Array       # cross-device payload ints, column-axis hop
    payload: jax.Array        # valid ints across all hop buffers
    padding: jax.Array        # EMPTY slots across all hop buffers
    wire: jax.Array           # metered wire ints (compressed words if on)
    deferred: jax.Array       # staged tasks delivered a round late
    overlap_rounds: jax.Array  # rounds that computed over a staged delivery

    @staticmethod
    def zero() -> "ShardCounters":
        z = jnp.int32(0)
        return ShardCounters(z, z, z, z, z, z, z, z, z, z, z, z, z, z, z)


@dataclasses.dataclass
class ShardRunStats:
    """Host-side run summary (per-device vectors are length num_shards)."""

    rounds: int
    items_processed: int
    dropped: int              # queue-replica overflow drops (sum)
    route_dropped: int
    exchanged: int            # distinct tasks delivered across shards (sum)
    donated: int              # tasks moved by stealing (sum)
    stolen_executed: int
    steal_rounds: int
    mis_routed: int           # must be 0: every task ran on its owner/thief
    per_device_items: np.ndarray
    per_device_sent: np.ndarray
    per_device_donated: np.ndarray
    final_sizes: np.ndarray
    # wire accounting (DESIGN.md §16) — a task relayed through both hops of
    # a 2-D mesh is carried twice, so payload_ints >= exchanged; 1-D runs
    # put all cross-device ints on the (single) column hop.
    exchanged_row: int = 0    # cross-device payload ints, row-axis hop
    exchanged_col: int = 0    # cross-device payload ints, column-axis hop
    payload_ints: int = 0     # valid ints carried by all hop buffers
    padding_ints: int = 0     # EMPTY slots those fixed-shape buffers carried
    wire_ints: int = 0        # metered wire: raw slots, or compressed words
    deferred_delivered: int = 0  # tasks that landed one round late (overlap)
    overlap_rounds: int = 0   # rounds overlapping compute with a delivery

    @property
    def occupancy_balance(self) -> float:
        """min/max of per-device processed items (1.0 = perfectly even)."""
        if self.per_device_items.size == 0:
            return 1.0
        hi = int(self.per_device_items.max())
        return float(self.per_device_items.min()) / hi if hi else 1.0

    @property
    def overlap_occupancy(self) -> float:
        """Fraction of rounds (busiest device) where staged arrivals were
        delivered while the wavefront also had work — the rounds whose
        exchange latency was actually hidden behind compute."""
        return self.overlap_rounds / self.rounds if self.rounds else 0.0

    def as_dict(self) -> dict:
        """Serialize into the canonical ``shard_run`` doc (obs/schema)."""
        from ..obs.schema import metric_doc  # lazy: obs is a leaf layer

        d = dataclasses.asdict(self)
        for k, v in d.items():
            if isinstance(v, np.ndarray):
                d[k] = v.tolist()
        d["occupancy_balance"] = self.occupancy_balance
        d["overlap_occupancy"] = self.overlap_occupancy
        return metric_doc("shard_run", **d)


# --------------------------------------------------------------- plumbing
def _make_queues(capacity: int, num_shards: int, seed_buf, seed_counts):
    """Stacked per-device 2-lane MultiQueue replicas, seeds pre-placed in
    each owner's LOCAL lane."""
    buf = np.full((num_shards, NUM_LANES, capacity), int(EMPTY),
                  dtype=np.int32)
    tails = np.zeros((num_shards, NUM_LANES), dtype=np.int32)
    seeds = np.asarray(seed_buf)
    counts = np.asarray(seed_counts)
    for d in range(num_shards):
        k = int(counts[d])
        if k > capacity:
            raise ValueError(
                f"shard {d} got {k} seed tasks > queue capacity {capacity}")
        buf[d, LANE_LOCAL, :k] = seeds[d, :k]
        tails[d, LANE_LOCAL] = k
    lanes = TaskQueue(
        buf=jnp.asarray(buf),
        head=jnp.zeros((num_shards, NUM_LANES), jnp.int32),
        tail=jnp.asarray(tails),
        dropped=jnp.zeros((num_shards, NUM_LANES), jnp.int32),
    )
    return MultiQueue(lanes=lanes, rr=jnp.zeros((num_shards,), jnp.int32))


def seed_queues(program: AtosProgram, seeds, num_vertices: int,
                num_shards: int, capacity: int) -> MultiQueue:
    """Owner-split ``seeds`` into stacked per-device queue replicas.

    Public piece of ``run_sharded``'s setup, used by the streaming driver
    (repro/stream) to place a dirty-seed frontier — or an empty one, as the
    snapshot-restore template — without re-running ``program.init()``.
    """
    seed_buf, seed_counts = split_seeds(seeds, num_vertices, num_shards,
                                        task_vertex=program.task_vertex)
    return _make_queues(capacity, num_shards, seed_buf, seed_counts)


def _local_view(tree):
    """Strip the leading per-device axis shard_map leaves on every leaf."""
    return jax.tree.map(lambda x: x[0], tree)


def _stacked_view(tree):
    return jax.tree.map(lambda x: x[None], tree)


def _mesh_axes(cfg: SchedulerConfig):
    """(axis name(s), mesh dims or None) for this config's mesh layout."""
    if cfg.mesh_shape is None:
        return AXIS, None
    rows, cols = cfg.mesh_shape
    if rows * cols != cfg.num_shards:
        raise ValueError(
            f"mesh_shape {cfg.mesh_shape} covers {rows * cols} devices but "
            f"num_shards is {cfg.num_shards}")
    return ("row", "col"), (rows, cols)


def _body_out_width(program: AtosProgram, parts: ShardedCSR,
                    cfg: SchedulerConfig, state0, mesh, axes) -> int:
    """Static width of the wavefront body's output buffer.

    Overlap mode needs the staged-arrivals buffer shape *before* the drain
    loop is built, and the default ``route_width`` is exactly the body's
    output width — recovered here by abstract evaluation (``eval_shape``
    traces nothing concrete and compiles nothing) of one body call under
    the real mesh, so bodies that consult the axis environment still trace.
    """
    w = cfg.wavefront

    def probe(row_ptr, col_idx, state):
        local_graph = CSRGraph(row_ptr=row_ptr[0], col_idx=col_idx[0])
        me = jax.lax.axis_index(axes)
        f = program.body(local_graph, _shard_context(cfg, me, axes))
        out, _, _ = f(jnp.zeros((w,), jnp.int32),
                      jnp.zeros((w,), jnp.bool_), state)
        return out

    fn = jax.shard_map(probe, mesh=mesh,
                       in_specs=(P(axes), P(axes), P()), out_specs=P(),
                       check_vma=False)
    shape = jax.eval_shape(fn, parts.row_ptr, parts.col_idx, state0)
    return shape.shape[0]


def _make_round(program: AtosProgram, cfg: SchedulerConfig, n: int,
                route_width: Optional[int], traced: bool = False,
                axes=AXIS, mesh_dims: Optional[Tuple[int, int]] = None):
    """The shared round body: deliver -> steal -> pop -> f -> exchange ->
    merge.

    The pop->body->push spine is the same :func:`~repro.core.scheduler.
    wavefront_step` the other engines drive; the sharded QueueOps wrap it
    with the 2-lane replica pop (stolen first, with the ownership meter)
    and the routed per-axis exchange, accumulating their telemetry in a
    trace-local ``aux`` dict.  ``always_run_body`` is set: a rescan folded
    into ``f`` must advance even on a drained replica, and SPMD lockstep
    forbids data-dependent branching across devices.

    ``round_step(f, mq, state, c, pending, ring)`` returns ``(mq, state,
    c, pending', ring)``; ``pending`` is the flat staged-arrivals buffer in
    overlap mode (``cfg.defer_rounds > 0``) and ``None`` in strict mode,
    where arrivals are pushed inside the round — the historical schedule,
    bit for bit.
    """
    s = cfg.num_shards
    w = cfg.wavefront
    steal_on = cfg.steal_threshold > 0
    defer = cfg.defer_rounds > 0
    merge = build_merge(program.merge)
    # chunked tasks (core/task.py): occupancy, donation plans, and the
    # processed meter all count vertices, so a coarse-chunk shard is charged
    # for the work it actually holds.  None keeps the slot-denominated
    # pre-granularity accounting bit-for-bit.
    width_of = program.task_width if cfg.granularity > 1 else None

    def round_step(f, mq: MultiQueue, state, c: ShardCounters,
                   pending=None, ring=None):
        me = jax.lax.axis_index(axes)
        deferred_n = jnp.int32(0)
        if pending is not None:
            # overlap delivery: last round's exchanged arrivals enter the
            # queue now — one round after a strict schedule would have
            # pushed them, while their collective ran behind that round.
            pv = pending != EMPTY
            deferred_n = jnp.sum(pv.astype(jnp.int32))
            mq = mq.push(LANE_LOCAL, pending, pv, backend=cfg.backend)
        if ring is not None:
            size_before = mq.size  # pre-steal, pre-pop replica occupancy
            counts0 = program.counts(state)
        donated = jnp.int32(0)
        triggered = jnp.bool_(False)
        if steal_on:
            mq, donated, triggered = rebalance(
                mq, axis_name=axes, num_shards=s,
                threshold=cfg.steal_threshold, chunk=cfg.steal_chunk,
                backend=cfg.backend, width_of=width_of)

        aux = {}

        def pop(mq):
            items, valid, n_stolen, mq2 = pop_wavefront(mq, w)
            # ownership meter: lanes [0, n_stolen) came off the stolen lane
            # and may belong to the ring predecessor; the rest must be ours.
            verts = program.task_vertex(jnp.where(valid, items, 0))
            verts = jnp.where(valid, verts, 0)
            owners = owner_of(verts, n, s)
            expected = jnp.where(jnp.arange(w, dtype=jnp.int32) < n_stolen,
                                 (me - 1) % s, me)
            aux["mis"] = jnp.sum((valid & (owners != expected))
                                 .astype(jnp.int32))
            aux["stolen"] = n_stolen
            return items, valid, mq2

        def push(mq, out, mask):
            with jax.named_scope(EXCHANGE):
                mq2, delivered, meters = route_tasks(
                    mq, out, mask, axis_name=axes, num_shards=s,
                    num_vertices=n, task_vertex=program.task_vertex,
                    route_width=route_width, backend=cfg.backend,
                    mesh_dims=mesh_dims, compress=cfg.compress)
            aux.update(meters)
            if defer:
                aux["delivered"] = delivered   # staged for next round
            else:
                mq2 = mq2.push(LANE_LOCAL, delivered, delivered != EMPTY,
                               backend=cfg.backend)
            return mq2

        ops = QueueOps(pop=pop, push=push, size=lambda mq: mq.size)
        mq, new_state, _, n_valid = wavefront_step(
            f, None, ops, (mq, state, jnp.int32(0), jnp.int32(0)),
            always_run_body=True)
        if ring is not None:
            # one row per device per round, written in-trace (zero syncs):
            # the counters' deltas are the device-local pre-merge ones, so
            # summing a round's rows across lanes reassembles the global
            # round.
            deltas = {k: v - counts0[k]
                      for k, v in program.counts(new_state).items()}
            ring = ring.record(
                round=c.rounds, lane=me, queue_size=size_before,
                pops=n_valid, pushes=mq.size - size_before + n_valid,
                **deltas, donated=donated, exchanged=aux["sent"],
                exchanged_row=aux["sent_row"], exchanged_col=aux["sent_col"],
                wire=aux["wire"], deferred=deferred_n)
        # round-synchronous replica reconciliation: after this every device
        # holds the identical merged state, so next round's pops read
        # globally fresh values (the TREES-style epoch barrier).
        state = merge(state, new_state, axes)

        c = ShardCounters(
            rounds=c.rounds + 1,
            items=c.items + n_valid,
            sent=c.sent + aux["sent"],
            route_dropped=c.route_dropped + aux["rdrop"],
            donated=c.donated + donated,
            stolen_run=c.stolen_run + aux["stolen"],
            steal_rounds=c.steal_rounds + triggered.astype(jnp.int32),
            mis_routed=c.mis_routed + aux["mis"],
            sent_row=c.sent_row + aux["sent_row"],
            sent_col=c.sent_col + aux["sent_col"],
            payload=c.payload + aux["payload"],
            padding=c.padding + aux["padding"],
            wire=c.wire + aux["wire"],
            deferred=c.deferred + deferred_n,
            overlap_rounds=c.overlap_rounds
            + ((deferred_n > 0) & (n_valid > 0)).astype(jnp.int32),
        )
        pending_next = aux["delivered"] if defer else None
        return mq, state, c, pending_next, ring

    def keep_going(mq: MultiQueue, state, c: ShardCounters, pending=None):
        """Global continuation: psum'd live-task mass + the stop predicate.

        The psum is the no-early-exit guarantee — a drained device sees its
        neighbours' backlog and keeps taking rounds (serving the exchange
        and merge collectives, and potentially receiving routed or stolen
        work) until the whole mesh is done.  Staged overlap arrivals count
        as live: a device whose queue drained but whose staging buffer
        holds tasks has not finished.  ``empty_means_done=False`` programs
        (PageRank's rescan) drop the queue-mass term, exactly as in the
        shared :func:`~repro.core.scheduler.continuation`.
        """
        in_bounds = c.rounds < cfg.max_rounds
        if program.empty_means_done:
            live = mq.size
            if pending is not None:
                live = live + jnp.sum((pending != EMPTY).astype(jnp.int32))
            global_size = jax.lax.psum(live, axes)
            more = in_bounds & (global_size > 0)
        else:
            more = in_bounds
        if program.stop is not None:
            more &= ~program.stop(state)
        return more

    return round_step, keep_going


def _counters_out(c: ShardCounters):
    return jax.tree.map(lambda x: x[None], c)


# ----------------------------------------------------------------- drivers
def persistent_run_sharded(program, parts: ShardedCSR, mq0, state0,
                           cfg: SchedulerConfig, mesh, route_width=None,
                           ring0=None, axes=AXIS, mesh_dims=None,
                           pend_width=None):
    """Whole drain in one shard_map'd while_loop (multi-device persistent).

    ``ring0``, if given, is a *stacked* per-device
    :class:`~repro.obs.TraceRing` (leading axis ``num_shards``); each device
    appends one row per round inside the while_loop — the traced drain is
    otherwise identical, and the rings come back stacked for the caller to
    drain.  ``pend_width`` (overlap mode) sizes the in-carry staging
    buffer; it is flushed back into the queue after the loop, so a
    ``max_rounds`` exit loses nothing.
    """
    n = parts.num_vertices
    traced = ring0 is not None
    defer = cfg.defer_rounds > 0
    round_step, keep_going = _make_round(program, cfg, n, route_width,
                                         traced=traced, axes=axes,
                                         mesh_dims=mesh_dims)

    def drain(row_ptr, col_idx, mq_st, state, *maybe_ring):
        local_graph = CSRGraph(row_ptr=row_ptr[0], col_idx=col_idx[0])
        me = jax.lax.axis_index(axes)
        f = program.body(local_graph, _shard_context(cfg, me, axes))

        mq = _local_view(mq_st)
        c0 = ShardCounters.zero()
        ring = _local_view(maybe_ring[0]) if traced else None
        pending0 = (jnp.full((pend_width,), EMPTY, jnp.int32)
                    if defer else None)

        def pack(mq, state, c, more, pending, ring):
            out = (mq, state, c, more)
            if defer:
                out = out + (pending,)
            if traced:
                out = out + (ring,)
            return out

        def unpack(carry):
            mq, state, c, more = carry[:4]
            rest = carry[4:]
            pending = rest[0] if defer else None
            ring = rest[-1] if traced else None
            return mq, state, c, more, pending, ring

        def cond(carry):
            return carry[3]

        def body(carry):
            mq, state, c, _, pending, ring = unpack(carry)
            mq, state, c, pending, ring = round_step(
                f, mq, state, c, pending, ring)
            more = keep_going(mq, state, c, pending)
            return pack(mq, state, c, more, pending, ring)

        carry0 = pack(mq, state, c0,
                      keep_going(mq, state, c0, pending0), pending0, ring)
        mq, state, c, _, pending, ring = unpack(
            jax.lax.while_loop(cond, body, carry0))
        if defer:
            # max_rounds / stop exits leave one round's arrivals staged:
            # flush them so segmented callers resume from a complete queue.
            mq = mq.push(LANE_LOCAL, pending, pending != EMPTY,
                         backend=cfg.backend)
        out = (_stacked_view(mq), state, _counters_out(c))
        if traced:
            out = out + (_stacked_view(ring),)
        return out

    specs_q = jax.tree.map(lambda _: P(axes), mq0)
    specs_c = jax.tree.map(lambda _: P(axes), ShardCounters.zero())
    in_specs = (P(axes), P(axes), specs_q, P())
    out_specs = (specs_q, P(), specs_c)
    operands = (parts.row_ptr, parts.col_idx, mq0, state0)
    if traced:
        specs_r = jax.tree.map(lambda _: P(axes), ring0)
        in_specs = in_specs + (specs_r,)
        out_specs = out_specs + (specs_r,)
        operands = operands + (ring0,)
    fn = jax.shard_map(drain, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    return jax.jit(fn)(*operands)


def discrete_run_sharded(program, parts: ShardedCSR, mq0, state0,
                         cfg: SchedulerConfig, mesh, route_width=None,
                         ring0=None, axes=AXIS, mesh_dims=None,
                         pend_width=None):
    """Host loop around one jitted sharded round (discrete kernels).

    ``ring0`` is the stacked per-device :class:`~repro.obs.TraceRing` as in
    :func:`persistent_run_sharded`: it rides the jitted round as a device
    operand, so per-round telemetry (queue sizes, exchange volume in total
    and per axis, wire ints, donations) costs zero extra host syncs.  In
    overlap mode the staging buffer rides the same way and is flushed after
    the loop.
    """
    n = parts.num_vertices
    traced = ring0 is not None
    defer = cfg.defer_rounds > 0
    round_step, keep_going = _make_round(program, cfg, n, route_width,
                                         traced=traced, axes=axes,
                                         mesh_dims=mesh_dims)

    def one_round(row_ptr, col_idx, mq_st, state, c_st, *rest):
        local_graph = CSRGraph(row_ptr=row_ptr[0], col_idx=col_idx[0])
        me = jax.lax.axis_index(axes)
        f = program.body(local_graph, _shard_context(cfg, me, axes))
        mq = _local_view(mq_st)
        c = _local_view(c_st)
        pending = rest[0][0] if defer else None
        ring = _local_view(rest[-1]) if traced else None
        mq, state, c, pending, ring = round_step(f, mq, state, c,
                                                 pending, ring)
        more = keep_going(mq, state, c, pending)
        out = (_stacked_view(mq), state, _counters_out(c), more)
        if defer:
            out = out + (pending[None],)
        if traced:
            out = out + (_stacked_view(ring),)
        return out

    specs_q = jax.tree.map(lambda _: P(axes), mq0)
    specs_c = jax.tree.map(lambda _: P(axes), ShardCounters.zero())
    in_specs = (P(axes), P(axes), specs_q, P(), specs_c)
    out_specs = (specs_q, P(), specs_c, P())
    if defer:
        in_specs = in_specs + (P(axes),)
        out_specs = out_specs + (P(axes),)
    if traced:
        specs_r = jax.tree.map(lambda _: P(axes), ring0)
        in_specs = in_specs + (specs_r,)
        out_specs = out_specs + (specs_r,)
    step = jax.jit(jax.shard_map(one_round, mesh=mesh, in_specs=in_specs,
                                 out_specs=out_specs, check_vma=False))

    mq_st, state = mq0, state0
    ring_st = ring0
    pending_st = (jnp.full((cfg.num_shards, pend_width), EMPTY, jnp.int32)
                  if defer else None)
    c_st = jax.tree.map(
        lambda x: jnp.zeros((cfg.num_shards,), x.dtype), ShardCounters.zero())
    rounds = 0
    # pre-round emptiness check mirrors discrete_run's host-synced predicate
    while rounds < cfg.max_rounds:
        if program.empty_means_done:
            live = int(np.asarray(_queue_sizes(mq_st)).sum())
            if defer:
                live += int((np.asarray(pending_st) != int(EMPTY)).sum())
            if live == 0:
                break
        if program.stop is not None and bool(program.stop(state)):
            break
        operands = [parts.row_ptr, parts.col_idx, mq_st, state, c_st]
        if defer:
            operands.append(pending_st)
        if traced:
            operands.append(ring_st)
        outs = step(*operands)
        mq_st, state, c_st, more = outs[:4]
        rest = outs[4:]
        if defer:
            pending_st = rest[0]
        if traced:
            ring_st = rest[-1]
        rounds += 1
        if not bool(more):
            break
    if defer:
        mq_st = _flush_pending(mq_st, pending_st, mq0, mesh, axes,
                               cfg.backend)
    if traced:
        return mq_st, state, c_st, ring_st
    return mq_st, state, c_st


def _flush_pending(mq_st, pending_st, mq0, mesh, axes, backend):
    """Push any still-staged overlap arrivals into the LOCAL lanes (the
    discrete driver's analogue of the persistent driver's in-trace flush)."""

    def flush(mq_st, p_st):
        mq = _local_view(mq_st)
        p = p_st[0]
        mq = mq.push(LANE_LOCAL, p, p != EMPTY, backend=backend)
        return _stacked_view(mq)

    specs_q = jax.tree.map(lambda _: P(axes), mq0)
    fn = jax.shard_map(flush, mesh=mesh, in_specs=(specs_q, P(axes)),
                       out_specs=specs_q, check_vma=False)
    return jax.jit(fn)(mq_st, pending_st)


def _queue_sizes(mq_st) -> jax.Array:
    """Per-device total replica occupancy from the stacked queue pytree."""
    return jnp.sum(mq_st.lanes.tail - mq_st.lanes.head, axis=-1)


# --------------------------------------------------------------- front door
def run_sharded(
    program: AtosProgram,
    graph: CSRGraph,
    cfg: SchedulerConfig,
    *,
    queue_capacity: Optional[int] = None,
    route_width: Optional[int] = None,
    mesh=None,
    trace: Optional[Trace] = None,
    trace_engine: Optional[str] = None,
    trace_round_offset: int = 0,
    initial_queues: Optional[MultiQueue] = None,
    initial_state: Any = None,
    final_queues: Optional[list] = None,
    parts: Optional[ShardedCSR] = None,
) -> Tuple[Any, ShardRunStats]:
    """Drain ``program`` over a ``cfg.num_shards``-device mesh.

    Returns ``(final_state, ShardRunStats)``.  The final state is the merged
    (replicated) global state — ``program.result(state)`` is the answer.

    ``cfg.mesh_shape`` selects the 2-D ``("row", "col")`` mesh (and its
    dimension-ordered two-hop exchange); ``cfg.defer_rounds`` the overlap
    pipeline; ``cfg.compress`` the wire codec — see DESIGN.md §16.

    ``trace`` takes an :class:`~repro.obs.Trace`: one stacked per-device
    ring rides the drain; every device appends one row per round in-trace
    (queue size, pops, exchanged per axis, wire ints, donations), drained
    per shard at run end under ``trace_engine`` with absolute round numbers
    shifted by ``trace_round_offset``.

    ``initial_state`` / ``initial_queues`` resume a drain from an explicit
    carry instead of ``program.init()`` (the streaming driver's dirty-seed
    re-seeds and snapshot restores; build queues via :func:`seed_queues`).
    ``final_queues``, if a list, receives the stacked end-of-drain queue
    pytree so a segmented caller can carry it into the next call.
    """
    s = cfg.num_shards
    axes, mesh_dims = _mesh_axes(cfg)
    if mesh is None:
        mesh = (make_shard_mesh(s) if mesh_dims is None
                else make_shard_mesh2d(*mesh_dims))
    n = graph.num_vertices
    steal_on = cfg.steal_threshold > 0
    if parts is None:
        # callers with a long-lived partition (the streaming driver's
        # per-owner patches, stream/ingest.reshard) pass it in; everyone
        # else pays the one-shot O(m) build here
        parts = partition_graph(graph, s, halo=steal_on)
    parts = place_partition(parts, mesh)
    capacity = queue_capacity or max(4 * n, 1024)
    if initial_state is None or initial_queues is None:
        init_state, seeds = program.init()
        if initial_state is None:
            initial_state = init_state
        if initial_queues is None:
            initial_queues = seed_queues(program, seeds, n, s, capacity)
    state0, mq0 = initial_state, initial_queues

    route_w = route_width
    pend_width = None
    if cfg.defer_rounds > 0:
        if route_w is None:
            route_w = _body_out_width(program, parts, cfg, state0, mesh,
                                      axes)
        pend_width = delivered_width(route_w, s, mesh_dims)

    ring0 = stacked_rings(trace.ring(), s) if trace is not None else None
    ring_st = None

    if cfg.persistent:
        out = persistent_run_sharded(
            program, parts, mq0, state0, cfg, mesh, route_width=route_w,
            ring0=ring0, axes=axes, mesh_dims=mesh_dims,
            pend_width=pend_width)
    else:
        out = discrete_run_sharded(
            program, parts, mq0, state0, cfg, mesh, route_width=route_w,
            ring0=ring0, axes=axes, mesh_dims=mesh_dims,
            pend_width=pend_width)
    if trace is not None:
        mq_st, state, c_st, ring_st = out
    else:
        mq_st, state, c_st = out

    c = jax.tree.map(np.asarray, c_st)
    stats = ShardRunStats(
        rounds=int(c.rounds.max()),
        items_processed=int(c.items.sum()),
        dropped=int(np.asarray(mq_st.lanes.dropped).sum()),
        route_dropped=int(c.route_dropped.sum()),
        exchanged=int(c.sent.sum()),
        donated=int(c.donated.sum()),
        stolen_executed=int(c.stolen_run.sum()),
        steal_rounds=int(c.steal_rounds.max()),
        mis_routed=int(c.mis_routed.sum()),
        per_device_items=c.items,
        per_device_sent=c.sent,
        per_device_donated=c.donated,
        final_sizes=np.asarray(_queue_sizes(mq_st)),
        exchanged_row=int(c.sent_row.sum()),
        exchanged_col=int(c.sent_col.sum()),
        payload_ints=int(c.payload.sum()),
        padding_ints=int(c.padding.sum()),
        wire_ints=int(c.wire.sum()),
        deferred_delivered=int(c.deferred.sum()),
        overlap_rounds=int(c.overlap_rounds.max()),
    )
    if trace is not None:
        engine = trace_engine or (
            "sharded.persistent" if cfg.persistent else "sharded.discrete")
        for d in range(s):
            trace.drain(unstack_ring(ring_st, d), engine=engine,
                        round_offset=trace_round_offset)
        trace.add_metric(stats.as_dict())
    if final_queues is not None:
        final_queues.append(mq_st)
    return state, stats
