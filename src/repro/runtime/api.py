"""``execute`` — one front door for every (program, policy) combination.

The three drain engines (single-device scheduler, fused MultiQueue lane,
sharded device mesh) share the :func:`~repro.core.scheduler.wavefront_step`
core and differ only in their :class:`~repro.core.scheduler.QueueOps` and
host-vs-device loop; this module is the dispatch that picks the driver from
the config's resolved :class:`~repro.runtime.policy.ExecutionPolicy` and
normalizes the outcome to ``(state, RunStats, info)`` so callers (algorithm
drivers, the autotuner, benchmarks, tests) never branch on topology.
"""
from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..core.backend import STREAM
from ..core.queue import make_multiqueue, make_queue
from ..core.scheduler import (QueueOps, RunStats, SchedulerConfig,
                              continuation, discrete_drive, megakernel_drive,
                              persistent_drive, taskqueue_ops, wavefront_step)
from ..obs import Trace, span
from ..obs.spans import RING
from .policy import ExecutionPolicy, policy_of
from .program import AtosProgram, ProgramContext


class ExecutionResult(NamedTuple):
    state: Any
    stats: RunStats
    info: dict


def _context(cfg: SchedulerConfig) -> ProgramContext:
    return ProgramContext(wavefront=cfg.wavefront,
                          num_workers=cfg.num_workers,
                          backend=cfg.backend,
                          granularity=cfg.granularity)


def fused_lane_ops(wavefront: int, backend: str, lane_id, job_id,
                   quota=None, aux: Optional[dict] = None,
                   task_width=None) -> QueueOps:
    """QueueOps over one packed MultiQueue lane — the task server's engine.

    Tasks on the wire are ``(job_id, zigzag(payload))`` int32s; the pop
    unpacks naturals for the body, the push re-packs.  ``lane_id``,
    ``job_id`` and ``quota`` may be traced scalars, so one compiled step
    serves every tenant sharing a kernel bundle (DESIGN.md section 8).
    ``aux``, if given, receives the per-pop routing-mismatch count
    (``aux["mismatch"]``) — the multi-tenant engine's wire-integrity meter.
    ``task_width`` (a *natural*-task -> chunk-width function, core/task.py)
    switches the quota to vertex units: the pop takes the longest slot
    prefix whose summed chunk widths fit the grant, so coarse-chunk lanes
    are charged for the vertices they actually advance.
    """
    from ..server.encoding import (pack, packed_width, unpack_job,
                                   unpack_natural)  # lazy: server->core

    width_of = None if task_width is None else packed_width(task_width)

    def pop(mq):
        packed, valid, mq2 = mq.pop_lane(lane_id, wavefront, quota,
                                         width_of=width_of)
        natural = jnp.where(valid, unpack_natural(packed), 0)
        if aux is not None:
            aux["mismatch"] = jnp.sum(
                (valid & (unpack_job(packed) != job_id)).astype(jnp.int32))
            # vertices the pop actually advanced: chunk-width weighted under
            # granularity (the occupancy numerator, DESIGN.md section 12);
            # one vertex per valid slot at G = 1.
            if width_of is None:
                aux["vertices"] = jnp.sum(valid.astype(jnp.int32))
            else:
                aux["vertices"] = jnp.sum(
                    jnp.where(valid, width_of(packed), 0).astype(jnp.int32))
        return natural, valid, mq2

    def push(mq, items, mask):
        return mq.push(lane_id, pack(job_id, items), mask, backend=backend)

    return QueueOps(pop=pop, push=push, size=lambda mq: mq.size)


def shared_queue_capacity(program: AtosProgram,
                          queue_capacity: Optional[int]) -> int:
    """The single/fused capacity rule — deterministic, so a snapshot restore
    (repro/stream) rebuilds an identically-shaped queue template."""
    return queue_capacity or program.default_queue_capacity


def _shared_setup(program: AtosProgram, graph, cfg: SchedulerConfig,
                  policy: ExecutionPolicy, queue_capacity: Optional[int],
                  *, init=None, queue=None):
    """Build the drain bundle for the single / fused topologies.

    Returns ``(queue, state, ops, step, cond, dropped_of)`` — everything a
    driver needs to run :func:`~repro.core.scheduler.wavefront_step` to a
    fixed point.  ``init=(state, seeds)`` overrides ``program.init()`` (the
    streaming driver passes the dirty-seed reseed here); ``queue`` bypasses
    seed placement entirely (snapshot restore hands back a mid-drain queue).
    """
    state, seeds = program.init() if init is None else init
    seeds = jnp.asarray(seeds, jnp.int32)
    capacity = shared_queue_capacity(program, queue_capacity)
    ctx = _context(cfg)
    mega = policy.kernel == "megakernel"
    if mega:
        # the megakernel body expands through the in-kernel DMA stream
        # (backend.STREAM, kernels/drain_loop/csr_stream); its queue ops run
        # on the jnp reference — a nested compaction kernel inside the fused
        # drain would add launch structure without changing a bit.
        ctx = ctx._replace(backend=STREAM)
        cfg = dataclasses.replace(cfg, backend="jnp")
    f = program.body(graph, ctx)
    on_empty = program.on_empty(graph, ctx)

    if policy.topology == "single":
        if queue is None:
            queue = make_queue(capacity, seeds)
        ops = taskqueue_ops(cfg)
        dropped_of = lambda q: q.dropped
    else:  # fused: the degenerate one-lane, one-tenant server drain
        from ..server.encoding import check_job_fits, pack
        if graph is not None:
            check_job_fits(0, graph.num_vertices)
        if queue is None:
            queue = make_multiqueue(capacity, 1).push(
                0, pack(0, seeds), jnp.ones(seeds.shape, bool))
        ops = fused_lane_ops(cfg.wavefront, cfg.backend, lane_id=0, job_id=0)
        dropped_of = lambda mq: jnp.sum(mq.lanes.dropped)

    cond = continuation(ops, cfg, program.stop, program.empty_means_done)
    step = lambda carry: wavefront_step(f, on_empty, ops, carry)
    return queue, state, ops, step, cond, dropped_of


def instrument_step(step, cond, ops: QueueOps, program: Optional[AtosProgram],
                    *, lane: int = 0):
    """Wrap a 4-tuple drain ``(step, cond)`` to thread a TraceRing.

    The traced carry is ``(*inner, ring)`` — the ring rides **last**, so the
    ``carry[2]``/``carry[3]`` index conventions every driver relies on are
    untouched.  Each round appends one structured record (pre-pop queue
    size, pops, pushes, the per-round delta of each counter the program
    declares: ``AtosProgram.counts``) with pure in-trace ``.at[]`` writes —
    zero host syncs; the wrapped ``cond`` simply strips the ring.  A
    counter the program does not declare reads 0.  The bookkeeping runs
    under the ``atos.ring`` device scope.
    """
    counts = program.counts if program is not None else lambda s: {}

    def traced_step(carry):
        *inner, ring = carry
        q0, s0, r0, p0 = inner
        with jax.named_scope(RING):
            size_before = ops.size(q0)
            c0 = counts(s0)
        q1, s1, r1, p1 = step((q0, s0, r0, p0))
        with jax.named_scope(RING):
            pops = p1 - p0
            deltas = {k: v - c0[k] for k, v in counts(s1).items()}
            ring = ring.record(
                round=r0, lane=lane, queue_size=size_before, pops=pops,
                pushes=ops.size(q1) - size_before + pops, **deltas)
        return q1, s1, r1, p1, ring

    def traced_cond(carry):
        return cond(tuple(carry[:4]))

    return traced_step, traced_cond


def _run_shared_core(program: AtosProgram, graph, cfg: SchedulerConfig,
                     policy: ExecutionPolicy, queue_capacity: Optional[int],
                     obs: Optional[Trace]):
    """single / fused topologies: same step core, different QueueOps.

    Each host step runs under its ``atos:`` span (``repro.obs.spans``):
    ``init`` (program init, seed placement, body construction — eager
    dispatches), ``drain`` (re-trace of the loop body, lowering, compile or
    cache load, dispatch) and ``readback`` (the host's waits on the
    device's counters)."""
    with span("init", obs):
        queue, state, ops, step, cond, dropped_of = _shared_setup(
            program, graph, cfg, policy, queue_capacity)
        carry0 = (queue, state, jnp.int32(0), jnp.int32(0))
        if obs is not None:
            # tracing on: identical drain with the ring as one extra leaf
            step, cond = instrument_step(step, cond, ops, program)
            carry0 = carry0 + (obs.ring(),)
    with span("drain", obs):
        if policy.kernel == "megakernel":
            carry = megakernel_drive(step, cond, carry0)
        elif policy.persistent:
            carry = persistent_drive(step, cond, carry0)
        else:
            carry = discrete_drive(step, cond, carry0)
    with span("readback", obs):
        queue, state, rounds, processed = carry[:4]
        stats = RunStats(rounds, processed, dropped_of(queue))
        info = {
            "rounds": int(stats.rounds),
            "dropped": int(stats.dropped),
            **counters_info(program, state),
            # kernel-entry events per drain: persistent/discrete re-enter
            # the expand/push kernels every round (one host dispatch per
            # round for discrete; one while-loop iteration per round for
            # persistent); the megakernel is ONE launch for the whole
            # drain (DESIGN.md §14)
            "launches": 1 if policy.kernel == "megakernel" else int(rounds),
        }
        if obs is not None:
            obs.drain(carry[4], engine=str(policy))
            obs.add_metric(run_doc(policy, stats, info))
    return ExecutionResult(state, stats, info)


def counters_info(program: AtosProgram, state) -> dict:
    """``info``'s counters: each one the program declares, read to the host
    at once; ``work`` and ``splits`` read 0 where undeclared."""
    counts = jax.device_get(program.counts(state))
    return {"work": 0, "splits": 0,
            **{name: int(value) for name, value in counts.items()}}


def run_doc(policy, stats: RunStats, info: dict) -> dict:
    """Serialize a single/fused run summary into the canonical ``run`` doc."""
    from ..obs.schema import metric_doc

    return metric_doc(
        "run", policy=str(policy), rounds=int(stats.rounds),
        items_processed=int(stats.items_processed),
        dropped=int(stats.dropped), work=int(info.get("work", 0)),
        splits=int(info.get("splits", 0)),
        launches=int(info.get("launches", 0)))


def _run_sharded(program: AtosProgram, graph, cfg: SchedulerConfig,
                 queue_capacity, trace, route_width, mesh):
    from .. import shard as _shard  # lazy: shard imports this package

    with span("drain", trace):
        state, sstats = _shard.run_sharded(
            program, graph, cfg, queue_capacity=queue_capacity,
            route_width=route_width, mesh=mesh, trace=trace)
    stats = RunStats(jnp.int32(sstats.rounds),
                     jnp.int32(sstats.items_processed),
                     jnp.int32(sstats.dropped + sstats.route_dropped))
    info = {
        "rounds": sstats.rounds,
        "dropped": sstats.dropped + sstats.route_dropped,
        **counters_info(program, state),
        "shards": len(sstats.per_device_items),
        "exchanged": sstats.exchanged,
        "donated": sstats.donated,
        "steal_rounds": sstats.steal_rounds,
        "mis_routed": sstats.mis_routed,
        "occupancy_balance": sstats.occupancy_balance,
        # wire accounting (DESIGN.md §16): per-axis cross-device volume,
        # payload vs padding, metered wire ints, and the overlap pipeline
        "exchanged_row": sstats.exchanged_row,
        "exchanged_col": sstats.exchanged_col,
        "payload_ints": sstats.payload_ints,
        "padding_ints": sstats.padding_ints,
        "wire_ints": sstats.wire_ints,
        "deferred": sstats.deferred_delivered,
        "overlap_rounds": sstats.overlap_rounds,
        "overlap_occupancy": sstats.overlap_occupancy,
    }
    return ExecutionResult(state, stats, info)


def execute(
    program: AtosProgram,
    graph,
    cfg: SchedulerConfig,
    *,
    queue_capacity: Optional[int] = None,
    trace: Optional[Trace] = None,
    route_width: Optional[int] = None,
    mesh=None,
) -> ExecutionResult:
    """Drain ``program`` under the config's resolved execution policy.

    Returns ``(final_state, RunStats, info)``; ``info`` carries the
    per-topology telemetry (exchange/steal meters for sharded runs; for
    single/fused runs ``info["launches"]`` counts kernel-entry events per
    drain — O(rounds) for persistent/discrete, 1 for the megakernel).
    ``trace`` takes an :class:`~repro.obs.Trace` — the unified
    observability collector (DESIGN.md §15): a device-side ring buffer rides
    the drain carry under *every* policy, recording one structured row per
    round with zero host syncs, drained into the collector at run end
    alongside a canonical run-summary doc.  ``trace=None`` (default) builds
    exactly the untraced computation — no ring, no wrapped step.  Either
    way the host steps carry ``atos:`` profiler spans and the round phases
    ``atos.*`` device scopes (``repro.obs.spans``).
    """
    if trace is not None and not isinstance(trace, Trace):
        raise TypeError(f"trace takes a repro.obs.Trace (its per-round rows "
                        f"are trace.records), not {type(trace).__name__}")
    policy = policy_of(cfg)
    if policy.topology == "sharded":
        return _run_sharded(program, graph, cfg, queue_capacity, trace,
                            route_width, mesh)
    return _run_shared_core(program, graph, cfg, policy, queue_capacity,
                            trace)


def stream_execute(
    algorithm,
    graph,
    deltas,
    cfg: SchedulerConfig,
    *,
    params: Optional[dict] = None,
    queue_capacity: Optional[int] = None,
    incremental: bool = True,
    snapshot_every: int = 0,
    checkpoint_dir: Optional[str] = None,
    keep: int = 3,
    resume: bool = False,
    route_width: Optional[int] = None,
    mesh=None,
    snapshot_hook=None,
    trace: Optional[Trace] = None,
    compact_every: int = 0,
    overlay_slack: float = 0.25,
):
    """Run ``algorithm`` as a long-lived streaming job over a mutating graph.

    Batch 0 drains the base ``graph``; each subsequent batch commits one
    :class:`~repro.stream.deltas.EdgeDelta` from ``deltas`` — an O(touched
    rows) in-place slotted-CSR commit (``graph/slotted.py``), never a full
    rebuild — re-seeds only the dirtied frontier (the program's
    ``dirty_seeds`` rule, unless ``incremental=False`` forces the
    full-recompute baseline), and drains again — under any of the six
    execution policies ``cfg`` resolves to.  ``compact_every`` /
    ``overlay_slack`` steer the slab compaction schedule: compact every N
    batches, and whenever the edge-log overlay exceeds ``overlay_slack *
    m`` (a slab-slack violation always forces one).
    ``snapshot_every > 0`` (with ``checkpoint_dir``) writes crash-consistent
    mid-drain snapshots every that-many rounds; ``resume=True`` continues
    from the newest one.  ``algorithm`` is a registered program name (the
    program must be *rebuilt* per batch — its body closes over the graph —
    so an :class:`AtosProgram` instance is accepted only as a name carrier).
    Returns a :class:`~repro.stream.driver.StreamResult`.
    """
    from ..stream.driver import run_stream  # lazy: stream imports runtime

    if isinstance(algorithm, AtosProgram):
        algorithm = algorithm.name
    return run_stream(
        algorithm, graph, deltas, cfg, params=params,
        queue_capacity=queue_capacity, incremental=incremental,
        snapshot_every=snapshot_every, checkpoint_dir=checkpoint_dir,
        keep=keep, resume=resume, route_width=route_width, mesh=mesh,
        snapshot_hook=snapshot_hook, trace=trace,
        compact_every=compact_every, overlay_slack=overlay_slack)
