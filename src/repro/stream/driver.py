"""The streaming drain driver: delta batches x incremental recompute x
crash-consistent snapshots (DESIGN.md §13).

``run_stream`` turns any registered :class:`~repro.runtime.program.
AtosProgram` into a long-running job over a mutating graph.  The timeline
is a sequence of **batches**: batch 0 drains the base graph from
``program.init()``; each batch ``b >= 1`` commits ``deltas[b-1]`` against
the current CSR (``stream/ingest``), re-seeds via the program's
``dirty_seeds`` rule (``stream/incremental``; or the conservative full
reseed), rebuilds the program on the new graph — its body closes over the
CSR — and drains again under whatever execution policy the config
resolves to.  The per-batch drains reuse the existing engines unchanged:
``runtime/api._shared_setup`` for the single/fused topologies,
``shard.run_sharded`` for the device mesh.

Snapshots segment a drain at round boundaries: rounds and processed
counts live *in the carry*, so a segmented drain takes exactly the same
steps as an unsegmented one, and a resumed run — replay the delta log,
rebuild the program, restore the carry, keep the same segment schedule —
is bit-identical to the uninterrupted run (tests/test_checkpoint_fault.py
proves this under SIGKILL).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.queue import make_multiqueue, make_queue
from ..core.scheduler import (SchedulerConfig, megakernel_drive,
                              megakernel_segment, persistent_drive)
from ..graph.slotted import SlottedCSR
from ..obs import Trace
from ..runtime.api import _shared_setup, instrument_step, \
    shared_queue_capacity
from ..runtime.policy import policy_of
from ..runtime.programs import build_program
from .deltas import EdgeDelta
from .incremental import reseed
from .ingest import commit, replay_commits, reshard
from .snapshot import SnapshotManager


@dataclasses.dataclass(frozen=True)
class StreamSpec:
    """Streaming attachment for a server job (``server/jobs.JobSpec``)."""

    deltas: Tuple[EdgeDelta, ...]
    incremental: bool = True
    snapshot_every: int = 0
    checkpoint_dir: Optional[str] = None
    resume: bool = False
    compact_every: int = 0        # 0 = occupancy/slack triggers only
    overlay_slack: float = 0.25   # compact when overlay > slack * m

    def __post_init__(self):
        object.__setattr__(self, "deltas", tuple(self.deltas))
        if self.snapshot_every < 0:
            raise ValueError("snapshot_every must be >= 0")
        if ((self.snapshot_every > 0 or self.resume)
                and not self.checkpoint_dir):
            raise ValueError(
                "snapshot_every/resume require a checkpoint_dir")
        if self.compact_every < 0:
            raise ValueError("compact_every must be >= 0")
        if not self.overlay_slack > 0:
            raise ValueError("overlay_slack must be > 0")


@dataclasses.dataclass
class BatchRecord:
    """Per-batch outcome (work/rounds are schedule-deterministic)."""

    batch: int
    incremental: bool     # did a dirty-seed rule produce the seeds?
    seeds: int            # seed tasks enqueued for this batch's drain
    effective_ops: int    # delta ops that actually changed the edge set
    rounds: int
    processed: int
    work: int             # program work-counter delta over this batch
    splits: int
    dropped: int
    touched_rows: int = 0     # slab rows rewritten by this batch's commit
    overlay: int = 0          # overlay occupancy after the commit
    compacted: bool = False   # did this commit trigger a compaction?
    commit_seconds: float = 0.0   # apply(+compaction) wall time


@dataclasses.dataclass
class StreamResult:
    state: Any            # final program state (last batch's graph)
    result: Any           # program.result(state)
    batches: List[BatchRecord]
    info: dict

    def as_dict(self) -> dict:
        """Serialize into the canonical ``stream`` doc (obs/schema)."""
        from ..obs.schema import metric_doc  # lazy: obs is a leaf layer

        return metric_doc(
            "stream",
            **{k: v for k, v in self.info.items() if v is not None})


def _drive_shared(step, cond, carry, kernel: str, every: int, cb):
    """Drive a single/fused carry to its fixed point, calling ``cb(carry)``
    at every ``every``-th round (0 = never).  Rounds live in ``carry[2]``,
    so the boundaries are absolute round numbers — a resumed drain lands on
    the same boundaries the uninterrupted one did.  ``kernel`` is the
    resolved strategy name (``policy.kernel``); a segmented megakernel
    drain bakes the same ``rounds < limit`` term into its in-kernel loop
    condition, so it snapshots at the identical boundaries."""
    if kernel == "megakernel":
        if every <= 0:
            return megakernel_drive(step, cond, carry)
        # build the fused segment ONCE: the round limit rides as a kernel
        # operand, so every snapshot window reuses the same traced jaxpr /
        # pallas_call instead of retracing the whole drain per segment
        seg = megakernel_segment(step, cond, carry)
        keep_going = jax.jit(cond)
        while bool(keep_going(carry)):
            carry = seg(carry, jnp.int32(int(carry[2]) + every))
            cb(carry)
        return carry
    if kernel == "persistent":
        if every <= 0:
            return persistent_drive(step, cond, carry)
        seg = jax.jit(lambda c, limit: jax.lax.while_loop(
            lambda cc: cond(cc) & (cc[2] < limit), step, c))
        keep_going = jax.jit(cond)
        while bool(keep_going(carry)):
            carry = seg(carry, jnp.int32(int(carry[2]) + every))
            cb(carry)
        return carry
    round_jit = jax.jit(step)
    while bool(cond(carry)):
        carry = round_jit(carry)
        if every > 0 and int(carry[2]) % every == 0:
            cb(carry)
    return carry


def _drive_sharded(program, graph, cfg: SchedulerConfig, capacity: int,
                   mq, state, rounds: int, processed: int, every: int, cb,
                   route_width, mesh, trace=None, trace_engine=None,
                   trace_round_offset: int = 0, parts=None):
    """Segmented sharded drain: each segment is one ``run_sharded`` call
    with its round budget clamped to the next snapshot boundary.  The
    host-side continuation replicates the in-loop ``keep_going`` exactly
    (queue mass for ``empty_means_done`` programs, then ``stop``)."""
    from .. import shard as _shard
    from ..shard.driver import _queue_sizes

    extra = {"exchanged": 0, "donated": 0, "steal_rounds": 0,
             "mis_routed": 0, "route_dropped": 0}

    def more() -> bool:
        if rounds >= cfg.max_rounds:
            return False
        if program.empty_means_done and \
                int(np.asarray(_queue_sizes(mq)).sum()) == 0:
            return False
        if program.stop is not None and bool(program.stop(state)):
            return False
        return True

    while more():
        budget = cfg.max_rounds - rounds
        if every > 0:
            at_boundary = rounds % every
            budget = min(budget, every - at_boundary if at_boundary else every)
        scfg = dataclasses.replace(cfg, max_rounds=budget)
        fq: list = []
        state, st = _shard.run_sharded(
            program, graph, scfg, queue_capacity=capacity,
            route_width=route_width, mesh=mesh, trace=trace,
            trace_engine=trace_engine,
            trace_round_offset=trace_round_offset + rounds,
            initial_queues=mq, initial_state=state, final_queues=fq,
            parts=parts)
        mq = fq[0]
        rounds += st.rounds
        processed += st.items_processed
        extra["exchanged"] += st.exchanged
        extra["donated"] += st.donated
        extra["steal_rounds"] += st.steal_rounds
        extra["mis_routed"] += st.mis_routed
        extra["route_dropped"] += st.route_dropped
        if every > 0:
            cb(mq, state, rounds, processed)
        if st.rounds == 0:  # defensive: never spin on a no-progress segment
            break
    dropped = int(np.asarray(mq.lanes.dropped).sum()) + extra["route_dropped"]
    return mq, state, rounds, processed, dropped, extra


def run_stream(
    algorithm: str,
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
    trace_engine: Optional[str] = None,
    compact_every: int = 0,
    overlay_slack: float = 0.25,
) -> StreamResult:
    """Run ``algorithm`` over ``graph`` + a delta log, batch by batch.

    See :func:`repro.runtime.api.stream_execute` (the front door) for the
    argument contract.  ``snapshot_hook(tick, batch)``, if given, fires
    after every committed snapshot — the fault-injection tests kill the
    process inside it.  On resume, records for batches that completed
    before the restored snapshot are not re-synthesized; the final state
    and result are nevertheless bit-identical to an uninterrupted run.

    ``trace`` (an :class:`~repro.obs.Trace`) threads a fresh device ring
    through every batch's drain — snapshots never see it (the save hooks
    receive only queue + state), so segmented and resumed runs stay
    bit-identical — draining each batch under ``trace_engine`` with
    absolute (cross-batch) round numbers, and registers the canonical
    ``stream`` summary doc at the end.
    """
    policy = policy_of(cfg)
    deltas = list(deltas)
    params = dict(params or {})
    total = len(deltas) + 1
    snap = SnapshotManager(checkpoint_dir, keep=keep) if checkpoint_dir \
        else None
    if (snapshot_every > 0 or resume) and snap is None:
        raise ValueError("snapshot_every/resume require a checkpoint_dir")

    tick = 0
    start_batch = 0
    resume_tick = None
    if resume:
        resume_tick = snap.latest()
        if resume_tick is not None:
            start_batch = snap.peek(resume_tick)["batch"]
            tick = resume_tick + 1
    resumed = resume_tick is not None

    # ONE slotted CSR lives across the whole stream (graph/slotted.py):
    # batch commits mutate it in place, O(touched rows) instead of the old
    # per-batch from_edges rebuild.  Resume replays the committed prefix
    # through the SAME commit path — identical compaction schedule, hence
    # identical slab layout and snapshot fingerprints (the deltas and the
    # knobs fully determine both).
    slotted = SlottedCSR.from_csr(graph)
    if start_batch:
        replay_commits(slotted, deltas[:start_batch], compact_every,
                       overlay_slack)
    cur_graph = slotted.view()
    parts = None  # sharded: long-lived partition, patched per owner below
    state = None
    records: List[BatchRecord] = []
    totals = {"rounds": 0, "processed": 0, "work": 0, "dropped": 0}
    program = None

    for b in range(start_batch, total):
        restoring = resumed and b == start_batch
        applied = None
        commit_s = 0.0
        if b > 0 and not restoring:
            t_commit = time.perf_counter()
            applied = commit(slotted, deltas[b - 1], b, compact_every,
                             overlay_slack)
            commit_s = time.perf_counter() - t_commit
            cur_graph = applied.new_graph
        # the body closes over the adjacency view, so the program is
        # rebuilt per batch (fresh chunk codec, budgets, and dirty-seed
        # closure for the committed graph)
        program = build_program(algorithm, cur_graph, cfg,
                                params=dict(params),
                                queue_capacity=queue_capacity)
        was_incremental = bool(b > 0 and incremental
                               and program.dirty_seeds is not None)
        n = cur_graph.num_vertices
        sharded = policy.topology == "sharded"
        if sharded:
            # owner-aware patch: only shards owning an effectively changed
            # row (plus their halo successors) are rewritten; batch 0 (or a
            # fresh resume) pays the one full build
            t_commit = time.perf_counter()
            halo = cfg.steal_threshold > 0
            if parts is None:
                parts = reshard(slotted, cfg.num_shards, halo=halo)
            elif applied is not None:
                parts = reshard(
                    slotted, cfg.num_shards, halo=halo, parts=parts,
                    touched_rows=np.concatenate([applied.ins_src,
                                                 applied.del_src]))
            commit_s += time.perf_counter() - t_commit
        capacity = (queue_capacity or max(4 * n, 1024)) if sharded else \
            shared_queue_capacity(program, queue_capacity)

        restored = None
        if restoring:
            state_template, _ = program.init()
            if sharded:
                from ..shard.driver import seed_queues
                q_template = seed_queues(program, jnp.zeros((0,), jnp.int32),
                                         n, cfg.num_shards, capacity)
            elif policy.topology == "single":
                q_template = make_queue(capacity)
            else:
                q_template = make_multiqueue(capacity, 1)
            tree = snap.restore(resume_tick, queue_template=q_template,
                                state_template=state_template,
                                graph=cur_graph, num_deltas=b)
            cur = {k: int(v) for k, v in tree["cursor"].items()}
            restored = (tree["queue"], cur["rounds"], cur["processed"])
            state = tree["state"]
            seeds = jnp.zeros((0,), jnp.int32)
            seeds_count, eff = cur["seeds"], cur["eff"]
            pre_work, pre_splits = cur["pre_work"], cur["pre_splits"]
        else:
            if b == 0:
                state, seeds = program.init()
                eff = 0
            else:
                state, seeds = reseed(program, applied, state,
                                      incremental=incremental)
                eff = applied.num_effective
            seeds = jnp.asarray(seeds, jnp.int32)
            seeds_count = int(seeds.shape[0])
            pre_work = program.count_of("work", state)
            pre_splits = program.count_of("splits", state)

        def save_snapshot(queue_tree, st, r, p):
            nonlocal tick
            snap.save(tick, cursor={
                "batch": b, "rounds": r, "processed": p,
                "pre_work": pre_work, "pre_splits": pre_splits,
                "seeds": seeds_count, "eff": eff,
            }, graph=cur_graph, num_deltas=b, queue=queue_tree, state=st)
            t, tick = tick, tick + 1
            if snapshot_hook is not None:
                snapshot_hook(t, b)

        every = snapshot_every if snap is not None else 0
        engine = trace_engine or f"stream.{algorithm}"
        # cross-batch round offset: batches tile one absolute timeline in
        # the exported trace (in-batch rounds restart at r0 per batch)
        batch_offset = totals["rounds"]
        if not sharded:
            init_arg = (state, seeds)
            queue_in = restored[0] if restored is not None else None
            queue, state0, ops, step, cond, dropped_of = _shared_setup(
                program, cur_graph, cfg, policy, queue_capacity,
                init=init_arg, queue=queue_in)
            r0 = restored[1] if restored is not None else 0
            p0 = restored[2] if restored is not None else 0
            carry = (queue, state0, jnp.int32(r0), jnp.int32(p0))
            if trace is not None:
                # fresh ring per batch, riding LAST in the carry — the
                # snapshot hooks below only ever see c[0]/c[1], so the
                # ring never reaches a checkpoint
                step, cond = instrument_step(step, cond, ops, program)
                carry = carry + (trace.ring(),)
            if snap is not None and restored is None:
                save_snapshot(carry[0], carry[1], 0, 0)
            cb = (lambda c: save_snapshot(c[0], c[1], int(c[2]), int(c[3])))
            carry = _drive_shared(step, cond, carry, policy.kernel,
                                  every, cb)
            queue, state, rounds_a, processed_a = carry[:4]
            if trace is not None:
                trace.drain(carry[4], engine=engine,
                            round_offset=batch_offset - r0)
            rounds, processed = int(rounds_a), int(processed_a)
            dropped = int(dropped_of(queue))
            extra = {}
        else:
            from ..shard.driver import seed_queues
            if restored is None:
                mq = seed_queues(program, seeds, n, cfg.num_shards, capacity)
                r0 = p0 = 0
            else:
                mq, r0, p0 = restored
            if snap is not None and restored is None:
                save_snapshot(mq, state, 0, 0)
            _, state, rounds, processed, dropped, extra = _drive_sharded(
                program, cur_graph, cfg, capacity, mq, state, r0, p0, every,
                lambda q, st, r, p: save_snapshot(q, st, r, p),
                route_width, mesh, trace=trace, trace_engine=engine,
                trace_round_offset=batch_offset - r0, parts=parts)

        records.append(BatchRecord(
            batch=b, incremental=was_incremental, seeds=seeds_count,
            effective_ops=eff, rounds=rounds, processed=processed,
            work=program.count_of("work", state) - pre_work,
            splits=program.count_of("splits", state) - pre_splits,
            dropped=dropped,
            # a restoring batch's commit happened inside replay_commits —
            # the slotted counters still hold exactly that batch's numbers
            touched_rows=(applied.touched_rows if applied is not None
                          else (slotted.last_touched if b > 0 else 0)),
            overlay=slotted.overlay_size,
            compacted=(applied.compacted if applied is not None
                       else (slotted.last_compacted if b > 0 else False)),
            commit_seconds=commit_s,
        ))
        totals["rounds"] += rounds
        totals["processed"] += processed
        totals["work"] += records[-1].work
        totals["dropped"] += dropped
        for k, v in extra.items():
            totals[k] = totals.get(k, 0) + v

    if snap is not None:
        snap.wait()
    info = dict(totals)
    info.update({
        "batches": total,
        "batches_run": total - start_batch,
        "resumed_at": start_batch if resumed else None,
        "incremental": incremental,
        "topology": policy.topology,
        # commit-cost meters (cumulative over the whole delta log,
        # including any resume-replayed prefix — same totals as an
        # uninterrupted run)
        "touched_rows": slotted.touched_rows,
        "compactions": slotted.compactions,
        "commit_seconds": round(sum(r.commit_seconds for r in records), 6),
    })
    out = StreamResult(state=state, result=program.result(state),
                       batches=records, info=info)
    if trace is not None:
        trace.add_metric(out.as_dict())
    return out
