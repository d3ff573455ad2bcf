"""The multi-tenant task server: one resident scheduler, many graph jobs.

Atos's final analysis derives per-workload launch configurations; its
``num_queues`` lanes let one queue serve heterogeneous task streams.  This
module turns both into a serving system (DESIGN.md section 8):

  * every admitted job owns one **lane** of a shared :class:`MultiQueue`;
    its tasks are packed ``(job_id, payload)`` int32s (``server/encoding``);
  * each scheduling round a **fairness policy** splits the wavefront budget
    ``W = num_workers x fetch_size`` into per-lane quotas, and the server
    drives every granted lane through its job's wavefront body — a *fused
    wavefront*: one scheduler round advances many tenants, so the
    small-frontier rounds that underfill a single-tenant wavefront instead
    overlap across jobs and the batch finishes in fewer total rounds;
  * **backpressure**: a lane whose ``dropped`` counter grew last round is
    drain-boosted (served first) and new admissions are deferred until the
    overflow clears;
  * **admission control**: at most one job per lane; excess jobs wait in a
    FIFO and are admitted as lanes free up.

The loop is host-driven — the discrete-kernel regime — because tenants have
heterogeneous graph shapes and therefore distinct XLA executables; the
per-round host sync is exactly the discrete launch overhead the paper
measures, and the autotuner (``server/autotune``) still picks persistent
configs for the single-tenant calibration runs.
"""
from __future__ import annotations

import dataclasses
import logging
import time
from collections import deque
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.counters import JobTelemetry
from ..core.queue import MultiQueue, make_multiqueue
from ..core.scheduler import SchedulerConfig, wavefront_step
from ..runtime.api import fused_lane_ops
from .encoding import MAX_JOBS, pack
from .encoding import packed_width as encoding_packed_width
from .jobs import JobRegistry, JobSpec, Program
from .policies import FairnessPolicy, make_policy

log = logging.getLogger("repro.server")


@dataclasses.dataclass
class Job:
    """Runtime record of one submitted job."""

    job_id: int
    program: Optional[Program]     # built at admission (config-specialized)
    weight: float
    spec: Optional[JobSpec] = None
    status: str = "pending"        # pending -> active -> done
    lane: int = -1
    state: Any = None
    counters: Any = None           # device int32[3]: (items, verts, mism)
    #: packed-wire chunk-width fn (encoding.packed_width), built once at
    #: admission; None when the program is width-1 or width-agnostic
    width_of: Any = None
    stopped: bool = False
    telemetry: Optional[JobTelemetry] = None
    result: Optional[np.ndarray] = None
    #: streaming jobs only: the full per-batch StreamResult (repro/stream)
    stream_result: Any = None


@dataclasses.dataclass
class ServerStats:
    rounds: int = 0
    wall_seconds: float = 0.0
    items_processed: int = 0
    backpressure_events: int = 0
    deferred_admissions: int = 0
    wavefront: int = 0
    sharded_jobs: int = 0          # jobs served as device-wide sharded phases
    sharded_rounds: int = 0        # device rounds spent in those phases
    streaming_jobs: int = 0        # jobs served as streaming phases
    stream_batches: int = 0        # delta batches drained in those phases

    @property
    def occupancy(self) -> float:
        denom = self.rounds * self.wavefront
        return self.items_processed / denom if denom else 0.0

    def as_dict(self) -> dict:
        """Serialize into the canonical ``server`` doc (obs/schema)."""
        from ..obs.schema import metric_doc  # lazy: obs is a leaf layer

        d = dataclasses.asdict(self)
        d["occupancy"] = self.occupancy
        return metric_doc("server", **d)


@dataclasses.dataclass
class ServerResult:
    results: Dict[int, np.ndarray]
    telemetry: Dict[int, JobTelemetry]
    stats: ServerStats


class TaskServer:
    """Multi-tenant graph-analytics server over one shared MultiQueue."""

    def __init__(
        self,
        registry: JobRegistry,
        num_lanes: int = 8,
        config: Optional[SchedulerConfig] = None,
        policy: str | FairnessPolicy = "weighted",
        lane_capacity: Optional[int] = None,
        autotuner=None,
        max_rounds: int = 1 << 17,
        strict_drops: bool = True,
        trace=None,
    ) -> None:
        self.registry = registry
        self.num_lanes = num_lanes
        self._config = config
        self.policy = (policy if isinstance(policy, FairnessPolicy)
                       else make_policy(policy))
        self._lane_capacity = lane_capacity
        self.autotuner = autotuner
        self.max_rounds = max_rounds
        #: optional :class:`~repro.obs.Trace`: one device ring rides every
        #: compiled lane step (one row per granted lane per round, written
        #: in-trace), drained once when ``run()`` returns, alongside the
        #: canonical server/job summary docs and per-job latency histograms.
        self.trace = trace
        # a dropped task is work lost forever: for the graph algorithms that
        # silently corrupts the answer (an unreached BFS vertex stays INF),
        # so by default any overflow fails the run loudly.  Opt out only for
        # workloads that tolerate loss (see tests' synthetic flood program).
        self.strict_drops = strict_drops
        self._jobs: List[Job] = []

    # ------------------------------------------------------------ submission
    def _next_job_id(self) -> int:
        # job ids are baked into the packed-task bitfield and never
        # recycled, so one server instance serves at most MAX_JOBS jobs
        # over its lifetime; fail at submit time, not mid-run.
        job_id = len(self._jobs)
        if job_id >= MAX_JOBS:
            raise ValueError(
                f"job id space exhausted: one TaskServer serves at most "
                f"{MAX_JOBS} jobs over its lifetime (encoding.PAYLOAD_BITS "
                f"bitfield); create a new server for the next batch")
        return job_id

    def submit(self, spec: JobSpec) -> int:
        """Queue a job for admission; returns its job_id."""
        job_id = self._next_job_id()
        self._jobs.append(Job(job_id=job_id, program=None,
                              weight=spec.weight, spec=spec))
        return job_id

    def submit_program(self, program: Program, weight: float = 1.0) -> int:
        """Escape hatch for synthetic/custom programs (tests, experiments).

        The program must already match the server's wavefront width.
        """
        job_id = self._next_job_id()
        self._jobs.append(Job(job_id=job_id, program=program, weight=weight))
        return job_id

    # ------------------------------------------------------------- plumbing
    def _resolve_config(self) -> SchedulerConfig:
        if self._config is not None:
            return self._config
        if self.autotuner is not None:
            pairs = [(j.spec.algorithm, self.registry.graph(j.spec.graph))
                     for j in self._jobs if j.spec is not None]
            if pairs:
                cfg = self.autotuner.recommend_for_mix(pairs)
                log.info("autotuned server config: %s", cfg)
                return cfg
        return SchedulerConfig()

    def _resolve_lane_capacity(self) -> int:
        if self._lane_capacity is not None:
            return self._lane_capacity
        biggest = 1024
        for j in self._jobs:
            if j.spec is not None:
                n = self.registry.graph(j.spec.graph).num_vertices
                biggest = max(biggest, 8 * n)
        return biggest

    def _step_for(self, f, stop, W: int, backend: str, task_width=None,
                  work_fn=None, traced: bool = False):
        """One compiled scheduler step per distinct wavefront body.

        The pop->body->push spine is the shared
        :func:`~repro.core.scheduler.wavefront_step` core (DESIGN.md
        section 11), driven through fused-lane QueueOps: pop unpacks
        ``(job_id, payload)`` tasks from one MultiQueue lane (metering
        routing mismatches on the way), push re-packs.  ``quota`` and
        ``job_id`` are traced scalars, so every tenant sharing a kernel
        bundle shares this executable.  Telemetry (items popped, routing
        mismatches) accumulates in a device-side ``counters`` array and the
        convergence predicate is evaluated in-step, so the host loop syncs
        one boolean per stop-ful job per round and nothing else.

        Steps are cached on the registry (whose kernel bundles own the
        closures), so a fused server and the sequential baseline over the
        same registry share executables, and the cache dies with the
        registry instead of pinning every served graph process-wide.
        """
        cache = self.registry.step_cache
        # function objects as keys: no id-reuse after GC; backend is part of
        # the key so jnp- and pallas-backed servers never share a step.
        # task_width switches the pop quota to vertex units (granularity >
        # 1, DESIGN.md section 12), so it distinguishes executables too.
        # traced variants live under distinct keys: an untraced server keeps
        # exactly the pre-observability executables (disabled = identity).
        key = (f, stop, W, backend, task_width, work_fn, traced)
        if key not in cache:
            def core(mq, lane_id, state, counters, quota, job_id):
                # lane extraction/writeback is traced: one dispatch per
                # scheduler step instead of a shower of eager slice ops.
                aux = {}
                ops = fused_lane_ops(W, backend, lane_id, job_id,
                                     quota=quota, aux=aux,
                                     task_width=task_width)
                # always_run_body: a granted lane advances even on a
                # zero-valid pop (PageRank's in-body rescan must tick).
                mq, state, _, n_valid = wavefront_step(
                    f, None, ops, (mq, state, jnp.int32(0), jnp.int32(0)),
                    always_run_body=True)
                counters = counters + jnp.stack(
                    [n_valid, aux["vertices"], aux["mismatch"]])
                stopped = (jnp.bool_(False) if stop is None
                           else stop(state))
                return mq, state, counters, stopped, n_valid

            if traced:
                @jax.jit
                def step(mq, lane_id, state, counters, quota, job_id,
                         ring, round_ix):
                    size_before = mq.lane(lane_id).size
                    work0 = work_fn(state) if work_fn is not None else 0
                    mq, state, counters, stopped, n_valid = core(
                        mq, lane_id, state, counters, quota, job_id)
                    work1 = work_fn(state) if work_fn is not None else 0
                    size_after = mq.lane(lane_id).size
                    ring = ring.record(
                        round=round_ix, lane=lane_id,
                        queue_size=size_before, pops=n_valid,
                        pushes=size_after - size_before + n_valid,
                        work=work1 - work0)
                    return mq, state, counters, stopped, ring
            else:
                @jax.jit
                def step(mq, lane_id, state, counters, quota, job_id):
                    return core(mq, lane_id, state, counters, quota,
                                job_id)[:4]

            cache[key] = step
        return cache[key]

    def _empty_step_for(self, on_empty, stop, backend: str,
                        traced: bool = False):
        cache = self.registry.empty_step_cache
        key = (on_empty, stop, backend, traced)
        if key not in cache:
            def core(mq, lane_id, state, job_id):
                out, mask, state = on_empty(state)
                mq = mq.push(lane_id, pack(job_id, out), mask,
                             backend=backend)
                stopped = (jnp.bool_(False) if stop is None
                           else stop(state))
                return mq, state, stopped

            if traced:
                @jax.jit
                def step(mq, lane_id, state, job_id, ring, round_ix):
                    size_before = mq.lane(lane_id).size
                    mq, state, stopped = core(mq, lane_id, state, job_id)
                    ring = ring.record(
                        round=round_ix, lane=lane_id,
                        queue_size=size_before, pops=0,
                        pushes=mq.lane(lane_id).size - size_before)
                    return mq, state, stopped, ring
            else:
                step = jax.jit(core)

            cache[key] = step
        return cache[key]

    def _admit(self, job: Job, mq: MultiQueue, lane: int, cfg: SchedulerConfig,
               lane_capacity: int, rounds: int) -> MultiQueue:
        if job.program is None:
            job.program = self.registry.build(
                job.spec, job.job_id, cfg.wavefront, cfg.num_workers,
                lane_capacity, backend=cfg.backend,
                granularity=cfg.granularity,
                split_threshold=cfg.split_threshold)
        prog = job.program
        job.state, seeds = prog.init()
        job.counters = jnp.zeros((3,), jnp.int32)
        job.width_of = (encoding_packed_width(prog.task_width)
                        if cfg.granularity > 1 and prog.task_width is not None
                        else None)
        job.stopped = False
        job.lane = lane
        job.status = "active"
        if job.telemetry is None:  # submit-time round was 0 for batch mode
            job.telemetry = JobTelemetry(
                job_id=job.job_id, algorithm=prog.algorithm,
                graph=prog.graph_name, wavefront=cfg.wavefront,
                ideal_work=prog.ideal_work, granularity=cfg.granularity)
        job.telemetry.admitted_round = rounds
        mq = mq.reset_lane(lane)
        seeds = jnp.asarray(seeds, jnp.int32)
        # seed push stays on the jnp path: it runs once per admission outside
        # the compiled round step, and push results are backend-identical.
        mq = mq.push(lane, pack(job.job_id, seeds),
                     jnp.ones(seeds.shape, bool))
        log.info("admit job %d (%s on %s) -> lane %d at round %d",
                 job.job_id, prog.algorithm, prog.graph_name, lane, rounds)
        return mq

    def _finalize(self, job: Job, mq: MultiQueue, rounds: int) -> MultiQueue:
        prog = job.program
        job.result = np.asarray(prog.result(job.state))
        items, vertices, mismatches = (int(x)
                                       for x in np.asarray(job.counters))
        job.telemetry.items_processed = items
        job.telemetry.vertices_processed = vertices
        job.telemetry.routing_mismatches = mismatches
        job.telemetry.work = int(prog.work(job.state))
        job.telemetry.completed_round = rounds
        job.telemetry.dropped += int(mq.lane(job.lane).dropped)
        if self.strict_drops and job.telemetry.dropped > 0:
            raise RuntimeError(
                f"job {job.job_id} ({prog.algorithm} on {prog.graph_name}) "
                f"dropped {job.telemetry.dropped} tasks to lane overflow — "
                f"its result would be silently wrong.  Raise lane_capacity "
                f"(or pass strict_drops=False for loss-tolerant workloads).")
        job.status = "done"
        mq = mq.reset_lane(job.lane)
        log.info("job %d done at round %d (work=%d, occupancy=%.3f)",
                 job.job_id, rounds, job.telemetry.work,
                 job.telemetry.occupancy)
        job.lane = -1
        return mq

    # -------------------------------------------------------- sharded jobs
    def _run_sharded(self, job: Job, cfg: SchedulerConfig,
                     stats: ServerStats) -> None:
        """Serve one ``shards > 1`` job as a device-wide sharded drain.

        A sharded drain owns the whole mesh (every device runs a queue
        replica plus the exchange/steal collectives), so these jobs run as
        serialized phases before the fused multi-tenant rounds rather than
        as lanes inside them — coexistence at the batch level, not the
        round level (DESIGN.md section 10).
        """
        from .. import shard as _shard
        from ..runtime import build_program

        spec = job.spec
        graph = self.registry.graph(spec.graph)
        scfg = dataclasses.replace(cfg, num_shards=spec.shards,
                                   topology="sharded")
        program = build_program(spec.algorithm, graph, scfg,
                                params=dict(spec.params),
                                queue_capacity=self._lane_capacity)
        log.info("sharded job %d (%s on %s) over %d shards",
                 job.job_id, spec.algorithm, spec.graph, spec.shards)
        state, sstats = _shard.run_sharded(
            program, graph, scfg, queue_capacity=self._lane_capacity,
            trace=self.trace,
            trace_engine=f"server.job{job.job_id}.sharded")
        job.result = np.asarray(program.result(state))
        tel = JobTelemetry(
            job_id=job.job_id, algorithm=spec.algorithm, graph=spec.graph,
            wavefront=scfg.wavefront * spec.shards,  # mesh-wide pop budget
            ideal_work=program.ideal_work)
        tel.admitted_round = tel.completed_round = 0
        tel.rounds_active = sstats.rounds
        tel.items_processed = sstats.items_processed
        tel.work = program.count_of("work", state)
        tel.dropped = sstats.dropped + sstats.route_dropped
        job.telemetry = tel
        if self.strict_drops and tel.dropped > 0:
            raise RuntimeError(
                f"sharded job {job.job_id} ({spec.algorithm} on "
                f"{spec.graph}) dropped {tel.dropped} tasks to replica "
                f"overflow — its result would be silently wrong.  Raise "
                f"lane_capacity (or pass strict_drops=False).")
        if sstats.mis_routed:
            raise RuntimeError(
                f"sharded job {job.job_id}: {sstats.mis_routed} tasks ran "
                f"off their owner shard (routing invariant violated)")
        job.status = "done"
        stats.sharded_jobs += 1
        stats.sharded_rounds += sstats.rounds
        log.info("sharded job %d done in %d device rounds "
                 "(exchanged=%d donated=%d balance=%.3f)",
                 job.job_id, sstats.rounds, sstats.exchanged,
                 sstats.donated, sstats.occupancy_balance)

    # ------------------------------------------------------ streaming jobs
    def _run_streaming(self, job: Job, cfg: SchedulerConfig,
                       stats: ServerStats) -> None:
        """Serve one streaming job (``spec.stream``) as a dedicated phase.

        A streaming job mutates its graph between drains, so it cannot
        share the fused wavefront (every other lane's kernel is compiled
        against the registry's immutable CSR); like sharded jobs it runs as
        a serialized phase — ``run_stream`` over the spec's delta log, with
        the spec's snapshot/resume posture (repro/stream).  ``shards > 1``
        makes each per-batch drain a device-wide sharded one.
        """
        from ..stream.driver import run_stream

        spec = job.spec
        stream = spec.stream
        graph = self.registry.graph(spec.graph)
        scfg = (dataclasses.replace(cfg, num_shards=spec.shards,
                                    topology="sharded")
                if spec.shards > 1 else
                dataclasses.replace(cfg, topology="single"))
        log.info("streaming job %d (%s on %s): %d delta batches",
                 job.job_id, spec.algorithm, spec.graph, len(stream.deltas))
        res = run_stream(
            spec.algorithm, graph, stream.deltas, scfg,
            params=dict(spec.params), queue_capacity=self._lane_capacity,
            incremental=stream.incremental,
            snapshot_every=stream.snapshot_every,
            checkpoint_dir=stream.checkpoint_dir, resume=stream.resume,
            compact_every=stream.compact_every,
            overlay_slack=stream.overlay_slack,
            trace=self.trace,
            trace_engine=f"server.job{job.job_id}.stream")
        job.result = np.asarray(res.result)
        job.stream_result = res
        tel = JobTelemetry(
            job_id=job.job_id, algorithm=spec.algorithm, graph=spec.graph,
            wavefront=scfg.wavefront * max(spec.shards, 1),
            ideal_work=0)
        tel.admitted_round = tel.completed_round = 0
        tel.rounds_active = res.info["rounds"]
        tel.items_processed = res.info["processed"]
        tel.work = res.info["work"]
        tel.dropped = res.info["dropped"]
        job.telemetry = tel
        if self.strict_drops and tel.dropped > 0:
            raise RuntimeError(
                f"streaming job {job.job_id} ({spec.algorithm} on "
                f"{spec.graph}) dropped {tel.dropped} tasks to queue "
                f"overflow — its result would be silently wrong.  Raise "
                f"lane_capacity (or pass strict_drops=False).")
        job.status = "done"
        stats.streaming_jobs += 1
        stats.stream_batches += len(res.batches)
        log.info("streaming job %d done: %d batches, %d rounds, work=%d",
                 job.job_id, len(res.batches), res.info["rounds"],
                 res.info["work"])

    # ------------------------------------------------------------------ run
    def run(self) -> ServerResult:
        """Drain every submitted job; returns per-job results + telemetry.

        Jobs with ``spec.shards > 1`` are served first as device-wide
        sharded phases; everything else shares the fused multi-tenant
        wavefront that follows.
        """
        cfg = self._resolve_config()
        if getattr(cfg, "kernel", "auto") == "megakernel":
            # the multi-tenant loop is host-driven by design (tenants are
            # admitted/finalized between rounds), so it cannot fuse a
            # tenant's whole drain into one launch — never degrade to the
            # persistent strategy silently.  Streaming jobs are unaffected:
            # their per-batch drains go through stream/driver, which does
            # honor the megakernel.
            log.warning(
                "kernel='megakernel' requested, but the multi-tenant "
                "server loop is host-driven (one dispatch per scheduling "
                "round) and cannot fuse a tenant's drain into one launch; "
                "batch jobs run the per-round wavefront instead (streaming "
                "jobs still drain via the megakernel).  Use "
                "runtime.execute() for a fused single-tenant drain.")
        W = cfg.wavefront
        lane_capacity = self._resolve_lane_capacity()
        stats = ServerStats(wavefront=W)
        trace = self.trace
        ring = trace.ring() if trace is not None else None
        t0 = time.perf_counter()
        for job in self._jobs:
            if job.status != "pending" or job.spec is None:
                continue
            if job.spec.stream is not None:
                self._run_streaming(job, cfg, stats)
            elif job.spec.shards > 1:
                self._run_sharded(job, cfg, stats)
        mq = make_multiqueue(lane_capacity, self.num_lanes)
        pending = deque(j for j in self._jobs if j.status == "pending")
        lane_owner: Dict[int, Job] = {}
        free_lanes = deque(range(self.num_lanes))
        prev_dropped = np.zeros(self.num_lanes, dtype=np.int64)
        backpressured = False
        rounds = 0

        while (pending or lane_owner) and rounds < self.max_rounds:
            # -- one snapshot per round drives completion, backpressure
            # detection, and quota allocation (two scalars-vectors synced;
            # everything else stays on device until a job finalizes).
            sizes = np.asarray(mq.lane_sizes(), dtype=np.int64)
            dropped_now = np.asarray(mq.lane_dropped(), dtype=np.int64)

            # -- completion: convergence predicate wins (its flag was
            # computed inside last round's step); otherwise a drained lane
            # finishes the job iff the program declares empty-means-done
            # (an empty_means_done=False program without a stop keeps
            # running its on_empty refills until max_rounds — the same
            # contract as the other engines, DESIGN.md section 11).
            for lane, job in list(lane_owner.items()):
                done = (job.stopped if job.program.stop is not None
                        else (sizes[lane] == 0
                              and job.program.empty_means_done))
                if done:
                    mq = self._finalize(job, mq, rounds)
                    del lane_owner[lane]
                    free_lanes.append(lane)
                    prev_dropped[lane] = dropped_now[lane] = 0
                    sizes[lane] = 0

            # -- admission control: drops observed last round defer new
            # tenants (the queue is telling us it is over-committed), unless
            # the server is idle and would otherwise deadlock the FIFO.
            if pending and (not backpressured or not lane_owner):
                while pending and free_lanes:
                    lane = free_lanes.popleft()
                    job = pending.popleft()
                    mq = self._admit(job, mq, lane, cfg, lane_capacity,
                                     rounds)
                    lane_owner[lane] = job
                    sizes[lane] = int(mq.lane(lane).size)  # seeded just now
            elif pending and backpressured:
                stats.deferred_admissions += 1
            if not lane_owner:
                break  # everything drained and nothing left to admit

            boosted = np.zeros(self.num_lanes, dtype=bool)
            weights = np.zeros(self.num_lanes)
            for lane, job in lane_owner.items():
                weights[lane] = job.weight
                if dropped_now[lane] > prev_dropped[lane]:
                    boosted[lane] = True
                    job.telemetry.backpressure_events += 1
                    stats.backpressure_events += 1
            backpressured = bool(boosted.any())
            prev_dropped = dropped_now

            # -- quota allocation: slot-denominated at granularity 1
            # (bit-for-bit the pre-granularity behavior); vertex-denominated
            # beyond (DESIGN.md section 12) — lane occupancy is chunk-width
            # weighted and the round budget is the wavefront's vertex
            # capacity W x G, so a coarse-chunk tenant is charged for the
            # vertices it actually advances, not the slots it occupies.
            granular = cfg.granularity > 1
            if granular:
                # one eager ring scan per occupied coarse lane per round
                # (widths live in the task bits; empty lanes are free).
                # Fine enough for the serving loop's O(lanes) host work —
                # an incremental load tracker would save the scan but put
                # a second copy of the occupancy invariant at risk.
                loads = sizes.copy()
                for lane, job in lane_owner.items():
                    if job.width_of is not None and sizes[lane] > 0:
                        loads[lane] = int(
                            mq.lane(lane).vertex_size(job.width_of))
                quotas = self.policy.allocate(loads, weights, boosted,
                                              W * cfg.granularity)
            else:
                quotas = self.policy.allocate(sizes, weights, boosted, W)

            # -- fused wavefront: every granted lane advances this round
            for lane, job in lane_owner.items():
                prog = job.program
                quota = int(quotas[lane])
                if quota > 0:
                    step = self._step_for(
                        prog.wavefront_fn, prog.stop, W, cfg.backend,
                        task_width=prog.task_width if granular else None,
                        work_fn=prog.work if trace is not None else None,
                        traced=trace is not None)
                    if trace is not None:
                        mq, job.state, job.counters, stopped, ring = step(
                            mq, lane, job.state, job.counters, quota,
                            job.job_id, ring, rounds)
                    else:
                        mq, job.state, job.counters, stopped = step(
                            mq, lane, job.state, job.counters, quota,
                            job.job_id)
                    job.telemetry.rounds_active += 1
                elif sizes[lane] == 0 and prog.on_empty is not None \
                        and not job.stopped:
                    estep = self._empty_step_for(prog.on_empty, prog.stop,
                                                 cfg.backend,
                                                 traced=trace is not None)
                    if trace is not None:
                        mq, job.state, stopped, ring = estep(
                            mq, lane, job.state, job.job_id, ring, rounds)
                    else:
                        mq, job.state, stopped = estep(
                            mq, lane, job.state, job.job_id)
                    job.telemetry.rounds_active += 1
                else:
                    continue
                if prog.stop is not None:
                    job.stopped = bool(stopped)

            rounds += 1

        if pending or lane_owner:
            unfinished = [j.job_id for j in self._jobs if j.status != "done"]
            raise RuntimeError(
                f"server hit max_rounds={self.max_rounds} with unfinished "
                f"jobs {unfinished}")

        stats.rounds = rounds
        stats.wall_seconds = time.perf_counter() - t0
        stats.items_processed = sum(
            j.telemetry.items_processed for j in self._jobs)
        if trace is not None:
            trace.drain(ring, engine="server")
            trace.add_metric(stats.as_dict())
            latency = trace.histogram("job_latency_rounds")
            delay = trace.histogram("job_queue_delay_rounds")
            for j in self._jobs:
                tel = j.telemetry
                if tel is None:
                    continue
                trace.add_metric(tel.as_dict())
                if tel.latency_rounds >= 0:
                    latency.add(tel.latency_rounds)
                if tel.queue_delay_rounds >= 0:
                    delay.add(tel.queue_delay_rounds)
                # per-job distribution: one sample per drain the job ran —
                # each delta batch for a streaming job, the whole drain for
                # a batch job — so p50/p99 are meaningful per tenant.
                per_job = trace.histogram(f"job{j.job_id}_latency_rounds")
                if j.stream_result is not None:
                    per_job.extend(b.rounds
                                   for b in j.stream_result.batches)
                elif tel.latency_rounds >= 0:
                    per_job.add(tel.latency_rounds)
        return ServerResult(
            results={j.job_id: j.result for j in self._jobs},
            telemetry={j.job_id: j.telemetry for j in self._jobs},
            stats=stats,
        )


def serve_sequential(
    registry: JobRegistry,
    specs: List[JobSpec],
    config: Optional[SchedulerConfig] = None,
    lane_capacity: Optional[int] = None,
    max_rounds: int = 1 << 17,
) -> ServerResult:
    """Baseline: each job runs alone (single lane, full wavefront).

    Total rounds are the sum over jobs — what a tenant-at-a-time deployment
    pays.  Job ids match submission order so results are comparable 1:1 with
    a fused :class:`TaskServer` run over the same specs.
    """
    results: Dict[int, np.ndarray] = {}
    telemetry: Dict[int, JobTelemetry] = {}
    stats = ServerStats()
    t0 = time.perf_counter()
    for i, spec in enumerate(specs):
        server = TaskServer(registry, num_lanes=1, config=config,
                            policy="weighted", lane_capacity=lane_capacity,
                            max_rounds=max_rounds)
        server.submit(spec)
        out = server.run()
        results[i] = out.results[0]
        tel = out.telemetry[0]
        tel.job_id = i
        telemetry[i] = tel
        stats.rounds += out.stats.rounds
        stats.items_processed += out.stats.items_processed
        stats.backpressure_events += out.stats.backpressure_events
        stats.wavefront = out.stats.wavefront
    stats.wall_seconds = time.perf_counter() - t0
    return ServerResult(results=results, telemetry=telemetry, stats=stats)
