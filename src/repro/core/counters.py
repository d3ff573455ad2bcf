"""Instrumentation for the paper's analysis artifacts.

The paper's quantitative story rests on three measurements:
  * runtime/speedup          (Table 1)  -> benchmarks/bench_table1.py
  * workload ratio / overwork (Table 4) -> ``WorkCounter``
  * throughput vs. time      (Figs 1-3) -> per-round traces (discrete driver)

``WorkCounter`` threads through algorithm state; every processed item bumps
``work``; the ideal workload (|V| for coloring, |E| for BFS, etc.) is fixed
per algorithm, giving ``overwork = work / ideal`` — the Table 4 metric.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class WorkCounter:
    work: jax.Array  # vertices processed (int32)
    #: chunks the push-side coalescer declined to form because their CSR
    #: degree-sum exceeded the split threshold or they crossed a shard
    #: boundary (core/task.coalesce_chunks) — the task-granularity dial's
    #: engagement meter (DESIGN.md section 12).  Always 0 at granularity 1.
    splits: jax.Array
    #: scheduling rounds this counter's state has been driven through —
    #: bumped exactly once per :func:`~repro.core.scheduler.wavefront_step`
    #: (empty rounds included), so overwork and round counts come from ONE
    #: source of truth instead of each driver recomputing its own.  Under
    #: the sharded topology every replica bumps in lockstep, so the merge
    #: rule is replicated-take-new, not delta-sum (runtime/program
    #: ``"work_counter"``).
    rounds: jax.Array = dataclasses.field(
        default_factory=lambda: jnp.int32(0))
    #: work units the merge-path expansions produced, ``min(total,
    #: budget)`` a round: the lanes of the budget the load-balancing
    #: search gives an edge (the rest are past the round's total).  0
    #: where a body expands per item.
    units: jax.Array = dataclasses.field(
        default_factory=lambda: jnp.int32(0))

    @staticmethod
    def zero() -> "WorkCounter":
        return WorkCounter(work=jnp.int32(0), splits=jnp.int32(0),
                           rounds=jnp.int32(0), units=jnp.int32(0))

    def add(self, n) -> "WorkCounter":
        return dataclasses.replace(
            self, work=self.work + jnp.asarray(n, jnp.int32))

    def add_splits(self, n) -> "WorkCounter":
        return dataclasses.replace(
            self, splits=self.splits + jnp.asarray(n, jnp.int32))

    def add_units(self, total, budget: int) -> "WorkCounter":
        """Count one merge-path expansion of ``total`` units on a
        ``budget``-lane work list."""
        return dataclasses.replace(
            self, units=self.units + jnp.minimum(total, budget))

    def bump_round(self) -> "WorkCounter":
        return dataclasses.replace(self, rounds=self.rounds + jnp.int32(1))


def overwork_ratio(counter: WorkCounter, ideal: int) -> float:
    return float(counter.work) / float(max(ideal, 1))


@dataclasses.dataclass
class JobTelemetry:
    """Per-tenant metering for the multi-job task server (host-side).

    Layered on ``WorkCounter``: ``work`` is the job's counter value at
    completion, ``ideal_work`` the algorithm's minimum (|V| for our three
    workloads), so ``overwork`` is the Table 4 metric per tenant.  Rounds are
    *server* scheduling rounds, so ``latency_rounds`` is queueing delay plus
    service time — the serving-system view of the paper's round counts.
    """

    job_id: int
    algorithm: str
    graph: str
    wavefront: int                 # server W — denominator for occupancy
    ideal_work: int
    submitted_round: int = 0
    admitted_round: int = -1       # -1 while waiting for a lane
    completed_round: int = -1
    rounds_active: int = 0         # rounds with quota > 0 or an on_empty step
    items_processed: int = 0       # valid tasks popped for this job
    #: vertices those pops actually advanced (sum of chunk widths).  At
    #: granularity 1 this equals ``items_processed``; beyond it, quotas
    #: are vertex-denominated (DESIGN.md section 12), so occupancy must
    #: count vertices too — a width-4 chunk fills 4 vertex slots of the
    #: round budget, not 1.  0 means "not metered" (legacy paths) and
    #: occupancy falls back to the item count.
    vertices_processed: int = 0
    #: the server's chunk-width cap G — the occupancy denominator is the
    #: round budget ``rounds_active x wavefront x G`` (vertex units)
    granularity: int = 1
    work: int = 0                  # WorkCounter at completion
    dropped: int = 0               # lane overflow drops attributed to the job
    backpressure_events: int = 0   # rounds the lane was drain-boosted
    routing_mismatches: int = 0    # packed job_id != lane owner (must be 0)

    @property
    def latency_rounds(self) -> int:
        if self.completed_round < 0:
            return -1
        return self.completed_round - self.submitted_round

    @property
    def queue_delay_rounds(self) -> int:
        if self.admitted_round < 0:
            return -1
        return self.admitted_round - self.submitted_round

    @property
    def occupancy(self) -> float:
        """Mean fraction of the round budget this job filled while active.

        Vertex-denominated, matching the quota allocator: the numerator is
        the vertices the job's pops advanced (chunk-width weighted) and the
        denominator is ``rounds_active x wavefront x granularity`` — the
        vertex capacity of the rounds it was granted.  At granularity 1
        both reduce to the pre-granularity item/slot accounting bit-for-
        bit.  Paths that never metered vertices (``vertices_processed ==
        0`` with items popped) fall back to the item count.
        """
        denom = self.rounds_active * self.wavefront * max(self.granularity, 1)
        if not denom:
            return 0.0
        filled = self.vertices_processed or self.items_processed
        return filled / denom

    @property
    def overwork(self) -> float:
        return self.work / max(self.ideal_work, 1)

    def as_dict(self) -> dict:
        """Serialize into the canonical ``job`` metric doc (obs/schema)."""
        from ..obs.schema import metric_doc  # lazy: obs is a leaf layer

        d = dataclasses.asdict(self)
        d.update(latency_rounds=self.latency_rounds,
                 queue_delay_rounds=self.queue_delay_rounds,
                 occupancy=self.occupancy, overwork=self.overwork)
        return metric_doc("job", **d)
