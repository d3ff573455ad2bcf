"""The :class:`AtosProgram` protocol — declare a drain once, run it anywhere.

Atos's core claim is that one scheduling framework serves many irregular
applications by keeping the application logic orthogonal to the launch
strategy.  Before this layer the repo had three divergent drain engines
(``core/scheduler``, ``server/engine``, ``shard/driver``) and each algorithm
re-implemented its wavefront body, stop condition, rescan hook, and
replica-merge adapter per engine.  An ``AtosProgram`` packages all of that
*once*:

    init()                    -> (state, seed tasks)
    make_body(graph, ctx)     -> WavefrontFn        (the expansion kernel)
    make_on_empty(graph, ctx) -> optional refill    (PageRank's re-scan)
    stop(state)               -> optional convergence predicate
    empty_means_done          -> does a drained queue end the run?
    merge                     -> per-field replica-merge spec (sharded runs)
    task_vertex(task)         -> head vertex id (ownership/routing/stealing)
    task_width(task)          -> chunk width (vertex-denominated occupancy)
    dirty_seeds(delta, state) -> optional incremental re-seed (repro/stream)
    result(state), counters, ideal_work

The body builders receive a :class:`ProgramContext` describing *where* the
body will run: wavefront width, backend, and — under the sharded topology —
the device's (traced) shard index and the mesh axis name, so a program can
restrict its rescan to the owned vertex block or switch to an
epoch-consistent variant without knowing anything about the driver.

The **merge spec** replaces ``shard/programs.py``'s hand-written per-
algorithm merges.  Each state field declares its reconciliation lattice:

  * ``"pmin"`` / ``"pmax"``   — monotone lattices (BFS dist: the union of
    all relaxations is the elementwise min of the replicas);
  * ``"sum_delta"``           — exact for single-writer-per-round or
    additive fields: ``prev + psum(new - prev)`` reassembles the global
    round (PageRank residue scatter-adds, coloring's unique-target colors,
    every WorkCounter);
  * ``"or_delta"``            — boolean single-writer fields (presence bits);
  * ``"replicated"``          — already identical on every device (cursors
    advanced by the same constant each round): no collective.

A spec may be a dict over dataclass field names, one rule string applied to
the whole state pytree, or a bare callable ``(prev, new, axis_name) ->
merged`` for exotic states.
"""
from __future__ import annotations

import dataclasses
from typing import (Any, Callable, Dict, Mapping, NamedTuple, Optional, Tuple,
                    Union)

import jax
import jax.numpy as jnp


class ProgramContext(NamedTuple):
    """Where a wavefront body is about to run.

    ``shard``/``axis_name`` are ``None`` outside the sharded topology; under
    it, ``shard`` is the device's (traced) mesh index and ``axis_name`` the
    1-D mesh axis, and ``graph`` passed to the builders is the device-local
    CSR slice — static bounds (budgets, max degree) must come from the
    program's construction-time view of the global graph so every device
    traces the identical computation.
    """

    wavefront: int
    num_workers: int
    backend: str = "jnp"
    shard: Any = None            # traced device index | None
    num_shards: int = 1
    axis_name: Optional[str] = None
    granularity: int = 1         # max chunk width G (core/task.py)

    @property
    def sharded(self) -> bool:
        return self.axis_name is not None


def identity_task_vertex(items: jax.Array) -> jax.Array:
    return items


def unit_task_width(items: jax.Array) -> jax.Array:
    """Default ``task_width``: every task is one vertex wide (G = 1)."""
    return jnp.ones(jnp.asarray(items).shape, jnp.int32)


# ------------------------------------------------------------- merge rules
def delta_psum(prev: jax.Array, new: jax.Array, axis_name: str) -> jax.Array:
    """Exact cross-device merge for single-writer / additive round updates."""
    return prev + jax.lax.psum(new - prev, axis_name)


def _or_delta(prev: jax.Array, new: jax.Array, axis_name: str) -> jax.Array:
    d = delta_psum(prev.astype(jnp.int32), new.astype(jnp.int32), axis_name)
    return d > 0


def _merge_work_counter(prev, new, axis_name: str):
    """Field-level merge for a whole :class:`~repro.core.counters.WorkCounter`.

    ``work``/``splits``/``units`` are single-writer additive per round
    (delta-psum, exactly ``sum_delta``), but ``rounds`` ticks in lockstep on
    every replica (``wavefront_step`` bumps it unconditionally), so it must
    be taken as-is — delta-summing a replicated tick would multiply the
    round count by the shard count every round.
    """
    from ..core.counters import WorkCounter  # local: keep layering one-way

    assert isinstance(new, WorkCounter), new
    return dataclasses.replace(
        new,
        work=delta_psum(prev.work, new.work, axis_name),
        splits=delta_psum(prev.splits, new.splits, axis_name),
        units=delta_psum(prev.units, new.units, axis_name))


def _is_work_counter(x) -> bool:
    from ..core.counters import WorkCounter

    return isinstance(x, WorkCounter)


#: rules that consume a whole sub-pytree instead of individual array leaves
_merge_work_counter.whole = _is_work_counter  # type: ignore[attr-defined]


MERGE_RULES: Dict[str, Callable] = {
    "pmin": lambda prev, new, axis: jax.lax.pmin(new, axis),
    "pmax": lambda prev, new, axis: jax.lax.pmax(new, axis),
    "sum_delta": delta_psum,
    "or_delta": _or_delta,
    "replicated": lambda prev, new, axis: new,
    "work_counter": _merge_work_counter,
}

MergeSpec = Union[str, Callable, Dict[str, Union[str, Callable]]]


def _leafwise(rule: Callable, prev, new, axis_name: str):
    return jax.tree.map(lambda p, n: rule(p, n, axis_name), prev, new,
                        is_leaf=getattr(rule, "whole", None))


def build_merge(spec: MergeSpec) -> Callable[[Any, Any, str], Any]:
    """Compile a merge spec into ``merge(prev, new, axis_name) -> state``."""
    if callable(spec):
        return spec
    if isinstance(spec, str):
        rule = MERGE_RULES[spec]
        return lambda prev, new, axis: _leafwise(rule, prev, new, axis)
    if isinstance(spec, dict):
        rules = {name: (MERGE_RULES[r] if isinstance(r, str) else r)
                 for name, r in spec.items()}

        def merge(prev, new, axis_name):
            fields = {f.name for f in dataclasses.fields(prev)}
            unknown = set(rules) - fields
            if unknown:
                raise ValueError(
                    f"merge spec names unknown state fields {sorted(unknown)}")
            # a field-spec must be total: silently keeping `prev` for an
            # omitted field would drop that field's per-device updates after
            # every sharded round — wrong state with no error.  Fields that
            # really are identical on every device declare "replicated".
            missing = fields - set(rules)
            if missing:
                raise ValueError(
                    f"merge spec missing rules for state fields "
                    f"{sorted(missing)} (declare 'replicated' for fields "
                    f"that are identical on every device)")
            updates = {
                name: _leafwise(rule, getattr(prev, name), getattr(new, name),
                                axis_name)
                for name, rule in rules.items()
            }
            return dataclasses.replace(prev, **updates)

        return merge
    raise TypeError(f"bad merge spec: {spec!r}")


# ----------------------------------------------------------------- program
@dataclasses.dataclass(frozen=True)
class AtosProgram:
    """One drain, declared once, runnable under every execution policy.

    Construct via the per-algorithm factories (``bfs.make_program`` etc.) or
    directly for synthetic workloads; run via :func:`repro.runtime.execute`
    (any topology), the task server (fused multi-tenant), or
    ``repro.shard.run_sharded`` (device mesh).
    """

    name: str
    init: Callable[[], Tuple[Any, jax.Array]]
    make_body: Callable[..., Callable]       # (graph, ProgramContext) -> f
    result: Callable[[Any], Any]
    make_on_empty: Optional[Callable] = None  # (graph, ctx) -> on_empty fn
    stop: Optional[Callable[[Any], jax.Array]] = None
    #: does a globally empty queue end the drain?  Programs whose body (or
    #: ``on_empty``) legally refills a drained queue — PageRank's rotating
    #: rescan — declare False and must provide ``stop`` (or rely on
    #: ``max_rounds``).  This replaces the old implicit "``on_empty`` is set,
    #: so ignore queue size" inference (DESIGN.md section 11).
    empty_means_done: bool = True
    merge: MergeSpec = "sum_delta"
    #: task -> *head* vertex id; with chunked tasks (core/task.py) routing,
    #: ownership, and steal accounting all key off the chunk head (chunk
    #: formation guarantees every member shares the head's owner).
    task_vertex: Callable[[jax.Array], jax.Array] = identity_task_vertex
    #: task -> chunk width in vertices; drives vertex-denominated queue
    #: occupancy, fairness quotas, and steal plans (DESIGN.md section 12).
    task_width: Callable[[jax.Array], jax.Array] = unit_task_width
    #: the state counters the program keeps, by trace field
    #: (``obs/schema.TRACE_FIELDS``): name -> state -> traced int scalar.
    #: ``execute`` reports each in ``info``, the trace ring records each
    #: one's per-round delta, and one not declared reads 0.  ``work`` is the
    #: tasks processed (Table 4's unit), ``splits`` the chunks split by the
    #: formation threshold, ``units`` the merge-path work units expanded
    #: (``WorkCounter``), ``wide_picks`` coloring's full-palette rounds.
    counters: Mapping[str, Callable[[Any], jax.Array]] = dataclasses.field(
        default_factory=dict)
    ideal_work: int = 0
    #: capacity hint when the caller does not size the queue explicitly
    default_queue_capacity: int = 1024
    #: optional streaming hook (repro/stream): ``dirty_seeds(applied, state)
    #: -> (state', seeds)`` re-seeds only the frontier invalidated by a
    #: committed edge-delta batch.  ``applied`` is a
    #: :class:`~repro.stream.ingest.AppliedDelta` whose ``new_graph`` is the
    #: graph this program was built on; ``state`` is the previous drain's
    #: final state (shapes match: deltas change edges, never the vertex
    #: count).  ``None`` means "no incremental rule": the stream driver
    #: falls back to a conservative full reseed via ``init()``.
    dirty_seeds: Optional[Callable[[Any, Any], Tuple[Any, jax.Array]]] = None

    # ------------------------------------------------------------- helpers
    def body(self, graph, ctx: ProgramContext):
        return self.make_body(graph, ctx)

    def on_empty(self, graph, ctx: ProgramContext):
        if self.make_on_empty is None:
            return None
        return self.make_on_empty(graph, ctx)

    def merge_fn(self) -> Callable[[Any, Any, str], Any]:
        return build_merge(self.merge)

    def count_of(self, name: str, state) -> int:
        """The counter ``name`` of ``state`` on the host (0 if undeclared)."""
        read = self.counters.get(name)
        return 0 if read is None else int(read(state))

    def counts(self, state) -> Dict[str, jax.Array]:
        """Trace field -> traced value of each declared counter."""
        return {name: read(state) for name, read in self.counters.items()}

    # ----------------------------------------------------- legacy adapters
    @property
    def algorithm(self) -> str:
        """Deprecated alias (pre-runtime ``ShardProgram.algorithm``)."""
        return self.name

    @property
    def rescans(self) -> bool:
        """Deprecated alias (pre-runtime ``ShardProgram.rescans``)."""
        return not self.empty_means_done
