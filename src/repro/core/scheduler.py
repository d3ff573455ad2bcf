"""Persistent and discrete schedulers — Atos's kernel-strategy axis on TPU.

Atos launches workers either as a *persistent* kernel (one launch; workers
loop, popping from the shared queue until it drains) or as *discrete* kernels
(one launch per scheduling round).  On TPU the launch boundary is the
host->device dispatch:

  * ``persistent_run``  — the whole drain loop is a single fused
    ``jax.lax.while_loop``; zero host round-trips, one XLA executable.  This
    is the persistent-kernel analogue and removes the "small frontier"
    fixed cost exactly as in the paper.
  * ``discrete_run``    — a host-side Python loop around one jitted wavefront
    step; every round pays a dispatch + a one-scalar device->host sync on the
    continuation flag (the analogue of per-kernel launch overhead + the BSP
    barrier).  The stop predicate is folded *into* the jitted step
    (DESIGN.md section 11), so the host never evaluates ``stop(state)``
    eagerly per round.
  * ``megakernel_drive`` — the literal persistent kernel (DESIGN.md
    section 14): the whole drain loop is fused into a single Pallas kernel
    launch (``kernels/drain_loop``) that owns the queue buffers and
    DMA-streams CSR row slices in-kernel; selected by
    ``SchedulerConfig(kernel="megakernel")`` through the runtime layer.

Both drivers run the same *wavefront step*: pop ``num_workers x fetch_size``
tasks, apply the application function f, push the produced tasks.  Since the
runtime layer (``repro/runtime``) the step core is parameterized over a
:class:`QueueOps` triple, so the exact same ``wavefront_step`` drives the
single-device ``TaskQueue``, the task server's packed ``MultiQueue`` lanes,
and the sharded per-device replicas with routed exchange — three policy
drivers, one core.

API mirror of Atos's ``launchWarp/launchCTA(ifPersist, numBlock, numThread,
f1, f2, ...)``: here ``ifPersist`` picks the driver, ``num_workers`` plays
numBlock, ``fetch_size`` plays FETCH_SIZE, ``f`` plays f1.  ``on_empty``
(Atos's f2) runs when a pop returns no valid items but the stop condition has
not fired — useful for PageRank's residual re-scan.  Whether an empty queue
*ends* the drain is an explicit declaration (``empty_means_done``), not an
inference from ``on_empty``'s presence (see :func:`resolve_empty_means_done`).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ..obs.spans import ON_EMPTY, POP, PUSH
from .counters import WorkCounter
from .queue import TaskQueue

# f(items, valid, state) -> (new_items, new_mask, new_state)
WavefrontFn = Callable[[jax.Array, jax.Array, Any], Tuple[jax.Array, jax.Array, Any]]


class RunStats(NamedTuple):
    rounds: jax.Array          # wavefronts executed
    items_processed: jax.Array  # total valid items popped (overwork metric)
    dropped: jax.Array         # queue overflow drops (must be 0 in tests)


class QueueOps(NamedTuple):
    """The three queue operations the shared wavefront step is generic over.

    Each engine supplies its own triple: the single-device scheduler wraps a
    plain :class:`~repro.core.queue.TaskQueue`, the task server wraps one
    ``MultiQueue`` lane with (job_id, payload) packing, and the sharded
    driver wraps a per-device replica whose push is the routed all-to-all
    exchange.  ``queue`` below is whatever pytree the engine threads through.
    """

    pop: Callable[[Any], Tuple[jax.Array, jax.Array, Any]]  # q -> items, valid, q'
    push: Callable[[Any, jax.Array, jax.Array], Any]        # q, items, mask -> q'
    size: Callable[[Any], jax.Array]                        # q -> live item count


@dataclasses.dataclass(frozen=True)
class SchedulerConfig:
    """Atos launch configuration (see Listing 3 of the paper).

    ``backend`` is the kernel-backend axis (DESIGN.md section 9): ``"jnp"``
    (reference, default — bit-exact and fastest on CPU), ``"pallas"`` (the
    TPU kernels: LBS expansion + stream-compaction push; interpret mode
    off-TPU), or ``"auto"`` (pallas iff a TPU is attached).  Results are
    bit-identical across backends, so the autotuner searches this axis
    alongside the paper's three (``server/autotune.py``).

    ``topology`` is the execution-policy axis (DESIGN.md section 11):
    ``"single"`` (one TaskQueue, the classic drain), ``"fused"`` (the drain
    runs through a packed MultiQueue lane — the task server's engine),
    ``"sharded"`` (per-device queue replicas over a 1-D mesh, repro/shard),
    or ``"auto"`` (sharded iff ``num_shards > 1``, else single).  Together
    with ``persistent`` and ``granularity`` it forms the 3 x 2 x G
    :class:`~repro.runtime.policy.ExecutionPolicy` matrix every
    :class:`~repro.runtime.program.AtosProgram` runs under unchanged.

    ``num_shards`` is the device-mesh axis (DESIGN.md section 10): with
    ``num_shards > 1`` the drain runs one queue replica per device of a 1-D
    ``("shard",)`` mesh, routing produced tasks to their owner shard every
    round (``repro/shard``).  ``num_workers x fetch_size`` is then the
    *per-device* wavefront.  ``steal_threshold`` enables work stealing: when
    ``(max - min)`` queue occupancy exceeds ``steal_threshold x mean``, rich
    shards donate up to ``steal_chunk`` owned tasks to their ring successor
    before the next round; ``0.0`` disables stealing.

    ``mesh_shape`` (DESIGN.md section 16) folds the shard axis into a 2-D
    ``("row", "col")`` mesh of ``rows x cols == num_shards`` devices: the
    routed exchange then decomposes into two smaller per-axis all_to_alls
    (dimension-ordered: column hop, then row hop) instead of one global
    one.  ``None`` (default) keeps the 1-D ``("shard",)`` ring exactly.

    ``defer_rounds`` (DESIGN.md section 16) relaxes exchange delivery by
    that many rounds (0 = strict, today's round-synchronous path bit for
    bit; 1 = double-buffered overlap: round ``k``'s routed tasks land in a
    staging buffer and enter the owner's queue at the start of round
    ``k+1``, so the collective overlaps round ``k+1``'s expansion on
    already-delivered work).  Legal under Atos semantics — tasks are
    idempotent re-checks, so delaying delivery changes the schedule, never
    the fixpoint; the global stop predicate counts staged tasks as live.

    ``compress`` (DESIGN.md section 16) delta-compresses exchange payloads
    before the wire (shard/codec.py: sorted-run delta + zigzag bit-packing
    with a raw fallback); results are unchanged and the wire meters record
    compressed words instead of raw buffer slots.

    ``kernel`` names the kernel strategy explicitly (DESIGN.md section 14):
    ``"persistent"`` / ``"discrete"`` are the two strategies ``persistent``
    has always toggled between; ``"megakernel"`` fuses the whole drain loop
    into a single Pallas kernel launch (``kernels/drain_loop``) with
    in-kernel DMA-streamed CSR expansion — bit-identical results, one
    kernel entry per drain instead of one per round.  The default
    ``"auto"`` defers to the legacy ``persistent`` bool so every existing
    config resolves exactly as before; configs naming ``"megakernel"``
    should keep ``persistent=True`` (the device-resident strategy it
    degrades to wherever only the bool is consulted).

    ``granularity`` is the task-granularity axis (DESIGN.md section 12):
    the maximum chunk width ``G`` — how many consecutive CSR rows one queue
    slot may carry (core/task.py).  ``1`` (default) is the pre-granularity
    single-vertex task, bit-for-bit; larger values let seed frontiers and
    coalescible pushes ride in coarse chunks, so one ``num_workers x
    fetch_size`` wavefront of slots advances up to ``G`` times as many
    vertices.  ``split_threshold`` caps a chunk's CSR degree-sum at
    formation time (0 = bounded only by the merge-path work budget): the
    paper's level-of-balancing dial — a low threshold keeps hub-bearing
    chunks fine on heavy-tailed graphs, a high one lets mesh-like graphs
    coarsen freely.
    """

    num_workers: int = 64        # numBlock — parallel workers per wavefront
    fetch_size: int = 1          # FETCH_SIZE — items each worker pops
    persistent: bool = True      # ifPersist — kernel strategy
    max_rounds: int = 1 << 16    # safety bound for while_loop
    backend: str = "jnp"         # kernel backend: jnp | pallas | auto
    topology: str = "auto"       # execution topology: single|fused|sharded|auto
    num_shards: int = 1          # device-mesh axis (repro/shard)
    steal_threshold: float = 0.0  # occupancy-skew trigger; 0 = stealing off
    steal_chunk: int = 64        # max tasks donated per shard per round
    granularity: int = 1         # max chunk width G (core/task.py); 1 = fine
    split_threshold: int = 0     # chunk degree-sum cap; 0 = work-budget only
    kernel: str = "auto"         # persistent | discrete | megakernel | auto
    mesh_shape: Optional[Tuple[int, int]] = None  # (rows, cols) 2-D mesh
    defer_rounds: int = 0        # exchange delivery relaxation (0 = strict)
    compress: bool = False       # delta-compress exchange payloads (codec)

    @property
    def wavefront(self) -> int:
        return self.num_workers * self.fetch_size


def taskqueue_ops(cfg: SchedulerConfig) -> QueueOps:
    """The single-device engine's ops: one plain TaskQueue."""
    w = cfg.wavefront
    return QueueOps(
        pop=lambda q: q.pop(w),
        push=lambda q, items, mask: q.push(items, mask, backend=cfg.backend),
        size=lambda q: q.size,
    )


def wavefront_step(f: WavefrontFn, on_empty, ops: QueueOps, carry,
                   *, always_run_body: bool = False):
    """One scheduling round, generic over the queue implementation.

    ``carry = (queue, state, rounds, processed)``.  When the pop yields no
    valid item, the body is skipped and ``on_empty`` (if any) runs instead —
    unless ``always_run_body`` is set, in which case ``f`` runs on the
    zero-valid wavefront (the sharded engine's mode: a rescan folded into
    ``f`` must advance even on a drained replica, and SPMD lockstep forbids
    data-dependent branching across devices anyway).

    Each phase runs under its device scope (``repro.obs.spans``): the pop
    under ``atos.pop``, the push under ``atos.push``, the empty branch under
    ``atos.on_empty``; ``f`` names its own expand / apply / push phases.
    """
    queue, state, rounds, processed = carry
    with jax.named_scope(POP):
        items, valid, queue = ops.pop(queue)
        n_valid = jnp.sum(valid.astype(jnp.int32))

    def push(q, out, mask):
        with jax.named_scope(PUSH):
            return ops.push(q, out, mask)

    if always_run_body:
        out, mask, state = f(items, valid, state)
        queue = push(queue, out, mask)
    else:
        def run_f(args):
            q, s = args
            out, mask, s2 = f(items, valid, s)
            return push(q, out, mask), s2

        def run_empty(args):
            q, s = args
            if on_empty is None:
                return q, s
            with jax.named_scope(ON_EMPTY):
                out, mask, s2 = on_empty(s)
            return push(q, out, mask), s2

        queue, state = jax.lax.cond(n_valid > 0, run_f, run_empty,
                                    (queue, state))
    # one source of truth for round counts: every WorkCounter in the state
    # ticks exactly once per step (empty rounds included), matching the
    # driver-level ``rounds`` carry element.
    state = jax.tree_util.tree_map(
        lambda x: x.bump_round() if isinstance(x, WorkCounter) else x,
        state, is_leaf=lambda x: isinstance(x, WorkCounter))
    return queue, state, rounds + 1, processed + n_valid


def resolve_empty_means_done(on_empty, empty_means_done: Optional[bool]) -> bool:
    """Explicit-declaration default: historically the mere *presence* of
    ``on_empty`` silently dropped the ``queue.size > 0`` term from the
    continuation — a drain with ``on_empty`` but no ``stop`` ran to
    ``max_rounds`` even after the queue emptied for good.  Programs now
    declare the interaction (``AtosProgram.empty_means_done``); ``None``
    preserves the legacy inference for the deprecated raw entry points.
    """
    return on_empty is None if empty_means_done is None else empty_means_done


def continuation(ops: QueueOps, cfg: SchedulerConfig, stop,
                 empty_means_done: bool):
    """The shared while-condition: bounded rounds, optional drain/stop terms."""

    def cond(carry):
        queue, state, rounds, _ = carry
        more = rounds < cfg.max_rounds
        if empty_means_done:
            more &= ops.size(queue) > 0
        if stop is not None:
            more &= ~stop(state)
        return more

    return cond


# ----------------------------------------------------------------- drivers
def persistent_drive(step, cond, carry0):
    """Whole drain in one ``lax.while_loop`` (zero host round-trips)."""
    return jax.lax.while_loop(cond, step, carry0)


def megakernel_drive(step, cond, carry0, *, limit=None, interpret=None):
    """Whole drain in ONE fused Pallas kernel launch (DESIGN.md §14).

    The third kernel strategy: where :func:`persistent_drive` still
    re-enters the expand/push kernels every round of its while-loop, the
    megakernel evaluates the identical loop jaxpr *inside* a single
    ``pallas_call`` — bit-identical by construction, one kernel entry per
    drain.  ``limit`` bounds the segment for the streaming snapshot layer.
    Imported lazily: kernels/ imports this module's types.
    """
    from ..kernels.drain_loop.ops import megakernel_drive as _drive

    return _drive(step, cond, carry0, limit=limit, interpret=interpret)


def megakernel_segment(step, cond, example_carry, *, interpret=None):
    """Build-once segmented megakernel driver for the snapshot layer.

    Returns ``seg(carry, limit)``: the round limit rides as a kernel
    operand, so one traced jaxpr / pallas_call serves every snapshot
    segment (:func:`repro.kernels.drain_loop.ops.make_megakernel_segment`)
    — the fused analogue of jitting one persistent segment function and
    reusing it with ``limit`` as a traced argument.  Imported lazily:
    kernels/ imports this module's types.
    """
    from ..kernels.drain_loop.ops import make_megakernel_segment

    return make_megakernel_segment(step, cond, example_carry,
                                   interpret=interpret)


def discrete_drive(step, cond, carry0):
    """Host loop, one jitted round per iteration (discrete kernels).

    The continuation predicate — including any ``stop(state)`` — is
    evaluated *inside* the jitted step, so each round costs exactly one
    scalar device->host sync (the flag) instead of a full ``stop``
    evaluation + retrace hazard on the host.  Per-round telemetry comes
    from the obs ring (``runtime.execute(..., trace=obs.Trace())``), which
    adds no host sync.
    """

    @jax.jit
    def round_step(carry):
        carry = step(carry)
        return carry, cond(carry)

    carry = carry0
    # cond on concrete arrays evaluates eagerly — the pre-loop check costs
    # one tiny dispatch, never a per-round one.
    more = bool(cond(carry0))
    while more:
        carry, more_dev = round_step(carry)
        more = bool(more_dev)  # the one per-round device->host sync
    return carry


# ---------------------------------------------------- TaskQueue entry points
def persistent_run(
    f: WavefrontFn,
    queue: TaskQueue,
    state: Any,
    cfg: SchedulerConfig,
    stop: Optional[Callable[[Any], jax.Array]] = None,
    on_empty=None,
    empty_means_done: Optional[bool] = None,
):
    """Run until the queue drains (or ``stop(state)``), fully on device."""
    ops = taskqueue_ops(cfg)
    cond = continuation(ops, cfg, stop,
                        resolve_empty_means_done(on_empty, empty_means_done))
    step = lambda carry: wavefront_step(f, on_empty, ops, carry)
    q, s, rounds, processed = persistent_drive(
        step, cond, (queue, state, jnp.int32(0), jnp.int32(0)))
    return q, s, RunStats(rounds, processed, q.dropped)


def discrete_run(
    f: WavefrontFn,
    queue: TaskQueue,
    state: Any,
    cfg: SchedulerConfig,
    stop: Optional[Callable[[Any], jax.Array]] = None,
    on_empty=None,
    empty_means_done: Optional[bool] = None,
):
    """Host-driven loop: one jitted wavefront per round (discrete kernels)."""
    ops = taskqueue_ops(cfg)
    cond = continuation(ops, cfg, stop,
                        resolve_empty_means_done(on_empty, empty_means_done))
    step = lambda carry: wavefront_step(f, on_empty, ops, carry)
    q, s, rounds, processed = discrete_drive(
        step, cond, (queue, state, jnp.int32(0), jnp.int32(0)))
    return q, s, RunStats(rounds, processed, q.dropped)


def megakernel_run(
    f: WavefrontFn,
    queue: TaskQueue,
    state: Any,
    cfg: SchedulerConfig,
    stop: Optional[Callable[[Any], jax.Array]] = None,
    on_empty=None,
    empty_means_done: Optional[bool] = None,
):
    """Run the whole drain as ONE fused Pallas launch (DESIGN.md §14).

    The raw-``WavefrontFn`` analogue of the runtime layer's megakernel
    dispatch, so ``cfg.kernel="megakernel"`` is honored — not silently
    degraded to the persistent strategy — even through the legacy
    :func:`run` front door.
    """
    # queue ops inside the fused drain run the jnp reference — a nested
    # compaction kernel would add launch structure without changing a bit
    # (the runtime layer does the same, runtime/api._shared_setup).
    ops = taskqueue_ops(dataclasses.replace(cfg, backend="jnp"))
    cond = continuation(ops, cfg, stop,
                        resolve_empty_means_done(on_empty, empty_means_done))
    step = lambda carry: wavefront_step(f, on_empty, ops, carry)
    q, s, rounds, processed = megakernel_drive(
        step, cond, (queue, state, jnp.int32(0), jnp.int32(0)))
    return q, s, RunStats(rounds, processed, q.dropped)


def run(f, queue, state, cfg: SchedulerConfig, stop=None, on_empty=None,
        empty_means_done: Optional[bool] = None):
    """Dispatch on the kernel strategy — the Atos ``ifPersist`` switch,
    three-valued since the megakernel: an explicit
    ``cfg.kernel="megakernel"`` routes to :func:`megakernel_run` (the
    legacy ``persistent`` bool alone never selects it).

    Deprecated front door: new code should express the drain as an
    :class:`~repro.runtime.program.AtosProgram` and call
    :func:`repro.runtime.execute`, which also serves the fused and sharded
    topologies.  This shim remains for raw-``WavefrontFn`` callers.
    """
    if getattr(cfg, "kernel", "auto") == "megakernel":
        return megakernel_run(f, queue, state, cfg, stop=stop,
                              on_empty=on_empty,
                              empty_means_done=empty_means_done)
    if cfg.persistent:
        return persistent_run(f, queue, state, cfg, stop=stop,
                              on_empty=on_empty,
                              empty_means_done=empty_means_done)
    return discrete_run(f, queue, state, cfg, stop=stop, on_empty=on_empty,
                        empty_means_done=empty_means_done)


# ------------------------------------------------------- deprecated aliases
def _wavefront_step(f: WavefrontFn, on_empty, cfg: SchedulerConfig, carry):
    """Deprecated: pre-runtime-layer signature (one PR grace period)."""
    return wavefront_step(f, on_empty, taskqueue_ops(cfg), carry)


def partial_step(f, on_empty, cfg):
    """Deprecated: pre-runtime-layer step builder (one PR grace period)."""
    ops = taskqueue_ops(cfg)

    def step(carry):
        return wavefront_step(f, on_empty, ops, carry)

    return step
