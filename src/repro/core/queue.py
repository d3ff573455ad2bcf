"""Functional task queue — the TPU-native analogue of Atos's shared queue.

Atos (GPU) uses a single HBM-resident MPMC queue with atomic ``concurrent_pop``
/ ``concurrent_push``.  TPU cores cannot contend on an atomic counter, so this
module implements the *wavefront queue*: a fixed-capacity ring buffer (a JAX
pytree, so it lives in HBM and threads through ``lax.while_loop``) where

  * ``pop(n)`` removes up to ``n`` items at once — one *wavefront* of
    ``num_workers x fetch_size`` tasks, mirroring all Atos workers popping in
    the same scheduling round; and
  * ``push(items, mask)`` reserves slots with an **exclusive prefix sum** over
    the validity mask instead of an atomic ticket counter.  This is
    deterministic and collision-free by construction — the TPU-idiomatic
    replacement for ``atomicAdd`` reservation (see DESIGN.md section 2).
    ``push(..., backend="pallas")`` runs the reservation through the
    two-phase Pallas stream-compaction kernel (``kernels/queue_compact``)
    instead of the jnp prefix sum — bit-identical results, hardware hot
    path (DESIGN.md section 9).

The queue stores int32 task ids.  Atos tags tasks by sign (graph coloring) or
by payload; both patterns work unchanged here.  A ``num_lanes``-wide variant
(``MultiQueue``) gives per-priority/per-iteration lanes like Atos's
``init(..., num_queues, ...)``.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp

from .backend import resolve_backend

EMPTY = jnp.int32(-(2 ** 31))  # sentinel for "no item"


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class TaskQueue:
    """Fixed-capacity ring buffer of int32 task ids.

    Invariants (checked by tests/property tests):
      0 <= tail - head <= capacity      (int32 wraparound-safe for < 2^31 ops)
      buf[(head + i) % capacity] for i in [0, size) are the live items.
    """

    buf: jax.Array        # [capacity] int32
    head: jax.Array       # scalar int32 — pop cursor
    tail: jax.Array       # scalar int32 — push cursor
    dropped: jax.Array    # scalar int32 — items lost to overflow (diagnostic)

    # ------------------------------------------------------------------ api
    @property
    def capacity(self) -> int:
        return self.buf.shape[0]

    @property
    def size(self) -> jax.Array:
        return self.tail - self.head

    def empty(self) -> jax.Array:
        return self.size == 0

    def pop(self, n: int) -> Tuple[jax.Array, jax.Array, "TaskQueue"]:
        """Pop up to ``n`` items.

        Returns ``(items[n], valid[n], queue')``.  Missing items are EMPTY
        with ``valid=False``.  ``n`` is a static wavefront width.
        """
        return self.pop_upto(n, n)

    def pop_upto(self, n: int, quota,
                 width_of=None) -> Tuple[jax.Array, jax.Array, "TaskQueue"]:
        """Pop up to ``quota``'s worth of items into an ``n``-wide wavefront.

        ``n`` is the static buffer width (compiled shape); ``quota`` may be a
        traced scalar — the dynamic share a fairness policy granted this
        queue for the round (see server/policies.py).  Lanes beyond the quota
        are EMPTY/invalid, so the same compiled step serves every quota.

        Without ``width_of`` the quota counts *slots* (one item each) — the
        pre-granularity behavior, unchanged bit-for-bit.  With ``width_of``
        (an item -> chunk-width function, see core/task.py) the quota counts
        **vertices**: the pop takes the longest slot prefix whose cumulative
        width stays within the quota, so a fairness share or a steal plan
        expressed in units of work grants fewer slots to coarse-chunk lanes.
        A chunk is never split by a pop — the first slot always pops when
        the quota is positive-enough only if its whole width fits; quota 0
        pops nothing either way.
        """
        quota = jnp.asarray(quota, jnp.int32)
        idx = (self.head + jnp.arange(n, dtype=jnp.int32)) % self.capacity
        items = self.buf[idx]
        in_queue = jnp.arange(n, dtype=jnp.int32) < jnp.minimum(
            jnp.int32(n), self.size)
        if width_of is None:
            valid = in_queue & (jnp.arange(n, dtype=jnp.int32) < quota)
        else:
            w = jnp.where(in_queue, jnp.asarray(width_of(items), jnp.int32), 0)
            # widths >= 1 inside the queue keep the cumsum strictly
            # increasing over live slots, so the quota cut is a prefix.
            valid = in_queue & (jnp.cumsum(w) <= quota)
        k = jnp.sum(valid.astype(jnp.int32))
        items = jnp.where(valid, items, EMPTY)
        q = dataclasses.replace(self, head=self.head + k)
        return items, valid, q

    def vertex_size(self, width_of=None) -> jax.Array:
        """Occupancy in *vertices*: the sum of live slots' chunk widths.

        ``width_of=None`` (or a width-1 codec) degenerates to :attr:`size`.
        Computed by scanning the ring's live window — chunk widths are
        carried by the task bits themselves (core/task.py), so the queue
        needs no auxiliary state and the pre-granularity pytree layout is
        untouched.
        """
        if width_of is None:
            return self.size
        cap = self.capacity
        i = jnp.arange(cap, dtype=jnp.int32)
        live = ((i - self.head) % cap) < self.size
        return jnp.sum(jnp.where(live,
                                 jnp.asarray(width_of(self.buf), jnp.int32),
                                 0))

    def push(self, items: jax.Array, mask: jax.Array,
             backend: str = "jnp") -> "TaskQueue":
        """Push ``items[mask]`` — prefix-sum slot reservation.

        Each valid item i gets slot ``tail + excl_cumsum(mask)[i]``: the
        valid items are compacted in order and committed as one contiguous
        run of the ring.  Items beyond capacity are dropped and counted
        (Atos's queue is sized to never overflow; we keep the counter so
        tests & benchmarks can assert no drops happened).

        ``backend="pallas"`` compacts through the Pallas stream-compaction
        kernel (``kernels/queue_compact``) instead of the jnp prefix sum;
        the resulting queue pytree — buffer contents, cursors, dropped
        counter — is bit-identical to the jnp path (tested in
        tests/test_backend.py).
        """
        if resolve_backend(backend) == "pallas":
            from ..kernels.queue_compact.ops import compact  # lazy: kernels->core

            compacted, count = compact(items, mask.astype(bool))
        else:
            mask = mask.astype(jnp.int32)
            offs = jnp.cumsum(mask) - mask  # exclusive prefix sum
            # invalid items add 0 at the next valid item's slot, so the
            # indices stay sorted and no sort precedes the scatter
            compacted = jnp.zeros_like(items).at[offs].add(
                jnp.where(mask > 0, items, 0), mode="drop",
                indices_are_sorted=True)
            count = jnp.sum(mask)
        free = self.capacity - self.size
        n_push = jnp.minimum(count, free)
        return dataclasses.replace(
            self, buf=_ring_write(self.buf, self.tail, compacted, n_push),
            tail=self.tail + n_push, dropped=self.dropped + (count - n_push))

    def push_dense(self, items: jax.Array, backend: str = "jnp") -> "TaskQueue":
        """Push every element of ``items`` (all valid)."""
        return self.push(items, jnp.ones(items.shape, dtype=bool),
                         backend=backend)


def _ring_write(buf: jax.Array, start, vals: jax.Array, n) -> jax.Array:
    """``buf[(start + j) % capacity] = vals[j]`` for ``j < n``.

    Two masked contiguous windows (the run before the ring's end, then
    its wrapped remainder) instead of a scatter: a TPU scatter first sorts
    its indices.  A ring narrower than ``vals`` takes the scatter.
    """
    cap, k = buf.shape[0], vals.shape[0]
    j = jnp.arange(k, dtype=jnp.int32)
    t = start % cap
    if k > cap:
        return buf.at[jnp.where(j < n, (t + j) % cap, cap)].set(
            vals, mode="drop")
    pad = jnp.zeros((k,), vals.dtype)
    ext = jnp.concatenate([pad, vals, pad])     # ext[k + i] = vals[i]
    for lo, src in ((jnp.minimum(t, cap - k), None), (jnp.int32(0), cap - t)):
        # ring slot lo + j holds vals[lo + j - t] (window 1) or, past the
        # wrap, vals[j + cap - t] (window 2)
        off = lo - t if src is None else src
        want = ((j + off >= 0) & (j + off < n) if src is None
                else j + off < n)
        old = jax.lax.dynamic_slice(buf, (lo,), (k,))
        new = jax.lax.dynamic_slice(ext, (k + off,), (k,))
        buf = jax.lax.dynamic_update_slice(buf, jnp.where(want, new, old),
                                           (lo,))
    return buf


def make_queue(capacity: int, init_items: jax.Array | None = None) -> TaskQueue:
    """Build an empty queue, optionally seeded with ``init_items`` (1-D)."""
    q = TaskQueue(
        buf=jnp.full((capacity,), EMPTY, dtype=jnp.int32),
        head=jnp.int32(0),
        tail=jnp.int32(0),
        dropped=jnp.int32(0),
    )
    if init_items is not None:
        q = q.push_dense(jnp.asarray(init_items, dtype=jnp.int32))
    return q


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class MultiQueue:
    """``num_lanes`` independent ring buffers with a round-robin pop pointer.

    The Atos API exposes ``init(counter, num_queues, iteration)`` so that an
    application can segregate tasks (e.g. per outer iteration, or by task
    kind).  Pops rotate across non-empty lanes; pushes name a lane.
    """

    lanes: TaskQueue          # stacked: buf [L, capacity], cursors [L]
    rr: jax.Array             # scalar int32 round-robin pointer

    @property
    def num_lanes(self) -> int:
        return self.lanes.buf.shape[0]

    @property
    def size(self) -> jax.Array:
        return jnp.sum(self.lanes.tail - self.lanes.head)

    def empty(self) -> jax.Array:
        return self.size == 0

    # -------------------------------------------------------- lane plumbing
    def lane(self, lane_id) -> TaskQueue:
        """View of a single lane as a standalone ``TaskQueue``."""
        return jax.tree.map(lambda x: x[lane_id], self.lanes)

    def with_lane(self, lane_id, lane: TaskQueue) -> "MultiQueue":
        """Write a (possibly updated) lane back into the stack."""
        lanes = jax.tree.map(
            lambda full, new: full.at[lane_id].set(new), self.lanes, lane
        )
        return dataclasses.replace(self, lanes=lanes)

    def reset_lane(self, lane_id) -> "MultiQueue":
        """Recycle a lane for a new tenant: empty buffer, zeroed cursors."""
        cap = self.lanes.buf.shape[1]
        fresh = TaskQueue(
            buf=jnp.full((cap,), EMPTY, dtype=jnp.int32),
            head=jnp.int32(0), tail=jnp.int32(0), dropped=jnp.int32(0),
        )
        return self.with_lane(lane_id, fresh)

    def lane_sizes(self) -> jax.Array:
        return self.lanes.tail - self.lanes.head

    def lane_loads(self, width_of=None) -> jax.Array:
        """Per-lane occupancy in vertices (chunk-width weighted).

        The granularity-aware analogue of :meth:`lane_sizes`: fairness
        quotas and steal plans budget *work*, and with chunked tasks
        (core/task.py) a slot may carry several vertices.  ``width_of=None``
        is exactly :meth:`lane_sizes`.
        """
        if width_of is None:
            return self.lane_sizes()
        cap = self.lanes.buf.shape[1]
        i = jnp.arange(cap, dtype=jnp.int32)[None, :]
        live = ((i - self.lanes.head[:, None]) % cap) < self.lane_sizes()[:, None]
        w = jnp.asarray(width_of(self.lanes.buf), jnp.int32)
        return jnp.sum(jnp.where(live, w, 0), axis=1)

    def lane_dropped(self) -> jax.Array:
        return self.lanes.dropped

    # ----------------------------------------------------------------- api
    def pop(self, n: int) -> Tuple[jax.Array, jax.Array, "MultiQueue"]:
        """Pop up to ``n`` items from the next non-empty lane (round robin).

        The cursor is stored modulo ``num_lanes`` so it cannot overflow
        int32 over long runs (it previously grew without bound).
        """
        sizes = self.lane_sizes()
        order = (self.rr + jnp.arange(self.num_lanes, dtype=jnp.int32)) % self.num_lanes
        nonempty = sizes[order] > 0
        pick = order[jnp.argmax(nonempty)]  # first non-empty in rr order

        items, valid, lane2 = self.lane(pick).pop(n)
        return items, valid, dataclasses.replace(
            self.with_lane(pick, lane2), rr=(pick + 1) % self.num_lanes
        )

    def pop_lane(self, lane_id, n: int, quota=None, width_of=None):
        """Pop up to ``quota``'s worth of items from one named lane.

        ``quota`` counts slots by default, or vertices when ``width_of``
        gives each slot's chunk width (see :meth:`TaskQueue.pop_upto`).
        """
        items, valid, lane2 = self.lane(lane_id).pop_upto(
            n, n if quota is None else quota, width_of=width_of
        )
        return items, valid, self.with_lane(lane_id, lane2)

    def push(self, lane_id, items: jax.Array, mask: jax.Array,
             backend: str = "jnp") -> "MultiQueue":
        return self.with_lane(
            lane_id, self.lane(lane_id).push(items, mask, backend=backend))


def make_multiqueue(capacity: int, num_lanes: int) -> MultiQueue:
    lanes = TaskQueue(
        buf=jnp.full((num_lanes, capacity), EMPTY, dtype=jnp.int32),
        head=jnp.zeros((num_lanes,), jnp.int32),
        tail=jnp.zeros((num_lanes,), jnp.int32),
        dropped=jnp.zeros((num_lanes,), jnp.int32),
    )
    return MultiQueue(lanes=lanes, rr=jnp.int32(0))
