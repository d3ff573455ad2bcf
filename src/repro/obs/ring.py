"""The device-side trace ring buffer (DESIGN.md §15).

A :class:`TraceRing` is a fixed-size, preallocated ``(capacity,
NUM_FIELDS)`` int32 buffer plus a monotone cursor, registered as a pytree
so it threads through every jitted drain loop exactly like the queue does
— the single/fused ``lax.while_loop``, the sharded ``shard_map`` round,
the server's per-lane step, the stream driver's snapshot segments, and
the megakernel's in-kernel loop (whose ``make_fused_drain`` flattens an
arbitrary carry pytree, so a ring leaf rides into the fused kernel for
free).

:meth:`TraceRing.record` writes one row at ``cursor % capacity`` and
bumps the cursor — pure array ops on traced values, so tracing costs
**zero host syncs**: the buffer lives on device for the whole drain and
is drained to host exactly once, at run end (:func:`ring_rows`).  When a
drain outruns the capacity the ring wraps — the newest ``capacity``
records survive and :func:`ring_rows` reports how many older ones were
overwritten, the classic flight-recorder contract.
"""
from __future__ import annotations

import dataclasses
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .schema import NUM_FIELDS, TRACE_FIELDS
from .spans import RING

_FIELD_INDEX = {name: i for i, name in enumerate(TRACE_FIELDS)}

#: default ring capacity (rounds) when the caller does not size it
DEFAULT_CAPACITY = 4096


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class TraceRing:
    """Fixed-size per-round trace buffer, carried through jitted drains."""

    buf: jax.Array     # (capacity, NUM_FIELDS) int32
    cursor: jax.Array  # int32: total records ever written (monotone)

    @property
    def capacity(self) -> int:
        return int(self.buf.shape[0])

    @staticmethod
    def make(capacity: int = DEFAULT_CAPACITY) -> "TraceRing":
        if capacity < 1:
            raise ValueError(f"ring capacity must be >= 1, got {capacity}")
        return TraceRing(buf=jnp.zeros((capacity, NUM_FIELDS), jnp.int32),
                         cursor=jnp.int32(0))

    def record(self, **fields) -> "TraceRing":
        """Write one row (unnamed columns are 0) and advance the cursor.

        ``fields`` values may be traced scalars; the write is a single
        dynamic row update — no host sync, no shape change, safe inside
        ``while_loop`` / ``shard_map`` / the megakernel body.
        """
        unknown = set(fields) - set(TRACE_FIELDS)
        if unknown:
            raise ValueError(
                f"unknown trace fields {sorted(unknown)}; the row layout is "
                f"{TRACE_FIELDS} (obs/schema.TRACE_FIELDS)")
        with jax.named_scope(RING):
            row = jnp.zeros((NUM_FIELDS,), jnp.int32)
            for name, value in fields.items():
                row = row.at[_FIELD_INDEX[name]].set(
                    jnp.asarray(value, jnp.int32))
            idx = jnp.mod(self.cursor, self.buf.shape[0])
            return TraceRing(buf=self.buf.at[idx].set(row),
                             cursor=self.cursor + 1)


def ring_rows(ring: TraceRing) -> Tuple[List[dict], int]:
    """Drain a ring to host: ``(records, truncated)``.

    Records come back oldest-first as ``{field: int}`` dicts over
    :data:`~repro.obs.schema.TRACE_FIELDS`; ``truncated`` is how many of
    the oldest rounds the wraparound overwrote (0 unless the drain ran
    longer than the capacity).  This is the run's ONE device->host sync
    for tracing.
    """
    cursor = int(ring.cursor)
    cap = ring.capacity
    buf = np.asarray(ring.buf)
    if cursor <= cap:
        data = buf[:cursor]
        truncated = 0
    else:
        k = cursor % cap
        data = np.concatenate([buf[k:], buf[:k]])
        truncated = cursor - cap
    records = [
        {name: int(row[i]) for i, name in enumerate(TRACE_FIELDS)}
        for row in data
    ]
    return records, truncated


def stacked_rings(ring: TraceRing, count: int) -> TraceRing:
    """``count`` device-replica rings as one stacked pytree (leading axis
    per device) — the sharded driver's ``shard_map`` operand shape."""
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x, (count,) + x.shape), ring)


def unstack_ring(ring_st: TraceRing, device: int) -> TraceRing:
    """One device's ring out of a stacked pytree (host side, post-drain:
    the slice is taken on the host, so a mesh of fewer devices than the
    host has needs no gather sharding)."""
    return jax.tree.map(lambda x: np.asarray(x)[device], ring_st)
