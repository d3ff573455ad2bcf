"""Vertex-block graph partitioner for the sharded task scheduler.

Ownership is by contiguous vertex block: shard ``d`` of ``S`` owns vertices
``[d*B, min(n, (d+1)*B))`` with ``B = ceil(n / S)`` — the static function
``owner_of`` is evaluated inside traced code to route every produced task to
the device that owns its vertex (DESIGN.md section 10).

The CSR adjacency — the O(m) payload — is *resharded*: each device holds
only the edges of its own block (plus, when stealing is enabled, a **steal
halo**: a replica of its ring predecessor's block, so donated tasks are
expandable by the thief at the cost of 2x edge storage).  The O(n) per-shard
``row_ptr`` keeps the *global* vertex index space so the existing wavefront
bodies run unchanged on a device-local :class:`~repro.graph.csr.CSRGraph`;
entries for rows a device neither owns nor halos are never read (every
popped task is owned or freshly stolen — an invariant the driver meters and
the tests assert).

Everything here is host-side numpy, run once per (graph, shard count); the
stacked ``[S, ...]`` arrays are what ``shard_map`` splits across the mesh.
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..graph.csr import CSRGraph


def block_size(n: int, num_shards: int) -> int:
    """Vertices per shard (ceil split; trailing shards may be short/empty)."""
    return -(-n // num_shards)


def owner_of(vids, n: int, num_shards: int):
    """Owning shard of each vertex id (traced-friendly; callers mask
    invalid lanes to a safe id before calling)."""
    b = block_size(n, num_shards)
    return jnp.clip(jnp.asarray(vids, jnp.int32) // b, 0, num_shards - 1)


def block_bounds(shard: int, n: int, num_shards: int) -> Tuple[int, int]:
    """[start, end) vertex range owned by ``shard`` (host-side ints)."""
    b = block_size(n, num_shards)
    return min(n, shard * b), min(n, (shard + 1) * b)


def owner_coords(vids, n: int, rows: int, cols: int):
    """2-D mesh coordinates ``(row, col)`` of each vertex's owner.

    Ownership on the 2-D mesh is the *same* linear vertex-block split as
    the 1-D ring (``owner_of`` with ``num_shards = rows * cols``) mapped
    row-major onto the mesh: linear shard ``d`` sits at ``(d // cols,
    d % cols)`` — exactly the order jax linearizes ``("row", "col")``
    tuple-axis collectives in, so partitioning, steal halos (linear ring
    predecessor), and the replica merge are untouched by the mesh shape.
    The per-axis exchange (shard/exchange.py) routes dimension-ordered:
    first to the owner's column (a ``cols``-wide all_to_all inside the
    row), then to its row (a ``rows``-wide all_to_all inside the column).
    """
    d = owner_of(vids, n, rows * cols)
    return d // cols, d % cols


@dataclasses.dataclass(frozen=True)
class ShardedCSR:
    """Per-device CSR slices, stacked for shard_map.

    ``row_ptr[d]`` is a full ``[n+1]`` int32 vector whose entries are local
    edge offsets for shard ``d``'s own (and halo) rows and zeros elsewhere;
    ``col_idx[d]`` holds shard ``d``'s edges padded to the widest shard.
    ``local(d)`` reassembles the device view as a plain CSRGraph — the same
    container the wavefront bodies already consume.
    """

    row_ptr: jax.Array        # [S, n+1] int32 (global vertex index space)
    col_idx: jax.Array        # [S, E_pad] int32 (global neighbor ids)
    num_shards: int
    num_vertices: int
    halo: bool                # ring-predecessor block replicated (stealing)
    edges_per_shard: Tuple[int, ...]   # owned edges only (diagnostic)

    def local(self, shard) -> CSRGraph:
        """Device-local graph view (works on traced ``shard`` too)."""
        return CSRGraph(row_ptr=self.row_ptr[shard],
                        col_idx=self.col_idx[shard])


def place_partition(parts: ShardedCSR, mesh) -> ShardedCSR:
    """Put shard ``d``'s CSR slice on mesh device ``d`` — the split
    ``shard_map`` makes — instead of leaving the whole stack on the device
    that built it.  A no-op for a partition already placed on ``mesh``."""
    sharding = NamedSharding(mesh, P(tuple(mesh.axis_names)))
    return dataclasses.replace(
        parts, row_ptr=jax.device_put(parts.row_ptr, sharding),
        col_idx=jax.device_put(parts.col_idx, sharding))


def partition_graph(graph: CSRGraph, num_shards: int,
                    halo: bool = True) -> ShardedCSR:
    """Reshard a CSR graph by vertex block.

    With ``halo=True`` shard ``d`` also carries a replica of shard
    ``(d-1) % S``'s rows, which is what makes ring work stealing legal: the
    only foreign tasks a device ever pops are donations from its ring
    predecessor (see shard/steal.py).
    """
    if num_shards < 1:
        raise ValueError(f"num_shards must be >= 1, got {num_shards}")
    n = graph.num_vertices
    rp = np.asarray(graph.row_ptr, dtype=np.int64)
    col = np.asarray(graph.col_idx, dtype=np.int32)
    use_halo = halo and num_shards > 1

    locals_rp, locals_col, owned_edges = [], [], []
    for d in range(num_shards):
        own_lo, own_hi = block_bounds(d, n, num_shards)
        e_lo, e_hi = int(rp[own_lo]), int(rp[own_hi])
        owned_edges.append(e_hi - e_lo)
        lrp = np.zeros(n + 1, dtype=np.int32)
        if use_halo and d > 0:
            # predecessor block immediately precedes the own block in vertex
            # (and therefore edge) space: one contiguous global slice.
            pre_lo, _ = block_bounds(d - 1, n, num_shards)
            ep_lo = int(rp[pre_lo])
            lcol = col[ep_lo:e_hi]
            lrp[pre_lo:own_hi + 1] = rp[pre_lo:own_hi + 1] - ep_lo
        elif use_halo:
            # shard 0's predecessor is the last block: wraps around, so the
            # local layout is [own edges | halo edges].
            pre_lo, pre_hi = block_bounds(num_shards - 1, n, num_shards)
            ep_lo, ep_hi = int(rp[pre_lo]), int(rp[pre_hi])
            lcol = np.concatenate([col[e_lo:e_hi], col[ep_lo:ep_hi]])
            lrp[own_lo:own_hi + 1] = rp[own_lo:own_hi + 1] - e_lo
            lrp[pre_lo:pre_hi + 1] = (e_hi - e_lo) + (rp[pre_lo:pre_hi + 1]
                                                      - ep_lo)
        else:
            lcol = col[e_lo:e_hi]
            lrp[own_lo:own_hi + 1] = rp[own_lo:own_hi + 1] - e_lo
        locals_rp.append(lrp)
        locals_col.append(lcol)

    e_pad = max(1, max(len(c) for c in locals_col))
    col_stack = np.zeros((num_shards, e_pad), dtype=np.int32)
    for d, c in enumerate(locals_col):
        col_stack[d, :len(c)] = c
    return ShardedCSR(
        row_ptr=jnp.asarray(np.stack(locals_rp)),
        col_idx=jnp.asarray(col_stack),
        num_shards=num_shards,
        num_vertices=n,
        halo=use_halo,
        edges_per_shard=tuple(owned_edges),
    )


def split_seeds(seeds, n: int, num_shards: int, task_vertex=None):
    """Host-side owner split of the initial tasks: ``[S, max_per_shard]``
    items plus a per-shard count — what seeds each device's queue replica.

    ``task_vertex`` maps a task int to its vertex id (identity by default;
    coloring passes ``|t| - 1``).
    """
    seeds = np.asarray(seeds, dtype=np.int32)
    verts = seeds if task_vertex is None else np.asarray(
        task_vertex(seeds), dtype=np.int32)
    owners = np.clip(verts // block_size(n, num_shards), 0, num_shards - 1)
    per = [seeds[owners == d] for d in range(num_shards)]
    width = max(1, max(len(p) for p in per))
    out = np.zeros((num_shards, width), dtype=np.int32)
    counts = np.zeros((num_shards,), dtype=np.int32)
    for d, p in enumerate(per):
        out[d, :len(p)] = p
        counts[d] = len(p)
    return jnp.asarray(out), jnp.asarray(counts)
