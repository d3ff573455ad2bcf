"""Sharded multi-device task scheduler (DESIGN.md sections 10 and 16).

One Atos drain across every device of a mesh — the 1-D ``("shard",)`` ring,
or a 2-D ``("row", "col")`` mesh (``SchedulerConfig.mesh_shape``) whose
routed exchange decomposes into two per-axis all_to_alls: a vertex-block
partitioner reshards the CSR adjacency, each device runs a queue replica
plus the existing wavefront body on its local slice, produced tasks are
routed to their owner every round (optionally staged one round to overlap
the collective with compute, ``defer_rounds``; optionally delta-compressed
on the wire, ``compress`` + shard/codec.py), occupancy skew triggers ring
work stealing, and a psum'd stop predicate keeps the mesh in lockstep until
the global drain ends.  Fully testable on CPU via
``XLA_FLAGS=--xla_force_host_platform_device_count=8``.

Since the runtime layer (DESIGN.md section 11) the driver consumes the
unified :class:`~repro.runtime.program.AtosProgram`; program construction
lives in :mod:`repro.runtime` (``build_program``), and the one-PR
deprecation shim that used to forward it from here (``shard/programs.py``)
is gone.
"""
from .codec import codec_capacity, decode_buffer, encode_buffer
from .driver import (ShardCounters, ShardRunStats, discrete_run_sharded,
                     persistent_run_sharded, run_sharded)
from .exchange import (LANE_LOCAL, LANE_STOLEN, NUM_LANES, delivered_width,
                       pop_wavefront, route_tasks)
from .partition import (ShardedCSR, block_bounds, block_size, owner_coords,
                        owner_of, partition_graph, place_partition,
                        split_seeds)
from .steal import plan_donations, rebalance

__all__ = [
    "ShardCounters", "ShardRunStats", "discrete_run_sharded",
    "persistent_run_sharded", "run_sharded",
    "LANE_LOCAL", "LANE_STOLEN", "NUM_LANES", "delivered_width",
    "pop_wavefront", "route_tasks",
    "ShardedCSR", "block_bounds", "block_size", "owner_coords", "owner_of",
    "partition_graph", "place_partition", "split_seeds",
    "plan_donations", "rebalance",
    "codec_capacity", "decode_buffer", "encode_buffer",
]

_MOVED = {
    "ShardProgram": "repro.runtime.program.AtosProgram",
    "build_program": "repro.runtime.build_program",
    "delta_psum": "repro.runtime.program.delta_psum",
}


def __getattr__(name):
    if name in _MOVED:
        raise ImportError(
            f"repro.shard.{name} was a one-PR deprecation shim and has been "
            f"removed; import {_MOVED[name]} instead (the unified runtime "
            f"layer, DESIGN.md section 11)")
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
