"""Atos core: wavefront task queue, persistent/discrete schedulers, expansion."""
from .backend import (BACKENDS, default_interpret, has_tpu, resolve_backend,
                      resolve_interpret)
from .queue import EMPTY, MultiQueue, TaskQueue, make_multiqueue, make_queue
from .scheduler import RunStats, SchedulerConfig, discrete_run, persistent_run, run
from .frontier import (Expansion, adjacency_of, chunk_degrees, chunk_row_of,
                       expand_merge_path, expand_per_item, gather_neighbors)
from .task import (MAX_GRANULARITY, ChunkCodec, chunk_seeds, coalesce_chunks,
                   flatten_chunks)
from .counters import STATE_COUNTERS, WorkCounter, overwork_ratio

__all__ = [
    "BACKENDS", "default_interpret", "has_tpu", "resolve_backend",
    "resolve_interpret",
    "EMPTY", "MultiQueue", "TaskQueue", "make_multiqueue", "make_queue",
    "RunStats", "SchedulerConfig", "discrete_run", "persistent_run", "run",
    "Expansion", "adjacency_of", "chunk_degrees", "chunk_row_of",
    "expand_merge_path", "expand_per_item", "gather_neighbors",
    "MAX_GRANULARITY", "ChunkCodec", "chunk_seeds", "coalesce_chunks",
    "flatten_chunks",
    "STATE_COUNTERS", "WorkCounter", "overwork_ratio",
]
