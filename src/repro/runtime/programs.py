"""Program registry: compile (algorithm name, graph, config) -> AtosProgram.

The single source of the per-algorithm parameter parsing that used to be
copied between ``shard/programs.build_program`` and
``server/jobs._kernel_bundle``.  Each algorithm module owns exactly one
program definition (``make_program``); adding a workload is now a
single-file drop plus one registry line.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

from ..core.scheduler import SchedulerConfig
from ..graph.csr import CSRGraph
from ..obs import span
from .program import AtosProgram


def _factories():
    # lazy: the algorithm modules import repro.runtime.program at top level
    from ..algorithms import bfs, coloring, pagerank

    return {
        "bfs": bfs.make_program,
        "pagerank": pagerank.make_program,
        "coloring": coloring.make_program,
    }


def algorithms() -> tuple:
    """Registered algorithm names (stable order)."""
    return tuple(sorted(_factories()))


def build_program(algorithm: str, graph: CSRGraph, cfg: SchedulerConfig,
                  params: Optional[Dict[str, Any]] = None,
                  queue_capacity: Optional[int] = None) -> AtosProgram:
    """Compile one drain.  ``params`` mirrors the single-tenant drivers'
    keyword arguments (BFS ``source``/``strategy``, PageRank ``damping``/
    ``eps``/``check_size``, ...); unknown keys raise ``ValueError`` at build
    time, not mid-drain.  All static budgets derive from the *global* graph
    so a sharded run traces the identical body on every device.  Runs
    under the ``atos:build_program`` profiler span.
    """
    factories = _factories()
    if algorithm not in factories:
        raise ValueError(f"unknown algorithm {algorithm!r}; "
                         f"expected one of {algorithms()}")
    with span("build_program"):
        return factories[algorithm](graph, cfg,
                                    queue_capacity=queue_capacity,
                                    **dict(params or {}))


def reject_unknown_params(algorithm: str, params: Dict[str, Any]) -> None:
    """Shared tail-check for the factories' explicit ``pop`` parsing."""
    if params:
        raise ValueError(
            f"unknown {algorithm} params: {sorted(params)}")
