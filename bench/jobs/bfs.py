"""BFS searches through ``repro.runtime.execute``, and their check.

One job is one search, run the way a user runs it: build the BFS program
for the root, ``execute`` it under the configuration's policy, and wait for
the distances.  ``check`` compares the distances of every search with the
plain reference (``bench/reference/bfs.py``), exactly.
"""
from __future__ import annotations

import numpy as np

from bench.reference.bfs import bfs

#: the largest value each checked number may read (exact comparisons)
LIMITS = {"dist_mismatches": 0, "dropped_tasks": 0, "searches_at_round_cap": 0}


class Jobs:
    """Runs searches on one resident graph."""

    def __init__(self, graph, scheduler: dict):
        import jax
        from repro.core import SchedulerConfig
        from repro.runtime import config_for, parse_policy

        self._jax = jax
        self.graph = graph
        self.scheduler = scheduler
        self.cfg = config_for(SchedulerConfig(
            num_workers=scheduler["num_workers"],
            max_rounds=scheduler["max_rounds"],
            backend=scheduler["backend"]), parse_policy(scheduler["policy"]))
        if self.cfg.granularity != scheduler["granularity"]:
            raise ValueError(f"policy {scheduler['policy']} has granularity "
                             f"{self.cfg.granularity}, the configuration "
                             f"{scheduler['granularity']}")

    @property
    def wavefront(self) -> int:
        return self.cfg.wavefront

    def run(self, root: int, span) -> dict:
        """One search from ``root``, to its end on the device; ``span(name)``
        marks each host step in the profiler's trace.  ``pops`` (tasks the
        wavefronts popped) and ``work`` (vertices expanded) are the
        program's own counters."""
        from repro.runtime import build_program, execute

        with span("build_program"):
            program = build_program("bfs", self.graph, self.cfg, {
                "source": root, "work_budget": self.scheduler["work_budget"]})
        with span("execute"):
            res = execute(program, self.graph, self.cfg)
            dist = self._jax.block_until_ready(res.state.dist)
        return {"root": root, "dist": dist, "rounds": res.info["rounds"],
                "pops": int(res.stats.items_processed),
                "work": int(res.info["work"]),
                "dropped": res.info["dropped"]}


def check(jobs: list, row_ptr: np.ndarray, col_idx: np.ndarray,
          max_rounds: int) -> dict:
    """The numbers compared, each to stay at or under ``LIMITS``, over all
    of ``jobs`` (each with its ``dist`` on the host): distances that differ
    from the reference, tasks dropped, and searches cut at the round cap."""
    for job in jobs:
        ref = bfs(row_ptr, col_idx, job["root"])
        job["mismatches"] = int(np.count_nonzero(job["dist"] != ref))
    return {"dist_mismatches": sum(job["mismatches"] for job in jobs),
            "dropped_tasks": sum(job["dropped"] for job in jobs),
            "searches_at_round_cap": sum(job["rounds"] >= max_rounds
                                         for job in jobs)}


def failed(job: dict, max_rounds: int) -> bool:
    return bool(job["mismatches"] or job["dropped"]
                or job["rounds"] >= max_rounds)
