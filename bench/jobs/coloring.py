"""Speculative colorings through ``repro.runtime.execute``, and their check.

One job colors the whole graph from scratch, the way a user runs it:
build the coloring program (every vertex seeded as an assign task),
``execute`` it under the configuration's policy and work budget, and wait
for the colors.  ``check`` holds every coloring to what a greedy coloring
guarantees (``bench/reference/coloring.py``): proper, complete and
first-fit; and holds the speculation to its recolors.  Detect re-queues only
the vertex it checks, and only where it loses, so an assign that misses
some neighbours' colors can still end proper: its vertices are recolored
again and again, which ``recolor_share`` reads.
"""
from __future__ import annotations

import numpy as np

from bench.jobs import bfs
from bench.reference.coloring import (colors_above_degree, conflict_edges,
                                      uncolored)

#: the largest value each checked number may read: exact counts, and the
#: largest share of a coloring's vertices assigned again, in % (PERF.md
#: section 4 gives the readings of sound and faulty runs it lies between)
LIMITS = {"conflict_edges": 0, "uncolored": 0, "colors_above_degree": 0,
          "dropped_tasks": 0, "jobs_at_round_cap": 0, "recolor_share": 5.0}


class Jobs(bfs.Jobs):
    """Colors one resident graph, under the BFS job's policy settings."""

    def run(self, root: int, span) -> dict:
        """One coloring of the whole graph, to its end on the device.

        The harness reads the colors, returned as ``dist``, only for reach
        (``!= INF``: every vertex counts) and takes ``root`` only as a
        label: a coloring has no root.  ``pops`` (tasks popped), ``work``
        (assigns, so ``work - n`` are recolors) and ``wide_picks`` (rounds
        that needed the full-palette forbidden table; 0 from a program that
        keeps no such counter) are the program's own counters."""
        from repro.runtime import build_program, execute

        with span("build_program"):
            program = build_program("coloring", self.graph, self.cfg, {
                "work_budget": self.scheduler["work_budget"]})
        with span("execute"):
            res = execute(program, self.graph, self.cfg)
            colors = self._jax.block_until_ready(res.state.colors)
        return {"root": root, "dist": colors, "rounds": res.info["rounds"],
                "pops": int(res.stats.items_processed),
                "work": int(res.info["work"]),
                "dropped": res.info["dropped"],
                "wide_picks": res.info.get("wide_picks", 0)}


def check(jobs: list, row_ptr: np.ndarray, col_idx: np.ndarray,
          max_rounds: int) -> dict:
    """The numbers compared, each to stay at or under ``LIMITS``, over all
    of ``jobs`` (each with its colors as ``dist`` on the host): conflicting
    CSR entries, uncolored vertices, colors past their vertex's degree,
    tasks dropped, jobs cut at the round cap, and the largest
    ``recolor_share`` (assigns past the first of each vertex, over the
    vertices, in %).  With no limit: ``colors_max`` (the largest palette),
    ``recolors`` and ``wide_picks``, summed over the jobs."""
    n = row_ptr.shape[0] - 1
    for job in jobs:
        colors = job["dist"]
        job["conflicts"] = conflict_edges(row_ptr, col_idx, colors)
        job["uncolored"] = uncolored(colors)
        job["above_degree"] = colors_above_degree(row_ptr, colors)
        job["mismatches"] = (job["conflicts"] + job["uncolored"]
                             + job["above_degree"])
        job["recolor_share"] = 100.0 * (job["work"] - n) / n
    return {"conflict_edges": sum(job["conflicts"] for job in jobs),
            "uncolored": sum(job["uncolored"] for job in jobs),
            "colors_above_degree": sum(job["above_degree"] for job in jobs),
            "dropped_tasks": sum(job["dropped"] for job in jobs),
            "jobs_at_round_cap": sum(job["rounds"] >= max_rounds
                                     for job in jobs),
            "recolor_share": max((job["recolor_share"] for job in jobs),
                                 default=0.0),
            "colors_max": max((int(job["dist"].max()) + 1 for job in jobs),
                              default=0),
            "recolors": sum(job["work"] - n for job in jobs),
            "wide_picks": sum(job["wide_picks"] for job in jobs)}


def failed(job: dict, max_rounds: int) -> bool:
    return (bfs.failed(job, max_rounds)
            or job["recolor_share"] > LIMITS["recolor_share"])
