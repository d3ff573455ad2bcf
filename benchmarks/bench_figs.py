"""Paper Figs 1-3 (throughput vs time) and Fig 4 (fetch x workers heatmap).

Fig 1-3: per-round rows of the obs ring under the discrete driver —
(queue_size, pops) per wavefront; normalized throughput = items/round
divided by the overwork factor, exactly the paper's normalization.  Emitted as CSV
rows (round, items) per algorithm/dataset; the derived field carries the
normalized mean throughput.

Fig 4: runtime heatmap over (num_workers x fetch_size) for BFS and PageRank
on both dataset classes — the paper's task/data-parallelism trade-off.
"""
from __future__ import annotations

import numpy as np

from repro.algorithms.bfs import bfs_bsp, bfs_speculative
from repro.algorithms.pagerank import pagerank_async
from repro.algorithms.coloring import coloring_async
from repro.core import SchedulerConfig
from repro.graph import grid2d, rmat
from repro.obs import Trace

from .harness import row, timeit

#: ring rows per drain: above every Fig 1-3 drain's round count
RING_ROUNDS = 1 << 16

DATASETS = {
    "scale_free": lambda: rmat(9, 8, seed=1),
    "mesh_like": lambda: grid2d(32, 32),
}


def pops_per_round(trace: Trace) -> list:
    """Tasks popped in each round of a traced drain (whole, untruncated)."""
    assert trace.truncated == 0, "a drain outran the ring"
    return [r["pops"] for r in trace.records]


def run_figs123():
    for dname, make in DATASETS.items():
        g = make()
        cfg = SchedulerConfig(num_workers=16, fetch_size=4, persistent=False,
                              max_rounds=1 << 20)
        # BFS trace
        trace = Trace(capacity=RING_ROUNDS)
        dist, info = bfs_speculative(g, 0, cfg, trace=trace)
        reached = int((np.asarray(dist) < 0x7FFFFFFF).sum())
        overwork = info["work"] / max(reached, 1)
        thr = pops_per_round(trace)
        row(f"fig1/bfs/{dname}", float(np.mean(thr)) * 1000,
            f"rounds={len(thr)};overwork={overwork:.2f};"
            f"norm_thr={np.mean(thr) / overwork:.1f}")
        # PageRank trace
        trace = Trace(capacity=RING_ROUNDS)
        _, info = pagerank_async(g, cfg, eps=1e-6, trace=trace)
        thr = pops_per_round(trace)
        row(f"fig2/pagerank/{dname}", float(np.mean(thr)) * 1000,
            f"rounds={len(thr)};norm_thr={np.mean(thr):.1f}")
        # Coloring trace
        trace = Trace(capacity=RING_ROUNDS)
        _, info = coloring_async(g, cfg, trace=trace)
        overwork = info["work"] / g.num_vertices
        thr = pops_per_round(trace)
        row(f"fig3/coloring/{dname}", float(np.mean(thr)) * 1000,
            f"rounds={len(thr)};overwork={overwork:.2f};"
            f"norm_thr={np.mean(thr) / overwork:.1f}")


def run_fig4():
    for dname, make in DATASETS.items():
        g = make()
        for workers in [4, 16, 64]:
            for fetch in [1, 4, 16]:
                cfg = SchedulerConfig(num_workers=workers, fetch_size=fetch,
                                      persistent=True, max_rounds=1 << 20)
                t = timeit(lambda: bfs_speculative(g, 0, cfg)[0], iters=3)
                row(f"fig4/bfs/{dname}/w{workers}xf{fetch}", t * 1e6,
                    f"wavefront={workers * fetch}")


def run():
    run_figs123()
    run_fig4()
