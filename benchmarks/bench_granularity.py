"""Task-granularity benchmark: the paper's third scheduling dial, measured.

  PYTHONPATH=src python -m benchmarks.run granularity

Sweeps chunk width g ∈ {1, 2, 4, 8} x execution policy over the paper's
two graph regimes (R-MAT scale-free vs 2-D mesh) and emits
``BENCH_granularity.json`` with, per (graph, g):

  * ``pagerank_ample``  — async PageRank, default (ample) merge-path work
    budget, ``single.discrete.g<g>``: rounds / work / splits.  The mesh
    regime's headline: the dense seed frontier and the rotating rescan ride
    in chunks, so coarse tasks cut rounds ~2x while degree uniformity keeps
    the overwork cost mild — *coarse tasks win on mesh-like graphs*.
  * ``pagerank_tight``  — same drain with the work budget pinned to the
    max-degree floor (the LBS capacity a hub already saturates): on the
    scale-free graph coarse chunks fight the budget — formation splits
    engage (the ``splits`` meter) and whole-chunk truncation re-queues
    inflate rounds, so *fine tasks + LBS win on power-law graphs*.  The
    g=1 row beats every coarser row in both rounds and work.
  * ``bfs_shard``       — sharded BFS over 8 devices,
    ``sharded.discrete.g<g>``: rounds / per-g exchange volume (chunked
    tasks ship fewer wire ints for the same routed vertices) / splits,
    with bit-identical distances asserted at every width.

All recorded counters are schedule-deterministic (pure functions of graph,
seeds, launch shape, width) — ``benchmarks/smoke.py`` recomputes them in CI
and fails on drift, exactly like the BENCH_shard.json guard.  Wall times
are recorded for context but excluded from the guard.  The crossover is
explained in DESIGN.md section 12.

The measurement is a CPU counter: it runs in a subprocess pinned to
``JAX_PLATFORMS=cpu`` that forces 8 XLA host devices before jax
initializes.  Its parent has already imported JAX (and holds the chip
where there is one), so the child never asks for an accelerator.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

from .harness import emit_json, row

OUT = "BENCH_granularity.json"
GRANULARITIES = (1, 2, 4, 8)
SCALE = 8          # R-MAT: 2**8 vertices
GRID_SIDE = 16     # mesh: 16x16
# launch shapes shared with benchmarks/smoke.py — the regression guard must
# recompute with exactly the configs that produced the checked-in JSON
PR_WORKERS = 16        # single-device PageRank wavefront (slots)
PR_EPS = 1e-4
TIGHT_BUDGET = 128     # ~the max-degree floor of the scale-free graph
SHARD_WORKERS = 32     # per-device BFS wavefront over the 8-shard mesh


def _child() -> None:
    import time

    import numpy as np

    from repro.algorithms.bfs import bfs_bsp
    from repro.algorithms.pagerank import pagerank_async
    from repro.core import SchedulerConfig
    from repro.graph.generators import grid2d, rmat
    from repro.runtime import build_program
    from repro.shard import run_sharded

    graphs = {
        "rmat": rmat(SCALE, edge_factor=8, seed=1),
        "grid": grid2d(GRID_SIDE, GRID_SIDE, seed=0),
    }
    payload: dict = {"granularities": list(GRANULARITIES), "graphs": {}}
    for name, g in graphs.items():
        ref = np.asarray(bfs_bsp(g, 0)[0])
        entry: dict = {"n": g.num_vertices, "m": g.num_edges, "g": {}}
        for gr in GRANULARITIES:
            cell: dict = {}
            for label, budget in (("pagerank_ample", None),
                                  ("pagerank_tight", TIGHT_BUDGET)):
                cfg = SchedulerConfig(num_workers=PR_WORKERS, fetch_size=1,
                                      persistent=False, granularity=gr)
                t0 = time.perf_counter()
                _, info = pagerank_async(g, cfg, eps=PR_EPS,
                                         work_budget=budget)
                cell[label] = {
                    "rounds": info["rounds"],
                    "work": info["work"],
                    "splits": info["splits"],
                    "wall_seconds": time.perf_counter() - t0,
                }
            cfg = SchedulerConfig(num_workers=SHARD_WORKERS, fetch_size=1,
                                  num_shards=8, persistent=False,
                                  granularity=gr)
            program = build_program("bfs", g, cfg, params={"source": 0})
            t0 = time.perf_counter()
            state, stats = run_sharded(program, g, cfg)
            wall = time.perf_counter() - t0
            assert (np.asarray(state.dist) == ref).all(), (name, gr)
            assert stats.mis_routed == 0 and stats.dropped == 0, (name, gr)
            cell["bfs_shard"] = {
                "rounds": stats.rounds,
                "exchanged_total": stats.exchanged,
                "splits": program.splits_of(state),
                "wall_seconds": wall,
            }
            entry["g"][str(gr)] = cell
        payload["graphs"][name] = entry

    def best(graph, workload):
        cells = payload["graphs"][graph]["g"]
        return min(cells, key=lambda k: cells[k][workload]["rounds"])

    # the paper's granularity finding, pinned as data: coarse chunks win
    # the mesh regime, width-1 wins the budget-bound scale-free regime
    payload["findings"] = {
        "coarse_wins_mesh": {"graph": "grid", "workload": "pagerank_ample",
                             "best_g": best("grid", "pagerank_ample")},
        "fine_wins_scale_free": {"graph": "rmat",
                                 "workload": "pagerank_tight",
                                 "best_g": best("rmat", "pagerank_tight")},
    }
    print(json.dumps(payload))


def run(out: str = OUT):
    env = dict(
        os.environ,
        XLA_FLAGS="--xla_force_host_platform_device_count=8",
        JAX_PLATFORMS="cpu",
    )
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.bench_granularity", "--child"],
        capture_output=True, text=True, env=env, timeout=1800)
    if proc.returncode != 0:
        raise RuntimeError(
            f"bench_granularity child failed:\n{proc.stderr[-3000:]}")
    payload = json.loads(proc.stdout.strip().splitlines()[-1])

    for name, entry in payload["graphs"].items():
        for gr, cell in sorted(entry["g"].items(), key=lambda kv: int(kv[0])):
            a, t, s = (cell["pagerank_ample"], cell["pagerank_tight"],
                       cell["bfs_shard"])
            row(f"granularity/{name}/g{gr}",
                a["wall_seconds"] * 1e6,
                f"pr_rounds={a['rounds']} pr_tight_rounds={t['rounds']} "
                f"tight_splits={t['splits']} shard_rounds={s['rounds']} "
                f"exchanged={s['exchanged_total']}")
    f = payload["findings"]
    row("granularity/crossover", 0.0,
        f"mesh best_g={f['coarse_wins_mesh']['best_g']} "
        f"scale_free_tight best_g={f['fine_wins_scale_free']['best_g']}")
    emit_json(out, payload)
    return payload


if __name__ == "__main__":
    if "--child" in sys.argv:
        _child()
    else:
        run()
