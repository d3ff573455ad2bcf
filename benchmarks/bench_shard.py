"""Sharded-scheduler benchmark: rounds, exchange volume, occupancy balance.

  PYTHONPATH=src python -m benchmarks.run shard

Drains BFS (the exchange-heavy workload: improved neighbors are routed to
their owner every round) over the paper's two graph regimes at several
shard counts, via the discrete sharded driver so per-round telemetry is
observable.  Emits ``BENCH_shard.json`` with, per (graph, shard count):

  * rounds to drain (vs. the 1-shard run of the same machinery);
  * total + per-round task exchange volume (the all-to-all wire traffic,
    in tasks; the replica merge adds a fixed O(n)-per-round term recorded
    as ``merge_ints_per_round``);
  * per-device processed items and the min/max occupancy balance;
  * steal telemetry (donated tasks, triggered rounds) for the skewed
    single-source drain with stealing on vs. off;
  * the 2-D mesh sweep (DESIGN.md section 16, R-MAT): both 8-device
    layouts (2x4, 4x2) x strict/one-round-deferred delivery x raw/
    compressed wire — per-axis exchange volume, payload vs padding ints,
    metered wire ints (compressed strictly below the payload), and the
    overlap pipeline's occupancy;
  * autotune agreement: the cost-model-seeded successive-halving search
    reproduces the exhaustive grid's pick under the deterministic
    structural runner while measuring <= 1/4 of the cells.

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

OUT = "BENCH_shard.json"
SHARD_COUNTS = (1, 2, 4, 8)
SCALE = 8          # R-MAT: 2**8 vertices
GRID_SIDE = 16     # mesh: 16x16
#: 2-D mesh layouts (DESIGN.md section 16): both factorizations of the
#: 8-device pool, measured strict vs one-round-deferred, raw vs compressed
MESH_SHAPES = ((2, 4), (4, 2))
# launch shapes shared with benchmarks/smoke.py — the regression guard must
# recompute with exactly the configs that produced the checked-in JSON
SHARD_WORKERS = 32       # scaling sweep: per-device wavefront width
STEAL_WORKERS = 8        # steal case study: narrow wavefront, 8 shards
STEAL_THRESHOLD = 0.5
STEAL_CHUNK = 16


def _child() -> None:
    import time

    import numpy as np

    from repro.algorithms.bfs import bfs_bsp
    from repro.core import SchedulerConfig
    from repro.graph.generators import grid2d, rmat
    from repro import shard as SH
    from repro.runtime import build_program

    graphs = {
        "rmat": rmat(SCALE, edge_factor=8, seed=1),
        "grid": grid2d(GRID_SIDE, GRID_SIDE, seed=0),
    }
    payload: dict = {"shard_counts": list(SHARD_COUNTS), "graphs": {}}
    for name, g in graphs.items():
        ref = np.asarray(bfs_bsp(g, 0)[0])
        entry: dict = {"n": g.num_vertices, "m": g.num_edges, "shards": {}}
        for s in SHARD_COUNTS:
            cfg = SchedulerConfig(num_workers=SHARD_WORKERS, fetch_size=1,
                                  num_shards=s, persistent=False)
            program = build_program("bfs", g, cfg, params={"source": 0})
            trace: list = []
            t0 = time.perf_counter()
            state, stats = SH.run_sharded(program, g, cfg, trace=trace)
            wall = time.perf_counter() - t0
            assert (np.asarray(state.dist) == ref).all(), (name, s)
            assert stats.mis_routed == 0 and stats.dropped == 0
            entry["shards"][str(s)] = {
                "rounds": stats.rounds,
                "wall_seconds": wall,
                "exchanged_total": stats.exchanged,
                "per_round_exchanged": [t["exchanged"] for t in trace],
                "per_device_items": stats.per_device_items.tolist(),
                "occupancy_balance": stats.occupancy_balance,
                # every round merges the int32 dist replica via pmin
                "merge_ints_per_round": g.num_vertices,
            }
        # stealing case study: single-source drain seeds only shard 0 —
        # the most skewed start the partitioner can produce
        steal_cfgs = {
            "steal_off": SchedulerConfig(num_workers=STEAL_WORKERS,
                                         num_shards=8, persistent=False),
            "steal_on": SchedulerConfig(num_workers=STEAL_WORKERS,
                                        num_shards=8, persistent=False,
                                        steal_threshold=STEAL_THRESHOLD,
                                        steal_chunk=STEAL_CHUNK),
        }
        entry["steal"] = {}
        for label, cfg in steal_cfgs.items():
            program = build_program("bfs", g, cfg, params={"source": 0})
            state, stats = SH.run_sharded(program, g, cfg)
            assert (np.asarray(state.dist) == ref).all(), (name, label)
            entry["steal"][label] = {
                "rounds": stats.rounds,
                "donated": stats.donated,
                "steal_rounds": stats.steal_rounds,
                "stolen_executed": stats.stolen_executed,
                "occupancy_balance": stats.occupancy_balance,
            }
        # 2-D mesh sweep (section 16): both layouts x delivery mode x wire
        # codec, on the exchange-heavy R-MAT regime.  The per-axis and wire
        # meters are schedule-deterministic; walls are measurements.
        if name == "rmat":
            entry["mesh"] = {}
            for mesh in MESH_SHAPES:
                label = "%dx%d" % mesh
                entry["mesh"][label] = {}
                for dlabel, defer in (("strict", 0), ("defer", 1)):
                    cell = {}
                    for clabel, comp in (("raw", False),
                                         ("compressed", True)):
                        cfg = SchedulerConfig(num_workers=SHARD_WORKERS,
                                              num_shards=8, mesh_shape=mesh,
                                              defer_rounds=defer,
                                              compress=comp)
                        program = build_program("bfs", g, cfg,
                                                params={"source": 0})
                        t0 = time.perf_counter()
                        state, stats = SH.run_sharded(program, g, cfg)
                        wall = time.perf_counter() - t0
                        assert (np.asarray(state.dist) == ref).all(), \
                            (label, dlabel, clabel)
                        assert stats.mis_routed == 0 and stats.dropped == 0
                        if comp:
                            assert stats.wire_ints < stats.payload_ints, \
                                (label, dlabel, stats.wire_ints,
                                 stats.payload_ints)
                        cell[clabel] = {
                            "rounds": stats.rounds,
                            "wall_seconds": wall,
                            "exchanged_total": stats.exchanged,
                            "exchanged_row": stats.exchanged_row,
                            "exchanged_col": stats.exchanged_col,
                            "payload_ints": stats.payload_ints,
                            "padding_ints": stats.padding_ints,
                            "wire_ints": stats.wire_ints,
                            "deferred": stats.deferred_delivered,
                            "overlap_rounds": stats.overlap_rounds,
                            "overlap_occupancy": stats.overlap_occupancy,
                        }
                    entry["mesh"][label][dlabel] = cell

        # autotune agreement (section 16): the cost-model-seeded successive
        # halving must reproduce the exhaustive grid's pick on this
        # workload under the deterministic structural runner, measuring at
        # most a quarter of the cells.
        import tempfile
        from pathlib import Path

        from repro.server import Autotuner, structural_cost_runner

        with tempfile.TemporaryDirectory() as td:
            Autotuner(cache_path=Path(td) / "grid.json", warmup=0, iters=1,
                      runner=structural_cost_runner,
                      search="grid").tune("bfs", g)
            Autotuner(cache_path=Path(td) / "sh.json", warmup=0, iters=1,
                      runner=structural_cost_runner,
                      search="sh").tune("bfs", g)
            ge = next(iter(json.loads(
                (Path(td) / "grid.json").read_text()).values()))
            se = next(iter(json.loads(
                (Path(td) / "sh.json").read_text()).values()))
        entry["autotune"] = {
            "grid_chosen": ge["chosen"],
            "sh_chosen": se["chosen"],
            "agree": ge["chosen"] == se["chosen"],
            "cells_total": se["cells_total"],
            "cells_measured": se["cells_measured"],
        }
        assert entry["autotune"]["agree"], (name, ge["chosen"], se["chosen"])
        assert se["cells_measured"] <= se["cells_total"] // 4

        payload["graphs"][name] = entry
    print(json.dumps(payload))


def run(out: str = OUT):
    env = dict(
        os.environ,
        XLA_FLAGS="--xla_force_host_platform_device_count=8",
        JAX_PLATFORMS="cpu",
    )
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.bench_shard", "--child"],
        capture_output=True, text=True, env=env, timeout=1800)
    if proc.returncode != 0:
        raise RuntimeError(f"bench_shard child failed:\n{proc.stderr[-3000:]}")
    payload = json.loads(proc.stdout.strip().splitlines()[-1])

    for name, entry in payload["graphs"].items():
        base = entry["shards"]["1"]["rounds"]
        for s, m in sorted(entry["shards"].items(), key=lambda kv: int(kv[0])):
            row(f"shard/{name}/s{s}", m["wall_seconds"] * 1e6,
                f"rounds={m['rounds']} (1-shard={base}) "
                f"exchanged={m['exchanged_total']} "
                f"balance={m['occupancy_balance']:.3f}")
        on, off = entry["steal"]["steal_on"], entry["steal"]["steal_off"]
        row(f"shard/{name}/steal", 0.0,
            f"donated={on['donated']} steal_rounds={on['steal_rounds']} "
            f"balance {off['occupancy_balance']:.3f}->"
            f"{on['occupancy_balance']:.3f}")
        for label, modes in entry.get("mesh", {}).items():
            for dlabel, cell in modes.items():
                raw, comp = cell["raw"], cell["compressed"]
                row(f"shard/{name}/mesh{label}/{dlabel}",
                    comp["wall_seconds"] * 1e6,
                    f"rounds={comp['rounds']} "
                    f"row={comp['exchanged_row']} "
                    f"col={comp['exchanged_col']} "
                    f"wire {raw['wire_ints']}->{comp['wire_ints']} "
                    f"(payload={comp['payload_ints']}) "
                    f"overlap={comp['overlap_occupancy']:.2f}")
        if "autotune" in entry:
            at = entry["autotune"]
            row(f"shard/{name}/autotune", 0.0,
                f"agree={at['agree']} cells "
                f"{at['cells_measured']}/{at['cells_total']} "
                f"chosen={at['sh_chosen']}")
    emit_json(out, payload)
    return payload


if __name__ == "__main__":
    if "--child" in sys.argv:
        _child()
    else:
        run()
