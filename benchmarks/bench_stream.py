"""Streaming benchmark: incremental recompute vs full recompute, measured.

  PYTHONPATH=src python -m benchmarks.run stream

Runs the three algorithms over the same R-MAT graph + seeded delta log
(``graph/generators.edge_delta_stream``: small mixed insert/delete batches)
twice — once with the per-algorithm dirty-seed rules
(``stream/incremental``), once with the conservative full reseed — and
emits ``BENCH_stream.json`` with, per algorithm and mode, the per-batch
rounds / work-counter / seed and effective-op counts.  The headline
``findings`` block pins the subsystem's reason to exist as data:
**incremental work is strictly below full-recompute work on small-delta
batches** for every algorithm (coloring's conflict-repair rule is the
dramatic case: it re-colors only the losing endpoints of newly conflicted
edges).

Every run commits its deltas through the slotted-CSR path
(``graph/slotted``, ``--compact-every`` = :data:`COMPACT_EVERY` here), so
the per-batch rows also carry the O(delta) commit-cost columns — rows
touched by the commit, overlay occupancy after it, whether it compacted —
and each mode totals its commit wall seconds / touched rows / compactions.
The ``findings`` block asserts the tentpole property as data: **every
commit touches strictly fewer rows than the graph has edges** (the old
path rebuilt all m edges per batch).

Also recorded:

  * ``sharded_bfs`` — the same streamed BFS over the 8-device mesh,
    asserted bit-identical to the single-topology stream (the owner-aware
    delta rebuild preserves the ownership blocks);
  * ``snapshot`` — wall-second overhead of crash-consistent mid-drain
    snapshots (save-enabled run vs plain run, plus one resume), excluded
    from the CI guard like every other wall measurement.

All rounds/work/seed counters are schedule-deterministic, so
``benchmarks/smoke.py`` recomputes them in CI and fails on drift, exactly
like the BENCH_shard.json / BENCH_granularity.json guards.

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

OUT = "BENCH_stream.json"
# shared with benchmarks/smoke.py — the regression guard recomputes with
# exactly the configs that produced the checked-in JSON
SCALE = 9           # R-MAT: 2**9 vertices
EDGE_FACTOR = 8
GRAPH_SEED = 1
STREAM_SEED = 2
BATCHES = 4         # delta batches per stream
BATCH_SIZE = 16     # edge ops per batch (small deltas — the target regime)
WORKERS = 32
PR_EPS = 1e-4
SNAP_EVERY = 2      # rounds between mid-drain snapshots (overhead section)
COMPACT_EVERY = 2   # slotted-CSR re-pack cadence (taskserver --compact-every)
ALGOS = (("bfs", {"source": 0}), ("pagerank", {"eps": PR_EPS}),
         ("coloring", {}))


def _child() -> None:
    import tempfile
    import time

    import numpy as np

    from repro.core import SchedulerConfig
    from repro.graph.generators import edge_delta_stream, rmat
    from repro.runtime import stream_execute

    base = rmat(SCALE, edge_factor=EDGE_FACTOR, seed=GRAPH_SEED)
    deltas = edge_delta_stream(base, BATCHES, BATCH_SIZE, seed=STREAM_SEED)
    cfg = SchedulerConfig(num_workers=WORKERS, topology="single",
                          persistent=False)
    payload: dict = {
        "config": {"scale": SCALE, "edge_factor": EDGE_FACTOR,
                   "batches": BATCHES, "batch_size": BATCH_SIZE,
                   "workers": WORKERS, "eps": PR_EPS},
        "algorithms": {},
    }

    def batch_rows(res):
        return [{"rounds": r.rounds, "work": r.work, "seeds": r.seeds,
                 "eff": r.effective_ops, "touched": r.touched_rows,
                 "overlay": r.overlay, "compacted": r.compacted}
                for r in res.batches]

    m = base.num_edges
    for algo, params in ALGOS:
        entry: dict = {}
        for mode, incr in (("incremental", True), ("full", False)):
            t0 = time.perf_counter()
            res = stream_execute(algo, base, deltas, cfg,
                                 params=dict(params), incremental=incr,
                                 compact_every=COMPACT_EVERY)
            wall = time.perf_counter() - t0
            assert res.info["dropped"] == 0, (algo, mode)
            assert all(r.touched_rows < m for r in res.batches), (algo, mode)
            entry[mode] = {
                "per_batch": batch_rows(res),
                # delta-batch totals only: batch 0 (the cold drain on the
                # base graph) is identical in both modes by construction
                "total_rounds": sum(r.rounds for r in res.batches[1:]),
                "total_work": sum(r.work for r in res.batches[1:]),
                "wall_seconds": wall,
                # O(delta) commit cost (apply + patch wall, rows touched,
                # slotted re-packs) — the tentpole meters
                "commit_seconds": res.info["commit_seconds"],
                "touched_rows": res.info["touched_rows"],
                "compactions": res.info["compactions"],
            }
        iw = entry["incremental"]["total_work"]
        fw = entry["full"]["total_work"]
        assert iw < fw, (algo, iw, fw)
        entry["savings"] = {"work_ratio": iw / fw if fw else 0.0}
        payload["algorithms"][algo] = entry

    # sharded streaming parity: same log over the 8-device mesh
    scfg = SchedulerConfig(num_workers=WORKERS, topology="sharded",
                           num_shards=8, persistent=False)
    t0 = time.perf_counter()
    sres = stream_execute("bfs", base, deltas, scfg, params={"source": 0},
                          compact_every=COMPACT_EVERY)
    swall = time.perf_counter() - t0
    ref = stream_execute("bfs", base, deltas, cfg, params={"source": 0},
                         compact_every=COMPACT_EVERY)
    parity = bool((np.asarray(sres.result) == np.asarray(ref.result)).all())
    assert parity and sres.info["dropped"] == 0
    payload["sharded_bfs"] = {
        "rounds": sres.info["rounds"],
        "work": sres.info["work"],
        "exchanged": sres.info["exchanged"],
        "parity": parity,
        "wall_seconds": swall,
    }

    # snapshot overhead: save-enabled run vs the plain run, plus a resume
    # (the resume replays the log and re-drains from the newest snapshot)
    with tempfile.TemporaryDirectory() as d:
        t0 = time.perf_counter()
        snap_res = stream_execute("bfs", base, deltas, cfg,
                                  params={"source": 0},
                                  snapshot_every=SNAP_EVERY,
                                  checkpoint_dir=d, keep=1000,
                                  compact_every=COMPACT_EVERY)
        snap_wall = time.perf_counter() - t0
        ticks = len([p for p in os.listdir(d) if p.startswith("snap_")])
        t0 = time.perf_counter()
        stream_execute("bfs", base, deltas, cfg, params={"source": 0},
                       snapshot_every=SNAP_EVERY, checkpoint_dir=d,
                       keep=1000, resume=True,
                       compact_every=COMPACT_EVERY)
        resume_wall = time.perf_counter() - t0
        assert (np.asarray(snap_res.result)
                == np.asarray(ref.result)).all()
    plain_wall = payload["algorithms"]["bfs"]["incremental"]["wall_seconds"]
    payload["snapshot"] = {
        "ticks": ticks,
        "snapshot_every": SNAP_EVERY,
        "save_wall_seconds": snap_wall,
        "plain_wall_seconds": plain_wall,
        "resume_wall_seconds": resume_wall,
    }

    payload["findings"] = {
        "incremental_below_full": {
            a: payload["algorithms"][a]["incremental"]["total_work"]
            < payload["algorithms"][a]["full"]["total_work"]
            for a, _ in ALGOS},
        # O(delta) commits: every batch's slab-touched row count stays
        # strictly below m (= full-rebuild cost in rows)
        "commit_touched_below_m": {
            a: all(r["touched"] < m
                   for mode in ("incremental", "full")
                   for r in payload["algorithms"][a][mode]["per_batch"])
            for a, _ in ALGOS},
    }
    print(json.dumps(payload))


def run(out: str = OUT):
    env = dict(
        os.environ,
        XLA_FLAGS="--xla_force_host_platform_device_count=8",
        JAX_PLATFORMS="cpu",
    )
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.bench_stream", "--child"],
        capture_output=True, text=True, env=env, timeout=1800)
    if proc.returncode != 0:
        raise RuntimeError(
            f"bench_stream child failed:\n{proc.stderr[-3000:]}")
    payload = json.loads(proc.stdout.strip().splitlines()[-1])

    for algo, entry in payload["algorithms"].items():
        inc, full = entry["incremental"], entry["full"]
        row(f"stream/{algo}", inc["wall_seconds"] * 1e6,
            f"inc_work={inc['total_work']} full_work={full['total_work']} "
            f"inc_rounds={inc['total_rounds']} "
            f"full_rounds={full['total_rounds']} "
            f"ratio={entry['savings']['work_ratio']:.3f}")
        row(f"stream/{algo}/commit", inc["commit_seconds"] * 1e6,
            f"touched={inc['touched_rows']} "
            f"compactions={inc['compactions']}")
    s = payload["sharded_bfs"]
    row("stream/bfs_shard", s["wall_seconds"] * 1e6,
        f"rounds={s['rounds']} work={s['work']} "
        f"exchanged={s['exchanged']} parity={s['parity']}")
    sn = payload["snapshot"]
    row("stream/snapshot", sn["save_wall_seconds"] * 1e6,
        f"ticks={sn['ticks']} plain={sn['plain_wall_seconds']:.2f}s "
        f"resume={sn['resume_wall_seconds']:.2f}s")
    emit_json(out, payload)
    return payload


if __name__ == "__main__":
    if "--child" in sys.argv:
        _child()
    else:
        run()
