"""Megakernel benchmark: launches-per-drain collapse + roofline posture.

  PYTHONPATH=src python -m benchmarks.run megakernel

Runs the three algorithms over the same R-MAT graph under the three kernel
strategies of the single topology — ``persistent`` (device while-loop,
one kernel entry per round), ``discrete`` (host loop, one dispatch per
round) and ``megakernel`` (the whole drain fused into ONE Pallas launch,
kernels/drain_loop, DESIGN.md section 14) — and emits
``BENCH_megakernel.json`` with, per (algorithm x kernel), the
schedule-deterministic rounds / launches / work counters plus wall
seconds.  The headline ``findings`` block pins the subsystem's reason to
exist as data: **kernel-entry events per drain collapse from O(rounds)
to exactly 1** while every result stays bit-identical to the persistent
drain (the megakernel body IS the persistent while-loop's jaxpr,
evaluated in-kernel).

The ``roofline`` section compiles the persistent drain body once
(``launch/roofline.cost_terms``), composes the per-round HLO bytes/flops
over the measured round count (XLA costs a while-loop body once, the same
convention launch/dryrun.py uses for scans), then ADDS the megakernel's
own streamed-slice traffic — every expansion DMAs ``wavefront x
work_budget`` int32 lanes regardless of actual chunk degrees
(``kernels/drain_loop/csr_stream``, DESIGN.md section 14) — and reports
the memory/compute terms against the TPU v5e roofline next to the
measured megakernel wall — achieved-vs-roofline bandwidth.  Wall-based
numbers are excluded from the CI guard like every other timing; the
rounds / launches / work counters are recomputed by
``benchmarks/smoke.py`` on every push.

The megakernel is an interpret-mode prototype (no Mosaic lowering for the
jaxpr-in-kernel body yet, DESIGN.md section 14), so its wall seconds are
an emulation artifact on every backend — the counters and the parity bit
are the portable signal, and the roofline terms bound what a future
compiled lowering would have to beat.

The measurement is a CPU counter: it runs in a subprocess pinned to
``JAX_PLATFORMS=cpu``.  Its parent has already imported JAX (and holds
the chip where there is one), so the child never asks for an
accelerator.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

from .harness import emit_json, row

OUT = "BENCH_megakernel.json"
# shared with benchmarks/smoke.py — the regression guard recomputes with
# exactly the configs that produced the checked-in JSON
SCALE = 7           # R-MAT: 2**7 vertices
EDGE_FACTOR = 8
GRAPH_SEED = 1
WORKERS = 32
PR_EPS = 1e-4
KERNELS = ("persistent", "discrete", "megakernel")
ALGOS = (("bfs", {"source": 0}), ("pagerank", {"eps": PR_EPS}),
         ("coloring", {}))


def _child() -> None:
    import time

    import jax
    import numpy as np

    from repro.core import SchedulerConfig
    from repro.graph.generators import rmat
    from repro.launch.roofline import (HBM_BW, cost_terms, make_roofline)
    from repro.runtime import (ExecutionPolicy, build_program, config_for,
                               execute)

    g = rmat(SCALE, edge_factor=EDGE_FACTOR, seed=GRAPH_SEED)
    payload: dict = {
        "config": {"scale": SCALE, "edge_factor": EDGE_FACTOR,
                   "workers": WORKERS, "eps": PR_EPS},
        "algorithms": {},
    }

    for algo, params in ALGOS:
        entry: dict = {}
        results = {}
        for kernel in KERNELS:
            cfg = config_for(SchedulerConfig(num_workers=WORKERS),
                             ExecutionPolicy("single", kernel))
            program = build_program(algo, g, cfg, params=dict(params))
            t0 = time.perf_counter()
            state, stats, info = execute(program, g, cfg)
            wall = time.perf_counter() - t0
            assert info["dropped"] == 0, (algo, kernel)
            results[kernel] = np.asarray(program.result(state))
            entry[kernel] = {
                "rounds": info["rounds"],
                "launches": info["launches"],
                "work": info["work"],
                "wall_seconds": wall,
            }
        # the whole point, asserted at measurement time: one launch per
        # drain, bit-identical state
        assert entry["megakernel"]["launches"] == 1, algo
        assert entry["persistent"]["launches"] == \
            entry["persistent"]["rounds"], algo
        assert (results["megakernel"] == results["persistent"]).all(), algo
        entry["parity_vs_persistent"] = True
        payload["algorithms"][algo] = entry

    # roofline: compile the persistent BFS drain, cost its body once, and
    # compose the per-round HLO terms over the measured round count
    from repro.runtime.api import _shared_setup
    from repro.runtime.policy import policy_of
    import jax.numpy as jnp

    cfg = config_for(SchedulerConfig(num_workers=WORKERS),
                     ExecutionPolicy("single", "persistent"))
    program = build_program("bfs", g, cfg, params={"source": 0})
    queue, state, ops, step, cond, _ = _shared_setup(
        program, g, cfg, policy_of(cfg), None)
    carry0 = (queue, state, jnp.int32(0), jnp.int32(0))
    drain = jax.jit(lambda c: jax.lax.while_loop(cond, step, c))
    compiled = drain.lower(carry0).compile()
    per_round = cost_terms(compiled)
    rounds = payload["algorithms"]["bfs"]["persistent"]["rounds"]
    total = per_round.scaled(float(rounds))
    roof = make_roofline(total, chips=1, model_flops=total.flops)
    mega_wall = payload["algorithms"]["bfs"]["megakernel"]["wall_seconds"]
    # the megakernel's streamed-slice term: every expansion DMAs a full
    # wavefront x work_budget int32 block regardless of chunk degrees
    # (csr_stream, DESIGN.md section 14) — traffic the persistent drain's
    # HLO byte count does not model, so it is added explicitly before
    # computing achieved bandwidth.
    from repro.algorithms.common import default_work_budget
    work_budget = default_work_budget(g, cfg.wavefront)
    stream_bytes = float(rounds) * cfg.wavefront * work_budget * 4
    mega_bytes = total.bytes + stream_bytes
    achieved_bw = mega_bytes / mega_wall if mega_wall else 0.0
    payload["roofline"] = {
        "drain": "bfs/persistent body x rounds + megakernel stream term",
        "rounds": rounds,
        "hlo_flops": total.flops,
        "hlo_bytes": total.bytes,
        "stream_slice_bytes": stream_bytes,
        "megakernel_bytes": mega_bytes,
        "t_compute_s": roof.t_compute,
        "t_memory_s": roof.t_memory,
        "dominant": roof.dominant,
        "megakernel_wall_seconds": mega_wall,
        "achieved_bytes_per_s": achieved_bw,
        "roofline_bw_fraction": achieved_bw / HBM_BW,
        "backend": jax.default_backend(),
    }

    payload["findings"] = {
        "launch_collapse": {
            a: {"persistent": payload["algorithms"][a]["persistent"]
                ["launches"],
                "megakernel": payload["algorithms"][a]["megakernel"]
                ["launches"]}
            for a, _ in ALGOS},
        "bit_identical_to_persistent": {
            a: payload["algorithms"][a]["parity_vs_persistent"]
            for a, _ in ALGOS},
    }
    print(json.dumps(payload))


def run(out: str = OUT):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.bench_megakernel", "--child"],
        capture_output=True, text=True, env=env, timeout=1800)
    if proc.returncode != 0:
        raise RuntimeError(
            f"bench_megakernel child failed:\n{proc.stderr[-3000:]}")
    payload = json.loads(proc.stdout.strip().splitlines()[-1])

    for algo, entry in payload["algorithms"].items():
        for kernel in KERNELS:
            cell = entry[kernel]
            row(f"megakernel/{algo}/{kernel}", cell["wall_seconds"] * 1e6,
                f"rounds={cell['rounds']} launches={cell['launches']} "
                f"work={cell['work']}")
    r = payload["roofline"]
    row("megakernel/roofline", r["megakernel_wall_seconds"] * 1e6,
        f"dom={r['dominant']} tC={r['t_compute_s']:.2e} "
        f"tM={r['t_memory_s']:.2e} "
        f"bw_frac={r['roofline_bw_fraction']:.2e} "
        f"backend={r['backend']}")
    emit_json(out, payload)
    return payload


if __name__ == "__main__":
    if "--child" in sys.argv:
        _child()
    else:
        run()
