"""Bench smoke: recompute deterministic counters, diff vs checked-in JSON.

``PYTHONPATH=src python -m benchmarks.run --smoke``

The sharded and granularity benchmarks' counters are pure functions of the
schedule — graph, seeds, launch shape, shard count, chunk width — with
zero timing noise, so any change to the drain engines that shifts them is
a real behavioral regression, not jitter.  This re-runs the exact
configurations ``bench_shard`` records in ``BENCH_shard.json`` (BFS over
the R-MAT and grid graphs, every shard count, steal on/off, the 2-D mesh
sweep's per-axis exchange / overlap / compression counters, and the
grid-vs-successive-halving autotune agreement record) and
``bench_granularity`` records in ``BENCH_granularity.json`` (PageRank
ample/tight-budget rounds + formation splits and sharded per-g exchange
volume, every chunk width) and ``bench_stream`` records in
``BENCH_stream.json`` (per-delta-batch rounds/work/seed counts for the
incremental and full-recompute streaming modes, plus the sharded streaming
parity bit) and ``bench_megakernel`` records in ``BENCH_megakernel.json``
(rounds / launches-per-drain / work for every algorithm x kernel-strategy
cell — the megakernel's launches == 1 collapse and its bit-parity with the
persistent drain) and ``bench_obs`` records in ``BENCH_obs.json`` (per
policy cell: the tracing-disabled-is-identity parity bit, the round count
and the one-ring-record-per-round invariant) and fails loudly when any
recomputed counter disagrees with the checked-in value.  CI runs it on
every push (``bench-smoke`` job); the full benchmark suite refreshes the
JSONs deliberately, this guard keeps them honest in between.

The guard also validates every emitted artifact against the canonical
observability schema (``repro/obs/schema.py``): each ``BENCH_*.json``
must carry the ``meta`` provenance envelope (``validate_bench``), the
checked-in Chrome trace must be loadable trace-event JSON
(``validate_chrome_trace``) and the metrics JSONL must contain only
schema-valid documents (``validate_metrics_jsonl``).

Like the benchmarks, the measurement runs in a subprocess that forces 8
host devices before jax initializes, so the smoke works under plain CPU CI.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
SHARD_JSON = REPO / "BENCH_shard.json"
GRANULARITY_JSON = REPO / "BENCH_granularity.json"
STREAM_JSON = REPO / "BENCH_stream.json"
MEGAKERNEL_JSON = REPO / "BENCH_megakernel.json"
OBS_JSON = REPO / "BENCH_obs.json"
OBS_TRACE_JSON = REPO / "BENCH_obs_trace.json"
OBS_METRICS_JSONL = REPO / "BENCH_obs_metrics.jsonl"

#: fields of each per-shard-count entry that are schedule-deterministic
#: (wall_seconds, balances etc. are measurements, not invariants)
_SHARD_FIELDS = ("rounds", "exchanged_total", "per_device_items")
_STEAL_FIELDS = ("rounds", "donated", "stolen_executed")
#: schedule-deterministic fields of each 2-D mesh cell (section 16):
#: per-axis cross-device payload, payload vs padding split, metered wire
#: ints, and the overlap pipeline's delivery counters
_MESH_FIELDS = ("rounds", "exchanged_total", "exchanged_row",
                "exchanged_col", "payload_ints", "padding_ints",
                "wire_ints", "deferred", "overlap_rounds")
#: the autotune agreement record is deterministic end to end (structural
#: runner, CRC tiebreak): the chosen keys themselves are pinned
_AUTOTUNE_FIELDS = ("grid_chosen", "sh_chosen", "agree", "cells_total",
                    "cells_measured")
#: schedule-deterministic fields of each granularity cell's workloads
_GRAN_FIELDS = {
    "pagerank_ample": ("rounds", "work", "splits"),
    "pagerank_tight": ("rounds", "work", "splits"),
    "bfs_shard": ("rounds", "exchanged_total", "splits"),
}
#: schedule-deterministic fields of each streaming per-batch record
#: (touched/overlay/compacted meter the slotted O(delta) commit path —
#: pure functions of the delta log + COMPACT_EVERY, so guarded too)
_STREAM_FIELDS = ("rounds", "work", "seeds", "eff", "touched", "overlay",
                  "compacted")
_STREAM_SHARD_FIELDS = ("rounds", "work", "exchanged", "parity")
#: schedule-deterministic fields of each (algorithm x kernel) cell —
#: launches is the megakernel's headline invariant (1 per drain)
_MEGA_FIELDS = ("rounds", "launches", "work")
#: schedule-deterministic fields of each obs policy cell — parity is the
#: tracing-disabled-is-identity invariant, ring_records the
#: one-record-per-round invariant (walls/ratios are measurements)
_OBS_FIELDS = ("rounds", "work", "ring_records", "parity")


def _recompute() -> dict:
    """Run bench_shard's deterministic portion in an 8-device subprocess.

    Every graph parameter and launch shape is imported from bench_shard so
    the guard can never drift from the configs that produced the baseline.
    """
    from .bench_shard import (GRID_SIDE, MESH_SHAPES, SCALE, SHARD_COUNTS,
                              SHARD_WORKERS, STEAL_CHUNK, STEAL_THRESHOLD,
                              STEAL_WORKERS)

    body = f"""
import os
os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=8'
os.environ['JAX_PLATFORMS'] = 'cpu'
import json
import numpy as np
from repro.core import SchedulerConfig
from repro.graph.generators import grid2d, rmat
from repro.runtime import build_program
from repro.shard import run_sharded

graphs = {{
    'rmat': rmat({SCALE}, edge_factor=8, seed=1),
    'grid': grid2d({GRID_SIDE}, {GRID_SIDE}, seed=0),
}}
out = {{}}
for name, g in graphs.items():
    entry = {{'shards': {{}}, 'steal': {{}}}}
    for s in {list(SHARD_COUNTS)}:
        cfg = SchedulerConfig(num_workers={SHARD_WORKERS}, fetch_size=1,
                              num_shards=s, persistent=False)
        program = build_program('bfs', g, cfg, params={{'source': 0}})
        state, stats = run_sharded(program, g, cfg)
        entry['shards'][str(s)] = {{
            'rounds': stats.rounds,
            'exchanged_total': stats.exchanged,
            'per_device_items': stats.per_device_items.tolist(),
        }}
    for label, kw in {{'steal_off': {{}},
                       'steal_on': {{'steal_threshold': {STEAL_THRESHOLD},
                                     'steal_chunk': {STEAL_CHUNK}}}}}.items():
        cfg = SchedulerConfig(num_workers={STEAL_WORKERS}, num_shards=8,
                              persistent=False, **kw)
        program = build_program('bfs', g, cfg, params={{'source': 0}})
        state, stats = run_sharded(program, g, cfg)
        entry['steal'][label] = {{
            'rounds': stats.rounds,
            'donated': stats.donated,
            'stolen_executed': stats.stolen_executed,
        }}
    if name == 'rmat':
        entry['mesh'] = {{}}
        for mesh in {list(MESH_SHAPES)}:
            label = '%dx%d' % tuple(mesh)
            entry['mesh'][label] = {{}}
            for dlabel, defer in (('strict', 0), ('defer', 1)):
                cell = {{}}
                for clabel, comp in (('raw', False), ('compressed', True)):
                    cfg = SchedulerConfig(num_workers={SHARD_WORKERS},
                                          num_shards=8,
                                          mesh_shape=tuple(mesh),
                                          defer_rounds=defer, compress=comp)
                    program = build_program('bfs', g, cfg,
                                            params={{'source': 0}})
                    state, stats = run_sharded(program, g, cfg)
                    cell[clabel] = {{
                        'rounds': stats.rounds,
                        'exchanged_total': stats.exchanged,
                        'exchanged_row': stats.exchanged_row,
                        'exchanged_col': stats.exchanged_col,
                        'payload_ints': stats.payload_ints,
                        'padding_ints': stats.padding_ints,
                        'wire_ints': stats.wire_ints,
                        'deferred': stats.deferred_delivered,
                        'overlap_rounds': stats.overlap_rounds,
                    }}
                entry['mesh'][label][dlabel] = cell
    import tempfile
    from pathlib import Path as _P
    from repro.server import Autotuner, structural_cost_runner
    with tempfile.TemporaryDirectory() as td:
        Autotuner(cache_path=_P(td) / 'g.json', warmup=0, iters=1,
                  runner=structural_cost_runner,
                  search='grid').tune('bfs', g)
        Autotuner(cache_path=_P(td) / 's.json', warmup=0, iters=1,
                  runner=structural_cost_runner, search='sh').tune('bfs', g)
        ge = next(iter(json.loads((_P(td) / 'g.json').read_text()).values()))
        se = next(iter(json.loads((_P(td) / 's.json').read_text()).values()))
    entry['autotune'] = {{
        'grid_chosen': ge['chosen'], 'sh_chosen': se['chosen'],
        'agree': ge['chosen'] == se['chosen'],
        'cells_total': se['cells_total'],
        'cells_measured': se['cells_measured'],
    }}
    out[name] = entry
print(json.dumps(out))
"""
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        [str(REPO / "src")] + ([os.environ["PYTHONPATH"]]
                               if "PYTHONPATH" in os.environ else [])))
    proc = subprocess.run([sys.executable, "-c", body], capture_output=True,
                          text=True, env=env, timeout=1800, cwd=REPO)
    if proc.returncode != 0:
        raise RuntimeError(f"smoke subprocess failed:\n{proc.stderr[-3000:]}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def _recompute_granularity() -> dict:
    """Re-run bench_granularity's deterministic portion (8-device child).

    Imports the sweep constants from bench_granularity so the guard can
    never drift from the configs that produced the baseline.
    """
    from .bench_granularity import (GRANULARITIES, GRID_SIDE, PR_EPS,
                                    PR_WORKERS, SCALE, SHARD_WORKERS,
                                    TIGHT_BUDGET)

    body = f"""
import os
os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=8'
os.environ['JAX_PLATFORMS'] = 'cpu'
import json
import numpy as np
from repro.algorithms.pagerank import pagerank_async
from repro.core import SchedulerConfig
from repro.graph.generators import grid2d, rmat
from repro.runtime import build_program
from repro.shard import run_sharded

graphs = {{
    'rmat': rmat({SCALE}, edge_factor=8, seed=1),
    'grid': grid2d({GRID_SIDE}, {GRID_SIDE}, seed=0),
}}
out = {{}}
for name, g in graphs.items():
    entry = {{}}
    for gr in {list(GRANULARITIES)}:
        cell = {{}}
        for label, budget in (('pagerank_ample', None),
                              ('pagerank_tight', {TIGHT_BUDGET})):
            cfg = SchedulerConfig(num_workers={PR_WORKERS}, fetch_size=1,
                                  persistent=False, granularity=gr)
            _, info = pagerank_async(g, cfg, eps={PR_EPS},
                                     work_budget=budget)
            cell[label] = {{'rounds': info['rounds'], 'work': info['work'],
                            'splits': info['splits']}}
        cfg = SchedulerConfig(num_workers={SHARD_WORKERS}, fetch_size=1,
                              num_shards=8, persistent=False,
                              granularity=gr)
        program = build_program('bfs', g, cfg, params={{'source': 0}})
        state, stats = run_sharded(program, g, cfg)
        cell['bfs_shard'] = {{'rounds': stats.rounds,
                              'exchanged_total': stats.exchanged,
                              'splits': program.count_of('splits', state)}}
        entry[str(gr)] = cell
    out[name] = entry
print(json.dumps(out))
"""
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        [str(REPO / "src")] + ([os.environ["PYTHONPATH"]]
                               if "PYTHONPATH" in os.environ else [])))
    proc = subprocess.run([sys.executable, "-c", body], capture_output=True,
                          text=True, env=env, timeout=1800, cwd=REPO)
    if proc.returncode != 0:
        raise RuntimeError(
            f"granularity smoke subprocess failed:\n{proc.stderr[-3000:]}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def _recompute_stream() -> dict:
    """Re-run bench_stream's deterministic portion (8-device child).

    Imports the stream constants from bench_stream so the guard can never
    drift from the configs that produced the baseline.
    """
    from .bench_stream import (ALGOS, BATCH_SIZE, BATCHES, COMPACT_EVERY,
                               EDGE_FACTOR, GRAPH_SEED, SCALE, STREAM_SEED,
                               WORKERS)

    body = f"""
import os
os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=8'
os.environ['JAX_PLATFORMS'] = 'cpu'
import json
import numpy as np
from repro.core import SchedulerConfig
from repro.graph.generators import edge_delta_stream, rmat
from repro.runtime import stream_execute

base = rmat({SCALE}, edge_factor={EDGE_FACTOR}, seed={GRAPH_SEED})
deltas = edge_delta_stream(base, {BATCHES}, {BATCH_SIZE},
                           seed={STREAM_SEED})
cfg = SchedulerConfig(num_workers={WORKERS}, topology='single',
                      persistent=False)
out = {{'algorithms': {{}}, 'm': base.num_edges}}
for algo, params in {list(ALGOS)!r}:
    entry = {{}}
    for mode, incr in (('incremental', True), ('full', False)):
        res = stream_execute(algo, base, deltas, cfg, params=dict(params),
                             incremental=incr,
                             compact_every={COMPACT_EVERY})
        entry[mode] = [{{'rounds': r.rounds, 'work': r.work,
                         'seeds': r.seeds, 'eff': r.effective_ops,
                         'touched': r.touched_rows, 'overlay': r.overlay,
                         'compacted': r.compacted}}
                       for r in res.batches]
    out['algorithms'][algo] = entry
scfg = SchedulerConfig(num_workers={WORKERS}, topology='sharded',
                       num_shards=8, persistent=False)
sres = stream_execute('bfs', base, deltas, scfg, params={{'source': 0}},
                      compact_every={COMPACT_EVERY})
ref = stream_execute('bfs', base, deltas, cfg, params={{'source': 0}},
                     compact_every={COMPACT_EVERY})
out['sharded_bfs'] = {{
    'rounds': sres.info['rounds'], 'work': sres.info['work'],
    'exchanged': sres.info['exchanged'],
    'parity': bool((np.asarray(sres.result)
                    == np.asarray(ref.result)).all()),
}}
print(json.dumps(out))
"""
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        [str(REPO / "src")] + ([os.environ["PYTHONPATH"]]
                               if "PYTHONPATH" in os.environ else [])))
    proc = subprocess.run([sys.executable, "-c", body], capture_output=True,
                          text=True, env=env, timeout=1800, cwd=REPO)
    if proc.returncode != 0:
        raise RuntimeError(
            f"stream smoke subprocess failed:\n{proc.stderr[-3000:]}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def _recompute_megakernel() -> dict:
    """Re-run bench_megakernel's deterministic portion in a subprocess.

    Imports the sweep constants from bench_megakernel so the guard can
    never drift from the configs that produced the baseline.
    """
    from .bench_megakernel import (ALGOS, EDGE_FACTOR, GRAPH_SEED, KERNELS,
                                   SCALE, WORKERS)

    body = f"""
import os
os.environ['JAX_PLATFORMS'] = 'cpu'
import json
import numpy as np
from repro.core import SchedulerConfig
from repro.graph.generators import rmat
from repro.runtime import (ExecutionPolicy, build_program, config_for,
                           execute)

g = rmat({SCALE}, edge_factor={EDGE_FACTOR}, seed={GRAPH_SEED})
out = {{'algorithms': {{}}}}
for algo, params in {list(ALGOS)!r}:
    entry = {{}}
    results = {{}}
    for kernel in {list(KERNELS)}:
        cfg = config_for(SchedulerConfig(num_workers={WORKERS}),
                         ExecutionPolicy('single', kernel))
        program = build_program(algo, g, cfg, params=dict(params))
        state, stats, info = execute(program, g, cfg)
        results[kernel] = np.asarray(program.result(state))
        entry[kernel] = {{'rounds': info['rounds'],
                          'launches': info['launches'],
                          'work': info['work']}}
    entry['parity_vs_persistent'] = bool(
        (results['megakernel'] == results['persistent']).all())
    out['algorithms'][algo] = entry
print(json.dumps(out))
"""
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        [str(REPO / "src")] + ([os.environ["PYTHONPATH"]]
                               if "PYTHONPATH" in os.environ else [])))
    proc = subprocess.run([sys.executable, "-c", body], capture_output=True,
                          text=True, env=env, timeout=1800, cwd=REPO)
    if proc.returncode != 0:
        raise RuntimeError(
            f"megakernel smoke subprocess failed:\n{proc.stderr[-3000:]}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def _recompute_obs() -> dict:
    """Re-run bench_obs's deterministic portion in a subprocess.

    Recomputes, per policy cell, the traced-vs-untraced parity bit, the
    round count and the ring record count — the walls/ratios in the
    checked-in JSON are measurements and are not guarded.
    """
    from .bench_obs import CELLS, EDGE_FACTOR, GRAPH_SEED, SCALE, WORKERS

    body = f"""
import os
os.environ['JAX_PLATFORMS'] = 'cpu'
import json
import numpy as np
from repro.core import SchedulerConfig
from repro.graph.generators import rmat
from repro.obs import Trace
from repro.runtime import build_program, config_for, execute, parse_policy

g = rmat({SCALE}, edge_factor={EDGE_FACTOR}, seed={GRAPH_SEED})
out = {{'cells': {{}}}}
for cell in {list(CELLS)!r}:
    policy = parse_policy(cell)
    cfg = config_for(SchedulerConfig(num_workers={WORKERS}), policy)
    program = build_program('bfs', g, cfg, params={{'source': 0}})
    base_state, base_stats, base_info = execute(program, g, cfg)
    trace = Trace()
    tr_state, tr_stats, tr_info = execute(program, g, cfg, trace=trace)
    out['cells'][cell] = {{
        'rounds': base_info['rounds'],
        'work': base_info['work'],
        'ring_records': len(trace.records),
        'parity': bool(
            (np.asarray(program.result(tr_state))
             == np.asarray(program.result(base_state))).all()
            and tr_info == base_info),
    }}
print(json.dumps(out))
"""
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        [str(REPO / "src")] + ([os.environ["PYTHONPATH"]]
                               if "PYTHONPATH" in os.environ else [])))
    proc = subprocess.run([sys.executable, "-c", body], capture_output=True,
                          text=True, env=env, timeout=1800, cwd=REPO)
    if proc.returncode != 0:
        raise RuntimeError(
            f"obs smoke subprocess failed:\n{proc.stderr[-3000:]}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def validate_artifacts() -> list:
    """Schema-validate every emitted artifact; returns a list of error
    strings (empty = pass).

    Every ``BENCH_*.json`` at the repo root must carry the canonical
    ``meta`` envelope (``obs.validate_bench``); the obs trace must be a
    loadable Chrome trace-event document and the obs metrics JSONL must
    contain only schema-valid docs.  Runs in-process — validation needs
    no jax and no devices.
    """
    sys.path.insert(0, str(REPO / "src"))
    try:
        from repro.obs import (validate_bench, validate_chrome_trace,
                               validate_metrics_jsonl)
    finally:
        sys.path.pop(0)

    errors = []
    for path in sorted(REPO.glob("BENCH_*.json")):
        if path.name == OBS_TRACE_JSON.name:
            continue          # chrome-trace format, validated below
        try:
            validate_bench(json.loads(path.read_text()), name=path.name)
        except ValueError as e:
            errors.append(str(e))
    if OBS_TRACE_JSON.exists():
        try:
            validate_chrome_trace(json.loads(OBS_TRACE_JSON.read_text()))
        except ValueError as e:
            errors.append(f"{OBS_TRACE_JSON.name}: {e}")
    if OBS_METRICS_JSONL.exists():
        try:
            validate_metrics_jsonl(
                OBS_METRICS_JSONL.read_text().splitlines())
        except ValueError as e:
            errors.append(f"{OBS_METRICS_JSONL.name}: {e}")
    return errors


def run() -> int:
    """Returns the number of mismatches (0 = pass); prints a report."""
    missing = [p for p in (SHARD_JSON, GRANULARITY_JSON, STREAM_JSON,
                           MEGAKERNEL_JSON, OBS_JSON)
               if not p.exists()]
    if missing:
        for p in missing:
            section = {SHARD_JSON: "shard",
                       GRANULARITY_JSON: "granularity",
                       STREAM_JSON: "stream",
                       MEGAKERNEL_JSON: "megakernel",
                       OBS_JSON: "obs"}[p]
            print(f"smoke: {p.name} missing — run "
                  f"'python -m benchmarks.run {section}' to create the "
                  f"baseline")
        return 1
    mismatches = 0

    def check(path: str, want, got):
        nonlocal mismatches
        if want != got:
            mismatches += 1
            print(f"smoke MISMATCH {path}: checked-in {want!r} != "
                  f"recomputed {got!r}")

    baseline = json.loads(SHARD_JSON.read_text())["graphs"]
    fresh = _recompute()
    for gname, entry in baseline.items():
        for s, want in entry["shards"].items():
            got = fresh[gname]["shards"][s]
            for field in _SHARD_FIELDS:
                check(f"{gname}/shards={s}/{field}", want[field], got[field])
        for label, want in entry.get("steal", {}).items():
            got = fresh[gname]["steal"][label]
            for field in _STEAL_FIELDS:
                check(f"{gname}/steal/{label}/{field}", want[field],
                      got[field])
        for label, modes in entry.get("mesh", {}).items():
            for dlabel, want_cell in modes.items():
                for clabel in ("raw", "compressed"):
                    got_cell = fresh[gname]["mesh"][label][dlabel][clabel]
                    for field in _MESH_FIELDS:
                        check(f"{gname}/mesh{label}/{dlabel}/{clabel}"
                              f"/{field}", want_cell[clabel][field],
                              got_cell[field])
        if "autotune" in entry:
            got_at = fresh[gname]["autotune"]
            for field in _AUTOTUNE_FIELDS:
                check(f"{gname}/autotune/{field}",
                      entry["autotune"][field], got_at[field])

    gran_base = json.loads(GRANULARITY_JSON.read_text())["graphs"]
    gran_fresh = _recompute_granularity()
    for gname, entry in gran_base.items():
        for gr, cell in entry["g"].items():
            got_cell = gran_fresh[gname][gr]
            for workload, fields in _GRAN_FIELDS.items():
                for field in fields:
                    check(f"{gname}/g={gr}/{workload}/{field}",
                          cell[workload][field],
                          got_cell[workload][field])

    stream_base = json.loads(STREAM_JSON.read_text())
    stream_fresh = _recompute_stream()
    stream_m = stream_fresh["m"]
    for algo, entry in stream_base["algorithms"].items():
        for mode in ("incremental", "full"):
            want_rows = entry[mode]["per_batch"]
            got_rows = stream_fresh["algorithms"][algo][mode]
            for i, (want, got) in enumerate(zip(want_rows, got_rows)):
                for field in _STREAM_FIELDS:
                    check(f"stream/{algo}/{mode}/batch{i}/{field}",
                          want[field], got[field])
                # O(delta) commit guard: a commit rewriting >= m rows
                # means the slotted path degraded to a full rebuild
                check(f"stream/{algo}/{mode}/batch{i}/touched<m",
                      True, got["touched"] < stream_m)
    for field in _STREAM_SHARD_FIELDS:
        check(f"stream/sharded_bfs/{field}",
              stream_base["sharded_bfs"][field],
              stream_fresh["sharded_bfs"][field])

    mega_base = json.loads(MEGAKERNEL_JSON.read_text())["algorithms"]
    mega_fresh = _recompute_megakernel()["algorithms"]
    from .bench_megakernel import KERNELS as _MEGA_KERNELS
    for algo, entry in mega_base.items():
        for kernel in _MEGA_KERNELS:
            for field in _MEGA_FIELDS:
                check(f"megakernel/{algo}/{kernel}/{field}",
                      entry[kernel][field],
                      mega_fresh[algo][kernel][field])
        check(f"megakernel/{algo}/parity_vs_persistent",
              entry["parity_vs_persistent"],
              mega_fresh[algo]["parity_vs_persistent"])

    obs_base = json.loads(OBS_JSON.read_text())["cells"]
    obs_fresh = _recompute_obs()["cells"]
    for cell, entry in obs_base.items():
        for field in _OBS_FIELDS:
            check(f"obs/{cell}/{field}", entry[field],
                  obs_fresh[cell][field])

    for err in validate_artifacts():
        mismatches += 1
        print(f"smoke SCHEMA {err}")

    names = (f"{SHARD_JSON.name} / {GRANULARITY_JSON.name} / "
             f"{STREAM_JSON.name} / {MEGAKERNEL_JSON.name} / "
             f"{OBS_JSON.name} + artifact schemas")
    if mismatches:
        print(f"smoke: {mismatches} counter regression(s) vs {names}")
    else:
        print(f"smoke: OK — all deterministic counters match {names}")
    return mismatches


def main() -> None:
    sys.exit(1 if run() else 0)


if __name__ == "__main__":
    main()
