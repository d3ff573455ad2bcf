"""Chip benchmark of the Atos graph scheduler: one run of one cell.

    python3 bench/run.py --workload kron20.bfs --seed 7 --seconds 51 --trace 0

The cell comes from ``BENCHMARK.json``: its configuration from the file the
cell names, its traffic from ``bench/traffic/<traffic>.json``.  The graph
family (``bench/graphs/<family>.py``), the job (``bench/jobs/<job>.py``) and
each metric's reader (``bench/metrics/<metric>.py``) are found by name, so
a new cell, configuration, traffic or metric adds files and edits none.

Set-up: the graph is made on the device from the seed, then one warm-up
search compiles (or loads from the cache) every program the window runs.
Window: searches back to back, one at a time, for ``--seconds``; a search
that starts in the window runs to its end and counts.  Then the peak
memory is read, the device graph freed, and every search of the window
compared with the plain reference.  ``--trace 1`` runs the same programs with a profiler
trace of the window, and reports the per-layer metrics instead of the
end-to-end ones.

The last line of standard output is one JSON object; the numbers compared
are the last lines of standard error.  Without a TPU, on a device kind
missing from ``bench/peaks.json``, or with fewer chips than the cell needs,
it exits 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()  # set-up is timed from here

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Optional  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
for _path in (str(ROOT / "src"), str(ROOT)):
    if _path not in sys.path:
        sys.path.insert(0, _path)

from bench import device as chip  # noqa: E402
from bench import trace as trace_mod  # noqa: E402
from bench import traffic as traffic_mod  # noqa: E402

INF = 0x7FFFFFFF


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list


def load_cell(name: str) -> Cell:
    """The cell ``name`` of BENCHMARK.json, with its configuration file and
    its traffic."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; cells: {sorted(cells)}")
    cell = cells[name]
    entry = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    config = json.loads((ROOT / entry["file"]).read_text())

    def mine(metrics):
        return [m for m in metrics if name in m.get("workloads", [name])]

    return Cell(name, cell["chips"], config,
                traffic_mod.load(cell["traffic"]),
                mine(spec["end_to_end"]), mine(spec["per_layer"]))


def _module(kind: str, name: str):
    """``bench/<kind>/<name>.py``, loaded once per process."""
    key = f"bench.{kind}.{name.replace('.', '_')}"
    if key not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            key, BENCH / kind / f"{name}.py")
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        sys.modules[key] = module
    return sys.modules[key]


def span(name: str):
    """A host span of the benchmark in the profiler's trace."""
    import jax

    return jax.profiler.TraceAnnotation(trace_mod.SPAN_PREFIX + name)


@dataclasses.dataclass
class RunRecord:
    """What the metric readers read (``bench/metrics/*.py``)."""
    n: int                    # vertices
    m: int                    # CSR entries (twice the undirected edges)
    wavefront: int
    setup: dict               # seconds of each set-up step
    jobs: list                # one dict per search of the window
    peaks: Optional[dict]     # the chip's published peaks
    profile: Optional[trace_mod.Profile] = None
    window_ns: Optional[tuple] = None   # traced window on the trace clock

    def job_spans(self) -> list:
        return [s for s in self.profile.spans if s.name == "job"]


def report(tag: str, **fields) -> None:
    print(f"[{tag}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


def run(cell: Cell, seed: int, seconds: float, traced: bool) -> dict:
    """One run of ``cell``; returns the result object."""
    import jax
    import numpy as np

    from repro.graph.csr import CSRGraph

    dev = chip.require_chip(jax, cell.chips)
    peaks = chip.peaks(dev["kind"])
    cache_dir = chip.use_compile_cache(jax)
    meter = chip.CompileMeter(jax)
    t_init = time.perf_counter()
    report("device", jax=jax.__version__, cache_dir=cache_dir, **dev)

    config, traffic = cell.config, cell.traffic
    family = _module("graphs", config["family"])
    job_mod = _module("jobs", traffic["job"])
    with span("graph"):
        made = family.generate(config, seed)
        graph = CSRGraph(row_ptr=made["row_ptr"], col_idx=made["col_idx"])
        degrees = np.diff(np.asarray(made["row_ptr"]))
    n, m = degrees.shape[0], made["m"]
    t_graph = time.perf_counter()
    report("graph", config=config["name"], family=config["family"], n=n,
           m=m, undirected_edges=m // 2, max_degree=int(degrees.max()),
           csr_capacity=graph.col_idx.shape[0], **made.get("facts", {}))

    runner = job_mod.Jobs(graph, config["scheduler"])
    with span("warmup"):
        warm = runner.run(traffic_mod.warmup_root(traffic, degrees), span)
    hops_from_warmup = np.asarray(warm["dist"])
    reached = hops_from_warmup != INF
    roots = traffic_mod.roots(traffic, seed, reached, degrees,
                              made.get("points"))
    setup_compile = meter.snapshot()
    t_warm = time.perf_counter()
    setup = {"init_s": t_init - T_START, "graph_s": t_graph - t_init,
             "warmup_s": t_warm - t_graph, "setup_s": t_warm - T_START}
    report("setup", **setup, **setup_compile,
           warmup_rounds=warm["rounds"], component=int(reached.sum()),
           roots=len(roots))
    del warm

    log_dir = tempfile.mkdtemp(prefix="bench_trace_") if traced else None
    if traced:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        jax.profiler.start_trace(log_dir, profiler_options=options)
    jobs, t0 = [], time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        root = roots[len(jobs) % len(roots)]
        before, start = meter.snapshot(), time.perf_counter()
        with span("job"):
            out = runner.run(root, span)
        out["wall_s"] = time.perf_counter() - start
        out["compile_s"] = meter.snapshot()["compile_s"] - before["compile_s"]
        jobs.append(out)
    t_end = time.perf_counter()
    if traced:
        jax.profiler.stop_trace()
    in_window = chip.CompileMeter.since(setup_compile, meter.snapshot())
    report("window", searches=len(jobs), seconds=t_end - t0, **in_window)

    stats = jax.devices()[0].memory_stats() or {}
    memory_peak = stats.get("peak_bytes_in_use")
    row_ptr = np.asarray(made["row_ptr"])
    col_idx = np.asarray(made["col_idx"])[:m]
    for job in jobs:
        job["dist"] = np.asarray(job["dist"])
        job["reached"] = int(np.count_nonzero(job["dist"] != INF))
        job["component_edges"] = int(degrees[job["dist"] != INF].sum()) // 2
    wavefront = runner.wavefront
    del graph, made, runner

    max_rounds = config["scheduler"]["max_rounds"]
    numbers = job_mod.check(jobs, row_ptr, col_idx, max_rounds)
    failed = sum(job_mod.failed(job, max_rounds) for job in jobs)
    for i, job in enumerate(jobs):
        root = job["root"]
        report("search", i=i, root=root, degree=int(degrees[root]),
               hops_from_warmup_root=int(hops_from_warmup[root]),
               rounds=job["rounds"], wall_s=job["wall_s"],
               compile_s=job["compile_s"], reached=job["reached"],
               teps=job["component_edges"] / job["wall_s"],
               mismatches=job["mismatches"])
        job["dist"] = None

    record = RunRecord(n=n, m=m, wavefront=wavefront, setup=setup,
                       jobs=jobs, peaks=peaks)
    result_device = dict(dev, memory_peak_bytes=memory_peak)
    breakdown = None
    if traced:
        record.profile = trace_mod.load(trace_mod.xplane_file(log_dir))
        shutil.rmtree(log_dir)
        spans = record.job_spans()
        if record.profile.ops and spans:
            lo, hi = spans[0].start_ns, spans[-1].end_ns
            record.window_ns = (lo, hi)
            result_device["busy_s"] = trace_mod.busy_ns(
                record.profile.ops, lo, hi) / 1e9
            result_device["window_s"] = (hi - lo) / 1e9
            breakdown = trace_mod.breakdown(record.profile, lo, hi)
    metrics = {}
    for spec in (cell.per_layer if traced else cell.end_to_end):
        value = _module("metrics", spec["name"]).read(record)
        if value is not None:
            metrics[spec["name"]] = {"value": value, "unit": spec["unit"]}

    limits = job_mod.LIMITS
    correct = bool(jobs) and all(numbers[k] <= limits[k] for k in limits)
    check = {k: {"value": numbers[k], "limit": limits[k]} for k in limits}
    report("check", searches=len(jobs), **numbers)
    result = {"correct": correct, "attempted": len(jobs), "failed": failed,
              "metrics": metrics, "device": result_device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["check"] = check
    for name, pair in check.items():
        print(f"check {name}={pair['value']} limit={pair['limit']}",
              file=sys.stderr, flush=True)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    try:
        result = run(cell, args.seed, args.seconds, bool(args.trace))
    except chip.NoChip as e:
        print(f"bench/run.py: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
