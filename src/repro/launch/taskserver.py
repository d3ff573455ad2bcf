"""Multi-tenant task-server driver: N concurrent graph jobs, one scheduler.

  PYTHONPATH=src python -m repro.launch.taskserver --jobs 8 --policy weighted
  PYTHONPATH=src python -m repro.launch.taskserver --jobs 12 --lanes 4 \
      --autotune --compare-sequential
  PYTHONPATH=src python -m repro.launch.taskserver --jobs 8 --backend pallas

Builds one scale-free (R-MAT) and one mesh (2-D grid) graph — the paper's
two dataset regimes — submits a mixed batch of BFS / PageRank / coloring
jobs against them, and drains everything through a single TaskServer,
printing per-job telemetry (latency, rounds, occupancy, overwork) and the
server totals.  ``--compare-sequential`` also runs the tenant-at-a-time
baseline to show the fused-wavefront round savings.
"""
from __future__ import annotations

import argparse
import logging
import subprocess

from ..core.scheduler import SchedulerConfig
from ..graph.generators import grid2d, rmat
from ..runtime.policy import POLICY_GRID, parse_policy
from ..server import (Autotuner, JobRegistry, JobSpec, TaskServer,
                      serve_sequential)
from .compile_cache import use_compile_cache

ALGO_CYCLE = ("bfs", "pagerank", "coloring")


def git_sha() -> str:
    """Best-effort provenance stamp for the trace meta block."""
    try:
        return subprocess.run(
            ["git", "rev-parse", "HEAD"], capture_output=True, text=True,
            timeout=5, check=True).stdout.strip()
    except Exception:
        return "unknown"


def build_registry(scale: int, grid_side: int, seed: int) -> JobRegistry:
    reg = JobRegistry()
    reg.register_graph("rmat", rmat(scale, edge_factor=8, seed=seed))
    reg.register_graph("grid", grid2d(grid_side, grid_side, seed=seed))
    return reg


def mixed_specs(n_jobs: int, registry: JobRegistry, eps: float,
                seed: int, shards: int = 1,
                stream: int = 0, stream_batch: int = 32,
                snapshot_every: int = 0, checkpoint_dir: str | None = None,
                resume: bool = False, compact_every: int = 0,
                overlay_slack: float = 0.25) -> list[JobSpec]:
    """Round-robin over algorithms x graphs, sources spread over vertices.

    With ``shards > 1`` the BFS jobs become sharded single-tenant jobs (the
    exchange-heavy workload benefits most from the mesh) while PageRank and
    coloring stay in the fused multi-tenant rounds — one batch exercising
    both serving modes.

    With ``stream > 0`` the BFS jobs become *streaming* jobs: each gets a
    deterministic seeded delta log (``graph/generators.edge_delta_stream``,
    ``stream`` batches of ``stream_batch`` edge ops) committed batch by
    batch with incremental recompute between drains; snapshot/resume
    posture per ``snapshot_every`` / ``checkpoint_dir`` / ``resume``
    (per-job subdirectories under ``checkpoint_dir``).
    """
    from ..graph.generators import edge_delta_stream
    from ..stream import StreamSpec

    specs = []
    graphs = registry.graph_names
    for i in range(n_jobs):
        algorithm = ALGO_CYCLE[i % len(ALGO_CYCLE)]
        gname = graphs[(i // len(ALGO_CYCLE)) % len(graphs)]
        n = registry.graph(gname).num_vertices
        params = {}
        if algorithm == "bfs":
            params["source"] = (seed + 7919 * i) % n
        elif algorithm == "pagerank":
            params["eps"] = eps
        stream_spec = None
        if stream > 0 and algorithm == "bfs":
            deltas = edge_delta_stream(registry.graph(gname), stream,
                                       stream_batch, seed=seed + i)
            job_dir = (f"{checkpoint_dir}/job_{i}"
                       if checkpoint_dir else None)
            stream_spec = StreamSpec(
                deltas=tuple(deltas),
                snapshot_every=snapshot_every if job_dir else 0,
                checkpoint_dir=job_dir, resume=resume and job_dir is not None,
                compact_every=compact_every, overlay_slack=overlay_slack)
        specs.append(JobSpec(algorithm, gname, params,
                             weight=1.0 + (i % 3),
                             shards=shards if algorithm == "bfs" else 1,
                             stream=stream_spec))
    return specs


def print_telemetry(result) -> None:
    hdr = (f"{'job':>3} {'algorithm':<9} {'graph':<5} {'lat(rounds)':>11} "
           f"{'active':>6} {'items':>7} {'occ':>6} {'overwork':>8} "
           f"{'drops':>5} {'bp':>3}")
    print(hdr)
    print("-" * len(hdr))
    for job_id in sorted(result.telemetry):
        t = result.telemetry[job_id]
        print(f"{job_id:>3} {t.algorithm:<9} {t.graph:<5} "
              f"{t.latency_rounds:>11} {t.rounds_active:>6} "
              f"{t.items_processed:>7} {t.occupancy:>6.3f} "
              f"{t.overwork:>8.2f} {t.dropped:>5} "
              f"{t.backpressure_events:>3}")
    s = result.stats
    print(f"server: rounds={s.rounds} occupancy={s.occupancy:.3f} "
          f"wall={s.wall_seconds:.2f}s "
          f"backpressure={s.backpressure_events} "
          f"deferred_admissions={s.deferred_admissions}")
    if s.sharded_jobs:
        print(f"sharded phases: {s.sharded_jobs} jobs, "
              f"{s.sharded_rounds} device rounds")
    if s.streaming_jobs:
        print(f"streaming phases: {s.streaming_jobs} jobs, "
              f"{s.stream_batches} delta batches")


def print_stream_records(server) -> None:
    """Per-batch breakdown of every streaming job's drains."""
    for job in server._jobs:
        if job.stream_result is None:
            continue
        res = job.stream_result
        print(f"streaming job {job.job_id}: {res.info['batches_run']} "
              f"batches (incremental={res.info['incremental']})")
        for r in res.batches:
            mode = "incr" if r.incremental else "full"
            print(f"  batch {r.batch:>3} [{mode}] ops={r.effective_ops:>4} "
                  f"seeds={r.seeds:>5} rounds={r.rounds:>5} "
                  f"work={r.work:>7} touched={r.touched_rows:>4} "
                  f"ovl={r.overlay:>4}{' compact' if r.compacted else ''}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--jobs", type=int, default=8)
    ap.add_argument("--lanes", type=int, default=8)
    ap.add_argument("--policy", default="weighted",
                    choices=["weighted", "round_robin",
                             "longest_queue_first"])
    ap.add_argument("--workers", type=int, default=64)
    ap.add_argument("--fetch", type=int, default=1)
    ap.add_argument("--exec-policy", default="auto",
                    help="execution policy "
                         "('<topology>.<kernel>[.g<width>]', DESIGN.md "
                         "sections 11-12, 14): e.g. fused.discrete drains "
                         "through a packed MultiQueue lane with a host "
                         "loop, sharded.persistent.g4 adds width-4 chunk "
                         "tasks, single.megakernel fuses a drain loop "
                         "into ONE Pallas kernel launch — an "
                         "interpret-mode prototype (no Mosaic lowering "
                         "yet, so a TPU refuses it), honored "
                         "by streaming jobs' per-batch drains; the "
                         "multi-tenant server rounds themselves stay "
                         "host-driven and warn.  auto keeps the config "
                         "defaults (single topology, persistent "
                         "kernel).  Known cells: "
                         + ", ".join(str(p) for p in POLICY_GRID))
    ap.add_argument("--granularity", type=int, default=1,
                    help="max task chunk width G (core/task.py, DESIGN.md "
                         "section 12): each queue slot carries up to G "
                         "consecutive CSR rows; 1 = classic single-vertex "
                         "tasks.  A .g<width> suffix on --exec-policy "
                         "overrides this.")
    ap.add_argument("--split-threshold", type=int, default=0,
                    help="chunk degree-sum cap at formation time (0 = "
                         "bounded by the merge-path work budget only) — "
                         "the paper's level-of-balancing dial")
    ap.add_argument("--backend", default="auto",
                    choices=["jnp", "pallas", "auto"],
                    help="kernel backend: jnp reference, Pallas TPU kernels "
                         "(interpret mode off-TPU), or auto-detect "
                         "(ignored under --autotune, which searches the "
                         "backend axis itself)")
    ap.add_argument("--shards", type=int, default=1,
                    help="run the BFS jobs as sharded single-tenant drains "
                         "over an N-device ('shard',) mesh (repro/shard); "
                         "needs N visible devices — on CPU set XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N")
    ap.add_argument("--mesh", type=int, nargs=2, default=None,
                    metavar=("R", "C"),
                    help="shard the BFS jobs over a 2-D ('row', 'col') "
                         "R x C device mesh instead of the 1-D ring "
                         "(DESIGN.md section 16): the routed exchange "
                         "decomposes into two per-axis all_to_alls; "
                         "implies --shards R*C")
    ap.add_argument("--overlap", action="store_true",
                    help="hide the exchange: stage routed task deliveries "
                         "one round (defer_rounds=1) so the collective "
                         "overlaps the next round's compute — results "
                         "unchanged (tasks are idempotent re-checks), "
                         "schedule may differ from strict delivery")
    ap.add_argument("--compress", action="store_true",
                    help="delta-compress exchange payloads on the wire "
                         "(sorted-run delta + zigzag bit-packing, "
                         "shard/codec.py); lossless, raw fallback when a "
                         "batch is incompressible")
    ap.add_argument("--stream", type=int, default=0, metavar="N",
                    help="turn the BFS jobs into streaming jobs over N "
                         "delta batches (repro/stream): each batch commits "
                         "edge inserts/deletes against the job's graph and "
                         "incrementally recomputes from the dirty frontier")
    ap.add_argument("--stream-batch", type=int, default=32, metavar="K",
                    help="edge operations per delta batch (mixed "
                         "inserts/deletes, both directions emitted)")
    ap.add_argument("--compact-every", type=int, default=0, metavar="B",
                    help="re-pack the slotted CSR's slabs every B delta "
                         "batches (graph/slotted.py; 0 = compact only on "
                         "overlay occupancy / slab-slack triggers).  "
                         "Commits stay O(touched rows) either way; "
                         "compaction amortizes the overlay away")
    ap.add_argument("--overlay-slack", type=float, default=0.25, metavar="F",
                    help="compact when the edge-log overlay exceeds F * m "
                         "live edges (default 0.25); smaller = tighter "
                         "slabs and more frequent O(m) re-packs")
    ap.add_argument("--snapshot-every", type=int, default=0, metavar="R",
                    help="write a crash-consistent mid-drain snapshot every "
                         "R rounds of a streaming drain (0 = batch "
                         "boundaries only; needs --checkpoint-dir)")
    ap.add_argument("--checkpoint-dir", default=None,
                    help="directory for streaming snapshots (per-job "
                         "subdirectories); enables snapshots and --resume")
    ap.add_argument("--resume", action="store_true",
                    help="resume each streaming job from its newest "
                         "snapshot under --checkpoint-dir (bit-identical "
                         "to the uninterrupted run)")
    ap.add_argument("--scale", type=int, default=8,
                    help="R-MAT scale (2**scale vertices)")
    ap.add_argument("--grid-side", type=int, default=16)
    ap.add_argument("--eps", type=float, default=1e-4,
                    help="PageRank convergence threshold")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write a Perfetto-loadable Chrome trace of every "
                         "round (server lanes, sharded phases, streaming "
                         "drains) to PATH — enables the in-trace ring "
                         "buffer (repro/obs, DESIGN.md section 15)")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write the canonical metrics JSONL (server/job "
                         "summaries, per-job latency histograms with exact "
                         "p50/p95/p99, per-round records) to PATH")
    ap.add_argument("--trace-capacity", type=int, default=0, metavar="N",
                    help="trace ring capacity in rounds per drain (0 = "
                         "default; oldest rounds are overwritten on "
                         "wraparound and counted as truncated)")
    ap.add_argument("--autotune", action="store_true",
                    help="pick the SchedulerConfig via the autotuner")
    ap.add_argument("--autotune-cache", default=".atos_autotune.json")
    ap.add_argument("--compare-sequential", action="store_true")
    ap.add_argument("-v", "--verbose", action="store_true")
    args = ap.parse_args()
    use_compile_cache()

    logging.basicConfig(
        level=logging.INFO if args.verbose else logging.WARNING,
        format="%(name)s: %(message)s")

    mesh_shape = tuple(args.mesh) if args.mesh else None
    if mesh_shape:
        rows, cols = mesh_shape
        if args.shards > 1 and args.shards != rows * cols:
            ap.error(f"--shards {args.shards} contradicts "
                     f"--mesh {rows} {cols} (= {rows * cols} shards)")
        args.shards = rows * cols
    if args.shards > 1:
        from .mesh import require_devices

        require_devices(args.shards, purpose=f"--shards {args.shards}")
    if args.resume and not args.checkpoint_dir:
        ap.error("--resume requires --checkpoint-dir")
    if args.snapshot_every and not args.checkpoint_dir:
        ap.error("--snapshot-every requires --checkpoint-dir")
    registry = build_registry(args.scale, args.grid_side, args.seed)
    specs = mixed_specs(args.jobs, registry, args.eps, args.seed,
                        shards=args.shards, stream=args.stream,
                        stream_batch=args.stream_batch,
                        snapshot_every=args.snapshot_every,
                        checkpoint_dir=args.checkpoint_dir,
                        resume=args.resume,
                        compact_every=args.compact_every,
                        overlay_slack=args.overlay_slack)

    granularity = args.granularity
    if args.exec_policy == "auto":
        topology, kernel, persistent = "auto", "auto", True
    else:
        policy = parse_policy(args.exec_policy)
        topology, kernel = policy.topology, policy.kernel
        persistent = policy.persistent
        # an explicit granularity segment — including .g1 — wins over
        # --granularity, as the flag's help promises
        if len(args.exec_policy.split(".")) == 3:
            granularity = policy.granularity
    if args.autotune and (mesh_shape or args.overlap or args.compress):
        # the tuner searches launch shapes, not exchange posture; the mesh
        # knobs would be silently dropped from its chosen config
        ap.error("--mesh/--overlap/--compress need an explicit config; "
                 "drop --autotune")
    config = None if args.autotune else SchedulerConfig(
        num_workers=args.workers, fetch_size=args.fetch,
        backend=args.backend, topology=topology, persistent=persistent,
        kernel=kernel, granularity=granularity,
        split_threshold=args.split_threshold,
        mesh_shape=mesh_shape, defer_rounds=1 if args.overlap else 0,
        compress=args.compress)
    autotuner = (Autotuner(cache_path=args.autotune_cache)
                 if args.autotune else None)

    trace = None
    if args.trace_out or args.metrics_out:
        from ..obs import DEFAULT_CAPACITY, Trace

        trace = Trace(capacity=args.trace_capacity or DEFAULT_CAPACITY,
                      meta={"git_sha": git_sha()})

    server = TaskServer(registry, num_lanes=args.lanes, config=config,
                        policy=args.policy, autotuner=autotuner,
                        trace=trace)
    for spec in specs:
        server.submit(spec)
    print(f"submitted {len(specs)} jobs to {args.lanes} lanes "
          f"(policy={args.policy})")
    result = server.run()
    print_telemetry(result)
    if args.stream > 0:
        print_stream_records(server)
    if trace is not None:
        trace.write(args.trace_out, args.metrics_out)
        lat = trace.histograms.get("job_latency_rounds")
        if lat is not None and lat.count:
            print(f"job latency (rounds): p50={lat.percentile(50)} "
                  f"p95={lat.percentile(95)} p99={lat.percentile(99)} "
                  f"over {lat.count} jobs")
        for path, what in ((args.trace_out, "chrome trace"),
                           (args.metrics_out, "metrics jsonl")):
            if path:
                print(f"wrote {what}: {path} "
                      f"({len(trace.records)} round records, "
                      f"{trace.truncated} truncated)")

    if args.compare_sequential:
        seq_config = config
        if seq_config is None and autotuner is not None:
            seq_config = autotuner.recommend_for_mix(
                [(s.algorithm, registry.graph(s.graph)) for s in specs])
        seq = serve_sequential(registry, specs, config=seq_config)
        print(f"sequential: rounds={seq.stats.rounds} "
              f"occupancy={seq.stats.occupancy:.3f} "
              f"wall={seq.stats.wall_seconds:.2f}s")
        print(f"fused/sequential rounds: {result.stats.rounds}"
              f"/{seq.stats.rounds} "
              f"({result.stats.rounds / max(seq.stats.rounds, 1):.2f}x)")


if __name__ == "__main__":
    main()
