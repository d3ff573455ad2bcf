"""End-to-end smoke run of the Atos graph scheduler on a TPU.

    python chip_smoke.py [--seed N]             # one chip: every phase below
    python chip_smoke.py --chips 4 [--seed N]   # only the sharded path

One process builds everything from ``--seed``.  Any failure exits non-zero;
no phase's error is caught.  Without a TPU it exits non-zero at once.

Graphs are Graph500-shaped R-MAT (edge factor 16, A/B/C = 0.57/0.19/0.19),
symmetrized and resident on the device.  Phases on one chip:

1. device    -- platform, kind, count, JAX version, compile-cache directory.
2. graph     -- the scale-22 graph (4.2M vertices, ~128M CSR entries).
3. analytics -- ``runtime.execute`` under ``single.persistent`` for BFS from
                2 seeded roots and coloring on the scale-22 graph, and
                PageRank on a scale-16 graph, each with ``backend="jnp"``
                and again with ``"pallas"``; every result is checked against
                a numpy/scipy reference held in this file.
4. kernels   -- the pallas runs were compiled, not interpreted: the drain's
                compiled text holds ``tpu_custom_call``.
5. server    -- ``TaskServer`` serving 7 BFS jobs on a scale-18 graph and
                one PageRank job on the scale-16 graph with
                ``backend="auto"``, each job checked.

PageRank pops every vertex ~20 times and the server runs every tenant's
full merge-path budget each round, so both get smaller graphs than BFS and
coloring to end within the 1200 s a run may take (PERF.md, section 4).

Per run it prints rounds, seconds spent compiling (with persistent-cache
hits) and wall seconds ending on ``block_until_ready``.  These are smoke
numbers, not a benchmark.  The last line of standard output is
``{"ok": true, "device": {...}}``.

``--chips 4`` runs BFS (scale 20) and full-width coloring (scale 12) under
``sharded.persistent`` over four chips, on a 1-D mesh and on a 2x2 mesh,
plus the one comparison run of that coloring under ``single.persistent``
on device 0.
"""
from __future__ import annotations

import argparse
import collections
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

SCALE = 22            # BFS and coloring: Graph500 scale 22
PR_SCALE = 16         # PageRank (see the module docstring)
SERVER_SCALE = 18     # the TaskServer's BFS jobs
SHARD_SCALE = 20      # --chips 4: sharded BFS
FULL_WIDTH_SCALE = 12  # --chips 4: full-width coloring vs single.persistent
EDGE_FACTOR = 16      # Graph500 edgefactor
NUM_WORKERS = 4096    # wavefront width of every drain
WORK_BUDGET = 1 << 17  # merge-path edges per wavefront, floored at max degree
SERVER_BUDGET = 1 << 15  # per tenant: the server runs every lane's budget
MAX_ROUNDS = 1 << 17  # a drain that reaches it has failed
NUM_ROOTS = 2         # BFS roots per backend: one cold, one warm
PAGERANK_EPS = 1e-7   # residue bound; see check_pagerank
DAMPING = 0.85
INF = 0x7FFFFFFF      # algorithms.bfs.INF: unreached


# ------------------------------------------------------------ references
def bfs_reference(row_ptr: np.ndarray, col_idx: np.ndarray,
                  root: int) -> np.ndarray:
    """BFS hop distances, INF where unreached: scipy's BFS tree, then each
    vertex's depth in it by pointer doubling."""
    import scipy.sparse as sp
    from scipy.sparse.csgraph import breadth_first_order

    n = row_ptr.shape[0] - 1
    a = sp.csr_matrix((np.ones(col_idx.shape[0], np.int8), col_idx, row_ptr),
                      shape=(n, n))
    order, pred = breadth_first_order(a, root, directed=True,
                                      return_predecessors=True)
    parent = np.where(pred >= 0, pred, np.arange(n))
    hops = (pred >= 0).astype(np.int64)
    while True:
        up = parent[parent]
        if (up == parent).all():
            break
        hops += hops[parent]
        parent = up
    dist = np.full(n, INF, np.int32)
    dist[order] = hops[order]
    return dist


def pagerank_reference(row_ptr: np.ndarray, col_idx: np.ndarray,
                       damping: float = DAMPING) -> np.ndarray:
    """float64 power iteration of pr = (1-d) + d * A^T D^-1 pr."""
    import scipy.sparse as sp

    n = row_ptr.shape[0] - 1
    a = sp.csr_matrix((np.ones(col_idx.shape[0]), col_idx, row_ptr),
                      shape=(n, n))
    inv_deg = 1.0 / np.maximum(np.diff(row_ptr), 1)
    pr = np.full(n, 1.0 - damping)
    for _ in range(1000):
        nxt = (1.0 - damping) + damping * (a.T @ (pr * inv_deg))
        delta = np.abs(nxt - pr).max()
        pr = nxt
        if delta < 1e-9:
            return pr
    raise RuntimeError("reference PageRank did not converge")


def check_bfs(dist, ref: np.ndarray, what: str) -> None:
    dist = np.asarray(dist)
    bad = int((dist != ref).sum())
    if bad:
        raise AssertionError(f"{what}: {bad} BFS distances differ")


def check_pagerank(rank, ref: np.ndarray, what: str,
                   max_residue: float = 0.0) -> float:
    """The runtime parity tests' bound, max |rank - ref| < 1e-3, and
    every residue <= eps.  Those tests drain a 64-vertex graph at eps
    1e-5; the error the leftover residues leave grows with the largest
    rank (3.8e-3 at eps 1e-5 on a scale-14 graph), hence eps 1e-7."""
    if not max_residue <= PAGERANK_EPS:
        raise AssertionError(f"{what}: residue {max_residue} > eps")
    err = float(np.abs(np.asarray(rank, np.float64) - ref).max())
    if not err < 1e-3:
        raise AssertionError(f"{what}: PageRank error {err} >= 1e-3")
    return err


def check_coloring(colors, row_ptr: np.ndarray, col_idx: np.ndarray,
                   what: str) -> int:
    """Proper coloring: every vertex colored, no edge inside one color."""
    c = np.asarray(colors)
    src = np.repeat(np.arange(c.shape[0]), np.diff(row_ptr))
    if (c < 0).any() or (c[src] == c[col_idx]).any():
        raise AssertionError(f"{what}: not a proper coloring")
    return int(c.max()) + 1


# ----------------------------------------------------------- measurement
class CompileMeter:
    """Seconds JAX spends tracing, lowering and compiling, and persistent
    compilation-cache hits, read from ``jax.monitoring`` events."""

    def __init__(self):
        self.seconds = 0.0
        self.counts = collections.Counter()
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, name, secs, **_):
        if name.startswith("/jax/core/compile/"):
            self.seconds += secs

    def _event(self, name, **_):
        self.counts[name] += 1

    def mark(self):
        return self.seconds, self.counts["/jax/compilation_cache/cache_hits"]

    def since(self, mark):
        return (self.seconds - mark[0],
                self.counts["/jax/compilation_cache/cache_hits"] - mark[1])


def timed(meter: CompileMeter, fn):
    """Run ``fn`` to completion; return (result, wall s, compile s, hits)."""
    mark, t0 = meter.mark(), time.perf_counter()
    out = fn()
    jax.block_until_ready(out)
    wall = time.perf_counter() - t0
    compile_s, hits = meter.since(mark)
    return out, wall, compile_s, hits


def report(phase: str, **fields) -> None:
    print(f"[{phase}] " + " ".join(
        f"{k}={v:.6g}" if isinstance(v, float) else f"{k}={v}"
        for k, v in fields.items()), flush=True)


def graph_summary(g) -> dict:
    degrees = np.diff(np.asarray(g.row_ptr))
    return {"n": g.num_vertices, "m": g.num_edges,
            "max_degree": int(degrees.max()),
            "device_bytes": g.row_ptr.nbytes + g.col_idx.nbytes,
            "devices": sorted({str(d) for d in g.col_idx.devices()})}


def pick_roots(row_ptr: np.ndarray, count: int, seed: int) -> list:
    """``count`` seeded roots with at least one edge (Graph500's rule)."""
    candidates = np.flatnonzero(np.diff(row_ptr) > 0)
    rng = np.random.default_rng(seed)
    return [int(r) for r in rng.choice(candidates, count, replace=False)]


# ---------------------------------------------------------------- phases
def require_tpu() -> dict:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found {dev.platform!r} "
                 f"({dev.device_kind}); refusing to run on it")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def run_drain(meter, algo, g, backend, policy, params=None, *,
              num_workers=NUM_WORKERS, max_rounds=MAX_ROUNDS, **cfg_kw):
    """One ``runtime.execute`` drain; fails on max_rounds or a drop."""
    from repro.core import SchedulerConfig
    from repro.runtime import build_program, config_for, execute, parse_policy

    cfg = config_for(SchedulerConfig(num_workers=num_workers,
                                     max_rounds=max_rounds, backend=backend,
                                     **cfg_kw), parse_policy(policy))
    program = build_program(algo, g, cfg, params)
    res, wall, compile_s, hits = timed(
        meter, lambda: execute(program, g, cfg))
    rounds = res.info["rounds"]
    if rounds >= max_rounds or res.info["dropped"]:
        raise AssertionError(f"{algo} {policy} {backend}: rounds={rounds} "
                             f"dropped={res.info['dropped']}")
    return res, {"rounds": rounds, "compile_s": compile_s,
                 "cache_hits": hits, "wall_s": wall}, (program, g, cfg)


def drain_text(program, g, cfg) -> str:
    """Compiled text of the drain ``execute`` runs for ``program``."""
    from repro.core.scheduler import persistent_drive
    from repro.runtime import policy_of
    from repro.runtime.api import _shared_setup

    queue, state, _, step, cond, _ = _shared_setup(
        program, g, cfg, policy_of(cfg), None)
    carry = (queue, state, jnp.int32(0), jnp.int32(0))
    return jax.jit(lambda c: persistent_drive(step, cond, c)).lower(
        carry).compile().as_text()


def analytics(meter, g, host, roots, bfs_refs, pr_graph, pr_ref, *,
              prove_kernels=True, num_workers=NUM_WORKERS) -> None:
    """BFS and coloring on ``g``, PageRank on ``pr_graph``, all under
    single.persistent, with backend jnp and then pallas."""
    from repro.core.backend import resolve_interpret

    row_ptr, col_idx = host
    budget = {"work_budget": WORK_BUDGET}
    # PageRank keeps the default budget: a truncated chunk re-queues
    # behind fresh re-scan tasks, and the rank of a starved hub then sums
    # many small float32 pushes into a large residue
    pr_params = {"eps": PAGERANK_EPS, "damping": DAMPING}
    colorings = {}
    for backend in ("jnp", "pallas"):
        built = {}

        def drain(algo, graph, params, **fields):
            res, metrics, built[algo] = run_drain(
                meter, algo, graph, backend, "single.persistent", params,
                num_workers=num_workers)
            return res, dict(backend=backend, **fields, **metrics)

        for i, root in enumerate(roots):
            res, m = drain("bfs", g, {"source": root, **budget}, root=root,
                           run="cold" if i == 0 else "warm")
            check_bfs(res.state.dist, bfs_refs[i], f"bfs root {root}")
            report("bfs", **m)
        for run in ("cold", "warm"):
            res, m = drain("coloring", g, budget, run=run)
            colors = check_coloring(res.state.colors, row_ptr, col_idx,
                                    f"coloring {backend}")
            colorings[backend] = np.asarray(res.state.colors)
            report("coloring", **m, colors=colors)
        for run in ("cold", "warm"):
            res, m = drain("pagerank", pr_graph, pr_params, run=run)
            err = check_pagerank(res.state.rank, pr_ref,
                                 f"pagerank {backend}",
                                 float(jnp.max(res.state.residue)))
            report("pagerank", **m, max_abs_err=err)
        if backend == "pallas" and prove_kernels:
            interpret = resolve_interpret(None)
            custom = {algo: "tpu_custom_call" in drain_text(p, graph, c)
                      for algo, (p, graph, c) in built.items()}
            report("kernels", resolve_interpret=interpret,
                   tpu_custom_call=custom)
            if interpret or not all(custom.values()):
                raise AssertionError("the pallas drains were not compiled")
    if not (colorings["jnp"] == colorings["pallas"]).all():
        raise AssertionError("coloring differs between jnp and pallas")


def server(meter, g, host, pr_graph, pr_ref, seed: int,
           num_workers=NUM_WORKERS) -> None:
    """7 BFS jobs on ``g`` and one PageRank job on ``pr_graph`` through one
    TaskServer."""
    from repro.core import SchedulerConfig
    from repro.server import JobRegistry, JobSpec, TaskServer

    row_ptr, col_idx = host
    registry = JobRegistry()
    registry.register_graph("rmat", g)
    registry.register_graph("rmat-pr", pr_graph)
    srv = TaskServer(registry, num_lanes=8, config=SchedulerConfig(
        num_workers=num_workers, backend="auto"))
    roots = pick_roots(row_ptr, 7, seed + 1)
    bfs_jobs = {srv.submit(JobSpec("bfs", "rmat", {
        "source": r, "work_budget": SERVER_BUDGET})): r for r in roots}
    pr_job = srv.submit(JobSpec("pagerank", "rmat-pr", {
        "eps": PAGERANK_EPS, "damping": DAMPING}))
    out, wall, compile_s, hits = timed(meter, srv.run)
    for job, root in bfs_jobs.items():
        check_bfs(out.results[job], bfs_reference(row_ptr, col_idx, root),
                  f"server bfs job {job}")
    err = check_pagerank(out.results[pr_job], pr_ref, "server pagerank")
    report("server", jobs=len(bfs_jobs) + 1, rounds=out.stats.rounds,
           compile_s=compile_s, cache_hits=hits, wall_s=wall,
           pagerank_max_abs_err=err)


def shard_devices(array) -> list:
    return [str(s.device) for s in array.addressable_shards]


def sharded(meter, g, host, root: int, cg, chost, *,
            num_workers=NUM_WORKERS) -> None:
    """BFS on ``g`` and coloring on ``cg`` over four chips, each on a 1-D
    and a 2x2 mesh, and the coloring of one ``single.persistent`` run on
    device 0 to compare with.

    Coloring runs at full width (a wavefront of 2n: every queued task pops
    each round), where the sharded body's epoch-start reads and the single
    body's fused reads take the same schedule, so the colorings must be
    identical bit for bit; below full width each device pops its own
    wavefront and the (still proper) coloring differs.  A full-width
    wavefront expands up to every edge each round, hence the smaller graph.
    """
    from repro.core import SchedulerConfig
    from repro.launch.mesh import make_shard_mesh, make_shard_mesh2d
    from repro.runtime import build_program, config_for, parse_policy
    from repro.shard import partition_graph, place_partition, run_sharded

    shards = 4
    full_width = 2 * cg.num_vertices
    ref = bfs_reference(*host, root)
    res, m, _ = run_drain(meter, "coloring", cg, "jnp", "single.persistent",
                          num_workers=full_width)
    single = np.asarray(res.state.colors)
    colors = check_coloring(single, *chost, "coloring single")
    report("coloring", policy="single.persistent", num_workers=full_width,
           device=str(res.state.colors.devices()), colors=colors, **m)
    runs = (("bfs", g, {"source": root, "work_budget": WORK_BUDGET},
             num_workers), ("coloring", cg, None, full_width))
    for mesh_shape in (None, (2, 2)):
        mesh = (make_shard_mesh(shards) if mesh_shape is None
                else make_shard_mesh2d(*mesh_shape))
        layout = "1d" if mesh_shape is None else "2x2"
        for algo, graph, params, workers in runs:
            placed = place_partition(
                partition_graph(graph, shards, halo=False), mesh)
            cfg = config_for(SchedulerConfig(
                num_workers=workers, max_rounds=MAX_ROUNDS,
                num_shards=shards, mesh_shape=mesh_shape),
                parse_policy("sharded.persistent"))
            program = build_program(algo, graph, cfg, params)
            queues = []
            (state, stats), wall, compile_s, hits = timed(
                meter, lambda: run_sharded(program, graph, cfg, mesh=mesh,
                                           parts=placed,
                                           final_queues=queues))
            csr_devices = shard_devices(placed.col_idx)
            queue_devices = shard_devices(queues[0].lanes.buf)
            if (len(set(csr_devices)) != shards
                    or len(set(queue_devices)) != shards):
                raise AssertionError(
                    f"{algo} {layout}: shards on {csr_devices} / "
                    f"{queue_devices}, not one on each of {shards} devices")
            if (stats.rounds >= MAX_ROUNDS or stats.dropped
                    or stats.route_dropped or stats.mis_routed):
                raise AssertionError(f"{algo} {layout}: {stats}")
            fields = dict(mesh=layout, n=graph.num_vertices,
                          num_workers=workers, rounds=stats.rounds,
                          exchanged=stats.exchanged, compile_s=compile_s,
                          cache_hits=hits, wall_s=wall, csr_on=csr_devices,
                          queues_on=queue_devices)
            if algo == "bfs":
                check_bfs(state.dist, ref, f"sharded bfs {layout}")
                report("sharded-bfs", root=root, **fields)
            else:
                got = np.asarray(state.colors)
                colors = check_coloring(got, *chost,
                                        f"sharded coloring {layout}")
                if not (got == single).all():
                    raise AssertionError(f"sharded coloring {layout} differs "
                                         "from the single.persistent one")
                report("sharded-coloring", colors=colors,
                       identical_to_single=True, **fields)


def build_graph(scale: int, seed: int):
    """R-MAT graph on the device, its host CSR, and its summary line."""
    from repro.graph import rmat

    t0 = time.perf_counter()
    g = rmat(scale, EDGE_FACTOR, seed=seed)
    host = (np.asarray(g.row_ptr), np.asarray(g.col_idx))
    report("graph", scale=scale, build_s=time.perf_counter() - t0,
           **graph_summary(g))
    return g, host


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    device = require_tpu()
    from repro.launch.compile_cache import use_compile_cache

    cache_dir = use_compile_cache()
    if device["count"] < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} but JAX sees "
                 f"{device['count']} devices")
    report("device", jax=jax.__version__, cache_dir=cache_dir, **device)
    meter = CompileMeter()

    if args.chips == 4:
        g, host = build_graph(SHARD_SCALE, args.seed)
        cg, chost = build_graph(FULL_WIDTH_SCALE, args.seed + 2)
        sharded(meter, g, host, pick_roots(host[0], 1, args.seed)[0],
                cg, chost)
    else:
        g, host = build_graph(SCALE, args.seed)
        roots = pick_roots(host[0], NUM_ROOTS, args.seed)
        t0 = time.perf_counter()
        bfs_refs = [bfs_reference(*host, r) for r in roots]
        report("reference", roots=roots, seconds=time.perf_counter() - t0)
        pr_graph, pr_host = build_graph(PR_SCALE, args.seed + 1)
        t0 = time.perf_counter()
        pr_ref = pagerank_reference(*pr_host)
        report("reference", pagerank_seconds=time.perf_counter() - t0)
        analytics(meter, g, host, roots, bfs_refs, pr_graph, pr_ref)
        sg, shost = build_graph(SERVER_SCALE, args.seed + 3)
        server(meter, sg, shost, pr_graph, pr_ref, args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": device["platform"], "kind": device["kind"],
        "count": device["count"]}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
