"""Sharded multi-device scheduler (DESIGN.md section 10).

Three tiers:

  * pure host math (partitioner, ownership, donation planning) — always;
  * degenerate 1-shard runs through the full shard_map machinery — always
    (a mesh of one device is valid);
  * real 8-device runs — spawned in subprocesses that force
    ``--xla_force_host_platform_device_count=8`` *before* jax initializes,
    so they run under plain tier-1 too (the in-process route would need the
    flag on the whole session; the CI ``multidevice`` job provides exactly
    that for tests/test_distributed_multidev.py).

The 8-device assertions are the acceptance bar: BFS depths and coloring
results bit-identical to the 1-device run, PageRank within tolerance,
every task landing on its owner (``mis_routed == 0``), stealing moving work
off a skewed shard without corrupting results, and the psum'd stop
predicate keeping drained devices in the collective until global
completion.
"""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import SchedulerConfig
from repro.graph.generators import grid2d, rmat
from repro.obs import Trace
from repro.runtime import build_program
from repro.shard import (block_bounds, block_size, owner_of,
                         partition_graph, plan_donations, run_sharded,
                         split_seeds)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------------- host math
def test_blocks_partition_the_vertex_space():
    for n, s in [(128, 8), (9, 8), (7, 3), (1, 4), (256, 1)]:
        covered = []
        for d in range(s):
            lo, hi = block_bounds(d, n, s)
            covered.extend(range(lo, hi))
        assert covered == list(range(n)), (n, s)
        v = np.arange(n)
        owners = np.asarray(owner_of(v, n, s))
        for d in range(s):
            lo, hi = block_bounds(d, n, s)
            assert (owners[lo:hi] == d).all()


def test_partition_matches_global_csr():
    g = rmat(6, edge_factor=8, seed=3)
    n = g.num_vertices
    rp = np.asarray(g.row_ptr)
    col = np.asarray(g.col_idx)
    for s in (1, 2, 8):
        for halo in (False, True):
            parts = partition_graph(g, s, halo=halo)
            assert parts.halo == (halo and s > 1)
            assert sum(parts.edges_per_shard) == g.num_edges
            lrp = np.asarray(parts.row_ptr)
            lcol = np.asarray(parts.col_idx)
            for d in range(s):
                rows = list(range(*block_bounds(d, n, s)))
                if parts.halo:
                    rows += list(range(*block_bounds((d - 1) % s, n, s)))
                for v in rows:
                    deg = rp[v + 1] - rp[v]
                    assert lrp[d, v + 1] - lrp[d, v] == deg, (s, halo, d, v)
                    np.testing.assert_array_equal(
                        lcol[d, lrp[d, v]:lrp[d, v] + deg],
                        col[rp[v]:rp[v] + deg])


def test_partition_rejects_bad_shard_count():
    g = rmat(4, edge_factor=4, seed=0)
    with pytest.raises(ValueError, match="num_shards"):
        partition_graph(g, 0)


def test_split_seeds_places_tasks_on_owners():
    n, s = 40, 4
    seeds = np.arange(n, dtype=np.int32)
    buf, counts = split_seeds(seeds, n, s)
    assert int(np.asarray(counts).sum()) == n
    for d in range(s):
        lo, hi = block_bounds(d, n, s)
        got = np.sort(np.asarray(buf[d, :int(counts[d])]))
        np.testing.assert_array_equal(got, np.arange(lo, hi))
    # coloring tasks are ±(v+1): ownership follows the decoded vertex
    ctasks = np.array([1, -1, 11, -11, 40, -40], np.int32)  # v = 0,0,10,10,39,39
    buf, counts = split_seeds(ctasks, n, s,
                              task_vertex=lambda t: jnp.abs(t) - 1)
    assert list(np.asarray(counts)) == [2, 2, 0, 2]


def test_plan_donations_balanced_is_noop():
    give = np.asarray(plan_donations(jnp.asarray([10, 10, 10, 10]),
                                     threshold=0.5, chunk=8))
    assert (give == 0).all()


def test_plan_donations_rebalances_a_skewed_drain():
    """Skewed occupancy converges: a drain with donations finishes sooner.

    Models the driver's dynamics (each shard pops a wavefront per round,
    donations move queue mass one ring hop) on the round level: all work on
    shard 0, stealing must cut rounds-to-drain vs. no stealing.
    """
    s, w, chunk = 8, 16, 16

    def drain_rounds(steal: bool, start=400, max_rounds=200):
        sizes = np.zeros(s, np.int64)
        sizes[0] = start
        rounds = 0
        while sizes.sum() > 0 and rounds < max_rounds:
            if steal:
                give = np.asarray(plan_donations(
                    jnp.asarray(sizes, jnp.int32), 0.5, chunk),
                    dtype=np.int64)
                sizes = sizes - give + np.roll(give, 1)
            sizes = np.maximum(sizes - w, 0)
            rounds += 1
        return rounds

    without = drain_rounds(False)
    with_steal = drain_rounds(True)
    assert with_steal < without, (with_steal, without)


def test_plan_donations_respects_caps():
    sizes = jnp.asarray([100, 0, 0, 0], jnp.int32)
    give = np.asarray(plan_donations(sizes, threshold=0.5, chunk=8))
    assert give[0] <= 8          # chunk cap
    assert (give[1:] == 0).all()  # no surplus elsewhere
    # donation never exceeds the successor's deficit
    sizes = jnp.asarray([100, 24, 0, 0], jnp.int32)
    give = np.asarray(plan_donations(sizes, threshold=0.1, chunk=64))
    mean_ceil = -(-int(np.asarray(sizes).sum()) // 4)
    assert give[0] <= mean_ceil - 24


# -------------------------------------------- 1-shard runs (any device count)
def test_one_shard_run_matches_plain_bfs():
    """num_shards=1 drives the full shard_map/exchange/merge machinery on a
    single-device mesh; distances must equal the plain scheduler's."""
    from repro.algorithms.bfs import bfs_speculative

    g = rmat(6, edge_factor=8, seed=1)
    cfg = SchedulerConfig(num_workers=16, fetch_size=1)
    ref, _ = bfs_speculative(g, 0, cfg)
    program = build_program("bfs", g, cfg, params={"source": 0})
    state, stats = run_sharded(program, g, cfg)
    np.testing.assert_array_equal(np.asarray(state.dist), np.asarray(ref))
    assert stats.mis_routed == 0
    assert stats.exchanged == 0    # one shard: nothing to ship
    assert stats.dropped == 0


def test_one_shard_discrete_driver_traces():
    from repro.algorithms.bfs import bfs_bsp

    g = grid2d(8, 8, seed=0)
    ref, _ = bfs_bsp(g, 0)
    cfg = SchedulerConfig(num_workers=16, fetch_size=1, persistent=False)
    program = build_program("bfs", g, cfg, params={"source": 0})
    trace = Trace()
    state, stats = run_sharded(program, g, cfg, trace=trace)
    np.testing.assert_array_equal(np.asarray(state.dist), np.asarray(ref))
    assert len(trace.records) == stats.rounds
    assert sum(r["pops"] for r in trace.records) == stats.items_processed
    assert all(r["exchanged"] == 0 for r in trace.records)


# --------------------------------------------------- 8-device subprocesses
def _run(body: str, timeout=900) -> dict:
    """Run ``body`` in a subprocess with 8 forced host devices; expect JSON
    on the last stdout line."""
    prog = ("import os\n"
            "os.environ['XLA_FLAGS'] = "
            "'--xla_force_host_platform_device_count=8'\n"
            "os.environ.setdefault('JAX_PLATFORMS', 'cpu')\n"
            + textwrap.dedent(body))
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                         text=True, env=env, timeout=timeout)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_multidevice_parity_and_routing():
    """8 shards: BFS/coloring bit-identical to the 1-device run, PageRank
    within tolerance, every task on its owner, no overflow anywhere."""
    res = _run("""
        import json
        import numpy as np
        from repro.algorithms.bfs import bfs_bsp, bfs_speculative
        from repro.algorithms.coloring import coloring_async, validate_coloring
        from repro.algorithms.pagerank import pagerank_async, pagerank_reference
        from repro.core import SchedulerConfig
        from repro.graph.generators import rmat
        from repro import shard as SH
        from repro.runtime import build_program

        g = rmat(7, edge_factor=8, seed=2)
        n = g.num_vertices
        out = {}

        # BFS: depths are exact shortest hops on any schedule — the sharded
        # result must be bit-identical to both the BSP oracle and the plain
        # 1-device speculative run.
        ref, _ = bfs_bsp(g, 0)
        d1, _ = bfs_speculative(g, 0, SchedulerConfig(num_workers=32))
        bfs_ok, bfs_exchanged, bfs_mis = [], [], []
        for s in (2, 8):
            cfg = SchedulerConfig(num_workers=32, num_shards=s)
            d, info = bfs_speculative(g, 0, cfg)
            bfs_ok.append(bool((np.asarray(d) == np.asarray(ref)).all()
                               and (np.asarray(d) == np.asarray(d1)).all()))
            bfs_exchanged.append(info['exchanged'])
            bfs_mis.append(info['mis_routed'] + info['dropped'])
        out['bfs_ok'] = bfs_ok
        out['bfs_exchanged'] = bfs_exchanged
        out['bfs_mis'] = bfs_mis

        # coloring: the unfused sharded body reads epoch-start colors, so a
        # full-width drain is schedule-identical for every shard count
        W = 2 * n
        colors = {}
        for s in (1, 2, 8):
            cfg = SchedulerConfig(num_workers=W, num_shards=s)
            prog = build_program("coloring", g, cfg)
            st, stats = SH.run_sharded(prog, g, cfg)
            colors[s] = np.asarray(st.colors)
            out['color_mis_%d' % s] = stats.mis_routed + stats.dropped
        out['color_valid'] = bool(validate_coloring(g, colors[8]))
        out['color_identical'] = bool((colors[8] == colors[1]).all()
                                      and (colors[2] == colors[1]).all())

        # the drain reads each device's CSR slice where it runs: one
        # shard of the partition on each of the 8 devices
        from repro.launch.mesh import make_shard_mesh
        placed = SH.place_partition(SH.partition_graph(g, 8, halo=False),
                                    make_shard_mesh(8))
        out['csr_devices'] = sorted(
            s.device.id for s in placed.col_idx.addressable_shards)

        # pagerank: schedule differs across meshes; ranks agree within the
        # eps*deg slack of the residual formulation
        ref_pr = np.asarray(pagerank_reference(g, iters=300))
        cfg = SchedulerConfig(num_workers=16, num_shards=8)
        rank, info = pagerank_async(g, cfg, eps=1e-6)
        out['pr_err'] = float(np.abs(np.asarray(rank) - ref_pr).max())
        out['pr_mis'] = info['mis_routed'] + info['dropped']
        print(json.dumps(out))
    """)
    assert all(res["bfs_ok"]), res
    assert all(m == 0 for m in res["bfs_mis"]), res
    assert res["bfs_exchanged"][1] > 0     # 8 shards really exchanged tasks
    assert res["color_valid"] and res["color_identical"], res
    assert res["color_mis_8"] == 0
    assert res["csr_devices"] == list(range(8)), res
    assert res["pr_err"] < 1e-4, res
    assert res["pr_mis"] == 0


def test_multidevice_steal_and_global_stop():
    """All seeds on shard 0: the psum'd stop predicate must keep the other
    seven (initially empty) shards in the drain until their blocks are
    reached, and stealing must move tasks without breaking ownership."""
    res = _run("""
        import json
        import numpy as np
        from repro.algorithms.bfs import bfs_bsp, bfs_speculative
        from repro.core import SchedulerConfig
        from repro.graph.generators import grid2d

        g = grid2d(16, 16, seed=0)   # vertex 0 sits in shard 0's block
        n = g.num_vertices
        ref, _ = bfs_bsp(g, 0)
        out = {}

        # no stealing: a drained shard may only receive work via routing —
        # if any shard bailed early its whole block would stay INF
        cfg = SchedulerConfig(num_workers=8, num_shards=8)
        d, info = bfs_speculative(g, 0, cfg)
        d = np.asarray(d)
        out['stop_ok'] = bool((d == np.asarray(ref)).all())
        INF = np.int32(0x7FFFFFFF)
        out['all_blocks_reached'] = bool((d < INF).all())
        out['exchanged'] = info['exchanged']

        # stealing on: donations happen, results stay exact, ownership
        # (owner or ring predecessor for stolen tasks) never violated
        cfg_s = SchedulerConfig(num_workers=8, num_shards=8,
                                steal_threshold=0.5, steal_chunk=16)
        ds, si = bfs_speculative(g, 0, cfg_s)
        out['steal_ok'] = bool((np.asarray(ds) == np.asarray(ref)).all())
        out['donated'] = si['donated']
        out['steal_rounds'] = si['steal_rounds']
        out['steal_mis'] = si['mis_routed'] + si['dropped']

        # discrete driver: per-round telemetry, same answer
        cfg_d = SchedulerConfig(num_workers=8, num_shards=8,
                                persistent=False, steal_threshold=0.5,
                                steal_chunk=16)
        from repro.obs import Trace
        trace = Trace()
        dd, di = bfs_speculative(g, 0, cfg_d, trace=trace)
        out['discrete_ok'] = bool((np.asarray(dd) == np.asarray(ref)).all())
        out['discrete_rounds'] = di['rounds']
        # one ring row per device per round
        out['trace_len'] = len({r['round'] for r in trace.records})
        out['trace_rows'] = len(trace.records)
        out['trace_exchanged'] = sum(r['exchanged'] for r in trace.records)
        out['info_exchanged'] = di['exchanged']
        # a mesh of fewer devices than the host has: the rings unstack
        trace2 = Trace()
        _, i2 = bfs_speculative(g, 0, SchedulerConfig(
            num_workers=8, num_shards=2, persistent=False), trace=trace2)
        out['two_shard_rows'] = len(trace2.records)
        out['two_shard_rounds'] = i2['rounds']
        print(json.dumps(out))
    """)
    assert res["stop_ok"] and res["all_blocks_reached"], res
    assert res["exchanged"] > 0
    assert res["steal_ok"], res
    assert res["donated"] > 0 and res["steal_rounds"] > 0, res
    assert res["steal_mis"] == 0, res
    assert res["discrete_ok"], res
    assert res["trace_len"] == res["discrete_rounds"]
    assert res["trace_rows"] == 8 * res["discrete_rounds"]
    assert res["trace_exchanged"] == res["info_exchanged"] > 0
    assert res["two_shard_rows"] == 2 * res["two_shard_rounds"]


def test_multidevice_server_mixes_sharded_and_fused_jobs():
    """TaskServer batch with shards>1 BFS jobs alongside fused tenants."""
    res = _run("""
        import json
        import numpy as np
        from repro.algorithms.bfs import bfs_bsp
        from repro.core import SchedulerConfig
        from repro.graph.generators import grid2d, rmat
        from repro.server import JobRegistry, JobSpec, TaskServer

        reg = JobRegistry()
        reg.register_graph('rmat', rmat(6, edge_factor=8, seed=1))
        reg.register_graph('grid', grid2d(8, 8, seed=0))
        server = TaskServer(reg, num_lanes=4,
                            config=SchedulerConfig(num_workers=16))
        jid_sh = server.submit(JobSpec('bfs', 'rmat', {'source': 3},
                                       shards=8))
        jid_f1 = server.submit(JobSpec('coloring', 'grid'))
        jid_f2 = server.submit(JobSpec('bfs', 'grid', {'source': 0}))
        result = server.run()
        ref, _ = bfs_bsp(reg.graph('rmat'), 3)
        ref2, _ = bfs_bsp(reg.graph('grid'), 0)
        out = {
            'sharded_ok': bool((result.results[jid_sh]
                                == np.asarray(ref)).all()),
            'fused_ok': bool((result.results[jid_f2]
                              == np.asarray(ref2)).all()),
            'sharded_jobs': result.stats.sharded_jobs,
            'sharded_rounds': result.stats.sharded_rounds,
            'fused_rounds': result.stats.rounds,
            'sh_items': result.telemetry[jid_sh].items_processed,
        }
        print(json.dumps(out))
    """)
    assert res["sharded_ok"] and res["fused_ok"], res
    assert res["sharded_jobs"] == 1
    assert res["sharded_rounds"] > 0
    assert res["fused_rounds"] > 0      # fused tenants still ran rounds
    assert res["sh_items"] > 0


def test_owner_coords_factorizes_owner_of():
    """2-D ownership is the linear block owner split row-major: owner_of
    == row * cols + col for every vertex, on both checked mesh layouts."""
    from repro.shard import owner_coords

    n = 97
    vids = jnp.arange(n, dtype=jnp.int32)
    lin = np.asarray(owner_of(vids, n, 8))
    for rows, cols in ((2, 4), (4, 2)):
        r, c = owner_coords(vids, n, rows, cols)
        np.testing.assert_array_equal(np.asarray(r) * cols + np.asarray(c),
                                      lin)
        assert int(np.asarray(r).max()) == rows - 1
        assert int(np.asarray(c).max()) == cols - 1


def test_delivered_width_covers_both_hops():
    """The overlap staging buffer must hold everything one round can
    deliver: S*w on the ring, C*w + R*C*w on a 2-D mesh (hop-1 width w
    per col peer kept locally + hop-2 width C*w per row peer)."""
    from repro.shard import delivered_width

    assert delivered_width(5, 8) == 40
    assert delivered_width(5, 8, (2, 4)) == 4 * 5 + 2 * 4 * 5
    assert delivered_width(5, 8, (4, 2)) == 2 * 5 + 4 * 2 * 5


def test_multidevice_mesh2d_parity_and_per_axis_meters():
    """2-D ('row','col') meshes, strict delivery: BFS and coloring are
    bit-identical to the 1-device run on both 2x4 and 4x2 layouts,
    PageRank agrees within the residual formulation's slack, every task
    lands on its owner, and the exchange meters split by axis."""
    res = _run("""
        import json
        import numpy as np
        from repro.algorithms.coloring import validate_coloring
        from repro.algorithms.pagerank import pagerank_reference
        from repro.core import SchedulerConfig
        from repro.graph.generators import rmat
        from repro.runtime import build_program, execute

        g = rmat(7, edge_factor=8, seed=2)
        n = g.num_vertices
        out = {}

        ref_bfs = np.asarray(execute(
            build_program("bfs", g, SchedulerConfig(num_workers=32),
                          params={"source": 0}),
            g, SchedulerConfig(num_workers=32)).state.dist)
        cfg_c1 = SchedulerConfig(num_workers=2 * n)
        ref_col = np.asarray(execute(
            build_program("coloring", g, cfg_c1), g, cfg_c1).state.colors)
        ref_pr = np.asarray(pagerank_reference(g, iters=300))

        for mesh in ((2, 4), (4, 2)):
            tag = "%dx%d" % mesh
            cfg = SchedulerConfig(num_workers=32, num_shards=8,
                                  mesh_shape=mesh)
            r = execute(build_program("bfs", g, cfg, params={"source": 0}),
                        g, cfg)
            info = r.info
            out["bfs_ok_" + tag] = bool(
                (np.asarray(r.state.dist) == ref_bfs).all())
            out["mis_" + tag] = info["mis_routed"]
            out["row_" + tag] = info["exchanged_row"]
            out["col_" + tag] = info["exchanged_col"]
            out["exch_" + tag] = info["exchanged"]
            out["pay_" + tag] = info["payload_ints"]
            out["pad_" + tag] = info["padding_ints"]

            cfg_c = SchedulerConfig(num_workers=2 * n, num_shards=8,
                                    mesh_shape=mesh)
            rc = execute(build_program("coloring", g, cfg_c), g, cfg_c)
            out["col_ok_" + tag] = bool(
                (np.asarray(rc.state.colors) == ref_col).all()
                and validate_coloring(g, np.asarray(rc.state.colors)))

        cfg_pr = SchedulerConfig(num_workers=16, num_shards=8,
                                 mesh_shape=(2, 4))
        rp = execute(build_program("pagerank", g, cfg_pr,
                                   params={"eps": 1e-6}), g, cfg_pr)
        out["pr_err"] = float(
            np.abs(np.asarray(rp.state.rank) - ref_pr).max())
        print(json.dumps(out))
    """)
    for tag in ("2x4", "4x2"):
        assert res["bfs_ok_" + tag], res
        assert res["col_ok_" + tag], res
        assert res["mis_" + tag] == 0, res
        # the exchange really decomposed into two per-axis hops, and the
        # padding meter accounts for everything the payload doesn't
        assert res["row_" + tag] > 0 and res["col_" + tag] > 0, res
        assert res["pay_" + tag] > 0 and res["pad_" + tag] > 0, res
    # axis split depends on layout: more col-peers in 2x4, more row-peers
    # in 4x2 — both decompositions route the same distinct tasks
    assert res["col_2x4"] > res["row_2x4"], res
    assert res["row_4x2"] > res["col_4x2"], res
    assert res["pr_err"] < 1e-4, res


def test_multidevice_mesh2d_overlap_and_compression():
    """One-round-deferred delivery and the wire codec, separately and
    together, on both 2-D layouts: BFS stays bit-identical, overlap really
    stages deliveries (deferred > 0 on overlap rounds), and compression
    meters strictly fewer wire ints than the raw payload."""
    res = _run("""
        import json
        import numpy as np
        from repro.core import SchedulerConfig
        from repro.graph.generators import rmat
        from repro.runtime import build_program, execute

        g = rmat(7, edge_factor=8, seed=2)
        ref = np.asarray(execute(
            build_program("bfs", g, SchedulerConfig(num_workers=32),
                          params={"source": 0}),
            g, SchedulerConfig(num_workers=32)).state.dist)

        out = []
        for mesh in ((2, 4), (4, 2)):
            for defer in (0, 1):
                for comp in (False, True):
                    cfg = SchedulerConfig(num_workers=32, num_shards=8,
                                          mesh_shape=mesh,
                                          defer_rounds=defer, compress=comp)
                    r = execute(build_program("bfs", g, cfg,
                                              params={"source": 0}), g, cfg)
                    info = r.info
                    out.append({
                        "mesh": list(mesh), "defer": defer, "comp": comp,
                        "ok": bool((np.asarray(r.state.dist) == ref).all()),
                        "mis": info["mis_routed"],
                        "payload": info["payload_ints"],
                        "wire": info["wire_ints"],
                        "deferred": info["deferred"],
                        "overlap": info["overlap_rounds"]})
        print(json.dumps(out))
    """)
    for row in res:
        assert row["ok"] and row["mis"] == 0, row
        if row["comp"]:
            assert 0 < row["wire"] < row["payload"], row
        else:
            assert row["wire"] > row["payload"], row   # raw slots incl. padding
        if row["defer"]:
            assert row["deferred"] > 0 and row["overlap"] > 0, row
        else:
            assert row["deferred"] == 0 and row["overlap"] == 0, row
