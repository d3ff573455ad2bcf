"""Case-study correctness + the paper's quantitative claims at test scale."""
import jax.numpy as jnp
import networkx as nx
import numpy as np
import pytest

from repro.algorithms.bfs import bfs_bsp, bfs_speculative
from repro.algorithms.coloring import coloring_async, coloring_bsp, \
    validate_coloring
from repro.algorithms.pagerank import pagerank_async, pagerank_bsp, \
    pagerank_reference
from repro.core import SchedulerConfig
from repro.graph import from_edges, grid2d, permute_vertices, rmat


def _nx_dists(g, source):
    G = nx.Graph()
    G.add_nodes_from(range(g.num_vertices))
    rp, ci = np.asarray(g.row_ptr), np.asarray(g.col_idx)
    for v in range(g.num_vertices):
        for e in range(rp[v], rp[v + 1]):
            G.add_edge(v, int(ci[e]))
    ref = np.full(g.num_vertices, 0x7FFFFFFF, np.int64)
    for k, d in nx.single_source_shortest_path_length(G, source).items():
        ref[k] = d
    return ref


GRAPHS = {
    "scale_free": rmat(8, 8, seed=1),
    "mesh_like": grid2d(20, 20),
}

# the kernel-backend axis (DESIGN.md section 9): "pallas" runs the real
# Pallas kernels in interpret mode on CPU, so every correctness test below
# doubles as a backend-parity oracle.
BACKENDS = ("jnp", "pallas")


@pytest.mark.parametrize("gname", list(GRAPHS))
def test_bfs_bsp_correct(gname):
    g = GRAPHS[gname]
    dist, info = bfs_bsp(g, 0)
    np.testing.assert_array_equal(np.asarray(dist, np.int64), _nx_dists(g, 0))
    assert info["work"] > 0


@pytest.mark.parametrize("gname", list(GRAPHS))
@pytest.mark.parametrize("strategy", ["merge_path", "per_item"])
@pytest.mark.parametrize("persistent", [True, False])
@pytest.mark.parametrize("backend", BACKENDS)
def test_bfs_speculative_correct(gname, strategy, persistent, backend):
    g = GRAPHS[gname]
    cfg = SchedulerConfig(num_workers=8, fetch_size=4, persistent=persistent,
                          max_rounds=100000, backend=backend)
    dist, info = bfs_speculative(g, 0, cfg, strategy=strategy)
    np.testing.assert_array_equal(np.asarray(dist, np.int64), _nx_dists(g, 0))
    assert info["dropped"] == 0
    # overwork is bounded (paper: small constant factor over n)
    reached = int((_nx_dists(g, 0) < 0x7FFFFFFF).sum())
    assert info["work"] >= reached - 1
    assert info["work"] <= 4 * reached


def test_bfs_small_budget_still_correct():
    g = GRAPHS["scale_free"]
    cfg = SchedulerConfig(num_workers=4, fetch_size=2, max_rounds=100000)
    dist, info = bfs_speculative(g, 0, cfg, strategy="merge_path",
                                 work_budget=8)  # heavy truncation
    np.testing.assert_array_equal(np.asarray(dist, np.int64), _nx_dists(g, 0))


@pytest.mark.parametrize("gname", list(GRAPHS))
@pytest.mark.parametrize("backend", BACKENDS)
def test_pagerank_matches_power_iteration(gname, backend):
    g = GRAPHS[gname]
    ref = pagerank_reference(g, iters=300)
    r_bsp, _ = pagerank_bsp(g, eps=1e-7)
    cfg = SchedulerConfig(num_workers=8, fetch_size=4, max_rounds=100000,
                          backend=backend)
    r_async, info = pagerank_async(g, cfg, eps=1e-7)
    assert float(jnp.max(jnp.abs(r_bsp - ref))) < 1e-3
    assert float(jnp.max(jnp.abs(r_async - ref))) < 1e-3
    assert info["max_residue"] <= 1e-7


def test_pagerank_small_explicit_budget_still_converges():
    """An explicit work_budget below max_degree must be clamped up (the
    progress-guarantee floor): otherwise a hub row is truncated and
    re-queued forever, its residue never harvested, and the drain spins to
    max_rounds."""
    g = GRAPHS["scale_free"]
    cfg = SchedulerConfig(num_workers=4, fetch_size=2, max_rounds=100000)
    rank, info = pagerank_async(g, cfg, eps=1e-5, work_budget=1)
    assert info["rounds"] < 100000
    assert info["max_residue"] <= 1e-5
    ref = pagerank_reference(g, iters=300)
    assert float(jnp.max(jnp.abs(rank - ref))) < 1e-3


def test_pagerank_async_does_less_work_on_scale_free():
    """Paper Table 4: async PageRank workload ratio < 1 vs BSP."""
    g = GRAPHS["scale_free"]
    _, info_bsp = pagerank_bsp(g, eps=1e-6)
    cfg = SchedulerConfig(num_workers=8, fetch_size=4, max_rounds=100000)
    _, info_async = pagerank_async(g, cfg, eps=1e-6)
    assert info_async["work"] < info_bsp["work"]


@pytest.mark.parametrize("gname", list(GRAPHS))
def test_coloring_bsp_valid(gname):
    g = GRAPHS[gname]
    colors, info = coloring_bsp(g)
    assert validate_coloring(g, colors)
    assert int(jnp.max(colors)) + 1 <= int(jnp.max(g.degrees())) + 1


@pytest.mark.parametrize("gname", list(GRAPHS))
@pytest.mark.parametrize("persistent", [True, False])
@pytest.mark.parametrize("backend", BACKENDS)
def test_coloring_async_valid(gname, persistent, backend):
    g = GRAPHS[gname]
    cfg = SchedulerConfig(num_workers=8, fetch_size=4, persistent=persistent,
                          max_rounds=100000, backend=backend)
    colors, info = coloring_async(g, cfg)
    assert validate_coloring(g, colors)
    assert info["dropped"] == 0


def test_coloring_truncated_hub_wavefront_stays_valid():
    """Two hubs adjacent to every other vertex share the first wavefront
    and overflow its merge-path budget (which is floored at one hub's
    degree): the spilled hub re-queues whole, and the coloring stays
    proper and bit-identical across backends."""
    n = 200
    rest = np.arange(2, n)
    g = from_edges(n, np.repeat([0, 1], n - 2), np.tile(rest, 2),
                   symmetrize=True)
    out = {}
    for backend in BACKENDS:
        cfg = SchedulerConfig(num_workers=2, max_rounds=100000,
                              backend=backend)
        colors, info = coloring_async(g, cfg)
        assert validate_coloring(g, colors), backend
        assert info["dropped"] == 0
        out[backend] = (np.asarray(colors), info["rounds"])
    np.testing.assert_array_equal(out["jnp"][0], out["pallas"][0])
    assert out["jnp"][1] == out["pallas"][1]


def test_coloring_small_explicit_budget_stays_valid():
    """An explicit coloring work_budget below max_degree is floored at it
    (progress guarantee); the heavily truncated drain still colors
    properly and agrees across backends."""
    from repro.runtime import build_program, execute

    g = GRAPHS["scale_free"]
    out = {}
    for backend in BACKENDS:
        cfg = SchedulerConfig(num_workers=4, fetch_size=2,
                              max_rounds=100000, backend=backend)
        res = execute(build_program("coloring", g, cfg, {"work_budget": 1}),
                      g, cfg)
        assert validate_coloring(g, res.state.colors), backend
        assert res.info["rounds"] < 100000 and res.info["dropped"] == 0
        out[backend] = np.asarray(res.state.colors)
    np.testing.assert_array_equal(out["jnp"], out["pallas"])


@pytest.mark.parametrize("first_colors", [2, 1024])
def test_first_free_color_matches_mex(monkeypatch, first_colors):
    """The narrow forbidden table (and its full-palette fallback when a
    lane has every narrow color taken) gives each lane the smallest color
    on none of its live edges."""
    import repro.algorithms.coloring as C

    monkeypatch.setattr(C, "FIRST_COLORS", first_colors)
    rng = np.random.default_rng(7)
    lanes, edges, max_colors = 16, 400, 40
    lane = rng.integers(0, lanes, edges).astype(np.int32)
    nbr_colors = rng.integers(-1, 12, edges).astype(np.int32)
    live = rng.random(edges) < 0.8
    got = np.asarray(C._first_free_color(
        jnp.asarray(lane), jnp.asarray(nbr_colors), jnp.asarray(live),
        lanes, max_colors))
    for i in range(lanes):
        taken = set(nbr_colors[(lane == i) & live & (nbr_colors >= 0)])
        assert got[i] == min(set(range(max_colors)) - taken), i


def test_coloring_async_less_overwork_than_bsp():
    """Paper section 6.4: relaxed coloring reduces overwork vs BSP."""
    g = GRAPHS["scale_free"]
    _, bsp = coloring_bsp(g)
    cfg = SchedulerConfig(num_workers=8, fetch_size=4, max_rounds=100000)
    _, asy = coloring_async(g, cfg)
    assert asy["work"] < bsp["work"]


# ------------------------------------------------- backend parity oracle
# Beyond "both backends are correct": the backends must agree *bit for bit*
# — same results, same rounds, same work — so the autotuner may switch
# between them on wall time alone (DESIGN.md section 9).
@pytest.mark.parametrize("gname", list(GRAPHS))
def test_backends_bit_identical(gname):
    g = GRAPHS[gname]
    def cfg(backend):
        return SchedulerConfig(num_workers=8, fetch_size=4,
                               max_rounds=100000, backend=backend)

    d_j, i_j = bfs_speculative(g, 0, cfg("jnp"), strategy="merge_path")
    d_p, i_p = bfs_speculative(g, 0, cfg("pallas"), strategy="merge_path")
    np.testing.assert_array_equal(np.asarray(d_j), np.asarray(d_p))
    assert i_j == i_p

    r_j, pi_j = pagerank_async(g, cfg("jnp"), eps=1e-6)
    r_p, pi_p = pagerank_async(g, cfg("pallas"), eps=1e-6)
    np.testing.assert_array_equal(np.asarray(r_j), np.asarray(r_p))
    assert pi_j == pi_p

    c_j, ci_j = coloring_async(g, cfg("jnp"))
    c_p, ci_p = coloring_async(g, cfg("pallas"))
    np.testing.assert_array_equal(np.asarray(c_j), np.asarray(c_p))
    assert ci_j == ci_p


def test_coloring_permutation_reduces_overwork():
    """Paper section 6.4: random ID permutation cuts conflicts sharply."""
    g = grid2d(24, 24)
    perm = np.random.default_rng(0).permutation(g.num_vertices).astype(np.int32)
    gp = permute_vertices(g, perm)
    cfg = SchedulerConfig(num_workers=16, fetch_size=8, max_rounds=100000)
    _, sorted_info = coloring_async(g, cfg)
    _, permuted_info = coloring_async(gp, cfg)
    assert permuted_info["work"] < sorted_info["work"]
