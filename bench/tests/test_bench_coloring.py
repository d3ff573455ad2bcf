"""The coloring cell end to end on the CPU at a small size; its check
against planted faults and against the program with its conflict
detection or its assigns broken; the program against the sequential
greedy where no two assigns share a round; the reference against
networkx's greedy; the cell's graph against graph500-kron20's."""
import json

import jax
import jax.numpy as jnp
import networkx as nx
import numpy as np
import pytest

from bench import run
from bench.graphs import kronecker
from bench.jobs import coloring as job
from bench.reference import coloring as ref

#: configuration keys overridden for CPU runs (as ``conftest.SMALL``): a
#: wavefront of 1/64 of the vertices, so that speculation recolors a few %
#: of them as at the cell's size (1/256), not a third as at 64 of 512
SMALL = {"scale": 11}
SMALL_SCHEDULER = {"num_workers": 32, "work_budget": 1024, "max_rounds": 1024}
SEED = 2**31 + 77


@pytest.fixture
def coloring_cell(small_cell):
    """``make(**scheduler)``: kron20.coloring at a CPU size (``small_cell``
    lets the harness run on the CPU), scheduler keys overridden."""
    def make(**scheduler):
        cell = run.load_cell("kron20.coloring")
        cell.config.update(SMALL)
        cell.config["scheduler"] = dict(cell.config["scheduler"],
                                        **dict(SMALL_SCHEDULER, **scheduler))
        return cell
    return make


def _graph(scale=9, seed=SEED):
    """A kron20.coloring graph at ``scale``: (generated, row_ptr, col_idx
    cut to the edges)."""
    config = json.loads((run.ROOT / "bench/configs/"
                         "graph500-kron20-coloring.json").read_text())
    made = kronecker.generate(dict(config, scale=scale), seed)
    return made, np.asarray(made["row_ptr"]), np.asarray(
        made["col_idx"])[:made["m"]]


@pytest.mark.parametrize("trace", [False, True])
def test_cell_runs_and_is_correct(coloring_cell, trace):
    result = run.run(coloring_cell(), SEED, 1.0, trace)
    json.dumps(result)
    assert result["correct"] is True
    assert result["attempted"] >= 1 and result["failed"] == 0
    numbers = {k: v["value"] for k, v in result["check"].items()}
    assert 0 < numbers.pop("recolor_share") <= job.LIMITS["recolor_share"]
    assert numbers == dict.fromkeys(set(job.LIMITS) - {"recolor_share"}, 0)
    metrics = result["metrics"]
    if trace:
        # the device-trace readings need a TPU plane and are left out here
        assert {"rounds_per_job", "wavefront_fill", "overwork"} <= set(metrics)
        assert 0 < metrics["wavefront_fill"]["value"] <= 100
        assert metrics["overwork"]["value"] > 1  # speculation recolors some
    else:
        assert set(metrics) == {"evps", "setup_s"}


def test_wavefront_one_is_sequential_first_fit(coloring_cell):
    """One task a round: every assign sees every earlier commit, so the
    program's colors are the sequential greedy's in vertex order, bit for
    bit, on the graph with its spare CSR capacity."""
    from repro.graph.csr import CSRGraph

    made, rp, ci = _graph()
    cell = coloring_cell(num_workers=1, max_rounds=4096)
    jobs = job.Jobs(CSRGraph(row_ptr=made["row_ptr"],
                             col_idx=made["col_idx"]), cell.config["scheduler"])
    out = jobs.run(0, run.span)
    assert out["dropped"] == 0 and out["rounds"] < 4096
    assert out["work"] == rp.shape[0] - 1       # no recolors
    np.testing.assert_array_equal(np.asarray(out["dist"]),
                                  ref.first_fit(rp, ci))


def _conflict(colors, rp, ci):
    u = int(np.argmax(np.diff(rp) > 0))
    colors[ci[rp[u]]] = colors[u]


def _uncolored(colors, rp, ci):
    colors[int(np.argmax(np.diff(rp)))] = -1


def _above_degree(colors, rp, ci):
    v = int(np.argmax(np.diff(rp) > 0))
    colors[v] = max(int(colors.max()), int(rp[v + 1] - rp[v])) + 1


@pytest.mark.parametrize("fault,number", [
    (_conflict, "conflict_edges"), (_uncolored, "uncolored"),
    (_above_degree, "colors_above_degree"), (None, "recolor_share")])
def test_check_reads_each_planted_fault(fault, number):
    """Each fault moves its own number past its limit and no other; the
    last is a proper first-fit coloring reached after twice the limit's
    share of the vertices were assigned again."""
    _, rp, ci = _graph()
    n = rp.shape[0] - 1
    good = ref.first_fit(rp, ci)
    bad = good.copy()
    recolors = 0
    if fault is None:
        recolors = int(2 * n * job.LIMITS["recolor_share"] / 100)
    else:
        fault(bad, rp, ci)
    jobs = [{"dist": colors, "rounds": 9, "dropped": 0, "work": n + extra,
             "wide_picks": 0} for colors, extra in ((good, 0), (bad, recolors))]
    numbers = job.check(jobs, rp, ci, max_rounds=256)
    assert numbers[number] > job.LIMITS[number]
    assert {k: numbers[k] for k in job.LIMITS if k != number} == dict.fromkeys(
        set(job.LIMITS) - {number}, 0)
    assert [job.failed(j, 256) for j in jobs] == [False, True]
    assert jobs[1]["mismatches"] == (numbers[number] if fault else 0)


def test_detect_broken_is_not_correct(coloring_cell, monkeypatch):
    """No assign ever yields to a neighbour: the conflicts of assigns that
    shared a round (a wavefront of 32 on 2048 vertices) stay."""
    import repro.algorithms.coloring as C

    monkeypatch.setattr(C, "_loses", lambda v, u: jnp.zeros(
        jnp.broadcast_shapes(v.shape, u.shape), bool))
    jax.clear_caches()
    result = run.run(coloring_cell(), SEED, 1.0, False)
    assert result["correct"] is False
    assert result["check"]["conflict_edges"]["value"] > 0
    assert result["failed"] >= 1


def test_assigns_blind_to_half_their_lanes_are_not_correct(coloring_cell,
                                                          monkeypatch):
    """Assigns in odd lanes see none of their neighbours' colors: their
    picks clash with settled neighbours, and the losers are assigned again
    and again.  The run reads not correct, by ``recolor_share``."""
    import repro.algorithms.coloring as C

    real = C._first_free_color

    def half_blind(lane, nbr_colors, live, num_lanes, max_colors):
        return real(lane, nbr_colors, live & (lane % 2 == 0), num_lanes,
                    max_colors)

    monkeypatch.setattr(C, "_first_free_color", half_blind)
    jax.clear_caches()
    result = run.run(coloring_cell(), SEED, 1.0, False)
    assert result["correct"] is False
    share = result["check"]["recolor_share"]
    assert share["value"] > 4 * share["limit"]
    assert result["failed"] >= 1


def test_config_graph_is_graph500_kron20s():
    """The coloring configuration colors graph500-kron20's graph: every
    key that makes the graph or drives the scheduler is the same."""
    def config(name):
        return json.loads((run.ROOT / f"bench/configs/{name}.json").read_text())

    bfs, coloring = config("graph500-kron20"), config(
        "graph500-kron20-coloring")
    for key in ("family", "scale", "edgefactor", "A", "B", "C", "scheduler"):
        assert coloring[key] == bfs[key], key


@pytest.mark.parametrize("seed", [3, 2**32 + 5])
def test_first_fit_matches_networkx(seed):
    _, rp, ci = _graph(scale=8, seed=seed)
    n = rp.shape[0] - 1
    graph = nx.Graph()
    graph.add_nodes_from(range(n))
    graph.add_edges_from(zip(np.repeat(np.arange(n), np.diff(rp)), ci))
    want = nx.greedy_color(graph, strategy=lambda g, colors: range(n))
    colors = ref.first_fit(rp, ci)
    np.testing.assert_array_equal(colors, [want[v] for v in range(n)])
    assert ref.conflict_edges(rp, ci, colors) == 0
    assert ref.colors_above_degree(rp, colors) == 0
