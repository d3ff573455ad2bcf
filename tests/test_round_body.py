"""Round bodies read wavefront tables onto the work budget's lanes by
broadcast, never by a budget-length gather, and compute what the gather
formulation computed, bit for bit.

The census counts, in the round body's HLO before optimisation, the
gathers whose operand is wavefront-long and whose result is budget-long:
the owner-indexed reads ``table[ex.owner]`` that
``core.frontier.owner_broadcast`` replaces.  The gather formulation of the
BFS and coloring bodies (and of the merge-path expansion under them) is
kept below as the reference of the parity test.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.algorithms import bfs, coloring
from repro.algorithms.coloring import _first_free_color, _loses
from repro.core import ChunkCodec, chunk_degrees, coalesce_chunks
from repro.core.frontier import (Expansion, chunk_row_of, gather_neighbors,
                                 owner_of_units)
from repro.core.task import flatten_chunks
from repro.graph import rmat

INF = bfs.INF


# ------------------------------------------------------------- census
W, BUDGET = 32, 256
_DEF = re.compile(r"^\s*(?:ROOT )?([\w.\-]+) = \w+\[([\d,]*)\]\S* "
                  r"([\w\-]+)\(([^)]*)\)", re.M)


def owner_gathers(hlo: str, w: int, budget: int) -> list:
    """The gathers of an HLO module whose operand has shape ``[w]`` and
    whose result has shape ``[budget]``."""
    defs = list(_DEF.finditer(hlo))
    shape = {m.group(1): m.group(2) for m in defs}
    return [m.group(0) for m in defs
            if m.group(3) == "gather" and m.group(2) == str(budget)
            and shape.get(m.group(4).split(",")[0].strip()) == str(w)]


@pytest.fixture(scope="module")
def census_graph():
    return rmat(9, 8, seed=3)


def _body(algo, g, backend, budget=BUDGET, codec=None):
    if algo == "bfs":
        return (bfs.make_wavefront_fn(g, "merge_path", budget,
                                      int(jnp.max(g.degrees())),
                                      backend=backend, codec=codec),
                bfs.init_state(g, 0))
    return (coloring.make_wavefront_fn(g, budget, backend=backend,
                                       codec=codec),
            coloring.init_state(g)[0])


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
@pytest.mark.parametrize("algo", ["bfs", "coloring"])
def test_round_body_has_no_owner_gather(census_graph, algo, backend):
    """0 owner-indexed budget-length gathers (the gather formulation had
    3 in BFS and 7 in coloring); the census itself finds them in the
    reference body below."""
    f, state = _body(algo, census_graph, backend)
    items = jnp.arange(1, W + 1, dtype=jnp.int32)
    valid = jnp.ones(W, bool)
    hlo = jax.jit(f).lower(items, valid, state).as_text(dialect="hlo")
    assert owner_gathers(hlo, W, BUDGET) == []
    ref = REFERENCE[algo](census_graph, BUDGET, ChunkCodec(1))
    hlo = jax.jit(ref).lower(items, valid, state).as_text(dialect="hlo")
    assert len(owner_gathers(hlo, W, BUDGET)) == {"bfs": 3,
                                                  "coloring": 7}[algo]


# ------------------------------------------- the gather formulation
def _gather_expand(items, valid, row_ptr, col_idx, budget, widths,
                   max_width):
    """Merge-path expansion with every per-unit read a gather at
    ``owner``."""
    safe = jnp.where(valid, items, 0)
    deg = chunk_degrees(items, widths, valid, row_ptr)
    scan = jnp.cumsum(deg)
    total = scan[-1]
    k = jnp.arange(budget, dtype=jnp.int32)
    owner = jnp.clip(owner_of_units(scan, budget), 0, items.shape[0] - 1)
    excl = scan - deg
    rank = k - excl[owner]
    src = chunk_row_of(row_ptr, safe[owner], rank, widths[owner], max_width)
    edge = (row_ptr[safe] - excl)[owner] + k
    nbr = gather_neighbors(row_ptr, col_idx, src, edge)
    in_range = k < total
    return Expansion(src=jnp.where(in_range, src, 0),
                     nbr=jnp.where(in_range, nbr, 0),
                     owner=jnp.where(in_range, owner, 0), valid=in_range,
                     total=total, excl=excl)


def _gather_bfs(graph, budget, codec):
    g = codec.granularity
    rp = graph.row_ptr

    def f(items, valid, state):
        safe = jnp.where(valid, items, 0)
        heads, widths = codec.decode(safe)
        ex = _gather_expand(heads, valid, rp, graph.col_idx, budget, widths,
                            g)
        deg = chunk_degrees(heads, widths, valid, rp)
        excl = jnp.cumsum(deg) - deg
        truncated = valid & (excl + deg > budget)
        live = ex.valid & ~truncated[ex.owner]
        src_dist = state.dist[heads][ex.owner] if g == 1 else \
            state.dist[ex.src]
        cand = jnp.where(live, src_dist + 1, INF)
        before = state.dist[ex.nbr]
        new_dist = state.dist.at[jnp.where(live, ex.nbr, 0)].min(
            jnp.where(live, cand, INF), mode="drop")
        improved = live & (cand < before)
        n = state.dist.shape[0]
        lanes = jnp.arange(budget, dtype=jnp.int32)
        first_lane = jnp.full((n,), budget, jnp.int32).at[
            jnp.where(improved, ex.nbr, n)].min(
            jnp.where(improved, lanes, budget), mode="drop")
        improved &= first_lane[ex.nbr] == lanes
        counter = state.counter.add(jnp.sum(jnp.where(
            valid & ~truncated, widths, 0))).add_units(ex.total, budget)
        out_new, new_mask, n_splits = coalesce_chunks(ex.nbr, improved,
                                                      codec, rp)
        counter = counter.add_splits(n_splits)
        return (jnp.concatenate([out_new, jnp.where(truncated, items, 0)]),
                jnp.concatenate([new_mask, truncated]),
                bfs.BFSState(dist=new_dist, counter=counter))

    return f


def _gather_coloring(graph, budget, codec):
    g = codec.granularity
    n = graph.num_vertices
    max_colors = int(jnp.max(graph.degrees())) + 1
    rp = graph.row_ptr

    def f(items, valid, state):
        is_assign = valid & (items > 0)
        is_detect = valid & (items < 0)
        codes = jnp.where(valid, jnp.where(is_assign, items - 1,
                                           -items - 1), 0)
        heads, widths = codec.decode(codes)
        deg = chunk_degrees(heads, widths, valid, rp)
        excl = jnp.cumsum(deg) - deg
        truncated = valid & (excl + deg > budget)
        ex = _gather_expand(heads, valid, rp, graph.col_idx, budget, widths,
                            g)
        live = ex.valid & ~truncated[ex.owner]
        vids, flat_valid, owner = flatten_chunks(heads, widths, valid, g)
        flat_run = flat_valid & ~truncated[owner]
        flat_assign = flat_run & is_assign[owner]
        flat_detect = flat_run & is_detect[owner]
        lane = ex.owner * g + (ex.src - heads[ex.owner])
        num_lanes = vids.shape[0]
        pick, wide = _first_free_color(
            lane, state.colors[ex.nbr], live & is_assign[ex.owner],
            num_lanes, max_colors)
        colors = state.colors.at[jnp.where(flat_assign, vids, n)].set(
            jnp.where(flat_assign, pick, 0), mode="drop")
        my = colors[heads][ex.owner] if g == 1 else colors[ex.src]
        clash = (live & is_detect[ex.owner] & (my >= 0)
                 & (colors[ex.nbr] == my) & _loses(ex.src, ex.nbr))
        bad = jnp.zeros((num_lanes,), jnp.bool_).at[
            jnp.where(clash, lane, num_lanes)].set(True, mode="drop")
        bad &= flat_detect
        counter = state.counter.add(jnp.sum(flat_assign.astype(
            jnp.int32))).add_units(ex.total, budget)
        re_assign, re_mask, n_splits = coalesce_chunks(vids, bad, codec, rp)
        done_assign = is_assign & ~truncated
        out = jnp.concatenate([jnp.where(done_assign, -(codes + 1), 0),
                               jnp.where(re_mask, re_assign + 1, 0),
                               jnp.where(truncated, items, 0)])
        mask = jnp.concatenate([done_assign, re_mask, truncated])
        return out, mask, coloring.ColorState(
            colors=colors, counter=counter.add_splits(n_splits),
            wide_picks=state.wide_picks + wide.astype(jnp.int32))

    return f


REFERENCE = {"bfs": _gather_bfs, "coloring": _gather_coloring}


# ------------------------------------------------------------- parity
PARITY_W, PARITY_ROUNDS = 48, 6


def _first_wavefront(algo, g, codec, hub, rng):
    """A wavefront that leads with the hub (so later tasks spill past the
    budget) and mixes chunk widths, invalid slots and, for coloring,
    assign and detect tasks."""
    n = g.num_vertices
    heads = rng.choice(n - codec.granularity, PARITY_W, replace=False)
    heads[0] = min(hub, n - codec.granularity)
    widths = rng.integers(1, codec.granularity + 1, PARITY_W)
    widths[0] = 1
    codes = np.asarray(codec.encode(heads, widths))
    if algo == "coloring":
        codes = np.where(rng.random(PARITY_W) < 0.6, codes + 1, -codes - 1)
    valid = rng.random(PARITY_W) < 0.9
    valid[0] = True
    return np.where(valid, codes, 0).astype(np.int32), valid


def _first_state(algo, g, rng):
    n = g.num_vertices
    if algo == "bfs":
        dist = np.where(rng.random(n) < 0.5, rng.integers(0, 6, n), INF)
        return bfs.BFSState(dist=jnp.asarray(dist, jnp.int32),
                            counter=bfs.WorkCounter.zero())
    colors = np.where(rng.random(n) < 0.5, rng.integers(0, 4, n), -1)
    state, _ = coloring.init_state(g)
    return coloring.ColorState(colors=jnp.asarray(colors, jnp.int32),
                               counter=state.counter)


@pytest.fixture(scope="module")
def hub_graph():
    g = rmat(10, 8, seed=11)
    deg = np.asarray(g.degrees())
    return g, int(deg.argmax()), int(deg.max())


@pytest.mark.parametrize("granularity", [1, 4])
@pytest.mark.parametrize("backend", ["jnp", "pallas"])
@pytest.mark.parametrize("algo", ["bfs", "coloring"])
def test_round_body_matches_gather_formulation(hub_graph, algo, backend,
                                               granularity):
    """Rounds driven from a truncating wavefront: outputs and states of
    the broadcast body equal the gather formulation's, bit for bit."""
    g, hub, max_degree = hub_graph
    codec = ChunkCodec(granularity)
    # the hub alone nearly fills the budget: the tasks after it spill
    budget = max_degree + 16
    new, _ = _body(algo, g, backend, budget=budget, codec=codec)
    new = jax.jit(new)
    ref = jax.jit(REFERENCE[algo](g, budget, codec))
    rng = np.random.default_rng(granularity)
    items, valid = _first_wavefront(algo, g, codec, hub, rng)
    state = _first_state(algo, g, rng)
    truncated_rounds = 0
    for _ in range(PARITY_ROUNDS):
        got = new(jnp.asarray(items), jnp.asarray(valid), state)
        want = ref(jnp.asarray(items), jnp.asarray(valid), state)
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        out, mask, state = got
        out, mask = np.asarray(out), np.asarray(mask)
        truncated_rounds += int(mask[-PARITY_W:].any())
        nxt = out[mask][:PARITY_W]
        items = np.zeros(PARITY_W, np.int32)
        items[:nxt.shape[0]] = nxt
        valid = np.arange(PARITY_W) < nxt.shape[0]
        if not valid.any():
            break
    assert truncated_rounds > 0
