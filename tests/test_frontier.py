"""Expansion strategies: merge-path LBS vs per-item produce the same work."""
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core import expand_merge_path, expand_per_item
from repro.graph import erdos, rmat


def _edge_set(ex):
    return sorted(
        (int(s), int(n))
        for s, n, v in zip(np.asarray(ex.src), np.asarray(ex.nbr),
                           np.asarray(ex.valid)) if v)


@pytest.mark.parametrize("gen,seed", [(rmat, 0), (erdos, 1)])
def test_strategies_agree(gen, seed):
    g = rmat(6, 4, seed=seed) if gen is rmat else erdos(64, 256, seed=seed)
    items = jnp.array([0, 5, 9, 13, 21, 33], dtype=jnp.int32)
    valid = jnp.array([True, True, False, True, True, True])
    max_deg = int(jnp.max(g.degrees()))
    ex_mp = expand_merge_path(items, valid, g.row_ptr, g.col_idx,
                              work_budget=6 * max_deg)
    ex_pi = expand_per_item(items, valid, g.row_ptr, g.col_idx,
                            max_degree=max_deg)
    assert _edge_set(ex_mp) == _edge_set(ex_pi)
    assert int(ex_mp.total) == int(ex_pi.total)


def test_merge_path_truncates_at_budget():
    g = rmat(6, 4, seed=0)
    items = jnp.arange(16, dtype=jnp.int32)
    valid = jnp.ones(16, bool)
    ex = expand_merge_path(items, valid, g.row_ptr, g.col_idx, work_budget=8)
    assert int(jnp.sum(ex.valid.astype(jnp.int32))) == min(8, int(ex.total))


def test_owner_maps_back_to_wavefront_index():
    g = erdos(32, 128, seed=3)
    items = jnp.array([3, 7, 11], dtype=jnp.int32)
    valid = jnp.ones(3, bool)
    ex = expand_merge_path(items, valid, g.row_ptr, g.col_idx, 64)
    src = np.asarray(ex.src)[np.asarray(ex.valid)]
    owner = np.asarray(ex.owner)[np.asarray(ex.valid)]
    assert (src == np.asarray(items)[owner]).all()


@settings(max_examples=50, deadline=None)
@given(st.lists(st.integers(0, 20), min_size=1, max_size=16))
def test_lbs_owner_rank_invariants(degs):
    """LBS over an arbitrary degree vector: every work unit maps to the row
    that owns it, with in-row rank < degree."""
    scan = jnp.cumsum(jnp.asarray(degs, dtype=jnp.int32))
    total = int(scan[-1])
    from repro.kernels.frontier_expand.ref import lbs_ref
    owner, rank = lbs_ref(scan, max(total, 1))
    owner, rank = np.asarray(owner)[:total], np.asarray(rank)[:total]
    excl = np.concatenate([[0], np.asarray(scan)[:-1]])
    for k in range(total):
        o = owner[k]
        assert degs[o] > 0
        assert 0 <= rank[k] < degs[o]
        assert excl[o] + rank[k] == k


INT_MIN, INF = -2**31, 2**31 - 1

#: (degrees, budget): degree-0 tasks at the ends and in runs, a total
#: below the budget, truncation past it, a single task, no work at all
BROADCAST_CASES = {
    "mixed": ([3, 0, 5, 0, 0, 2, 7, 1], 32),
    "zero_ends": ([0, 0, 4, 0, 3, 0, 0], 16),
    "truncating": ([5, 9, 2, 20, 4, 1], 24),
    "truncating_zero_tail": ([6, 6, 0, 30, 0, 0], 16),
    "w1": ([6], 8),
    "w1_truncated": ([9], 8),
    "no_work": ([0, 0, 0], 8),
    "hub": (list(np.random.default_rng(5).integers(0, 6, 63)) + [90], 128),
}


def _owners(scan, budget, source):
    """``owner(k)`` from either LBS backend, clipped as the expansion
    clips it."""
    from repro.core.frontier import owner_of_units
    from repro.kernels.frontier_expand.kernel import lbs_pallas

    if source == "jnp":
        owner = owner_of_units(scan, budget)
    else:
        owner, _ = lbs_pallas(scan, budget, interpret=True)
    return np.clip(np.asarray(owner), 0, scan.shape[0] - 1)


@pytest.mark.parametrize("source", ["jnp", "pallas"])
@pytest.mark.parametrize("case", sorted(BROADCAST_CASES))
def test_owner_broadcast_equals_owner_gather(case, source):
    """One scatter and one prefix sum give ``table[owner]`` on every lane
    of the budget, for int32 tables at the extremes and bool tables, with
    each table of a stacked call as it would be alone."""
    from repro.core import fitting_units, owner_broadcast

    deg, budget = BROADCAST_CASES[case]
    deg = np.asarray(deg, np.int32)
    w = deg.shape[0]
    scan = jnp.cumsum(jnp.asarray(deg))
    excl = scan - jnp.asarray(deg)
    owner = _owners(scan, budget, source)
    rng = np.random.default_rng(w)
    ints = rng.integers(INT_MIN, INF, w, dtype=np.int64, endpoint=True)
    ints[::3] = INF
    ints[1::4] = -1
    ints[2::5] = INT_MIN
    ints = ints.astype(np.int32)
    flags = rng.random(w) < 0.5
    got_int, got_flag = owner_broadcast(excl, budget, jnp.asarray(ints),
                                        jnp.asarray(flags))
    assert got_int.dtype == jnp.int32 and got_flag.dtype == jnp.bool_
    np.testing.assert_array_equal(np.asarray(got_int), ints[owner])
    np.testing.assert_array_equal(np.asarray(got_flag), flags[owner])
    alone, = owner_broadcast(excl, budget, jnp.asarray(ints))
    np.testing.assert_array_equal(np.asarray(alone), ints[owner])

    # the scalar truncation cut against the owner-indexed mask, on every
    # unit below the total (the lanes a body can make live)
    valid = deg > 0
    truncated = valid & (np.asarray(scan) > budget)
    k = np.arange(budget)
    units = k < min(int(scan[-1]), budget)
    cut = int(fitting_units(scan, budget))
    np.testing.assert_array_equal((k < cut)[units], ~truncated[owner][units])
