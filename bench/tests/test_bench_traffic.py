"""The root rules: Graph500's uniform roots inside the warm-up search's
component, and the nearest reached vertices to fixed points."""
import numpy as np
import pytest

from bench import traffic

UNIFORM = {"roots": {"rule": "uniform", "count": 3}}


def test_uniform_draws_from_the_component_by_the_seed():
    degrees = np.array([5, 9, 9, 1, 7, 0, 3, 2])
    reached = np.array([1, 1, 1, 1, 0, 0, 1, 1], bool)
    seen = set()
    for seed in (1, 2, 2**33 + 7, 12345):
        got = traffic.roots(UNIFORM, seed, reached, degrees)
        assert len(set(got)) == 3
        assert set(got) <= {0, 1, 2, 3, 6, 7}      # 4 unreached, 5 isolated
        assert got == traffic.roots(UNIFORM, seed, reached, degrees)
        seen.update(got)
    assert len(seen) > 3                          # the seed changes the set


def test_uniform_is_uniform_over_the_component():
    degrees = np.ones(10, int)
    reached = np.ones(10, bool)
    counts = np.zeros(10)
    for seed in range(2000):
        counts[traffic.roots(UNIFORM, seed, reached, degrees)] += 1
    assert counts.sum() == 6000
    assert counts.min() > 500 and counts.max() < 700   # 600 expected


def test_near_points_picks_the_nearest_reached_vertex():
    x = np.array([0.1, 0.26, 0.9, 0.74, 0.5])
    y = np.array([0.1, 0.24, 0.9, 0.76, 0.5])
    reached = np.array([1, 0, 1, 1, 1], bool)
    spec = {"roots": {"rule": "near_points",
                      "points": [[0.25, 0.25], [0.75, 0.75]]}}
    got = traffic.roots(spec, 3, reached, np.ones(5), points=(x, y))
    assert sorted(got) == [0, 3]


def test_unknown_rule_is_refused():
    with pytest.raises(ValueError):
        traffic.roots({"roots": {"rule": "top_degree"}}, 1,
                      np.ones(3, bool), np.ones(3))
