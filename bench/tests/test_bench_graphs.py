"""The generator copies at a small size: CSR invariants, the Kronecker
copy against the program's own CSR builder, the rgg copy against a
brute-force pair-distance graph."""
import json

import numpy as np
import pytest

from bench.graphs import kronecker, rgg
from bench.graphs.csr import seed_key

CONFIGS = "bench/configs/{}.json"


def _config(root, name, **small):
    return dict(json.loads((root / CONFIGS.format(name)).read_text()),
                **small)


def _host(made):
    rp = np.asarray(made["row_ptr"])
    return rp, np.asarray(made["col_idx"])[:made["m"]]


def _assert_simple_undirected(rp, ci):
    n = rp.shape[0] - 1
    assert rp[0] == 0 and (np.diff(rp) >= 0).all()
    src = np.repeat(np.arange(n), np.diff(rp))
    assert (src != ci).all(), "self-loop"
    key = src.astype(np.int64) * n + ci
    assert (np.diff(key) > 0).all(), "rows unsorted or duplicate edges"
    back = np.sort(ci.astype(np.int64) * n + src)
    assert (back == key).all(), "not symmetric"


@pytest.fixture
def root():
    from pathlib import Path
    return Path(__file__).resolve().parents[2]


@pytest.mark.parametrize("seed", [0, 7, 2**33 + 5])
def test_kronecker_matches_from_edges(root, seed):
    from repro.graph.csr import from_edges

    cfg = _config(root, "graph500-kron20", scale=9)
    made = kronecker.generate(cfg, seed)
    rp, ci = _host(made)
    _assert_simple_undirected(rp, ci)
    src, dst = kronecker.edge_list(seed_key(seed), scale=9, edgefactor=16,
                                   a=cfg["A"], b=cfg["B"], c=cfg["C"])
    ref = from_edges(1 << 9, np.asarray(src), np.asarray(dst),
                     symmetrize=True)
    np.testing.assert_array_equal(np.asarray(ref.row_ptr), rp)
    np.testing.assert_array_equal(np.asarray(ref.col_idx), ci)
    assert made["col_idx"].shape[0] == kronecker.capacity(cfg)


def test_kronecker_degree_law_is_heavy_tailed(root):
    rp, _ = _host(kronecker.generate(
        _config(root, "graph500-kron20", scale=12), 3))
    deg = np.diff(rp)
    assert deg.max() > 20 * deg.mean()


@pytest.mark.parametrize("seed,scale", [(1, 10), (2**31 + 9, 11)])
def test_rgg_matches_brute_force(root, seed, scale):
    from repro.graph.csr import from_edges

    cfg = _config(root, "dimacs10-rgg20", scale=scale)
    made = rgg.generate(cfg, seed)
    rp, ci = _host(made)
    _assert_simple_undirected(rp, ci)
    n = 1 << scale
    x, y = rgg.points(seed_key(seed), n, rgg.lattice_bits(cfg))
    x, y = np.asarray(x, np.int64), np.asarray(y, np.int64)
    d2 = (x[:, None] - x[None, :]) ** 2 + (y[:, None] - y[None, :]) ** 2
    adj = d2 < rgg.lattice_radius_sq(cfg)
    np.fill_diagonal(adj, False)
    ref = from_edges(n, *np.nonzero(adj))
    np.testing.assert_array_equal(np.asarray(ref.row_ptr), rp)
    np.testing.assert_array_equal(np.asarray(ref.col_idx), ci)


def test_rgg_full_size_constants(root):
    cfg = _config(root, "dimacs10-rgg20")
    assert rgg.lattice_bits(cfg) == 24
    assert abs(rgg.radius(cfg) - 0.0019998) < 1e-6
    assert rgg.cells_per_side(cfg) == 500
    # the capacity holds the published graph's 2 x 6,891,620 CSR entries
    assert 2 * 6891620 < rgg.capacity(cfg) < 1.1 * 2 * 6891620


def test_seed_key_takes_large_seeds():
    a, b = seed_key(2**40 + 1), seed_key(1)
    import jax
    assert not (jax.random.key_data(a) == jax.random.key_data(b)).all()
    with pytest.raises(ValueError):
        seed_key(-1)
