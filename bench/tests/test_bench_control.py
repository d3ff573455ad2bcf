"""Each cell's control, put in the program's place and driven through the
harness's own run and check, reads ``correct: false`` (at a size whose
depth and wavefront expose it: past 255 levels for ``dist_uint8``, a
queue longer than the wavefront for ``label_once``); the plain reference
agrees with a second BFS of its own kind (scipy's)."""
import json

import numpy as np
import pytest
import scipy.sparse as sp
from scipy.sparse.csgraph import shortest_path

from bench import control, run
from bench.graphs import kronecker, rgg
from bench.reference.bfs import UNREACHED, bfs

#: cell -> configuration scale at which its control must fail (the small
#: cells' wavefront is 64)
CONTROL_SCALE = {"kron20.bfs": 10, "rgg20.bfs": 18}


@pytest.mark.parametrize("name", sorted(CONTROL_SCALE))
@pytest.mark.parametrize("seed", [3, 4, 2**32 + 5])
def test_control_in_place_is_not_correct(small_cell, name, seed):
    cell = small_cell(name)
    cell.config["scale"] = CONTROL_SCALE[name]
    with control.in_place(cell, seed):
        result = run.run(cell, seed, 1.0, False)
    json.dumps(result)
    assert result["attempted"] >= 1
    assert result["correct"] is False
    assert result["check"]["dist_mismatches"]["value"] > 0
    assert result["failed"] >= 1


def test_in_place_restores_the_program(small_cell):
    cell = small_cell("kron20.bfs")
    jobs = run._module("jobs", "bfs")
    real = jobs.Jobs
    with control.in_place(cell, 1):
        assert jobs.Jobs is not real
    assert jobs.Jobs is real


def _graph(name, seed):
    from pathlib import Path
    root = Path(__file__).resolve().parents[2]
    cfg = json.loads((root / f"bench/configs/{name}.json").read_text())
    family = kronecker if cfg["family"] == "kronecker" else rgg
    made = family.generate(dict(cfg, scale=10 if family is kronecker else 11),
                           seed)
    rp = np.asarray(made["row_ptr"])
    return rp, np.asarray(made["col_idx"])[:made["m"]]


@pytest.mark.parametrize("name", ["graph500-kron20", "dimacs10-rgg20"])
def test_reference_matches_scipy(name):
    rp, ci = _graph(name, 11)
    n = rp.shape[0] - 1
    a = sp.csr_matrix((np.ones(ci.shape[0]), ci, rp), shape=(n, n))
    for root in (0, int(np.argmax(np.diff(rp))), n // 3):
        hops = shortest_path(a, unweighted=True, indices=root)
        want = np.where(np.isinf(hops), UNREACHED, hops).astype(np.int64)
        np.testing.assert_array_equal(bfs(rp, ci, root), want)
