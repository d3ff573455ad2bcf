"""Each cell's run end to end on the CPU at a small size of its family,
and the same run with the timed path broken underneath: ``correct`` has
to come out false for every fault a one-chip BFS cell can have.

(A cell on one chip has no exchange between chips to leave out.)"""
import json

import jax
import jax.numpy as jnp
import pytest

from bench import run

CELLS = ["kron20.bfs", "rgg20.bfs"]


def _run(cell, trace=False, seed=2**31 + 77):
    result = run.run(cell, seed, 1.0, trace)
    json.dumps(result)      # one JSON line
    return result


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("trace", [False, True])
def test_cell_runs_and_is_correct(small_cell, name, trace):
    result = _run(small_cell(name), trace)
    assert result["correct"] is True
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert list(result)[-1] == "check"
    assert result["check"]["dist_mismatches"] == {"value": 0, "limit": 0}
    metrics = result["metrics"]
    if trace:
        # program counters are read off the chip too; device-trace
        # metrics need a TPU plane and are left out here
        assert {"rounds_per_job", "wavefront_fill", "overwork"} <= set(metrics)
        assert 0 < metrics["wavefront_fill"]["value"] <= 100
        assert metrics["overwork"]["value"] >= 1
    else:
        assert set(metrics) == {"evps", "setup_s"}
        assert metrics["evps"]["value"] > 0


@pytest.mark.parametrize("name", CELLS)
def test_same_seed_same_inputs(small_cell, name):
    a = _run(small_cell(name), seed=5)
    b = _run(small_cell(name), seed=5)
    assert a["correct"] and b["correct"]


def _state_unchanged(monkeypatch):
    import repro.runtime.api as api
    orig = api.wavefront_step

    def step(f, on_empty, ops, carry, **kw):
        queue, _, rounds, processed = orig(f, on_empty, ops, carry, **kw)
        return queue, carry[1], rounds, processed

    monkeypatch.setattr(api, "wavefront_step", step)


def _half_batch(monkeypatch):
    import repro.runtime.api as api
    orig = api.wavefront_step

    def step(f, on_empty, ops, carry, **kw):
        def half(items, valid, state):
            lane = jnp.arange(valid.shape[0])
            return f(items, valid & (lane % 2 == 0), state)
        return orig(half, on_empty, ops, carry, **kw)

    monkeypatch.setattr(api, "wavefront_step", step)


def _answer_altered(monkeypatch):
    import repro.runtime.api as api
    orig = api.execute

    def execute(*args, **kw):
        res = orig(*args, **kw)
        dist = res.state.dist
        far = jnp.argmax(jnp.where(dist == 0x7FFFFFFF, -1, dist))
        state = type(res.state)(dist=dist.at[far].add(1),
                                counter=res.state.counter)
        return api.ExecutionResult(state, res.stats, res.info)

    monkeypatch.setattr(api, "execute", execute)


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch,
                                   _answer_altered])
def test_fault_in_timed_path_is_not_correct(small_cell, monkeypatch, name,
                                            fault):
    cell = small_cell(name)
    fault(monkeypatch)
    jax.clear_caches()
    result = _run(cell)
    assert result["correct"] is False
    assert result["failed"] >= 1
