"""Backend dispatch layer: resolution rules + kernel-backed push parity.

The contract under test (DESIGN.md section 9): ``backend`` is a pure
performance axis — every dispatch site must produce *bit-identical* results
whether it runs the jnp reference or the Pallas kernels (interpret mode on
CPU).  The queue tests here deliberately avoid hypothesis so they always run.
"""
import dataclasses
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (BACKENDS, SchedulerConfig, default_interpret,
                        expand_merge_path, has_tpu, make_multiqueue,
                        make_queue, resolve_backend, resolve_interpret)


# ------------------------------------------------------------- resolution
def test_resolve_backend_values():
    assert resolve_backend("jnp") == "jnp"
    assert resolve_backend("pallas") == "pallas"
    auto = resolve_backend("auto")
    assert auto in ("jnp", "pallas")
    assert auto == ("pallas" if has_tpu() else "jnp")


def test_resolve_backend_rejects_unknown():
    with pytest.raises(ValueError, match="unknown backend"):
        resolve_backend("cuda")


def test_interpret_resolution_tracks_hardware():
    # off-TPU the kernels must interpret; on TPU they must compile.
    assert default_interpret() == (not has_tpu())
    assert resolve_interpret(None) == default_interpret()
    assert resolve_interpret(True) is True
    assert resolve_interpret(False) is False


def test_compile_cache_placed_from_env_or_repo(monkeypatch):
    """``JAX_COMPILATION_CACHE_DIR`` wins and nothing is set in code;
    without it the cache goes to the fixed ``<repo>/.jax_cache``."""
    from repro.launch import compile_cache

    updates = []
    monkeypatch.setattr(compile_cache.jax.config, "update",
                        lambda *a: updates.append(a))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
    assert compile_cache.use_compile_cache() == "/elsewhere"
    assert updates == []
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    repo_cache = Path(__file__).resolve().parents[1] / ".jax_cache"
    assert compile_cache.use_compile_cache() == str(repo_cache)
    assert updates == [("jax_compilation_cache_dir", str(repo_cache))]


def test_scheduler_config_carries_backend_axis():
    assert SchedulerConfig().backend == "jnp"
    assert "auto" in BACKENDS
    cfg = dataclasses.replace(SchedulerConfig(), backend="pallas")
    assert cfg.backend == "pallas"
    assert cfg != SchedulerConfig()  # backend is part of config identity


# ------------------------------------------------- queue push parity (jnp
# prefix-sum reservation is the oracle for the queue_compact-backed push)
def _assert_queues_equal(qa, qb, ctx=""):
    for field in ("buf", "head", "tail", "dropped"):
        np.testing.assert_array_equal(
            np.asarray(getattr(qa, field)), np.asarray(getattr(qb, field)),
            err_msg=f"{field} diverged {ctx}")


@pytest.mark.parametrize("mask", [
    [True, True, True, True, True, True],       # dense
    [True, False, True, False, True, False],    # holes to compact
    [False] * 6,                                # nothing valid
])
def test_pallas_push_matches_prefix_sum_oracle(mask):
    items = jnp.arange(10, 16, dtype=jnp.int32)
    mask = jnp.asarray(mask)
    q0 = make_queue(16, jnp.array([1, 2, 3]))
    _assert_queues_equal(q0.push(items, mask),
                         q0.push(items, mask, backend="pallas"))


def test_pallas_push_dropped_counter_path():
    """Overflow: 5 valid items into 3 free slots — both backends must keep
    the same survivors (the first 3 valid, in order) and count 2 drops."""
    q0 = make_queue(8, jnp.array([1, 2, 3, 4, 5]))
    items = jnp.arange(10, 16, dtype=jnp.int32)
    mask = jnp.array([True, False, True, True, True, True])
    qa = q0.push(items, mask)
    qb = q0.push(items, mask, backend="pallas")
    _assert_queues_equal(qa, qb, "on overflow")
    assert int(qb.dropped) == 2
    got, valid, _ = qb.pop(8)
    assert [int(x) for x, v in zip(np.asarray(got), np.asarray(valid)) if v] \
        == [1, 2, 3, 4, 5, 10, 12, 13]


def test_pallas_push_wraparound_sequence():
    """Interleaved pops/pushes drive the ring cursors past the buffer edge;
    the two backends must stay in lockstep at every step."""
    qa = make_queue(4, jnp.array([0, 1]))
    qb = make_queue(4, jnp.array([0, 1]))
    for i in range(10):
        _, _, qa = qa.pop(1)
        _, _, qb = qb.pop(1)
        items = jnp.array([100 + i, 200 + i], jnp.int32)
        mask = jnp.array([True, i % 2 == 0])
        qa = qa.push(items, mask)
        qb = qb.push(items, mask, backend="pallas")
        _assert_queues_equal(qa, qb, f"at step {i}")


def test_pallas_push_spans_multiple_tiles():
    """Widths past the kernel TILE exercise the phase-2 cross-tile stitch."""
    from repro.kernels.queue_compact.kernel import TILE

    n = 2 * TILE + 37
    rng = np.random.default_rng(3)
    items = jnp.asarray(rng.integers(0, 1 << 20, size=n), jnp.int32)
    mask = jnp.asarray(rng.random(n) < 0.4)
    q0 = make_queue(2 * n)
    _assert_queues_equal(q0.push(items, mask),
                         q0.push(items, mask, backend="pallas"))


def test_multiqueue_push_backend_parity():
    mqa = make_multiqueue(8, 3)
    mqb = make_multiqueue(8, 3)
    for lane in range(3):
        items = jnp.arange(lane * 10, lane * 10 + 12, dtype=jnp.int32)
        mask = jnp.asarray(np.arange(12) % (lane + 2) == 0)
        mqa = mqa.push(lane, items, mask)
        mqb = mqb.push(lane, items, mask, backend="pallas")
    _assert_queues_equal(mqa.lanes, mqb.lanes)


def test_push_dense_backend_parity():
    q0 = make_queue(8)
    _assert_queues_equal(q0.push_dense(jnp.arange(5, dtype=jnp.int32)),
                         q0.push_dense(jnp.arange(5, dtype=jnp.int32),
                                       backend="pallas"))


# -------------------------------------------------------- expand dispatch
def test_expand_merge_path_backend_parity():
    from repro.graph import rmat

    g = rmat(7, 4, seed=5)
    items = jnp.array([1, 4, 9, 16, 25, 36, 49, 64], jnp.int32)
    valid = jnp.array([True] * 7 + [False])
    budget = 8 * int(jnp.max(g.degrees()))
    ref = expand_merge_path(items, valid, g.row_ptr, g.col_idx, budget)
    for backend in ("pallas", "auto"):
        got = expand_merge_path(items, valid, g.row_ptr, g.col_idx, budget,
                                backend=backend)
        for a, b in zip(ref, got):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
