"""The megakernel drain-loop battery (DESIGN.md section 14).

Three proof obligations for ``ExecutionPolicy(kernel="megakernel")`` — the
single-launch Pallas drain in ``repro/kernels/drain_loop``:

  * **parity** — the megakernel cells of the policy grid reproduce the
    persistent/discrete drains bit-for-bit (BFS, coloring; PageRank within
    eps and bitwise vs persistent, which runs the identical jaxpr) across
    single|fused topologies x granularities {1, 4}, and report exactly one
    kernel launch per drain;
  * **protocol** — hypothesis property tests drive scripted claim/push op
    tapes *inside* the fused kernel against the host-eager TaskQueue
    oracle: the claim cursor never passes the push cursor, ring wraparound
    is exact, invalid lanes are EMPTY-padded, and the dropped counter
    saturates precisely;
  * **fault tolerance** — SIGKILL a megakernel streaming drain at a
    snapshot boundary; the resumed process reproduces the uninterrupted
    run bit for bit (mirrors tests/test_checkpoint_fault.py).

Everything runs in interpret mode off-TPU, so the battery is CI-portable.
"""
import collections
import json
import os
import signal
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.algorithms.bfs import bfs_bsp, bfs_speculative
from repro.algorithms.coloring import coloring_async
from repro.algorithms.pagerank import pagerank_async, pagerank_reference
from repro.core import EMPTY, SchedulerConfig, make_queue
from repro.graph.generators import rmat
from repro.kernels.drain_loop import fused_drain_pallas
from repro.runtime import (ExecutionPolicy, POLICY_GRID, build_program,
                           config_for, execute)

try:  # only the property-test section needs hypothesis
    from hypothesis import given, settings, strategies as st
except ImportError:  # pragma: no cover - parity/fault tests still run
    st = None

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

MEGA_CELLS = tuple(p for p in POLICY_GRID if p.kernel == "megakernel")
GRANULARITIES = (1, 4)


@pytest.fixture(scope="module")
def g_rmat():
    return rmat(6, edge_factor=8, seed=2)


def _cfg(topology, kernel, granularity=1, **kw):
    policy = ExecutionPolicy(topology, kernel, granularity)
    return config_for(SchedulerConfig(**kw), policy)


# ------------------------------------------------ parity: one launch, same bits
def test_grid_has_the_two_megakernel_cells():
    # sharded.megakernel is invalid (the sharded round is a cross-device
    # collective; the megakernel is one device-resident launch)
    assert {(p.topology, p.kernel) for p in MEGA_CELLS} == \
        {("single", "megakernel"), ("fused", "megakernel")}


@pytest.mark.parametrize("granularity", GRANULARITIES)
def test_bfs_megakernel_bit_identical(g_rmat, granularity):
    ref = np.asarray(bfs_bsp(g_rmat, 0)[0])
    for policy in MEGA_CELLS:
        for baseline_kernel in ("persistent", "discrete"):
            base, _ = bfs_speculative(
                g_rmat, 0,
                _cfg(policy.topology, baseline_kernel, granularity,
                     num_workers=16))
            dist, info = bfs_speculative(
                g_rmat, 0,
                _cfg(policy.topology, "megakernel", granularity,
                     num_workers=16))
            assert (np.asarray(dist) == np.asarray(base)).all(), \
                (str(policy), baseline_kernel, granularity)
            assert (np.asarray(dist) == ref).all(), str(policy)
            assert info["dropped"] == 0, str(policy)


@pytest.mark.parametrize("granularity", GRANULARITIES)
def test_coloring_megakernel_bit_identical(g_rmat, granularity):
    W = 2 * g_rmat.num_vertices
    base, _ = coloring_async(
        g_rmat, _cfg("single", "persistent", granularity, num_workers=W))
    for policy in MEGA_CELLS:
        colors, _ = coloring_async(
            g_rmat, _cfg(policy.topology, "megakernel", granularity,
                         num_workers=W))
        assert (np.asarray(colors) == np.asarray(base)).all(), \
            (str(policy), granularity)


@pytest.mark.parametrize("granularity", GRANULARITIES)
def test_pagerank_megakernel_matches_persistent_bitwise(g_rmat, granularity):
    eps = 1e-5
    ref = np.asarray(pagerank_reference(g_rmat, iters=300))
    for policy in MEGA_CELLS:
        base, _ = pagerank_async(
            g_rmat, _cfg(policy.topology, "persistent", granularity,
                         num_workers=16), eps=eps)
        rank, info = pagerank_async(
            g_rmat, _cfg(policy.topology, "megakernel", granularity,
                         num_workers=16), eps=eps)
        # the megakernel body is the persistent while-loop's own jaxpr
        # evaluated in-kernel, so even float accumulation is bit-identical
        assert (np.asarray(rank) == np.asarray(base)).all(), \
            (str(policy), granularity)
        assert np.abs(np.asarray(rank) - ref).max() < 1e-3, str(policy)
        assert info["max_residue"] <= eps, str(policy)


def test_megakernel_is_one_launch_per_drain(g_rmat):
    """The whole point: kernel-entry events per drain collapse from
    O(rounds) to exactly 1."""
    program = build_program("bfs", g_rmat, SchedulerConfig(num_workers=16),
                            params={"source": 0})
    for kernel, want_one in [("persistent", False), ("discrete", False),
                             ("megakernel", True)]:
        _, stats, info = execute(program, g_rmat,
                                 _cfg("single", kernel, num_workers=16))
        assert int(stats.rounds) > 1, kernel
        if want_one:
            assert info["launches"] == 1, kernel
        else:
            assert info["launches"] == int(stats.rounds), kernel


# ------------------------- protocol: in-kernel claim/push vs TaskQueue oracle
# A scripted op tape (push k | claim k) is baked into the drain jaxpr as
# hoisted constants and replayed entirely inside ONE fused_drain_pallas
# launch, tracing per-op wavefronts and cursor snapshots.  The oracle runs
# the identical tape host-eagerly on TaskQueue (tests/test_queue.py's
# model-checked implementation).
_W = 4  # static wavefront width for every pop


def _run_tape_in_kernel(cap, ops):
    """Replay ``ops`` = [(kind, n)] in-kernel; return the trace arrays."""
    n_ops = len(ops)
    kinds = jnp.asarray([0 if k == "push" else 1 for k, _ in ops], jnp.int32)
    counts = jnp.asarray([n for _, n in ops], jnp.int32)

    q0 = make_queue(cap)
    carry0 = (q0, jnp.int32(0), jnp.int32(0),       # queue, op index, counter
              jnp.full((n_ops, _W), EMPTY, jnp.int32),   # popped items
              jnp.zeros((n_ops, _W), jnp.bool_),         # popped valid
              jnp.zeros((n_ops, 3), jnp.int32))          # (head, tail, dropped)

    def step(carry):
        q, i, counter, items_tr, valid_tr, cursor_tr = carry
        n = counts[i]

        def do_push(q):
            lane = jnp.arange(_W, dtype=jnp.int32)
            q2 = q.push(counter + lane, lane < n)
            return q2, jnp.full((_W,), EMPTY, jnp.int32), \
                jnp.zeros((_W,), jnp.bool_), counter + n

        def do_claim(q):
            items, valid, q2 = q.pop_upto(_W, n)
            return q2, items, valid, counter

        q, items, valid, counter = jax.lax.cond(
            kinds[i] == 0, do_push, do_claim, q)
        cursors = jnp.stack([q.head, q.tail, q.dropped])
        return (q, i + 1, counter, items_tr.at[i].set(items),
                valid_tr.at[i].set(valid), cursor_tr.at[i].set(cursors))

    def cond(carry):
        return carry[1] < n_ops

    q, i, _, items_tr, valid_tr, cursor_tr = fused_drain_pallas(
        step, cond, carry0)
    assert int(i) == n_ops
    return q, np.asarray(items_tr), np.asarray(valid_tr), \
        np.asarray(cursor_tr)


def _run_tape_oracle(cap, ops):
    """Host-eager replay on TaskQueue plus an independent deque model."""
    q = make_queue(cap)
    model = collections.deque()
    counter = 0
    rows = []
    for kind, n in ops:
        if kind == "push":
            lane = jnp.arange(_W, dtype=jnp.int32)
            q = q.push(counter + lane, lane < n)
            for v in range(counter, counter + n):
                if len(model) < cap:
                    model.append(v)
            counter += n
            rows.append(([int(EMPTY)] * _W, [False] * _W))
        else:
            items, valid, q = q.pop_upto(_W, n)
            want = [model.popleft() for _ in range(min(_W, n, len(model)))]
            got = [int(x) for x, v in zip(np.asarray(items),
                                          np.asarray(valid)) if v]
            assert got == want  # the oracle itself is model-checked
            rows.append((np.asarray(items).tolist(),
                         np.asarray(valid).tolist()))
        assert 0 <= int(q.size) <= cap
    return q, rows


def _check_tape(cap, ops):
    qk, items_tr, valid_tr, cursor_tr = _run_tape_in_kernel(cap, ops)
    qo, rows = _run_tape_oracle(cap, ops)

    # in-kernel wavefronts match the oracle bit for bit
    for i, (items, valid) in enumerate(rows):
        assert items_tr[i].tolist() == items, (i, ops)
        assert valid_tr[i].tolist() == valid, (i, ops)
    # final queue pytree identical: ring contents, cursors, drop counter
    assert (np.asarray(qk.buf) == np.asarray(qo.buf)).all()
    for field in ("head", "tail", "dropped"):
        assert int(getattr(qk, field)) == int(getattr(qo, field)), field

    heads, tails, drops = cursor_tr.T
    # the claim cursor never passes the push cursor, and the live window
    # never exceeds capacity — at every op, not just at the end
    assert (heads <= tails).all(), ops
    assert (tails - heads <= cap).all(), ops
    # cursors and the drop counter are monotone (no un-claim, no un-drop)
    assert (np.diff(heads, prepend=0) >= 0).all()
    assert (np.diff(tails, prepend=0) >= 0).all()
    assert (np.diff(drops, prepend=0) >= 0).all()
    # EMPTY-sentinel discipline on every claimed wavefront
    assert (items_tr[~valid_tr] == int(EMPTY)).all()
    assert (items_tr[valid_tr] != int(EMPTY)).all()


if st is not None:
    _OPS = st.lists(st.tuples(st.sampled_from(["push", "claim"]),
                              st.integers(0, _W)), min_size=1, max_size=20)

    @settings(max_examples=25, deadline=None)
    @given(_OPS)
    def test_in_kernel_claim_push_matches_oracle(ops):
        """Arbitrary claim/push tapes inside one kernel launch == TaskQueue."""
        _check_tape(8, ops)

    @settings(max_examples=15, deadline=None)
    @given(st.lists(st.integers(1, _W), min_size=4, max_size=12))
    def test_in_kernel_wraparound_is_exact(widths):
        """Tiny ring, long tape: the cursors lap the capacity several times
        in-kernel and FIFO order still matches the oracle exactly."""
        ops = []
        for n in widths:
            ops += [("push", n), ("claim", n)]
        _check_tape(4, ops)


def test_in_kernel_dropped_counter_saturates():
    """Overflow pushed inside the kernel is dropped and counted exactly:
    capacity 8, five width-4 pushes => 12 drops, then claims drain the 8
    survivors in FIFO order."""
    ops = [("push", _W)] * 5 + [("claim", _W)] * 3
    _check_tape(8, ops)
    qk, items_tr, valid_tr, _ = _run_tape_in_kernel(8, ops)
    assert int(qk.dropped) == 5 * _W - 8
    claimed = items_tr[5:][valid_tr[5:]]
    assert claimed.tolist() == list(range(8))  # survivors, in order
    assert int(qk.size) == 0


def test_in_kernel_claim_on_empty_is_all_empty():
    ops = [("claim", _W), ("push", 2), ("claim", _W), ("claim", _W)]
    qk, items_tr, valid_tr, _ = _run_tape_in_kernel(8, ops)
    assert not valid_tr[0].any() and not valid_tr[3].any()
    assert (items_tr[0] == int(EMPTY)).all()
    assert valid_tr[2].tolist() == [True, True, False, False]


# -------------------- TPU gating, build-once segments, legacy-path honesty
def _count_up_step(c):
    return (c[0] + 1,)


def test_explicit_compile_request_is_rejected():
    """The fused body has no Mosaic lowering (nested pallas_call +
    whole-array operands), so a demand to compile must raise, not hand
    Mosaic an un-lowerable program."""
    with pytest.raises(NotImplementedError, match="interpret-mode"):
        fused_drain_pallas(_count_up_step, lambda c: c[0] < 3,
                           (jnp.int32(0),), interpret=False)


def test_tpu_auto_refuses_and_points_to_persistent(monkeypatch):
    """On a real TPU the repo-wide interpret rule compiles; the megakernel
    has no lowering, so it must raise and name the compiled strategy
    instead of emulating the drain on the chip."""
    from repro.kernels.drain_loop import kernel as K
    monkeypatch.setattr(K, "resolve_interpret",
                        lambda i: False if i is None else bool(i))
    with pytest.raises(NotImplementedError, match="kernel='persistent'"):
        fused_drain_pallas(_count_up_step, lambda c: c[0] < 5,
                           (jnp.int32(0),))


def test_segment_builder_traces_once_across_limits():
    """The snapshot layer drives one fused drain through many round
    limits: the limit rides as a kernel operand, so segments 2..N reuse
    the first segment's traced jaxpr / pallas_call."""
    from repro.core.scheduler import megakernel_segment
    traces = []

    def step(c):
        traces.append(1)  # fires once per trace of the drain body
        return (c[0], c[1], c[2] + 1, c[3] + c[0])

    def cond(c):
        return c[2] < c[1]

    carry = (jnp.int32(2), jnp.int32(9), jnp.int32(0), jnp.int32(0))
    seg = megakernel_segment(step, cond, carry)
    baseline = len(traces)
    assert baseline >= 1
    for _ in range(4):  # limits 3, 6, 9, 12 — last two hit the cond cap
        carry = seg(carry, int(carry[2]) + 3)
    assert len(traces) == baseline, "segment retraced the fused drain"
    assert int(carry[2]) == 9 and int(carry[3]) == 18


def test_stream_row_slices_zero_items():
    """n_items == 0 must not issue the prologue DMA against an empty
    starts array."""
    from repro.kernels.drain_loop import stream_row_slices
    col = jnp.arange(16, dtype=jnp.int32)
    out = stream_row_slices(col, jnp.zeros((0,), jnp.int32), 4)
    assert out.shape == (0, 4)


def test_legacy_scheduler_run_honors_megakernel(monkeypatch):
    """core.scheduler.run must route kernel='megakernel' to the fused
    driver — not silently degrade to the persistent strategy through the
    legacy bool (policy.persistent is True for both)."""
    from repro.core import scheduler as S
    calls = []
    real = S.megakernel_drive
    monkeypatch.setattr(
        S, "megakernel_drive",
        lambda *a, **k: (calls.append(1), real(*a, **k))[1])

    def f(items, valid, state):
        drained = jnp.sum(jnp.where(valid, items, 0))
        return jnp.zeros_like(items), jnp.zeros_like(valid), state + drained

    cfg = S.SchedulerConfig(num_workers=8, kernel="megakernel")
    q = make_queue(16, jnp.arange(5, dtype=jnp.int32))
    _, s, stats = S.run(f, q, jnp.int32(0), cfg)
    assert calls, "run() bypassed the megakernel driver"
    assert int(s) == 10 and int(stats.items_processed) == 5
    assert int(stats.dropped) == 0


def test_taskserver_warns_on_megakernel_config(caplog):
    """The multi-tenant server loop is host-driven and cannot fuse a
    tenant's drain; a megakernel config must warn, never degrade
    silently."""
    import logging
    from repro.server.engine import TaskServer
    server = TaskServer(None, num_lanes=2,
                        config=SchedulerConfig(num_workers=4,
                                               kernel="megakernel"))
    with caplog.at_level(logging.WARNING, logger="repro.server"):
        server.run()  # no jobs: the config check still fires
    assert any("megakernel" in rec.getMessage() for rec in caplog.records)


# --------------------------- fault injection: SIGKILL the megakernel drain
# Mirror of tests/test_checkpoint_fault.py's streaming crash test, with the
# drain segments executed by the megakernel: stream/driver.py bakes each
# snapshot window's round limit into the in-kernel cond, so the checkpoint
# boundaries land on the same absolute rounds as the persistent driver's.
_MEGA_CHILD = """
    import json
    import os
    import signal
    import numpy as np
    from repro.core import SchedulerConfig
    from repro.graph.generators import edge_delta_stream, rmat
    from repro.runtime import stream_execute

    base = rmat(6, edge_factor=6, seed=5)
    deltas = edge_delta_stream(base, 3, 12, seed=6)
    cfg = SchedulerConfig(num_workers=32, topology="single",
                          kernel="megakernel")
    kill_at = int(os.environ.get("KILL_AT_TICK", "-1"))

    def hook(tick, batch):
        if tick == kill_at:
            os.kill(os.getpid(), signal.SIGKILL)

    res = stream_execute(
        "bfs", base, deltas, cfg, params={"source": 2},
        snapshot_every=2, checkpoint_dir=os.environ["SNAP_DIR"],
        keep=100, resume=os.environ.get("RESUME") == "1",
        snapshot_hook=hook)
    print(json.dumps({
        "result": np.asarray(res.result).tolist(),
        "resumed_at": res.info["resumed_at"],
        "batches_run": res.info["batches_run"],
    }))
"""


def _mega_child(snap_dir, kill_at=-1, resume=False):
    prog = ("import os\n"
            "os.environ.setdefault('JAX_PLATFORMS', 'cpu')\n"
            + textwrap.dedent(_MEGA_CHILD))
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               SNAP_DIR=str(snap_dir), KILL_AT_TICK=str(kill_at),
               RESUME="1" if resume else "0")
    return subprocess.run([sys.executable, "-c", prog],
                          capture_output=True, text=True, env=env,
                          timeout=900)


def test_sigkill_megakernel_drain_resume_bit_exact(tmp_path):
    """SIGKILL between two megakernel launches (at a snapshot boundary);
    the resumed process must reproduce the uninterrupted run bit for bit."""
    ref_dir = tmp_path / "ref"
    out = _mega_child(ref_dir)
    assert out.returncode == 0, out.stderr[-3000:]
    ref = json.loads(out.stdout.strip().splitlines()[-1])
    assert ref["resumed_at"] is None

    crash_dir = tmp_path / "crash"
    killed = _mega_child(crash_dir, kill_at=3)
    assert killed.returncode == -signal.SIGKILL
    assert any(p.startswith("snap_") for p in os.listdir(crash_dir))

    resumed = _mega_child(crash_dir, resume=True)
    assert resumed.returncode == 0, resumed.stderr[-3000:]
    got = json.loads(resumed.stdout.strip().splitlines()[-1])
    assert got["resumed_at"] is not None
    assert got["batches_run"] < ref["batches_run"]
    assert got["result"] == ref["result"]
