"""Names on the profiler's clock (``repro.obs.spans``, DESIGN.md §15).

  * **host spans** — one ``jax.profiler`` session around ``build_program``
    and ``execute``: the host plane holds ``atos:build_program``,
    ``atos:init``, ``atos:drain`` and ``atos:readback`` in that order, none
    overlapping the next, and ``Trace.span`` is the same helper;
  * **device scopes** — the compiled drain of every algorithm: each gather,
    scatter and custom-call of the while body carries a round phase
    (``atos.pop`` / ``expand`` / ``apply`` / ``push`` / ``on_empty``) in its
    ``op_name``, and the ring's bookkeeping carries ``atos.ring``.
"""
import glob
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.core import SchedulerConfig
from repro.core.scheduler import persistent_drive
from repro.graph.generators import rmat
from repro.obs import SPAN_PREFIX, Trace, TraceRing, span, validate_metric
from repro.obs.spans import APPLY, EXPAND, ON_EMPTY, POP, PUSH, RING
from repro.runtime import build_program, config_for, execute, parse_policy
from repro.runtime.api import _shared_setup, instrument_step
from repro.runtime.policy import policy_of

PHASES = (POP, EXPAND, APPLY, PUSH, ON_EMPTY)
PARAMS = {"bfs": {"source": 0}, "pagerank": {}, "coloring": {}}
#: an HLO instruction's opcode and op_name
INSTR = re.compile(r"= \S+ (gather|scatter|custom-call)\(.*"
                   r"op_name=\"([^\"]*)\"")


@pytest.fixture(scope="module")
def graph():
    return rmat(6, edge_factor=4, seed=1)


def _host_spans(log_dir) -> list:
    """``(name, start_ns, end_ns)`` of every ``atos:`` host event, by
    start."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                        recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            out.extend((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                       for ev in line.events
                       if ev.name.startswith(SPAN_PREFIX))
    return sorted(out, key=lambda s: s[1])


@pytest.mark.parametrize("policy, steps", [
    ("single.persistent", ["build_program", "init", "drain", "readback"]),
    ("fused.persistent", ["build_program", "init", "drain", "readback"]),
    ("sharded.persistent", ["build_program", "drain"]),
])
def test_execute_steps_are_host_spans_in_order(graph, tmp_path, policy,
                                               steps):
    cfg = config_for(SchedulerConfig(num_workers=16), parse_policy(policy))
    jax.profiler.start_trace(str(tmp_path))
    try:
        program = build_program("bfs", graph, cfg, {"source": 0})
        res = execute(program, graph, cfg)
        jax.block_until_ready(res.state.dist)
    finally:
        jax.profiler.stop_trace()
    spans = _host_spans(tmp_path)
    assert [name for name, _, _ in spans] == [SPAN_PREFIX + s for s in steps]
    for (_, _, end), (_, start, _) in zip(spans, spans[1:]):
        assert end <= start


def test_trace_span_records_its_doc_and_annotation(tmp_path):
    trace = Trace()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with trace.span("compile"):
            pass
        with span("bare"):             # no Trace: the annotation alone
            pass
    finally:
        jax.profiler.stop_trace()
    (doc,) = trace.spans
    validate_metric(doc)
    assert doc["name"] == "compile" and doc["dur_us"] >= 0
    assert [name for name, _, _ in _host_spans(tmp_path)] == [
        "atos:compile", "atos:bare"]


def _drain_text(program, graph, cfg, ring: bool = False) -> str:
    """Compiled HLO text of ``program``'s single.persistent drain."""
    queue, state, ops, step, cond, _ = _shared_setup(
        program, graph, cfg, policy_of(cfg), None)
    carry0 = (queue, state, jnp.int32(0), jnp.int32(0))
    if ring:
        step, cond = instrument_step(step, cond, ops, program)
        carry0 = carry0 + (TraceRing.make(8),)
    return jax.jit(lambda c: persistent_drive(step, cond, c)).lower(
        carry0).compile().as_text()


def _body_ops(text: str) -> list:
    """``(opcode, op_name)`` of the while body's gathers, scatters and
    custom-calls."""
    return [(op, name) for op, name in INSTR.findall(text)
            if "/while/body/" in name]


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
@pytest.mark.parametrize("algorithm", ["bfs", "pagerank", "coloring"])
def test_every_round_op_carries_its_phase(graph, algorithm, backend):
    cfg = config_for(SchedulerConfig(num_workers=16, backend=backend),
                     parse_policy("single.persistent"))
    program = build_program(algorithm, graph, cfg, PARAMS[algorithm])
    ops = _body_ops(_drain_text(program, graph, cfg))
    assert ops
    unscoped = [(op, name) for op, name in ops
                if not any(f"/{p}/" in name for p in PHASES)]
    assert unscoped == []
    scopes = {p for _, name in ops for p in PHASES if f"/{p}/" in name}
    assert {POP, EXPAND, APPLY} <= scopes
    if algorithm == "pagerank":
        assert ON_EMPTY in scopes        # the rotating rescan's gathers


def test_bfs_drain_names_all_four_phases(graph):
    """The push phase lowers to a scatter only through the compaction
    kernel's prefix sum: the compiled text names all four phases."""
    cfg = config_for(SchedulerConfig(num_workers=16, backend="jnp"),
                     parse_policy("single.persistent"))
    program = build_program("bfs", graph, cfg, {"source": 0})
    text = _drain_text(program, graph, cfg)
    for phase in (POP, EXPAND, APPLY, PUSH):
        assert f"/{phase}/" in text, phase


def test_ring_bookkeeping_is_scoped(graph):
    cfg = config_for(SchedulerConfig(num_workers=16),
                     parse_policy("single.persistent"))
    program = build_program("bfs", graph, cfg, {"source": 0})
    plain = _drain_text(program, graph, cfg)
    traced = _drain_text(program, graph, cfg, ring=True)
    assert f"/{RING}/" not in plain
    assert f"/{RING}/" in traced
    # the ring adds no op to the round's phases
    assert sorted(_body_ops(plain)) == sorted(
        (op, name) for op, name in _body_ops(traced)
        if f"/{RING}/" not in name)
