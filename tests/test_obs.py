"""Observability layer (DESIGN.md section 15): ring, schema, exporters,
and the tracing-disabled-is-identity contract.

Four tiers:

  * **ring model** — the device TraceRing against a ``deque(maxlen=cap)``
    reference model: wraparound keeps exactly the newest ``capacity``
    rows oldest-first and reports the overwritten count, driven by
    hypothesis when available and by a seeded deterministic sweep always;
  * **schema/exporters** — the canonical metric kinds, the hand-rolled
    validators (including the bool-is-not-int trap), exact nearest-rank
    percentiles, atomic temp-then-rename writes, and the Chrome-trace
    layout (one pid per engine, metadata naming, logical round timebase);
  * **parity** — for every POLICY_GRID cell x granularity {1, 4} (the
    sharded cells on a degenerate 1-device mesh, tier-1 safe), running
    with ``trace=Trace()`` returns bit-identical results/stats/info to
    ``trace=None`` while collecting one ring record per round — plus the
    empty-run (``max_rounds=0``) and capacity-truncation edges;
  * **integration** — WorkCounter.rounds as the single round source of
    truth, the vertex-denominated occupancy fix at granularity 4, and the
    traced task server / stream driver (records reconcile with stats,
    per-job latency histograms are exact).
"""
import json
import os
import random
from collections import deque

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import SchedulerConfig
from repro.core.counters import JobTelemetry, WorkCounter
from repro.graph.generators import grid2d, rmat
from repro.obs import (DEFAULT_CAPACITY, LatencyHistogram, Trace, TraceRing,
                       atomic_write_text, chrome_trace, metric_doc,
                       read_jsonl, ring_rows, stacked_rings, unstack_ring,
                       validate_bench, validate_chrome_trace,
                       validate_metric, validate_metrics_jsonl,
                       write_chrome_trace, write_jsonl)
from repro.obs.export import HOST_PID, ROUND_DUR_US
from repro.obs.schema import NUM_FIELDS, SCHEMA_VERSION, TRACE_FIELDS
from repro.runtime import (POLICY_GRID, build_program, config_for, execute,
                           parse_policy, stream_execute)

try:  # only the property-test section needs hypothesis
    from hypothesis import given, settings, strategies as st
except ImportError:  # pragma: no cover - the seeded sweep still runs
    st = None


@pytest.fixture(scope="module")
def g_rmat():
    return rmat(6, edge_factor=4, seed=1)


@pytest.fixture(scope="module")
def g_grid():
    return grid2d(8, 8)


# ------------------------------------------------------------- ring model
def _check_against_model(capacity, values):
    """Drive a ring and a deque(maxlen=capacity) with the same rows."""
    ring = TraceRing.make(capacity)
    model = deque(maxlen=capacity)
    for i, v in enumerate(values):
        ring = ring.record(round=i, work=v)
        model.append((i, v))
    rows, truncated = ring_rows(ring)
    assert truncated == max(0, len(values) - capacity)
    assert [(r["round"], r["work"]) for r in rows] == list(model)
    # unnamed columns are zero
    for r in rows:
        assert all(r[f] == 0 for f in TRACE_FIELDS
                   if f not in ("round", "work"))


def test_ring_empty():
    rows, truncated = ring_rows(TraceRing.make(4))
    assert rows == [] and truncated == 0


def test_ring_partial_fill_keeps_order():
    _check_against_model(8, [10, 20, 30])


def test_ring_exact_fill_boundary():
    _check_against_model(4, [1, 2, 3, 4])


def test_ring_wraparound_keeps_newest():
    ring = TraceRing.make(3)
    for i in range(7):
        ring = ring.record(round=i, pops=i * 10)
    rows, truncated = ring_rows(ring)
    assert truncated == 4
    assert [r["round"] for r in rows] == [4, 5, 6]
    assert [r["pops"] for r in rows] == [40, 50, 60]


def test_ring_seeded_model_sweep():
    """Deterministic wraparound/truncation sweep (runs without hypothesis)."""
    rng = random.Random(0)
    for capacity in (1, 2, 3, 5, 8):
        for n in (0, 1, capacity - 1, capacity, capacity + 1,
                  3 * capacity + 2):
            if n < 0:
                continue
            _check_against_model(
                capacity, [rng.randrange(-2**31, 2**31) for _ in range(n)])


if st is not None:
    @settings(max_examples=40, deadline=None)
    @given(st.integers(min_value=1, max_value=7),
           st.lists(st.integers(min_value=-2**31, max_value=2**31 - 1),
                    max_size=30))
    def test_ring_matches_deque_model(capacity, values):
        _check_against_model(capacity, values)


def test_ring_rejects_unknown_field_and_bad_capacity():
    with pytest.raises(ValueError, match="unknown trace fields"):
        TraceRing.make(2).record(bogus=1)
    with pytest.raises(ValueError, match="capacity"):
        TraceRing.make(0)


def test_ring_records_inside_jit():
    """record() is pure array ops — safe inside a jitted loop."""
    def body(i, ring):
        return ring.record(round=i, work=2 * i)

    ring = jax.jit(
        lambda r: jax.lax.fori_loop(0, 5, body, r))(TraceRing.make(8))
    rows, truncated = ring_rows(ring)
    assert truncated == 0
    assert [(r["round"], r["work"]) for r in rows] == [
        (i, 2 * i) for i in range(5)]


def test_stacked_ring_round_trip():
    ring = TraceRing.make(4).record(round=0, work=7)
    stacked = stacked_rings(ring, 3)
    assert stacked.buf.shape == (3, 4, NUM_FIELDS)
    for d in range(3):
        rows, _ = ring_rows(unstack_ring(stacked, d))
        assert [(r["round"], r["work"]) for r in rows] == [(0, 7)]


# ---------------------------------------------------------------- schema
def test_metric_doc_tags_and_validates():
    doc = metric_doc("span", name="x", ts_us=0.0, dur_us=1.5)
    assert doc["schema"] == SCHEMA_VERSION and doc["kind"] == "span"
    validate_metric(doc)  # idempotent


def test_validate_metric_rejects_drift():
    with pytest.raises(ValueError, match="unknown metric kind"):
        validate_metric({"schema": SCHEMA_VERSION, "kind": "nope"})
    with pytest.raises(ValueError, match="missing required field"):
        validate_metric({"schema": SCHEMA_VERSION, "kind": "span",
                         "name": "x", "ts_us": 0.0})
    with pytest.raises(ValueError, match="schema"):
        validate_metric({"schema": 999, "kind": "span", "name": "x",
                         "ts_us": 0.0, "dur_us": 1.0})
    # bool is an int subclass — an int field must still reject it
    bad = metric_doc("span", name="x", ts_us=0.0, dur_us=1.0)
    bad = dict(bad, kind="round", engine="e",
               **{f: 0 for f in TRACE_FIELDS})
    validate_metric(bad)
    bad["pops"] = True
    with pytest.raises(ValueError, match="bool"):
        validate_metric(bad)


def test_validate_metric_allows_extra_fields():
    doc = metric_doc("span", name="x", ts_us=0.0, dur_us=1.0, extra="ok")
    validate_metric(doc)


def test_validate_metrics_jsonl_reports_line():
    good = json.dumps(metric_doc("span", name="a", ts_us=0.0, dur_us=1.0))
    assert validate_metrics_jsonl([good, "", good]) == 2
    with pytest.raises(ValueError, match="line 1"):
        validate_metrics_jsonl([good, "{not json"])
    with pytest.raises(ValueError, match="line 1"):
        validate_metrics_jsonl([good, json.dumps({"kind": "nope"})])


def test_validate_chrome_trace_shape():
    with pytest.raises(ValueError, match="traceEvents"):
        validate_chrome_trace({})
    with pytest.raises(ValueError, match="missing"):
        validate_chrome_trace({"traceEvents": [{"name": "x", "ph": "M"}]})
    with pytest.raises(ValueError, match="dur"):
        validate_chrome_trace({"traceEvents": [
            {"name": "x", "ph": "X", "pid": 1, "tid": 0, "ts": 0}]})


def test_validate_bench_envelope():
    meta = {"git_sha": "a", "jax_version": "b", "device_kind": "c",
            "python": "d", "schema": SCHEMA_VERSION}
    validate_bench({"meta": meta, "whatever": 1}, name="X")
    with pytest.raises(ValueError, match="meta"):
        validate_bench({"whatever": 1}, name="X")
    with pytest.raises(ValueError, match="meta.schema"):
        validate_bench({"meta": dict(meta, schema=0)}, name="X")


# ------------------------------------------------------------- histogram
def test_histogram_exact_nearest_rank():
    h = LatencyHistogram("t")
    h.extend(range(1, 101))
    assert h.percentile(50) == 50 and h.percentile(99) == 99
    assert h.percentile(100) == 100 and h.percentile(1) == 1
    doc = h.to_doc()
    validate_metric(doc)
    assert doc["count"] == 100 and doc["p95"] == 95
    single = LatencyHistogram("s")
    single.add(7)
    assert single.percentile(50) == 7 and single.percentile(99) == 7
    empty = LatencyHistogram("e")
    assert empty.percentile(99) == 0.0
    validate_metric(empty.to_doc())
    with pytest.raises(ValueError):
        h.percentile(0)


# ------------------------------------------------------------- exporters
def test_atomic_write_leaves_no_temp(tmp_path):
    path = tmp_path / "out.json"
    atomic_write_text(path, "one")
    atomic_write_text(path, "two")
    assert path.read_text() == "two"
    assert [p.name for p in tmp_path.iterdir()] == ["out.json"]


def test_jsonl_round_trip(tmp_path):
    docs = [metric_doc("span", name=f"s{i}", ts_us=float(i), dur_us=1.0)
            for i in range(3)]
    path = write_jsonl(tmp_path / "m.jsonl", docs)
    assert read_jsonl(path) == docs
    assert validate_metrics_jsonl(path.read_text().splitlines()) == 3


def test_chrome_trace_layout(tmp_path):
    recs = []
    for engine in ("alpha", "beta"):
        for rnd in range(2):
            rec = {f: 0 for f in TRACE_FIELDS}
            rec.update(round=rnd, lane=1, engine=engine)
            recs.append(rec)
    spans = [metric_doc("span", name="compile", ts_us=3.0, dur_us=9.0)]
    doc = chrome_trace(recs, spans, meta={"git_sha": "x"})
    validate_chrome_trace(doc)
    events = doc["traceEvents"]
    xs = [e for e in events if e["ph"] == "X" and e.get("cat") == "round"]
    assert len(xs) == len(recs)
    # one pid per engine, in first-seen order, disjoint from the host pid
    pids = {e["pid"] for e in xs}
    assert pids == {1, 2} and HOST_PID not in pids
    names = {e["args"]["name"] for e in events
             if e["ph"] == "M" and e["name"] == "process_name"}
    assert names == {"host", "alpha", "beta"}
    # logical timebase: round index x ROUND_DUR_US
    assert {e["ts"] for e in xs} == {0, ROUND_DUR_US}
    host = [e for e in events if e["ph"] == "X" and e["pid"] == HOST_PID]
    assert len(host) == 1 and host[0]["dur"] == 9.0
    assert doc["otherData"]["git_sha"] == "x"
    path = write_chrome_trace(tmp_path / "t.json", doc)
    validate_chrome_trace(json.loads(path.read_text()))


def test_trace_collects_and_writes(tmp_path):
    trace = Trace(capacity=8, meta={"git_sha": "deadbeef"})
    ring = trace.ring()
    assert ring.capacity == 8
    for i in range(3):
        ring = ring.record(round=i, pops=i)
    assert trace.drain(ring, engine="e", round_offset=10) == 3
    assert [r["round"] for r in trace.records] == [10, 11, 12]
    with trace.span("compile"):
        pass
    trace.histogram("lat").extend([1, 2, 3])
    with pytest.raises(ValueError):
        trace.add_metric({"kind": "nope"})
    docs = trace.metric_docs()
    assert docs[0]["kind"] == "meta"
    assert docs[0]["git_sha"] == "deadbeef"
    assert validate_metrics_jsonl(json.dumps(d) for d in docs) == len(docs)
    trace.write(tmp_path / "t.json", tmp_path / "m.jsonl")
    validate_chrome_trace(json.loads((tmp_path / "t.json").read_text()))
    validate_metrics_jsonl((tmp_path / "m.jsonl").read_text().splitlines())


# ----------------------------------------------------- parity, all cells
def _cfg_for(cell: str) -> SchedulerConfig:
    # sharded cells run on a degenerate 1-device mesh (tier-1 safe; the
    # 8-device path is exercised by the benchmarks' subprocess children)
    return config_for(SchedulerConfig(num_workers=16, fetch_size=1),
                      parse_policy(cell))


ALL_CELLS = [str(p) for p in POLICY_GRID]


@pytest.mark.parametrize("granularity", [1, 4])
@pytest.mark.parametrize("cell", ALL_CELLS)
def test_tracing_disabled_is_identity(g_rmat, cell, granularity):
    """trace=Trace() is observation only: results, stats and info are
    bit-identical to trace=None, with one ring record per round (times
    the shard count under the sharded topology)."""
    policy = parse_policy(cell)
    if granularity > 1:
        cell = f"{cell}.g{granularity}"
    cfg = _cfg_for(cell)
    program = build_program("bfs", g_rmat, cfg, params={"source": 0})

    base_state, base_stats, base_info = execute(program, g_rmat, cfg)
    trace = Trace()
    tr_state, tr_stats, tr_info = execute(program, g_rmat, cfg, trace=trace)

    assert np.array_equal(np.asarray(program.result(tr_state)),
                          np.asarray(program.result(base_state)))
    assert tr_info == base_info
    assert tr_stats.rounds == base_stats.rounds
    assert tr_stats.items_processed == base_stats.items_processed
    shards = cfg.num_shards if policy.topology == "sharded" else 1
    assert len(trace.records) == base_info["rounds"] * shards
    assert all(r["engine"].startswith(policy.topology)
               for r in trace.records)
    # the records reconcile with the run's own counters
    assert sum(r["pops"] for r in trace.records) == \
        base_stats.items_processed
    if "work" in base_info:
        assert sum(r["work"] for r in trace.records) == base_info["work"]


def test_empty_run_edge(g_rmat):
    """max_rounds=0: the drain loop never iterates; tracing sees nothing
    and parity still holds."""
    import dataclasses

    cfg = dataclasses.replace(_cfg_for("single.persistent"), max_rounds=0)
    program = build_program("bfs", g_rmat, cfg, params={"source": 0})
    _, base_stats, base_info = execute(program, g_rmat, cfg)
    trace = Trace()
    _, tr_stats, tr_info = execute(program, g_rmat, cfg, trace=trace)
    assert base_info["rounds"] == 0 and tr_info == base_info
    assert trace.records == [] and trace.truncated == 0


def test_capacity_truncation_edge(g_rmat):
    """A ring smaller than the round count keeps the newest rounds and
    reports the overwritten count — the flight-recorder contract."""
    cfg = _cfg_for("single.persistent")
    program = build_program("bfs", g_rmat, cfg, params={"source": 0})
    _, _, info = execute(program, g_rmat, cfg)
    rounds = info["rounds"]
    assert rounds > 2, "need a multi-round drain for this edge"
    trace = Trace(capacity=2)
    execute(program, g_rmat, cfg, trace=trace)
    assert len(trace.records) == 2
    assert trace.truncated == rounds - 2
    assert [r["round"] for r in trace.records] == [rounds - 2, rounds - 1]


def test_run_doc_in_registry(g_rmat):
    cfg = _cfg_for("single.persistent")
    program = build_program("bfs", g_rmat, cfg, params={"source": 0})
    trace = Trace()
    execute(program, g_rmat, cfg, trace=trace)
    runs = [d for d in trace.metrics if d["kind"] == "run"]
    assert len(runs) == 1
    assert runs[0]["policy"] == "single.persistent"
    assert runs[0]["rounds"] == len(trace.records)
    assert [s["name"] for s in trace.spans] == ["init", "drain", "readback"]


def test_legacy_list_trace_still_works(g_rmat):
    """What the retired ``trace=list`` hook gave the discrete driver —
    per-round queue size and items — comes from the obs ring's rows, and a
    plain list is refused by name."""
    cfg = _cfg_for("single.discrete")
    program = build_program("bfs", g_rmat, cfg, params={"source": 0})
    trace = Trace()
    _, stats, info = execute(program, g_rmat, cfg, trace=trace)
    assert len(trace.records) == info["rounds"]
    assert sum(r["pops"] for r in trace.records) == int(
        stats.items_processed)
    assert trace.records[0]["queue_size"] == 1   # the seed
    with pytest.raises(TypeError, match="repro.obs.Trace"):
        execute(program, g_rmat, cfg, trace=[])


# ------------------------------------------------- counters & occupancy
def test_work_counter_rounds_single_source_of_truth(g_rmat):
    """WorkCounter.rounds is bumped once per wavefront_step — it matches
    the driver's round count without the driver maintaining it."""
    for cell in ("single.persistent", "single.discrete",
                 "sharded.persistent"):
        cfg = _cfg_for(cell)
        program = build_program("bfs", g_rmat, cfg, params={"source": 0})
        state, stats, info = execute(program, g_rmat, cfg)
        assert int(state.counter.rounds) == stats.rounds == \
            info["rounds"], cell


def _count_popped_degrees(program, graph, sums):
    """``program`` with a body that also hands each round's summed degree
    of the popped tasks to the host (``sums``), outside the counters."""
    import dataclasses

    def make_body(local_graph, ctx):
        f = program.make_body(local_graph, ctx)

        def counted(items, valid, state):
            safe = jnp.where(valid, items, 0)
            deg = jnp.where(valid, graph.row_ptr[safe + 1]
                            - graph.row_ptr[safe], 0)
            jax.debug.callback(lambda d: sums.append(int(d)), jnp.sum(deg))
            return f(items, valid, state)
        return counted

    return dataclasses.replace(program, make_body=make_body)


@pytest.mark.parametrize("cell", ["single.persistent", "sharded.persistent"])
def test_units_field_counts_the_expansions_work_units(g_rmat, cell):
    """With a budget no wavefront reaches, each round's merge-path
    expansion produces one unit per edge of the popped tasks: the ring's
    ``units`` rows sum to the popped tasks' summed degrees."""
    cfg = _cfg_for(cell)
    budget = int(g_rmat.row_ptr[-1])
    program = build_program("bfs", g_rmat, cfg,
                            params={"source": 0, "work_budget": budget})
    sums = []
    trace = Trace()
    state, _, info = execute(_count_popped_degrees(program, g_rmat, sums),
                             g_rmat, cfg, trace=trace)
    jax.effects_barrier()
    assert len(sums) == info["rounds"]
    assert sum(r["units"] for r in trace.records) == sum(sums) > 0
    assert int(state.counter.units) == sum(sums)


def test_units_field_costs_one_add_with_the_ring_off(g_rmat, monkeypatch):
    """Ring off, the compiled drain's while body computes nothing more
    than with the ``units`` count taken out, bar the one scalar add."""
    import re
    from collections import Counter

    from repro.core.scheduler import persistent_drive
    from repro.runtime.api import _shared_setup
    from repro.runtime.policy import policy_of

    cfg = _cfg_for("single.persistent")
    plumbing = {"get-tuple-element", "tuple", "parameter", "constant",
                "copy", "bitcast"}

    def body_ops():
        program = build_program("bfs", g_rmat, cfg, params={"source": 0})
        queue, state, _, step, cond, _ = _shared_setup(
            program, g_rmat, cfg, policy_of(cfg), None)
        carry0 = (queue, state, jnp.int32(0), jnp.int32(0))
        text = jax.jit(lambda c: persistent_drive(step, cond, c)).lower(
            carry0).compile().as_text()
        body, = re.findall(r"while\(.*?body=(%?[\w.\-]+)", text)
        block = re.search("^" + re.escape(body) + r" .*?\{\n(.*?)\n\}",
                          text, re.S | re.M).group(1)
        ops = Counter(re.findall(r"= \S+ ([\w\-]+)\(", block))
        return sum(n for op, n in ops.items() if op not in plumbing)

    counted = body_ops()
    monkeypatch.setattr(WorkCounter, "add_units",
                        lambda self, total, budget: self)
    assert 0 <= counted - body_ops() <= 1


def test_occupancy_vertex_denominated_at_g4():
    """The granularity > 1 occupancy fix: the numerator counts vertices
    (chunk-width weighted), the denominator counts the vertex budget
    rounds_active x wavefront x G."""
    t = JobTelemetry(job_id=0, algorithm="bfs", graph="g", wavefront=8,
                     ideal_work=64, rounds_active=2, items_processed=10,
                     vertices_processed=40, granularity=4)
    assert t.occupancy == pytest.approx(40 / (2 * 8 * 4))
    # the pre-fix item-over-slot accounting would have read 10/(2*8) —
    # claiming 62% while the vertex budget was only 62.5% filled by luck;
    # make the distinction explicit with a chunk-heavy tenant:
    t2 = JobTelemetry(job_id=0, algorithm="bfs", graph="g", wavefront=8,
                      ideal_work=64, rounds_active=1, items_processed=8,
                      vertices_processed=32, granularity=4)
    assert t2.occupancy == pytest.approx(1.0)   # 8 width-4 chunks fill W*G
    assert t2.occupancy <= 1.0
    # granularity 1 reduces to the legacy item/slot accounting
    t3 = JobTelemetry(job_id=0, algorithm="bfs", graph="g", wavefront=8,
                      ideal_work=64, rounds_active=2, items_processed=10,
                      vertices_processed=10, granularity=1)
    assert t3.occupancy == pytest.approx(10 / 16)
    # legacy unmetered paths fall back to items
    t4 = JobTelemetry(job_id=0, algorithm="bfs", graph="g", wavefront=8,
                      ideal_work=64, rounds_active=2, items_processed=10,
                      vertices_processed=0, granularity=1)
    assert t4.occupancy == pytest.approx(10 / 16)
    validate_metric(t.as_dict())


def test_server_occupancy_bounded_at_g4(g_rmat):
    """Regression: at granularity 4 a server tenant's occupancy stays a
    fraction of the vertex budget (<= 1) and vertex metering engages."""
    from repro.server import JobRegistry, JobSpec, TaskServer

    reg = JobRegistry()
    reg.register_graph("g", g_rmat)
    cfg = SchedulerConfig(num_workers=16, fetch_size=1, granularity=4)
    server = TaskServer(reg, num_lanes=2, config=cfg)
    server.submit(JobSpec("bfs", "g", {"source": 0}))
    server.submit(JobSpec("coloring", "g"))
    result = server.run()
    for t in result.telemetry.values():
        assert 0.0 < t.occupancy <= 1.0, t
        assert t.granularity == 4
        assert t.vertices_processed >= t.items_processed > 0


# -------------------------------------------------- traced server/stream
def test_traced_server_reconciles(g_grid, g_rmat):
    from repro.server import JobRegistry, JobSpec, TaskServer

    reg = JobRegistry()
    reg.register_graph("grid", g_grid)
    reg.register_graph("rmat", g_rmat)
    specs = [JobSpec("bfs", "grid", {"source": 0}),
             JobSpec("pagerank", "rmat", {"eps": 1e-4}),
             JobSpec("coloring", "grid")]
    cfg = SchedulerConfig(num_workers=16, fetch_size=1)

    base = TaskServer(reg, num_lanes=2, config=cfg)
    for s in specs:
        base.submit(s)
    base_result = base.run()

    trace = Trace()
    traced = TaskServer(reg, num_lanes=2, config=cfg, trace=trace)
    for s in specs:
        traced.submit(s)
    tr_result = traced.run()

    # observation only: same rounds, same per-job telemetry
    assert tr_result.stats.rounds == base_result.stats.rounds
    for job_id, t in base_result.telemetry.items():
        t2 = tr_result.telemetry[job_id]
        assert (t2.items_processed, t2.latency_rounds, t2.work) == \
            (t.items_processed, t.latency_rounds, t.work)
    # ring rows reconcile with the server's own counters
    server_rows = [r for r in trace.records if r["engine"] == "server"]
    assert sum(r["pops"] for r in server_rows) == \
        tr_result.stats.items_processed
    assert {r["lane"] for r in server_rows} <= {0, 1}
    # registry: one server doc + one job doc per tenant, all schema-valid
    kinds = [d["kind"] for d in trace.metrics]
    assert kinds.count("server") == 1 and kinds.count("job") == len(specs)
    # per-job latency histograms with exact percentiles
    lat = trace.histograms["job_latency_rounds"]
    assert lat.count == len(specs)
    expected = sorted(t.latency_rounds
                      for t in tr_result.telemetry.values())
    assert lat.percentile(100) == expected[-1]
    for job_id in tr_result.telemetry:
        assert trace.histograms[
            f"job{job_id}_latency_rounds"].count == 1


def test_traced_stream_absolute_rounds(g_rmat):
    from repro.graph.generators import edge_delta_stream

    deltas = edge_delta_stream(g_rmat, 3, 16, seed=5)
    cfg = SchedulerConfig(num_workers=16, topology="single",
                          persistent=False)
    base = stream_execute("bfs", g_rmat, deltas, cfg,
                          params={"source": 0})
    trace = Trace()
    traced = stream_execute("bfs", g_rmat, deltas, cfg,
                            params={"source": 0}, trace=trace)
    assert np.array_equal(np.asarray(traced.result),
                          np.asarray(base.result))
    # schedule determinism: every counter identical; commit_seconds is the
    # one wall-clock meter in stream info, so it alone may differ
    drop = lambda d: {k: v for k, v in d.items() if k != "commit_seconds"}
    assert drop(traced.info) == drop(base.info)
    # one record per round across ALL batches, on an absolute round axis
    assert len(trace.records) == base.info["rounds"]
    assert sorted(r["round"] for r in trace.records) == \
        list(range(base.info["rounds"]))
    assert {r["engine"] for r in trace.records} == {"stream.bfs"}
    stream_docs = [d for d in trace.metrics if d["kind"] == "stream"]
    assert len(stream_docs) == 1
    assert stream_docs[0]["rounds"] == base.info["rounds"]
