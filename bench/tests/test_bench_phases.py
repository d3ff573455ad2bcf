"""The reduction of the program's own names (``bench/phases.py``):
hand-made events whose answers are known, a small trace recorded on a v5e
(two searches of a scale-14 Kronecker cell, with scopes and ``atos:``
spans) whose readings are pinned, and a CPU run of the tool at a small
size (no device plane there, so every reading is None)."""
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import phases
from bench.phases import ScopedEvent
from bench.trace import Event, Profile


def test_scope_is_the_innermost_phase():
    assert phases.scope_of(
        "jit(f)/while/body/cond/branch_1_fun/atos.expand/"
        "jit(frontier_expand)/gather") == "atos.expand"
    assert phases.scope_of("a/atos.push/atos.exchange/all-to-all") == \
        "atos.exchange"
    assert phases.scope_of("jit(f)/while/body/add") == ""


def test_recorded_rows_with_and_without_a_scope():
    prof = phases.from_json({"devices": 1, "ops": [
        ["%a = f32[] add()", 0, 5, 0, "atos.pop"], ["%b = f32[] add()", 5, 9, 0]],
        "spans": [["job", 0, 9, 0]]})
    assert [e.scope for e in prof.ops] == ["atos.pop", ""]
    assert prof.spans == [Event("job", 0, 9, 0)]


def _run():
    # two searches of two rounds each; a while op encloses each drain
    ops = [ScopedEvent("%while.1 = () while()", 100, 180),
           ScopedEvent("%pop", 100, 110, 0, "atos.pop"),
           ScopedEvent("%gather", 110, 140, 0, "atos.expand"),
           ScopedEvent("%scatter", 140, 160, 0, "atos.apply"),
           ScopedEvent("%compact", 160, 170, 0, "atos.push"),
           ScopedEvent("%add", 170, 180, 0, ""),
           ScopedEvent("%while.1 = () while()", 300, 340),
           ScopedEvent("%pop", 300, 310, 0, "atos.pop"),
           ScopedEvent("%gather", 310, 330, 0, "atos.expand"),
           ScopedEvent("%compact", 330, 340, 0, "atos.push")]
    spans = [Event("job", 0, 200), Event("atos:build_program", 0, 20),
             Event("atos:init", 20, 40), Event("atos:drain", 40, 180),
             Event("lower_sharding_computation", 45, 90),
             Event("atos:readback", 180, 200),
             Event("job", 200, 400), Event("atos:build_program", 200, 210),
             Event("atos:init", 210, 230), Event("atos:drain", 230, 340),
             Event("atos:readback", 340, 400)]
    jobs = [{"rounds": 2}, {"rounds": 3}]
    prof = Profile(ops=sorted(ops, key=lambda e: e.start_ns),
                   spans=sorted(spans, key=lambda e: e.start_ns), devices=1)
    return type("Run", (), {"profile": prof, "jobs": jobs,
                            "job_spans": lambda self: [
                                s for s in prof.spans if s.name == "job"]})()


def test_phases_split_the_busy_time_per_round():
    run = _run()
    got = {name: read(run) for name, read in phases.READERS.items()}
    assert got["pop_ms"] == pytest.approx(20 / 1e6 / 5)
    assert got["expand_ms"] == pytest.approx(50 / 1e6 / 5)
    assert got["apply_ms"] == pytest.approx(20 / 1e6 / 5)
    assert got["push_ms"] == pytest.approx(20 / 1e6 / 5)
    assert phases.phase_ms(run, "") == pytest.approx(10 / 1e6 / 5)
    # drains: 140 - 80 busy and 110 - 40 busy; init: 40 and 30, all idle
    assert got["drain_host_ms"] == pytest.approx((60 + 70) / 1e6 / 2)
    assert got["init_host_ms"] == pytest.approx((40 + 30) / 1e6 / 2)


def test_gaps_are_named_by_the_program_span_inside_execute():
    run = _run()
    chains = phases.gap_chains(run.profile, 0, 400, top=2)
    # the longest gap, 180..300, ends in the second drain's re-trace
    assert chains[0] == [["job", "atos:drain"], 120e-9]
    # the first one, 0..100, has a compile-path event at its middle
    assert chains[1] == [["job", "atos:drain", "lower_sharding_computation"],
                         100e-9]


def test_trace_without_scopes_reads_none():
    run = _run()
    run.profile.ops = [Event(e.name, e.start_ns, e.end_ns) for e in
                       run.profile.ops]
    assert phases.pop_ms(run) is None
    assert phases.drain_host_ms(run) is not None


def test_tool_runs_a_small_cell_on_cpu(small_cell):
    cell = small_cell("kron20.bfs")
    out = phases.measure(cell, seed=3, searches=2, ring=True)
    assert len(out["jobs"]) == 2 and all(j["rounds"] > 0 for j in out["jobs"])
    assert all(value is None for value in out["readings"].values())
    assert out["evps"] > 0


RECORDED = Path(__file__).resolve().parent / "data" / "small_phase_trace.json"


def test_recorded_trace_reduces_to_its_pinned_readings():
    doc = json.loads(RECORDED.read_text())
    prof = phases.from_json(doc)
    expected = doc["expected"]
    run = SimpleNamespace(profile=prof, jobs=expected["jobs"],
                          job_spans=lambda: [s for s in prof.spans
                                             if s.name == "job"])
    got = phases.readings(run)
    for name in phases.READERS:
        assert got[name] == pytest.approx(expected["readings"][name],
                                          rel=1e-9), name
    # the four phases are the round: scoped leaves cover >= 95% of it
    rounds = sum(got[f"{p}_ms"] for p in ("pop", "expand", "apply", "push"))
    assert rounds >= 0.95 * got["round_ms"]
    assert got["unscoped_ms"] < 0.05 * got["round_ms"]
    # the host steps the program names cover the search's idle time
    assert got["drain_host_ms"] + got["init_host_ms"] <= \
        got["host_ms_per_job"]
    # and every long idle gap is named by one of them, not by `execute`
    jobs = run.job_spans()
    for chain, seconds in phases.gap_chains(prof, jobs[0].start_ns,
                                            jobs[-1].end_ns):
        if seconds >= 0.01:
            assert any(n.startswith(phases.PROGRAM_SPAN) for n in chain)


def test_op_scopes_read_the_tf_op_stat_of_event_metadata(tmp_path):
    space = phases._xspace()()
    plane = space.planes.add(name="/device:TPU:0")
    stat = plane.stat_metadata.add(key=7)
    stat.value.name = "tf_op"
    other = plane.stat_metadata.add(key=8)
    other.value.name = "hlo_category"
    for i, (op, tf_op) in enumerate([
            ("%fusion.21 = s32[8] fusion()",
             "jit(while)/while/body/atos.expand/jit(frontier_expand)/gather:"),
            ("%copy.3 = s32[8] copy()", "jit(while)/while/body/copy:")]):
        meta = plane.event_metadata.add(key=i)
        meta.value.name = op
        meta.value.stats.add(metadata_id=8, str_value="loop fusion")
        meta.value.stats.add(metadata_id=7, str_value=tf_op)
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(space.SerializeToString())
    assert phases.op_scopes(str(path)) == {
        "%fusion.21 = s32[8] fusion()": "atos.expand"}
