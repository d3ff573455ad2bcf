"""The reduction from trace events to metrics: hand-made events whose
answers are known, and a small trace recorded on a v5e (two searches of a
scale-14 Kronecker cell) whose answers are pinned."""
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import trace
from bench.trace import Event, Profile

RECORDED = Path(__file__).resolve().parent / "data" / "small_trace.json"


def _ops():
    # a while op enclosing two rounds of two ops, then an op after a gap
    return [Event("%while.1 = () while()", 0, 100),
            Event("%cond.2 = () conditional()", 0, 50),
            Event("%fusion.1 = s32[8] fusion()", 0, 30),
            Event("%lbs_pallas.6 = () custom-call()", 30, 50),
            Event("%cond.2 = () conditional()", 50, 100),
            Event("%fusion.1 = s32[8] fusion()", 50, 80),
            Event("%lbs_pallas.6 = () custom-call()", 80, 100),
            Event("%copy.3 = s32[8] copy()", 150, 160)]


def test_merged_is_the_union():
    assert trace.merged([(5, 7), (0, 2), (1, 3), (7, 9)]) == [(0, 3), (5, 9)]


def test_busy_and_gaps_cover_the_window():
    ops = _ops()
    assert trace.busy_ns(ops, 0, 200) == 110
    assert trace.busy_ns(ops, 90, 155) == 15
    assert trace.idle_gaps(ops, 0, 200) == [(100, 150), (160, 200)]
    assert trace.idle_gaps(ops, -10, 120) == [(-10, 0), (100, 120)]


def test_busy_averages_over_chips():
    ops = [Event("a", 0, 10, 0), Event("b", 0, 30, 1)]
    assert trace.busy_ns(ops, 0, 40) == 20


def test_leaves_drop_control_flow():
    names = [e.name.split()[0] for e in trace.leaves(_ops())]
    assert names == ["%fusion.1", "%lbs_pallas.6", "%fusion.1",
                     "%lbs_pallas.6", "%copy.3"]


def test_gaps_are_named_by_the_innermost_span():
    spans = [Event("job", 0, 200), Event("execute", 0, 130),
             Event("build_program", 140, 170)]
    prof = Profile(ops=_ops(), spans=spans, devices=1)
    out = trace.breakdown(prof, 0, 200, top=3)
    assert out["idle_gaps"] == [["execute", 50e-9], ["job", 40e-9]]
    top = dict(out["device_ops"])
    assert top["%fusion.1 = s32[8] fusion()"] == pytest.approx(60e-9)
    assert "%while.1 = () while()" not in top
    assert trace.span_at(spans, 500) == "outside any span"


def _recorded():
    doc = json.loads(RECORDED.read_text())
    return Profile.from_json(doc), doc["expected"]


def test_recorded_trace_reduces_to_its_pinned_metrics():
    from bench.metrics import (compact_roofline, device_idle_share,
                               host_ms_per_job, lbs_roofline, round_ms)

    prof, expected = _recorded()
    spans = [s for s in prof.spans if s.name == "job"]
    run = SimpleNamespace(
        profile=prof, jobs=expected["jobs"], peaks={"hbm_bytes_per_s": 819e9},
        window_ns=(spans[0].start_ns, spans[-1].end_ns),
        job_spans=lambda: spans)
    got = {"round_ms": round_ms.read(run),
           "host_ms_per_job": host_ms_per_job.read(run),
           "device_idle_share": device_idle_share.read(run),
           "lbs_roofline": lbs_roofline.read(run),
           "compact_roofline": compact_roofline.read(run)}
    for name, value in expected["metrics"].items():
        assert got[name] == pytest.approx(value, rel=1e-9), name
    # the device time of a search is its drain: the while loops' own span
    drains = sum(e.dur_ns for e in prof.ops if e.name.startswith("%while"))
    busy = trace.busy_ns(prof.ops, *run.window_ns)
    assert busy == pytest.approx(expected["busy_s"] * 1e9, rel=1e-9)
    assert 0.9 * busy < drains <= busy
    assert 0 < got["lbs_roofline"] < 100 and 0 < got["compact_roofline"] < 100
    assert 0 <= got["device_idle_share"] < 100
