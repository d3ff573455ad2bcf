"""Round phases and host steps of a traced cell, read off the program's
own names (``repro.obs.spans``).

The program names each phase of a scheduling round with a device scope
(``atos.pop``, ``atos.expand``, ``atos.apply``, ``atos.push``, and
``atos.on_empty`` / ``atos.ring`` / ``atos.exchange`` where they run): the
scope sits in the ``op_name`` of every XLA op the phase lowers to, which
the TPU profiler keeps as the ``tf_op`` stat of each op's event metadata
(:func:`op_scopes`).  It
names each host step of ``execute`` with an ``atos:`` span
(``atos:build_program``, ``atos:init``, ``atos:drain``,
``atos:readback``), and JAX's own compile-path events nest inside
``atos:drain``.

``bench/trace.load`` keeps neither (host spans that start ``bench:`` and
each op's HLO text only), so no cell of ``BENCHMARK.json`` reads these
numbers yet.  :func:`load` is the reduction that does, checked on a small
recorded trace (``bench/tests/data/small_phase_trace.json``), and the
readers below are the per-layer metrics it gives, in the form of
``bench/metrics/*.py``.  Run alone, this module traces a few searches of a
cell on the chip and prints them as one JSON line:

    python3 bench/phases.py --workload rgg20.bfs --seed 7 --searches 4

``--ring 1`` passes an ``obs.Trace`` to ``execute`` (the device ring rides
the drain), ``--scale`` cuts the graph, ``--record`` writes the trace's
ops and spans in the recorded-trace form.
"""
from __future__ import annotations

import dataclasses
import json
import re
import sys
import time
from pathlib import Path
from typing import Iterable, List, Optional

BENCH = Path(__file__).resolve().parent
for _path in (str(BENCH.parent / "src"), str(BENCH.parent)):
    if _path not in sys.path:
        sys.path.insert(0, _path)

from bench import trace  # noqa: E402

#: prefix of the program's host spans; its device scopes start ``atos.``
PROGRAM_SPAN = "atos:"
#: the host steps of a search whose device-idle time is init's
INIT_STEPS = ("atos:build_program", "atos:init")
DRAIN_STEP = "atos:drain"
#: the planes of the chips in a trace
DEVICE_PLANE = "/device:TPU:"
#: the stat of a device op's event metadata that holds its op_name
OP_NAME_STAT = "tf_op"
_SCOPE = re.compile(r"atos\.[a-z_]+")


@dataclasses.dataclass(frozen=True)
class ScopedEvent(trace.Event):
    """A device op with the innermost ``atos.<phase>`` of its op_name
    (``""`` when unscoped)."""
    scope: str = ""


def scope_of(op_name: str) -> str:
    """The innermost ``atos.<phase>`` of an op_name path, or ``""``."""
    found = _SCOPE.findall(op_name)
    return found[-1] if found else ""


def from_json(doc: dict) -> trace.Profile:
    """From the recorded-trace form: op rows ``[name, start_ns, end_ns,
    device]`` with an optional fifth element, the scope."""
    return trace.Profile(ops=[ScopedEvent(*e) for e in doc["ops"]],
                         spans=[trace.Event(*e) for e in doc["spans"]],
                         devices=doc["devices"])


def _nested(events: list, outer: list) -> list:
    """The events of ``events`` that lie inside one of ``outer``."""
    return [e for e in events if any(
        o.start_ns <= e.start_ns and e.end_ns <= o.end_ns for o in outer)]


def _xspace():
    """The ``XSpace`` message (tsl/profiler/protobuf/xplane.proto) down to
    each plane's event metadata and its string stats; every other field
    is skipped."""
    from google.protobuf import (descriptor_pb2, descriptor_pool,
                                 message_factory)
    from google.protobuf.descriptor_pb2 import FieldDescriptorProto as F

    one, many = F.LABEL_OPTIONAL, F.LABEL_REPEATED
    spec = {
        "XStat": [("metadata_id", 1, F.TYPE_INT64, one),
                  ("str_value", 5, F.TYPE_STRING, one)],
        "XEventMetadata": [("name", 2, F.TYPE_STRING, one),
                           ("stats", 5, F.TYPE_MESSAGE, many, "XStat")],
        "XStatMetadata": [("name", 2, F.TYPE_STRING, one)],
        "EventMetadataEntry": [("key", 1, F.TYPE_INT64, one),
                               ("value", 2, F.TYPE_MESSAGE, one,
                                "XEventMetadata")],
        "StatMetadataEntry": [("key", 1, F.TYPE_INT64, one),
                              ("value", 2, F.TYPE_MESSAGE, one,
                               "XStatMetadata")],
        "XPlane": [("name", 2, F.TYPE_STRING, one),
                   ("event_metadata", 4, F.TYPE_MESSAGE, many,
                    "EventMetadataEntry"),
                   ("stat_metadata", 5, F.TYPE_MESSAGE, many,
                    "StatMetadataEntry")],
        "XSpace": [("planes", 1, F.TYPE_MESSAGE, many, "XPlane")],
    }
    package = "atos_bench_xplane"
    proto = descriptor_pb2.FileDescriptorProto(
        name=f"{package}.proto", package=package, syntax="proto3")
    for name, fields in spec.items():
        msg = proto.message_type.add(name=name)
        for field, number, kind, label, *ref in fields:
            fd = msg.field.add(name=field, number=number, type=kind,
                               label=label)
            if ref:
                fd.type_name = f".{package}.{ref[0]}"
    pool = descriptor_pool.DescriptorPool()
    pool.Add(proto)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName(f"{package}.XSpace"))


def op_scopes(path: str) -> dict:
    """``{op: scope}`` for the device ops of a trace: the innermost
    ``atos.<phase>`` of the op's ``op_name``, which the TPU profiler keeps
    as the ``tf_op`` stat of the op's event metadata (``jit(f)/atos.pop/
    gather:``), keyed by the op's name on the ``XLA Ops`` line (its HLO
    text).  ``jax.profiler.ProfileData`` gives events their own stats
    only, so the metadata is read from the ``.xplane.pb`` itself."""
    space = _xspace().FromString(Path(path).read_bytes())
    out = {}
    for plane in space.planes:
        if not plane.name.startswith(DEVICE_PLANE):
            continue
        tf_op = {e.key for e in plane.stat_metadata
                 if e.value.name == OP_NAME_STAT}
        for entry in plane.event_metadata:
            for stat in entry.value.stats:
                if stat.metadata_id in tf_op and "atos." in stat.str_value:
                    out.setdefault(entry.value.name,
                                   scope_of(stat.str_value))
    return out


def load(path: str) -> trace.Profile:
    """Device ops with their scope (:func:`op_scopes`), and the host
    spans: the benchmark's (``bench:`` stripped, as ``bench/trace.load``),
    the program's (``atos:`` kept) and the host events nested in an
    ``atos:drain`` on its thread (JAX's re-trace, lowering, compile and
    dispatch)."""
    from jax.profiler import ProfileData

    scopes = op_scopes(path)
    ops, spans, devices = [], [], 0
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(DEVICE_PLANE):
            device = int(plane.name.split(":")[2].split()[0])
            devices += 1
            for line in plane.lines:
                if line.name != trace.OPS_LINE:
                    continue
                ops.extend(ScopedEvent(ev.name, ev.start_ns,
                                       ev.start_ns + ev.duration_ns, device,
                                       scopes.get(ev.name, ""))
                           for ev in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                events = [trace.Event(ev.name, ev.start_ns,
                                      ev.start_ns + ev.duration_ns)
                          for ev in line.events]
                drains = [e for e in events if e.name == DRAIN_STEP]
                for e in events:
                    if e.name.startswith(trace.SPAN_PREFIX):
                        spans.append(dataclasses.replace(
                            e, name=e.name[len(trace.SPAN_PREFIX):]))
                    elif e.name.startswith(PROGRAM_SPAN):
                        spans.append(e)
                spans.extend(e for e in _nested(events, drains)
                             if not e.name.startswith(PROGRAM_SPAN))
    ops.sort(key=lambda e: e.start_ns)
    spans.sort(key=lambda e: e.start_ns)
    return trace.Profile(ops=ops, spans=spans, devices=devices)


# ------------------------------------------------------------------ readers
def _job_spans(run) -> list:
    return [s for s in run.profile.spans if s.name == "job"]


def phase_ms(run, scope: str) -> Optional[float]:
    """Device-busy ms per round of the leaf ops scoped ``scope``, inside
    the searches' spans (``""``: the unscoped leaf ops)."""
    if run.profile is None or not run.profile.ops:
        return None
    spans = _job_spans(run)
    if len(spans) != len(run.jobs):
        return None
    leaves = trace.leaves(run.profile.ops)
    if not any(getattr(e, "scope", "") for e in leaves):
        return None                    # a trace without scopes
    mine = [e for e in leaves if getattr(e, "scope", "") == scope]
    busy = sum(trace.busy_ns(mine, s.start_ns, s.end_ns, device=0)
               for s in spans)
    return busy / 1e6 / sum(job["rounds"] for job in run.jobs)


def host_ms(run, steps: Iterable[str]) -> Optional[float]:
    """Ms per search inside the program's host spans named ``steps`` in
    which the device ran nothing."""
    if run.profile is None or not run.profile.ops:
        return None
    jobs = _job_spans(run)
    mine = _nested([s for s in run.profile.spans if s.name in steps], jobs)
    if not jobs or not mine:
        return None
    idle = sum(s.dur_ns - trace.busy_ns(run.profile.ops, s.start_ns,
                                        s.end_ns) for s in mine)
    return idle / 1e6 / len(jobs)


def pop_ms(run):
    return phase_ms(run, "atos.pop")


def expand_ms(run):
    return phase_ms(run, "atos.expand")


def apply_ms(run):
    return phase_ms(run, "atos.apply")


def push_ms(run):
    return phase_ms(run, "atos.push")


def drain_host_ms(run):
    return host_ms(run, (DRAIN_STEP,))


def init_host_ms(run):
    return host_ms(run, INIT_STEPS)


#: the six readings, by the name a metric of ``BENCHMARK.json`` would give
READERS = {"pop_ms": pop_ms, "expand_ms": expand_ms, "apply_ms": apply_ms,
           "push_ms": push_ms, "drain_host_ms": drain_host_ms,
           "init_host_ms": init_host_ms}


def readings(run) -> dict:
    """The six readings, the other scopes' ms per round, and the existing
    ``round_ms`` / ``host_ms_per_job`` read from the same trace."""
    from bench.metrics import host_ms_per_job, round_ms

    out = {name: read(run) for name, read in READERS.items()}
    for scope in ("atos.on_empty", "atos.ring", "atos.exchange", ""):
        out[f"{scope.replace('atos.', '') or 'unscoped'}_ms"] = phase_ms(
            run, scope)
    out["readback_host_ms"] = host_ms(run, ("atos:readback",))
    out["round_ms"] = round_ms.read(run)
    out["host_ms_per_job"] = host_ms_per_job.read(run)
    return out


def gap_chains(profile: trace.Profile, lo: float, hi: float,
               top: int = 10) -> List[list]:
    """The ``top`` longest idle gaps of chip 0 in [lo, hi], each with the
    host spans that cover its middle, outermost first, and its seconds."""
    gaps = sorted(trace.idle_gaps(profile.ops, lo, hi),
                  key=lambda g: g[1] - g[0], reverse=True)[:top]
    out = []
    for a, b in gaps:
        mid = (a + b) / 2
        chain = sorted((s for s in profile.spans
                        if s.start_ns <= mid <= s.end_ns),
                       key=lambda s: -s.dur_ns)
        out.append([[s.name for s in chain], (b - a) / 1e9])
    return out


# ---------------------------------------------------------------- the tool
def measure(cell, seed: int, searches: int, ring: bool,
            record: Optional[str] = None) -> dict:
    """Trace ``searches`` searches of ``cell`` (after one warm-up) and
    reduce the trace; ``ring`` passes an ``obs.Trace`` to ``execute``."""
    import shutil
    import tempfile

    import jax
    import numpy as np

    from bench import device as chip
    from bench import run as harness
    from bench import traffic as traffic_mod
    from repro.graph.csr import CSRGraph
    from repro.obs import Trace
    from repro.runtime import build_program, execute

    dev = chip.require_chip(jax, cell.chips)
    chip.use_compile_cache(jax)
    config, traffic = cell.config, cell.traffic
    family = harness._module("graphs", config["family"])
    job_mod = harness._module("jobs", traffic["job"])
    made = family.generate(config, seed)
    graph = CSRGraph(row_ptr=made["row_ptr"], col_idx=made["col_idx"])
    degrees = np.diff(np.asarray(made["row_ptr"]))
    runner = job_mod.Jobs(graph, config["scheduler"])
    span = harness.span

    def search(root: int) -> dict:
        start = time.perf_counter()
        with span("build_program"):
            program = build_program("bfs", graph, runner.cfg, {
                "source": root,
                "work_budget": config["scheduler"]["work_budget"]})
        with span("execute"):
            res = execute(program, graph, runner.cfg,
                          trace=Trace() if ring else None)
            dist = jax.block_until_ready(res.state.dist)
        return {"root": root, "dist": dist, "rounds": res.info["rounds"],
                "pops": int(res.stats.items_processed),
                "work": int(res.info["work"]),
                "wall_s": time.perf_counter() - start}

    warm = search(traffic_mod.warmup_root(traffic, degrees))
    reached = np.asarray(warm["dist"]) != harness.INF
    roots = traffic_mod.roots(traffic, seed, reached, degrees,
                              made.get("points"))
    log_dir = tempfile.mkdtemp(prefix="bench_phases_")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(log_dir, profiler_options=options)
    jobs = []
    for i in range(searches):
        with span("job"):
            jobs.append(search(roots[i % len(roots)]))
    jax.profiler.stop_trace()
    for job in jobs:
        del job["dist"]
    profile = load(trace.xplane_file(log_dir))
    shutil.rmtree(log_dir)
    n, m = degrees.shape[0], made["m"]
    run = harness.RunRecord(n=n, m=m, wavefront=runner.wavefront, setup={},
                            jobs=jobs, peaks=None, profile=profile)
    out = {"workload": cell.name, "seed": seed, "ring": ring,
           "device": dev, "scale": config.get("scale"),
           "jobs": jobs,
           "evps": (n + m // 2) * len(jobs)
           / sum(job["wall_s"] for job in jobs)}
    out["readings"] = readings(run)
    spans = run.job_spans()
    if profile.ops and spans:
        lo, hi = spans[0].start_ns, spans[-1].end_ns
        out["breakdown"] = trace.breakdown(profile, lo, hi)
        out["gap_chains"] = gap_chains(profile, lo, hi)
        if record:
            _record(record, profile, lo, hi, out)
    return out


def _record(path: str, profile: trace.Profile, lo: float, hi: float,
            out: dict) -> None:
    """Write the window's ops and spans, times from the window's start,
    with the readings they give, as a recorded test trace."""
    def rows(events, scoped):
        return [[e.name, e.start_ns - lo, e.end_ns - lo, e.device]
                + ([e.scope] if scoped and e.scope else [])
                for e in events if e.end_ns > lo and e.start_ns < hi]

    doc = {"about": f"{len(out['jobs'])} searches of {out['workload']} cut "
                    f"to scale {out['scale']} (seed {out['seed']}) on one "
                    f"{out['device']['kind']}, traced by bench/phases.py; "
                    f"ops with their atos scope, spans of the window, and "
                    f"the readings of the full trace",
           "devices": profile.devices,
           "ops": rows(profile.ops, True),
           "spans": rows(profile.spans, False),
           "expected": {"jobs": [{"rounds": j["rounds"]}
                                 for j in out["jobs"]],
                        "readings": out["readings"]}}
    Path(path).parent.mkdir(parents=True, exist_ok=True)
    Path(path).write_text(json.dumps(doc))


def main(argv=None) -> int:
    import argparse

    from bench import device as chip
    from bench import run as harness

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--searches", type=int, default=4)
    ap.add_argument("--ring", type=int, choices=(0, 1), default=0)
    ap.add_argument("--scale", type=int, default=None,
                    help="cut the configuration's graph to this scale")
    ap.add_argument("--record", default=None,
                    help="write the traced window as a recorded trace")
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    if args.scale is not None:
        cell.config = dict(cell.config, scale=args.scale)
    try:
        out = measure(cell, args.seed, args.searches, bool(args.ring),
                      args.record)
    except chip.NoChip as e:
        print(f"bench/phases.py: {e}", file=sys.stderr)
        return 2
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
