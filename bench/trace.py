"""Reduction of a profiler trace to what the per-layer metrics read.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into two
lists of events on one clock: the device's operations (the ``XLA Ops``
line of each TPU plane, each event named by its HLO text; control flow
such as ``while`` is there too, enclosing the operations it runs) and the
benchmark's own host spans (its ``TraceAnnotation`` names, which all
start with ``SPAN_PREFIX``).  The
rest works on those lists alone, so it is tested on a small recorded
trace without a chip.
"""
from __future__ import annotations

import dataclasses
import glob
import os
from collections import Counter
from typing import Iterable, List, Optional, Tuple

SPAN_PREFIX = "bench:"
#: characters of an operation's HLO text kept in a breakdown
NAME_CHARS = 160
#: the device line whose events are the operations XLA ran
OPS_LINE = "XLA Ops"


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    end_ns: float
    device: int = 0

    @property
    def dur_ns(self) -> float:
        return self.end_ns - self.start_ns


@dataclasses.dataclass
class Profile:
    ops: List[Event]      # device operations, every chip
    spans: List[Event]    # the benchmark's host spans
    devices: int          # chips seen in the trace

    @staticmethod
    def from_json(doc: dict) -> "Profile":
        """From ``{"devices": k, "ops": [[name, start_ns, end_ns, device],
        ...], "spans": [...]}``, the form of a recorded test trace."""
        return Profile(ops=[Event(*e) for e in doc["ops"]],
                       spans=[Event(*e) for e in doc["spans"]],
                       devices=doc["devices"])


def xplane_file(log_dir: str) -> str:
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, "
                           f"found {files}")
    return files[0]


def load(path: str) -> Profile:
    """Device operations and benchmark spans of one ``.xplane.pb``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops, spans, devices = [], [], 0
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            device = int(plane.name.split(":")[2].split()[0])
            devices += 1
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    ops.append(Event(ev.name, ev.start_ns,
                                     ev.start_ns + ev.duration_ns, device))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append(Event(ev.name[len(SPAN_PREFIX):],
                                           ev.start_ns,
                                           ev.start_ns + ev.duration_ns))
    ops.sort(key=lambda e: e.start_ns)
    spans.sort(key=lambda e: e.start_ns)
    return Profile(ops=ops, spans=spans, devices=devices)


def merged(intervals: Iterable[Tuple[float, float]]
           ) -> List[Tuple[float, float]]:
    """The union of intervals, as sorted disjoint intervals."""
    out: List[List[float]] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(lo, hi) for lo, hi in out]


def busy_ns(ops: List[Event], lo: float, hi: float,
            device: Optional[int] = None) -> float:
    """Nanoseconds of [lo, hi] in which some operation ran (on ``device``,
    or averaged over the chips when None)."""
    chips = sorted({e.device for e in ops}) if device is None else [device]
    total = 0.0
    for chip in chips:
        spans = merged((max(e.start_ns, lo), min(e.end_ns, hi))
                       for e in ops if e.device == chip
                       and e.end_ns > lo and e.start_ns < hi)
        total += sum(b - a for a, b in spans)
    return total / max(1, len(chips))


def idle_gaps(ops: List[Event], lo: float, hi: float, device: int = 0
              ) -> List[Tuple[float, float]]:
    """Intervals of [lo, hi] in which ``device`` ran nothing."""
    gaps, cursor = [], lo
    for a, b in merged((e.start_ns, e.end_ns) for e in ops
                       if e.device == device):
        if b <= lo or a >= hi:
            continue
        if a > cursor:
            gaps.append((cursor, a))
        cursor = max(cursor, b)
    if cursor < hi:
        gaps.append((cursor, hi))
    return gaps


def span_at(spans: List[Event], t: float) -> str:
    """Name of the innermost span that covers time ``t``."""
    best = None
    for s in spans:
        if s.start_ns <= t <= s.end_ns and (
                best is None or s.dur_ns < best.dur_ns):
            best = s
    return best.name if best is not None else "outside any span"


def leaves(ops: List[Event]) -> List[Event]:
    """The operations that enclose no other operation of their chip
    (``ops`` sorted by start): control flow drops out."""
    out = []
    for chip in sorted({e.device for e in ops}):
        mine = [e for e in ops if e.device == chip]
        for e, nxt in zip(mine, mine[1:] + [None]):
            if nxt is None or nxt.start_ns >= e.end_ns:
                out.append(e)
    return out


def breakdown(profile: Profile, lo: float, hi: float, top: int = 10
              ) -> dict:
    """The ``top`` device operations by time and the ``top`` longest idle
    gaps of chip 0 in [lo, hi], each gap named by the host span at its
    middle; seconds as measured."""
    per_op: Counter = Counter()
    for e in leaves(profile.ops):
        if e.end_ns > lo and e.start_ns < hi:
            per_op[e.name] += (min(e.end_ns, hi) - max(e.start_ns, lo)) / 1e9
    gaps = sorted(idle_gaps(profile.ops, lo, hi),
                  key=lambda g: g[1] - g[0], reverse=True)[:top]
    return {"device_ops": [[name[:NAME_CHARS], s]
                           for name, s in per_op.most_common(top)],
            "idle_gaps": [[span_at(profile.spans, (a + b) / 2),
                           (b - a) / 1e9] for a, b in gaps]}
