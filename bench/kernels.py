"""Bytes each Pallas kernel's contract requires, from its shapes.

A trace names each operation by its HLO text, e.g.

    %lbs_pallas.6 = (s32[1,131072]{...}, s32[1,131072]{...})
        custom-call(s32[1,4096]{...} %fusion.14), ...

so the result shapes and the operand shapes of every kernel call are read
from the event itself.  The count is what the kernel's job needs to move
through HBM at the least, not what this implementation moves, so a later
kernel that does the same job is read against the same count:

* ``lbs_pallas`` (load-balancing search): read the inclusive degree scan
  of the wavefront (W int32), write the owner and the rank of every work
  unit of the budget (2 x budget int32).
* ``compact_tiles_pallas`` (the push's stream compaction): read N int32
  items and N one-byte flags, write N int32 compacted items and one int32
  count.

Both do integer compares and sums and no multiplication worth a peak: the
table holds no integer-compare peak, so each is held to the HBM roofline.
"""
from __future__ import annotations

import re
from typing import List, Optional, Tuple

_SHAPE = re.compile(r"([a-z]+\d*)\[([\d,]*)\]")
_BYTES = {"s32": 4, "u32": 4, "f32": 4, "pred": 1, "s8": 1, "u8": 1,
          "bf16": 2, "f16": 2, "s64": 8, "f64": 8}


def shapes(text: str) -> Tuple[List[Tuple[str, int]], List[Tuple[str, int]]]:
    """``(results, operands)`` of one HLO instruction, as (dtype, elements)."""
    head, sep, tail = text.partition("custom-call(")
    if not sep:
        raise ValueError(f"not a custom call: {text[:80]!r}")

    def parse(part: str):
        out = []
        for dtype, dims in _SHAPE.findall(part):
            count = 1
            for d in filter(None, dims.split(",")):
                count *= int(d)
            out.append((dtype, count))
        return out

    return parse(head.partition("=")[2]), parse(tail.split(")")[0])


def lbs_bytes(text: str) -> int:
    results, operands = shapes(text)
    (_, w), = operands
    budget = results[0][1]
    return 4 * w + 2 * 4 * budget


def compact_bytes(text: str) -> int:
    _, operands = shapes(text)
    n = operands[0][1]
    return 4 * n + n + 4 * n + 4


def kernel_of(name: str) -> str:
    """``lbs_pallas`` of ``%lbs_pallas.6 = ...``: the op's name, less its
    ``%`` and its numeric suffix."""
    op = name.lstrip("%").split(" ", 1)[0]
    return re.sub(r"\.\d+$", "", op)


#: kernel name in the trace -> bytes its contract requires per call
REQUIRED_BYTES = {"lbs_pallas": lbs_bytes,
                  "compact_tiles_pallas": compact_bytes}


def roofline_share(events, kernel: str, hbm_bytes_per_s: float
                   ) -> Optional[float]:
    """Least time the chip could take for ``kernel``'s calls over the time
    they took, in %; None where no call of it ran."""
    calls = [e for e in events if kernel_of(e.name) == kernel]
    if not calls:
        return None
    need = sum(REQUIRED_BYTES[kernel](e.name) for e in calls)
    took = sum(e.dur_ns for e in calls) / 1e9
    return 100.0 * (need / hbm_bytes_per_s) / took
