"""Shared benchmark utilities: timed runs + CSV/JSON emission.

Every ``BENCH_*.json`` document carries a ``meta`` provenance block
(git sha, jax version, device kind, python, schema version) stamped by
:func:`bench_meta` and is written atomically (temp-then-rename) so a
crashed or interrupted benchmark can never leave a truncated artifact
behind; ``benchmarks/smoke.py`` validates every emitted document against
the canonical schema in ``repro/obs/schema.py``.
"""
from __future__ import annotations

import json
import statistics
import subprocess
import time
from pathlib import Path
from typing import Callable

import jax


def timeit(fn: Callable, *, warmup: int = 1, iters: int = 3) -> float:
    """Median wall seconds over ``iters`` runs (after ``warmup``)."""
    for _ in range(warmup):
        jax.block_until_ready(fn())
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2]


def row(name: str, us_per_call: float, derived: str = ""):
    print(f"{name},{us_per_call:.1f},{derived}")


def timeit_host(fn: Callable, *, warmup: int = 1, iters: int = 3):
    """Like ``timeit`` but for host-driven loops whose return value matters:
    returns (median wall seconds, last result)."""
    result = None
    for _ in range(warmup):
        result = fn()
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        result = fn()
        times.append(time.perf_counter() - t0)
    return statistics.median(times), result


def bench_meta() -> dict:
    """Provenance block stamped into every BENCH_*.json (obs/schema.py)."""
    import platform

    from repro.obs import SCHEMA_VERSION

    try:
        git_sha = subprocess.run(
            ["git", "rev-parse", "HEAD"], capture_output=True, text=True,
            timeout=5, check=True).stdout.strip()
    except Exception:
        git_sha = "unknown"
    return {
        "git_sha": git_sha,
        "jax_version": jax.__version__,
        "device_kind": str(jax.devices()[0].device_kind),
        "python": platform.python_version(),
        "schema": SCHEMA_VERSION,
    }


def emit_json(path: str | Path, payload: dict) -> Path:
    """Atomically write a benchmark result document with its ``meta``
    provenance block; returns the path written.

    temp-then-rename so a crash mid-write never leaves a truncated
    ``BENCH_*.json`` behind (os.replace is atomic on POSIX)."""
    from repro.obs import atomic_write_text

    path = Path(path)
    payload = dict(payload)
    payload.setdefault("meta", bench_meta())
    atomic_write_text(path, json.dumps(payload, indent=2, sort_keys=True)
                      + "\n")
    print(f"wrote {path}")
    return path
