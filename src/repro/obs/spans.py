"""Names on the profiler's clock (DESIGN.md §15).

Two kinds, both always on and both read off a ``jax.profiler`` trace:

  * **host spans** — :func:`span` opens a
    ``jax.profiler.TraceAnnotation("atos:<name>")`` around one host step
    (``build_program``, ``init``, ``drain``, ``readback``), which costs
    almost nothing when no profiler session is running;
  * **device scopes** — the ``atos.<phase>`` names below, opened with
    ``jax.named_scope`` around each phase of a scheduling round, so the
    ``op_name`` metadata of every HLO op the phase lowers to carries it.
    Metadata changes no fusion and costs nothing on the device.

The scope names are defined here once; no layer spells them out.
"""
from __future__ import annotations

import time
from contextlib import contextmanager

import jax

#: prefix of every host span the program opens
SPAN_PREFIX = "atos:"

#: device scopes, one per round phase
POP = "atos.pop"            # ops.pop and the valid count
EXPAND = "atos.expand"      # decode, load-balanced expansion, truncation
APPLY = "atos.apply"        # the algorithm's gathers, scatters and counters
PUSH = "atos.push"          # push-side coalescing and ops.push
ON_EMPTY = "atos.on_empty"  # the empty-wavefront branch
EXCHANGE = "atos.exchange"  # the sharded engine's routed exchange
RING = "atos.ring"          # the obs ring's per-round bookkeeping


@contextmanager
def span(name: str, trace=None):
    """One host step: a profiler annotation ``atos:<name>``, and, when a
    :class:`~repro.obs.Trace` is given, its span doc on the trace's epoch."""
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation(SPAN_PREFIX + name):
        try:
            yield
        finally:
            if trace is not None:
                trace.add_span(name, t0, time.perf_counter())
