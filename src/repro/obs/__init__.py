"""Unified observability layer (DESIGN.md §15).

One schema, one collector, two exporters for every engine in the repo:

  * :mod:`~repro.obs.ring`   — the device-side per-round trace ring buffer
    threaded through every jitted drain loop (zero host syncs while
    tracing, drained once at run end);
  * :mod:`~repro.obs.schema` — the canonical metric schema every summary
    (`RunStats`, `ShardRunStats`, `ServerStats`, `StreamResult`,
    `JobTelemetry`) serializes into, plus the hand-rolled validators the
    bench-smoke CI guard runs;
  * :mod:`~repro.obs.hist`   — exact p50/p95/p99 latency histograms;
  * :mod:`~repro.obs.export` — atomic JSONL + Chrome-trace writers;
  * :mod:`~repro.obs.trace`  — the :class:`Trace` front door wired through
    ``runtime.execute(..., trace=...)``, the task server, the stream
    driver, and ``taskserver --trace-out/--metrics-out``;
  * :mod:`~repro.obs.spans`  — the always-on names on the profiler's
    clock: ``atos:<step>`` host spans (:func:`span`) and the ``atos.<phase>``
    device scopes of each scheduling round.

The ring is strictly opt-in: every entry point takes ``trace=None`` by
default and builds exactly the pre-observability computation when it is
absent — the disabled path is the identity, proven bit-for-bit by
tests/test_obs.py across all six policies plus the megakernel.
"""
from .export import (atomic_write_text, chrome_trace, read_jsonl,
                     write_chrome_trace, write_jsonl)
from .hist import LatencyHistogram
from .ring import (DEFAULT_CAPACITY, TraceRing, ring_rows, stacked_rings,
                   unstack_ring)
from .schema import (BENCH_META_KEYS, KINDS, NUM_FIELDS, SCHEMA_VERSION,
                     TRACE_FIELDS, metric_doc, validate_bench,
                     validate_chrome_trace, validate_metric,
                     validate_metrics_jsonl)
from .spans import SPAN_PREFIX, span
from .trace import Trace, default_meta

__all__ = [
    "atomic_write_text", "chrome_trace", "read_jsonl", "write_chrome_trace",
    "write_jsonl", "LatencyHistogram", "DEFAULT_CAPACITY", "TraceRing",
    "ring_rows", "stacked_rings", "unstack_ring", "BENCH_META_KEYS",
    "KINDS", "NUM_FIELDS", "SCHEMA_VERSION", "TRACE_FIELDS", "metric_doc",
    "validate_bench", "validate_chrome_trace", "validate_metric",
    "validate_metrics_jsonl", "SPAN_PREFIX", "span", "Trace",
    "default_meta",
]
