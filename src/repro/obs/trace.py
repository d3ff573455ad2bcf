"""The :class:`Trace` front door — one object per observed run.

A ``Trace`` ties the layer together (DESIGN.md §15):

  * hands fresh device rings to engines (:meth:`Trace.ring`) and collects
    their drained rows (:meth:`Trace.drain`), tagging each record with the
    engine name so one trace can hold a whole multi-engine session
    (server rounds + sharded phases + stream segments side by side);
  * records host wall-clock **spans** (:meth:`Trace.span` context
    manager) on a shared epoch, so the steps of ``execute`` line up in the
    exported timeline; each span is also a ``jax.profiler`` annotation of
    the same name (:mod:`~repro.obs.spans`);
  * owns a **metrics registry** (:meth:`Trace.add_metric`): every
    end-of-run summary doc the engines serialize (run / shard_run /
    server / stream / job kinds) validated against ``obs/schema`` at
    insertion time, plus exact-percentile latency histograms
    (:meth:`Trace.histogram`);
  * exports everything (:meth:`Trace.write`) as a JSONL metrics file and
    a Perfetto-loadable Chrome trace, both written atomically.

Passing a ``Trace`` enables tracing; passing ``None`` (the default
everywhere) runs exactly today's code paths — the engines construct no
ring and wrap no step, so the disabled path is the identity by
construction (the parity tests in tests/test_obs.py pin this
bit-for-bit across every policy).
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional

from .export import chrome_trace, write_chrome_trace, write_jsonl
from .hist import LatencyHistogram
from .ring import DEFAULT_CAPACITY, TraceRing, ring_rows
from .schema import SCHEMA_VERSION, metric_doc, validate_metric
from .spans import span


def default_meta() -> dict:
    """Provenance stamp: jax version, device kind, python — the metrics
    twin of the bench harness's ``bench_meta`` block."""
    import platform

    import jax

    return {
        "git_sha": "unknown",  # CLI entry points stamp the real sha
        "jax_version": jax.__version__,
        "device_kind": str(jax.devices()[0].device_kind),
        "python": platform.python_version(),
    }


class Trace:
    """Collector for one observed run: rings, spans, metrics, histograms."""

    def __init__(self, capacity: int = DEFAULT_CAPACITY,
                 meta: Optional[dict] = None) -> None:
        self.capacity = capacity
        self.records: List[dict] = []     # drained round rows (+engine tag)
        self.spans: List[dict] = []       # host wall-clock span docs
        self.metrics: List[dict] = []     # validated summary docs
        self.histograms: Dict[str, LatencyHistogram] = {}
        self.truncated = 0                # ring rows lost to wraparound
        self.meta = default_meta()
        if meta:
            self.meta.update(meta)
        self._epoch = time.perf_counter()

    # ------------------------------------------------------------- device
    def ring(self) -> TraceRing:
        """A fresh device ring sized to this trace's capacity."""
        return TraceRing.make(self.capacity)

    def drain(self, ring: TraceRing, engine: str,
              round_offset: int = 0) -> int:
        """Pull a finished drain's ring to host (the one tracing sync).

        ``engine`` tags every record (it becomes the Chrome-trace process
        lane); ``round_offset`` shifts the in-ring round indices to
        absolute round numbers for segmented drains (stream snapshots).
        Returns the number of records appended.
        """
        rows, truncated = ring_rows(ring)
        self.truncated += truncated
        for row in rows:
            rec = dict(row)
            rec["round"] += round_offset
            rec["engine"] = engine
            self.records.append(rec)
        return len(rows)

    # --------------------------------------------------------------- host
    def span(self, name: str):
        """Record one host wall-clock span on the trace's shared epoch; the
        same call opens the profiler annotation ``atos:<name>``
        (:func:`~repro.obs.spans.span`)."""
        return span(name, self)

    def add_span(self, name: str, t0: float, t1: float) -> None:
        """The span doc of ``[t0, t1]`` (``time.perf_counter`` seconds)."""
        self.spans.append(metric_doc(
            "span", name=name, ts_us=(t0 - self._epoch) * 1e6,
            dur_us=(t1 - t0) * 1e6))

    def histogram(self, name: str) -> LatencyHistogram:
        """Get-or-create a named latency histogram."""
        if name not in self.histograms:
            self.histograms[name] = LatencyHistogram(name)
        return self.histograms[name]

    def add_metric(self, doc: dict) -> dict:
        """Register one canonical summary doc (validated on insertion)."""
        validate_metric(doc)
        self.metrics.append(doc)
        return doc

    # ------------------------------------------------------------- export
    def metric_docs(self) -> List[dict]:
        """Every document this trace will export, canonical order: meta,
        summaries, histograms, spans, then the per-round records."""
        docs = [metric_doc("meta", **self.meta)]
        docs.extend(self.metrics)
        docs.extend(h.to_doc() for h in self.histograms.values())
        docs.extend(self.spans)
        for rec in self.records:
            docs.append(metric_doc("round", **rec))
        return docs

    def chrome(self) -> dict:
        """The Perfetto-loadable Chrome trace-event document."""
        meta = dict(self.meta, schema=SCHEMA_VERSION,
                    truncated_rounds=self.truncated)
        return chrome_trace(self.records, self.spans, meta=meta)

    def write(self, trace_path: Optional[str] = None,
              metrics_path: Optional[str] = None) -> None:
        """Atomically write the Chrome trace and/or the metrics JSONL."""
        if trace_path:
            write_chrome_trace(trace_path, self.chrome())
        if metrics_path:
            write_jsonl(metrics_path, self.metric_docs())
