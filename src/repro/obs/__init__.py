"""Unified telemetry for the out-of-core data plane.

One substrate, three surfaces:

- ``MetricsRegistry`` — thread-safe counters/gauges/log-bucket
  histograms, lock-free hot path via per-thread shards, merged at
  snapshot time; periodic JSONL snapshots through ``MetricsWriter``.
- ``trace_span`` / ``step_span`` — closed-by-construction spans and
  step markers, written into any active ``jax.profiler`` trace (beside
  the device's ops, on its clock) and, with a session, onto
  ``SpanTracer``'s per-lane tracks, exported as Chrome/Perfetto
  trace-event JSON; ``session_span`` for the tracer alone.
- ``names`` — the canonical metric-name table every emitter uses
  (``IOContext.KEYS``, the device-cache counter keys).

The session is enabled declaratively via the ``obs`` node on
``PipelineSpec`` (``--trace-out`` / ``--metrics-out``); with neither a
session nor a profiler the hooks are a no-op fast path.
"""

from repro.obs import names
from repro.obs.metrics import (HIST_BUCKETS, HIST_EDGES, MetricsRegistry,
                               MetricsWriter, bucket_index, idle_fraction,
                               merge_snapshots)
from repro.obs.session import (NULL_SPAN, ObsSession, active_session,
                               install, metric_inc, metric_observe,
                               session_span, step_span, tick, trace_span,
                               tracing, uninstall)
from repro.obs.summary import epoch_summary
from repro.obs.tracer import SpanTracer

__all__ = [
    "HIST_BUCKETS", "HIST_EDGES", "MetricsRegistry", "MetricsWriter",
    "NULL_SPAN", "ObsSession", "SpanTracer", "active_session",
    "bucket_index", "epoch_summary", "idle_fraction", "install",
    "merge_snapshots", "metric_inc", "metric_observe", "names",
    "session_span", "step_span", "tick", "trace_span", "tracing",
    "uninstall",
]
