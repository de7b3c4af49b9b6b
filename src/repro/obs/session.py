"""The process-wide telemetry session and the span hook.

Instrumentation points (loader lanes, store preads, the consumer step,
the oracle lane) call the module-level ``trace_span``/``tick`` hooks.
A span goes to two sinks, each only while it is on:

- the JAX profiler, while a profiler session is active
  (``TraceMe.is_enabled()``): the span opens a
  ``jax.profiler.TraceAnnotation`` with the span's attributes, so it
  lands on the xplane host plane, per thread and on the same clock as
  the device's ``XLA Ops``;
- the installed ``ObsSession``'s ``SpanTracer`` (the Perfetto JSON of
  ``--trace-out``).

With both off the hooks are a fast path: one global read, one
``is_enabled()`` call and a shared null context manager.  Spans only
*observe* the monotonic clock, so traced runs never perturb the
bit-exact batch stream (loss trajectories are repr-identical either
way).  This module never imports jax itself: the profiler hook is
resolved on first use once ``jax`` is in ``sys.modules``, so ``obs``
keeps its stdlib-only import.

``build_pipeline`` opens one ``ObsSession`` per enabled pipeline and
``Pipeline.close()`` finalizes it: the trace JSON and the terminal
metrics snapshot are flushed exactly once, on the owner's close path.
"""

from __future__ import annotations

import sys
import threading

from repro.obs.metrics import MetricsRegistry, MetricsWriter
from repro.obs.tracer import SpanTracer


class _NullSpan:
    """Shared do-nothing context manager — the telemetry-off fast path."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        return False


NULL_SPAN = _NullSpan()


class _BothSpans:
    """A profiler annotation and a ``SpanTracer`` span around one block."""
    __slots__ = ("_annotation", "_span")

    def __init__(self, annotation, span):
        self._annotation, self._span = annotation, span

    def __enter__(self):
        self._annotation.__enter__()
        self._span.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb):
        self._span.__exit__(exc_type, exc, tb)
        return self._annotation.__exit__(exc_type, exc, tb)


_lock = threading.Lock()
_session: "ObsSession | None" = None
_tracer: SpanTracer | None = None   # mirrored for the hot-path read


def _resolve_profiler() -> bool:
    """Stands in for ``TraceMe.is_enabled`` until jax has been imported
    (by anyone), then installs it and the annotation classes."""
    global _profiling, _annotation, _step_annotation
    if "jax" not in sys.modules:
        return False
    from jax import profiler
    _annotation = profiler.TraceAnnotation
    _step_annotation = profiler.StepTraceAnnotation
    _profiling = profiler.TraceAnnotation.is_enabled
    return _profiling()


_profiling = _resolve_profiler      # () -> is a profiler session active?
_annotation = None                  # jax.profiler.TraceAnnotation
_step_annotation = None             # jax.profiler.StepTraceAnnotation


class ObsSession:
    """One telemetry scope: a metrics registry (+ optional JSONL sink)
    and a span tracer (+ optional Perfetto export path)."""

    def __init__(self, *, trace_path: str | None = None,
                 metrics_path: str | None = None,
                 metrics_interval_s: float = 5.0):
        self.trace_path = trace_path
        self.metrics_path = metrics_path
        self.registry = MetricsRegistry()
        self.tracer = SpanTracer() if trace_path else None
        self.writer = (MetricsWriter(self.registry, metrics_path,
                                     metrics_interval_s)
                       if metrics_path else None)
        self.trace_summary: dict | None = None
        self._closed = False

    def close(self) -> None:
        """Flush both sinks (idempotent) and uninstall if active."""
        if self._closed:
            return
        self._closed = True
        uninstall(self)
        if self.writer is not None:
            self.writer.close()
        if self.tracer is not None and self.trace_path:
            self.trace_summary = self.tracer.export(self.trace_path)


def install(session: ObsSession) -> ObsSession:
    """Make ``session`` the process-wide telemetry target (last wins)."""
    global _session, _tracer
    with _lock:
        _session = session
        _tracer = session.tracer
    return session


def uninstall(session: ObsSession) -> None:
    """Detach ``session`` if it is the active one (a later ``install``
    already superseded it otherwise)."""
    global _session, _tracer
    with _lock:
        if _session is session:
            _session = None
            _tracer = None


def active_session() -> ObsSession | None:
    return _session


def tracing() -> bool:
    """Cheap guard for instrumentation that wants to skip even the
    attrs-dict construction when spans are off."""
    return _tracer is not None or _profiling()


def trace_span(name: str, **attrs):
    """``with trace_span("resolve", batch=t): ...`` — one closed span,
    into the profiler's trace while a profiler session is active and
    onto the installed tracer while a session is; the shared null
    context when neither is.  ``lane=`` overrides the tracer's track
    (defaults to the current thread's name, i.e. the pipeline lane); the
    profiler tracks threads itself.  ``None`` attributes are left out of
    the profiler's event."""
    t = _tracer
    if _profiling():
        annotation = _annotation(name, **{
            k: v for k, v in attrs.items() if v is not None and k != "lane"})
        return annotation if t is None else _BothSpans(annotation,
                                                       t.span(name, attrs))
    if t is None:
        return NULL_SPAN
    return t.span(name, attrs)


def session_span(name: str, **attrs):
    """``trace_span`` onto the installed session's tracer alone, for a
    span too frequent to annotate in the profiler's trace at little cost
    (one a disk block); the shared null context without a session."""
    t = _tracer
    if t is None:
        return NULL_SPAN
    return t.span(name, attrs)


def step_span(name: str, step: int):
    """``with step_span("train", i): ...`` — a step marker
    (``jax.profiler.StepTraceAnnotation``) while a profiler session is
    active, by which XProf and TensorBoard split a profile per step; the
    shared null context otherwise."""
    if _profiling():
        return _step_annotation(name, step_num=step)
    return NULL_SPAN


def metric_inc(name: str, value: float = 1) -> None:
    """Add to a counter on the active registry (no-op when off)."""
    s = _session
    if s is not None:
        s.registry.inc(name, value)


def metric_observe(name: str, value: float) -> None:
    """Record into a histogram on the active registry (no-op when off)."""
    s = _session
    if s is not None:
        s.registry.observe(name, value)


def tick() -> None:
    """Give the periodic JSONL sink a chance to snapshot.  Called from
    the consumer loop once per step; a no-op without an active writer."""
    s = _session
    if s is not None and s.writer is not None:
        s.writer.tick()
