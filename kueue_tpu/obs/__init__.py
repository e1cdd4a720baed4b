"""Admission tracing and decision explainability.

One recorder times the engine: ``obs.span.SpanRecorder`` (``eng.spans``,
always on) keeps a real span tree per ``schedule_once()`` — pre_hooks,
cycle (host_encode > tas_place, upload, dispatch, device_wait,
readback, verdict_decode, apply, finalize, host_tail), snapshot /
decide / apply on the sequential path, gc_sweep, journal_sync,
listeners — each span also a ``kueue.<name>``
``jax.profiler.TraceAnnotation``, so a profiler capture holds the same
tree beside the device's operations. ``Engine.last_cycle_phases`` is
derived from it.

On top of it: per-cycle trees with structured decision rationale
(obs.tracer.CycleTracer, which adopts the recorder's spans, so the
timestamps at ``/debug/trace`` and in a Perfetto export are the ones the
work was timed with), cheap rationale hooks for the decision path
(obs.hooks), Chrome/Perfetto export (obs.perfetto) and ``kueuectl
explain`` (obs.explain).
"""

from kueue_tpu.obs import hooks
from kueue_tpu.obs.explain import explain_workload, render_explain
from kueue_tpu.obs.perf import PerfRecorder, PhaseHistogram, attach_perf
from kueue_tpu.obs.perfetto import (
    spans_from_flight_trace,
    to_perfetto,
    write_perfetto,
)
from kueue_tpu.obs.slo import SLO, SLOEngine, attach_slo
from kueue_tpu.obs.span import Span, SpanRecorder, correlation_id
from kueue_tpu.obs.tracer import CycleTracer


def attach_tracer(engine, retain: int = 64, **kwargs) -> CycleTracer:
    """Attach a CycleTracer to a live engine (idempotent: an existing
    tracer is returned rather than doubled)."""
    existing = getattr(engine, "tracer", None)
    if existing is not None:
        return existing
    return CycleTracer(engine, retain=retain, **kwargs)


__all__ = [
    "CycleTracer",
    "PerfRecorder",
    "PhaseHistogram",
    "SLO",
    "SLOEngine",
    "Span",
    "SpanRecorder",
    "attach_perf",
    "attach_slo",
    "attach_tracer",
    "correlation_id",
    "explain_workload",
    "hooks",
    "render_explain",
    "spans_from_flight_trace",
    "to_perfetto",
    "write_perfetto",
]
