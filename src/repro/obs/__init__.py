"""Unified observability plane: metrics registry, span tracing, and
prediction-quality telemetry.

One layer, three instruments, shared across predictor, engines, and the
scheduler service:

  * **Metrics registry** (:mod:`repro.obs.metrics`) — named counter and
    gauge families with Prometheus-style text exposition
    (:func:`scrape`). The process-global work counters the tests and the
    CI regression gates consume (``TRACE_COUNTS``, ``DISPATCH_COUNTS``,
    ``BOUNDARY_COUNTS``) are registry-backed :class:`CounterFamily`
    instances — genuine ``collections.Counter`` subclasses, so every
    existing snapshot-before / diff-after consumer works verbatim.
    :func:`scoped_counters` brackets a run so back-to-back simulations
    report independent counts without losing the process totals.
  * **Span tracing** (:mod:`repro.obs.trace`) — ``with span("predict",
    pool=...)`` context managers on the hot paths, each linked to its
    parent span, exported as Chrome/Perfetto ``trace_event`` JSON so a
    cluster replay renders as a flamegraph, and mirrored as
    ``jax.profiler`` annotations so a profiler trace shows them beside
    the device operations. A single ``None`` check when tracing is off;
    wall clocks are read only while a collector is active.
  * **Quality telemetry** (:mod:`repro.obs.quality`) — per-pool,
    virtual-clock-stamped prediction-quality samples (RAQ, selected
    model, dynamic offset, prequential under/over-prediction error,
    retrain cadence) emitted by :class:`~repro.baselines.sizey_method.
    SizeyMethod` as ``kind="quality"`` aux rows on the provenance JSONL.

Telemetry is side-effect-free by construction: no instrument consumes
rng state, reorders events, or feeds back into sizing arithmetic, so
every bitwise invariant (serial equivalence, kill-at-any-byte warm
resume, policy A/B) holds with tracing on. The package imports the
stdlib only (``jax`` once tracing starts) and nothing from ``repro``, so
every subsystem can import it without cycles.
"""
from repro.obs.metrics import (CounterFamily, Gauge, MetricsRegistry,
                               counter, default_registry, gauge, scrape,
                               scoped_counters)
from repro.obs.quality import (QUALITY_KIND, read_quality_rows,
                               summarize_pools, write_quality_csv)
from repro.obs.trace import (TraceCollector, async_span, span,
                             start_tracing, stop_tracing, tracing,
                             tracing_active)

__all__ = [
    "CounterFamily", "Gauge", "MetricsRegistry", "counter",
    "default_registry", "gauge", "scrape", "scoped_counters",
    "QUALITY_KIND", "read_quality_rows", "summarize_pools",
    "write_quality_csv",
    "TraceCollector", "async_span", "span", "start_tracing",
    "stop_tracing", "tracing", "tracing_active",
]
