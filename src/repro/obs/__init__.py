"""Unified observability plane: spans, journal, metrics registry, attribution.

One package owns every "what happened and where did the time go" question:

* ``obs.trace`` -- per-request span timelines on the virtual clock
  (``TraceConfig``/``SpanTracer``), with JSON-timeline and Chrome
  trace-event (Perfetto-loadable) exporters.
* ``obs.journal`` -- the append-only, monotonically-timestamped
  control-plane journal unifying reconcile decisions, scoped-recovery
  records, rollout transitions, autoscaler scale events, and tenancy
  event routing.
* ``obs.metrics`` -- counter/gauge/histogram primitives with label sets,
  exported as one schema-validated snapshot.
* ``obs.stats`` -- the single nearest-rank percentile + latency report
  implementation (serving, tenancy, and the autoscaler all route here).
* ``obs.profiler`` -- the one part on the wall clock: the ``seifer.gc``
  hook beside the engine's ``seifer.*`` ``jax.profiler.TraceAnnotation``
  spans, which land next to the device's ops in a profiler capture.
* ``obs.critical_path`` -- folds span timelines into per-request and
  aggregate latency attributions (queue/compute/wire/transcode) and pins
  observed per-stage service times against the plan's
  ``core.bottleneck.service_times`` predictions.

Nothing in this package imports from ``repro.api``/``repro.cluster`` --
it sits below them so every layer can depend on it without cycles.
"""

from repro.obs.critical_path import analyze_spans, request_attribution
from repro.obs.journal import Journal, JournalRecord
from repro.obs.metrics import MetricsRegistry
from repro.obs.profiler import install_gc_span
from repro.obs.stats import latency_report, latency_stats, percentile
from repro.obs.trace import Span, SpanTracer, TraceConfig

__all__ = [
    "Journal",
    "JournalRecord",
    "MetricsRegistry",
    "Span",
    "SpanTracer",
    "TraceConfig",
    "analyze_spans",
    "install_gc_span",
    "latency_report",
    "latency_stats",
    "percentile",
    "request_attribution",
]
