"""repro.obs — tracing, metrics, and Perfetto timeline export.

Three parts, one package:

  * `repro.obs.trace` — contextvar-scoped runtime spans (`span`, `tracing`,
    `Stopwatch`) with a no-op fast path when disabled, written into the JAX
    profiler's trace while it records; instrumented into the planner, the
    simulator-scored beam, the planner service, the kernel pre-flight, the
    network runner and the garbage collector.
  * `repro.obs.metrics` — the process-wide metric `REGISTRY`
    (counters/gauges/histograms) that absorbs the planner's cache stats and
    the service's latency distribution; Prometheus text + JSON snapshot.
  * `repro.obs.export` — Chrome/Perfetto trace-event JSON from runtime
    spans (wall-clock) or from a `SimReport` (virtual-time resource
    timeline with an interconnect-bandwidth counter track).

CLI: ``python -m repro.obs`` (export / metrics / trace-load). See the
README "Observability" section for the span API, the metric name table,
and the Perfetto walkthrough.
"""

from repro.obs.export import (simreport_to_trace, spans_to_trace, trace_json,
                              verify_sim_trace, write_trace)
from repro.obs.metrics import (REGISTRY, Counter, Gauge, Histogram, Registry,
                               StatsCounter, counter, gauge, histogram)
from repro.obs.trace import (SpanRecord, Stopwatch, Tracer, disable, enable,
                             enabled, get_tracer, span, tracing)

__all__ = [
    # trace
    "SpanRecord", "Tracer", "Stopwatch", "span", "enabled", "enable",
    "disable", "get_tracer", "tracing",
    # metrics
    "REGISTRY", "Registry", "Counter", "Gauge", "Histogram", "StatsCounter",
    "counter", "gauge", "histogram",
    # export
    "spans_to_trace", "simreport_to_trace", "trace_json", "write_trace",
    "verify_sim_trace",
]
