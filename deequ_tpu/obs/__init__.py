"""deequ_tpu.obs — run flight recorder + unified telemetry.

Three pieces (docs/observability.md):

- :mod:`~deequ_tpu.obs.recorder` — ``seam``, the one way the engine
  takes a duration (exclusive seconds into ``SCAN_STATS.seam_*``, a
  ``deequ.<name>`` annotation on the profiler's clock, a recorder span
  when armed), and the flight recorder those spans land on: typed
  span/event records, ring-buffer bounded, OFF by default and armed via
  ``run_scan(trace=...)`` / ``VerificationRunBuilder.with_tracing()`` /
  ``DEEQU_TPU_TRACE=1``;
- :mod:`~deequ_tpu.obs.export` — Chrome-trace/Perfetto JSON export of a
  recording (one track per thread, nested spans, instant events for
  fault rungs and budget charges);
- :mod:`~deequ_tpu.obs.registry` — the unified metrics registry:
  counters/gauges/histograms plus read-through collectors over the
  existing singletons (``ScanStats``, ``RETRY_TELEMETRY``, HBM ledger,
  envcfg, the serving layer's latency histograms), scraped whole by
  ``deequ_tpu.execution_report()``.
"""

from deequ_tpu.obs.export import to_chrome_trace, write_chrome_trace
from deequ_tpu.obs.recorder import (
    DEFAULT_CAPACITY,
    SEAM_NAMES,
    FlightRecorder,
    SpanRecord,
    current_recorder,
    global_recorder,
    install_global_recorder,
    maybe_arm_from_env,
    recording_scope,
    resolve_recorder,
    seam,
    seam_fields,
)
from deequ_tpu.obs.registry import (
    REGISTRY,
    Counter,
    Gauge,
    Histogram,
    HistogramFamily,
    MetricsRegistry,
)

__all__ = [
    "DEFAULT_CAPACITY",
    "SEAM_NAMES",
    "FlightRecorder",
    "SpanRecord",
    "current_recorder",
    "global_recorder",
    "install_global_recorder",
    "maybe_arm_from_env",
    "recording_scope",
    "resolve_recorder",
    "seam",
    "seam_fields",
    "to_chrome_trace",
    "write_chrome_trace",
    "REGISTRY",
    "Counter",
    "Gauge",
    "Histogram",
    "HistogramFamily",
    "MetricsRegistry",
]
