"""repro.telemetry — span tracing, serve metrics, quantisation-health taps.

Three layers (see README §repro.telemetry):

* :mod:`repro.telemetry.trace`   — host-side nested spans -> Chrome/Perfetto
  trace-event JSON, and into the JAX profiler's trace while it collects;
  free when neither is on.
* :mod:`repro.telemetry.metrics` — counters / gauges / ring-reservoir
  histograms with Prometheus-text + JSON export and the shared
  :func:`latency_summary` schema; :func:`log` structured log lines.
* :mod:`repro.telemetry.taps`    — in-graph quantisation-health statistics
  collected by the Engine's opt-in ``compile_model(..., taps=True)`` aux
  program (int8 saturation, LUT out-of-domain fractions, Q8.24 headroom).
* :mod:`repro.telemetry.flight`  — a bounded flight recorder for the
  serving cell: last-N-hops ring + anomaly-triggered post-mortem dumps
  with stage attribution of the slow hops by their measured host phases.

:func:`annotate` names a stage *inside* a jitted program (a
``jax.named_scope`` pass-through): metadata-only, shows up in jaxprs /
XLA profiles, never changes numerics.
"""

from jax import named_scope as annotate

from repro.telemetry import taps
from repro.telemetry.cell import CellMetrics, make_cell_metrics
from repro.telemetry.flight import FlightConfig, FlightRecorder, HopRecord
from repro.telemetry.check import (
    TelemetryFormatError,
    validate_chrome_trace,
    validate_prometheus,
)
from repro.telemetry.metrics import (
    Counter,
    Gauge,
    Histogram,
    Registry,
    default_registry,
    latency_summary,
    log,
)
from repro.telemetry.trace import (
    NOOP_SPAN,
    Tracer,
    active_tracer,
    disable,
    enable,
    span,
    span_coverage,
    tracing,
)

__all__ = [
    "NOOP_SPAN",
    "CellMetrics",
    "Counter",
    "FlightConfig",
    "FlightRecorder",
    "Gauge",
    "Histogram",
    "HopRecord",
    "Registry",
    "TelemetryFormatError",
    "Tracer",
    "active_tracer",
    "annotate",
    "default_registry",
    "disable",
    "enable",
    "latency_summary",
    "log",
    "make_cell_metrics",
    "span",
    "span_coverage",
    "taps",
    "tracing",
    "validate_chrome_trace",
    "validate_prometheus",
]
