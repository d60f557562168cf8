"""Unified telemetry: metrics registry, wire tracing, snapshot surface.

Quick tour::

    from distriflow_tpu import obs

    t = obs.Telemetry(save_dir="runs/exp0")      # or obs.get_telemetry()
    t.counter("transport_frames_sent_total", role="client").inc()
    with t.span("upload", trace_id=tid) as s:
        s.set(attempts=2)
    t.snapshot()        # plain dict: counters / gauges / histograms
    t.prometheus()      # text exposition for scraping
    t.export_snapshot() # one JSONL row in <save_dir>/metrics.jsonl

Offline, ``python -m distriflow_tpu.obs.dump <dir>`` summarizes a run's
``metrics.jsonl`` + ``spans.jsonl``. See ``docs/OBSERVABILITY.md`` for
the metric-name and span-schema reference.
"""

from distriflow_tpu.obs.collector import (
    REPORT_VERSION,
    ReportBuilder,
    TelemetryCollector,
)
from distriflow_tpu.obs.flight_recorder import (
    FlightRecorder,
    NOOP_FLIGHT,
)
from distriflow_tpu.obs.health import (
    FleetTable,
    HealthSentinel,
    SLOBand,
    default_bands,
)
from distriflow_tpu.obs.jax_hooks import install_jax_hooks
from distriflow_tpu.obs.profiler import (
    NOOP_PHASE,
    NOOP_PROFILER,
    PhaseProfiler,
)
from distriflow_tpu.obs.registry import (
    BUCKET_BOUNDS,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    NOOP_HANDLE,
    metric_ident,
    parse_ident,
    render_prometheus,
)
from distriflow_tpu.obs.telemetry import (
    Telemetry,
    get_telemetry,
    set_telemetry,
)
from distriflow_tpu.obs.timeline import (
    NOOP_TIMELINE,
    TIMELINE_FILENAME,
    TimelineStore,
    fit_slope,
    quantile_from_buckets,
)
from distriflow_tpu.obs.trace_assembler import (
    Assembly,
    Round,
    assemble,
    assemble_dir,
)
from distriflow_tpu.obs.tracing import (
    NOOP_SPAN,
    Span,
    Tracer,
    new_span_id,
    new_trace_id,
)

__all__ = [
    "Assembly",
    "BUCKET_BOUNDS",
    "Counter",
    "FleetTable",
    "FlightRecorder",
    "Gauge",
    "HealthSentinel",
    "Histogram",
    "MetricsRegistry",
    "NOOP_FLIGHT",
    "NOOP_HANDLE",
    "NOOP_PHASE",
    "NOOP_PROFILER",
    "NOOP_SPAN",
    "NOOP_TIMELINE",
    "PhaseProfiler",
    "REPORT_VERSION",
    "ReportBuilder",
    "Round",
    "SLOBand",
    "Span",
    "TIMELINE_FILENAME",
    "Telemetry",
    "TelemetryCollector",
    "TimelineStore",
    "Tracer",
    "assemble",
    "assemble_dir",
    "default_bands",
    "fit_slope",
    "get_telemetry",
    "install_jax_hooks",
    "metric_ident",
    "new_span_id",
    "new_trace_id",
    "parse_ident",
    "quantile_from_buckets",
    "render_prometheus",
    "set_telemetry",
]
