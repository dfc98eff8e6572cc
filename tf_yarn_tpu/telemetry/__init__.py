"""Unified runtime telemetry: spans, metrics, heartbeats.

One subsystem behind every observability surface in the framework
(docs/Observability.md):

* :mod:`~tf_yarn_tpu.telemetry.spans` — nested, thread-aware span
  tracing with a ring buffer, a JSONL sink, and a Chrome/Perfetto
  ``trace_event`` exporter (``TPU_YARN_TRACE=<dir>`` →
  ``trace_<task>.json``).
* :mod:`~tf_yarn_tpu.telemetry.profile` — the one start/stop of the
  XLA profiler (train loop window, serving ``POST /debug/profile``),
  tied to the span clock by a ``tf_yarn_tpu/clock_sync`` annotation.
* :mod:`~tf_yarn_tpu.telemetry.registry` — process-global
  counters/gauges/histograms with labels, snapshot-able as a dict and
  flushed to the log, MLflow, and the coordination KV store.
* :mod:`~tf_yarn_tpu.telemetry.heartbeat` — per-task liveness gauges
  over KV, so stragglers are visible from the chief.
* :mod:`~tf_yarn_tpu.telemetry.exposition` — Prometheus text rendering
  for `/metrics` plus the versioned `signals` block `/stats` embeds
  (windowed histogram bucket sketches the fleet monitor merges into
  pooled quantiles).
* :mod:`~tf_yarn_tpu.telemetry.slo` — declared latency objectives
  evaluated over histogram windows into ``slo/attainment`` gauges and
  ``slo/burn_total`` counters.

Everything is host-side: no instrument or span may live inside a jit
body (the analysis checker gates the instrumented call sites in CI).
"""

from tf_yarn_tpu.telemetry.exposition import (  # noqa: F401
    PROMETHEUS_CONTENT_TYPE,
    SIGNALS_VERSION,
    STATS_SCHEMA_VERSION,
    render_prometheus,
    signals_block,
)
from tf_yarn_tpu.telemetry import profile  # noqa: F401
from tf_yarn_tpu.telemetry.heartbeat import Heartbeat  # noqa: F401
from tf_yarn_tpu.telemetry.registry import (  # noqa: F401
    Counter,
    Gauge,
    HIST_ALPHA,
    Histogram,
    MetricsRegistry,
    flush_metrics,
    get_registry,
)
from tf_yarn_tpu.telemetry.slo import (  # noqa: F401
    SloEvaluator,
    SloObjective,
    parse_slo,
)
from tf_yarn_tpu.telemetry.spans import (  # noqa: F401
    Span,
    TRACE_ENV,
    TRACE_JSONL_ENV,
    Tracer,
    close_jsonl_sinks,
    enable_env_jsonl,
    export_trace,
    get_tracer,
    span,
    trace_dir,
)

__all__ = [
    "Counter",
    "Gauge",
    "HIST_ALPHA",
    "Heartbeat",
    "Histogram",
    "MetricsRegistry",
    "PROMETHEUS_CONTENT_TYPE",
    "SIGNALS_VERSION",
    "STATS_SCHEMA_VERSION",
    "SloEvaluator",
    "SloObjective",
    "Span",
    "TRACE_ENV",
    "TRACE_JSONL_ENV",
    "Tracer",
    "close_jsonl_sinks",
    "enable_env_jsonl",
    "export_trace",
    "flush_metrics",
    "get_registry",
    "get_tracer",
    "parse_slo",
    "profile",
    "render_prometheus",
    "signals_block",
    "span",
    "trace_dir",
]
