"""Observability layer: metrics registry, dual-timeline span tracing,
JAX profiling hooks, structured run logging.

Public surface:

  * ``ObsConfig`` — frozen config threaded through ``SimConfig`` /
    ``launch/train.py`` (zero overhead when absent/disabled).
  * ``make_telemetry`` / ``Telemetry`` / ``NULL_TELEMETRY`` — the handle
    the engine emits through.
  * ``MetricsRegistry`` + ``current_registry``/``set_registry``/
    ``use_registry`` — named counters/gauges/histograms with labels; the
    ambient registry serves modules that cannot thread a handle
    (wireless pricing, sync-step builders).
  * ``SpanTracer`` / ``validate_trace`` — virtual+host clock spans,
    Chrome/Perfetto trace-event JSON export.
  * ``StepClock`` / ``live_bytes`` — compile vs steady step timing,
    live-memory probe.
  * ``RunLogger`` — console + JSONL structured run log.
"""
from repro.obs.config import ObsConfig
from repro.obs.health import (
    DEFAULT_RULES, NULL_HEALTH, HealthMonitor, NullHealthMonitor, Rule,
    Window,
)
from repro.obs.jaxprof import StepClock, live_bytes
from repro.obs.metrics import (
    NULL_REGISTRY, MetricsRegistry, current_registry, set_registry,
    use_registry,
)
from repro.obs.runlog import (
    EVENT_SCHEMAS, SCHEMA_VERSION, RunLogger, validate_event,
    validate_runlog,
)
from repro.obs.spans import (
    HOST_PID, VIRTUAL_PID, SpanTracer, to_jsonable, validate_trace,
)
from repro.obs.telemetry import (
    NULL_TELEMETRY, NullTelemetry, Telemetry, make_telemetry,
)

__all__ = [
    "ObsConfig", "StepClock", "live_bytes",
    "NULL_REGISTRY", "MetricsRegistry", "current_registry", "set_registry",
    "use_registry", "RunLogger", "HOST_PID", "VIRTUAL_PID", "SpanTracer",
    "to_jsonable", "validate_trace", "NULL_TELEMETRY", "NullTelemetry",
    "Telemetry", "make_telemetry", "DEFAULT_RULES", "NULL_HEALTH",
    "HealthMonitor", "NullHealthMonitor", "Rule", "Window",
    "EVENT_SCHEMAS", "SCHEMA_VERSION", "validate_event", "validate_runlog",
]
