"""Observability configuration (``ObsConfig``).

A plain frozen dataclass (hashable, replace-able) with NO repro imports,
so it can be embedded in ``configs.base.SimConfig`` — the thread that
carries it from the CLI (``launch/train.py``) through
``scenarios.build_engine`` into the engine — without import cycles.

``obs=None`` / ``enabled=False`` resolve to the shared null telemetry
(``repro.obs.telemetry.NULL_TELEMETRY``): every emit site in the hot loops
is guarded by one attribute check (``obs.enabled``), so a run without
observability pays nothing but the host spans' profiler annotations (about
a microsecond each) and replays bit-identically (tracing only ever *reads*
engine state; it never touches the RNG or the virtual clock).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional


@dataclass(frozen=True)
class ObsConfig:
    """Knobs for the telemetry layer (``repro.obs``)."""

    enabled: bool = True
    # Chrome/Perfetto trace-event JSON output path (--trace-viz); None
    # keeps spans in memory only (still available for the conservation
    # check and tests)
    trace_path: Optional[str] = None
    # structured run-log JSONL path (--metrics-out); consumed by
    # launch/train.py's RunLogger, carried here so one config travels
    metrics_path: Optional[str] = None
    # host-clock Chrome events (pid 2) of the engine's host spans (hfl.step,
    # hfl.round, hfl.batch, hfl.train, hfl.sync, hfl.on_step). Durations
    # measure *dispatch* time — jax runs async — so the first call shows
    # trace+compile and steady calls show enqueue. The spans are profiler
    # annotations whether or not this is on.
    host_spans: bool = True
    # emit a live events/s + live-bytes heartbeat every N engine events
    # (gauges in the registry + one stderr line); 0 = off
    heartbeat_events: int = 0
    # span-event cap: fleet-scale runs keep the trace bounded. Past the
    # cap events are counted (``dropped_events`` in the export metadata)
    # but not stored; per-link bit accumulation continues regardless, so
    # the conservation check stays exact.
    max_trace_events: int = 2_000_000
    # learning-health monitoring (--obs-health): in-jit sync statistics
    # (consensus drift, residual norms, Ω overlap), streaming anomaly
    # rules, fleet participation-fairness. Stats are extra read-only
    # outputs of the jitted sync step — replay stays bit-identical.
    health: bool = False
    # streaming-window length (observations) for the health aggregators;
    # anomaly rules evaluate over this window
    health_window: int = 64
