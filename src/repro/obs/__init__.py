"""Unified observability layer (DESIGN.md §12).

  registry — typed Counter/Gauge/Histogram instruments behind the
             serving/store stats objects, with OpenMetrics-style text
             exposition (`MetricRegistry.expose`) next to the unchanged
             JSON `summary()` schemas.
  trace    — span trees (request → batch → store dispatch → R-block
             fan-out; mutations, recovery, resync, checkpoint) with
             monotonic timestamps, propagated via a per-thread context
             stack so layers compose without signature threading.
  recorder — the flight recorder: a bounded ring of recent spans and
             fault events that dumps JSONL on demand and automatically
             on fault.
  profile  — opt-in `jax.profiler` capture around N batches; every span
             is also a `knn.<name>` annotation on the profiler's timeline.
"""
from repro.obs.recorder import FlightRecorder, get_recorder, set_recorder
from repro.obs.registry import (
    Counter,
    Gauge,
    Histogram,
    MetricRegistry,
    get_registry,
    parse_exposition,
    set_registry,
)
from repro.obs.trace import Span, Tracer, default_tracer, set_tracing
from repro.obs.profile import ProfileCapture

__all__ = [
    "Counter",
    "FlightRecorder",
    "Gauge",
    "Histogram",
    "MetricRegistry",
    "ProfileCapture",
    "Span",
    "Tracer",
    "default_tracer",
    "get_recorder",
    "get_registry",
    "parse_exposition",
    "set_recorder",
    "set_registry",
    "set_tracing",
]
