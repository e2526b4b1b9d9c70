"""Continuous-batching serving front-end for the KNN store (DESIGN.md §8).

  KNNScheduler — async request coalescing: concurrent ``submit(rows, k,
                 deadline)`` calls pack into full r_block-sized batches
                 (micro-batch window / block-full / deadline-pressure
                 flush), dispatch through ONE store query per batch, and
                 de-interleave bit-identical per-request results.
  ServeConfig  — flush window, admission high-water mark, batch watchdog
                 + retry policy, batch geometry.
  ServeMetrics — rolling p50/p99 latency, queue depth, batch occupancy,
                 queries/sec, store dispatch counters.
  ServeResult  — the ``(ids, scores)`` pair ``submit`` resolves to,
                 carrying ``missing_shards`` when served degraded.
  QueueFull    — admission-control bounce carrying ``retry_after_s``.
"""
from repro.serve.metrics import RollingWindow, ServeMetrics
from repro.serve.scheduler import (
    KNNScheduler,
    QueueFull,
    ServeConfig,
    ServeResult,
)

__all__ = [
    "KNNScheduler",
    "QueueFull",
    "RollingWindow",
    "ServeConfig",
    "ServeResult",
    "ServeMetrics",
]
