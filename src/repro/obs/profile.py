"""Opt-in profiler hook: ``jax.profiler`` capture around scheduler batches.

:class:`ProfileCapture` wraps ``jax.profiler.start_trace`` /
``stop_trace`` around the next N scheduler batches.  The scheduler calls
``on_batch_start``/``on_batch_end`` unconditionally; the hook is inert
until armed, and degrades to a no-op where the profiler backend is
unavailable (it must never take serving down).  The trace it writes
holds the tracer's spans as ``knn.*`` annotations and the scan's device
ops under ``knn.*`` name scopes (see ``obs.trace``).
"""
from __future__ import annotations

import threading
from typing import Optional


class ProfileCapture:
    """Capture a ``jax.profiler`` trace around the next ``n_batches``
    scheduler batches, writing to ``logdir``."""

    def __init__(self, logdir: str, n_batches: int = 3):
        if n_batches < 1:
            raise ValueError("n_batches must be >= 1")
        self.logdir = logdir
        self.n_batches = n_batches
        self.seen = 0
        self.active = False
        self.done = False
        self.error: Optional[str] = None
        self._lock = threading.Lock()

    def on_batch_start(self) -> None:
        with self._lock:
            if self.done or self.active:
                return
            try:
                import jax

                jax.profiler.start_trace(self.logdir)
                self.active = True
            except Exception as e:  # noqa: BLE001 — profiling is best-effort
                self.error = f"{type(e).__name__}: {e}"
                self.done = True

    def on_batch_end(self) -> None:
        with self._lock:
            if not self.active:
                return
            self.seen += 1
            if self.seen >= self.n_batches:
                self._stop_locked()

    def stop(self) -> None:
        """Stop early (scheduler shutdown with the capture still open)."""
        with self._lock:
            if self.active:
                self._stop_locked()

    def _stop_locked(self) -> None:
        try:
            import jax

            jax.profiler.stop_trace()
        except Exception as e:  # noqa: BLE001 — see on_batch_start
            self.error = f"{type(e).__name__}: {e}"
        self.active = False
        self.done = True

    def summary(self) -> dict:
        return {"logdir": self.logdir, "batches": self.seen,
                "done": self.done, "error": self.error}
