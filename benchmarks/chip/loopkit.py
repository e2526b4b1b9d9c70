"""What the traffic loops (``loops/<name>.py``) share: a compile counter,
the traced window, and pool rows as a batch.

A loop warms up every shape its window uses, calls ``on_ready`` (the end
of set-up), then measures for ``seconds``.  With a ``log_dir`` the window
runs under the profiler, wrapped in a ``bench.window`` annotation.  It
returns a record of host-clock numbers, counters and the answers to
check (see ``layout.py``).
"""
from __future__ import annotations

import contextlib

import jax
from jax.profiler import TraceAnnotation


class CompileCounter:
    """Counts backend compiles (and persistent-cache loads) as they happen."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == self.EVENT:
            self.count += 1

    def close(self):
        jax.monitoring.unregister_event_duration_listener(self._on)


@contextlib.contextmanager
def traced(log_dir):
    """Profile the block when ``log_dir`` is set; always annotate it as the
    window."""
    if log_dir is not None:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        with TraceAnnotation("bench.window"):
            yield
    finally:
        if log_dir is not None:
            jax.profiler.stop_trace()


def rows_batch(pool, ix, dim):
    """Pool rows ``ix`` as a SparseBatch on the host, at the pool's
    feature width."""
    from repro.sparse.format import SparseBatch

    idx, val, nnz = pool
    return SparseBatch(indices=idx[ix], values=val[ix], nnz=nnz[ix], dim=dim)
