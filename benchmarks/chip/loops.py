"""The traffic loops a mix's file names (``"loop"``), driven by its
parameters.

``closed_join`` (``"loop": "closed_join"``): one caller sends
``store.query`` on the next ``rows_per_call`` rows of the query pool,
cycling through it, and sends the next call when the last returns.  It
warms up the one call shape the window uses, then measures for
``seconds``; the window closes when the first call that finishes after
``seconds`` completes.  With ``trace`` the window runs under the profiler,
wrapped in a ``bench.window`` annotation.  It returns a record of
host-clock numbers, counters and the answers to check.
"""
from __future__ import annotations

import contextlib
import time

import jax
import numpy as np
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


def closed_join(store, pool, dim, traffic, seconds, log_dir, counter, on_ready):
    n = pool[0].shape[0]
    per = int(traffic["rows_per_call"])

    def call_rows(i):
        return (np.arange(per) + i * per) % n

    t_w = time.perf_counter()
    res = store.query(rows_batch(pool, call_rows(0), dim))
    np.asarray(res.ids)
    warm = {"warmup_s": time.perf_counter() - t_w, "warmup_calls": 1}
    del res
    ready = on_ready()

    compiles0 = counter.count
    blocks0 = store.stats.device_dispatches
    answers = []
    with traced(log_dir):
        t0 = time.perf_counter()
        i = 0
        while True:
            batch = rows_batch(pool, call_rows(i), dim)
            with TraceAnnotation("bench.query"):
                res = store.query(batch)
            with TraceAnnotation("bench.pull"):
                ids, scores = np.asarray(res.ids), np.asarray(res.scores)
            answers.append((call_rows(i), ids, scores))
            i += 1
            t1 = time.perf_counter()
            if t1 - t0 >= seconds:
                break
    rows = sum(len(a[0]) for a in answers)
    return {
        "ready": ready,
        "window_s": t1 - t0,
        "rows_per_s": rows / (t1 - t0),
        "attempted": i,
        "failed": 0,
        "compiles_in_window": counter.count - compiles0,
        "blocks": store.stats.device_dispatches - blocks0,
        "window_rows": np.concatenate([a[0] for a in answers]),
        "answers": answers,
        **warm,
    }


LOOPS = {"closed_join": closed_join}
