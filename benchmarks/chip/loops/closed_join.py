"""One caller sends ``store.query`` on the next ``rows_per_call`` rows of
the query pool, cycling through it, and sends the next call when the last
returns.  It warms up the one call shape the window uses, then measures
for ``seconds``; the window closes when the first call that finishes
after ``seconds`` completes.

Mix parameters: ``rows_per_call``, ``check_rows``.
"""
import time

import numpy as np
from jax.profiler import TraceAnnotation

import loopkit


def run(store, pool, dim, traffic, seconds, log_dir, counter, on_ready):
    n = pool[0].shape[0]
    per = int(traffic["rows_per_call"])

    def call_rows(i):
        return (np.arange(per) + i * per) % n

    t_w = time.perf_counter()
    res = store.query(loopkit.rows_batch(pool, call_rows(0), dim))
    np.asarray(res.ids)
    warm = {"warmup_s": time.perf_counter() - t_w, "warmup_calls": 1}
    del res
    ready = on_ready()

    compiles0 = counter.count
    blocks0 = store.stats.device_dispatches
    answers = []
    with loopkit.traced(log_dir):
        t0 = time.perf_counter()
        i = 0
        while True:
            batch = loopkit.rows_batch(pool, call_rows(i), dim)
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
        "metrics": {"rows_per_s": rows / (t1 - t0)},
        "attempted": i,
        "failed": 0,
        "compiles_in_window": counter.count - compiles0,
        "blocks": store.stats.device_dispatches - blocks0,
        "window_rows": np.concatenate([a[0] for a in answers]),
        "answers": answers,
        **warm,
    }
