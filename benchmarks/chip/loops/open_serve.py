"""An open loop of independent clients through the program's serving front
end: ``KNNScheduler(store)`` with the default ``ServeConfig``, each
request sent with ``submit(rows, k=...)``.

The schedule comes from the mix's own ``structure_seed``, so every run
sends the same one (``schedule``): Poisson arrivals at
``rate_req_per_s`` over ``[0, seconds)``; each request takes the next
``rows_min``..``rows_max`` rows (uniform) of the query pool, cycling
through it, and asks for k drawn uniformly from ``ks``.  The rows'
weights come from the run's seed, as the pool does.  A request carries
its rows at their own feature width, as a client holds them, so the
scheduler pads each batch to one of its ``feature_bucket`` widths.

Set-up sends one request of every width bucket the schedule holds, each
alone, so that nothing compiles in the window, then restarts the
scheduler's window counters (``ServeMetrics.reset_window``).

In the window the sender sleeps to each request's due time and submits
it.  A ``QueueFull`` is resubmitted after its ``retry_after_s``; only a
request that ends in an error counts as failed.  A request's latency runs
from its due time, not from when it was sent, to its answer.  The window
closes when the first request that returns at or after ``seconds``
returns (or the last, if none does): ``served_rows_per_s`` and
``requests_per_s`` are what was answered by then over that time.  Nothing
is due after ``seconds``; the loop then drains, untimed, and stops the
scheduler.

``metrics``: ``p50_ms``, ``p90_ms``, ``p95_ms`` over every request due in
the window, ``served_rows_per_s`` (kept apart from the join cells'
``rows_per_s``: its runs spread wider and need their own bound),
``requests_per_s``.  ``counters``: the scheduler's own ``ServeMetrics``
from the window's start to its close (``batches``, ``requests``
completed, ``live_rows``, and ``padded_rows``, batches × ``r_block``).

Mix parameters: ``rate_req_per_s``, ``structure_seed``, ``rows_min``,
``rows_max``, ``ks``, ``check_rows``.
"""
import asyncio
import time

import numpy as np

import loopkit


def schedule(traffic: dict, seconds: float, n_pool: int):
    """Due times (s from the window's start), pool rows and k of every
    request due in ``[0, seconds)``."""
    seed = int(traffic["structure_seed"])
    rng = np.random.default_rng([seed, 0])
    mean_gap = 1.0 / float(traffic["rate_req_per_s"])
    gaps = rng.exponential(mean_gap, 4096)
    while gaps.sum() < seconds:
        gaps = np.concatenate([gaps, rng.exponential(mean_gap, 4096)])
    due = np.cumsum(gaps)
    due = due[due < seconds]
    m = len(due)
    sizes = np.random.default_rng([seed, 1]).integers(
        int(traffic["rows_min"]), int(traffic["rows_max"]) + 1, m)
    ks = np.random.default_rng([seed, 2]).choice(np.asarray(traffic["ks"]), m)
    starts = np.cumsum(sizes) - sizes
    rows = [(s + np.arange(n)) % n_pool for s, n in zip(starts, sizes)]
    return due, rows, ks


def _bucket(width: int, m: int) -> int:
    """``width`` rounded up to a multiple of ``m`` (at least ``m``)."""
    return max(m, -(-width // m) * m)


def _request(pool, rows, dim):
    """Pool rows as a client's SparseBatch, at the rows' own width."""
    from repro.sparse.format import SparseBatch

    idx, val, nnz = pool
    f = max(1, int(nnz[rows].max()))
    return SparseBatch(indices=idx[rows, :f], values=val[rows, :f],
                       nnz=nnz[rows], dim=dim)


def _counters(m, r_block):
    return {"batches": m.batches, "requests": m.completed,
            "live_rows": m.batch_rows, "padded_rows": m.batches * r_block}


def _pct_ms(lat, q):
    return float(np.percentile(lat, q)) * 1e3 if len(lat) else None


def run(store, pool, dim, traffic, seconds, log_dir, counter, on_ready):
    return asyncio.run(_serve(store, pool, dim, traffic, seconds, log_dir,
                              counter, on_ready))


async def _serve(store, pool, dim, traffic, seconds, log_dir, counter, on_ready):
    from repro.serve import KNNScheduler, QueueFull

    due, rows, ks = schedule(traffic, seconds, pool[0].shape[0])
    m = len(due)
    batches = [_request(pool, r, dim) for r in rows]
    sent = np.full(m, np.nan)
    done = np.full(m, np.nan)
    answers = [None] * m
    failed = bounces = 0
    errors = []
    closed = asyncio.Event()
    t0 = close_at = None

    async with KNNScheduler(store) as sched:
        fb = sched.config.feature_bucket
        first = {}
        for i, b in enumerate(batches):
            first.setdefault(_bucket(b.indices.shape[1], fb), i)
        t_w = time.perf_counter()
        for i in first.values():
            await sched.submit(batches[i], k=int(ks[i]))
        warm = {"warmup_s": time.perf_counter() - t_w, "warmup_calls": len(first)}
        ready = on_ready()
        sched.metrics.reset_window()
        base = _counters(sched.metrics, sched.r_block)

        async def one(i):
            nonlocal failed, bounces, close_at
            sent[i] = time.perf_counter()
            while True:
                try:
                    ids, scores = await sched.submit(batches[i], k=int(ks[i]))
                    break
                except QueueFull as e:
                    bounces += 1
                    await asyncio.sleep(e.retry_after_s)
                except Exception as e:  # noqa: BLE001 - counted and reported
                    failed += 1
                    errors.append(f"{type(e).__name__}: {e}")
                    ids = scores = None
                    break
            done[i] = t = time.perf_counter()
            if ids is not None:
                answers[i] = (rows[i], np.asarray(ids), np.asarray(scores), int(ks[i]))
            if close_at is None and (t - t0 >= seconds or not np.isnan(done).any()):
                close_at = t
                closed.set()

        compiles0 = counter.count
        blocks0 = store.stats.device_dispatches
        tasks = []
        with loopkit.traced(log_dir):
            t0 = time.perf_counter()
            for i in range(m):
                delay = t0 + due[i] - time.perf_counter()
                if delay > 0:
                    await asyncio.sleep(delay)
                tasks.append(asyncio.create_task(one(i)))
            if m:
                await closed.wait()
            end = _counters(sched.metrics, sched.r_block)
            blocks = store.stats.device_dispatches - blocks0
        compiles = counter.count - compiles0
        t_drain = time.perf_counter()
        await asyncio.gather(*tasks)
    drain_s = time.perf_counter() - t_drain

    close_s = (close_at - t0) if m else seconds
    by_close = done <= close_at if m else np.zeros(0, bool)
    rows_by_close = int(sum(len(rows[i]) for i in np.flatnonzero(by_close)))
    ok = [i for i in range(m) if answers[i] is not None]
    lat = done[ok] - (t0 + due[ok])
    late = sent - (t0 + due)
    return {
        "ready": ready,
        "window_s": close_s,
        "metrics": {
            "p50_ms": _pct_ms(lat, 50),
            "p90_ms": _pct_ms(lat, 90),
            "p95_ms": _pct_ms(lat, 95),
            "served_rows_per_s": rows_by_close / close_s,
            "requests_per_s": int(by_close.sum()) / close_s,
        },
        "attempted": m,
        "failed": failed,
        "compiles_in_window": compiles,
        "blocks": blocks,
        "window_rows": np.concatenate([rows[i] for i in np.flatnonzero(by_close)]
                                      or [np.zeros(0, np.int64)]),
        "answers": [answers[i] for i in ok],
        "counters": {c: end[c] - base[c] for c in end},
        "info": {
            "r_block": sched.r_block,
            "requests_answered_by_close": int(by_close.sum()),
            "lateness_ms_p50": _pct_ms(late, 50),
            "lateness_ms_max": float(np.max(late)) * 1e3 if m else None,
            "queue_full": bounces,
            "first_errors": errors[:3],
            "drain_s": drain_s,
        },
        **warm,
    }
