"""Continuous-batching scheduler (repro.serve): de-interleaving parity,
flush policy, backpressure, non-blocking dispatch, retry/watchdog."""
import asyncio
import dataclasses
import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import JoinSpec
from repro.core.engine import JoinStats
from repro.runtime.fault import RetryPolicy
from repro.serve import KNNScheduler, QueueFull, ServeConfig
from repro.sparse.datagen import synthetic_sparse
from repro.sparse.format import SparseBatch
from repro.store import ShardedKNNStore


def rows_of(R: SparseBatch, lo: int, hi: int) -> SparseBatch:
    return SparseBatch(indices=R.indices[lo:hi], values=R.values[lo:hi],
                       nnz=R.nnz[lo:hi], dim=R.dim)


def tiny_rows(n: int, f: int = 3, dim: int = 32) -> SparseBatch:
    idx = np.tile(np.arange(f, dtype=np.int32), (n, 1))
    val = np.ones((n, f), np.float32)
    return SparseBatch(indices=jnp.asarray(idx), values=jnp.asarray(val),
                       nnz=jnp.asarray(np.full(n, f, np.int32)), dim=dim)


# ---------------------------------------------------------------------------
# stub store: scheduler behaviour without device work
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class _StubSpec:
    k: int = 4


class _StubStats:
    index_builds = 0


class _StubResult:
    def __init__(self, ids, scores, stats):
        self.ids, self.scores, self.stats = ids, scores, stats


class StubStore:
    """Deterministic per-row results: id row r = nnz[r]*10 + [0..k)."""

    dim = 32
    spec = _StubSpec()
    stats = _StubStats()

    def __init__(self, sleep_s: float = 0.0, fail_first: int = 0):
        self.sleep_s = sleep_s
        self.fail_first = fail_first
        self.calls = 0
        self.batch_rows = []
        self.live_rows = []                 # non-pad rows of each batch
        self.started = threading.Event()

    def query(self, R: SparseBatch):
        self.started.set()
        self.calls += 1
        if self.calls <= self.fail_first:
            raise RuntimeError("injected dispatch failure")
        if self.sleep_s:
            time.sleep(self.sleep_s)
        self.batch_rows.append(R.num_vectors)
        self.live_rows.append(int((np.asarray(R.nnz) > 0).sum()))
        base = np.asarray(R.nnz)[:, None].astype(np.int32) * 10
        ids = base + np.arange(self.spec.k, dtype=np.int32)[None, :]
        st = JoinStats()
        st.device_dispatches = 1
        st.host_syncs = 1
        return _StubResult(jnp.asarray(ids),
                           jnp.asarray(ids.astype(np.float32) / 100.0), st)


# ---------------------------------------------------------------------------
# de-interleaving parity against the real store
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("algorithm", ["iib", "iiib"])
def test_deinterleave_parity_ragged_sizes_and_k(algorithm):
    """A batch mixing ragged request sizes and differing k must return
    bit-identical ids/scores to per-request direct store.query() calls —
    including after interleaved add()/expire()/delete() mutations."""

    S = synthetic_sparse(96, dim=256, nnz_mean=12, seed=1)
    R = synthetic_sparse(36, dim=256, nnz_mean=10, seed=2)
    store = ShardedKNNStore.build(
        S, JoinSpec(k=5, algorithm=algorithm, r_block=8, s_block=32))
    sizes = [1, 3, 2, 5, 4, 1, 2, 6]
    ks = [5, 2, 4, 5, 1, 3, 5, 2]
    bounds = np.concatenate([[0], np.cumsum(sizes)])

    def check(outs, round_requests):
        for (ids, scores), (lo, hi, k) in zip(outs, round_requests):
            direct = store.query(rows_of(R, lo, hi))
            assert ids.shape == (hi - lo, k)
            np.testing.assert_array_equal(ids, np.asarray(direct.ids)[:, :k])
            np.testing.assert_array_equal(
                scores, np.asarray(direct.scores)[:, :k])

    async def main():
        reqs = [(int(bounds[i]), int(bounds[i + 1]), ks[i])
                for i in range(len(sizes))]
        async with KNNScheduler(
            store, ServeConfig(r_block=8, window_s=0.02)
        ) as sched:
            outs = await asyncio.gather(*[
                sched.submit(rows_of(R, lo, hi), k=k) for lo, hi, k in reqs])
            check(outs, reqs)

            # mutate the store through the scheduler (serialized with
            # dispatches): add a TTL'd batch, expire it later, delete ids
            await sched.mutate(store.add, rows_of(R, 24, 36), ttl=5.0, now=0.0)
            await sched.mutate(store.delete, [0, 1])
            outs = await asyncio.gather(*[
                sched.submit(rows_of(R, lo, hi), k=k) for lo, hi, k in reqs])
            check(outs, reqs)

            await sched.mutate(store.expire, 10.0)   # TTL batch tombstones
            outs = await asyncio.gather(*[
                sched.submit(rows_of(R, lo, hi), k=k) for lo, hi, k in reqs])
            check(outs, reqs)

            assert sched.metrics.query_index_builds == 0
            assert sched.metrics.completed == 3 * len(sizes)

    asyncio.run(main())


def test_store_ids_are_global(tmp_path=None):
    """De-interleaved ids are the store's stable global ids (no per-batch
    renumbering): every returned id indexes into the concatenated S."""
    S = synthetic_sparse(64, dim=128, nnz_mean=8, seed=3)
    store = ShardedKNNStore.build(S, JoinSpec(k=3, algorithm="iib",
                                              r_block=8, s_block=16))
    R = synthetic_sparse(8, dim=128, nnz_mean=8, seed=4)

    async def main():
        async with KNNScheduler(store, ServeConfig(r_block=8)) as sched:
            outs = await asyncio.gather(*[
                sched.submit(rows_of(R, i, i + 1)) for i in range(8)])
        for ids, scores in outs:
            valid = scores > -np.inf
            assert ((ids[valid] >= 0) & (ids[valid] < 64)).all()

    asyncio.run(main())


# ---------------------------------------------------------------------------
# flush policy
# ---------------------------------------------------------------------------

def test_flush_on_block_full_before_window():
    """queued rows == r_block flushes immediately, not at window expiry."""
    store = StubStore()

    async def main():
        cfg = ServeConfig(r_block=4, window_s=30.0)  # window would stall CI
        t0 = time.monotonic()
        async with KNNScheduler(store, cfg) as sched:
            await asyncio.gather(*[sched.submit(tiny_rows(1)) for _ in range(4)])
        assert time.monotonic() - t0 < 5.0
        assert store.calls == 1 and store.batch_rows == [4]

    asyncio.run(main())


def test_flush_on_window_expiry():
    """A partial batch flushes once the oldest request waited window_s."""
    store = StubStore()

    async def main():
        cfg = ServeConfig(r_block=64, window_s=0.02)
        async with KNNScheduler(store, cfg) as sched:
            t0 = time.monotonic()
            await sched.submit(tiny_rows(2))
            waited = time.monotonic() - t0
        assert store.batch_rows == [64]     # padded to the block shape
        assert waited >= 0.015              # sat out (most of) the window

    asyncio.run(main())


def test_flush_on_deadline_pressure():
    """A tight request deadline overrides a long micro-batch window."""
    store = StubStore()

    async def main():
        cfg = ServeConfig(r_block=64, window_s=30.0)
        t0 = time.monotonic()
        async with KNNScheduler(store, cfg) as sched:
            await sched.submit(tiny_rows(1), deadline=0.05)
        assert time.monotonic() - t0 < 5.0
        assert store.calls == 1

    asyncio.run(main())


def test_head_of_line_request_never_splits():
    """Requests pack whole: a request that would overflow r_block starts
    the next batch instead of splitting its rows across two dispatches."""
    store = StubStore()

    async def main():
        cfg = ServeConfig(r_block=4, window_s=0.01)
        async with KNNScheduler(store, cfg) as sched:
            await asyncio.gather(
                sched.submit(tiny_rows(3)), sched.submit(tiny_rows(3)))
        assert store.calls == 2
        assert store.batch_rows == [4, 4]   # 3+pad | 3+pad, never 4|2

    asyncio.run(main())


# ---------------------------------------------------------------------------
# forming waits for the in-flight batch
# ---------------------------------------------------------------------------

async def _until_set(started: threading.Event):
    while not started.is_set():           # first batch inside query()
        await asyncio.sleep(0.001)


def test_requests_behind_inflight_batch_coalesce():
    """Requests that arrive while batch A is inside store.query() are held,
    not formed one per window: they leave as ONE batch B once A is done."""
    store = StubStore(sleep_s=0.3)

    async def main():
        cfg = ServeConfig(r_block=16, window_s=0.001)
        async with KNNScheduler(store, cfg) as sched:
            a = asyncio.create_task(sched.submit(tiny_rows(2)))
            await _until_set(store.started)
            held = []
            for _ in range(5):                # each one's window expires
                held.append(asyncio.create_task(sched.submit(tiny_rows(1))))
                await asyncio.sleep(0.01)
            assert sched.metrics.queue_depth == 5     # still queued
            await asyncio.gather(a, *held)
        assert store.calls == 2
        assert store.live_rows == [2, 5]
        assert sched.metrics.held_batches == 1
        assert sched.metrics.completed == 6

    asyncio.run(main())


def test_held_rows_count_toward_high_water_mark():
    """Rows held behind an in-flight batch stay queued, so admission
    control sees them: a submit past queue_rows_hwm bounces, and its
    retry succeeds once the held rows drain."""
    store = StubStore(sleep_s=0.3)

    async def main():
        cfg = ServeConfig(r_block=4, window_s=0.001, queue_rows_hwm=6)
        async with KNNScheduler(store, cfg) as sched:
            a = asyncio.create_task(sched.submit(tiny_rows(4)))
            await _until_set(store.started)
            held = []
            for _ in range(3):
                held.append(asyncio.create_task(sched.submit(tiny_rows(1))))
                await asyncio.sleep(0.01)
            with pytest.raises(QueueFull) as exc:
                await sched.submit(tiny_rows(4))  # 3 held + 4 > hwm=6
            assert exc.value.retry_after_s > 0
            await asyncio.gather(a, *held)
            await sched.submit(tiny_rows(4))      # the retry is admitted
        assert sched.metrics.rejected == 1
        assert sched.metrics.completed == 5
        assert store.live_rows == [4, 3, 4]

    asyncio.run(main())


def test_stop_with_drain_completes_held_requests():
    """stop(drain=True) while requests are held behind an in-flight batch
    forms them into a batch once the worker frees and completes all."""
    store = StubStore(sleep_s=0.2)

    async def main():
        cfg = ServeConfig(r_block=8, window_s=0.001)
        sched = await KNNScheduler(store, cfg).start()
        a = asyncio.create_task(sched.submit(tiny_rows(2)))
        await _until_set(store.started)
        held = [asyncio.create_task(sched.submit(tiny_rows(n)))
                for n in (1, 2, 3)]
        await asyncio.sleep(0.02)
        assert sched.metrics.queue_depth == 6
        await sched.stop(drain=True)
        for t, n in zip([a, *held], (2, 1, 2, 3)):
            assert t.done()
            ids, _ = t.result()
            assert ids.shape == (n, 4)
        assert store.live_rows == [2, 6]
        assert sched.metrics.completed == 4
        assert sched.metrics.failed == 0
        assert sched.metrics.held_batches == 1
        assert sched.metrics.queue_depth == 0
        assert sched.metrics.inflight == 0

    asyncio.run(main())


@pytest.mark.parametrize("algorithm", ["iib", "iiib"])
def test_held_batch_parity_ragged_sizes_and_k(algorithm):
    """Batches formed behind an in-flight batch coalesce ragged requests
    of differing k; each answer is bit-identical to a direct store.query()
    of the same rows."""
    S = synthetic_sparse(96, dim=256, nnz_mean=12, seed=1)
    R = synthetic_sparse(36, dim=256, nnz_mean=10, seed=2)
    store = ShardedKNNStore.build(
        S, JoinSpec(k=5, algorithm=algorithm, r_block=8, s_block=32))
    direct = store.query
    started = threading.Event()

    def slow_query(batch, **kw):            # keeps each batch in flight
        started.set()
        time.sleep(0.05)
        return direct(batch, **kw)

    store.query = slow_query
    sizes = [2, 1, 3, 2, 5, 4, 1, 2, 6]
    ks = [5, 5, 2, 4, 5, 1, 3, 5, 2]
    bounds = np.concatenate([[0], np.cumsum(sizes)])
    reqs = [(int(bounds[i]), int(bounds[i + 1]), ks[i])
            for i in range(len(sizes))]

    async def main():
        async with KNNScheduler(
            store, ServeConfig(r_block=8, window_s=0.001)
        ) as sched:
            (lo, hi, k), rest = reqs[0], reqs[1:]
            first = asyncio.create_task(sched.submit(rows_of(R, lo, hi), k=k))
            await _until_set(started)
            outs = await asyncio.gather(first, *[
                sched.submit(rows_of(R, lo, hi), k=k) for lo, hi, k in rest])
        for (ids, scores), (lo, hi, k) in zip(outs, reqs):
            want = direct(rows_of(R, lo, hi))
            assert ids.shape == (hi - lo, k)
            np.testing.assert_array_equal(ids, np.asarray(want.ids)[:, :k])
            np.testing.assert_array_equal(
                scores, np.asarray(want.scores)[:, :k])
        m = sched.metrics
        assert m.held_batches >= 1
        assert m.batches < len(reqs)         # held requests coalesced
        assert m.completed == len(reqs)

    asyncio.run(main())


# ---------------------------------------------------------------------------
# admission control + non-blocking dispatch
# ---------------------------------------------------------------------------

def test_backpressure_rejects_with_retry_after():
    store = StubStore(sleep_s=0.2)

    async def main():
        cfg = ServeConfig(r_block=4, window_s=0.001, queue_rows_hwm=6)
        async with KNNScheduler(store, cfg) as sched:
            t1 = asyncio.create_task(sched.submit(tiny_rows(4)))
            await asyncio.sleep(0.05)            # first batch now in flight
            t2 = asyncio.create_task(sched.submit(tiny_rows(4)))
            await asyncio.sleep(0)               # t2 queued: 4 rows
            with pytest.raises(QueueFull) as exc:
                await sched.submit(tiny_rows(4))  # 4 + 4 > hwm=6 → bounce
            assert exc.value.retry_after_s > 0
            await asyncio.gather(t1, t2)
            # queue drained — the bounced caller's retry now succeeds
            await sched.submit(tiny_rows(4))
        assert sched.metrics.rejected == 1
        assert sched.metrics.completed == 3

    asyncio.run(main())


def test_submit_returns_while_batch_in_flight():
    """The flush path must not hold the queue across the device dispatch:
    new submissions are admitted (and the event loop stays responsive)
    while a batch is inside store.query()."""
    store = StubStore(sleep_s=0.4)

    async def main():
        cfg = ServeConfig(r_block=2, window_s=0.001)
        async with KNNScheduler(store, cfg) as sched:
            a = asyncio.create_task(sched.submit(tiny_rows(2)))
            while not store.started.is_set():     # batch A inside query()
                await asyncio.sleep(0.001)
            t0 = time.monotonic()
            b = asyncio.create_task(sched.submit(tiny_rows(1)))
            await asyncio.sleep(0)
            admit_wall = time.monotonic() - t0
            assert sched.metrics.submitted == 2   # B admitted mid-flight
            assert not a.done() and not b.done()
            assert admit_wall < 0.1               # ≪ the 0.4s dispatch
            await asyncio.gather(a, b)
        assert store.calls == 2

    asyncio.run(main())


# ---------------------------------------------------------------------------
# watchdog + retry
# ---------------------------------------------------------------------------

def test_dispatch_retry_then_success():
    store = StubStore(fail_first=1)

    async def main():
        cfg = ServeConfig(
            r_block=2, window_s=0.001,
            retry=RetryPolicy(max_retries=2, backoff_s=0.001, jitter=0.5))
        async with KNNScheduler(store, cfg) as sched:
            ids, scores = await sched.submit(tiny_rows(1))
        assert ids.shape == (1, 4)
        assert sched.metrics.retries == 1
        assert sched.metrics.failed == 0

    asyncio.run(main())


def test_batch_timeout_exhausts_and_fails_futures():
    store = StubStore(sleep_s=0.5)

    async def main():
        cfg = ServeConfig(
            r_block=2, window_s=0.001, batch_timeout_s=0.02,
            retry=RetryPolicy(max_retries=1, backoff_s=0.001))
        async with KNNScheduler(store, cfg) as sched:
            with pytest.raises(RuntimeError, match="batch dispatch failed"):
                await sched.submit(tiny_rows(1))
        assert sched.metrics.timeouts >= 1
        assert sched.metrics.failed == 1
        assert sched.metrics.completed == 0

    asyncio.run(main())


def test_stop_without_drain_fails_queued_but_completes_inflight():
    """stop(drain=False) with a batch inside store.query(): the in-flight
    batch still delivers (stop awaits dispatch tasks), but queued requests
    that never made a batch fail immediately with a stopped error — and
    the queue-depth gauge returns to zero, not negative."""
    store = StubStore(sleep_s=0.3)

    async def main():
        cfg = ServeConfig(r_block=2, window_s=5.0)   # window parks request B
        sched = await KNNScheduler(store, cfg).start()
        a = asyncio.create_task(sched.submit(tiny_rows(2)))  # block-full flush
        while not store.started.is_set():     # batch A inside query()
            await asyncio.sleep(0.001)
        b = asyncio.create_task(sched.submit(tiny_rows(1)))  # queued only
        await asyncio.sleep(0.01)
        assert sched.metrics.submitted == 2
        await sched.stop(drain=False)
        ids, scores = await a                 # in-flight batch delivered
        assert ids.shape == (2, 4)
        with pytest.raises(RuntimeError, match="stopped without drain"):
            await b
        assert store.calls == 1               # B never dispatched
        assert sched.metrics.failed == 1
        assert sched.metrics.completed == 1
        assert sched.metrics.queue_depth == 0
        assert sched.metrics.inflight == 0

    asyncio.run(main())


# ---------------------------------------------------------------------------
# metrics + validation
# ---------------------------------------------------------------------------

def test_metrics_summary_schema():
    store = StubStore()

    async def main():
        cfg = ServeConfig(r_block=4, window_s=0.005)
        async with KNNScheduler(store, cfg) as sched:
            await asyncio.gather(*[sched.submit(tiny_rows(2)) for _ in range(6)])
        s = sched.metrics.summary()
        assert s["requests"]["submitted"] == s["requests"]["completed"] == 6
        assert s["requests"]["inflight_peak"] >= 1
        assert s["latency"]["p50_ms"] is not None
        assert s["latency"]["p99_ms"] >= s["latency"]["p50_ms"]
        assert s["throughput"]["queries_per_s"] > 0
        assert 0 < s["batches"]["mean_occupancy"] <= 1.0
        assert s["batches"]["count"] == store.calls
        assert s["dispatch"]["device_dispatches"] == store.calls
        assert s["dispatch"]["query_index_builds"] == 0
        assert s["queue"]["depth"] == 0

    asyncio.run(main())


def test_submit_validation():
    store = StubStore()

    async def main():
        async with KNNScheduler(store, ServeConfig(r_block=4)) as sched:
            with pytest.raises(ValueError, match="rows > r_block"):
                await sched.submit(tiny_rows(5))
            with pytest.raises(ValueError, match="k="):
                await sched.submit(tiny_rows(1), k=9)
            with pytest.raises(ValueError, match="dim mismatch"):
                await sched.submit(tiny_rows(1, dim=64))

    asyncio.run(main())


# ---------------------------------------------------------------------------
# end-to-end showcase: kNN-LM serving over a real fan-out
# ---------------------------------------------------------------------------

@pytest.mark.subproc
def test_knnlm_serve_example_under_fanout():
    """The example's full loop — scheduler-coalesced decode + background
    traffic, per-token add() + TTL expire() through mutate() — runs under
    forced virtual devices (its own asserts check zero query-time builds,
    completed == submitted, and real coalescing)."""
    from tests.util_subproc import run_with_devices

    out = run_with_devices(
        "import runpy; runpy.run_path('examples/knnlm_serve.py', "
        "run_name='__main__')",
        n_devices=2,
    )
    assert "serving:" in out and "coalesced" in out
