"""Open-loop serving load bench: Poisson arrivals against the scheduler.

Open-loop means arrivals do NOT wait for completions (the honest way to
measure a serving system: a closed loop self-throttles and hides the
latency cliff).  Thousands of small requests (1–4 sparse rows each)
arrive on a Poisson process, pile up in flight, and the scheduler
coalesces them into full r_block batches over the sharded store.  The
bench records:

  * p50/p99 submit→result latency and queries/sec, plus a 10-bucket
    latency/throughput trajectory over the run;
  * peak concurrent in-flight requests (the acceptance bar is ≥ 1k);
  * a batch-size-1 baseline — the same requests served by direct
    per-request ``store.query()`` calls — and the batched/serial
    queries-per-sec speedup (the acceptance bar is ≥ 3x);
  * a parity sample: scheduler results must be bit-identical to direct
    ``store.query()`` on the same rows;
  * the dispatch shape: device dispatches per request and query-time
    index builds (must be 0 — build-once is the store's contract).

With ``--fault-plan`` the bench instead records the ``serving_faulted``
stream: the same open loop, but a :class:`FaultPlan` kills shard 0
mid-traffic.  The scheduler (``allow_partial=True`` + a ``recover``
hook) must complete EVERY in-flight future — degraded (flagged with the
missing shard set) or full after recovery, never dropped — and results
must return to bit-parity with direct queries once the shard rebuilds
from its checkpoint slice.

With ``--replica-fault`` the bench records the ``replica_faulted``
stream: a ``replicas=2`` store under the same open loop, a
:class:`FaultPlan` replica kill mid-traffic.  The bar is STRICTLY
stronger than the shard-loss stream: failover inside the store must
absorb the loss entirely — every future completes FULL (zero degraded
results, ``allow_partial`` stays off), ``replica_failovers >= 1``, the
background anti-entropy resync repairs the dead replica behind the
traffic, and ``verify_replicas()`` asserts post-resync bit-parity.

  PYTHONPATH=src python -m benchmarks.serve_load --fast --merge BENCH_PR8.json
  PYTHONPATH=src python -m benchmarks.serve_load --fault-plan --merge BENCH_PR8.json
  XLA_FLAGS=--xla_force_host_platform_device_count=4 \
      PYTHONPATH=src python -m benchmarks.serve_load --replica-fault --merge BENCH_PR8.json
  XLA_FLAGS=--xla_force_host_platform_device_count=4 \
      PYTHONPATH=src python -m benchmarks.serve_load --smoke
"""
from __future__ import annotations

import argparse
import asyncio
import json
import shutil
import sys
import tempfile
import time

import numpy as np

from repro.core import JoinSpec
from repro.obs import FlightRecorder, set_recorder
from repro.runtime.compile_cache import enable_compile_cache
from repro.serve import KNNScheduler, QueueFull, ServeConfig
from repro.sparse.datagen import synthetic_sparse
from repro.sparse.format import SparseBatch
from repro.store import ShardedKNNStore


def slice_rows(R: SparseBatch, lo: int, hi: int) -> SparseBatch:
    return SparseBatch(indices=R.indices[lo:hi], values=R.values[lo:hi],
                       nnz=R.nnz[lo:hi], dim=R.dim)


def make_workload(n_requests: int, rate: float, max_rows: int, k: int,
                  dim: int, nnz: int, seed: int):
    """Pre-sampled open-loop workload: arrival offsets (Poisson process),
    per-request row spans into one shared R pool, and per-request k."""
    rng = np.random.default_rng(seed)
    sizes = rng.integers(1, max_rows + 1, n_requests)
    bounds = np.concatenate([[0], np.cumsum(sizes)])
    pool = synthetic_sparse(int(bounds[-1]), dim=dim, nnz_mean=nnz, seed=seed + 1)
    arrivals = np.cumsum(rng.exponential(1.0 / rate, n_requests))
    ks = rng.integers(max(1, k - 2), k + 1, n_requests)
    return pool, bounds, arrivals, ks


async def open_loop(store, pool, bounds, arrivals, ks, config: ServeConfig,
                    warm_rounds: int = 1, arm=None):
    """Fire the workload at its recorded arrival times; resubmit on
    admission bounces (after the advertised retry_after).

    ONE scheduler serves warmup and the timed run: ``warm_rounds`` full
    blocks of 1-row requests compile the batch-shaped program, then
    ``metrics.reset_window()`` restarts the measurement window (rolling
    latency/phase samples, window clock, gauge peaks — lifetime counters
    keep running) so the record measures serving, not XLA compilation.
    ``arm`` (optional zero-arg callable) runs after warmup — the fault
    benches install their FaultPlan there, so the plan's dispatch counter
    starts at the timed traffic."""
    n = len(arrivals)
    lat = np.zeros(n)
    done_at = np.zeros(n)
    bounces = 0

    async def one(i: int):
        nonlocal bounces
        rows = slice_rows(pool, int(bounds[i]), int(bounds[i + 1]))
        t0 = time.monotonic()
        while True:
            try:
                await sched.submit(rows, k=int(ks[i]))
                break
            except QueueFull as e:
                bounces += 1
                await asyncio.sleep(e.retry_after_s)
        lat[i] = time.monotonic() - t0
        done_at[i] = time.monotonic()

    async with KNNScheduler(store, config) as sched:
        rb = sched.r_block
        for _ in range(max(0, warm_rounds)):
            await asyncio.gather(*[
                sched.submit(slice_rows(pool, i, i + 1)) for i in range(rb)
            ])
        sched.metrics.reset_window()
        base = {c: getattr(sched.metrics, c) for c in _WINDOW_COUNTERS}
        if arm is not None:
            arm()
        t_start = time.monotonic()
        tasks = []
        for i in range(n):
            delay = t_start + arrivals[i] - time.monotonic()
            if delay > 0:
                await asyncio.sleep(delay)
            tasks.append(asyncio.create_task(one(i)))
        await asyncio.gather(*tasks)
        wall = time.monotonic() - t_start
        metrics = sched.metrics
    return lat, done_at - t_start, wall, bounces, metrics, base


# lifetime counters the bench records as window deltas (warm traffic runs
# through the SAME scheduler now, so record values subtract the post-warm
# baseline captured by open_loop)
_WINDOW_COUNTERS = ("completed", "failed", "batches", "batch_rows",
                    "device_dispatches")


def serial_baseline(store, pool, bounds, ks, sample: int):
    """Batch-size-1 submit loop: per-request direct store.query()."""
    n = min(sample, len(ks))
    # warm every compiled (rb = request size) variant before timing
    for size in sorted({int(bounds[i + 1] - bounds[i]) for i in range(n)}):
        store.query(slice_rows(pool, 0, size))
    lat = np.zeros(n)
    t0 = time.monotonic()
    for i in range(n):
        t = time.monotonic()
        store.query(slice_rows(pool, int(bounds[i]), int(bounds[i + 1])))
        lat[i] = time.monotonic() - t
    wall = time.monotonic() - t0
    return {
        "requests": n,
        "queries_per_s": round(n / wall, 2),
        "p50_ms": round(float(np.percentile(lat, 50)) * 1e3, 3),
        "p99_ms": round(float(np.percentile(lat, 99)) * 1e3, 3),
    }


def parity_sample(store, pool, bounds, ks, results_fn, sample: int) -> bool:
    """Scheduler results must match direct per-request queries bitwise."""
    idxs = np.linspace(0, len(ks) - 1, num=min(sample, len(ks)), dtype=int)
    for i in idxs:
        rows = slice_rows(pool, int(bounds[i]), int(bounds[i + 1]))
        direct = store.query(rows)
        ids, scores = results_fn(int(i))
        di = np.asarray(direct.ids)[:, : int(ks[i])]
        ds = np.asarray(direct.scores)[:, : int(ks[i])]
        if not ((ids == di).all() and (scores == ds).all()):
            return False
    return True


def trajectory(done_at: np.ndarray, lat: np.ndarray, buckets: int = 10):
    """Latency/throughput over the run in ``buckets`` time slices."""
    if len(done_at) == 0:
        return []
    edges = np.linspace(0, float(done_at.max()) + 1e-9, buckets + 1)
    out = []
    for b in range(buckets):
        m = (done_at >= edges[b]) & (done_at < edges[b + 1])
        if not m.any():
            continue
        span = edges[b + 1] - edges[b]
        out.append({
            "t_s": round(float(edges[b + 1]), 3),
            "completed": int(m.sum()),
            "qps": round(float(m.sum() / span), 1),
            "p50_ms": round(float(np.percentile(lat[m], 50)) * 1e3, 3),
        })
    return out


def run(n_requests: int, rate: float, n_store: int, dim: int, nnz: int,
        k: int, r_block: int, s_block: int, window_s: float, seed: int,
        serial_sample: int, algorithm: str = "iib"):
    import jax

    S = synthetic_sparse(n_store, dim=dim, nnz_mean=nnz, seed=seed)
    spec = JoinSpec(k=k, algorithm=algorithm, r_block=r_block, s_block=s_block)
    store = ShardedKNNStore.build(S, spec)

    pool, bounds, arrivals, ks = make_workload(
        n_requests, rate, max_rows=4, k=k, dim=dim, nnz=nnz, seed=seed)

    serial = serial_baseline(store, pool, bounds, ks, serial_sample)

    config = ServeConfig(r_block=r_block, window_s=window_s,
                         queue_rows_hwm=4 * max(n_requests * 4, r_block))

    # tracing is ON for the record (the scheduler's default) — the qps it
    # reports is WITH span + recorder overhead; compare.py gates it within
    # 5% of the pre-tracing baseline stream
    recorder = FlightRecorder()
    set_recorder(recorder)

    # compile warmup runs through the SAME scheduler (open_loop warm
    # rounds + metrics.reset_window), so the timed run measures serving,
    # not XLA compilation, and the record deltas out the warm traffic
    lat, done_at, wall, bounces, metrics, base = asyncio.run(
        open_loop(store, pool, bounds, arrivals, ks, config))
    summary = metrics.summary()
    dispatches = summary["dispatch"]["device_dispatches"] - base["device_dispatches"]

    qps = n_requests / wall
    record = {
        "algorithm": algorithm,
        "requests": n_requests,
        "completed": summary["requests"]["completed"] - base["completed"],
        "rejected_bounces": bounces,
        "failed": summary["requests"]["failed"] - base["failed"],
        "max_inflight": summary["requests"]["inflight_peak"],
        "arrival_rate_per_s": rate,
        "wall_s": round(wall, 4),
        "queries_per_s": round(qps, 2),
        "p50_ms": round(float(np.percentile(lat, 50)) * 1e3, 3),
        "p99_ms": round(float(np.percentile(lat, 99)) * 1e3, 3),
        "batches": summary["batches"]["count"] - base["batches"],
        "mean_occupancy": summary["batches"]["mean_occupancy"],
        "device_dispatches": dispatches,
        "dispatches_per_request": round(dispatches / max(n_requests, 1), 4),
        "query_index_builds": summary["dispatch"]["query_index_builds"],
        "phases": metrics.phase_summary(),
        "tracing": {"enabled": True, "flight_recorder": recorder.summary()},
        "serial": serial,
        "speedup_vs_serial": round(qps / serial["queries_per_s"], 2),
        "trajectory": trajectory(done_at, lat),
        "shards": store.n_shards,
        "device_count": jax.device_count(),
    }

    # bit-parity of de-interleaved results vs direct per-request queries:
    # re-serve a sample through a fresh scheduler and compare
    sample_n = min(16, n_requests)

    async def reserve():
        out = {}
        async with KNNScheduler(store, config) as sched:
            idxs = np.linspace(0, n_requests - 1, num=sample_n, dtype=int)
            outs = await asyncio.gather(*[
                sched.submit(slice_rows(pool, int(bounds[i]), int(bounds[i + 1])),
                             k=int(ks[i]))
                for i in idxs
            ])
            for i, o in zip(idxs, outs):
                out[int(i)] = o
        return out

    sampled = asyncio.run(reserve())
    record["parity_ok"] = parity_sample(
        store, pool, bounds, ks, lambda i: sampled[i], sample_n)
    return record


def run_faulted(n_requests: int, rate: float, n_store: int, dim: int,
                nnz: int, k: int, r_block: int, s_block: int, window_s: float,
                seed: int, fault_at: int, algorithm: str = "iib",
                flight_dump: str = None):
    """Open loop with an injected shard loss at dispatch ``fault_at``.

    The acceptance bar is ZERO LOST FUTURES: every submitted request
    resolves — degraded while the shard is down, full once the
    background recovery (rebuild from the checkpoint slice) lands — and
    a post-recovery sample is bit-identical to direct queries.

    The run shares one flight recorder across serve → store → fault
    plan: the injected fault auto-dumps the span/event ring to
    ``flight_dump`` (JSONL) the moment it fires, and the record carries
    the recorder summary (CI uploads the JSONL next to the bench JSON).
    """
    import jax

    from repro.runtime.fault import FaultPlan, FaultSpec

    recorder = FlightRecorder(auto_dump_path=flight_dump)
    set_recorder(recorder)

    S = synthetic_sparse(n_store, dim=dim, nnz_mean=nnz, seed=seed)
    spec = JoinSpec(k=k, algorithm=algorithm, r_block=r_block, s_block=s_block)
    store = ShardedKNNStore.build(S, spec)
    ckpt_dir = tempfile.mkdtemp(prefix="serve_fault_ckpt_")
    try:
        store.save(ckpt_dir)
        pool, bounds, arrivals, ks = make_workload(
            n_requests, rate, max_rows=4, k=k, dim=dim, nnz=nnz, seed=seed)
        config = ServeConfig(
            r_block=r_block, window_s=window_s,
            queue_rows_hwm=4 * max(n_requests * 4, r_block),
            allow_partial=True,
            recover=lambda: store.recover(ckpt_dir),
        )

        # the fault arms AFTER open_loop's warm rounds (the ``arm`` hook
        # fires post-reset_window), so the plan's dispatch counter starts
        # at the timed traffic
        def arm():
            store.fault_plan = FaultPlan(
                [FaultSpec("shard_error", shard=0, at_dispatch=fault_at)])

        lat, done_at, wall, bounces, metrics, base = asyncio.run(
            open_loop(store, pool, bounds, arrivals, ks, config, arm=arm))
        store.fault_plan = None
        summary = metrics.summary()
        faults = summary["faults"]

        # the scheduler's drain awaited the background recovery; the
        # store must be whole again and back at bit-parity
        sample_n = min(16, n_requests)

        async def reserve():
            out = {}
            async with KNNScheduler(store, config) as sched:
                idxs = np.linspace(0, n_requests - 1, num=sample_n, dtype=int)
                outs = await asyncio.gather(*[
                    sched.submit(
                        slice_rows(pool, int(bounds[i]), int(bounds[i + 1])),
                        k=int(ks[i]))
                    for i in idxs
                ])
                for i, o in zip(idxs, outs):
                    out[int(i)] = o
            return out

        sampled = asyncio.run(reserve())
        parity = parity_sample(
            store, pool, bounds, ks, lambda i: sampled[i], sample_n)

        if flight_dump:
            # the fault's auto-dump snapshotted the ring mid-incident;
            # re-dump now so the artifact also covers recovery + re-parity
            recorder.dump(flight_dump)

        record = {
            "algorithm": algorithm,
            "requests": n_requests,
            "completed": summary["requests"]["completed"] - base["completed"],
            "failed": summary["requests"]["failed"] - base["failed"],
            "rejected_bounces": bounces,
            "degraded": faults["degraded"],
            "shard_losses": faults["shard_losses"],
            "recoveries": faults["recoveries"],
            "recovery_s": faults["recovery_s"],
            "recovered_all": store.lost_shards == (),
            "parity_after_recovery": parity,
            "query_index_builds": summary["dispatch"]["query_index_builds"],
            "fault": {"kind": "shard_error", "shard": 0,
                      "at_dispatch": fault_at},
            "wall_s": round(wall, 4),
            "p50_ms": round(float(np.percentile(lat, 50)) * 1e3, 3),
            "p99_ms": round(float(np.percentile(lat, 99)) * 1e3, 3),
            "phases": metrics.phase_summary(),
            "flight_recorder": recorder.summary(),
            "flight_dump": flight_dump,
            "shards": store.n_shards,
            "device_count": jax.device_count(),
        }
    finally:
        shutil.rmtree(ckpt_dir, ignore_errors=True)
    return record


def run_replica_faulted(n_requests: int, rate: float, n_store: int, dim: int,
                        nnz: int, k: int, r_block: int, s_block: int,
                        window_s: float, seed: int, fault_at: int,
                        algorithm: str = "iib", flight_dump: str = None):
    """Open loop over a ``replicas=2`` store with a replica kill at
    dispatch ``fault_at``.

    The acceptance bar is FULL SERVICE THROUGH THE LOSS: every submitted
    request resolves complete — never degraded, never dropped — because
    the store fails the dispatch over to the surviving replica inside
    the batch (``allow_partial`` stays off; a degraded result would fail
    the gate).  The scheduler's background anti-entropy resync
    (``ServeConfig.resync``) repairs the dead replica from the host
    mirror behind the traffic; ``verify_replicas()`` then asserts
    bit-parity, and results must stay bit-identical to a single-device
    index over the same rows.
    """
    import jax

    from repro.core.engine import SparseKNNIndex
    from repro.store.mesh import make_store_mesh
    from repro.runtime.fault import FaultPlan, FaultSpec

    if jax.device_count() < 4:
        raise SystemExit(
            "replica fault bench needs >= 4 devices (2 replicas x 2 shards); "
            "set XLA_FLAGS=--xla_force_host_platform_device_count=4")

    recorder = FlightRecorder(auto_dump_path=flight_dump)
    set_recorder(recorder)

    S = synthetic_sparse(n_store, dim=dim, nnz_mean=nnz, seed=seed)
    spec = JoinSpec(k=k, algorithm=algorithm, r_block=r_block, s_block=s_block)
    store = ShardedKNNStore(S, spec, mesh=make_store_mesh(2, replicas=2))
    single = SparseKNNIndex.build(S, spec)

    pool, bounds, arrivals, ks = make_workload(
        n_requests, rate, max_rows=4, k=k, dim=dim, nnz=nnz, seed=seed)
    config = ServeConfig(
        r_block=r_block, window_s=window_s,
        queue_rows_hwm=4 * max(n_requests * 4, r_block),
        resync=lambda: store.resync_replicas(),
    )

    # two warm rounds compile the batch shape on BOTH replicas; the fault
    # arms only after them (replica kinds arm at at_dispatch and fire on
    # the first dispatch routed to the target replica)
    def arm():
        store.fault_plan = FaultPlan(
            [FaultSpec("replica_error", replica=1, at_dispatch=fault_at)])

    lat, done_at, wall, bounces, metrics, base = asyncio.run(
        open_loop(store, pool, bounds, arrivals, ks, config,
                  warm_rounds=2, arm=arm))
    store.fault_plan = None
    summary = metrics.summary()
    faults = summary["faults"]

    # the scheduler drain awaited the background resync; the dead replica
    # must be repaired (or at least repairable) and bit-parity must hold
    if store.needs_resync:
        store.resync_replicas()
    try:
        replica_parity = bool(store.verify_replicas())
    except ValueError:
        replica_parity = False

    # post-resync: a routed probe re-admits the half-open replica, and
    # results must bit-match the single-device build over the same rows
    sample_n = min(16, n_requests)
    idxs = np.linspace(0, n_requests - 1, num=sample_n, dtype=int)
    single_parity = True
    for i in idxs:
        rows = slice_rows(pool, int(bounds[i]), int(bounds[i + 1]))
        got = store.query(rows)
        want = single.query(rows)
        if not (np.asarray(got.ids) == np.asarray(want.ids)).all():
            single_parity = False
            break
        if not (np.asarray(got.scores) == np.asarray(want.scores)).all():
            single_parity = False
            break

    if flight_dump:
        # cover the resync + parity probes too, not just the kill moment
        recorder.dump(flight_dump)

    record = {
        "algorithm": algorithm,
        "requests": n_requests,
        "completed": summary["requests"]["completed"] - base["completed"],
        "failed": summary["requests"]["failed"] - base["failed"],
        "rejected_bounces": bounces,
        "degraded": faults["degraded"],
        "replica_failovers": faults["replica_failovers"],
        "resyncs": faults["resyncs"],
        "resync_s": faults["resync_s"],
        "replica_dispatches": faults["replica_dispatches"],
        "replica_losses": store.stats.replica_losses,
        "dead_replicas_after": list(store.dead_replicas),
        "replica_parity_ok": replica_parity,
        "parity_vs_single_device": single_parity,
        "query_index_builds": summary["dispatch"]["query_index_builds"],
        "fault": {"kind": "replica_error", "replica": 1,
                  "at_dispatch": fault_at},
        "wall_s": round(wall, 4),
        "p50_ms": round(float(np.percentile(lat, 50)) * 1e3, 3),
        "p99_ms": round(float(np.percentile(lat, 99)) * 1e3, 3),
        "phases": metrics.phase_summary(),
        "flight_recorder": recorder.summary(),
        "flight_dump": flight_dump,
        "replicas": store.n_replicas,
        "shards": store.n_shards,
        "device_count": jax.device_count(),
    }
    return record


def replica_faulted_checks(record: dict) -> dict:
    return {
        # full service through the replica loss: every future resolved
        # complete, none degraded, none dropped
        "zero_lost_futures_ok": (
            record["completed"] == record["requests"]
            and record["failed"] == 0),
        "zero_degraded_ok": record["degraded"] == 0,
        "failover_fired_ok": record["replica_failovers"] >= 1,
        "replica_killed_ok": record["replica_losses"] >= 1,
        "resynced_ok": (record["resyncs"] >= 1
                        and not record["dead_replicas_after"]),
        "replica_parity_ok": bool(record["replica_parity_ok"]),
        "single_device_parity_ok": bool(record["parity_vs_single_device"]),
        "zero_query_builds_ok": record["query_index_builds"] == 0,
    }


def faulted_checks(record: dict) -> dict:
    return {
        # zero lost futures: every submitted request resolved, none errored
        "zero_lost_futures_ok": (
            record["completed"] == record["requests"]
            and record["failed"] == 0),
        "fault_fired_ok": record["shard_losses"] >= 1,
        "served_degraded_ok": record["degraded"] > 0,
        "recovered_ok": (record["recoveries"] >= 1
                         and record["recovered_all"]),
        "parity_after_recovery_ok": bool(record["parity_after_recovery"]),
        "zero_query_builds_ok": record["query_index_builds"] == 0,
    }


def smoke() -> int:
    """CI gate (``make serve-smoke``): tiny load under forced virtual
    devices.  Every submitted request must complete, results must be
    bit-identical to direct queries, batching must actually coalesce
    (> 1 request per dispatch), and the store must do ZERO query-time
    index builds."""
    record = run(n_requests=64, rate=4000.0, n_store=192, dim=512, nnz=16,
                 k=5, r_block=32, s_block=48, window_s=0.005, seed=0,
                 serial_sample=16)
    checks = {
        "all_completed_ok": record["completed"] == record["requests"],
        "none_failed_ok": record["failed"] == 0,
        "zero_query_builds_ok": record["query_index_builds"] == 0,
        "coalesced_ok": record["requests"] > record["batches"],
        "parity_ok": record["parity_ok"],
    }
    print(json.dumps({"serving": record, **checks}))
    return 0 if all(checks.values()) else 1


def main(argv=None):
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI load: completed == submitted, zero "
                         "query-time builds, bit-parity (exit 1 on failure)")
    ap.add_argument("--fast", action="store_true", help="CI-sized record run")
    ap.add_argument("--fault-plan", action="store_true",
                    help="record the 'serving_faulted' stream: inject a "
                         "shard loss mid-traffic; every future must "
                         "complete (degraded or recovered, never dropped)")
    ap.add_argument("--replica-fault", action="store_true",
                    help="record the 'replica_faulted' stream: kill a "
                         "replica of a replicas=2 store mid-traffic; every "
                         "future must complete FULL (failover, not "
                         "degradation) and the resynced replica must "
                         "bit-match (needs >= 4 devices)")
    ap.add_argument("--fault-at", type=int, default=2,
                    help="store dispatch index the shard loss fires at")
    ap.add_argument("--flight-dump", default=None, metavar="PATH",
                    help="fault runs: dump the flight recorder (spans + "
                         "fault events) to this JSONL path — auto-dumped "
                         "the moment the fault fires, re-dumped at exit")
    ap.add_argument("--requests", type=int, default=None)
    ap.add_argument("--rate", type=float, default=None,
                    help="Poisson arrival rate (requests/s)")
    ap.add_argument("--merge", default=None, metavar="BENCH.json",
                    help="add the 'serving' stream to an existing perf record")
    ap.add_argument("--out", default=None, metavar="PATH",
                    help="write a standalone record")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if args.smoke:
        return smoke()

    if args.replica_fault:
        record = run_replica_faulted(
            n_requests=args.requests or 256, rate=(args.requests or 256) / 0.2,
            n_store=512, dim=2048, nnz=32, k=5, r_block=64, s_block=128,
            window_s=0.002, seed=args.seed, fault_at=args.fault_at,
            flight_dump=args.flight_dump)
        checks = replica_faulted_checks(record)
        print(json.dumps({"replica_faulted": record, **checks}, indent=1))
        if args.merge:
            with open(args.merge) as f:
                doc = json.load(f)
            doc.setdefault("streams", {})["replica_faulted"] = record
            with open(args.merge, "w") as f:
                json.dump(doc, f, indent=1)
                f.write("\n")
            print(f"merged replica_faulted stream into {args.merge}")
        elif args.out:
            with open(args.out, "w") as f:
                json.dump({"streams": {"replica_faulted": record}}, f, indent=1)
                f.write("\n")
            print(f"wrote {args.out}")
        return 0 if all(checks.values()) else 1

    if args.fault_plan:
        record = run_faulted(
            n_requests=args.requests or 256, rate=(args.requests or 256) / 0.2,
            n_store=512, dim=2048, nnz=32, k=5, r_block=64, s_block=128,
            window_s=0.002, seed=args.seed, fault_at=args.fault_at,
            flight_dump=args.flight_dump)
        checks = faulted_checks(record)
        print(json.dumps({"serving_faulted": record, **checks}, indent=1))
        if args.merge:
            with open(args.merge) as f:
                doc = json.load(f)
            doc.setdefault("streams", {})["serving_faulted"] = record
            with open(args.merge, "w") as f:
                json.dump(doc, f, indent=1)
                f.write("\n")
            print(f"merged serving_faulted stream into {args.merge}")
        elif args.out:
            with open(args.out, "w") as f:
                json.dump({"streams": {"serving_faulted": record}}, f, indent=1)
                f.write("\n")
            print(f"wrote {args.out}")
        return 0 if all(checks.values()) else 1

    n_requests = args.requests or (2000 if args.fast else 4000)
    # arrivals must outpace service so in-flight climbs past 1k (open loop)
    rate = args.rate or (n_requests / 0.35)
    size = dict(n_store=512, dim=4096, nnz=32, k=5, r_block=64, s_block=128) \
        if args.fast else dict(n_store=2048, dim=8192, nnz=64, k=5,
                               r_block=128, s_block=256)
    record = run(n_requests=n_requests, rate=rate, window_s=0.002,
                 seed=args.seed, serial_sample=200, **size)
    print(json.dumps({k: v for k, v in record.items() if k != "trajectory"},
                     indent=1))
    ok = (record["completed"] == record["requests"]
          and record["parity_ok"]
          and record["query_index_builds"] == 0)
    if args.merge:
        with open(args.merge) as f:
            doc = json.load(f)
        doc.setdefault("streams", {})["serving"] = record
        with open(args.merge, "w") as f:
            json.dump(doc, f, indent=1)
            f.write("\n")
        print(f"merged serving stream into {args.merge}")
    elif args.out:
        with open(args.out, "w") as f:
            json.dump({"streams": {"serving": record}}, f, indent=1)
            f.write("\n")
        print(f"wrote {args.out}")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
