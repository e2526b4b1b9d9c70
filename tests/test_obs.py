"""Unified observability layer (repro.obs): metric registry + exposition
round-trip, span tracing threaded serve → store → engine, flight-recorder
dump-on-fault, summary-schema compatibility, and tracing bit-parity."""
import asyncio
import json
import threading

import numpy as np
import pytest

from repro.core import JoinSpec
from repro.core.engine import MIN_PRUNE_TRACE_CAP, JoinStats
from repro.obs import (
    FlightRecorder,
    MetricRegistry,
    Tracer,
    get_recorder,
    parse_exposition,
    set_recorder,
    set_tracing,
)
from repro.obs.registry import Counter, Gauge, Histogram
from repro.runtime.fault import FaultPlan, FaultSpec, ShardLostError
from repro.serve import KNNScheduler, ServeConfig, ServeMetrics
from repro.sparse.datagen import synthetic_sparse
from repro.store import ShardedKNNStore


@pytest.fixture(autouse=True)
def _fresh_recorder():
    """Each test gets its own process-global flight recorder."""
    old = get_recorder()
    rec = FlightRecorder()
    set_recorder(rec)
    yield rec
    set_recorder(old)


# ---------------------------------------------------------------------------
# metric registry + exposition
# ---------------------------------------------------------------------------

def test_registry_exposition_round_trip():
    reg = MetricRegistry()
    c = reg.counter("knn_queries", "queries served")
    g = reg.gauge("knn_inflight", "in flight")
    h = reg.histogram("knn_latency_seconds", "latency", buckets=(0.1, 1.0))
    c.inc(3)
    g.set(2)
    for v in (0.05, 0.5, 5.0):
        h.observe(v)

    text = reg.expose()
    assert text.rstrip().endswith("# EOF")
    parsed = parse_exposition(text)
    assert parsed["knn_queries"] == {"type": "counter", "value": 3}
    assert parsed["knn_inflight"] == {"type": "gauge", "value": 2}
    hist = parsed["knn_latency_seconds"]
    assert hist["type"] == "histogram"
    assert hist["buckets"] == {0.1: 1, 1.0: 2, float("inf"): 3}
    assert hist["count"] == 3
    assert hist["sum"] == pytest.approx(5.55)

    with pytest.raises(ValueError):
        parse_exposition(text.replace("# EOF", ""))  # truncated exposition


def test_registry_idempotent_and_kind_clash():
    reg = MetricRegistry()
    a = reg.counter("x_total_things", "help")
    assert reg.counter("x_total_things", "help") is a
    with pytest.raises(ValueError):
        reg.gauge("x_total_things", "same name, different kind")


def test_histogram_skips_non_finite():
    h = Histogram("h", "help", buckets=(1.0,))
    h.observe(float("-inf"))           # IIIB's -inf threshold seed
    h.observe(float("nan"))
    h.observe(0.5)
    assert h.count == 1
    assert h.sum == pytest.approx(0.5)


def test_instrument_types():
    c = Counter("c", "help")
    c.inc()
    c.set(c.value + 1)                 # what `m.attr += 1` lowers to
    assert c.value == 2
    g = Gauge("g", "help")
    g.set(5)
    g.dec(2)
    assert g.value == 3


# ---------------------------------------------------------------------------
# ServeMetrics: registry backing, frozen summary schema, reset_window
# ---------------------------------------------------------------------------

SUMMARY_SCHEMA = {
    "requests": ["submitted", "completed", "rejected", "failed",
                 "deadline_misses", "inflight_peak"],
    "latency": ["p50_ms", "p99_ms", "mean_ms"],
    "throughput": ["queries_per_s", "rows_per_s", "elapsed_s"],
    "batches": ["count", "mean_occupancy", "mean_wall_ms", "retries",
                "timeouts"],
    "queue": ["depth", "depth_peak"],
    "faults": ["timeouts", "retries", "rejected", "failed", "degraded",
               "shard_losses", "recoveries", "recovery_s",
               "replica_failovers", "resyncs", "resync_s",
               "replica_dispatches"],
    "dispatch": ["device_dispatches", "host_syncs", "query_index_builds"],
}


def test_summary_schema_frozen():
    """The pre-registry JSON shape is pinned: same sections, same keys,
    same zero-state values (floats stay floats)."""
    m = ServeMetrics(r_block=8)
    s = m.summary()
    assert list(s) == list(SUMMARY_SCHEMA)
    for section, keys in SUMMARY_SCHEMA.items():
        assert list(s[section]) == keys, section
    # zero-state spot checks — ints stay ints, floats stay floats
    assert s["requests"]["submitted"] == 0
    assert s["faults"]["recovery_s"] == 0.0
    assert isinstance(s["faults"]["recovery_s"], float)
    assert isinstance(s["faults"]["resync_s"], float)
    assert s["latency"]["p50_ms"] is None
    json.dumps(s)  # JSON-able end to end


def test_metrics_attributes_are_registry_cells():
    m = ServeMetrics(r_block=4)
    m.on_submit(2)
    m.on_batch(2, wall_s=0.01)
    m.on_complete(0.02)
    m.retries += 1
    parsed = parse_exposition(m.expose())
    assert parsed["serve_requests_submitted"]["value"] == m.submitted == 1
    assert parsed["serve_batch_retries"]["value"] == m.retries == 1
    assert parsed["serve_batches"]["value"] == 1
    assert parsed["serve_batches_held"]["value"] == m.held_batches == 0
    assert parsed["serve_inflight"]["value"] == 0     # completed drained it
    assert parsed["serve_inflight_peak"]["value"] == 1
    assert parsed["serve_latency_seconds"]["count"] == 1


def test_reset_window_rebases_window_not_lifetime():
    m = ServeMetrics(r_block=4)
    for _ in range(5):
        m.on_submit(1)
        m.on_complete(1.0)             # 1s latencies before the reset
    m.on_phases([0.5], 0.5, 0.5, 0.5)
    assert m.summary()["latency"]["p50_ms"] == pytest.approx(1000.0)

    m.reset_window()
    assert m.completed == 5            # lifetime counter untouched
    s = m.summary()
    assert s["requests"]["completed"] == 5
    assert s["latency"]["p50_ms"] is None          # window dropped
    assert s["throughput"]["queries_per_s"] == 0.0  # rebased on _completed0
    for ph in m.phase_summary().values():
        assert ph["p50_ms"] is None
    m.on_submit(1)
    m.on_complete(0.002)
    assert m.summary()["latency"]["p50_ms"] == pytest.approx(2.0)


def test_phase_summary_counts():
    m = ServeMetrics(r_block=4)
    m.on_phases([0.001, 0.002], 0.0005, 0.01, 0.0002)
    ph = m.phase_summary()
    assert ph["queue_wait"]["count"] == 2          # per-request
    for name in ("pad", "dispatch", "post"):
        assert ph[name]["count"] == 1              # per-batch
    assert ph["dispatch"]["p50_ms"] == pytest.approx(10.0)


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------

def test_recorder_ring_bounded_and_dump(tmp_path):
    rec = FlightRecorder(capacity=4)
    for i in range(10):
        rec.record("tick", i=i)
    s = rec.summary()
    assert s["events"] == 4 and s["recorded"] == 10 and s["evicted"] == 6
    path = rec.dump(tmp_path / "flight.jsonl")
    lines = [json.loads(ln) for ln in open(path)]
    assert [e["i"] for e in lines] == [6, 7, 8, 9]  # oldest-first, bounded


def test_recorder_auto_dump_on_fault(tmp_path):
    path = tmp_path / "fault.jsonl"
    rec = FlightRecorder(auto_dump_path=path)
    rec.record("span", name="warm")
    rec.fault("shard_lost", shard=2)
    assert path.exists()
    events = [json.loads(ln) for ln in open(path)]
    assert events[-1]["kind"] == "shard_lost" and events[-1]["fault"]
    assert rec.summary()["faults"] == 1
    assert rec.summary()["auto_dumps"] == 1


def test_fault_plan_records_injection(tmp_path, _fresh_recorder):
    """An injected shard kill lands in the flight recorder (kind
    ``fault_injected`` from the plan itself + the store's ``shard_lost``)
    and auto-dumps the ring the moment it fires."""
    dump = tmp_path / "flight.jsonl"
    rec = FlightRecorder(auto_dump_path=dump)
    set_recorder(rec)

    S = synthetic_sparse(48, dim=64, nnz_mean=8, seed=0)
    store = ShardedKNNStore.build(
        S, JoinSpec(k=3, algorithm="iib", r_block=4, s_block=16))
    R = synthetic_sparse(4, dim=64, nnz_mean=8, seed=1)
    store.query(R)                      # warm: spans land in the ring
    store.fault_plan = FaultPlan(
        [FaultSpec("shard_error", shard=0, at_dispatch=0)])
    with pytest.raises(ShardLostError):
        store.query(R)

    assert dump.exists()
    kinds = {e["kind"] for e in map(json.loads, open(dump))}
    assert "fault_injected" in kinds
    assert "shard_lost" in kinds
    assert "span" in kinds              # the warm query's span timeline
    assert rec.summary()["faults"] >= 2


# ---------------------------------------------------------------------------
# span tracing: serve -> store -> engine parenting, on/off parity
# ---------------------------------------------------------------------------

def _span_events(rec):
    return [e for e in rec.events() if e.get("kind") == "span"]


def test_span_parenting_across_threads(_fresh_recorder):
    """request → batch → store.dispatch → store.r_block must form one
    parented tree even though dispatch hops event loop → executor →
    watchdog thread."""
    S = synthetic_sparse(48, dim=64, nnz_mean=8, seed=0)
    store = ShardedKNNStore.build(
        S, JoinSpec(k=3, algorithm="iib", r_block=4, s_block=16))
    R = synthetic_sparse(2, dim=64, nnz_mean=8, seed=1)

    async def main():
        async with KNNScheduler(
            store, ServeConfig(r_block=4, window_s=0.005)
        ) as sched:
            await sched.submit(R)

    asyncio.run(main())
    spans = _span_events(_fresh_recorder)
    by_id = {e["span_id"]: e for e in spans}
    by_name = {}
    for e in spans:
        by_name.setdefault(e["name"], []).append(e)
    assert {"request", "batch", "store.dispatch", "store.r_block"} <= set(by_name)

    req = by_name["request"][0]
    assert req["parent_id"] is None
    batch = by_name["batch"][0]
    assert batch["parent_id"] == req["span_id"]
    dispatch = by_name["store.dispatch"][0]
    assert by_id[dispatch["parent_id"]]["name"] == "batch"
    for rb in by_name["store.r_block"]:
        assert by_id[rb["parent_id"]]["name"] == "store.dispatch"
    for e in spans:
        assert e["t_end"] >= e["t_start"]
        assert e["dur_ms"] >= 0.0


def test_mutate_and_ckpt_spans(tmp_path, _fresh_recorder):
    S = synthetic_sparse(32, dim=64, nnz_mean=8, seed=0)
    store = ShardedKNNStore.build(
        S, JoinSpec(k=3, algorithm="iib", r_block=4, s_block=16))
    store.save(tmp_path / "ckpt")
    ShardedKNNStore.load(tmp_path / "ckpt")
    names = {e["name"] for e in _span_events(_fresh_recorder)}
    assert "ckpt.save" in names
    assert "ckpt.load" in names


def test_tracing_off_bit_parity(_fresh_recorder):
    """set_tracing(False) must not change a single output bit — and must
    record nothing."""
    S = synthetic_sparse(64, dim=64, nnz_mean=8, seed=3)
    R = synthetic_sparse(8, dim=64, nnz_mean=8, seed=4)
    store = ShardedKNNStore.build(
        S, JoinSpec(k=4, algorithm="iiib", r_block=8, s_block=32))
    on = store.query(R)
    set_tracing(False)
    try:
        before = _fresh_recorder.summary()["recorded"]
        off = store.query(R)
        assert _fresh_recorder.summary()["recorded"] == before
    finally:
        set_tracing(True)
    np.testing.assert_array_equal(np.asarray(on.ids), np.asarray(off.ids))
    np.testing.assert_array_equal(
        np.asarray(on.scores), np.asarray(off.scores))


def test_tracer_cross_thread_attach():
    rec = FlightRecorder()
    tr = Tracer(recorder=rec)
    with tr.span("parent") as parent:
        def worker():
            with tr.attach(parent):
                with tr.span("child"):
                    pass
        t = threading.Thread(target=worker)
        t.start()
        t.join()
    spans = {e["name"]: e for e in rec.events()}
    assert spans["child"]["parent_id"] == spans["parent"]["span_id"]


# ---------------------------------------------------------------------------
# engine: bounded min-prune trace + threshold histogram
# ---------------------------------------------------------------------------

def test_min_prune_trace_bounded():
    stats = JoinStats()
    assert stats.min_prune_trace.maxlen == MIN_PRUNE_TRACE_CAP
    for i in range(MIN_PRUNE_TRACE_CAP + 10):
        stats.min_prune_trace.append(np.full(4, float(i)))
    assert len(stats.min_prune_trace) == MIN_PRUNE_TRACE_CAP
    assert stats.min_prune_trace[0][0] == 10.0   # oldest evicted


def test_iiib_query_populates_prune_trace():
    S = synthetic_sparse(64, dim=64, nnz_mean=8, seed=5)
    R = synthetic_sparse(8, dim=64, nnz_mean=8, seed=6)
    store = ShardedKNNStore.build(
        S, JoinSpec(k=4, algorithm="iiib", r_block=8, s_block=32))
    res = store.query(R)
    assert len(res.stats.min_prune_trace) >= 1
    from repro.obs.registry import get_registry
    hist = get_registry().get("knn_min_prune_threshold")
    assert hist is not None and hist.count >= 1


# ---------------------------------------------------------------------------
# the profiler's timeline: span annotations and device name scopes
# ---------------------------------------------------------------------------

STORE_PHASES = ["knn.store.prep", "knn.store.launch", "knn.store.wait",
                "knn.store.pull"]


def _profiled(tmp_path, fn):
    """Run ``fn`` under a ``jax.profiler`` trace; returns its result and
    the trace's ``knn.*`` host events as (start_ns, end_ns, name)."""
    import glob

    import jax
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        out = fn()
        jax.block_until_ready(out)
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0]
    events = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                events.extend(
                    (e.start_ns, e.start_ns + e.duration_ns, e.name)
                    for e in line.events if e.name.startswith("knn."))
    return out, sorted(events)


def _iiib_store():
    S = synthetic_sparse(64, dim=64, nnz_mean=8, seed=3)
    R = synthetic_sparse(16, dim=64, nnz_mean=8, seed=4)
    store = ShardedKNNStore.build(
        S, JoinSpec(k=4, algorithm="iiib", r_block=8, s_block=32))
    store.query(R)                      # compile outside the traces
    return store, R


def test_store_phases_on_profiler_timeline(tmp_path):
    """Each R block is a knn.store.r_block annotation holding its four
    phases, nested and in order, on the profiler's timeline."""
    store, R = _iiib_store()
    _, events = _profiled(tmp_path, lambda: store.query(R).ids)
    blocks = [e for e in events if e[2] == "knn.store.r_block"]
    assert len(blocks) == 2             # 16 rows in R blocks of 8
    for lo, hi, _ in blocks:
        inside = [e for e in events if lo <= e[0] and e[1] <= hi
                  and e[2] != "knn.store.r_block"]
        assert [e[2] for e in inside] == STORE_PHASES
        assert all(a[1] <= b[0] for a, b in zip(inside, inside[1:]))


def test_tracing_off_leaves_profiler_timeline_empty(tmp_path):
    """set_tracing(False) creates no annotation, and the answers do not
    change by a bit with the annotations on."""
    store, R = _iiib_store()
    on, on_events = _profiled(tmp_path / "on", lambda: store.query(R))
    set_tracing(False)
    try:
        off, off_events = _profiled(tmp_path / "off", lambda: store.query(R))
    finally:
        set_tracing(True)
    assert {e[2] for e in on_events} >= {"knn.store.r_block", *STORE_PHASES}
    assert off_events == []
    np.testing.assert_array_equal(np.asarray(on.ids), np.asarray(off.ids))
    np.testing.assert_array_equal(
        np.asarray(on.scores), np.asarray(off.scores))


def test_start_end_span_annotate_with_the_recorder_unchanged(tmp_path):
    """The non-pushing pair annotates too; the recorder's span dict keeps
    its fields."""
    rec = FlightRecorder()
    tr = Tracer(recorder=rec)

    def work():
        s = tr.begin("ckpt.load", step=3)
        tr.end(s, n_shards=1)
        return np.zeros(1)

    _, events = _profiled(tmp_path, work)
    assert [e[2] for e in events] == ["knn.ckpt.load"]
    (ev,) = rec.events()
    assert set(ev) == {"t_wall", "kind", "name", "span_id", "parent_id",
                       "t_start", "t_end", "dur_ms", "attrs"}
    assert ev["attrs"] == {"step": 3, "n_shards": 1}


@pytest.mark.parametrize("algorithm,scopes", [
    ("bf", {"knn.matmul", "knn.topk"}),
    ("iib", {"knn.matmul", "knn.scatter", "knn.topk"}),
    ("iiib", {"knn.matmul", "knn.scatter", "knn.bound", "knn.topk"}),
])
def test_fanout_ops_carry_scan_scopes(algorithm, scopes):
    """The compiled fan-out program's ops name their scan phase in their
    op_name metadata, which the profiler's device trace carries."""
    import re

    S = synthetic_sparse(64, dim=64, nnz_mean=8, seed=3)
    R = synthetic_sparse(8, dim=64, nnz_mean=8, seed=4)
    store = ShardedKNNStore.build(
        S, JoinSpec(k=4, algorithm=algorithm, r_block=8, s_block=32))
    br, r_valid, prep, rk, rr = store._prep_block(R, 0, 8, False)
    args = store._fanout_args(br, prep, r_valid, store._stacks[0], False)
    text = store._query_fn(8).lower(*args).compile().as_text()
    found = set(re.findall(r'op_name="[^"]*?(knn\.[a-z]+)/', text))
    assert found == scopes
