"""The served cells' arrival schedule and their per-layer readers, on the
CPU without a run."""
import numpy as np
import pytest

import layout

SERVE = layout.module("loops", "open_serve")
MIX = {"loop": "open_serve", "rate_req_per_s": 10.0, "structure_seed": 7,
       "rows_min": 1, "rows_max": 4, "ks": [3, 4, 5], "check_rows": 512}


def _same(a, b):
    due_a, rows_a, ks_a = a
    due_b, rows_b, ks_b = b
    return (np.array_equal(due_a, due_b) and np.array_equal(ks_a, ks_b)
            and len(rows_a) == len(rows_b)
            and all(np.array_equal(x, y) for x, y in zip(rows_a, rows_b)))


def test_the_same_structure_seed_gives_the_same_schedule():
    a = SERVE.schedule(MIX, 30, 50_000)
    assert _same(a, SERVE.schedule(dict(MIX), 30, 50_000))
    assert not _same(a, SERVE.schedule(dict(MIX, structure_seed=8), 30, 50_000))


def test_a_shorter_window_sends_the_first_requests_of_a_longer_one():
    long, short = SERVE.schedule(MIX, 30, 50_000), SERVE.schedule(MIX, 10, 50_000)
    m = len(short[0])
    assert _same(short, (long[0][:m], long[1][:m], long[2][:m]))
    assert long[0][m] >= 10 > short[0][-1]


def test_the_schedule_follows_its_parameters():
    due, rows, ks = SERVE.schedule(MIX, 2000, 1000)
    assert 0 < due[0] and (np.diff(due) > 0).all() and due[-1] < 2000
    assert len(due) == pytest.approx(10.0 * 2000, rel=0.03)
    sizes = np.array([len(r) for r in rows])
    assert set(sizes) == {1, 2, 3, 4} and set(ks) == {3, 4, 5}
    flat = np.concatenate(rows)
    np.testing.assert_array_equal(flat, np.arange(len(flat)) % 1000)


def test_a_request_carries_its_rows_at_their_own_width():
    idx = np.array([[1, 5, 9, 10], [2, 10, 10, 10], [3, 4, 10, 10]], np.int32)
    val = np.where(idx < 10, 0.5, 0.0).astype(np.float32)
    nnz = np.array([3, 1, 2], np.int32)
    b = SERVE._request((idx, val, nnz), np.array([1, 2]), 10)
    assert b.indices.shape == (2, 2)
    np.testing.assert_array_equal(b.indices, [[2, 10], [3, 4]])
    assert [SERVE._bucket(w, 8) for w in (1, 8, 9, 250)] == [8, 8, 16, 256]


COUNTERS = {"batches": 40, "requests": 50, "live_rows": 120, "padded_rows": 40 * 256}
TRACE = {"busy_s": 3.2, "window_s": 10.0,
         "scopes": {"knn.scatter": 1.92, "knn.matmul": 0.7, "other": 0.58},
         "host_spans": {"knn.batch": 12.0, "knn.store.dispatch": 3.6,
                        "knn.request": 20.0}}
WANT = {"pad_share": 100.0 * (1 - 120 / (40 * 256)),
        "requests_per_batch": 50 / 40,
        "dispatch_wait_ms": (12.0 - 3.6) / 40 * 1e3,
        "device_ms_per_batch": 3.2 / 40 * 1e3,
        "scatter_ms_per_batch": 1.92 / 40 * 1e3}


@pytest.mark.parametrize("suffix", ["serve", "overload"])
@pytest.mark.parametrize("name", sorted(WANT))
def test_each_served_reader_on_a_record(name, suffix):
    read = layout.reader(f"{name}.{suffix}")
    assert read({"trace": TRACE, "loop": COUNTERS}) == pytest.approx(WANT[name], rel=1e-12)
    assert read({"trace": TRACE, "loop": None}) is None
    assert read({"trace": TRACE, "loop": dict(COUNTERS, batches=0, padded_rows=0)}) is None


def test_readers_of_the_programs_names_read_nothing_without_them():
    bare = {"busy_s": 3.2, "window_s": 10.0, "scopes": {"other": 3.2}, "host_spans": {}}
    run = {"trace": bare, "loop": COUNTERS}
    assert layout.reader("dispatch_wait_ms.serve")(run) is None
    assert layout.reader("scatter_ms_per_batch.serve")(run) is None
    assert layout.reader("device_ms_per_batch.serve")(run) == pytest.approx(80.0)


def test_the_tail_reader_reads_the_runs_own_p95():
    read = layout.reader("p95_ms.serve")
    assert read({"trace": TRACE, "loop": COUNTERS, "values": {"p95_ms": 612.5}}) == 612.5
    assert read({"trace": TRACE, "loop": COUNTERS, "values": {"p50_ms": 200.0}}) is None
    assert read({"trace": TRACE, "loop": None}) is None
