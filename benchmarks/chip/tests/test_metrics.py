"""The per-layer readers on the recorded chip traces, through the
reduction ``run.py`` makes of a traced window."""
import json
import pathlib

import pytest

import layout
import roofline
import run
import timeline
import tracereduce

DATA = pathlib.Path(__file__).resolve().parent / "data"
PLAIN = DATA / "trace_synth50k_join.json"
SCOPED = DATA / "trace_synth50k_join_scoped.json"
SERVED = DATA / "trace_synth50k_serve.json"
ROWS = 3 * 4096
NAMED = {
    "matmul_ms_per_block.join": ("scopes", ["knn.matmul"]),
    "topk_ms_per_block.join": ("scopes", ["knn.topk"]),
    "scatter_ms_per_block.join": ("scopes", ["knn.scatter"]),
    "store_host_ms_per_block.join": (
        "host_spans", ["knn.store.prep", "knn.store.launch", "knn.store.pull"]),
}


# the scheduler's window counters a served run hands its readers
BATCHES = 4
COUNTERS = {"batches": BATCHES, "requests": 5, "live_rows": 11,
            "padded_rows": BATCHES * 256}


# the end-to-end values a served run measured, handed to its readers
VALUES = {"p50_ms": 240.0, "p95_ms": 610.0, "setup_s": 35.0}


def _run(trace, loop=None, values=None):
    return {"trace": trace, "rows": ROWS, "loop": loop, "values": values,
            "scan": {"ops": 3e12, "bytes": 2e9, "peak": roofline.peaks("TPU v5 lite")}}


def _read_as_run_does(monkeypatch, path):
    ex = json.loads(path.read_text())
    monkeypatch.setattr(timeline, "extract", lambda log_dir: ex)
    return ex, run.read_trace("unused")


def _plain(ex):
    return {"devices": {p: [e[:3] for e in evs] for p, evs in ex["devices"].items()},
            "host": ex["host"]}


@pytest.mark.parametrize("name", [
    "device_ms_per_block.join", "scan_roofline_pct.join", "device_idle_pct.join"])
def test_earlier_readers_read_the_same_from_the_named_reduction(monkeypatch, name):
    ex, named = _read_as_run_does(monkeypatch, SCOPED)
    read = layout.reader(name)
    assert read(_run(named)) == read(_run(tracereduce.reduce(_plain(ex))))


@pytest.mark.parametrize("name", sorted(NAMED))
def test_named_readers_give_the_traces_own_sums_per_block(monkeypatch, name):
    """Summed here from the recorded events: each scoped op of this cut is
    a leaf (only the two loops hold others), and spans are clipped to the
    window."""
    ex, named = _read_as_run_does(monkeypatch, SCOPED)
    lo, hi = tracereduce._window(ex["host"])
    kind, names = NAMED[name]
    if kind == "scopes":
        [events] = ex["devices"].values()
        holders = {e[2] for e in events if any(
            e[0] <= f[0] and f[0] + f[1] <= e[0] + e[1] and f is not e for f in events)}
        assert holders == {"%while.46", "%while.47"}
        ns = sum(e[1] for e in events if e[3] in names and e[0] < hi and e[0] + e[1] > lo)
    else:
        ns = sum(min(s + d, hi) - max(s, lo) for s, d, n in ex["host"] if n in names)
    want = ns / 1e9 / (ROWS / 4096) * 1e3
    assert want > 0
    assert layout.reader(name)(_run(named)) == pytest.approx(want, rel=1e-12)


def test_a_name_the_trace_lacks_reads_zero_and_a_trace_without_names_none(monkeypatch):
    ex, named = _read_as_run_does(monkeypatch, SCOPED)
    no_scatter = dict(named, scopes={k: v for k, v in named["scopes"].items()
                                     if k != "knn.scatter"})
    assert layout.reader("scatter_ms_per_block.join")(_run(no_scatter)) == 0.0
    spans_only = dict(named, scopes={"other": named["leaf_s"]})
    assert layout.reader("matmul_ms_per_block.join")(_run(spans_only)) == 0.0
    assert layout.reader("store_host_ms_per_block.join")(_run(spans_only)) > 0
    scopes_only = dict(named, host_spans={})
    assert layout.reader("store_host_ms_per_block.join")(_run(scopes_only)) == 0.0
    _, unnamed = _read_as_run_does(monkeypatch, PLAIN)
    for name in NAMED:
        assert layout.reader(name)(_run(unnamed)) is None


def _clipped_ns(host, names, lo, hi):
    return sum(min(s + d, hi) - max(s, lo) for s, d, n in host
               if n in names and s < hi and s + d > lo)


@pytest.mark.parametrize("suffix", ["serve", "overload"])
def test_served_readers_give_the_traces_own_sums_per_batch(monkeypatch, suffix):
    """On a recorded window of ``synth50k.serve`` (v5e): each batch's
    ``knn.batch`` span holds its ``knn.store.dispatch`` span, and the
    S densify is the ``knn.scatter`` scope's leaf ops."""
    ex, named = _read_as_run_does(monkeypatch, SERVED)
    lo, hi = tracereduce._window(ex["host"])
    [events] = ex["devices"].values()
    scatter_ns = sum(e[1] for e in events
                     if e[3] == "knn.scatter" and e[0] < hi and e[0] + e[1] > lo)
    wait_ns = (_clipped_ns(ex["host"], {"knn.batch"}, lo, hi)
               - _clipped_ns(ex["host"], {"knn.store.dispatch"}, lo, hi))
    want = {"dispatch_wait_ms": wait_ns / 1e6 / BATCHES,
            "scatter_ms_per_batch": scatter_ns / 1e6 / BATCHES,
            "device_ms_per_batch": named["busy_s"] * 1e3 / BATCHES,
            "pad_share": 100.0 * (1 - 11 / (BATCHES * 256)),
            "requests_per_batch": 5 / BATCHES}
    assert 40 < want["scatter_ms_per_batch"] < 60 < want["device_ms_per_batch"] < 100
    assert want["dispatch_wait_ms"] > 0
    for name, value in want.items():
        read = layout.reader(f"{name}.{suffix}")
        assert read(_run(named, COUNTERS)) == pytest.approx(value, rel=1e-12), name
        assert read(_run(named)) is None, name


@pytest.mark.parametrize("cell", [w["name"] for w in layout.benchmark()["workloads"]])
def test_a_traced_run_reports_every_per_layer_metric_of_its_cell(monkeypatch, cell):
    """What a cell's ``--trace 1`` line must carry, read from a trace of its
    kind that holds the program's names; a served cell's run also carries
    the scheduler's window counters."""
    bench = layout.benchmark()
    served = layout.traffic(layout.cell(bench, cell)["traffic"])["loop"] == "open_serve"
    _, named = _read_as_run_does(monkeypatch, SERVED if served else SCOPED)
    got = run.per_layer(bench, cell, _run(named, *((COUNTERS, VALUES) if served else ())))
    assert set(got) == {m["name"] for m in layout.metrics_of(bench, "per_layer", cell)}
    assert all(isinstance(m["value"], float) and m["value"] > 0 for m in got.values())
