import json
import pathlib

import pytest

import tracereduce

FIXTURE = pathlib.Path(__file__).resolve().parent / "data" / "trace_synth50k_join.json"


def test_union_idle_and_gaps_on_a_hand_made_trace():
    ex = {
        "devices": {"/device:TPU:0": [[100, 50, "fusion.1"], [120, 60, "fusion.2"],
                                      [300, 100, "scatter"], [600, 10, "after"]]},
        "host": [[50, 500, "bench.window"], [60, 200, "bench.query"],
                 [260, 30, "bench.pull"]],
    }
    r = tracereduce.reduce(ex)
    assert r["window_s"] == pytest.approx(500e-9)
    assert r["busy_s"] == pytest.approx(180e-9)  # [100, 180) and [300, 400)
    assert r["device_ops"][0] == ["scatter", pytest.approx(100e-9)]
    assert [g[0] for g in r["idle_gaps"]] == [
        "host.between_annotations", "bench.query", "bench.query"]
    assert [g[1] for g in r["idle_gaps"]] == pytest.approx([150e-9, 120e-9, 50e-9])


def test_busy_is_averaged_over_devices():
    ex = {
        "devices": {"/device:TPU:0": [[0, 100, "a"]], "/device:TPU:1": [[0, 50, "a"]]},
        "host": [[0, 200, "bench.window"]],
    }
    assert tracereduce.reduce(ex)["busy_s"] == pytest.approx(75e-9)


def test_a_trace_without_device_ops_or_window_is_an_error():
    with pytest.raises(ValueError):
        tracereduce.reduce({"devices": {}, "host": [[0, 1, "bench.window"]]})
    with pytest.raises(ValueError):
        tracereduce.reduce({"devices": {"/device:TPU:0": [[0, 1, "a"]]}, "host": []})


def test_recorded_chip_trace():
    """A cut of a traced synth50k.join window on one v5e: the reduction
    finds device ops, a busy share below the window, and gaps named by
    the benchmark's annotations."""
    ex = json.loads(FIXTURE.read_text())
    r = tracereduce.reduce(ex)
    assert 0 < r["busy_s"] <= r["window_s"]
    assert r["device_ops"] and all(s > 0 for _, s in r["device_ops"])
    assert len(r["idle_gaps"]) == tracereduce.TOP
    assert {g[0] for g in r["idle_gaps"]} <= {
        "bench.query", "bench.pull", "host.between_annotations"}
