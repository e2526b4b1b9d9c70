import json

import layout


def test_a_new_config_mix_and_metric_are_found_by_name(tmp_path):
    """A fifth cell is files and entries only: nothing in the harness
    lists configurations, mixes or metrics."""
    here = tmp_path / "bench"
    for d in ("configs", "traffic", "metrics"):
        (here / d).mkdir(parents=True)
    (here / "configs" / "new.json").write_text(json.dumps({"name": "new", "n_s": 7}))
    (here / "traffic" / "burst.json").write_text(json.dumps({"loop": "closed_join", "rows_per_call": 64}))
    (here / "metrics" / "busy_ms.burst.py").write_text(
        "def read(run):\n    return run['trace']['busy_s'] * 1e3\n")
    bench = {
        "configs": [{"name": "new", "file": "bench/configs/new.json"}],
        "workloads": [{"name": "new.burst", "config": "new", "traffic": "burst", "chips": 1}],
        "end_to_end": [{"name": "setup_s"}, {"name": "p95_ms", "workloads": ["other"]}],
        "per_layer": [{"name": "busy_ms.burst", "workloads": ["new.burst"]}],
    }
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    bench = layout.benchmark(tmp_path)
    w = layout.cell(bench, "new.burst")
    assert layout.config(bench, w["config"], root=tmp_path)["n_s"] == 7
    assert layout.traffic(w["traffic"], here=here)["rows_per_call"] == 64
    assert [m["name"] for m in layout.metrics_of(bench, "end_to_end", "new.burst")] == ["setup_s"]
    [m] = layout.metrics_of(bench, "per_layer", "new.burst")
    assert layout.reader(m["name"], here=here)({"trace": {"busy_s": 2.0}}) == 2000.0


def test_every_cell_of_the_benchmark_resolves():
    bench = layout.benchmark()
    for w in bench["workloads"]:
        cfg = layout.config(bench, w["config"])
        assert cfg["name"] == w["config"]
        assert callable(layout.loop(layout.traffic(w["traffic"])["loop"]))
        for m in layout.metrics_of(bench, "per_layer", w["name"]):
            assert callable(layout.reader(m["name"]))
