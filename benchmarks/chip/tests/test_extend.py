"""A data generator, a traffic loop and the end-to-end metric the loop
reports, added to a copy of the harness as new files and entries alone:
the copy runs a cell of them, and no file it had is changed."""
import json
import os
import pathlib
import shutil
import subprocess
import sys

import layout

SRC = layout.ROOT / "src"

GENERATOR = '''"""Rows whose dims are one band of ``width`` consecutive dims."""
import numpy as np


def generate(cfg, n, structure, seed):
    w, dim = cfg["data"]["width"], cfg["dim"]
    start = np.random.default_rng(structure).integers(0, dim - w, n)
    idx = (start[:, None] + np.arange(w)[None, :]).astype(np.int32)
    val = np.random.default_rng(seed).uniform(0.1, 1.0, (n, w)).astype(np.float32)
    return idx, val, np.full(n, w, np.int32)
'''

LOOP = '''"""One caller, ``store.query`` on ``rows_per_call`` rows at a time."""
import time

import numpy as np

import loopkit


def run(store, pool, dim, traffic, seconds, log_dir, counter, on_ready):
    n, per = pool[0].shape[0], int(traffic["rows_per_call"])

    def rows(i):
        return (np.arange(per) + i * per) % n

    t_w = time.perf_counter()
    np.asarray(store.query(loopkit.rows_batch(pool, rows(0), dim)).ids)
    warmup_s = time.perf_counter() - t_w
    ready = on_ready()
    compiles0 = counter.count
    answers = []
    with loopkit.traced(log_dir):
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            res = store.query(loopkit.rows_batch(pool, rows(len(answers)), dim))
            answers.append((rows(len(answers)), np.asarray(res.ids), np.asarray(res.scores)))
        t = time.perf_counter() - t0
    return {"ready": ready, "window_s": t, "metrics": {"calls_per_s": len(answers) / t},
            "attempted": len(answers), "failed": 0,
            "compiles_in_window": counter.count - compiles0, "blocks": len(answers),
            "window_rows": np.concatenate([a[0] for a in answers]), "answers": answers,
            "warmup_s": warmup_s, "warmup_calls": 1}
'''

CONFIG = {"name": "band", "n_s": 300, "n_r": 200, "dim": 1000, "k": 5,
          "data": {"generator": "banded", "structure_seed": 3, "width": 20},
          "algorithm": "iiib", "shards": 1, "check": {"gap": 1.5e-6}}

BENCH = {
    "configs": [{"name": "band", "file": "benchmarks/chip/configs/band.json"}],
    "workloads": [{"name": "band.pairs", "config": "band", "traffic": "pairs", "chips": 1}],
    "end_to_end": [{"name": "calls_per_s", "unit": "calls/s"},
                   {"name": "index_bytes_per_nnz", "unit": "B/nnz"},
                   {"name": "setup_s", "unit": "s"}],
    "per_layer": [],
}

DRIVE = """
import json, sys
import layout, run
res = run.run_cell(layout.benchmark(), "band.pairs", 2**31 + 5, 0.5, False,
                   require_chip=False)
print(json.dumps({"correct": res["correct"], "metrics": sorted(res["metrics"]),
                  "calls_per_s": res["metrics"]["calls_per_s"]["value"]}))
"""


def _files(here: pathlib.Path) -> dict:
    return {p.relative_to(here): p.read_bytes() for p in here.rglob("*")
            if p.is_file() and "__pycache__" not in p.parts}


def test_a_generator_a_loop_and_its_metric_are_files_and_entries(tmp_path):
    here = tmp_path / "benchmarks" / "chip"
    shutil.copytree(layout.HERE, here,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    before = _files(here)
    (here / "generators" / "banded.py").write_text(GENERATOR)
    (here / "loops" / "pairs.py").write_text(LOOP)
    (here / "configs" / "band.json").write_text(json.dumps(CONFIG))
    (here / "traffic" / "pairs.json").write_text(
        json.dumps({"loop": "pairs", "rows_per_call": 2, "check_rows": 64}))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(BENCH))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(here), str(SRC)]))
    p = subprocess.run([sys.executable, "-c", DRIVE], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"]
    assert out["metrics"] == ["calls_per_s", "index_bytes_per_nnz", "setup_s"]
    assert out["calls_per_s"] > 0
    after = _files(here)
    assert {f: after[f] for f in before} == before
    assert set(after) - set(before) == {
        pathlib.Path("generators/banded.py"), pathlib.Path("loops/pairs.py"),
        pathlib.Path("configs/band.json"), pathlib.Path("traffic/pairs.json")}
