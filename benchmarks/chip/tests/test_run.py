"""The harness end to end on the CPU at a tiny size, with the chip check
skipped, and with the timed path broken underneath to see ``correct``
come out false."""
import asyncio
import dataclasses
import json
import os
import pathlib
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

import datagen
import layout
import run

ROOT = pathlib.Path(__file__).resolve().parents[3]
SEED = 2**31 + 4242
def _tiny(cell, config):
    """The cell's loop over ``configs/<config>.json`` at a tiny size."""
    bench = layout.benchmark()
    w = layout.cell(bench, cell)
    cfg = json.loads((layout.HERE / "configs" / f"{config}.json").read_text())
    cfg.update(n_s=500, n_r=600)
    cfg["dim"] = 2000 if cfg["data"]["generator"] == "synthetic" else 4000
    traffic = layout.traffic(w["traffic"])
    if "rows_per_call" in traffic:
        traffic["rows_per_call"] = 256
    return bench, cfg, traffic


def _run(cell, config, fault=None, seconds=1.5):
    bench, cfg, traffic = _tiny(cell, config)
    return run.run_cell(bench, cell, SEED, seconds, False, require_chip=False,
                        cfg=cfg, traffic=traffic, fault=fault)


def test_without_an_accelerator_it_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", "synth50k.join",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 2
    assert "{" not in p.stdout
    assert "no chip" in p.stderr


# every cell by its own name, and synth50k's loop over the spectra too
CASES = [("synth50k.join", "synth50k"), ("synth50k.join", "spectra52k"),
         ("spectra52k.join", "spectra52k"), ("synth50k.serve", "synth50k"),
         ("synth50k.overload", "synth50k")]
SERVED = [c for c in CASES if c[0].endswith((".serve", ".overload"))]


@pytest.mark.parametrize("cell,config", CASES)
def test_a_tiny_run_is_correct_and_names_its_device(cell, config):
    res = _run(cell, config)
    assert res["correct"], res["check"]
    assert res["device"] == {"platform": "cpu", "kind": "cpu", "count": 1,
                             "memory_peak_bytes": 0}
    want = {m["name"] for m in layout.metrics_of(layout.benchmark(), "end_to_end", cell)}
    assert set(res["metrics"]) == want
    assert list(res)[-1] == "check"
    assert res["info"]["compiles_in_window"] == 0


def _altered(store):
    """Every eighth row's best neighbour replaced where it is produced."""
    query = store.query

    def altered(R, **kw):
        res = query(R, **kw)
        ids = np.asarray(res.ids).copy()
        ids[::8, 0] = (ids[::8, 0] + 1) % store.num_vectors
        return dataclasses.replace(res, ids=jnp.asarray(ids))

    store.query = altered


def _half_left_out(store):
    """The second half of every block's live rows left without an answer."""
    query = store.query

    def halved(R, **kw):
        res = query(R, **kw)
        ids, scores = np.asarray(res.ids).copy(), np.asarray(res.scores).copy()
        live = np.flatnonzero(np.asarray(R.nnz) > 0)
        gone = live[len(live) // 2:]
        ids[gone], scores[gone] = -1, -np.inf
        return dataclasses.replace(res, ids=jnp.asarray(ids), scores=jnp.asarray(scores))

    store.query = halved


@pytest.mark.parametrize("fault", [_altered, _half_left_out], ids=["altered", "half"])
@pytest.mark.parametrize("cell,config", CASES)
def test_a_broken_timed_path_is_not_correct(cell, config, fault):
    res = _run(cell, config, fault=fault)
    assert not res["correct"], res["check"]


def _first_request_altered(store):
    """The best neighbour of the first row of the window's first store
    call replaced: one request's answer altered where it is produced."""
    query = store.query
    calls = []

    def altered(R, **kw):
        res = query(R, **kw)
        calls.append(1)
        if len(calls) > 1:
            return res
        ids = np.asarray(res.ids).copy()
        ids[0, 0] = (ids[0, 0] + 1) % store.num_vectors
        return dataclasses.replace(res, ids=jnp.asarray(ids))

    store.query = altered


def _two_swapped(monkeypatch):
    """The answers of the window's first two answered requests handed to
    each other, as a de-interleave that mixes up two requests would."""
    from repro.serve import scheduler

    submit = scheduler.KNNScheduler.submit
    held = []

    async def swapped(self, rows, k=None, **kw):
        res = await submit(self, rows, k=k, **kw)
        held.append(res)
        if len(held) == 1:
            other = asyncio.get_running_loop().create_future()
            held.append(other)
            return await other
        if len(held) == 3:
            held[1].set_result(res)
            return held[0]
        return res

    return lambda store: monkeypatch.setattr(scheduler.KNNScheduler, "submit", swapped)


@pytest.mark.parametrize("fault", ["one_altered", "two_swapped"])
@pytest.mark.parametrize("cell,config", SERVED)
def test_a_broken_serving_path_is_not_correct(cell, config, fault, monkeypatch):
    broken = (_first_request_altered if fault == "one_altered"
              else _two_swapped(monkeypatch))
    res = _run(cell, config, fault=broken)
    assert not res["correct"], res["check"]
    assert res["attempted"] > 2


class _StubChip:
    """A device whose allocator reads ``before`` until the store is built,
    then ``after``."""
    platform = device_kind = "stub"

    def __init__(self, before, after):
        self.readings = [before, after]

    def memory_stats(self):
        used = self.readings.pop(0) if len(self.readings) > 1 else self.readings[0]
        return {"bytes_in_use": used, "peak_bytes_in_use": used}


def test_index_bytes_count_every_chip_of_the_cell(monkeypatch):
    """Two chips whose allocators differ: the index is what both gained."""
    devs = [_StubChip(1_000, 5_000), _StubChip(200, 9_200)]
    monkeypatch.setattr(run, "chips", lambda n, require_chip: devs)
    bench, cfg, traffic = _tiny("synth50k.join", "synth50k")
    res = run.run_cell(bench, "synth50k.join", SEED, 0.2, False, require_chip=False,
                       cfg=cfg, traffic=traffic)
    s_nnz = int(datagen.generate(cfg, cfg["n_s"], SEED, part=0)[2].sum())
    assert res["metrics"]["index_bytes_per_nnz"]["value"] == (4_000 + 9_000) / s_nnz
    assert res["device"]["count"] == 2
    assert res["device"]["memory_peak_bytes"] == 9_200
