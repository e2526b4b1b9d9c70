#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 benchmarks/chip/run.py --workload synth50k.join --seed 7 \\
        --seconds 30 --trace 0

The cell, its configuration and its traffic mix are found by name from
``BENCHMARK.json`` (see ``layout.py``).  Set-up makes S and the query pool
from ``--seed``, builds ``ShardedKNNStore`` over S; then the loop the mix
names warms up every shape of the window and measures for ``--seconds``.
Afterwards a sample of the window's answers, drawn from the seed, is
compared with the float64 reference (``oracle.py``), each row over the
ranks its request asked for.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics read from a profiler trace of the
window), ``device``, with ``--trace 1`` a ``breakdown``, and last the
``check``: each compared number beside its limit, which also ends standard
error.  Without an accelerator, or with fewer chips than the cell asks
for, it exits with code 2 and prints no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
for _p in (str(ROOT / "src"), str(HERE)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import numpy as np  # noqa: E402

import datagen  # noqa: E402
import layout  # noqa: E402
import loopkit  # noqa: E402
import oracle  # noqa: E402
import roofline  # noqa: E402
import timeline  # noqa: E402

CACHE_DIR = ROOT / ".jax_cache"


class NoChip(RuntimeError):
    """No accelerator, or fewer chips than the cell asks for."""


def chips(n: int, require_chip: bool):
    import jax

    devs = jax.devices()
    if require_chip and (devs[0].platform == "cpu" or len(devs) < n):
        raise NoChip(f"cell needs {n} accelerator chip(s); JAX sees "
                     f"{len(devs)} {devs[0].platform} device(s)")
    return devs[:n]


def enable_cache():
    """JAX's persistent compilation cache at one fixed path in the
    checkout, every program in it, so only a checkout's first run
    compiles."""
    import jax

    CACHE_DIR.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def _mem(dev, key):
    stats = dev.memory_stats() or {}
    return int(stats.get(key, 0))


def in_use(devs) -> int:
    """Device bytes in use, summed over every chip of the cell."""
    return sum(_mem(d, "bytes_in_use") for d in devs)


def read_trace(log_dir: str) -> dict:
    """The traced window reduced by the program's own names
    (``timeline.py``): ``tracereduce``'s numbers plus scopes and spans."""
    return timeline.reduce(timeline.extract(log_dir))


def per_layer(bench: dict, cell_name: str, run: dict) -> dict:
    """The cell's per-layer metrics its readers find in ``run``."""
    out = {}
    for m in layout.metrics_of(bench, "per_layer", cell_name):
        v = layout.reader(m["name"])(run)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def _check_rows(rec, limit, k, rng):
    """A seeded sample of the rows the loop answered: pool rows, the
    program's ids and scores of each, and the k its request asked for.

    An answer is ``(pool rows, ids, scores)``, asked at the configuration's
    ``k``, or ``(pool rows, ids, scores, k)``; a row its answer lacks reads
    an empty answer."""
    answers = rec["answers"]
    ends = np.cumsum([len(a[0]) for a in answers])
    pos = np.sort(rng.choice(ends[-1], min(limit, ends[-1]), replace=False))
    rows, ids, scores, ks = [], [], [], []
    for p in pos:
        a = int(np.searchsorted(ends, p, side="right"))
        got, j = answers[a], p - (ends[a] - len(answers[a][0]))
        rows.append(got[0][j])
        ids.append(got[1][j] if j < len(got[1]) else np.empty(0))
        scores.append(got[2][j] if j < len(got[2]) else np.empty(0))
        ks.append(got[3] if len(got) > 3 else k)
    return np.asarray(rows), ids, scores, ks


def run_cell(bench: dict, cell_name: str, seed: int, seconds: float,
             trace: bool, *, require_chip: bool = True, cfg=None,
             traffic=None, t_start: float = T_PROCESS, fault=None) -> dict:
    """One run of one cell; returns the result object.  ``cfg`` and
    ``traffic`` replace the files the cell names, and ``fault`` is called
    with the built store once set-up ends, before the window; tests use
    them."""
    w = layout.cell(bench, cell_name)
    cfg = cfg or layout.config(bench, w["config"])
    traffic = traffic or layout.traffic(w["traffic"])
    devs = chips(w["chips"], require_chip)
    enable_cache()
    counter = loopkit.CompileCounter()

    from repro.core import JoinSpec
    from repro.sparse.format import SparseBatch
    from repro.store import ShardedKNNStore

    dim, k = cfg["dim"], cfg["k"]
    s_rows = datagen.generate(cfg, cfg["n_s"], seed, part=0)
    pool = datagen.generate(cfg, cfg["n_r"], seed, part=1)
    mem0 = in_use(devs)
    store = ShardedKNNStore.build(
        SparseBatch(indices=s_rows[0], values=s_rows[1], nnz=s_rows[2], dim=dim),
        JoinSpec(k=k, algorithm=cfg["algorithm"]), num_shards=cfg["shards"])
    s_nnz = int(s_rows[2].sum())

    def on_ready():
        ready = {"t": time.perf_counter(), "bytes": in_use(devs)}
        if fault is not None:
            fault(store)
        return ready

    log_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    try:
        rec = layout.loop(traffic["loop"])(store, pool, dim, traffic, seconds,
                                           log_dir, counter, on_ready)
        peak = max(_mem(d, "peak_bytes_in_use") for d in devs)
        reduced = read_trace(log_dir) if trace else None
    finally:
        if log_dir is not None:
            shutil.rmtree(log_dir, ignore_errors=True)
    counter.close()
    del store
    gc.collect()

    values = {
        "setup_s": rec["ready"]["t"] - t_start,
        "index_bytes_per_nnz": (rec["ready"]["bytes"] - mem0) / s_nnz,
        **rec["metrics"],
    }

    # the check: reference on the host, after the window and the store
    rng = np.random.default_rng([seed, 3])
    r_ix, prog_ids, prog_scores, ks = _check_rows(rec, int(traffic["check_rows"]), k, rng)
    ref = oracle.Reference(oracle.csr64(*s_rows, dim), k)
    ref_scores, _, table = ref.run(pool[0][r_ix], pool[1][r_ix], pool[2][r_ix], dim)
    asked = [ref_scores[i, :kk] for i, kk in enumerate(ks)]
    numbers = oracle.compare(prog_ids, prog_scores, asked, table)
    limits = dict(cfg["check"], bad_rows=0)
    correct = oracle.verdict(numbers, limits)

    result = {
        "correct": bool(correct),
        "attempted": int(rec["attempted"]),
        "failed": int(rec["failed"]),
        "metrics": {},
        "device": {
            "platform": devs[0].platform,
            "kind": devs[0].device_kind,
            "count": len(devs),
            "memory_peak_bytes": peak,
        },
    }
    if not trace:
        for m in layout.metrics_of(bench, "end_to_end", cell_name):
            result["metrics"][m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    else:
        counts = roofline.dim_counts(s_rows[0], s_rows[2], dim)
        rows = rec["window_rows"]
        ops, nbytes = roofline.scan_work(pool[0][rows], pool[2][rows], counts, s_nnz, k)
        run = {
            "trace": reduced,
            "rows": len(rows),
            "loop": rec.get("counters"),
            "values": values,
            "scan": {"ops": ops, "bytes": nbytes,
                     "peak": roofline.peaks(devs[0].device_kind)},
        }
        result["metrics"] = per_layer(bench, cell_name, run)
        result["device"]["busy_s"] = reduced["busy_s"]
        result["device"]["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["info"] = {
        "compiles_in_window": rec["compiles_in_window"],
        "window_s": rec["window_s"],
        "warmup_s": rec["warmup_s"],
        "warmup_calls": rec["warmup_calls"],
        "checked_rows": len(r_ix),
        "blocks": rec["blocks"],
        "counters": rec.get("counters"),
        **rec.get("info", {}),
        **values,
    }
    result["check"] = {name: {"value": numbers[name], "limit": limits[name]}
                       for name in numbers}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run_cell(layout.benchmark(), args.workload, args.seed,
                          args.seconds, bool(args.trace))
    except NoChip as e:
        print(f"no chip: {e}", file=sys.stderr)
        return 2
    info = result["info"]
    print(f"compiles in window: {info['compiles_in_window']}", file=sys.stderr)
    print("info " + json.dumps(info), file=sys.stderr)
    for name, c in result["check"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
