#!/usr/bin/env python3
"""The control of the comparison, and the program's readings beside it.

The configurations state float32 scoring at ``Precision.HIGHEST``.  The
control puts the reference in the program's place one precision step
below: float32 products taken as three bfloat16 passes (``Precision.HIGH``
on the TPU, written out here so that it means the same on every
backend), summed in float32, then the top k.  The comparison in
``oracle.py`` has to call the control's answers not correct; its readings
set each limit's upper end.

    python3 benchmarks/chip/control.py --workload synth50k.join \\
        --control-seeds 1,2,3 --program-seeds 4,5,...,15

For each seed it makes the cell's data as a run does, takes the query
rows a run's check would draw from the window (in a join cell the first
calls' rows; in a served cell, whose loop has a ``schedule``, the rows of
the requests due in a window of ``run_seconds``, each compared over the
ranks its request asks for), and prints one JSON line: the control's
numbers for a control seed; for a program seed the numbers of the
program's own answers, from ``store.query`` on the window's 8,192-row
calls (a served cell's program readings are its runs' own check).  The
benchmark's runs do not run this; it needs the chip at the cells' size.
"""
from __future__ import annotations

import argparse
import json
import sys

import numpy as np

import run as bench_run  # first: puts the program's src on the path
import datagen
import layout
import loopkit
import oracle

S_BLOCK = 4096


def _split(x):
    """x = hi + lo + O(2^-16 x), hi and lo bfloat16 values held in float32.
    ``reduce_precision`` rounds where a cast pair may be folded away."""
    import jax

    bf16 = lambda v: jax.lax.reduce_precision(v, exponent_bits=8, mantissa_bits=7)
    hi = bf16(x)
    return hi, bf16(x - hi)


def _bf16x3(a, b):
    """a @ b.T in three bfloat16 passes with float32 sums."""
    import jax
    import jax.numpy as jnp

    a_hi, a_lo = _split(a)
    b_hi, b_lo = _split(b)
    dot = lambda x, y: jnp.dot(x, y.T, precision=jax.lax.Precision.HIGHEST)
    return dot(a_hi, b_hi) + dot(a_hi, b_lo) + dot(a_lo, b_hi)


def _dense32(idx, val, nnz, dim):
    out = np.zeros((idx.shape[0], dim), np.float32)
    mask = np.arange(idx.shape[1])[None, :] < nnz[:, None]
    rows = np.broadcast_to(np.arange(idx.shape[0])[:, None], idx.shape)
    out[rows[mask], idx[mask]] = val[mask]
    return out


def control_answers(s_rows, q_rows, dim, k):
    """Top-k (ids, scores) of the query rows by the three-pass product."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def step(best_s, best_i, q, blk, base):
        s = _bf16x3(q, blk)
        ids = base + jnp.arange(blk.shape[0], dtype=jnp.int32)
        cat_s = jnp.concatenate([best_s, s], axis=1)
        cat_i = jnp.concatenate([best_i, jnp.broadcast_to(ids, s.shape)], axis=1)
        top_s, pos = jax.lax.top_k(cat_s, k)
        return top_s, jnp.take_along_axis(cat_i, pos, axis=1)

    q = jnp.asarray(_dense32(*q_rows, dim))
    m = q.shape[0]
    best_s = jnp.full((m, k), -jnp.inf, jnp.float32)
    best_i = jnp.full((m, k), -1, jnp.int32)
    n = s_rows[0].shape[0]
    for lo in range(0, n, S_BLOCK):
        blk = np.zeros((S_BLOCK, dim), np.float32)
        part = [a[lo:lo + S_BLOCK] for a in s_rows]
        blk[:part[0].shape[0]] = _dense32(*part, dim)
        best_s, best_i = step(best_s, best_i, q, jnp.asarray(blk), jnp.int32(lo))
    return np.asarray(best_i), np.asarray(best_s)


def _served_rows(schedule, traffic, seconds, n_pool, rng):
    """The pool rows a served run's check draws, each with its request's
    k, where every request due in the window is answered."""
    _, reqs, req_k = schedule(traffic, seconds, n_pool)
    rows = np.concatenate(reqs)
    ks = np.repeat(req_k, [len(r) for r in reqs])
    pos = np.sort(rng.choice(len(rows), min(int(traffic["check_rows"]), len(rows)),
                             replace=False))
    return rows[pos], ks[pos]


def readings(cfg, traffic, seed, control: bool, seconds: float = 30.0):
    """The compared numbers of one seed: the control's, or the program's
    answers on the window's first calls."""
    dim, k = cfg["dim"], cfg["k"]
    s_rows = datagen.generate(cfg, cfg["n_s"], seed, part=0)
    pool = datagen.generate(cfg, cfg["n_r"], seed, part=1)
    per = int(traffic.get("rows_per_call", 8192))
    rng = np.random.default_rng([seed, 3])
    loop = layout.module("loops", traffic.get("loop", "closed_join"))
    schedule = getattr(loop, "schedule", None)
    if schedule is not None:
        if not control:
            raise ValueError("a served cell's program readings are its runs' own check")
        r_ix, ks = _served_rows(schedule, traffic, seconds, pool[0].shape[0], rng)
    else:
        r_ix = np.sort(rng.choice(2 * per, int(traffic["check_rows"]), replace=False)) % pool[0].shape[0]
        ks = np.full(len(r_ix), k)
    q_rows = tuple(a[r_ix] for a in pool)
    if control:
        ids, scores = control_answers(s_rows, q_rows, dim, k)
    else:
        from repro.core import JoinSpec
        from repro.sparse.format import SparseBatch
        from repro.store import ShardedKNNStore

        store = ShardedKNNStore.build(
            SparseBatch(indices=s_rows[0], values=s_rows[1], nnz=s_rows[2], dim=dim),
            JoinSpec(k=k, algorithm=cfg["algorithm"]), num_shards=cfg["shards"])
        calls = [store.query(loopkit.rows_batch(pool, np.arange(i * per, (i + 1) * per)
                                              % pool[0].shape[0], dim))
                 for i in range(2)]
        pos = np.searchsorted(np.arange(2 * per) % pool[0].shape[0], r_ix)
        ids = np.concatenate([np.asarray(c.ids) for c in calls])[pos]
        scores = np.concatenate([np.asarray(c.scores) for c in calls])[pos]
        del store, calls
    ref = oracle.Reference(oracle.csr64(*s_rows, dim), k)
    ref_scores, _, table = ref.run(*q_rows, dim)
    return oracle.compare([a[:kk] for a, kk in zip(ids, ks)],
                          [a[:kk] for a, kk in zip(scores, ks)],
                          [a[:kk] for a, kk in zip(ref_scores, ks)], table)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--program-seeds", default="")
    args = ap.parse_args(argv)
    bench = layout.benchmark()
    w = layout.cell(bench, args.workload)
    cfg = layout.config(bench, w["config"])
    traffic = layout.traffic(w["traffic"])
    bench_run.chips(w["chips"], True)
    bench_run.enable_cache()
    for kind, seeds in (("control", args.control_seeds), ("program", args.program_seeds)):
        for seed in (int(s) for s in seeds.split(",") if s):
            nums = readings(cfg, traffic, seed, kind == "control", bench["run_seconds"])
            print(json.dumps({"config": cfg["name"], "kind": kind, "seed": seed,
                              **nums, "limits": cfg["check"],
                              "correct": oracle.verdict(nums, dict(cfg["check"], bad_rows=0))}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
