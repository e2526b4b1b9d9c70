"""Distributed KNN-join job launcher (the paper's workload as a service).

Runs R ⋈_KNN S with the requested algorithm either single-process
(build-once/query-many engine, core/engine.py) or sharded over the local
device mesh (``--ring``, now backed by repro.store.ShardedKNNStore: one
build-once index stack per shard, fan-out queries with an on-device top-k
reduction).  In both modes the S side is built once and ``--repeat N``
replays the query against it — the serving shape — reporting per-query
wall times plus the ``index_builds`` / ``device_dispatches`` counters
(builds stay at the number of S blocks, dispatches at the number of R
blocks, regardless of queries x shards).

  PYTHONPATH=src python -m repro.launch.join_job --nr 2000 --ns 4000 \
      --dim 10000 --k 5 --algorithm iiib --ring --data-par 4
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np

from repro.configs.paper_knn import JoinConfig
from repro.runtime.compile_cache import enable_compile_cache
from repro.sparse.datagen import spectra_like, synthetic_sparse


def build_index(cfg: JoinConfig, S):
    """Build the reusable S-side index once (engine build phase)."""
    from repro.core.engine import JoinSpec, SparseKNNIndex

    spec = JoinSpec(
        k=cfg.k, algorithm=cfg.algorithm,
        r_block=cfg.r_block, s_block=cfg.s_block, tile=cfg.tile,
    )
    return SparseKNNIndex.build(S, spec)


def build_store(cfg: JoinConfig, S, num_shards: int):
    """Build the sharded datastore once (one device-resident index stack
    per shard; the serving shape's multi-device build phase)."""
    from repro.core.engine import JoinSpec
    from repro.store import ShardedKNNStore

    spec = JoinSpec(
        k=cfg.k, algorithm=cfg.algorithm,
        r_block=cfg.r_block, s_block=cfg.s_block, tile=cfg.tile,
    )
    return ShardedKNNStore.build(S, spec, num_shards=num_shards)


def main(argv=None):
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--nr", type=int, default=2000)
    ap.add_argument("--ns", type=int, default=4000)
    ap.add_argument("--dim", type=int, default=10_000)
    ap.add_argument("--nnz", type=int, default=120)
    ap.add_argument("--k", type=int, default=5)
    ap.add_argument("--algorithm", default="iiib", choices=["bf", "iib", "iiib"])
    ap.add_argument("--spectra", action="store_true", help="MS/MS-like data")
    ap.add_argument("--ring", action="store_true")
    ap.add_argument("--data-par", type=int, default=1)
    ap.add_argument("--r-block", type=int, default=2048)
    ap.add_argument("--s-block", type=int, default=2048)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--repeat", type=int, default=1,
                    help="query the same built index N times (serving shape)")
    args = ap.parse_args(argv)

    cfg = JoinConfig(
        name="cli", n_r=args.nr, n_s=args.ns, dim=args.dim, nnz_mean=args.nnz,
        k=args.k, algorithm=args.algorithm,
        r_block=args.r_block, s_block=args.s_block,
    )
    gen = spectra_like if args.spectra else synthetic_sparse
    kw = dict(dim=args.dim) if not args.spectra else dict(dim=args.dim)
    R = gen(args.nr, seed=args.seed, **kw)
    S = gen(args.ns, seed=args.seed + 1, **kw)

    t0 = time.time()
    summary = {
        "algorithm": args.algorithm, "nr": args.nr, "ns": args.ns, "k": args.k,
    }
    if args.ring:
        # sharded store: build once over the local devices, replay queries
        store = build_store(cfg, S, args.data_par)
        query_s = []
        for _ in range(max(args.repeat, 1)):
            tq = time.time()
            res = store.query(R)
            res.scores.block_until_ready()
            query_s.append(round(time.time() - tq, 3))
        state = res.state
        summary.update({
            "wall_s": round(time.time() - t0, 3),
            "build_s": round(store.stats.build_wall_s, 3),
            "query_s": query_s,
            "shards": store.n_shards,
            "shard_rows": store.shard_rows,
            "s_blocks": store.num_blocks,
            "index_builds": store.stats.index_builds,
            "device_dispatches": store.stats.device_dispatches,
            "host_syncs": store.stats.host_syncs,
        })
    else:
        index = build_index(cfg, S)
        query_s = []
        for _ in range(max(args.repeat, 1)):
            tq = time.time()
            res = index.query(R)
            res.scores.block_until_ready()
            query_s.append(round(time.time() - tq, 3))
        state = res.state
        summary.update({
            "wall_s": round(time.time() - t0, 3),
            "build_s": round(index.stats.build_wall_s, 3),
            "query_s": query_s,
            "s_blocks": index.num_blocks,
            "index_builds": index.stats.index_builds,
        })
    summary["mean_top1"] = float(np.asarray(state.scores[:, 0]).mean())
    print(json.dumps(summary))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
