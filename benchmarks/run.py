"""Benchmark harness — one module per paper figure + the roofline table.

  PYTHONPATH=src python -m benchmarks.run            # full
  PYTHONPATH=src python -m benchmarks.run --fast     # CI-sized
  PYTHONPATH=src python -m benchmarks.run --only fig3_effect_k
  PYTHONPATH=src python -m benchmarks.run --smoke    # build-once/query-many CI check
  PYTHONPATH=src python -m benchmarks.run --fast --out BENCH_PR2.json
                                                     # machine-readable perf record
"""
from __future__ import annotations

import argparse
import json
import platform
import sys
import time

from benchmarks import fig1_data_size, fig2_relative_size, fig3_effect_k, fig4_buffer_size, roofline
from repro.runtime.compile_cache import enable_compile_cache

SUITES = {
    "fig1_data_size": fig1_data_size.run,
    "fig2_relative_size": fig2_relative_size.run,
    "fig3_effect_k": fig3_effect_k.run,
    "fig4_buffer_size": fig4_buffer_size.run,
    "roofline": roofline.run,
}


def smoke() -> int:
    """Tiny build-once/query-many join on CPU: the engine's serving shape
    must be visible in the counters.

    Fails (non-zero exit) on either regression the engine exists to prevent:
      * index reuse — S-block indexes rebuilt per query instead of once
        (IIB and, since the superset refactor, IIIB too);
      * dispatch shape — a query stream exceeding queries x r_blocks scan
        dispatches (i.e. the driver fell back to per-(R,S)-pair dispatch),
        or host syncs beyond the one per-R-block result pull (i.e. a
        per-pair host round-trip crept back in).
    """
    from benchmarks.common import (
        gen, gen_clustered, run_approx_query, run_repeated_query,
        run_store_query,
    )

    R = gen("synthetic", 96, seed=0, dim=2048, nnz=24)
    S = gen("synthetic", 160, seed=1, dim=2048, nnz=24)
    queries = 3
    checks = {}
    ok = True
    for algorithm in ("iib", "iiib"):
        out = run_repeated_query(R, S, k=5, algorithm=algorithm, queries=queries,
                                 r_block=48, s_block=64)
        r_blocks = out["r_blocks"]
        c = {
            "index_reuse_ok": out["index_builds"] == out["s_blocks"],
            "scan_dispatch_ok": sum(out["device_dispatches"]) <= queries * r_blocks,
            "host_sync_ok": all(h <= r_blocks for h in out["host_syncs"]),
        }
        ok &= all(c.values())
        checks[algorithm] = {"smoke": out, **c}
    # sharded store: same dispatch shape per query (O(R-blocks), NOT
    # O(R-blocks x shards)) and zero query-time index builds
    out = run_store_query(R, S, k=5, algorithm="iib", queries=queries,
                          r_block=48, s_block=64)
    c = {
        "store_no_query_builds_ok": out["query_index_builds"] == 0,
        "store_dispatch_ok":
            sum(out["device_dispatches"]) <= queries * out["r_blocks"],
        "store_sync_ok": all(h <= out["r_blocks"] for h in out["host_syncs"]),
    }
    ok &= all(c.values())
    checks["store"] = {"smoke": out, **c}
    # approximate tier: recall bar + a strictly-sublinear candidate set +
    # exact-mode bit-parity, on a planted-neighbor workload
    # r_block << n_clusters: the candidate mask is a union over the R
    # block's rows, so a block spanning every cluster would touch all of S
    Rc, Sc = gen_clustered(24, per_cluster=8, dim=2048, nnz=24, seed=2)
    out = run_approx_query(Rc, Sc, k=5, algorithm="iib", target_recall=0.95,
                           queries=queries, r_block=6, s_block=64)
    c = {
        "approx_recall_ok": out["recall"] >= out["target_recall"],
        "approx_candidates_sublinear": out["candidate_fraction"] < 1.0,
        "approx_exact_parity_ok": out["exact_parity_ok"],
        "approx_no_query_builds_ok": out["query_index_builds"] == 0,
    }
    ok &= all(c.values())
    checks["approx"] = {"smoke": out, **c}
    print(json.dumps(checks))
    return 0 if ok else 1


def perf_record(fast: bool, out_path: str) -> int:
    """Write the PR-trajectory perf record: per-query wall time, device
    dispatches, host syncs, index builds, and list-entry work for a
    build-once/query-many stream of every algorithm (+ the fused-kernel
    path).  Machine-readable so successive PRs can be diffed."""
    import jax

    from benchmarks.common import (
        gen, gen_clustered, run_approx_query, run_repeated_query,
        run_store_query,
    )

    n_r, n_s, dim, nnz = (128, 512, 4096, 32) if fast else (256, 2048, 8192, 64)
    r_block, s_block, k, queries = n_r // 2, n_s // 4, 5, 3
    R = gen("synthetic", n_r, seed=0, dim=dim, nnz=nnz)
    S = gen("synthetic", n_s, seed=1, dim=dim, nnz=nnz)

    streams = {}
    for name, algorithm, use_kernel in (
        ("bf", "bf", False),
        ("iib", "iib", False),
        ("iib_kernel", "iib", True),
        ("iiib", "iiib", False),
    ):
        streams[name] = run_repeated_query(
            R, S, k=k, algorithm=algorithm, queries=queries,
            r_block=r_block, s_block=s_block, use_kernel=use_kernel,
        )
        print(f"{name}: query_s={streams[name]['query_s']} "
              f"dispatches={streams[name]['device_dispatches']}", flush=True)
    # sharded store streams (shards = local devices; `make bench` forces 4
    # virtual CPU devices so the record captures a real fan-out)
    for algorithm in ("bf", "iib", "iiib"):
        name = f"store_{algorithm}"
        streams[name] = run_store_query(
            R, S, k=k, algorithm=algorithm, queries=queries,
            r_block=r_block, s_block=s_block,
        )
        print(f"{name}: query_s={streams[name]['query_s']} "
              f"dispatches={streams[name]['device_dispatches']} "
              f"shards={streams[name]['shards']}", flush=True)
    # approximate-tier streams: recall + candidate fraction are measured on
    # a planted-neighbor workload (uniform random sparse data has no
    # high-similarity neighbors to recall — see gen_clustered)
    n_cl = max(8, n_r // 4)
    Rc, Sc = gen_clustered(n_cl, per_cluster=2 * k, dim=dim, nnz=nnz, seed=2)
    # r_block << n_clusters keeps the per-block candidate union (the thing
    # the filter saves) well below |S|
    for name, kw in (
        ("approx_iib", {"algorithm": "iib"}),
        ("approx_iiib", {"algorithm": "iiib"}),
        ("approx_store_iib", {"algorithm": "iib", "store": True}),
    ):
        streams[name] = run_approx_query(
            Rc, Sc, k=k, target_recall=0.95, queries=queries,
            r_block=max(4, n_cl // 4),
            s_block=min(s_block, 2 * k * n_cl // 4), **kw,
        )
        print(f"{name}: recall={streams[name]['recall']} "
              f"cand_frac={streams[name]['candidate_fraction']} "
              f"parity={streams[name]['exact_parity_ok']}", flush=True)

    record = {
        "config": {
            "n_r": n_r, "n_s": n_s, "dim": dim, "nnz_mean": nnz, "k": k,
            "r_block": r_block, "s_block": s_block, "queries": queries,
            "fast": fast,
            "backend": jax.default_backend(),
            "device_count": jax.device_count(),
            "platform": platform.platform(),
        },
        "streams": streams,
    }
    with open(out_path, "w") as f:
        json.dump(record, f, indent=1)
        f.write("\n")
    print(f"wrote {out_path}")
    return 0


def main(argv=None):
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized build-once/query-many check (index reuse + dispatch shape)")
    ap.add_argument("--out", default=None, metavar="PATH",
                    help="write a machine-readable perf record (wall time per query, "
                         "device dispatches, index_builds, list_entries) and exit")
    ap.add_argument("--only", default=None, choices=list(SUITES))
    args = ap.parse_args(argv)

    if args.smoke:
        return smoke()
    if args.out:
        return perf_record(args.fast, args.out)

    names = [args.only] if args.only else list(SUITES)
    summary = {}
    for name in names:
        print(f"\n######## {name} ########", flush=True)
        t0 = time.time()
        out = SUITES[name](fast=args.fast)
        summary[name] = {
            "seconds": round(time.time() - t0, 1),
            "checks": out.get("checks") if isinstance(out, dict) else None,
        }
    print("\n######## summary ########")
    print(json.dumps(summary, indent=1, default=str))
    return 0


if __name__ == "__main__":
    sys.exit(main())
