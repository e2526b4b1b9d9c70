"""Crash-consistency smoke: kill -9 mid-checkpoint, warm-restart, parity.

The durability claim (DESIGN.md §9) is not "saves usually work" — it is
that a store killed at the WORST moment (mid-incremental-save, after the
tmp dir has absorbed some leaf files but before the manifest commits)
restarts from the newest *committed* step with bit-identical query
results.  This smoke proves it end to end, per algorithm:

  1. a child process builds a deterministic store, applies a mutation
     history (add + TTL batch, deletes, expiry), and commits it
     (``store.save`` — step 0);
  2. the child mutates again and starts an incremental ``save_dirty``,
     with a hook that SIGKILLs the process after the second leaf write —
     a torn ``step_1.tmp-<pid>`` dir with no manifest is left behind;
  3. once every algorithm's child has run, the parent verifies each child
     died by SIGKILL and left its torn tmp, builds an UNKILLED TWIN (same seeds, same mutation history
     up to the committed step), loads the checkpoint
     (``ShardedKNNStore.load`` — must resolve step 0, ignoring the torn
     write), and asserts ids AND scores of a query batch are bit-equal
     to the twin's, with ZERO query-time index builds after load.

The parent touches JAX only after the last child has exited: a process
that has initialized a backend holds the accelerator, and a child started
after that could not get the device.

  XLA_FLAGS=--xla_force_host_platform_device_count=4 \
      PYTHONPATH=src python -m benchmarks.crash_smoke        # make crash-smoke
"""
from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import tempfile
import time

import numpy as np

DIM, NNZ, K = 1024, 16, 5
N_SEED = 160


def _spec(algorithm: str):
    from repro.core import JoinSpec

    return JoinSpec(k=K, algorithm=algorithm, r_block=32, s_block=48)


def scenario(algorithm: str):
    """Build + the COMMITTED mutation history (everything before the
    checkpoint the child commits).  Deterministic: the killed child and
    the parent's unkilled twin both run exactly this."""
    from repro.sparse.datagen import synthetic_sparse
    from repro.store import ShardedKNNStore

    S = synthetic_sparse(N_SEED, dim=DIM, nnz_mean=NNZ, seed=0)
    store = ShardedKNNStore.build(S, _spec(algorithm))
    store.add(synthetic_sparse(12, dim=DIM, nnz_mean=NNZ, seed=1),
              ttl=2.0, now=0.0)                       # TTL batch ...
    store.add(synthetic_sparse(8, dim=DIM, nnz_mean=NNZ, seed=2))
    store.delete([0, 3, 7])
    store.expire(now=5.0)                             # ... tombstones here
    return store


def child(directory: str, algorithm: str, kill_after: int = 2) -> None:
    """Commit the scenario, then die by SIGKILL partway through a second
    (incremental) save — after ``kill_after`` leaf writes, before the
    manifest: the torn tmp dir is the crash artifact the parent checks."""
    store = scenario(algorithm)
    store.save(directory)                             # committed step 0
    from repro.sparse.datagen import synthetic_sparse

    store.add(synthetic_sparse(4, dim=DIM, nnz_mean=NNZ, seed=3))

    real_save = np.save
    writes = {"n": 0}

    def killing_save(file, arr, *a, **kw):
        real_save(file, arr, *a, **kw)
        writes["n"] += 1
        if writes["n"] >= kill_after:
            os.kill(os.getpid(), signal.SIGKILL)

    np.save = killing_save                            # ckpt writes leaves via np.save
    store.save_dirty(directory)
    raise SystemExit("kill hook never fired — save wrote no leaves?")


def crash_one(algorithm: str, base_dir: str) -> dict:
    """Run the killed-mid-save child for one algorithm.  No JAX here: the
    child must be the only process on the device."""
    d = os.path.join(base_dir, algorithm)
    t0 = time.perf_counter()
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.crash_smoke",
         "--child", d, "--algorithm", algorithm],
        env=os.environ.copy(), capture_output=True, text=True,
    )
    killed = proc.returncode == -signal.SIGKILL
    if not killed:
        sys.stderr.write(proc.stderr[-2000:] + "\n")
    torn = os.path.isdir(d) and any(".tmp-" in n for n in os.listdir(d))
    return {"dir": d, "killed": killed, "torn": torn,
            "child_s": time.perf_counter() - t0}


def verify_one(algorithm: str, crash: dict) -> dict:
    """Warm-restart the crashed child's checkpoint and check it against an
    unkilled twin (this process's first use of JAX)."""
    from repro.checkpoint import ckpt as _ckpt
    from repro.sparse.datagen import synthetic_sparse
    from repro.store import ShardedKNNStore

    d, killed, torn = crash["dir"], crash["killed"], crash["torn"]
    t0 = time.perf_counter()
    step = _ckpt.latest_step(d) if os.path.isdir(d) else None

    twin = scenario(algorithm)                        # unkilled twin
    t_load = time.perf_counter()
    loaded = ShardedKNNStore.load(d)
    load_s = time.perf_counter() - t_load

    R = synthetic_sparse(24, dim=DIM, nnz_mean=NNZ, seed=9)
    builds0 = loaded.stats.index_builds
    ref, got = twin.query(R), loaded.query(R)
    parity = (
        (np.asarray(ref.ids) == np.asarray(got.ids)).all()
        and (np.asarray(ref.scores) == np.asarray(got.scores)).all()
    )
    checks = {
        "killed_by_sigkill_ok": killed,
        "torn_tmp_left_ok": torn,
        "restart_skips_torn_ok": step == 0,
        "parity_ok": bool(parity),
        "zero_query_builds_ok": loaded.stats.index_builds == builds0,
        "rows_match_ok": loaded.num_vectors == twin.num_vectors,
    }
    return {
        "algorithm": algorithm,
        "live_rows": int(loaded.num_vectors),
        "shards": loaded.n_shards,
        "load_s": round(load_s, 4),
        "wall_s": round(crash["child_s"] + time.perf_counter() - t0, 4),
        **checks,
        "ok": all(checks.values()),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--child", default=None, metavar="DIR",
                    help="internal: run the killed-mid-save child")
    ap.add_argument("--algorithm", default=None,
                    help="child: which algorithm to build")
    ap.add_argument("--algorithms", default="bf,iib,iiib",
                    help="parent: comma-separated list to smoke")
    args = ap.parse_args(argv)

    if args.child:
        child(args.child, args.algorithm or "iib")
        return 1                                      # unreachable

    algorithms = [a.strip() for a in args.algorithms.split(",")]
    with tempfile.TemporaryDirectory(prefix="crash_smoke_") as base:
        # every child first: the parent may not hold a device while one runs
        crashes = [crash_one(a, base) for a in algorithms]
        records = [verify_one(a, c) for a, c in zip(algorithms, crashes)]
    ok = all(r["ok"] for r in records)
    print(json.dumps({"crash_smoke": records, "ok": ok}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
