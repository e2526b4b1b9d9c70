"""Durable sharded store (DESIGN.md §9): save/load bit-parity across all
algorithms and interleaved mutations, incremental hard-link saves, elastic
reshard-on-load, corrupt-leaf fallback, and shard-loss recovery under the
serving scheduler (degraded-immediate and queued-behind-recovery).

Each suite runs in a subprocess with forced virtual CPU devices so the
store is a REAL multi-shard fan-out, not a 1-shard degenerate case.
"""
import pytest

from tests.util_subproc import run_with_devices

pytestmark = [pytest.mark.slow, pytest.mark.subproc]

# Shared preamble: deterministic multi-shard store + mutation history.
_PRELUDE = r"""
import numpy as np
from repro.core import JoinSpec
from repro.sparse.datagen import synthetic_sparse
from repro.store import ShardedKNNStore

DIM, NNZ = 1024, 16

def build(algorithm, seed=0, n=160):
    S = synthetic_sparse(n, dim=DIM, nnz_mean=NNZ, seed=seed)
    return ShardedKNNStore.build(
        S, JoinSpec(k=5, algorithm=algorithm, r_block=32, s_block=48))

def mutate_a(store):
    store.add(synthetic_sparse(12, dim=DIM, nnz_mean=NNZ, seed=1),
              ttl=2.0, now=0.0)
    store.add(synthetic_sparse(8, dim=DIM, nnz_mean=NNZ, seed=2))
    store.delete([0, 3, 7])
    store.expire(now=5.0)            # tombstones the TTL batch

R = synthetic_sparse(24, dim=DIM, nnz_mean=NNZ, seed=9)

def assert_parity(ref, got, what):
    assert (np.asarray(ref.ids) == np.asarray(got.ids)).all(), \
        f"{what}: ids diverged"
    assert (np.asarray(ref.scores) == np.asarray(got.scores)).all(), \
        f"{what}: scores diverged"

# The scheduler pads R to its r_block (32 rows); the direct query runs one
# 24-row block.  The two compiled programs may round an f32 sum differently
# in the last ulp (scores here are < 8, one ulp < 1e-6), so served results
# match the direct query within 1e-5, and ids wherever scores are not tied.
SERVE_TOL = 1e-5

def assert_close(ref, got, what):
    from repro.core.reference import topk_agreement
    a = topk_agreement(ref.scores, ref.ids, got.scores, got.ids, SERVE_TOL)
    assert a["max_score_err"] <= SERVE_TOL and a["id_mismatches"] == 0, \
        f"{what}: {a}"
"""


def test_save_load_parity_all_algorithms_and_elastic():
    """Kill-9/warm-restart round trip: load() must reproduce query bits
    (ids AND scores) for bf/iib/iiib after interleaved add/delete/expire,
    with zero query-time index builds — including loaded onto HALF and
    DOUBLE the saved shard count (elastic reshard)."""
    code = _PRELUDE + r"""
import tempfile

for algorithm in ("bf", "iib", "iiib"):
    d = tempfile.mkdtemp(prefix=f"dur_{algorithm}_")
    store = build(algorithm)
    mutate_a(store)
    store.save(d, extra={"tag": algorithm})
    # post-commit mutations + INCREMENTAL save: the loaded state must be
    # the newest commit, not the first one
    store.add(synthetic_sparse(4, dim=DIM, nnz_mean=NNZ, seed=3))
    store.delete([11])
    store.save_dirty(d, extra={"tag": algorithm})
    ref = store.query(R)

    loaded = ShardedKNNStore.load(d)
    assert loaded.loaded_extra == {"tag": algorithm}
    assert loaded.n_shards == store.n_shards
    assert loaded.num_vectors == store.num_vectors
    b0 = loaded.stats.index_builds
    got = loaded.query(R)
    assert loaded.stats.index_builds == b0, "query-time build after load"
    assert_parity(ref, got, f"{algorithm} same-layout load")

    for n_shards in (2, 8):
        if n_shards > 4:
            continue                 # suite runs under 4 virtual devices
        el = ShardedKNNStore.load(d, num_shards=n_shards)
        assert el.n_shards == n_shards
        assert_parity(ref, el.query(R), f"{algorithm} elastic {n_shards}")
    print(algorithm, "OK")
"""
    out = run_with_devices(code, n_devices=4)
    assert out.splitlines()[-3:] == ["bf OK", "iib OK", "iiib OK"]


def test_save_dirty_hard_links_clean_shards():
    """An incremental save re-serializes ONLY the mutated shard; every
    clean shard's leaves are hard links into the previous commit."""
    code = _PRELUDE + r"""
import json, os, tempfile

d = tempfile.mkdtemp(prefix="dur_links_")
store = build("iib")
store.save(d)
# one add dirties exactly one shard (least-loaded; ties -> shard 0)
store.add(synthetic_sparse(2, dim=DIM, nnz_mean=NNZ, seed=3))
store.save_dirty(d)

def manifest(step):
    with open(os.path.join(d, f"step_{step:08d}", "manifest.json")) as f:
        return {e["path"]: e["file"] for e in json.load(f)["leaves"]}

m0, m1 = manifest(0), manifest(1)
linked = relinked = fresh = 0
for path, fname in m1.items():
    ino1 = os.stat(os.path.join(d, "step_00000001", fname)).st_ino
    ino0 = os.stat(os.path.join(d, "step_00000000", m0[path])).st_ino
    if path.startswith("['shard_00000']"):
        assert ino1 != ino0, f"dirty shard leaf {path} was linked, not saved"
        fresh += 1
    else:
        assert ino1 == ino0, f"clean shard leaf {path} was re-serialized"
        linked += 1
assert fresh == 6 and linked == 18      # 4 shards x 6 leaves, 1 dirty

# the incremental commit restores bit-identically
ref = store.query(R)
assert_parity(ref, ShardedKNNStore.load(d).query(R), "incremental load")
print("OK", fresh, linked)
"""
    out = run_with_devices(code, n_devices=4)
    assert "OK 6 18" in out


def test_scheduler_degraded_serving_and_background_recovery():
    """allow_partial policy: a shard loss mid-traffic yields IMMEDIATE
    degraded results flagged with the missing shard set, recovery rebuilds
    the shard from its checkpoint slice behind the traffic, and results
    return to bit-parity.  Zero futures lost throughout."""
    code = _PRELUDE + r"""
import asyncio, tempfile
from repro.runtime.fault import FaultPlan, FaultSpec
from repro.serve import KNNScheduler, ServeConfig

d = tempfile.mkdtemp(prefix="dur_degraded_")
store = build("iib")
store.save(d)
direct = store.query(R)           # full-fan-out reference

async def main():
    cfg = ServeConfig(r_block=32, window_s=0.002, allow_partial=True,
                      recover=lambda: store.recover(d))
    async with KNNScheduler(store, cfg) as sched:
        store.fault_plan = FaultPlan(
            [FaultSpec("shard_error", shard=1, at_dispatch=0)])
        res = await sched.submit(R, k=5)
        assert res.degraded and res.missing_shards == (1,), res.missing_shards
        ids, scores = res             # ServeResult unpacks like the old tuple
        assert ids.shape == (24, 5)
        for _ in range(500):          # background recovery is async; poll
            if not store.lost_shards:
                break
            await asyncio.sleep(0.01)
        assert store.lost_shards == (), "recovery never completed"
        res2 = await sched.submit(R, k=5)
        assert not res2.degraded
        assert_close(direct, type("J", (), {"ids": res2[0],
                                            "scores": res2[1]}),
                     "post-recovery")
        m = sched.metrics
    assert m.failed == 0
    assert m.shard_losses >= 1 and m.degraded >= 1 and m.recoveries == 1
    s = m.summary()["faults"]
    assert s["shard_losses"] >= 1 and s["recoveries"] == 1
    assert s["recovery_s"] > 0

asyncio.run(main())
print("OK")
"""
    out = run_with_devices(code, n_devices=4)
    assert "OK" in out


def test_scheduler_queued_behind_recovery():
    """allow_partial=False + recover hook: a batch that hits a lost shard
    WAITS for the rebuild and re-dispatches — callers only ever see FULL
    results, at the price of latency."""
    code = _PRELUDE + r"""
import asyncio, tempfile
from repro.runtime.fault import FaultPlan, FaultSpec
from repro.serve import KNNScheduler, ServeConfig

d = tempfile.mkdtemp(prefix="dur_queued_")
store = build("iib")
store.save(d)
direct = store.query(R)

async def main():
    cfg = ServeConfig(r_block=32, window_s=0.002, allow_partial=False,
                      recover=lambda: store.recover(d))
    async with KNNScheduler(store, cfg) as sched:
        store.fault_plan = FaultPlan(
            [FaultSpec("shard_error", shard=2, at_dispatch=0)])
        res = await sched.submit(R, k=5)      # resolves only when FULL
        assert res.missing_shards == ()
        assert_close(direct, type("J", (), {"ids": res[0],
                                            "scores": res[1]}),
                     "queued-behind-recovery")
        m = sched.metrics
    assert m.failed == 0 and m.degraded == 0
    assert m.shard_losses >= 1 and m.recoveries == 1
    assert store.lost_shards == ()

asyncio.run(main())
print("OK")
"""
    out = run_with_devices(code, n_devices=4)
    assert "OK" in out


def test_recover_when_only_previous_step_survives():
    """The newest commit is destroyed WHOLESALE (disk loss mid-replication,
    not a detectable corrupt leaf): latest_step must resolve the previous
    valid commit and recover() must rebuild the lost shard from it — the
    shard rolls back to that commit's state, survivors keep their newer
    mutations."""
    code = _PRELUDE + r"""
import os, shutil, tempfile
from repro.checkpoint import ckpt

d = tempfile.mkdtemp(prefix="dur_prevstep_")
store = build("iib")
store.save(d)                        # step 0: the eventual survivor
r0 = store.query(R)
store.add(synthetic_sparse(2, dim=DIM, nnz_mean=NNZ, seed=3))  # -> shard 0
store.save(d)                        # step 1: newest commit
assert ckpt.latest_step(d) == 1
shutil.rmtree(os.path.join(d, "step_00000001"))
assert ckpt.latest_step(d) == 0, "previous step did not survive"

store.mark_lost(0)
assert store.recover(d) == (0,)      # resolves the surviving step
assert store.lost_shards == ()
# shard 0 rolled back past its post-step-0 add; no other shard was
# mutated, so the store is bitwise back at the step-0 state
assert_parity(r0, store.query(R), "recover from previous step")
print("OK")
"""
    out = run_with_devices(code, n_devices=4)
    assert "OK" in out


def test_corrupt_leaf_recovery_falls_back_to_previous_step():
    """A corrupt leaf in the newest commit is DETECTED (sha mismatch) and
    recovery/load fall back to the previous valid step — the recovered
    shard loses its post-checkpoint mutations, nothing else changes."""
    code = _PRELUDE + r"""
import tempfile
from repro.runtime.fault import corrupt_checkpoint_leaf
from repro.store import ShardedKNNStore

d = tempfile.mkdtemp(prefix="dur_corrupt_")
store = build("iib")
store.save(d)                       # step 0: the fallback target
r0 = store.query(R)
store.add(synthetic_sparse(2, dim=DIM, nnz_mean=NNZ, seed=3))  # -> shard 0
store.save(d)                       # step 1 (about to be corrupted)
corrupt_checkpoint_leaf(d)          # newest step, leaf 0 = shard 0's

store.mark_lost(0)
try:
    store.recover(d, step=1)        # pinned at the corrupt commit
    raise SystemExit("corrupt leaf went undetected")
except ValueError as e:
    assert "corrupt checkpoint leaf" in str(e), e
assert store.lost_shards == (0,)    # detection left the store untouched

recovered = store.recover(d)        # resolves latest VALID step -> 0
assert recovered == (0,)
assert store.lost_shards == ()
# shard 0's post-checkpoint add died with it; survivors are untouched,
# so the store is bitwise back at the step-0 state
assert_parity(r0, store.query(R), "recover fallback")

loaded = ShardedKNNStore.load(d)    # full load takes the same fallback
assert_parity(r0, loaded.query(R), "load fallback")
print("OK")
"""
    out = run_with_devices(code, n_devices=4)
    assert "OK" in out
