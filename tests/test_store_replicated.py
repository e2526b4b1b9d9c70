"""Replicated sharded store (DESIGN.md §10), run in subprocesses with 4
forced virtual CPU devices (2 replicas x 2 shards): bit-parity with the
single-device engine through replica routing, round-robin read scaling
with the O(R-blocks) dispatch shape, in-batch failover on replica loss
(FULL results, no degraded flag), write-through + per-replica dirty
tracking for dead replicas, anti-entropy resync with half-open probe
re-admission, and the scheduler serving zero-degraded through a replica
kill with background resync."""
import pytest

from tests.util_subproc import run_with_devices

pytestmark = [pytest.mark.slow, pytest.mark.subproc]

_PRELUDE = r"""
import numpy as np
from repro.core.engine import SparseKNNIndex, JoinSpec, JoinStats
from repro.store.mesh import make_store_mesh
from repro.runtime.fault import FaultPlan, FaultSpec, ReplicaHealth
from repro.sparse.datagen import synthetic_sparse
from repro.store import ShardedKNNStore

DIM, NNZ = 512, 16
R = synthetic_sparse(45, dim=DIM, nnz_mean=NNZ, seed=0)
S = synthetic_sparse(131, dim=DIM, nnz_mean=NNZ, seed=1)

def assert_parity(ref, got, what):
    assert (np.asarray(ref.ids) == np.asarray(got.ids)).all(), \
        f"{what}: ids diverged"
    assert (np.asarray(ref.scores) == np.asarray(got.scores)).all(), \
        f"{what}: scores diverged"
"""


def test_replicated_parity_dispatch_shape_and_round_robin():
    """A replicas=2 store must be invisible to callers: bit-identical to
    the single-device build for every algorithm, the same one-dispatch-
    one-sync-per-R-block shape as unreplicated (no cross-replica
    collective), zero query-time index builds — while the router actually
    spreads consecutive queries across both replicas."""
    code = _PRELUDE + r"""
for alg in ('bf', 'iib', 'iiib'):
    spec = JoinSpec(k=5, algorithm=alg, s_block=16, r_block=20)
    single = SparseKNNIndex.build(S, spec).query(R)
    store = ShardedKNNStore.build(S, spec, mesh=make_store_mesh(2, replicas=2))
    assert store.n_replicas == 2 and store.n_shards == 2
    builds = store.stats.index_builds
    for q in range(2):
        stats = JoinStats()
        res = store.query(R, stats=stats)
        assert_parity(single, res, f'{alg} replicated q{q}')
        r_blocks = -(-45 // 20)
        assert stats.device_dispatches == r_blocks, (alg, stats.device_dispatches)
        assert stats.host_syncs == r_blocks, (alg, stats.host_syncs)
    assert store.stats.index_builds == builds, 'query-time index build'
    # round-robin: both replicas served some of the 2x3 blocks
    assert set(store.stats.replica_dispatches) == {0, 1}, \
        store.stats.replica_dispatches
    assert store.stats.replica_failovers == 0
    print(alg, 'OK')
"""
    out = run_with_devices(code, n_devices=4)
    assert out.splitlines()[-3:] == ["bf OK", "iib OK", "iiib OK"]


def test_failover_mutation_while_dead_and_resync():
    """A replica kill mid-query fails over WITHIN the batch (FULL result,
    no missing shards, failovers counted); mutations write through to the
    survivor and queue dirty shards for the dead replica; resync repairs
    it from the host mirror, re-admits it half-open, a probe success
    returns it to rotation, and verify_replicas() asserts bit-parity.
    Single-shard-copy losses below the health threshold keep the replica
    routable and resync without a health transition."""
    code = _PRELUDE + r"""
spec = JoinSpec(k=5, algorithm='iib', s_block=16, r_block=45)
store = ShardedKNNStore.build(S, spec, mesh=make_store_mesh(2, replicas=2))
single = SparseKNNIndex.build(S, spec)
ref = single.query(R)
assert_parity(ref, store.query(R), 'warm')

# whole-replica kill: ReplicaLostError -> mark dead, retry on survivor
store.fault_plan = FaultPlan([FaultSpec('replica_error', replica=1)])
res = store.query(R)
store.fault_plan = None
assert_parity(ref, res, 'through replica kill')
assert res.missing_shards == (), 'failover must not degrade'
assert store.stats.replica_failovers == 1
assert store.dead_replicas == (1,)
assert store.lost_shards == (), 'replica loss is not data loss'
assert store.needs_resync

# mutations while dead: write-through hits the survivor only; the dead
# replica accrues dirty shards for resync to replay
gids = store.add(synthetic_sparse(10, dim=DIM, nnz_mean=NNZ, seed=2))
single.extend(synthetic_sparse(10, dim=DIM, nnz_mean=NNZ, seed=2))
store.delete([3, 40]); single.delete([3, 40])
ref2 = single.query(R)
assert_parity(ref2, store.query(R), 'mutated while replica dead')
assert store._replica_dirty[1], 'dead replica missed writes untracked'

# anti-entropy resync: host mirror -> device, half-open re-admission
assert store.resync_replicas() == (1,)
assert store.health.state(1) == ReplicaHealth.HALF_OPEN
assert store.verify_replicas()
assert_parity(ref2, store.query(R), 'probe query')   # probe routed first
assert store.health.state(1) == ReplicaHealth.LIVE
assert not store.needs_resync
assert store.resync_replicas() == ()                 # converged: no-op

# shard-copy loss below the fail threshold (default 2): the dispatch
# fails over in-batch, the replica stays routable, resync repairs
d0 = store.stats.replica_dispatches.copy()
store.fault_plan = FaultPlan([FaultSpec('shard_error', shard=0, at_dispatch=0)])
res = store.query(R)
store.fault_plan = None
assert_parity(ref2, res, 'through shard-copy loss')
assert res.missing_shards == ()
assert store.stats.replica_failovers == 2
assert store.dead_replicas == () and store.lost_shards == ()
assert store.needs_resync
hit = [r for r in (0, 1)
       if store.stats.replica_dispatches.get(r, 0) > d0.get(r, 0)]
assert len(hit) == 2, 'failover should have used both replicas'
store.resync_replicas()
assert store.verify_replicas() and not store.needs_resync
assert_parity(ref2, store.query(R), 'after shard-copy resync')
print('FAILOVER_RESYNC_OK')
"""
    out = run_with_devices(code, n_devices=4)
    assert "FAILOVER_RESYNC_OK" in out


def test_replicated_load_and_unreplicated_loss_semantics():
    """Checkpoints hold ONE logical copy: a save from an unreplicated
    store loads onto a replicated mesh (fan-out on load) bit-identically,
    and vice versa.  With replicas=1 the PR 7 semantics are unchanged:
    a lost shard is data loss (lost_shards reports it, queries raise
    without allow_partial, needs_resync stays False — recover() is the
    only repair)."""
    code = _PRELUDE + r"""
import tempfile
from repro.runtime.fault import ShardLostError

spec = JoinSpec(k=5, algorithm='iib', s_block=16, r_block=45)
store = ShardedKNNStore.build(S, spec, num_shards=2)
store.add(synthetic_sparse(10, dim=DIM, nnz_mean=NNZ, seed=2))
store.delete([3, 40])
ref = store.query(R)

d = tempfile.mkdtemp(prefix='rep_ckpt_')
store.save(d)
rep = ShardedKNNStore.load(d, replicas=2)
assert rep.n_replicas == 2 and rep.n_shards == 2
assert_parity(ref, rep.query(R), 'unreplicated save -> replicated load')
rep.delete([41])
d2 = tempfile.mkdtemp(prefix='rep_ckpt2_')
rep.save(d2)
back = ShardedKNNStore.load(d2, num_shards=2)
assert back.n_replicas == 1
assert_parity(rep.query(R), back.query(R), 'replicated save -> flat load')

# unreplicated loss semantics are byte-for-byte PR 7
flat = ShardedKNNStore.load(d, num_shards=2)
flat.mark_lost(0)
assert flat.lost_shards == (0,)
assert not flat.needs_resync, 'one copy: nothing to resync from'
assert flat.resync_replicas() == ()
try:
    flat.query(R)
    raise AssertionError('lost shard must raise without allow_partial')
except ShardLostError as e:
    assert e.shard == 0
degraded = flat.query(R, allow_partial=True)
assert degraded.missing_shards == (0,)
assert flat.recover(d) == (0,)
assert_parity(ref, flat.query(R), 'after recover')
print('REPLICATED_LOAD_OK')
"""
    out = run_with_devices(code, n_devices=4)
    assert "REPLICATED_LOAD_OK" in out


def test_scheduler_full_service_through_replica_kill():
    """The serving acceptance bar: under continuous traffic with a replica
    killed mid-load, EVERY future completes FULL (zero degraded, zero
    failed — allow_partial stays off), failover and the background
    anti-entropy resync both land, the metrics faults section records
    them, and the repaired replica is back in rotation at bit-parity."""
    code = _PRELUDE + r"""
import asyncio
from repro.serve import KNNScheduler, ServeConfig

spec = JoinSpec(k=5, algorithm='iib', s_block=16, r_block=8)
store = ShardedKNNStore.build(S, spec, mesh=make_store_mesh(2, replicas=2))
single = SparseKNNIndex.build(S, spec)

def rows_of(lo, hi):
    from repro.sparse.format import SparseBatch
    return SparseBatch(indices=R.indices[lo:hi], values=R.values[lo:hi],
                       nnz=R.nnz[lo:hi], dim=R.dim)

async def main():
    cfg = ServeConfig(r_block=8, window_s=0.001,
                      resync=lambda: store.resync_replicas())
    async with KNNScheduler(store, cfg) as sched:
        # warm both replicas' compiled programs, then arm the kill
        await sched.submit(rows_of(0, 4)); await sched.submit(rows_of(0, 4))
        store.fault_plan = FaultPlan([FaultSpec('replica_error', replica=1)])
        outs = []
        for i in range(12):
            lo = (3 * i) % 36
            outs.append(await sched.submit(rows_of(lo, lo + 3)))
            await asyncio.sleep(0.002)
        store.fault_plan = None
        m = sched.metrics
        assert all(not o.degraded for o in outs), 'degraded result leaked'
        assert m.failed == 0 and m.degraded == 0
        faults = m.summary()['faults']
        assert faults['replica_failovers'] >= 1, faults
        # de-interleaved parity through the failover window
        for i, (ids, scores) in enumerate(outs):
            lo = (3 * i) % 36
            direct = single.query(rows_of(lo, lo + 3))
            assert (ids == np.asarray(direct.ids)).all(), i
            assert (scores == np.asarray(direct.scores)).all(), i
    # stop() awaited the background resync task
    faults = sched.metrics.summary()['faults']
    assert faults['resyncs'] >= 1, faults
    assert faults['resync_s'] > 0
    assert set(faults['replica_dispatches']) >= {'0'}

asyncio.run(main())
assert store.verify_replicas()
assert not store.needs_resync and store.dead_replicas == ()
# the resynced replica takes a probe and rejoins the rotation
d0 = store.stats.replica_dispatches.copy()
store.query(R); store.query(R)
assert store.stats.replica_dispatches.get(1, 0) > d0.get(1, 0)
print('SCHED_REPLICA_OK')
"""
    out = run_with_devices(code, n_devices=4)
    assert "SCHED_REPLICA_OK" in out
