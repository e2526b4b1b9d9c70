"""Sharded datastore (repro.store.ShardedKNNStore), run in subprocesses
with 4 forced virtual CPU devices: bit-parity with the single-device
engine over concatenated S (all three algorithms, ragged shards), the
O(R-blocks) fan-out dispatch shape with zero query-time index builds,
delete()/TTL tombstones (results change with NO stack rebuild until
compact()), add() balance, and store-level refreeze."""
import pytest

from tests.util_subproc import run_with_devices

pytestmark = [pytest.mark.slow, pytest.mark.subproc]


def test_store_bitwise_parity_and_dispatch_shape():
    """4-shard store == single-device SparseKNNIndex on concatenated S,
    bit for bit, for bf/iib/iiib with ragged shards AND ragged blocks;
    one device dispatch + one host sync per R block; index_builds frozen
    after build."""
    out = run_with_devices("""
import numpy as np
from repro.sparse.datagen import synthetic_sparse
from repro.core.engine import SparseKNNIndex, JoinSpec, JoinStats
from repro.store import ShardedKNNStore

R = synthetic_sparse(45, dim=512, nnz_mean=18, seed=0)
S = synthetic_sparse(131, dim=512, nnz_mean=18, seed=1)   # shards 33/33/33/32
for alg in ['bf', 'iib', 'iiib']:
    spec = JoinSpec(k=5, algorithm=alg, s_block=16, r_block=20)
    single = SparseKNNIndex.build(S, spec).query(R)
    store = ShardedKNNStore.build(S, spec, num_shards=4)
    builds = store.stats.index_builds
    for q in range(2):                      # second query: everything cached
        stats = JoinStats()
        res = store.query(R, stats=stats)
        assert np.array_equal(np.asarray(res.scores), np.asarray(single.scores)), alg
        assert np.array_equal(np.asarray(res.ids), np.asarray(single.ids)), alg
        r_blocks = -(-45 // 20)
        assert stats.device_dispatches == r_blocks, (alg, stats.device_dispatches)
        assert stats.host_syncs == r_blocks, (alg, stats.host_syncs)
    assert store.stats.index_builds == builds, 'query-time index build'
print('STORE_PARITY_OK')
""", n_devices=4)
    assert "STORE_PARITY_OK" in out


def test_store_delete_ttl_tombstones():
    """delete()/TTL expiry change results with NO index rebuild (only the
    valid masks move); parity is held three ways: vs the single-device
    engine with the same tombstones (bitwise), vs a fresh index built
    without the dead rows (id-mapped), and across compact(), which IS the
    real rebuild and keeps store ids stable."""
    out = run_with_devices("""
import numpy as np, jax.numpy as jnp
from repro.sparse.datagen import synthetic_sparse
from repro.sparse.format import SparseBatch
from repro.core.engine import SparseKNNIndex, JoinSpec
from repro.store import ShardedKNNStore

R = synthetic_sparse(30, dim=512, nnz_mean=18, seed=0)
S = synthetic_sparse(131, dim=512, nnz_mean=18, seed=1)
S2 = synthetic_sparse(21, dim=512, nnz_mean=18, seed=7)
idxn = np.asarray(S.indices); valn = np.asarray(S.values); nnzn = np.asarray(S.nnz)
for alg in ['bf', 'iib', 'iiib']:
    spec = JoinSpec(k=5, algorithm=alg, s_block=16, r_block=30)
    store = ShardedKNNStore.build(S, spec, num_shards=4, auto_compact=0.9)
    single = SparseKNNIndex.build(S, spec)
    dead = [0, 5, 40, 66, 99, 130]
    builds = store.stats.index_builds
    assert store.delete(dead) == 6 and single.delete(dead) == 6
    assert store.stats.index_builds == builds, 'delete rebuilt an index'
    a, b = store.query(R), single.query(R)
    assert np.array_equal(np.asarray(a.scores), np.asarray(b.scores)), alg
    assert np.array_equal(np.asarray(a.ids), np.asarray(b.ids)), alg
    # vs an index built WITHOUT the dead rows (ids mapped back; exact for
    # bf/iib, allclose for iiib whose fresh build freezes a different rank)
    keep = np.setdiff1d(np.arange(131), dead)
    Sk = SparseBatch(indices=jnp.asarray(idxn[keep]), values=jnp.asarray(valn[keep]),
                     nnz=jnp.asarray(nnzn[keep]), dim=512)
    c = SparseKNNIndex.build(Sk, spec).query(R)
    ok = np.asarray(c.scores) > -np.inf
    assert np.allclose(np.asarray(a.scores), np.asarray(c.scores)), alg
    assert np.array_equal(np.where(ok, keep[np.asarray(c.ids)], -1),
                          np.where(ok, np.asarray(a.ids), -1)), alg
    # TTL: add with a deadline, expire -> tombstoned, still no rebuild
    store.add(S2, ttl=10.0, now=100.0)
    single.extend(S2, deadline=110.0)
    builds = store.stats.index_builds
    assert store.expire(now=120.0) == 21 and single.expire(120.0) == 21
    assert store.stats.index_builds == builds, 'expire rebuilt an index'
    a, b = store.query(R), single.query(R)
    assert np.array_equal(np.asarray(a.scores), np.asarray(b.scores)), alg
    assert np.array_equal(np.asarray(a.ids), np.asarray(b.ids)), alg
    # compact(): the real rebuild; global ids of survivors stay stable
    assert store.compact() == 27
    assert store.stats.index_builds > builds or alg == 'bf'
    c = store.query(R)
    assert np.allclose(np.asarray(c.scores), np.asarray(a.scores)), alg
    assert np.array_equal(np.asarray(c.ids), np.asarray(a.ids)), alg
print('STORE_TOMBSTONE_OK')
""", n_devices=4)
    assert "STORE_TOMBSTONE_OK" in out


def test_store_add_balance_and_auto_compact():
    """add() lands on the least-loaded shard (stream converges balanced) and
    matches a single-device index built over the same append order; heavy
    delete trips the auto_compact threshold (a real rebuild, observable in
    index_builds + compactions)."""
    out = run_with_devices("""
import numpy as np
from repro.sparse.datagen import synthetic_sparse
from repro.core.engine import SparseKNNIndex, JoinSpec
from repro.store import ShardedKNNStore

R = synthetic_sparse(25, dim=512, nnz_mean=18, seed=0)
S = synthetic_sparse(100, dim=512, nnz_mean=18, seed=1)
spec = JoinSpec(k=5, algorithm='iib', s_block=16, r_block=25)
store = ShardedKNNStore.build(S, spec, num_shards=4, auto_compact=0.3)
single = SparseKNNIndex.build(S, spec)
for seed in (7, 8, 9):
    chunk = synthetic_sparse(12, dim=512, nnz_mean=18, seed=seed)
    gids = store.add(chunk)
    single.extend(chunk)
    assert gids[0] == single.num_vectors - 12
rows = store.shard_rows
assert sum(rows) == 136 and max(rows) - min(rows) <= 12, rows
a, b = store.query(R), single.query(R)
assert np.array_equal(np.asarray(a.scores), np.asarray(b.scores))
assert np.array_equal(np.asarray(a.ids), np.asarray(b.ids))
# chunked adds rebuild only the target shard's tail blocks, and the
# compiled fan-out program survives mutations (geometry keys the jit)
builds = store.stats.index_builds
fn = store._query_fn(25)
c = synthetic_sparse(4, dim=512, nnz_mean=18, seed=10)
store.add(c); single.extend(c)
assert store.stats.index_builds - builds <= 2, 'add() rebuilt the whole shard'
assert store._query_fn(25) is fn, 'mutation dropped the compiled query fn'
# shard 0 holds gids 0..24: killing 13 of them crosses auto_compact=0.3
before = store.stats.compactions
store.delete(np.arange(13))
assert store.stats.compactions > before, 'auto compact did not trigger'
single.delete(np.arange(13))
a, b = store.query(R), single.query(R)
assert np.array_equal(np.asarray(a.scores), np.asarray(b.scores))
assert np.array_equal(np.asarray(a.ids), np.asarray(b.ids))
# a fully-dead shard compacts to the engine's placeholder row and revives
gids0 = store._gids[0].copy()
store.delete(gids0); single.delete(gids0)
store.compact(shards=[0])
assert store.shards[0].n_s == 1 and store.shards[0].live_rows == 0
c = synthetic_sparse(4, dim=512, nnz_mean=18, seed=11)
store.add(c); single.extend(c)
assert store.shards[0].live_rows == 4, store.shard_rows
a, b = store.query(R), single.query(R)
assert np.array_equal(np.asarray(a.scores), np.asarray(b.scores))
assert np.array_equal(np.asarray(a.ids), np.asarray(b.ids))
print('STORE_ADD_OK')
""", n_devices=4)
    assert "STORE_ADD_OK" in out


def test_store_incremental_placement_counters():
    """add() placement is incremental even with replicas=1: while the
    padded stack geometry holds, only the touched shard's slice ships
    host->device (placed_shards +1, a small fraction of the build's
    bytes); tombstones move only the valid mask (no per-shard placement);
    a geometry-growing add falls back to the full re-place.  Parity with
    the single-device engine is held across all three paths."""
    out = run_with_devices("""
import numpy as np
from repro.sparse.datagen import synthetic_sparse
from repro.sparse.format import SparseBatch
from repro.core.engine import SparseKNNIndex, JoinSpec
from repro.store import ShardedKNNStore

R = synthetic_sparse(20, dim=512, nnz_mean=18, seed=0)
S = synthetic_sparse(131, dim=512, nnz_mean=18, seed=1)   # shards 33/33/33/32
spec = JoinSpec(k=5, algorithm='bf', s_block=16, r_block=20)
store = ShardedKNNStore.build(S, spec, num_shards=4)
single = SparseKNNIndex.build(S, spec)
assert store.stats.placed_shards == 4          # the build's full placement
full_bytes = store.stats.placed_bytes

def chunk(lo, hi):                             # sliced from S: same feature
    return SparseBatch(indices=S.indices[lo:hi], values=S.values[lo:hi],
                       nnz=S.nnz[lo:hi], dim=S.dim)   # width, no geometry bump

# geometry-stable add: 4 rows land on shard 3 (32 -> 36 rows, still <= 3
# blocks) -> exactly ONE shard slice ships, far below the full placement
ps0, pb0 = store.stats.placed_shards, store.stats.placed_bytes
store.add(chunk(0, 4)); single.extend(chunk(0, 4))
assert store.stats.placed_shards - ps0 == 1, 'add re-placed untouched shards'
assert (store.stats.placed_bytes - pb0) * 2 < full_bytes
a, b = store.query(R), single.query(R)
assert np.array_equal(np.asarray(a.ids), np.asarray(b.ids))
assert np.array_equal(np.asarray(a.scores), np.asarray(b.scores))

# tombstones: valid-mask-only upload, not a per-shard placement
ps1 = store.stats.placed_shards
store.delete([0]); single.delete([0])
assert store.stats.placed_shards == ps1, 'delete re-placed index stacks'
a, b = store.query(R), single.query(R)
assert np.array_equal(np.asarray(a.ids), np.asarray(b.ids))

# geometry growth (shard 0: 33 -> 49 rows, 3 -> 4 blocks) falls back to
# the full path: every shard re-placed once
ps2 = store.stats.placed_shards
store.add(chunk(4, 20)); single.extend(chunk(4, 20))
assert store.stats.placed_shards - ps2 == 4, 'geometry change must re-place all'
a, b = store.query(R), single.query(R)
assert np.array_equal(np.asarray(a.ids), np.asarray(b.ids))
assert np.array_equal(np.asarray(a.scores), np.asarray(b.scores))
print('STORE_PLACEMENT_OK')
""", n_devices=4)
    assert "STORE_PLACEMENT_OK" in out


def test_store_refreeze_matches_and_multi_axis_mesh():
    """Store-level refreeze (global live-row rank) keeps results identical;
    the store also runs over a named axis of a larger existing mesh (the
    ring join's configuration)."""
    out = run_with_devices("""
import numpy as np
from repro.launch.mesh import make_mesh
from repro.sparse.datagen import synthetic_sparse
from repro.core.engine import JoinSpec
from repro.store import ShardedKNNStore

R = synthetic_sparse(20, dim=512, nnz_mean=18, seed=0)
S = synthetic_sparse(90, dim=512, nnz_mean=18, seed=1)
spec = JoinSpec(k=5, algorithm='iiib', s_block=16, r_block=20)
mesh = make_mesh((2, 2), ('data', 'model'))
store = ShardedKNNStore.build(S, spec, mesh=mesh, axes=('data',))
assert store.n_shards == 2
r1 = store.query(R)
store.delete([3, 50])
store.add(synthetic_sparse(15, dim=512, nnz_mean=18, seed=9))
r2 = store.query(R)
store.refreeze()
r3 = store.query(R)
assert np.allclose(np.asarray(r2.scores), np.asarray(r3.scores))
ok = np.asarray(r2.scores) > -np.inf
assert np.array_equal(np.where(ok, np.asarray(r2.ids), -1),
                      np.where(ok, np.asarray(r3.ids), -1))
print('STORE_REFREEZE_OK')
""", n_devices=4)
    assert "STORE_REFREEZE_OK" in out


def test_traced_ring_join_lowers_via_legacy_ring():
    """jit-tracing ring_knn_join (the dry-run's shape) must still lower:
    the store's host-driven build can't trace, so distributed_join falls
    back to the fully-traceable ppermute ring for abstract inputs."""
    out = run_with_devices("""
import jax, jax.numpy as jnp
from repro.launch.mesh import make_mesh
from repro.core.ring import ring_knn_join
from repro.sparse.format import SparseBatch

mesh = make_mesh((4,), ('data',))
nr, ns, f, dim = 32, 64, 16, 512

def job(Ri, Rv, Rn, Si, Sv, Sn):
    R = SparseBatch(indices=Ri, values=Rv, nnz=Rn, dim=dim)
    S = SparseBatch(indices=Si, values=Sv, nnz=Sn, dim=dim)
    st = ring_knn_join(R, S, 5, mesh, algorithm='iiib', ring_axes=('data',))
    return st.scores, st.ids

args = (jax.ShapeDtypeStruct((nr, f), jnp.int32),
        jax.ShapeDtypeStruct((nr, f), jnp.float32),
        jax.ShapeDtypeStruct((nr,), jnp.int32),
        jax.ShapeDtypeStruct((ns, f), jnp.int32),
        jax.ShapeDtypeStruct((ns, f), jnp.float32),
        jax.ShapeDtypeStruct((ns,), jnp.int32))
with mesh:
    compiled = jax.jit(job).lower(*args).compile()
assert compiled is not None
print('TRACED_RING_OK')
""", n_devices=4)
    assert "TRACED_RING_OK" in out
