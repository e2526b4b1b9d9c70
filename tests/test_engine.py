"""Build-once/query-many engine (core/engine.py): parity with the legacy
one-shot join and the dense oracle, index-reuse accounting, extend()
equivalence, and C2/C3 planner sanity."""
import numpy as np
import pytest

from repro.core.blocknl import knn_join
from repro.core.engine import (
    PAIR_BUDGET,
    JoinSpec,
    JoinStats,
    SparseKNNIndex,
    plan,
)
from repro.core.reference import oracle_knn
from repro.sparse.datagen import synthetic_sparse
from repro.sparse.format import SparseBatch, densify


def _rows(sb: SparseBatch, lo: int, hi: int) -> SparseBatch:
    return SparseBatch(
        indices=sb.indices[lo:hi], values=sb.values[lo:hi], nnz=sb.nnz[lo:hi], dim=sb.dim
    )


def _check_oracle(scores, osc):
    pos = osc > 0
    np.testing.assert_allclose(
        np.where(pos, scores, 0.0), np.where(pos, osc, 0.0), atol=1e-4
    )


@pytest.mark.parametrize("algorithm", ["bf", "iib", "iiib"])
def test_engine_matches_legacy_and_oracle(small_rs, algorithm):
    """engine.query == legacy knn_join (identical arrays) == dense oracle."""
    R, S = small_rs
    spec = JoinSpec(k=5, algorithm=algorithm, r_block=24, s_block=32)
    res = SparseKNNIndex.build(S, spec).query(R)
    legacy = knn_join(R, S, 5, algorithm=algorithm, r_block=24, s_block=32)
    np.testing.assert_array_equal(np.asarray(res.scores), np.asarray(legacy.scores))
    np.testing.assert_array_equal(np.asarray(res.ids), np.asarray(legacy.ids))
    osc, _ = oracle_knn(np.asarray(densify(R)), np.asarray(densify(S)), 5)
    _check_oracle(np.asarray(res.scores), osc)


@pytest.mark.parametrize("algorithm", ["iib", "iiib"])
def test_engine_ragged_s_blocks(small_rs, algorithm):
    """n_s not divisible by s_block: the padded final block must stay exact."""
    R, S = small_rs  # n_s = 80; 80 = 2*33 + 14
    spec = JoinSpec(k=5, algorithm=algorithm, r_block=20, s_block=33)
    res = SparseKNNIndex.build(S, spec).query(R)
    osc, _ = oracle_knn(np.asarray(densify(R)), np.asarray(densify(S)), 5)
    _check_oracle(np.asarray(res.scores), osc)


def test_iib_index_built_once_across_queries(small_rs):
    """Two query() calls on one index build each S-block index exactly once."""
    R, S = small_rs
    spec = JoinSpec(k=5, algorithm="iib", r_block=24, s_block=32)
    index = SparseKNNIndex.build(S, spec)
    assert index.num_blocks == 3
    assert index.stats.index_builds == index.num_blocks  # built at build() time
    q1, q2 = JoinStats(), JoinStats()
    r1 = index.query(R, stats=q1)
    r2 = index.query(_rows(R, 0, 24), stats=q2)
    assert q1.index_builds == 0 and q2.index_builds == 0
    assert index.stats.index_builds == index.num_blocks  # NOT queries x blocks
    assert q1.query_wall_s > 0 and index.stats.build_wall_s > 0
    # both queries exact
    osc, _ = oracle_knn(np.asarray(densify(R)), np.asarray(densify(S)), 5)
    _check_oracle(np.asarray(r1.scores), osc)
    _check_oracle(np.asarray(r2.scores), osc[:24])


def test_iiib_superset_built_once(small_rs):
    """The IIIB superset index is threshold-independent: built once per S
    block at build() time, and NO query ever rebuilds it (the refinement is
    an on-device mask)."""
    R, S = small_rs
    spec = JoinSpec(k=5, algorithm="iiib", r_block=24, s_block=32)
    index = SparseKNNIndex.build(S, spec)
    assert index.stats.index_builds == index.num_blocks  # built up front
    q1, q2 = JoinStats(), JoinStats()
    index.query(R, stats=q1)
    index.query(R, stats=q2)
    assert q1.index_builds == 0 and q2.index_builds == 0
    assert index.stats.index_builds == index.num_blocks  # independent of queries
    # streaming mode keeps the legacy per-pair profile (the parity reference)
    stream = JoinStats()
    SparseKNNIndex.build(S, spec, cache_device_blocks=False).query(R, stats=stream)
    assert stream.index_builds == 2 * 3  # ceil(48/24) r-blocks x 3 s-blocks


def test_extend_matches_concatenated_build(small_rs):
    R, S = small_rs
    spec = JoinSpec(k=5, algorithm="iib", r_block=24, s_block=32)
    grown = SparseKNNIndex.build(_rows(S, 0, 50), spec).extend(_rows(S, 50, 80))
    full = SparseKNNIndex.build(S, spec)
    ra, rb = grown.query(R), full.query(R)
    np.testing.assert_array_equal(np.asarray(ra.scores), np.asarray(rb.scores))
    np.testing.assert_array_equal(np.asarray(ra.ids), np.asarray(rb.ids))
    assert grown.num_vectors == 80 and grown.num_blocks == full.num_blocks


def test_extend_unifies_feature_width(small_rs):
    """Extending with a batch of different max_features must stay exact."""
    R, S = small_rs
    extra = synthetic_sparse(24, dim=512, nnz_mean=35, nnz_std=5, seed=9)
    assert extra.max_features != S.max_features
    spec = JoinSpec(k=5, algorithm="iiib", r_block=24, s_block=32)
    res = SparseKNNIndex.build(S, spec).extend(extra).query(R)
    dense_s = np.concatenate([np.asarray(densify(S)), np.asarray(densify(extra))])
    osc, _ = oracle_knn(np.asarray(densify(R)), dense_s, 5)
    _check_oracle(np.asarray(res.scores), osc)


def test_extend_rebuilds_only_tail_blocks(small_rs):
    _, S = small_rs
    spec = JoinSpec(k=5, algorithm="iib", s_block=32)
    index = SparseKNNIndex.build(_rows(S, 0, 64), spec)  # 2 full blocks
    assert index.stats.index_builds == 2
    index.extend(_rows(S, 64, 80))  # old tail was block-aligned: 1 new block
    assert index.stats.index_builds == 3
    index.extend(synthetic_sparse(8, dim=512, nnz_mean=20, seed=3))
    # 80 % 32 = 16: the partial block 2 is rebuilt, no new block started
    assert index.num_blocks == 3 and index.stats.index_builds == 4


def test_warm_start_via_engine(small_rs):
    R, S = small_rs
    spec = JoinSpec(k=5, algorithm="iiib", r_block=24, s_block=20, warm_start=0.1)
    res = SparseKNNIndex.build(S, spec).query(R)
    osc, _ = oracle_knn(np.asarray(densify(R)), np.asarray(densify(S)), 5)
    _check_oracle(np.asarray(res.scores), osc)


@pytest.mark.parametrize("algorithm", ["bf", "iib", "iiib"])
def test_scanned_driver_matches_per_pair_loop(small_rs, algorithm):
    """Cached (scanned/fused) driver vs the streaming per-pair loop:
    identical arrays, and the cached BF/IIB paths dispatch once per R block
    with no per-pair host syncs (the only sync is the result pull)."""
    R, S = small_rs
    spec = JoinSpec(k=5, algorithm=algorithm, r_block=24, s_block=32)
    scanned, legacy = JoinStats(), JoinStats()
    res = SparseKNNIndex.build(S, spec).query(R, stats=scanned)
    res_stream = SparseKNNIndex.build(S, spec, cache_device_blocks=False).query(
        R, stats=legacy
    )
    np.testing.assert_array_equal(np.asarray(res.scores), np.asarray(res_stream.scores))
    np.testing.assert_array_equal(np.asarray(res.ids), np.asarray(res_stream.ids))
    r_blocks, s_blocks = 2, 3
    assert scanned.device_dispatches == r_blocks              # one scan per R block
    assert scanned.host_syncs == r_blocks                     # result pulls only
    assert legacy.device_dispatches >= r_blocks * s_blocks
    if algorithm == "iiib":
        # same pruned-work accounting in both drivers
        assert scanned.list_entries == legacy.list_entries


def test_fused_kernel_engine_matches_streaming(small_rs):
    """use_kernel cached mode: ONE fused knn_topk dispatch per R block,
    bit-identical to the streaming per-pair kernel path."""
    R, S = small_rs
    spec = JoinSpec(k=5, algorithm="iib", r_block=24, s_block=32, use_kernel=True)
    stats = JoinStats()
    res = SparseKNNIndex.build(S, spec).query(R, stats=stats)
    legacy = knn_join(R, S, 5, algorithm="iib", r_block=24, s_block=32, use_kernel=True)
    np.testing.assert_array_equal(np.asarray(res.scores), np.asarray(legacy.scores))
    np.testing.assert_array_equal(np.asarray(res.ids), np.asarray(legacy.ids))
    assert stats.device_dispatches == 2                       # == r_blocks


def test_fused_kernel_interprets_only_on_cpu(small_rs, monkeypatch):
    """The kernel path lowers the call query() makes: interpreted on the CPU
    backend (no Mosaic custom call), refused on a backend with no kernel
    path; an index without the kernel path has nothing to lower."""
    import jax

    R, S = small_rs
    spec = JoinSpec(k=5, algorithm="iib", r_block=24, s_block=32, use_kernel=True)
    index = SparseKNNIndex.build(S, spec)
    assert "tpu_custom_call" not in index.lowered_kernel(R).as_text()
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="use_kernel needs a TPU"):
        index.query(R)
    monkeypatch.undo()
    plain = SparseKNNIndex.build(S, JoinSpec(k=5, algorithm="iib", r_block=24, s_block=32))
    with pytest.raises(ValueError, match="no fused-kernel query path"):
        plain.lowered_kernel(R)


def test_warm_start_seed_varies_sample(small_rs):
    """JoinSpec.seed varies the warm-start sample across a stream; every
    seed stays exact."""
    R, S = small_rs
    osc, _ = oracle_knn(np.asarray(densify(R)), np.asarray(densify(S)), 5)
    traces = []
    for seed in (0, 7):
        spec = JoinSpec(k=5, algorithm="iiib", r_block=24, s_block=20,
                        warm_start=0.2, seed=seed)
        stats = JoinStats()
        res = SparseKNNIndex.build(S, spec).query(R, stats=stats)
        _check_oracle(np.asarray(res.scores), osc)
        # the sample seeds MinPruneScore on device: live from the first block
        assert all(t[0] > -np.inf for t in stats.min_prune_trace)
        traces.append(np.concatenate(stats.min_prune_trace))
    # different samples -> different threshold evolutions
    assert not np.array_equal(traces[0], traces[1])


def test_iiib_threshold_monotone_in_carry(small_rs):
    """The MinPruneScore carried through the scan only ever rises — the
    invariant that makes masking a sound replacement for rebuilding (masked
    sets only grow, so no entry is ever wrongly skipped)."""
    R, S = small_rs
    for ws in (0.0, 0.2):
        spec = JoinSpec(k=5, algorithm="iiib", r_block=24, s_block=20,
                        warm_start=ws)
        stats = JoinStats()
        SparseKNNIndex.build(S, spec).query(R, stats=stats)
        assert len(stats.min_prune_trace) == 2            # one per R block
        for trace in stats.min_prune_trace:
            assert trace.shape == (5,)                    # seed + 4 S blocks
            assert np.all(np.diff(trace) >= 0)
            assert trace[-1] > -np.inf


def test_iiib_threshold_live_on_ragged_r_block(small_rs):
    """A partial final R block must not pin the threshold at -inf: its
    padding rows never accrue candidates, so they are excluded from the
    MinPruneScore reduce (results exact either way — this is a work bug,
    caught only by the trace)."""
    R, S = small_rs   # n_r = 48; r_block=20 -> blocks of 20/20/8
    spec = JoinSpec(k=5, algorithm="iiib", r_block=20, s_block=32)
    stats = JoinStats()
    res = SparseKNNIndex.build(S, spec).query(R, stats=stats)
    assert len(stats.min_prune_trace) == 3
    for trace in stats.min_prune_trace:
        assert trace[-1] > -np.inf                        # incl. the ragged block
    # and still bit-identical to streaming
    stream = SparseKNNIndex.build(S, spec, cache_device_blocks=False).query(R)
    np.testing.assert_array_equal(np.asarray(res.scores), np.asarray(stream.scores))
    np.testing.assert_array_equal(np.asarray(res.ids), np.asarray(stream.ids))


def test_iiib_dispatch_shape_stream(small_rs):
    """A 3-query IIIB stream stays within queries x r_blocks scan dispatches
    and r_blocks host syncs per query (result pulls only) — the acceptance
    shape that PR 2 only achieved for BF/IIB."""
    R, S = small_rs
    spec = JoinSpec(k=5, algorithm="iiib", r_block=24, s_block=32)
    index = SparseKNNIndex.build(S, spec)
    queries, r_blocks = 3, 2
    total_dispatches = 0
    for _ in range(queries):
        stats = JoinStats()
        index.query(R, stats=stats)
        total_dispatches += stats.device_dispatches
        assert stats.host_syncs <= r_blocks
    assert total_dispatches <= queries * r_blocks
    assert index.stats.index_builds == index.num_blocks   # not per query


def test_iiib_mask_prunes_entries():
    """On paper-shaped data (high dim, sparse rows) the threshold mask must
    actually shrink the scored lists below the superset total, and a warm
    start may only shrink them further."""
    R = synthetic_sparse(64, dim=4096, nnz_mean=24, nnz_std=6, seed=0)
    S = synthetic_sparse(256, dim=4096, nnz_mean=24, nnz_std=6, seed=1)
    kept = {}
    for ws in (0.0, 0.25):
        spec = JoinSpec(k=3, algorithm="iiib", r_block=64, s_block=64,
                        warm_start=ws)
        index = SparseKNNIndex.build(S, spec)
        stats = JoinStats()
        res = index.query(R, stats=stats)
        superset_total = sum(b.list_total for b in index._blocks)
        assert stats.list_entries < superset_total
        kept[ws] = stats.list_entries
        osc, _ = oracle_knn(np.asarray(densify(R)), np.asarray(densify(S)), 3)
        _check_oracle(np.asarray(res.scores), osc)
    assert kept[0.25] <= kept[0.0]


def test_iiib_extend_reassembles_stacks(small_rs):
    """extend() on IIIB: retained superset-stack prefix is padded, never
    rebuilt (index_builds counts tail blocks only), and the grown index
    stays exact.  (Bit-equality with a from-scratch build is NOT expected:
    the superset ordering is frozen at build time by design, while a fresh
    build ranks with the full datastore's frequencies.)"""
    R, S = small_rs
    spec = JoinSpec(k=5, algorithm="iiib", r_block=24, s_block=32)
    grown = SparseKNNIndex.build(_rows(S, 0, 64), spec)   # 2 full blocks
    assert grown.stats.index_builds == 2
    grown.extend(_rows(S, 64, 80))                        # aligned tail: 1 new block
    assert grown.stats.index_builds == 3                  # tail only, prefix padded
    res = grown.query(R)
    osc, _ = oracle_knn(np.asarray(densify(R)), np.asarray(densify(S)), 5)
    _check_oracle(np.asarray(res.scores), osc)
    # the frozen rank also keeps cached and streaming modes in lockstep
    stream = SparseKNNIndex.build(_rows(S, 0, 64), spec, cache_device_blocks=False)
    stream.extend(_rows(S, 64, 80))
    rs = stream.query(R)
    np.testing.assert_array_equal(np.asarray(res.scores), np.asarray(rs.scores))
    np.testing.assert_array_equal(np.asarray(res.ids), np.asarray(rs.ids))


@pytest.mark.parametrize("algorithm,use_kernel", [("bf", False), ("iib", True)])
def test_extend_reuses_device_stacks(small_rs, algorithm, use_kernel):
    """extend() reassembles the BF/kernel device stacks by concatenating the
    retained prefix — query results match a from-scratch build exactly."""
    R, S = small_rs
    spec = JoinSpec(k=5, algorithm=algorithm, r_block=24, s_block=32,
                    use_kernel=use_kernel)
    grown = SparseKNNIndex.build(_rows(S, 0, 64), spec).extend(_rows(S, 64, 80))
    full = SparseKNNIndex.build(S, spec)
    ra, rb = grown.query(R), full.query(R)
    np.testing.assert_array_equal(np.asarray(ra.scores), np.asarray(rb.scores))
    np.testing.assert_array_equal(np.asarray(ra.ids), np.asarray(rb.ids))


def test_planner_cost_model_ordering():
    """Planner choices track the C2/C3 estimates and respect block bounds."""
    spec = JoinSpec(k=5)
    sparse = plan((1000, 8, 10_000), (1000, 8, 10_000), spec)
    assert sparse.cost_iib < sparse.cost_bf
    # no per-pair rebuild charge: the superset index is built once at build()
    # and masking can only shrink the scored mass
    assert sparse.cost_iiib <= sparse.cost_iib
    assert sparse.algorithm == "iiib"  # indexed side wins → threshold-refined
    dense = plan((1000, 5000, 10_000), (1000, 5000, 10_000), spec)
    assert dense.cost_bf <= dense.cost_iib
    assert dense.algorithm == "bf"
    for p in (sparse, dense):
        assert 1 <= p.r_block <= 1000 and 1 <= p.s_block <= 1000
        assert p.r_block * p.s_block <= PAIR_BUDGET
    # explicit spec fields pass through unchanged
    pinned = plan(
        (1000, 8, 10_000), (1000, 8, 10_000),
        JoinSpec(k=5, algorithm="bf", r_block=64, s_block=96),
    )
    assert (pinned.algorithm, pinned.r_block, pinned.s_block) == ("bf", 64, 96)
    # a narrower occupied-tile universe can only shrink the C3 estimate
    narrowed = plan((1000, 8, 10_000), (1000, 8, 10_000), spec, occupied_tiles=10)
    assert narrowed.cost_iib <= sparse.cost_iib


def test_planner_resolves_unset_spec_fields(small_rs):
    """With algorithm/blocks unset, build+query still runs and stays exact."""
    R, S = small_rs
    index = SparseKNNIndex.build(S, JoinSpec(k=5))
    p = index.plan_for(R)
    assert p.algorithm == index.algorithm
    res = index.query(R)
    osc, _ = oracle_knn(np.asarray(densify(R)), np.asarray(densify(S)), 5)
    _check_oracle(np.asarray(res.scores), osc)


def test_dim_mismatch_rejected(small_rs):
    _, S = small_rs
    index = SparseKNNIndex.build(S, JoinSpec(k=5, algorithm="bf"))
    bad = synthetic_sparse(4, dim=256, nnz_mean=10, seed=0)
    with pytest.raises(ValueError):
        index.query(bad)
    with pytest.raises(ValueError):
        index.extend(bad)


# ---------------------------------------------------------------------------
# tombstones (delete / TTL), refreeze, planner calibration
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("algorithm", ["bf", "iib", "iiib"])
def test_delete_matches_index_without_rows(small_rs, algorithm):
    """delete() excludes rows with NO index rebuild; results match an index
    built without them (id-mapped), in both cached and streaming modes."""
    R, S = small_rs
    spec = JoinSpec(k=5, algorithm=algorithm, r_block=24, s_block=32)
    dead = [0, 7, 33, 79]
    keep = np.setdiff1d(np.arange(S.num_vectors), dead)

    index = SparseKNNIndex.build(S, spec)
    builds = index.stats.index_builds
    assert index.delete([dead[0]] * 3) == 1  # duplicates counted once
    assert index.delete(dead) == 3
    assert index.delete(dead) == 0          # idempotent
    assert index.stats.index_builds == builds, "delete rebuilt an index"
    assert (index.live_rows, index.dead_rows) == (76, 4)
    res = index.query(R)

    streaming = SparseKNNIndex.build(S, spec, cache_device_blocks=False)
    streaming.delete(dead)
    res_s = streaming.query(R)
    np.testing.assert_array_equal(np.asarray(res.scores), np.asarray(res_s.scores))
    np.testing.assert_array_equal(np.asarray(res.ids), np.asarray(res_s.ids))

    fresh = SparseKNNIndex.build(_rows_subset(S, keep), spec).query(R)
    ok = np.asarray(fresh.scores) > -np.inf
    np.testing.assert_allclose(
        np.asarray(res.scores), np.asarray(fresh.scores), atol=1e-5
    )
    np.testing.assert_array_equal(
        np.where(ok, keep[np.asarray(fresh.ids)], -1),
        np.where(ok, np.asarray(res.ids), -1),
    )
    # compact(): the real rebuild — ids shift to the fresh index's positions
    assert index.compact() == 4
    res_c = index.query(R)
    np.testing.assert_allclose(
        np.asarray(res_c.scores), np.asarray(fresh.scores), atol=1e-5
    )
    np.testing.assert_array_equal(np.asarray(res_c.ids), np.asarray(fresh.ids))


def _rows_subset(sb: SparseBatch, rows) -> SparseBatch:
    import jax.numpy as jnp

    return SparseBatch(
        indices=jnp.asarray(np.asarray(sb.indices)[rows]),
        values=jnp.asarray(np.asarray(sb.values)[rows]),
        nnz=jnp.asarray(np.asarray(sb.nnz)[rows]),
        dim=sb.dim,
    )


def test_ttl_expiry_and_warm_start_skip_dead(small_rs):
    """extend(deadline=) rows vanish after expire(now); the warm-start
    sampler never offers tombstoned rows."""
    R, S = small_rs
    spec = JoinSpec(k=5, algorithm="iiib", r_block=24, s_block=32, warm_start=0.2)
    index = SparseKNNIndex.build(S, spec)
    base = index.query(R)
    extra = synthetic_sparse(16, dim=S.dim, nnz_mean=20, seed=9)
    index.extend(extra, deadline=50.0)
    assert index.expire(now=10.0) == 0      # not yet due
    assert index.query(R).scores.shape == base.scores.shape
    assert index.expire(now=50.0) == 16     # deadline inclusive
    res = index.query(R)
    # warm-start sample size tracks n_s, so the post-extend query routes
    # some dots through the BF warm pass — identical up to fp re-association
    np.testing.assert_allclose(
        np.asarray(res.scores), np.asarray(base.scores), atol=1e-5
    )
    np.testing.assert_array_equal(np.asarray(res.ids), np.asarray(base.ids))
    assert not np.isin(
        np.asarray(res.ids), np.arange(S.num_vectors, S.num_vectors + 16)
    ).any()


def test_refreeze_recovers_prune_rate():
    """ROADMAP open item: after heavy extend() drift the frozen IIIB rank
    prunes less; refreeze() recomputes it — kept list entries drop, results
    stay identical.  Drift shape: the new rows are dominated by fresh
    'boilerplate' dims the queries never touch, which the stale rank sorts
    AFTER the crossing (kept) and the refrozen rank sorts first (pruned)."""
    import jax.numpy as jnp

    rng_dim = 2048

    def make(n, pools_counts, weights, seed):
        rng = np.random.default_rng(seed)
        rows_i, rows_v = [], []
        for _ in range(n):
            ds, ws = [], []
            for (pool, cnt), w in zip(pools_counts, weights):
                ds.append(rng.choice(pool, cnt, replace=False))
                ws.append(w * (0.5 + rng.random(cnt)))
            d = np.concatenate(ds)
            order = np.argsort(d)
            rows_i.append(d[order])
            rows_v.append(np.concatenate(ws)[order].astype(np.float32))
        return SparseBatch(
            indices=jnp.asarray(np.stack(rows_i).astype(np.int32)),
            values=jnp.asarray(np.stack(rows_v)),
            nnz=jnp.asarray(np.full(n, len(rows_i[0]), np.int32)),
            dim=rng_dim,
        )

    content = np.arange(0, 256)
    boiler_old = np.arange(256, 512)
    boiler_new = np.arange(512, 1024)
    S1 = make(64, [(content, 16), (boiler_old, 16)], [1.0, 0.2], seed=1)
    S2 = make(512, [(content, 8), (boiler_new, 24)], [1.0, 0.2], seed=2)
    Rq = make(40, [(content, 24)], [2.0], seed=3)
    spec = JoinSpec(k=5, algorithm="iiib", s_block=64, r_block=40, warm_start=0.2)
    index = SparseKNNIndex.build(S1, spec)
    index.extend(S2)
    frozen = JoinStats()
    r1 = index.query(Rq, stats=frozen)
    builds = index.stats.index_builds
    index.refreeze()
    assert index.stats.index_builds > builds      # stacks really reassembled
    refrozen = JoinStats()
    r2 = index.query(Rq, stats=refrozen)
    assert refrozen.list_entries < frozen.list_entries, (
        frozen.list_entries, refrozen.list_entries
    )
    np.testing.assert_allclose(
        np.asarray(r1.scores), np.asarray(r2.scores), atol=1e-5
    )
    ok = np.asarray(r1.scores) > -np.inf
    np.testing.assert_array_equal(
        np.where(ok, np.asarray(r1.ids), -1), np.where(ok, np.asarray(r2.ids), -1)
    )


def test_plan_accepts_calibration(tmp_path):
    """plan(calibration=) consumes a dict or a JSON file and replaces the
    hard-coded unit costs — an extreme indexed-cost factor flips the
    algorithm choice; measured unit costs turn scores into seconds."""
    shape = (1000, 8, 10_000)
    default = plan(shape, shape, JoinSpec(k=5))
    assert default.algorithm == "iiib"
    forced = plan(shape, shape, JoinSpec(k=5), calibration={"index_cost_factor": 1e9})
    assert forced.algorithm == "bf"

    import json

    path = tmp_path / "cal.json"
    path.write_text(json.dumps({"c2_unit_s": 1e-10, "c3_unit_s": 2e-10}))
    cal = plan(shape, shape, JoinSpec(k=5), calibration=str(path))
    np.testing.assert_allclose(cal.cost_bf, default.cost_bf * 1e-10)
    # engine carries the calibration into its own planning
    S = synthetic_sparse(64, dim=512, nnz_mean=10, seed=1)
    index = SparseKNNIndex.build(
        S, JoinSpec(k=5), calibration={"index_cost_factor": 1e9}
    )
    assert index.algorithm == "bf"


def test_roofline_calibrate_roundtrip(tmp_path):
    """benchmarks/roofline.py --calibrate writes a record plan() accepts."""
    from benchmarks.roofline import calibrate

    path = str(tmp_path / "cal.json")
    rec = calibrate(path, fast=True)
    assert rec["c2_unit_s"] > 0 and rec["c3_unit_s"] > 0
    p = plan((1000, 8, 10_000), (1000, 8, 10_000), JoinSpec(k=5), calibration=path)
    assert p.cost_bf > 0
