"""Fused score→top-k kernel (kernels/knn_topk) vs the materialize-then-merge
path it replaces: knn_score ref + topk_merge ref, interpret mode.  Scores AND
ids must match (same tie resolution), including masked/padded columns, k not
a multiple of 8, and ragged final S blocks — bit-for-bit where both sides
run the same dots, within TOL where they do not (see TOL)."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core.reference import topk_agreement
from repro.core.topk import init_topk, topk_update
from repro.kernels.knn_score.ops import (
    _pad_rows,
    active_lists,
    dense_tiles_with_sentinel,
    knn_score,
)
from repro.kernels.knn_topk.kernel import knn_topk_pallas
from repro.kernels.knn_topk.ops import column_meta, knn_topk, pad_state
from repro.kernels.knn_topk.ref import knn_topk_ref
from repro.sparse.datagen import synthetic_sparse
from repro.sparse.format import SparseBatch, tile_occupancy


# The kernel's per-tile dots run inside the interpreted kernel program, the
# references' as separate XLA ops of other shapes, and XLA may sum the same
# f32 products in another order: results then differ in the last ulp (6e-8
# seen; scores here are < 8, one ulp < 1e-6).  Scores must agree within
# TOL, ids wherever the reference's neighbouring scores are more than TOL
# apart.
TOL = 1e-5


def _assert_topk_close(got_s, got_i, ref_s, ref_i):
    a = topk_agreement(ref_s, ref_i, got_s, got_i, TOL)
    assert a["max_score_err"] <= TOL, a
    assert a["id_mismatches"] == 0, a
    assert a["ids_checked"] > 0, a


def _rows(sb: SparseBatch, lo: int, hi: int) -> SparseBatch:
    return SparseBatch(
        indices=sb.indices[lo:hi], values=sb.values[lo:hi], nnz=sb.nnz[lo:hi], dim=sb.dim
    )


def _arrays(R, S, tile, br, bs):
    r_tiles = _pad_rows(dense_tiles_with_sentinel(R, tile), br)
    s_tiles = _pad_rows(dense_tiles_with_sentinel(S, tile), bs)
    r_occ = np.asarray(tile_occupancy(R, tile))
    s_occ = np.asarray(tile_occupancy(S, tile))
    active = jnp.asarray(active_lists(r_occ, s_occ, br, bs))
    return r_tiles, s_tiles, active


@pytest.mark.parametrize("nr,ns,dim,tile,br,bs,k", [
    (64, 64, 256, 128, 64, 64, 8),
    (70, 90, 640, 128, 64, 64, 5),     # padded rows + ragged final S block, k%8
    (48, 100, 512, 128, 16, 32, 12),   # k%8 != 0, small blocks
    (32, 200, 1024, 128, 32, 64, 3),   # tall-thin
])
def test_knn_topk_kernel_vs_ref(nr, ns, dim, tile, br, bs, k):
    """Kernel (interpret) vs the knn_score-ref + topk_merge-ref oracle,
    the reference taken one slot deeper so the k-th slot's tie test sees
    its lower neighbour."""
    R = synthetic_sparse(nr, dim=dim, nnz_mean=12, nnz_std=4, seed=nr + ns)
    S = synthetic_sparse(ns, dim=dim, nnz_mean=12, nnz_std=4, seed=nr * ns)
    r_tiles, s_tiles, active = _arrays(R, S, tile, br, bs)
    nr_pad, ns_pad = r_tiles.shape[1], s_tiles.shape[1]
    valid, ids = column_meta(ns, ns_pad)
    init_s, init_i = pad_state(init_topk(nr, k), nr_pad)
    out = knn_topk_pallas(r_tiles, s_tiles, active, valid, ids, init_s, init_i,
                          block_r=br, block_s=bs, interpret=True)
    deep_s, deep_i = pad_state(init_topk(nr, k + 1), nr_pad)
    ref = knn_topk_ref(r_tiles, s_tiles, active, valid, ids, deep_s, deep_i,
                       block_r=br, block_s=bs)
    _assert_topk_close(out[0], out[1], ref[0], ref[1])


def test_knn_topk_matches_materialize_then_merge():
    """ops.knn_topk == the exact path it replaces: full knn_score matrix,
    >0-candidate mask, then one topk_update (scores AND ids)."""
    R = synthetic_sparse(70, dim=640, nnz_mean=15, nnz_std=4, seed=160)
    S = synthetic_sparse(90, dim=640, nnz_mean=15, nnz_std=4, seed=6300)
    k = 5
    st = knn_topk(R, S, k=k, block_r=64, block_s=64, interpret=True)
    sc = knn_score(R, S, block_r=64, block_s=64, interpret=True)
    masked = jnp.where(sc > 0, sc, -jnp.inf)
    ref = topk_update(init_topk(70, k), masked, jnp.arange(90, dtype=jnp.int32))
    np.testing.assert_array_equal(np.asarray(st.scores), np.asarray(ref.scores))
    np.testing.assert_array_equal(np.asarray(st.ids), np.asarray(ref.ids))


def test_knn_topk_masked_columns():
    """User-masked columns (e.g. warm-start-sampled rows) never surface."""
    R = synthetic_sparse(40, dim=512, nnz_mean=14, seed=2)
    S = synthetic_sparse(64, dim=512, nnz_mean=14, seed=3)
    rng = np.random.default_rng(0)
    s_valid = rng.random(64) > 0.3
    st = knn_topk(R, S, k=7, s_valid=s_valid, block_r=32, block_s=32, interpret=True)
    sc = knn_score(R, S, block_r=32, block_s=32, interpret=True)
    masked = jnp.where((sc > 0) & jnp.asarray(s_valid)[None, :], sc, -jnp.inf)
    ref = topk_update(init_topk(40, 7), masked, jnp.arange(64, dtype=jnp.int32))
    np.testing.assert_array_equal(np.asarray(st.scores), np.asarray(ref.scores))
    np.testing.assert_array_equal(np.asarray(st.ids), np.asarray(ref.ids))
    assert not np.isin(np.asarray(st.ids), np.nonzero(~s_valid)[0]).any()


def test_knn_topk_threshold_inert_and_tracked():
    """The threshold input/output: results are bit-identical with the
    threshold on or off (masked candidates provably cannot enter any row's
    top-k), and thr_out reports the live per-r-block MinPruneScore."""
    nr, ns, dim, tile, br, bs, k = 70, 90, 640, 128, 64, 32, 5
    R = synthetic_sparse(nr, dim=dim, nnz_mean=15, nnz_std=4, seed=160)
    S = synthetic_sparse(ns, dim=dim, nnz_mean=15, nnz_std=4, seed=6300)
    r_tiles, s_tiles, active = _arrays(R, S, tile, br, bs)
    nr_pad, ns_pad = r_tiles.shape[1], s_tiles.shape[1]
    valid, ids = column_meta(ns, ns_pad)
    init_s, init_i = pad_state(init_topk(nr, k), nr_pad)
    nrv = jnp.full((1,), nr, jnp.int32)

    off = knn_topk_pallas(r_tiles, s_tiles, active, valid, ids, init_s, init_i,
                          block_r=br, block_s=bs, interpret=True)     # thr disabled
    on = knn_topk_pallas(r_tiles, s_tiles, active, valid, ids, init_s, init_i,
                         thr=jnp.full((1, 1), -jnp.inf, jnp.float32), nr_valid=nrv,
                         block_r=br, block_s=bs, interpret=True)
    np.testing.assert_array_equal(np.asarray(off[0]), np.asarray(on[0]))
    np.testing.assert_array_equal(np.asarray(off[1]), np.asarray(on[1]))

    ref = knn_topk_ref(r_tiles, s_tiles, active, valid, ids, init_s, init_i,
                       thr=jnp.full((1, 1), -jnp.inf, jnp.float32), nr_valid=nrv,
                       block_r=br, block_s=bs)
    np.testing.assert_array_equal(np.asarray(on[0]), np.asarray(ref[0]))
    np.testing.assert_array_equal(np.asarray(on[1]), np.asarray(ref[1]))
    np.testing.assert_array_equal(np.asarray(on[2]), np.asarray(ref[2]))
    # thr_out == min over each r-block's VALID rows of the k-th best score
    out_s = np.asarray(on[0])
    rows_valid = np.arange(nr_pad) < nr
    for bi in range(nr_pad // br):
        kth = out_s[bi * br : (bi + 1) * br, -1]
        vm = rows_valid[bi * br : (bi + 1) * br]
        expect = np.min(np.where(vm, kth, np.inf))
        assert np.asarray(on[2])[bi, 0] == np.float32(expect)


def test_knn_topk_warm_threshold_preserves_results():
    """Seeding thr from a warm state must not change scores or ids — the
    early exit only skips candidates that could never be inserted."""
    R = synthetic_sparse(40, dim=512, nnz_mean=14, seed=2)
    S = synthetic_sparse(64, dim=512, nnz_mean=14, seed=3)
    k = 7
    warm = knn_topk(R, _rows(S, 0, 32), k=k, block_r=32, block_s=32, interpret=True)
    # chained call seeds thr = min_prune_score(warm) internally (ops.py)
    st = knn_topk(R, _rows(S, 32, 64), state=warm, s_offset=32, block_r=32, block_s=32, interpret=True)
    sc = knn_score(R, S, block_r=32, block_s=32, interpret=True)
    masked = jnp.where(sc > 0, sc, -jnp.inf)
    ref = topk_update(init_topk(40, k), masked[:, :32], jnp.arange(32, dtype=jnp.int32))
    ref = topk_update(ref, masked[:, 32:], 32 + jnp.arange(32, dtype=jnp.int32))
    np.testing.assert_array_equal(np.asarray(st.scores), np.asarray(ref.scores))
    np.testing.assert_array_equal(np.asarray(st.ids), np.asarray(ref.ids))


def test_knn_topk_chained_state_ragged_blocks():
    """Streaming S through two ragged chunks with carried state == one-shot
    merge of everything (the engine's online-state invariant)."""
    R = synthetic_sparse(70, dim=640, nnz_mean=15, nnz_std=4, seed=160)
    S = synthetic_sparse(90, dim=640, nnz_mean=15, nnz_std=4, seed=6300)
    k = 12
    st = knn_topk(R, _rows(S, 0, 50), k=k, block_r=64, block_s=32, interpret=True)
    st = knn_topk(R, _rows(S, 50, 90), state=st, s_offset=50, block_r=64, block_s=32, interpret=True)
    sc = knn_score(R, S, block_r=64, block_s=64, interpret=True)
    masked = jnp.where(sc > 0, sc, -jnp.inf)
    ref = topk_update(init_topk(70, k + 1), masked[:, :50], jnp.arange(50, dtype=jnp.int32))
    ref = topk_update(ref, masked[:, 50:], 50 + jnp.arange(40, dtype=jnp.int32))
    _assert_topk_close(st.scores, st.ids, ref.scores, ref.ids)
