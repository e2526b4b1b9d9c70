"""Pallas kernel sweeps: shapes/dtypes vs the pure-jnp oracles (interpret mode)."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core.topk import init_topk, topk_update
from repro.kernels.knn_score.kernel import knn_score_pallas
from repro.kernels.knn_score.ops import (
    active_lists,
    dense_tiles_with_sentinel,
    knn_score,
    _pad_rows,
)
from repro.kernels.knn_score.ref import dense_oracle, knn_score_ref
from repro.kernels.topk_merge.ops import topk_merge
from repro.sparse.datagen import synthetic_sparse
from repro.sparse.format import densify, tile_occupancy


# ---------------------------------------------------------------------------
# knn_score kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("nr,ns,dim,tile,br,bs", [
    (64, 64, 256, 128, 64, 64),
    (70, 90, 640, 128, 64, 64),      # padding rows
    (128, 64, 384, 128, 128, 32),    # uneven blocks
    (32, 32, 512, 256, 32, 32),      # wider tile
    (16, 200, 1024, 128, 16, 64),    # tall-thin
])
def test_knn_score_shapes(nr, ns, dim, tile, br, bs):
    R = synthetic_sparse(nr, dim=dim, nnz_mean=15, nnz_std=4, seed=nr + ns)
    S = synthetic_sparse(ns, dim=dim, nnz_mean=15, nnz_std=4, seed=nr * ns)
    out = np.asarray(knn_score(R, S, tile=tile, block_r=br, block_s=bs, interpret=True))
    truth = np.asarray(densify(R)) @ np.asarray(densify(S)).T
    np.testing.assert_allclose(out, truth, atol=1e-4)


def test_knn_score_kernel_vs_ref_oracle():
    """Kernel vs the per-tile reference (same active lists)."""
    R = synthetic_sparse(64, dim=512, nnz_mean=12, seed=3)
    S = synthetic_sparse(64, dim=512, nnz_mean=12, seed=4)
    tile, br, bs = 128, 32, 32
    r_tiles = _pad_rows(dense_tiles_with_sentinel(R, tile), br)
    s_tiles = _pad_rows(dense_tiles_with_sentinel(S, tile), bs)
    r_occ = np.asarray(tile_occupancy(R, tile))
    s_occ = np.asarray(tile_occupancy(S, tile))
    active = jnp.asarray(active_lists(r_occ, s_occ, br, bs))
    out = knn_score_pallas(r_tiles, s_tiles, active, block_r=br, block_s=bs,
                           interpret=True)
    ref = knn_score_ref(r_tiles, s_tiles, active, block_r=br, block_s=bs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(out)[:64, :64],
        np.asarray(dense_oracle(r_tiles, s_tiles))[:64, :64],
        atol=1e-4,
    )


def test_active_lists_matches_naive():
    """The vectorized block-occupancy intersection == the per-pair nonzero
    scan it replaced (ascending tile ids packed first, sentinel padding)."""
    rng = np.random.default_rng(11)
    for nr, ns, br, bs, t in [(70, 90, 64, 64, 5), (33, 100, 16, 24, 17), (8, 8, 8, 8, 1)]:
        r_occ = rng.random((nr, t)) < 0.3
        s_occ = rng.random((ns, t)) < 0.3
        got = active_lists(r_occ, s_occ, br, bs)
        n_rb, n_sb = -(-nr // br), -(-ns // bs)
        assert got.shape[:2] == (n_rb, n_sb) and got.shape[2] % 8 == 0
        for i in range(n_rb):
            for j in range(n_sb):
                r_any = r_occ[i * br : (i + 1) * br].any(axis=0)
                s_any = s_occ[j * bs : (j + 1) * bs].any(axis=0)
                (tiles,) = np.nonzero(r_any & s_any)
                np.testing.assert_array_equal(got[i, j, : len(tiles)], tiles)
                assert (got[i, j, len(tiles):] == t).all()


def test_knn_score_skips_dead_tiles():
    """Active lists must be shorter than the full tile count on sparse data
    (this is the C3-vs-C2 win the kernel exists for)."""
    R = synthetic_sparse(32, dim=16384, nnz_mean=4, nnz_std=1, seed=5)
    S = synthetic_sparse(32, dim=16384, nnz_mean=4, nnz_std=1, seed=6)
    r_occ = np.asarray(tile_occupancy(R, 128))
    s_occ = np.asarray(tile_occupancy(S, 128))
    active = active_lists(r_occ, s_occ, 32, 32)
    n_tiles = 16384 // 128
    used = (active < n_tiles).sum()
    assert used < n_tiles // 2, f"no tile skipping: {used} of {n_tiles}"


# ---------------------------------------------------------------------------
# topk_merge kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,k,m", [(64, 5, 64), (256, 8, 300), (100, 16, 64), (32, 1, 50)])
def test_topk_merge_shapes(n, k, m):
    rng = np.random.default_rng(n * k + m)
    st = init_topk(n, k)
    cand = rng.standard_normal((n, m)).astype(np.float32)
    ids = np.tile(np.arange(m, dtype=np.int32), (n, 1))
    out_s, out_i = topk_merge(st.scores, st.ids, jnp.asarray(cand), jnp.asarray(ids), interpret=True)
    ref = topk_update(st, jnp.asarray(cand), jnp.asarray(np.arange(m, dtype=np.int32)))
    np.testing.assert_allclose(np.asarray(out_s), np.asarray(ref.scores), atol=1e-6)


def test_topk_merge_streaming_equals_batch():
    """Merging in chunks == merging all at once (associativity)."""
    rng = np.random.default_rng(0)
    n, k, m = 64, 5, 256
    cand = rng.standard_normal((n, m)).astype(np.float32)
    ids = np.tile(np.arange(m, dtype=np.int32), (n, 1))
    st = init_topk(n, k)
    s1, i1 = topk_merge(st.scores, st.ids, jnp.asarray(cand), jnp.asarray(ids), interpret=True)
    s2, i2 = st.scores, st.ids
    for lo in range(0, m, 64):
        s2, i2 = topk_merge(s2, i2, jnp.asarray(cand[:, lo:lo + 64]),
                            jnp.asarray(ids[:, lo:lo + 64]), interpret=True)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), atol=1e-6)


def test_topk_merge_with_ties():
    """Duplicate scores must not lose candidates."""
    n, k = 8, 4
    st = init_topk(n, k)
    cand = np.ones((n, 6), np.float32)
    ids = np.tile(np.arange(6, dtype=np.int32), (n, 1))
    s, i = topk_merge(st.scores, st.ids, jnp.asarray(cand), jnp.asarray(ids), interpret=True)
    assert (np.asarray(s) == 1.0).all()
    # ids are a subset of the candidates, no repeats per row
    for row in np.asarray(i):
        assert len(set(row.tolist())) == k
