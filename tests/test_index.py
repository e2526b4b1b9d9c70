"""The IIIB scan's scores (``core/index.masked_tile_scores``) against a
float64 reference, with lists near the block's length and short ones."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.engine import _pad_block
from repro.core.iiib import maxw_tiles, tile_mass_host
from repro.core.index import (
    DENSE_SCAN_MAX_BYTES,
    TileIndex,
    build_tile_index,
    dense_r_tiles,
    dense_scan_bytes,
    masked_tile_scores,
    max_rows_bound,
)
from repro.sparse.datagen import synthetic_sparse
from repro.sparse.format import SparseBatch, num_tiles


def _drop_tiles(batch, rank, tile, gone):
    """``batch`` with every feature whose permuted tile is in ``gone``
    removed, so those tiles hold no R mass."""
    idx, val = np.asarray(batch.indices), np.asarray(batch.values)
    ok = idx < batch.dim
    t = np.where(ok, rank[np.minimum(idx, batch.dim - 1)] // tile, -1)
    drop = ok & np.isin(t, gone)
    return SparseBatch(
        indices=jnp.asarray(np.where(drop, batch.dim, idx)),
        values=jnp.asarray(np.where(drop, 0.0, val).astype(np.float32)),
        nnz=jnp.asarray((ok & ~drop).sum(axis=1).astype(np.int32)),
        dim=batch.dim,
    )


def _dense64(batch, rank, t_total, tile):
    """(N, T·tile) float64 rows in permuted dim space."""
    idx, val = np.asarray(batch.indices), np.asarray(batch.values)
    out = np.zeros((idx.shape[0], t_total * tile))
    ok = idx < batch.dim
    rows = np.nonzero(ok)[0]
    np.add.at(out, (rows, rank[idx[ok]]), val[ok].astype(np.float64))
    return out


@pytest.mark.parametrize("dim,nnz,n_s,s_block,n_r", [
    (1024, 20, 200, 256, 24),        # lists ≈ the block
    (8192, 2, 2000, 2048, 24),       # M = 128 of 2,048 rows: short lists
    (1024, 20, 200, 256, 1),         # a one-row R block
])
def test_masked_tile_scores_match_float64(dim, nnz, n_s, s_block, n_r):
    tile = 128
    t_total = num_tiles(dim, tile)
    S = synthetic_sparse(n_s, dim=dim, nnz_mean=nnz, nnz_std=1, seed=5)
    sb, s_valid = _pad_block(S, 0, s_block)                # padded S rows
    rank = np.random.default_rng(7).permutation(dim).astype(np.int32)
    m = max_rows_bound(sb, tile, rank=rank)
    index = build_tile_index(sb, max_rows=m, tile=tile, rank=jnp.asarray(rank))
    assert np.asarray(index.counts)[:t_total].max() < m    # sentinel-padded slots

    # the R block leaves every third tile empty: those tiles are inactive
    gone = np.arange(1, t_total, 3)
    R = _drop_tiles(synthetic_sparse(n_r, dim=dim, nnz_mean=max(nnz, 12),
                                     nnz_std=1, seed=6), rank, tile, gone)
    r_tiles = dense_r_tiles(R, jnp.asarray(rank), tile)
    assert not np.asarray(r_tiles)[gone].any()

    # the live threshold masks a prefix of tiles of some rows and every
    # tile of others, as IIIB's bound does
    mass = tile_mass_host(np.asarray(sb.indices), np.asarray(sb.values), dim,
                          rank, tile)
    cum = np.cumsum(np.asarray(maxw_tiles(R, jnp.asarray(rank), tile))[None, :]
                    * mass, axis=1)
    keep = cum > np.quantile(cum[s_valid, -1], 0.3)
    masked = ~keep & (mass > 0)
    assert masked.any(axis=1).sum() > 10 and (~keep[s_valid]).all(axis=1).any()

    a_kept, a_full = jax.jit(masked_tile_scores)(r_tiles, index, jnp.asarray(keep))
    # no per-tile loop and no column scatter: one product per S block
    jaxpr = str(jax.make_jaxpr(masked_tile_scores)(r_tiles, index, jnp.asarray(keep)))
    assert "scan" not in jaxpr and jaxpr.count("dot_general") == 1

    r64 = _dense64(R, rank, t_total, tile)
    s64 = _dense64(sb, rank, t_total, tile)
    keep_cols = np.repeat(keep, tile, axis=1)
    assert a_full.shape == a_kept.shape == (n_r, s_block)
    np.testing.assert_allclose(np.asarray(a_full), r64 @ s64.T, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(a_kept), r64 @ (s64 * keep_cols).T,
                               rtol=1e-5, atol=1e-5)
    assert not np.asarray(a_full)[:, ~s_valid].any()       # padded rows score 0


def test_masked_tile_scores_refuses_temporaries_past_the_bound():
    """Dim 1M in 4,096-row S blocks would take 64 GiB of dense operands per
    step: the scan raises when traced.  Dim 65,536 (T = 512) is the largest
    the bound admits at that block size."""
    tile, n_s, m = 128, 4096, 128

    def shapes(t_total):
        index = TileIndex(
            rows=jax.ShapeDtypeStruct((t_total + 1, m), jnp.int32),
            vals=jax.ShapeDtypeStruct((t_total + 1, m, tile), jnp.float32),
            counts=jax.ShapeDtypeStruct((t_total + 1,), jnp.int32),
            pref_ub=jax.ShapeDtypeStruct((n_s,), jnp.float32),
            crossing=jax.ShapeDtypeStruct((n_s,), jnp.int32),
            tile=tile, num_s=n_s,
        )
        return (jax.ShapeDtypeStruct((t_total, 8, tile), jnp.float32), index,
                jax.ShapeDtypeStruct((n_s, t_total), jnp.bool_))

    assert dense_scan_bytes(512, n_s, tile) == DENSE_SCAN_MAX_BYTES
    kept, full = jax.eval_shape(masked_tile_scores, *shapes(512))
    assert kept.shape == full.shape == (8, n_s)
    with pytest.raises(ValueError, match="smaller s_block"):
        jax.eval_shape(masked_tile_scores, *shapes(num_tiles(1_000_000, tile)))
