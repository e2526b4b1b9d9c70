"""SparseBatch format + dim/tile statistics."""
import numpy as np
import jax.numpy as jnp
from hypothesis import given, settings, strategies as st

from repro.sparse.format import (
    SparseBatch,
    densify,
    densify_tile,
    dim_frequency,
    frequency_permutation,
    max_weight_per_dim,
    reorder_dims,
    tile_occupancy,
)


def _rand_dense(rng, n, d, density=0.1):
    m = rng.random((n, d)) < density
    return (rng.random((n, d)) * m).astype(np.float32)


def test_from_dense_roundtrip():
    rng = np.random.default_rng(0)
    dense = _rand_dense(rng, 10, 64)
    sb = SparseBatch.from_dense(dense)
    np.testing.assert_allclose(np.asarray(densify(sb)), dense, atol=0)


def test_from_coo_roundtrip():
    rng = np.random.default_rng(1)
    dense = _rand_dense(rng, 8, 50)
    r, c = np.nonzero(dense)
    sb = SparseBatch.from_coo(r, c, dense[r, c], num_vectors=8, dim=50)
    np.testing.assert_allclose(np.asarray(densify(sb)), dense, atol=0)


def test_densify_tile_matches_slice():
    rng = np.random.default_rng(2)
    dense = _rand_dense(rng, 6, 300)
    sb = SparseBatch.from_dense(dense)
    for start, width in [(0, 128), (128, 128), (256, 128)]:
        tile = np.asarray(densify_tile(sb, start, 128))
        want = np.zeros((6, 128), np.float32)
        lo, hi = start, min(start + width, 300)
        want[:, : hi - lo] = dense[:, lo:hi]
        np.testing.assert_allclose(tile, want, atol=0)


def test_tile_occupancy():
    rng = np.random.default_rng(3)
    dense = _rand_dense(rng, 5, 256, density=0.05)
    sb = SparseBatch.from_dense(dense)
    occ = np.asarray(tile_occupancy(sb, 128))
    want = np.stack(
        [(dense[:, :128] != 0).any(1), (dense[:, 128:] != 0).any(1)], axis=1
    )
    np.testing.assert_array_equal(occ, want)


def test_dim_frequency_and_maxweight():
    rng = np.random.default_rng(4)
    dense = _rand_dense(rng, 12, 100)
    sb = SparseBatch.from_dense(dense)
    np.testing.assert_array_equal(
        np.asarray(dim_frequency(sb)), (dense != 0).sum(0)
    )
    np.testing.assert_allclose(
        np.asarray(max_weight_per_dim(sb)), dense.max(0), atol=0
    )


def test_frequency_permutation_sorts_descending():
    freq = jnp.asarray(np.array([3, 9, 1, 9, 0]))
    perm, order = frequency_permutation(freq)
    freq_np = np.asarray(freq)
    reordered = freq_np[np.asarray(order)]
    assert list(reordered) == sorted(freq_np, reverse=True)
    # perm is the inverse of order
    np.testing.assert_array_equal(np.asarray(order)[np.asarray(perm)], np.arange(5))


def test_reorder_dims_preserves_dots():
    rng = np.random.default_rng(5)
    dense = _rand_dense(rng, 6, 64)
    sb = SparseBatch.from_dense(dense)
    freq = dim_frequency(sb)
    perm, _ = frequency_permutation(freq)
    sb2 = reorder_dims(sb, perm)
    d2 = np.asarray(densify(sb2))
    # dot products are permutation-invariant
    np.testing.assert_allclose(d2 @ d2.T, dense @ dense.T, rtol=1e-5)


@settings(max_examples=25, deadline=None)
@given(st.integers(1, 12), st.integers(8, 120), st.integers(0, 1000))
def test_property_roundtrip(n, d, seed):
    rng = np.random.default_rng(seed)
    dense = _rand_dense(rng, n, d, density=0.2)
    sb = SparseBatch.from_dense(dense)
    np.testing.assert_allclose(np.asarray(densify(sb)), dense, atol=0)
    assert int(np.asarray(sb.nnz).sum()) == int((dense != 0).sum())


def test_slice_rows():
    rng = np.random.default_rng(6)
    dense = _rand_dense(rng, 10, 40)
    sb = SparseBatch.from_dense(dense)
    sl = sb.slice_rows(2, 4)
    np.testing.assert_allclose(np.asarray(densify(sl)), dense[2:6], atol=0)
