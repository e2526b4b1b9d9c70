"""Public op: tile-skipping KNN scoring with padding/active-list plumbing.

``knn_score(r_block, s_block)`` takes two SparseBatches, densifies them
into dim-tiles, derives the per-(r-block, s-block) active tile lists from
occupancy (host- or trace-side), and calls the Pallas kernel.  The kernel
compiles to Mosaic unless the caller asks for ``interpret=True`` (the CPU
test path).
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from repro.kernels.knn_score.kernel import knn_score_pallas
from repro.sparse.format import SparseBatch


def _pad_rows(x: jax.Array, block: int) -> jax.Array:
    n = x.shape[1]
    target = -(-n // block) * block
    if target == n:
        return x
    pad = jnp.zeros((x.shape[0], target - n, x.shape[2]), x.dtype)
    return jnp.concatenate([x, pad], axis=1)


def dense_tiles_with_sentinel(batch: SparseBatch, tile: int) -> jax.Array:
    """(T+1, N, tile) — dense dim-tiles plus a trailing zero sentinel tile."""
    from repro.core.index import dense_r_tiles

    t = dense_r_tiles(batch, None, tile)          # (T, N, tile)
    return jnp.concatenate([t, jnp.zeros((1,) + t.shape[1:], t.dtype)], axis=0)


def active_lists(
    r_occ: np.ndarray,  # (NR, T) bool occupancy
    s_occ: np.ndarray,  # (NS, T)
    block_r: int,
    block_s: int,
    bucket: int = 8,
) -> np.ndarray:
    """(nR, nS, A) int32 — tiles occupied by BOTH blocks, sentinel-padded.

    Host-side: the list lengths are data-dependent (this is the point — the
    kernel's work is proportional to them), so they are materialized
    concretely and bucketed to bound recompilation.

    Fully vectorized: one block-level any-reduce per side, one broadcast
    intersection, and a stable argsort to pack the occupied tile ids to the
    front of each list (ascending, exactly the nonzero order).  The former
    pure-Python O(nR·nS·T) nested loop dominated setup for large block
    grids.
    """
    t_total = r_occ.shape[1]

    def block_any(occ: np.ndarray, block: int) -> np.ndarray:
        n_blocks = -(-occ.shape[0] // block)
        padded = np.zeros((n_blocks * block, t_total), dtype=bool)
        padded[: occ.shape[0]] = occ
        return padded.reshape(n_blocks, block, t_total).any(axis=1)

    r_any = block_any(r_occ, block_r)                       # (nR, T)
    s_any = block_any(s_occ, block_s)                       # (nS, T)
    both = r_any[:, None, :] & s_any[None, :, :]            # (nR, nS, T)
    counts = both.sum(axis=-1)                              # (nR, nS)
    a_len = -(-max(int(counts.max(initial=1)), 1) // bucket) * bucket
    # stable argsort on ~both packs occupied tiles first, ascending tile id
    packed = np.argsort(~both, axis=-1, kind="stable").astype(np.int32)
    slot = np.arange(t_total, dtype=np.int32)
    packed = np.where(slot[None, None, :] < counts[..., None], packed, t_total)
    out = np.full((both.shape[0], both.shape[1], a_len), t_total, dtype=np.int32)
    w = min(a_len, t_total)
    out[:, :, :w] = packed[:, :, :w]
    return out


def knn_score(
    r_block: SparseBatch,
    s_block: SparseBatch,
    tile: int = 128,
    block_r: int = 256,
    block_s: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """(|Br|, |Bs|) exact dot-product scores via the tile-skipping kernel."""
    from repro.sparse.format import tile_occupancy

    assert r_block.dim == s_block.dim
    r_tiles = _pad_rows(dense_tiles_with_sentinel(r_block, tile), block_r)
    s_tiles = _pad_rows(dense_tiles_with_sentinel(s_block, tile), block_s)
    r_occ = np.asarray(tile_occupancy(r_block, tile))
    s_occ = np.asarray(tile_occupancy(s_block, tile))
    active = jnp.asarray(active_lists(r_occ, s_occ, block_r, block_s))
    out = knn_score_pallas(
        r_tiles, s_tiles, active, block_r=block_r, block_s=block_s, interpret=interpret
    )
    return out[: r_block.num_vectors, : s_block.num_vectors]
