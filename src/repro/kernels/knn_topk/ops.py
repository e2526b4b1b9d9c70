"""Public op: fused score→top-k with padding/active-list plumbing.

``knn_topk(r_block, s_block, ...)`` merges one S block into a running
top-k state without materializing the score matrix in HBM: densify into
dim-tiles, derive the active tile lists from occupancy, and run the fused
Pallas kernel.  The engine's cached query path skips this wrapper and
calls ``knn_topk_pallas`` directly on S tiles stacked once at build time
(one kernel dispatch covers every S block).  The kernel compiles to
Mosaic unless the caller asks for ``interpret=True`` (the CPU test path).
"""
from __future__ import annotations

from typing import Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.topk import TopKState, init_topk, min_prune_score, pad_topk_state
from repro.kernels.knn_score.ops import _pad_rows, active_lists, dense_tiles_with_sentinel
from repro.kernels.knn_topk.kernel import knn_topk_pallas
from repro.sparse.format import SparseBatch, tile_occupancy


def pad_state(state: TopKState, n_pad: int) -> Tuple[jax.Array, jax.Array]:
    """Pad a (N, k) top-k state to ``n_pad`` rows with empty (-inf, -1) slots."""
    padded = pad_topk_state(state, n_pad)
    return padded.scores, padded.ids


def column_meta(
    n_valid: int, n_pad: int, s_offset: int = 0, s_valid: Optional[np.ndarray] = None
) -> Tuple[jax.Array, jax.Array]:
    """((1, n_pad) valid int32, (1, n_pad) global-id int32) column metadata."""
    valid = np.zeros(n_pad, np.int32)
    if s_valid is None:
        valid[:n_valid] = 1
    else:
        valid[:n_valid] = np.asarray(s_valid, np.int32)[:n_valid]
    ids = np.full(n_pad, -1, np.int32)
    ids[:n_valid] = s_offset + np.arange(n_valid, dtype=np.int32)
    return jnp.asarray(valid[None, :]), jnp.asarray(ids[None, :])


def knn_topk(
    r_block: SparseBatch,
    s_block: SparseBatch,
    k: Optional[int] = None,
    state: Optional[TopKState] = None,
    s_offset: int = 0,
    s_valid: Optional[np.ndarray] = None,
    tile: int = 128,
    block_r: int = 256,
    block_s: int = 256,
    interpret: bool = False,
) -> TopKState:
    """Merge B_s's candidates into ``state`` (or a fresh k-state) — exact,
    identical scores AND ids to scoring densely then ``topk_update``.

    The carried state's MinPruneScore seeds the kernel's threshold, so a
    chained stream of S blocks prunes later blocks with the earlier blocks'
    results (the paper's "previous loops prune forthcoming loops") —
    results are bit-identical with or without the threshold.
    """
    assert r_block.dim == s_block.dim
    n_r, n_s = r_block.num_vectors, s_block.num_vectors
    if state is None:
        if k is None:
            raise ValueError("pass k or an initial state")
        state = init_topk(n_r, k)

    thr = min_prune_score(state).reshape(1, 1)   # lower-bounds every row's k-th
    r_tiles = _pad_rows(dense_tiles_with_sentinel(r_block, tile), block_r)
    s_tiles = _pad_rows(dense_tiles_with_sentinel(s_block, tile), block_s)
    nr_pad, ns_pad = r_tiles.shape[1], s_tiles.shape[1]
    r_occ = np.asarray(tile_occupancy(r_block, tile))
    s_occ = np.asarray(tile_occupancy(s_block, tile))
    active = jnp.asarray(active_lists(r_occ, s_occ, block_r, block_s))
    valid, ids = column_meta(n_s, ns_pad, s_offset=s_offset, s_valid=s_valid)
    init_s, init_i = pad_state(state, nr_pad)
    out_s, out_i, _ = knn_topk_pallas(
        r_tiles, s_tiles, active, valid, ids, init_s, init_i,
        thr=thr, nr_valid=jnp.full((1,), n_r, jnp.int32),
        block_r=block_r, block_s=block_s, interpret=interpret,
    )
    return TopKState(scores=out_s[:n_r], ids=out_i[:n_r])
