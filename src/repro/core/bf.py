"""Brute-force (BF) KNN join — the paper's Algorithm 2, TPU-adapted.

The paper's BF walks both feature lists with a sort-merge iterator, cost
``|r| + |s|`` per pair.  On TPU the idiomatic equivalent of "compute every
pairwise dot product" is a dense blocked matmul on the MXU: each dim-tile
of the R block multiplies the matching dim-tile of the S block and partial
scores accumulate in f32.  This is the *faithful baseline* — it touches
every dimension tile whether or not it holds mass, exactly as BF touches
every feature.

``bf_block_scores`` is chunked over the dimension axis so the densified
working set stays bounded (the (N, D) densification of a 10k-dim block
never materializes at once unless D is small).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core.topk import TopKState, topk_update
from repro.sparse.format import SparseBatch, densify_tile


def bf_block_scores(
    r_block: SparseBatch,
    s_block: SparseBatch,
    dim_chunk: int = 2048,
) -> jax.Array:
    """(|Br|, |Bs|) dot-product scores via chunked dense matmul."""
    assert r_block.dim == s_block.dim
    d = r_block.dim
    n_chunks = -(-d // dim_chunk)

    def body(c, acc):
        start = c * dim_chunk
        rt = densify_tile(r_block, start, dim_chunk)  # (Nr, chunk)
        st = densify_tile(s_block, start, dim_chunk)  # (Ns, chunk)
        with jax.named_scope("knn.matmul"):
            return acc + jax.lax.dot_general(
                rt, st, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.HIGHEST,
            )

    acc = jnp.zeros((r_block.num_vectors, s_block.num_vectors), dtype=jnp.float32)
    return jax.lax.fori_loop(0, n_chunks, body, acc)


def block_ids(s_offset: jax.Array | int, num_s: int) -> jax.Array:
    """(num_s,) global ids of a block's columns.

    ``s_offset`` is either the scalar global id of the block's first row
    (contiguous blocks — the engine's layout) or an explicit ``(num_s,)``
    id array (the sharded store's layout, where ``add()`` interleaves
    global id ranges across shards).
    """
    if jnp.ndim(s_offset) == 0:
        return s_offset + jnp.arange(num_s, dtype=jnp.int32)
    return s_offset.astype(jnp.int32)


def bf_join_block(
    state: TopKState,
    r_block: SparseBatch,
    s_block: SparseBatch,
    s_offset: jax.Array | int,
    s_valid: jax.Array | None = None,
    dim_chunk: int = 2048,
) -> TopKState:
    """One (B_r, B_s) BF join step: score everything, merge into top-k.

    ``s_offset`` maps block-local S columns to global ids (scalar first-row
    id or per-row id array).  ``s_valid`` masks padding rows of a partial
    final block and tombstoned (deleted / TTL-expired) rows.
    """
    scores = bf_block_scores(r_block, s_block, dim_chunk=dim_chunk)
    ids = block_ids(s_offset, s_block.num_vectors)
    if s_valid is not None:
        scores = jnp.where(s_valid[None, :], scores, -jnp.inf)
    return topk_update(state, scores, ids)


@partial(jax.jit, static_argnames=("dim",))
def bf_scan_join(state, r_block, s_idx, s_val, s_nnz, s_ids, s_valid, dim):
    """BF inner loop over ALL stacked S blocks as one ``lax.scan``.

    The device-resident form of Algorithm 1's S loop: the engine stacks its
    cached S blocks into ``(B, s_block, …)`` batched arrays at build time
    and the whole S side of one R block is this single dispatch, carrying
    the TopKState — no per-(B_r, B_s)-pair launches or host syncs.
    ``s_ids`` is the (B, s_block) global-id stack (per-row, so the sharded
    store can scan blocks whose ids are not contiguous).
    """

    def body(st, xs):
        bi, bv, bn, ids, vm = xs
        blk = SparseBatch(indices=bi, values=bv, nnz=bn, dim=dim)
        return bf_join_block(st, r_block, blk, ids, vm), None

    state, _ = jax.lax.scan(body, state, (s_idx, s_val, s_nnz, s_ids, s_valid))
    return state
