"""Distributed KNN join — the paper's block nested-loop join on a TPU mesh.

``ring_knn_join`` is now a compat wrapper over the sharded datastore
(repro.store.ShardedKNNStore via ``engine.distributed_join``): S is
partitioned into build-once per-shard index stacks and each R block is one
fan-out dispatch with an on-device top-k reduction.  The ``lax.ppermute``
ring driver below (``_ring_join_impl``) remains the implementation for
``dim_axis`` — dimension-sharded tensor parallelism, where each model
shard scores its own dim range and partial scores psum before the merge —
which the store does not cover.

Legacy ring mapping (DESIGN.md §2):

* Each ring position (the flattened ``ring_axes`` of the mesh, e.g.
  ``("pod", "data")``) holds a resident **R shard** (the paper's in-buffer
  B_r) and one **S shard**.
* S shards rotate around the ring via ``lax.ppermute`` — the paper's
  "stream S block by block" becomes "each ring step presents a new B_s".
  The permute of step t+1 can overlap the matmuls of step t (the carry is
  rotated immediately after use, letting XLA hoist the permute).
* The paper's index-per-block-pair structure is preserved: every device
  builds the (tile-)inverted index of the incoming S shard against its own
  R block — including IIIB's threshold, which uses the device-local
  MinPruneScore exactly as the paper uses the block-local one, and
  *tightens monotonically as the ring progresses* (paper §4.4: "results of
  previous loops prune forthcoming loops").
* Optional ``dim_axis``: the dimension axis D is additionally sharded over
  the mesh's ``model`` axis (tensor parallelism for the join).  Each model
  shard scores its own dim range; partial scores are ``psum``-ed before the
  top-k merge.  Supported for bf and iib (IIIB's frequency-ordered global
  cumulative bound does not factorize across dim shards — it rings with
  dims replicated; documented in DESIGN.md).

Exactness is inherited from the single-device algorithms; the ring only
changes *which* (B_r, B_s) pair is joined where/when.
"""
from __future__ import annotations

import math
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.bf import bf_block_scores
from repro.core.iiib import iiib_join_block_uniform, prepare_r_block
from repro.core.index import build_tile_index, dense_r_tiles, tile_scores
from repro.core.topk import TopKState, init_topk, topk_update
from repro.sparse.format import SparseBatch


def _restrict_dims(block: SparseBatch, lo: jax.Array, local_dim: int) -> SparseBatch:
    """Project a SparseBatch onto dims [lo, lo+local_dim), reindexed from 0."""
    idx = block.indices
    ok = (idx >= lo) & (idx < lo + local_dim) & (idx < block.dim)
    new_idx = jnp.where(ok, idx - lo, local_dim).astype(jnp.int32)
    new_val = jnp.where(ok, block.values, 0.0)
    return SparseBatch(
        indices=new_idx, values=new_val, nnz=ok.sum(axis=1).astype(jnp.int32), dim=local_dim
    )


def ring_knn_join(
    R: SparseBatch,
    S: SparseBatch,
    k: int,
    mesh: Mesh,
    algorithm: str = "iiib",
    ring_axes: Sequence[str] = ("data",),
    dim_axis: Optional[str] = None,
    tile: int = 128,
    n_r_valid: Optional[int] = None,
    n_s_valid: Optional[int] = None,
) -> TopKState:
    """R ⋈_KNN S over a device mesh.

    Compat wrapper over the engine (core/engine.py): builds a JoinSpec and
    dispatches to :func:`repro.core.engine.distributed_join` — the sharded
    store by default, the ring driver below when ``dim_axis`` is set (only
    that path still requires R/S row counts to divide the ring size).
    Returns a TopKState for all R rows with global S ids; ``n_*_valid``
    mask padding rows appended by the caller.
    """
    from repro.core.engine import JoinSpec, distributed_join

    spec = JoinSpec(k=k, algorithm=algorithm, tile=tile)
    return distributed_join(
        R, S, spec, mesh, ring_axes=ring_axes, dim_axis=dim_axis,
        n_r_valid=n_r_valid, n_s_valid=n_s_valid,
    )


def _ring_join_impl(
    R: SparseBatch,
    S: SparseBatch,
    k: int,
    mesh: Mesh,
    algorithm: str = "iiib",
    ring_axes: Sequence[str] = ("data",),
    dim_axis: Optional[str] = None,
    tile: int = 128,
    n_r_valid: Optional[int] = None,
    n_s_valid: Optional[int] = None,
) -> TopKState:
    """The shard_map ring driver (see module docstring for the mapping)."""
    if algorithm not in ("bf", "iib", "iiib"):
        raise ValueError(algorithm)
    if algorithm == "iiib" and dim_axis is not None:
        raise ValueError("iiib rings with dims replicated (see DESIGN.md)")

    ring_axes = tuple(ring_axes)
    n_ring = math.prod(mesh.shape[a] for a in ring_axes)
    n_r, n_s = R.num_vectors, S.num_vectors
    assert n_r % n_ring == 0 and n_s % n_ring == 0, "pad R/S to the ring size"
    s_shard = n_s // n_ring
    n_r_valid = n_r if n_r_valid is None else n_r_valid
    n_s_valid = n_s if n_s_valid is None else n_s_valid
    n_dim_shards = mesh.shape[dim_axis] if dim_axis else 1
    assert R.dim % n_dim_shards == 0, "dim must divide the model axis"

    row_spec = P(ring_axes)
    mat_spec = P(ring_axes, None)

    def spec_of(batch: SparseBatch):
        return SparseBatch(indices=mat_spec, values=mat_spec, nnz=row_spec, dim=batch.dim)

    perm = [(i, (i + 1) % n_ring) for i in range(n_ring)]

    def local_join(r_loc: SparseBatch, s_loc: SparseBatch) -> TopKState:
        my = jax.lax.axis_index(ring_axes)
        n_r_loc = r_loc.num_vectors

        if dim_axis is not None:
            d_idx = jax.lax.axis_index(dim_axis)
            local_dim = R.dim // n_dim_shards
            r_loc_d = _restrict_dims(r_loc, d_idx * local_dim, local_dim)
        else:
            r_loc_d = r_loc

        if algorithm == "iib":
            r_tiles = dense_r_tiles(r_loc_d, None, tile)
            t_total = r_tiles.shape[0]
            all_tiles = jnp.arange(t_total, dtype=jnp.int32)
        elif algorithm == "iiib":
            rank, maxw, r_tiles = prepare_r_block(r_loc_d, tile)

        def step(t, carry):
            state, s_blk = carry
            src_shard = (my - t) % n_ring
            s_off = (src_shard * s_shard).astype(jnp.int32)
            s_valid = (s_off + jnp.arange(s_shard, dtype=jnp.int32)) < n_s_valid

            if dim_axis is not None:
                s_use = _restrict_dims(s_blk, d_idx * local_dim, local_dim)
            else:
                s_use = s_blk

            if algorithm == "bf":
                scores = bf_block_scores(r_loc_d, s_use)
                if dim_axis is not None:
                    scores = jax.lax.psum(scores, dim_axis)
                ids = s_off + jnp.arange(s_shard, dtype=jnp.int32)
                scores = jnp.where(s_valid[None, :], scores, -jnp.inf)
                state = topk_update(state, scores, ids)
            elif algorithm == "iib":
                index = build_tile_index(s_use, max_rows=s_shard, tile=tile)
                scores = tile_scores(r_tiles, index, all_tiles)
                if dim_axis is not None:
                    scores = jax.lax.psum(scores, dim_axis)
                ids = s_off + jnp.arange(s_shard, dtype=jnp.int32)
                scores = jnp.where((scores > 0.0) & s_valid[None, :], scores, -jnp.inf)
                state = topk_update(state, scores, ids)
            else:  # iiib, uniform-crossing jit variant
                from repro.core.topk import min_prune_score

                mps = min_prune_score(state)
                index = build_tile_index(
                    s_use, max_rows=s_shard, tile=tile, rank=rank, maxw=maxw,
                    min_prune_score=mps, uniform=True,
                )
                state = iiib_join_block_uniform(
                    state, r_loc_d, r_tiles, rank, index, s_use,
                    s_off, s_valid, tile=tile,
                )

            # rotate S to the next ring position (overlappable with next step)
            s_blk = jax.tree.map(lambda x: jax.lax.ppermute(x, ring_axes, perm), s_blk)
            return state, s_blk

        state = init_topk(n_r_loc, k)
        state, _ = jax.lax.fori_loop(0, n_ring, step, (state, s_loc))
        # mask padding R rows (harmless but deterministic output)
        r_global = my * n_r_loc + jnp.arange(n_r_loc)
        ok = (r_global < n_r_valid)[:, None]
        return TopKState(
            scores=jnp.where(ok, state.scores, -jnp.inf),
            ids=jnp.where(ok, state.ids, -1),
        )

    out_specs = TopKState(scores=mat_spec, ids=mat_spec)
    fn = jax.shard_map(
        local_join, mesh=mesh, in_specs=(spec_of(R), spec_of(S)),
        out_specs=out_specs, check_vma=False,
    )
    return fn(R, S)


def pad_to_ring(batch: SparseBatch, n_ring: int) -> Tuple[SparseBatch, int]:
    """Pad a SparseBatch with empty rows so the ring divides it. Host-side."""
    import numpy as np

    n = batch.num_vectors
    target = -(-n // n_ring) * n_ring
    if target == n:
        return batch, n
    pad = target - n
    idx = np.concatenate(
        [np.asarray(batch.indices), np.full((pad, batch.max_features), batch.dim, np.int32)]
    )
    val = np.concatenate(
        [np.asarray(batch.values), np.zeros((pad, batch.max_features), np.float32)]
    )
    nnz = np.concatenate([np.asarray(batch.nnz), np.zeros(pad, np.int32)])
    return (
        SparseBatch(indices=jnp.asarray(idx), values=jnp.asarray(val), nnz=jnp.asarray(nnz), dim=batch.dim),
        n,
    )
