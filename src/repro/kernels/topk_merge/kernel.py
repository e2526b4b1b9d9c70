"""Pallas TPU kernel: streaming top-k merge (the paper's candidate-set insert).

The paper's inner loop — "if v > pruneScore(r): insert s into r's KNN
candidate set" — vectorized over a row block.  The running (rows, k)
score/id state lives in VMEM; each grid step streams one chunk of M
candidate columns and performs M insertion passes, each a constant-depth
VPU select/shift over the k lanes (no sort, no concat materialization):

  pos       = Σ_j [state[j] >= cand]          (insertion position per row)
  state'[j] = state[j]            j < pos
            = cand                j == pos
            = state[j-1]          j > pos     (lane roll by 1)

Ties resolve in favour of incumbents (matches jax.lax.top_k stability on a
[state, candidates] concat).  k ≤ 128 keeps the state in one lane tile.
Candidate column j is extracted by a one-hot masked max over the chunk's
lanes (exact: one hit per row), not a lane slice at a traced offset, which
Mosaic cannot lower.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def insert_candidates(state_scores, state_ids, cand_scores, cand_ids):
    """(rows, k) state ⊕ (rows, M) candidates via M insertion passes.

    The shared merge body: used here as the whole kernel and by the fused
    score→top-k kernel (kernels/knn_topk) as its per-S-block epilogue.
    Plain arrays in, plain arrays out — callable from any kernel (or traced
    code; it is pure jnp).
    """
    rows, k = state_scores.shape
    m = cand_scores.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, k), 1)
    col = jax.lax.broadcasted_iota(jnp.int32, (rows, m), 1)
    id_floor = jnp.iinfo(jnp.int32).min

    def insert(j, carry):
        scores, ids = carry
        hit = col == j
        cand = jnp.max(jnp.where(hit, cand_scores, -jnp.inf), axis=1, keepdims=True)
        cid = jnp.max(jnp.where(hit, cand_ids, id_floor), axis=1, keepdims=True)
        pos = jnp.sum((scores >= cand).astype(jnp.int32), axis=1, keepdims=True)
        sh_s = jnp.roll(scores, 1, axis=1)
        sh_i = jnp.roll(ids, 1, axis=1)
        new_s = jnp.where(lane < pos, scores, jnp.where(lane == pos, cand, sh_s))
        new_i = jnp.where(lane < pos, ids, jnp.where(lane == pos, cid, sh_i))
        return new_s, new_i

    return jax.lax.fori_loop(0, m, insert, (state_scores, state_ids))


def _merge_kernel(state_s_ref, state_i_ref, cand_s_ref, cand_i_ref, out_s_ref, out_i_ref):
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        out_s_ref[...] = state_s_ref[...]
        out_i_ref[...] = state_i_ref[...]

    scores, ids = insert_candidates(
        out_s_ref[...], out_i_ref[...], cand_s_ref[...], cand_i_ref[...]
    )
    out_s_ref[...] = scores
    out_i_ref[...] = ids


@functools.partial(jax.jit, static_argnames=("block_rows", "chunk_m", "interpret"))
def topk_merge_pallas(
    state_scores: jax.Array,  # (N, k) f32 descending
    state_ids: jax.Array,     # (N, k) i32
    cand_scores: jax.Array,   # (N, M) f32
    cand_ids: jax.Array,      # (N, M) i32
    block_rows: int = 256,
    chunk_m: int = 256,
    interpret: bool = False,
):
    n, k = state_scores.shape
    m = cand_scores.shape[1]
    assert n % block_rows == 0 and m % chunk_m == 0, "ops.py pads"
    grid = (n // block_rows, m // chunk_m)

    state_spec = pl.BlockSpec((block_rows, k), lambda i, c: (i, 0))
    cand_spec = pl.BlockSpec((block_rows, chunk_m), lambda i, c: (i, c))

    return pl.pallas_call(
        _merge_kernel,
        grid=grid,
        in_specs=[state_spec, state_spec, cand_spec, cand_spec],
        out_specs=[state_spec, state_spec],
        out_shape=[
            jax.ShapeDtypeStruct((n, k), jnp.float32),
            jax.ShapeDtypeStruct((n, k), jnp.int32),
        ],
        interpret=interpret,
    )(state_scores, state_ids, cand_scores, cand_ids)
