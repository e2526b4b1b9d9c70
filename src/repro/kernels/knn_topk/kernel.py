"""Pallas TPU kernel: fused tile-skipping score → streaming top-k (DESIGN.md §2).

The engine's materialize-then-merge kernel path wrote the full
(|Br|, |Bs|) score matrix to HBM only to re-read it for a separate
``lax.top_k`` merge.  This kernel fuses the two: the score accumulator of
the tile-skipping matmul (kernels/knn_score) stays in VMEM scratch, and at
the last active tile of every S block the block's scores are folded into
the running per-row top-k state *in place* — flash-attention-style online
state carried across the S grid axis.  Block score matrices never touch
HBM; the only outputs are the (NR, k) score/id arrays and the updated
MinPruneScore.

Layout:
  active:  (nR, nS, A) int32 — per (r-block, s-block) active tile ids,
           sentinel-padded with T (scalar-prefetched)
  nr_valid:(1,) int32 — number of real R rows (scalar-prefetched; rows
           beyond it are padding and excluded from the threshold reduce)
  r_tiles: (T+1, NR, tile) f32 — dense dim-tiles of R (tile T = zero sentinel)
  s_tiles: (T+1, NS, tile) f32 — same for S (all blocks stacked)
  s_valid: (1, NS) int32 — 0 masks padding columns
  s_ids:   (1, NS) int32 — global S id per column
  init_s/init_i: (NR, k) — top-k state to merge into (warm starts compose)
  thr:     (1, 1) f32 in SMEM — seed MinPruneScore (a lower bound on every
           valid row's current k-th score; -inf disables)
  out:     (NR, k) scores f32 descending + ids i32
  thr_out: (nR, 1) f32 in SMEM — per-r-block live MinPruneScore (min over
           its valid rows' k-th scores), carried as a scalar across the
           (nS, A) plane (Mosaic stores no scalar to VMEM)

Grid: (nR, nS, A), all sequential on TPU.  The (block_r, block_s) f32
accumulator lives in VMEM scratch across the A axis; the (block_r, k)
state lives in a revisited VMEM output block across the whole (nS, A)
plane; the thresholds are one SMEM-resident (nR, 1) output, row i carried
as a scalar through r-block i.  The merge epilogue is the topk_merge
insertion body (``insert_candidates``) — one constant-depth VPU
select/shift pass per candidate column, candidate semantics identical to
``topk_update`` on a concat (incumbents win ties).

Candidate rule (IIB, paper Alg. 3 line 14): a column is offered only when
its accumulated score is > 0 — rows sharing no feature with r are never
returned.  The threshold adds the paper's pruneScore early-exit: a
candidate ≤ the block's MinPruneScore cannot enter any row's top-k (every
row's k-th is ≥ it, and ties favour incumbents), so such columns are
masked and — when an entire S block is pruned — the insertion epilogue is
skipped outright.  Results are bit-identical with the threshold on or off;
only the work changes.

VMEM working set = block_r·tile + block_s·tile + block_r·block_s +
2·block_r·k floats — ~0.6 MB at the (256, 256, tile=128, k≤128) defaults.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.topk_merge.kernel import insert_candidates

NEG_INF = -jnp.inf  # python float: safe to close over inside the kernel body


def _knn_topk_kernel(
    active_ref, nrv_ref, r_ref, s_ref, valid_ref, ids_ref, init_s_ref, init_i_ref,
    thr_ref, out_s_ref, out_i_ref, thr_out_ref, acc_ref,
):
    i = pl.program_id(0)
    j = pl.program_id(1)
    a = pl.program_id(2)
    n_a = pl.num_programs(2)

    @pl.when((j == 0) & (a == 0))
    def _seed_state():
        out_s_ref[...] = init_s_ref[...]
        out_i_ref[...] = init_i_ref[...]
        thr_out_ref[i, 0] = thr_ref[0, 0]

    @pl.when(a == 0)
    def _zero_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    rt = r_ref[0]  # (block_r, tile)
    st = s_ref[0]  # (block_s, tile)
    acc_ref[...] += jax.lax.dot_general(
        rt, st, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )

    @pl.when(a == n_a - 1)
    def _merge_epilogue():
        scores = acc_ref[...]                       # (block_r, block_s)
        thr = thr_out_ref[i, 0]
        ok = (scores > 0.0) & (valid_ref[0][None, :] > 0) & (scores > thr)

        # early exit: a fully-pruned S block never pays the insertion pass
        @pl.when(jnp.any(ok))
        def _insert():
            cand_s = jnp.where(ok, scores, NEG_INF)
            cand_i = jnp.broadcast_to(ids_ref[0][None, :], scores.shape)
            new_s, new_i = insert_candidates(
                out_s_ref[...], out_i_ref[...], cand_s, cand_i
            )
            out_s_ref[...] = new_s
            out_i_ref[...] = new_i
            # refresh the live MinPruneScore: min k-th over this block's
            # REAL rows (padding rows stay at -inf forever and would pin it)
            block_r = new_s.shape[0]
            rows = i * block_r + jax.lax.broadcasted_iota(
                jnp.int32, (block_r, 1), 0
            )
            kth = new_s[:, -1:]                     # (block_r, 1)
            thr_out_ref[i, 0] = jnp.min(
                jnp.where(rows < nrv_ref[0], kth, jnp.inf)
            )


@functools.partial(jax.jit, static_argnames=("block_r", "block_s", "interpret"))
def knn_topk_pallas(
    r_tiles: jax.Array,    # (T+1, NR, tile) — sentinel tile LAST, all zeros
    s_tiles: jax.Array,    # (T+1, NS, tile)
    active: jax.Array,     # (nR, nS, A) int32
    s_valid: jax.Array,    # (1, NS) int32
    s_ids: jax.Array,      # (1, NS) int32
    init_scores: jax.Array,  # (NR, k) f32
    init_ids: jax.Array,     # (NR, k) i32
    thr: jax.Array | None = None,       # (1, 1) f32 seed MinPruneScore
    nr_valid: jax.Array | None = None,  # (1,) i32 real R rows
    block_r: int = 256,
    block_s: int = 256,
    interpret: bool = False,
):
    """((NR, k) scores, (NR, k) ids, (nR, 1) MinPruneScore per r-block).
    NR % block_r == NS % block_s == 0 (ops.py pads)."""
    _, n_r, tile = r_tiles.shape
    _, n_s, _ = s_tiles.shape
    k = init_scores.shape[1]
    grid = (n_r // block_r, n_s // block_s, active.shape[-1])
    if thr is None:
        thr = jnp.full((1, 1), NEG_INF, jnp.float32)
    if nr_valid is None:
        nr_valid = jnp.full((1,), n_r, jnp.int32)

    def r_map(i, j, a, active_ref, nrv_ref):
        return (active_ref[i, j, a], i, 0)

    def s_map(i, j, a, active_ref, nrv_ref):
        return (active_ref[i, j, a], j, 0)

    def col_map(i, j, a, active_ref, nrv_ref):
        del i, a, active_ref, nrv_ref
        return (0, j)

    def state_map(i, j, a, active_ref, nrv_ref):
        del j, a, active_ref, nrv_ref
        return (i, 0)

    return pl.pallas_call(
        _knn_topk_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, block_r, tile), r_map),
                pl.BlockSpec((1, block_s, tile), s_map),
                pl.BlockSpec((1, block_s), col_map),
                pl.BlockSpec((1, block_s), col_map),
                pl.BlockSpec((block_r, k), state_map),
                pl.BlockSpec((block_r, k), state_map),
                pl.BlockSpec(memory_space=pltpu.SMEM),      # whole (1, 1)
            ],
            out_specs=[
                pl.BlockSpec((block_r, k), state_map),
                pl.BlockSpec((block_r, k), state_map),
                pl.BlockSpec(memory_space=pltpu.SMEM),      # whole (nR, 1)
            ],
            scratch_shapes=[pltpu.VMEM((block_r, block_s), jnp.float32)],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((n_r, k), jnp.float32),
            jax.ShapeDtypeStruct((n_r, k), jnp.int32),
            jax.ShapeDtypeStruct((n_r // block_r, 1), jnp.float32),
        ],
        interpret=interpret,
    )(active, nr_valid, r_tiles, s_tiles, s_valid, s_ids, init_scores, init_ids, thr)
