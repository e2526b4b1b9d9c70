"""Pallas TPU kernel: tile-skipping blocked KNN score matmul.

The paper's inverted index skips every feature of S that cannot contribute
to dot(r, s).  The TPU-native realization (DESIGN.md §2) is a block-sparse
matmul driven by **scalar-prefetched active-tile lists**: the grid's
innermost dimension walks only the dim-tiles that hold mass for the
current (R-block, S-block) pair — dead tiles are never fetched from HBM
and never touch the MXU.  This is where the C3-vs-C2 win materializes in
hardware terms: HBM traffic and FLOPs both scale with *occupied* tiles.

Layout:
  r_tiles: (T+1, BR_total, tile) f32 — dense dim-tiles of the R block
           (tile T is a zero sentinel for list padding)
  s_tiles: (T+1, BS_total, tile) f32 — same for the S block
  active:  (nR, nS, A) int32 — per (r-block, s-block) active tile ids,
           padded with T (the sentinel)
  out:     (BR_total, BS_total) f32 scores

Grid: (nR, nS, A); the (block_r, block_s) f32 accumulator lives in VMEM
across the A-loop (innermost, sequential on TPU) and is written once.

VMEM working set per step = block_r·tile + block_s·tile + block_r·block_s
floats; the default (256, 256, tile=128) uses ~0.5 MB — far under the
16 MB/core budget, leaving room for double-buffered prefetch of the next
tile pair.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _score_kernel(active_ref, r_ref, s_ref, out_ref):
    """One (r-block, s-block, active-tile) step: out += Rt @ St^T."""
    a = pl.program_id(2)

    @pl.when(a == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    rt = r_ref[0]  # (block_r, tile)
    st = s_ref[0]  # (block_s, tile)
    out_ref[...] += jax.lax.dot_general(
        rt, st, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )


@functools.partial(
    jax.jit, static_argnames=("block_r", "block_s", "interpret")
)
def knn_score_pallas(
    r_tiles: jax.Array,   # (T+1, NR, tile) — sentinel tile LAST, all zeros
    s_tiles: jax.Array,   # (T+1, NS, tile)
    active: jax.Array,    # (nR, nS, A) int32
    block_r: int = 256,
    block_s: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """(NR, NS) scores. NR % block_r == 0 and NS % block_s == 0 (ops.py pads)."""
    _, n_r, tile = r_tiles.shape
    _, n_s, _ = s_tiles.shape
    grid = (n_r // block_r, n_s // block_s, active.shape[-1])

    def r_map(i, j, a, active_ref):
        return (active_ref[i, j, a], i, 0)

    def s_map(i, j, a, active_ref):
        return (active_ref[i, j, a], j, 0)

    def o_map(i, j, a, active_ref):
        del a, active_ref
        return (i, j)

    return pl.pallas_call(
        _score_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, block_r, tile), r_map),
                pl.BlockSpec((1, block_s, tile), s_map),
            ],
            out_specs=pl.BlockSpec((block_r, block_s), o_map),
        ),
        out_shape=jax.ShapeDtypeStruct((n_r, n_s), jnp.float32),
        interpret=interpret,
    )(active, r_tiles, s_tiles)
