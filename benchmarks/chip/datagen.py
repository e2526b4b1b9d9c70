"""The benchmark's own data generators, vectorised over rows.

They follow the paper's evaluation data (arXiv:1011.2807, section 5) as the
program's ``repro.sparse.datagen`` describes it, but they are the
benchmark's copy: a change to the program's generators cannot change what
a cell measures.

A batch's sparsity pattern (how many non-zeros each row has, and in which
dims) comes from the configuration's own ``structure_seed``; its weights
come from the run's seed.  Every seed so asks the program the same shapes
and the same tile work, and only the answers change: a run compiles
nothing that the cell's first run did not, and two seeds time the same
work.  The same seed gives the same rows.

A batch is three host arrays, the padded-CSR layout the program takes:
``idx`` (n, F) int32 ascending per row and padded with ``dim``, ``val``
(n, F) float32 with 0 in padding, and ``nnz`` (n,) int32.
"""
from __future__ import annotations

import numpy as np


def _distinct_sorted(idx: np.ndarray, dim: int, redraw) -> np.ndarray:
    """Sort each row and redraw repeated dims until every row holds
    distinct dims; ``dim`` marks padding and is never redrawn."""
    while True:
        idx.sort(axis=1)
        dup = (idx[:, 1:] == idx[:, :-1]) & (idx[:, 1:] < dim)
        if not dup.any():
            return idx
        tail = idx[:, 1:]
        tail[dup] = redraw(int(dup.sum()))


def synthetic(n: int, dim: int, nnz_mean: float, nnz_std: float,
              w_low: float, w_high: float, structure: list, seed: list):
    """Section 5.1: |x| ~ N(nnz_mean, nnz_std) clipped to [1, dim] distinct
    dims drawn uniformly (from ``structure``), weights ~ U(w_low, w_high]
    (from ``seed``)."""
    rng = np.random.default_rng(structure)
    nnz = np.clip(rng.normal(nnz_mean, nnz_std, n).astype(np.int64), 1, dim)
    f = int(nnz.max())
    pad = np.arange(f)[None, :] >= nnz[:, None]
    idx = rng.integers(0, dim, (n, f))
    idx[pad] = dim
    idx = _distinct_sorted(idx, dim, lambda m: rng.integers(0, dim, m))
    val = np.random.default_rng(seed).uniform(w_low, w_high, (n, f)).astype(np.float32)
    val[pad] = 0.0
    return idx.astype(np.int32), val, nnz.astype(np.int32)


def spectra(n: int, dim: int, peaks_mean: float, spread: float,
            structure: list, seed: list):
    """Section 5.2, spectra: Poisson(peaks_mean) peaks (at least 4) placed
    around a precursor position drawn U(0.1, 0.9)·dim with a normal spread
    of ``spread``·dim, clipped to the dim range; peaks that land on one dim
    merge (all from ``structure``).  Intensities are exponential (from
    ``seed``) and scaled so each row's largest is 1."""
    rng = np.random.default_rng(structure)
    k = np.maximum(4, rng.poisson(peaks_mean, n))
    f = int(k.max())
    pad = np.arange(f)[None, :] >= k[:, None]
    base = rng.uniform(0.1, 0.9, n) * dim
    pos = base[:, None] + rng.normal(0.0, dim * spread, (n, f))
    idx = np.clip(pos.astype(np.int64), 0, dim - 1)
    idx[pad] = dim
    idx.sort(axis=1)
    dup = np.zeros_like(pad)
    dup[:, 1:] = (idx[:, 1:] == idx[:, :-1]) & (idx[:, 1:] < dim)
    idx[dup] = dim
    idx.sort(axis=1)
    nnz = (idx < dim).sum(axis=1)
    f = int(nnz.max())
    idx = idx[:, :f]
    val = np.random.default_rng(seed).exponential(1.0, (n, f)).astype(np.float32)
    val[idx >= dim] = 0.0
    val /= val.max(axis=1, keepdims=True)
    return idx.astype(np.int32), val, nnz.astype(np.int32)


def generate(cfg: dict, n: int, seed: int, part: int):
    """Rows of the configuration's kind: ``cfg["data"]`` names the
    generator and holds its parameters and ``structure_seed``.  ``part``
    keeps a run's batches apart: 0 for S, 1 for the query pool."""
    d = cfg["data"]
    structure, seed = [d["structure_seed"], part], [seed, part]
    if d["generator"] == "synthetic":
        return synthetic(n, cfg["dim"], d["nnz_mean"], d["nnz_std"],
                         d["weight_low"], d["weight_high"], structure, seed)
    if d["generator"] == "spectra":
        return spectra(n, cfg["dim"], d["peaks_mean"], d["position_spread"],
                       structure, seed)
    raise ValueError(f"unknown generator {d['generator']!r}")
