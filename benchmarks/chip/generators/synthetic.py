"""The paper's synthetic data (arXiv:1011.2807, section 5.1).

``data``: ``nnz_mean``, ``nnz_std``, ``weight_low``, ``weight_high``.
"""
import numpy as np

import datagen


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
    idx = datagen.distinct_sorted(idx, dim, lambda m: rng.integers(0, dim, m))
    val = np.random.default_rng(seed).uniform(w_low, w_high, (n, f)).astype(np.float32)
    val[pad] = 0.0
    return idx.astype(np.int32), val, nnz.astype(np.int32)


def generate(cfg: dict, n: int, structure: list, seed: list):
    d = cfg["data"]
    return synthetic(n, cfg["dim"], d["nnz_mean"], d["nnz_std"],
                     d["weight_low"], d["weight_high"], structure, seed)
