"""Binned mass spectra, after the paper's real-world data
(arXiv:1011.2807, section 5.2).

``data``: ``peaks_mean``, ``position_spread``.
"""
import numpy as np


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


def generate(cfg: dict, n: int, structure: list, seed: list):
    d = cfg["data"]
    return spectra(n, cfg["dim"], d["peaks_mean"], d["position_spread"],
                   structure, seed)
