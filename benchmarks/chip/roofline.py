"""The least work any implementation of the scan has to do, and the chip's
peaks to set it against.

For query rows R against the stored rows S, with |S_d| the number of
stored rows that hold dimension d:

- ops = 2 · Σ over the rows of R of Σ over their dims d of |S_d|: one
  multiply and one add for every pair of non-zeros that meet, the paper's
  C3 scan work (section 4);
- bytes = 8 · nnz(S) per 2,048 query rows (S's ids and weights read once
  per block of 2,048 rows), plus 8 · nnz(R) read and 8 · k written per
  query row.  The 2,048 is part of the yardstick, not read from the
  program's block size.

The least time is the larger of ops over the peak FLOP/s and bytes over
the peak HBM bandwidth.  These counts come from the generated data on the
host and do not change with the code that implements the scan.
"""
from __future__ import annotations

import json
import pathlib

import numpy as np

ROWS_PER_S_PASS = 2048
PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"


def dim_counts(s_idx: np.ndarray, s_nnz: np.ndarray, dim: int) -> np.ndarray:
    """|S_d| for every dimension d."""
    mask = np.arange(s_idx.shape[1])[None, :] < s_nnz[:, None]
    return np.bincount(s_idx[mask], minlength=dim).astype(np.int64)


def scan_work(r_idx, r_nnz, counts, s_nnz_total: int, k: int):
    """(ops, bytes) of the scan for the given query rows."""
    mask = np.arange(r_idx.shape[1])[None, :] < r_nnz[:, None]
    ops = 2 * int(counts[r_idx[mask]].sum())
    rows = r_idx.shape[0]
    nbytes = (8 * s_nnz_total * rows / ROWS_PER_S_PASS
              + 8 * int(r_nnz.sum()) + 8 * k * rows)
    return ops, nbytes


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip of this kind; an unknown kind is an
    error, never a default."""
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r}")
    return table[device_kind]


def least_time_s(ops: float, nbytes: float, peak: dict) -> float:
    return max(ops / peak["flops_per_s"], nbytes / peak["hbm_bytes_per_s"])
