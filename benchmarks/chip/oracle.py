"""The plain float64 reference and the comparison that decides ``correct``.

The reference is brute force on the host: S as a float64 CSR matrix times
the dense float64 query rows, then the k largest scores of each row.  It
imports nothing of the program and takes nothing the program made.

Two numbers are compared, each against its limit:

- ``gap``, limited by the configuration file's ``check``: the widest of
  two gaps, each as a share of the row's best reference score.  One is
  between a score the program returned and the reference's score at the
  same rank, which reads the precision of the scores.  The other is
  between the reference's score at a rank and the float64 score of the id
  the program returned there: a tie returned in another order reads 0, a
  wrong neighbour the distance to the right one.
- ``bad_rows``, limit 0: rows with an id outside S (a row left without
  an answer reads -1), an id twice, or another number of ranks than its
  request asked for.

Each row is compared over the ranks its request asked for: the first k
of the reference's top k, where a served request asks for fewer.

Only ranks whose reference score is above 0 are compared: a row that
overlaps fewer than k stored rows has no defined neighbour beyond them.
"""
from __future__ import annotations

import numpy as np
import scipy.sparse as sp

CHUNK = 128  # reference rows per matrix product: bounds host memory


def csr64(idx: np.ndarray, val: np.ndarray, nnz: np.ndarray, dim: int):
    """The padded rows as a float64 CSR matrix."""
    mask = np.arange(idx.shape[1])[None, :] < nnz[:, None]
    indptr = np.concatenate([[0], np.cumsum(nnz, dtype=np.int64)])
    return sp.csr_matrix(
        (val[mask].astype(np.float64), idx[mask].astype(np.int64), indptr),
        shape=(idx.shape[0], dim))


def _dense64(idx, val, nnz, dim):
    out = np.zeros((idx.shape[0], dim), np.float64)
    mask = np.arange(idx.shape[1])[None, :] < nnz[:, None]
    rows = np.broadcast_to(np.arange(idx.shape[0])[:, None], idx.shape)
    out[rows[mask], idx[mask]] = val[mask]
    return out


class Reference:
    """Float64 top-k of query rows against S, with each row's scores kept
    so that any returned id can be scored too."""

    def __init__(self, s_csr, k: int):
        self.s = s_csr
        self.k = k

    def run(self, idx, val, nnz, dim):
        """Reference top-k of the given rows; returns (scores, ids, table)
        where ``table`` (rows, |S|) holds every float64 score."""
        m = idx.shape[0]
        table = np.empty((m, self.s.shape[0]), np.float64)
        for lo in range(0, m, CHUNK):
            hi = min(lo + CHUNK, m)
            q = _dense64(idx[lo:hi], val[lo:hi], nnz[lo:hi], dim)
            table[lo:hi] = (self.s @ q.T).T
        k = min(self.k, table.shape[1])
        part = np.argpartition(-table, k - 1, axis=1)[:, :k]
        top = np.take_along_axis(table, part, axis=1)
        order = np.lexsort((part, -top), axis=1)
        ids = np.take_along_axis(part, order, axis=1)
        scores = np.take_along_axis(top, order, axis=1)
        return scores, ids, table


def compare(prog_ids, prog_scores, ref_scores, table) -> dict:
    """Compare the program's answers row by row with the reference.

    ``prog_ids[i]`` / ``prog_scores[i]`` hold row i's answer and
    ``ref_scores[i]`` the reference's best scores over the ranks row i
    asked for.  Returns the two numbers of the module docstring."""
    gap = 0.0
    bad = 0
    n_s = table.shape[1]
    for i, (ids, scores) in enumerate(zip(prog_ids, prog_scores)):
        ids = np.asarray(ids).astype(np.int64).ravel()
        scores = np.asarray(scores, np.float64).ravel()
        ref = np.asarray(ref_scores[i])
        live = ref > 0
        if not live.any():
            continue
        if ids.size != ref.size or scores.size != ref.size:
            bad += 1
            continue
        lid = ids[live]
        if (lid < 0).any() or (lid >= n_s).any() or len(np.unique(lid)) < lid.size:
            bad += 1
            continue
        scale = ref[0]
        gap = max(gap, float(np.max(np.abs(scores[live] - ref[live]))) / scale,
                  float(np.max(np.abs(table[i, lid] - ref[live]))) / scale)
    return {"gap": gap, "bad_rows": bad}


def verdict(numbers: dict, limits: dict) -> bool:
    """True when every compared number is within its limit."""
    return all(numbers[name] <= limits[name] for name in numbers)
