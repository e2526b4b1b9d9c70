"""The benchmark's own data generators, vectorised over rows.

They follow the paper's evaluation data (arXiv:1011.2807, section 5) as the
program's ``repro.sparse.datagen`` describes it, but they are the
benchmark's copy: a change to the program's generators cannot change what
a cell measures.  Each is a file ``generators/<name>.py`` with
``generate(cfg, n, structure, seed)``; a configuration's ``data`` names
one (``generator``) and holds its parameters and ``structure_seed``.

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

import layout


def distinct_sorted(idx: np.ndarray, dim: int, redraw) -> np.ndarray:
    """Sort each row and redraw repeated dims until every row holds
    distinct dims; ``dim`` marks padding and is never redrawn."""
    while True:
        idx.sort(axis=1)
        dup = (idx[:, 1:] == idx[:, :-1]) & (idx[:, 1:] < dim)
        if not dup.any():
            return idx
        tail = idx[:, 1:]
        tail[dup] = redraw(int(dup.sum()))


def generate(cfg: dict, n: int, seed: int, part: int):
    """``n`` rows of the configuration's kind from the generator its
    ``data`` names.  ``part`` keeps a run's batches apart: 0 for S, 1 for
    the query pool."""
    d = cfg["data"]
    structure, seed = [d["structure_seed"], part], [seed, part]
    return layout.generator(d["generator"])(cfg, n, structure, seed)
