"""Join-planner calibration: measure the C2/C3 unit costs of the dense
(BF) and indexed (IIB) scans on this device and write the record that
``core.engine.plan(calibration=...)`` reads.

  PYTHONPATH=src python -m benchmarks.roofline --calibrate out.json [--full]
"""
from __future__ import annotations

import json
from typing import Dict


def calibrate(out_path: str, fast: bool = True) -> Dict:
    """Measure the join planner's C2/C3 unit costs on THIS device and write
    a calibration record (``--calibrate out.json``).

    The engine's ``plan()`` charges ``c2_unit * n_r * n_s * T * tile`` for
    BF and ``c3_unit * n_r * n_s * E[tiles/row] * tile`` for the indexed
    side; the hard-coded defaults assume a fixed 4x indexed-work overhead.
    Here both sides run for real (warm, best-of-3) on a mid-size shape and
    the measured wall times divide out the SAME work formulas, so
    ``plan(..., calibration=...)`` turns its scores into wall-second
    estimates with the machine's true dense/indexed throughput ratio.
    """
    import jax

    from benchmarks.common import gen, timed
    from repro.core.engine import JoinSpec, SparseKNNIndex
    from repro.sparse.format import num_tiles

    n_r, n_s, dim, nnz = (128, 512, 4096, 32) if fast else (256, 2048, 8192, 64)
    tile = 128
    R = gen("synthetic", n_r, seed=0, dim=dim, nnz=nnz)
    S = gen("synthetic", n_s, seed=1, dim=dim, nnz=nnz)
    walls = {}
    occupied = None
    for alg in ("bf", "iib"):
        idx = SparseKNNIndex.build(
            S, JoinSpec(k=5, algorithm=alg, r_block=n_r // 2, s_block=n_s // 4)
        )
        occupied = idx.occupied_tiles
        idx.query(R)                      # compile warmup
        _, walls[alg] = timed(idx.query, R, repeat=3)

    t = num_tiles(dim, tile)
    t_eff = max(1, min(occupied, t))
    tiles_per_row = t_eff * (1.0 - (1.0 - 1.0 / t_eff) ** nnz)
    c2 = walls["bf"] / (n_r * n_s * t * tile)
    c3 = walls["iib"] / (n_r * n_s * tiles_per_row * tile)
    record = {
        "c2_unit_s": c2,
        "c3_unit_s": c3,
        "index_cost_factor": c3 / c2,
        "config": {
            "n_r": n_r, "n_s": n_s, "dim": dim, "nnz_mean": nnz, "tile": tile,
            "occupied_tiles": int(t_eff),
            "wall_bf_s": round(walls["bf"], 5), "wall_iib_s": round(walls["iib"], 5),
            "backend": jax.default_backend(),
        },
    }
    with open(out_path, "w") as f:
        json.dump(record, f, indent=1)
        f.write("\n")
    print(f"calibration (index_cost_factor={c3 / c2:.2f}) -> {out_path}")
    return record


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--calibrate", metavar="OUT.json", required=True,
                    help="measure C2/C3 unit costs for plan(calibration=...)")
    ap.add_argument("--full", action="store_true",
                    help="calibrate on the full (slower) shape")
    args = ap.parse_args()
    calibrate(args.calibrate, fast=not args.full)
