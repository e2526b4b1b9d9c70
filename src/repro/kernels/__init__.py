"""Pallas TPU kernels for the paper's compute hot-spots.

  knn_score  — tile-skipping blocked score matmul (IIB/IIIB scoring)
  topk_merge — streaming top-k candidate-set insert
  knn_topk   — fused score→top-k: the knn_score matmul with the topk_merge
               insertion body as a per-S-block epilogue; block score
               matrices stay in VMEM (the engine's device-resident query
               hot path)

Each subpackage: kernel.py (pl.pallas_call + BlockSpec), ops.py (jit'd
public wrapper with padding plumbing), ref.py (pure-jnp oracle).  Kernels
compile for TPU; CPU callers (tests) ask for ``interpret=True`` explicitly.
"""
