"""Public op: streaming top-k merge with padding plumbing."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.topk_merge.kernel import topk_merge_pallas

NEG_INF = jnp.float32(-jnp.inf)


def topk_merge(
    state_scores: jax.Array,
    state_ids: jax.Array,
    cand_scores: jax.Array,
    cand_ids: jax.Array,
    block_rows: int = 256,
    chunk_m: int = 256,
    interpret: bool = False,
):
    """Merge (N, M) candidates into the running (N, k) state. Exact top-k."""
    n, k = state_scores.shape
    m = cand_scores.shape[1]
    if cand_ids.ndim == 1:
        cand_ids = jnp.broadcast_to(cand_ids[None, :], (n, m))

    br = min(block_rows, n)
    n_pad = -(-n // br) * br
    cm = min(chunk_m, m)
    m_pad = -(-m // cm) * cm

    def pad(x, rows, cols, fill):
        out = jnp.full((rows, cols), fill, x.dtype)
        return out.at[: x.shape[0], : x.shape[1]].set(x)

    ss = pad(state_scores.astype(jnp.float32), n_pad, k, NEG_INF)
    si = pad(state_ids.astype(jnp.int32), n_pad, k, -1)
    cs = pad(cand_scores.astype(jnp.float32), n_pad, m_pad, NEG_INF)
    ci = pad(cand_ids.astype(jnp.int32), n_pad, m_pad, -1)

    out_s, out_i = topk_merge_pallas(
        ss, si, cs, ci, block_rows=br, chunk_m=cm, interpret=interpret
    )
    return out_s[:n], out_i[:n]
