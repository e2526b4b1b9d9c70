"""Sharding rules: param pytree paths -> PartitionSpecs.

Strategy (DESIGN.md §5):

* Batch / activations — data-parallel over ``(pod, data)``; the residual
  stream is additionally *sequence-sharded* over ``model`` between blocks
  (Megatron-SP, installed via models.shardctx) when the sequence length
  divides the model axis — this is what keeps 40-layer × 4k-token remat
  carries inside HBM.
* Parameters — TP over ``model`` (attention heads / d_ff / vocab / expert
  axis) + FSDP over ``data``.  Across ``pod`` parameters are REPLICATED:
  cross-pod links are the slowest, so they carry only the once-per-step
  gradient all-reduce (optionally int8-compressed), never per-layer
  all-gathers.
* Optimizer state mirrors the parameter sharding (ZeRO for free).
* KV caches / recurrent state — batch over data, head/feature over model.

Rules are name-targeted with a generic size-based fallback so every
family (incl. rwkv6 / rglru parameter shapes) gets a legal spec: an axis
is only sharded if its size divides the mesh axis.
"""
from __future__ import annotations

from typing import Any, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def _div(n: int, k: int) -> bool:
    return k > 0 and n % k == 0 and n >= k


def _leaf_path_strs(tree):
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    return [(jax.tree_util.keystr(kp), leaf) for kp, leaf in flat], treedef


# ---------------------------------------------------------------------------
# parameter specs
# ---------------------------------------------------------------------------

REPLICATE_BELOW = 1 << 16  # leaves smaller than this stay replicated


def param_spec(path: str, shape: Tuple[int, ...], mesh: Mesh, mode: str = "2d") -> P:
    """PartitionSpec for one parameter leaf.

    mode="2d": TP over 'model' + FSDP over 'data' (default).
    mode="fsdp": no tensor parallelism — every leaf FSDP-sharded over the
    combined ('data','model') axes.  Right for archs whose core op cannot
    split over 'model' (e.g. rwkv's 40 heads on a 16-way axis): activation
    gathers disappear; only per-layer param all-gathers remain.
    """
    dsz = mesh.shape.get("data", 1)
    msz = mesh.shape.get("model", 1)
    ndim = len(shape)
    spec = [None] * ndim
    if ndim == 0 or int(np.prod(shape)) < REPLICATE_BELOW:
        return P(*spec)

    if mode == "fsdp":
        first = 1 if ("stack" in path and ndim >= 2) else 0
        both = dsz * msz
        order = sorted(range(first, ndim), key=lambda a: -shape[a])
        for a in order:
            if _div(shape[a], both):
                spec[a] = ("data", "model")
                return P(*spec)
        # fall back: largest axis over whichever single axis divides
        for a in order:
            if _div(shape[a], dsz):
                spec[a] = "data"
                return P(*spec)
        return P(*spec)

    in_stack = "stack" in path
    first = 1 if (in_stack and ndim >= 2) else 0  # never shard the scan axis

    def place(axis: int, name: str, size: int) -> bool:
        if spec[axis] is None and _div(shape[axis], size):
            spec[axis] = name
            return True
        return False

    lower = path.lower()

    # --- name-targeted rules ----------------------------------------------
    if "pos_embed" in lower or ("embed" in lower and not in_stack):
        # (V, d): vocab -> model (TP vocab shard), d -> data (FSDP)
        place(0, "model", msz) or place(1, "model", msz)
        place(1, "data", dsz) or place(0, "data", dsz)
        return P(*spec)
    if "lm_head" in lower:
        place(ndim - 1, "model", msz)     # vocab
        place(ndim - 2, "data", dsz)
        return P(*spec)
    if ndim - first >= 3 and ("w_gate" in lower or "w_up" in lower or "w_down" in lower):
        if mode == "2d_etp":
            # expert tensor-parallelism: shard INSIDE each expert (ff over
            # model) — no token all-to-all, one psum per MoE layer instead.
            if "w_down" in lower:
                place(ndim - 2, "model", msz)   # row-parallel (ff input)
                place(ndim - 1, "data", dsz)
            else:
                place(ndim - 1, "model", msz)   # col-parallel (ff output)
                place(ndim - 2, "data", dsz)
            return P(*spec)
        # MoE expert stacks (L, E, d, ff): experts -> model (EP)
        place(first, "model", msz)
        # largest remaining axis -> data
        rest = sorted(range(first + 1, ndim), key=lambda a: -shape[a])
        for a in rest:
            if place(a, "data", dsz):
                break
        return P(*spec)
    if "w_o" in lower or "w_down" in lower or "w_out" in lower:
        # row-parallel: shard the INPUT-feature axis over model
        place(ndim - 2, "model", msz) or place(ndim - 1, "model", msz)
        place(ndim - 1, "data", dsz) or (ndim - 2 != first and place(ndim - 2, "data", dsz))
        return P(*spec)

    # --- generic: col-parallel last axis, FSDP the next --------------------
    if ndim - first >= 2:
        place(ndim - 1, "model", msz)
        # largest remaining (non-scan) axis -> data
        rest = sorted(
            (a for a in range(first, ndim) if spec[a] is None), key=lambda a: -shape[a]
        )
        for a in rest:
            if place(a, "data", dsz):
                break
    elif ndim - first == 1:
        place(ndim - 1, "model", msz) or place(ndim - 1, "data", dsz)
    return P(*spec)


def param_shardings(abstract_params, mesh: Mesh, mode: str = "2d"):
    """Pytree of NamedShardings mirroring the (abstract) param tree."""
    leaves, treedef = _leaf_path_strs(abstract_params)
    out = [
        NamedSharding(mesh, param_spec(path, leaf.shape, mesh, mode))
        for path, leaf in leaves
    ]
    return jax.tree_util.tree_unflatten(treedef, out)


def opt_shardings(abstract_opt_state, param_shards, mesh: Mesh):
    """m/v mirror the params; scalars replicated."""
    rep = NamedSharding(mesh, P())
    return {
        "m": param_shards,
        "v": param_shards,
        "step": rep,
    }


# ---------------------------------------------------------------------------
# KNN store stacks (repro.store)
# ---------------------------------------------------------------------------

def store_stack_specs(tree, axes) -> Any:
    """Pytree of PartitionSpecs sharding every leaf's LEADING axis over the
    store's shard axes (the rest replicated) — the layout of the sharded
    KNN datastore's per-shard index stacks: leaf shapes are
    ``(num_shards, blocks, ...)``, one shard slice per device."""
    axes = tuple(axes) if isinstance(axes, (tuple, list)) else (axes,)
    return jax.tree.map(lambda leaf: P(axes, *([None] * (leaf.ndim - 1))), tree)


def store_put(tree, mesh: Mesh, axes):
    """Place a store stack pytree on the mesh, leading axis sharded."""
    specs = store_stack_specs(tree, axes)
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), tree, specs)


def store_shard_update(arr, i: int, new_slice) -> "jax.Array":
    """Replace shard ``i``'s leading-axis slice of an already-placed store
    stack IN PLACE of a full re-placement: only the devices whose buffer
    covers row ``i`` receive new bytes (``device_put`` of the one-shard
    slice); every other device keeps its existing buffer, and the pieces
    reassemble into a new Array with the same sharding.  This is what
    makes mutation placement O(changed shard), not O(store) — the
    incremental-placement half of the ROADMAP's replication item.

    ``new_slice`` must already be padded to the stack's cross-shard
    maxima: shape ``(1,) + arr.shape[1:]``.  Callers that grew the global
    geometry (more blocks, wider list bound) must fall back to a full
    ``store_put`` — a stale-shaped buffer cannot be patched.
    """
    new_slice = np.asarray(new_slice)
    if new_slice.shape != (1,) + arr.shape[1:]:
        raise ValueError(
            f"slice shape {new_slice.shape} does not match stack row "
            f"{(1,) + arr.shape[1:]} — geometry changed, use store_put")
    bufs = []
    for s in arr.addressable_shards:
        sl = s.index[0]
        lo = 0 if sl.start is None else sl.start
        hi = arr.shape[0] if sl.stop is None else sl.stop
        if lo <= i < hi:
            local = new_slice if hi - lo == 1 else None
            if local is None:
                # device holds several shard rows: patch row i inside its
                # existing local buffer
                local = np.asarray(s.data).copy()
                local[i - lo] = new_slice[0]
            bufs.append(jax.device_put(
                np.asarray(local, dtype=arr.dtype), s.device))
        else:
            bufs.append(s.data)
    return jax.make_array_from_single_device_arrays(
        arr.shape, arr.sharding, bufs)


# ---------------------------------------------------------------------------
# batch / cache specs
# ---------------------------------------------------------------------------

def batch_spec(shape: Tuple[int, ...], mesh: Mesh, mode: str = "2d") -> P:
    """Input batch leaf: axis0 = global batch over DP axes (if divisible).
    mode="fsdp": the model axis joins DP, so batch shards over everything."""
    from repro.launch.mesh import dp_axes

    dp = dp_axes(mesh)
    if mode == "fsdp":
        dp = dp + ("model",)
    dpsz = int(np.prod([mesh.shape[a] for a in dp]))
    spec = [None] * len(shape)
    if shape and _div(shape[0], dpsz):
        spec[0] = dp
    elif shape and "data" in mesh.axis_names and _div(shape[0], mesh.shape["data"]):
        spec[0] = "data"
    return P(*spec)


def batch_shardings(abstract_batch, mesh: Mesh, mode: str = "2d"):
    leaves, treedef = _leaf_path_strs(abstract_batch)
    out = [NamedSharding(mesh, batch_spec(leaf.shape, mesh, mode)) for _, leaf in leaves]
    return jax.tree_util.tree_unflatten(treedef, out)


def cache_spec(path: str, shape: Tuple[int, ...], mesh: Mesh,
               batch: int = 0) -> P:
    """KV-cache / recurrent-state leaf: stacked (L, ..., B, ...) — the batch
    axis (located by ``batch`` size hint, else assumed axis 1) over DP, one
    feature axis over model (largest trailing axis that divides)."""
    from repro.launch.mesh import dp_axes

    ndim = len(shape)
    if "slot_pos" in path:          # per-window bookkeeping, tiny: replicate
        return P(*([None] * ndim))
    dp = dp_axes(mesh)
    dpsz = int(np.prod([mesh.shape[a] for a in dp]))
    msz = mesh.shape.get("model", 1)
    spec = [None] * ndim
    # locate the batch axis: first axis (excluding the leading stack axis)
    # whose extent equals the global batch; rank-6 vlm caches put it at 2.
    b_axis = None
    if batch:
        for a in range(1, ndim):
            if shape[a] == batch:
                b_axis = a
                break
    if b_axis is None and ndim >= 2:
        b_axis = 1
    if b_axis is not None and _div(shape[b_axis], dpsz):
        spec[b_axis] = dp
    cands = sorted(range((b_axis or 1) + 1, ndim), key=lambda a: -shape[a])
    for a in cands:
        if spec[a] is None and _div(shape[a], msz):
            spec[a] = "model"
            break
    return P(*spec)


def cache_shardings(abstract_cache, mesh: Mesh, batch: int = 0):
    leaves, treedef = _leaf_path_strs(abstract_cache)
    out = [
        NamedSharding(mesh, cache_spec(path, leaf.shape, mesh, batch))
        for path, leaf in leaves
    ]
    return jax.tree_util.tree_unflatten(treedef, out)


# ---------------------------------------------------------------------------
# activation constraint (sequence parallelism)
# ---------------------------------------------------------------------------

def make_activation_constraint(mesh: Mesh, seq_shard: bool = True, mode: str = "2d"):
    """Residual-stream constraint fn for models.shardctx.

    mode="2d": (B, S, d) — batch over DP axes; seq over ``model`` when
    divisible (Megatron-SP — layer I/O lives sharded, attention gathers
    internally).  mode="fsdp": batch over ALL axes, nothing else sharded.
    """
    from repro.launch.mesh import dp_axes

    dp = dp_axes(mesh)
    if mode == "fsdp":
        dp = dp + ("model",)
    dpsz = int(np.prod([mesh.shape[a] for a in dp]))
    msz = mesh.shape.get("model", 1)

    def constrain(x):
        if x.ndim != 3:
            return x
        b, s, _ = x.shape
        bspec = dp if _div(b, dpsz) else None
        sspec = (
            "model" if (mode == "2d" and seq_shard and _div(s, msz)) else None
        )
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, P(bspec, sspec, None))
        )

    return constrain


def make_named_constraint(mesh: Mesh, mode: str = "2d"):
    """Named tensor constraints (MoE dispatch path).

    In "2d" mode the MoE intermediates pin their expert axis to the EP
    shards ('model'), so the dispatch/expert einsums run local and only
    the combine output crosses shards (one psum per MoE layer):

      moe_dispatch (G, Tg, E, C) -> P(dp, None, 'model', None)
      moe_expert   (G, E, C, d)  -> P(dp, 'model', None, None)
      moe_out      (G, Tg, d)    -> P(dp, None, None)
    """
    from repro.launch.mesh import dp_axes

    dp = dp_axes(mesh)
    dpsz = int(np.prod([mesh.shape[a] for a in dp]))
    msz = mesh.shape.get("model", 1)
    if mode == "fsdp":
        dp = dp + ("model",)
        dpsz *= msz

    def named(x, kind):
        g = x.shape[0]
        gspec = dp if _div(g, dpsz) else None
        if mode != "2d":
            spec = [gspec] + [None] * (x.ndim - 1)
            return jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, P(*spec))
            )
        if kind == "moe_dispatch" and x.ndim == 4 and _div(x.shape[2], msz):
            spec = P(gspec, None, "model", None)
        elif kind == "moe_expert" and x.ndim == 4 and _div(x.shape[1], msz):
            spec = P(gspec, "model", None, None)
        elif kind == "moe_out" and x.ndim == 3:
            spec = P(gspec, None, None)
        else:
            return x
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))

    return named
