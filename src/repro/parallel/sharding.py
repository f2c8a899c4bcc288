"""Sharding rules: parameter-path -> PartitionSpec (FSDP + TP + EP + SP).

Scheme (DESIGN.md §5), on mesh axes (data, model) [+ pod]:
  * column-parallel packed weights (N1, K1, N0, K0): N1 -> model, K1 -> fsdp
  * row-parallel    packed weights              : N1 -> fsdp,  K1 -> model
  * embedding (V, D): vocab-parallel (V -> model)
  * KV caches: batch -> data, cache-seq -> model (decode sequence parallelism);
    recurrent states: heads/width -> model
  * small vectors (norms, biases, router, decay params): replicated
  * batch: leading dim over (pod,) data

Every spec is *sanitized* against the concrete shape: a mesh axis that does
not divide its dimension is dropped (e.g. batch=1 in long_500k stays
replicated instead of failing to lower).  Desired-vs-effective sharding is
thereby decoupled from the shape grid.
"""

from __future__ import annotations


import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# Path-name classification for packed (or plain transposed) weights.
_COLUMN_NAMES = {
    "wq", "wk", "wv", "w_gate", "w_up", "cm_wk", "w_in", "w_gate_branch",
    "wr", "wg", "w_a", "w_x", "fc1", "fc2", "head",
}
_ROW_NAMES = {"wo", "w_down", "cm_wv", "w_out"}
_REPLICATED_NAMES = {"router"}


def _axis_size(mesh: Mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    size = 1
    for a in axes:
        size *= mesh.shape[a]
    return size


def sanitize(spec: P, shape: tuple[int, ...], mesh: Mesh) -> P:
    """Drop axes that don't divide their dimension."""
    parts = list(spec) + [None] * (len(shape) - len(spec))
    out = []
    for dim, axes in zip(shape, parts):
        if axes is None:
            out.append(None)
            continue
        tup = (axes,) if isinstance(axes, str) else tuple(axes)
        kept: list[str] = []
        for a in tup:
            size = _axis_size(mesh, tuple(kept + [a]))
            if dim % size == 0:
                kept.append(a)
        out.append(tuple(kept) if len(kept) > 1 else (kept[0] if kept else None))
    return P(*out)


def _fsdp_axes(mesh: Mesh) -> tuple[str, ...]:
    return tuple(a for a in ("data", "pod") if a in mesh.axis_names)


def _dp_axes(mesh: Mesh) -> tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def _path_names(path) -> list[str]:
    names = []
    for p in path:
        if hasattr(p, "key"):
            names.append(str(p.key))
        elif hasattr(p, "idx"):
            names.append(f"[{p.idx}]")
        elif hasattr(p, "name"):
            names.append(str(p.name))
    return names


def param_spec(path, leaf, mesh: Mesh, *, fsdp: bool = True) -> P:
    """PartitionSpec for one parameter leaf, from its tree path."""
    names = _path_names(path)
    leafname = names[-1] if names else ""
    owner = names[-2] if len(names) >= 2 else ""
    fa = _fsdp_axes(mesh) if fsdp else ()
    nd = leaf.ndim

    def packed_spec(n1_axes, k1_axes):
        # (..., N1, K1, N0, K0): leading dims (layer-stack, experts) unsharded.
        lead = [None] * (nd - 4)
        return P(*lead, n1_axes, k1_axes, None, None)

    if leafname == "w_scale" and nd >= 2:  # int8 per-channel scales (..., N1, N0)
        is_col = owner in _COLUMN_NAMES
        lead = [None] * (nd - 2)
        return P(*lead, "model", None) if is_col else P(*lead, fa or None, None)
    if leafname in ("w_packed", "w_q") or (leafname == "w_t" and nd >= 2):
        if owner in _REPLICATED_NAMES:
            return P(*([None] * nd))
        is_col = owner in _COLUMN_NAMES
        if leafname == "w_t":
            lead = [None] * (nd - 2)
            return P(*lead, "model", fa or None) if is_col else P(*lead, fa or None, "model")
        return packed_spec("model", fa or None) if is_col else packed_spec(fa or None, "model")
    if leafname == "embed":
        return P("model", None)
    if leafname == "dec_pos_embed":
        return P(None, None)
    if leafname == "b" and owner in _COLUMN_NAMES and nd == 1:
        return P("model")
    # Norms, biases, conv weights, decay params, mus, loras: replicated.
    return P(*([None] * nd))


def params_shardings(params, mesh: Mesh, *, fsdp: bool = True):
    def one(path, leaf):
        spec = param_spec(path, leaf, mesh, fsdp=fsdp)
        return NamedSharding(mesh, sanitize(spec, leaf.shape, mesh))

    return jax.tree_util.tree_map_with_path(one, params)


def batch_shardings(batch, mesh: Mesh):
    dp = _dp_axes(mesh)

    def one(leaf):
        spec = P(dp, *([None] * (leaf.ndim - 1)))
        return NamedSharding(mesh, sanitize(spec, leaf.shape, mesh))

    return jax.tree_util.tree_map(one, batch)


def cache_shardings(caches, mesh: Mesh):
    """KV caches (G?, B, S, KV, hd): batch->data, seq->model (SP decode).
    Recurrent states (G?, B, ...): batch->data, first state dim -> model."""
    dp = _dp_axes(mesh)

    def one(path, leaf):
        names = _path_names(path)
        leafname = names[-1] if names else ""
        nd = leaf.ndim
        if leafname in ("k", "v", "cross_k", "cross_v") and nd >= 4:
            lead = [None] * (nd - 4)
            spec = P(*lead, dp, "model", None, None)
        elif leafname == "S" and nd >= 4:  # rwkv state (..., B, H, dk, dv)
            lead = [None] * (nd - 4)
            spec = P(*lead, dp, "model", None, None)
        elif leafname == "h" and nd >= 2:  # rg-lru state (..., B, rw)
            lead = [None] * (nd - 2)
            spec = P(*lead, dp, "model")
        elif leafname == "conv" and nd >= 3:
            lead = [None] * (nd - 3)
            spec = P(*lead, dp, None, "model")
        elif leafname in ("shift_tm", "shift_cm") and nd >= 2:
            lead = [None] * (nd - 2)
            spec = P(*lead, dp, "model")
        else:
            spec = P(*([None] * nd))
        return NamedSharding(mesh, sanitize(spec, leaf.shape, mesh))

    return jax.tree_util.tree_map_with_path(one, caches)


def serving_cache_shardings(caches, mesh: Mesh):
    """Serving (tensor-parallel) KV-cache shardings: KV HEADS -> model.

    The training-time `cache_shardings` shards the sequence axis (decode
    SP); the serving engine instead runs head-parallel attention — each
    shard owns the K/V slice of its own kv-head group, matching the
    column-parallel wk/wv projections, so attention needs NO collective
    until the row-parallel wo matmul's psum.  Covers both cache layouts:

      dense  k/v: (G?, B,         S,     KV, hd)  -> heads (axis -2) on model
      paged  k/v: (G?, num_pages, block, KV, hd)  -> heads (axis -2) on model
      table     : (..., nb) block tables          -> replicated (host-mirrored)

    `sanitize` drops the axis when kv_heads doesn't divide the shard count
    (e.g. the reduced test configs' kv=1 under tp=2) — the cache replicates
    and GSPMD still produces identical tokens, just without the capacity
    win (docs/DESIGN.md §Tensor-parallel serving).

    Prefix-cache interaction: the radix tree, refcounts, tenant ledgers and
    LRU clock are HOST-side metadata, mirrored per shard by
    `ShardedBlockAllocator` (serving/paged.py) — nothing of the tree lives
    on device.  Because every shard runs the identical, deterministic
    plan/commit/evict sequence, page number N means "prefix block X" on
    every shard simultaneously, and a cache hit revives the full kv-head
    slice set of that page with no collective: each shard's pool rows for
    page N already hold that shard's head slice, sharded by the rule
    above."""

    def one(path, leaf):
        names = _path_names(path)
        leafname = names[-1] if names else ""
        nd = leaf.ndim
        # Quantized-layout scale pages (G?, num_pages, block, KV, 1) carry
        # their kv-head axis at -2 exactly like the data pages they scale;
        # they must shard alongside them or a shard would dequantize its
        # head slice with another shard's magnitudes.
        if leafname in (
            "k", "v", "cross_k", "cross_v", "k_scale", "v_scale"
        ) and nd >= 4:
            spec = P(*([None] * (nd - 2)), "model", None)
        else:
            spec = P(*([None] * nd))
        return NamedSharding(mesh, sanitize(spec, leaf.shape, mesh))

    return jax.tree_util.tree_map_with_path(one, caches)


def replicated(mesh: Mesh):
    return NamedSharding(mesh, P())


def spec_tree_for(tree, mesh: Mesh, kind: str):
    if kind == "params":
        return params_shardings(tree, mesh)
    if kind == "batch":
        return batch_shardings(tree, mesh)
    if kind == "caches":
        return cache_shardings(tree, mesh)
    raise ValueError(kind)
