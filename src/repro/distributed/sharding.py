"""Sharding trees for params, optimizer state (ZeRO-1), batches and caches."""
from __future__ import annotations

import numpy as np

import jax
from jax.sharding import NamedSharding, PartitionSpec as PS

from repro.distributed.context import DistContext
from repro.optim.optimizer import OptState


def fsdp_sharding(ctx: DistContext, axes: tuple, shape: tuple) -> NamedSharding:
    """Fully shard a parameter over ALL mesh axes (zero-3/FSDP): the first
    dim divisible by the full mesh size gets the flattened axes; fallbacks
    try the model axis alone; tiny leaves stay replicated."""
    mesh_axes = tuple(ctx.mesh.axis_names)
    total = int(np.prod([ctx.mesh.shape[a] for a in mesh_axes]))
    spec = [None] * len(shape)
    for i, dim in enumerate(shape):
        if dim % total == 0:
            spec[i] = mesh_axes if len(mesh_axes) > 1 else mesh_axes[0]
            return NamedSharding(ctx.mesh, PS(*spec))
    msize = ctx.mesh.shape.get("model", 1)
    for i, dim in enumerate(shape):
        if msize > 1 and dim % msize == 0:
            spec[i] = "model"
            return NamedSharding(ctx.mesh, PS(*spec))
    return NamedSharding(ctx.mesh, PS(*spec))


def params_shardings(ctx: DistContext, axes_tree, abstract_params=None):
    """Map a logical-axes tree (same structure as params) to NamedShardings."""
    if ctx.mode == "fsdp":
        assert abstract_params is not None, "fsdp needs shapes"
        flat_a = jax.tree_util.tree_leaves(
            axes_tree, is_leaf=lambda x: isinstance(x, tuple))
        flat_p = jax.tree_util.tree_leaves(abstract_params)
        treedef = jax.tree_util.tree_structure(abstract_params)
        return jax.tree_util.tree_unflatten(
            treedef, [fsdp_sharding(ctx, a, p.shape)
                      for a, p in zip(flat_a, flat_p)])
    return jax.tree_util.tree_map(
        lambda axes: ctx.sharding(axes),
        axes_tree, is_leaf=lambda x: isinstance(x, tuple))


def _data_axes(ctx: DistContext):
    m = ctx.rules.get("batch")
    if m is None:
        return ()
    return (m,) if isinstance(m, str) else tuple(m)


def zero1_sharding(ctx: DistContext, axes: tuple, shape: tuple) -> NamedSharding:
    """Param sharding + extra data-axis sharding on the first divisible
    unsharded dim (ZeRO-1: optimizer state fully sharded)."""
    base = ctx.pspec(axes)
    data = _data_axes(ctx)
    dsize = int(np.prod([ctx.mesh.shape[a] for a in data])) if data else 1
    used = set()
    for entry in base:
        if entry is not None:
            used.update((entry,) if isinstance(entry, str) else entry)
    if dsize <= 1 or used & set(data):
        return NamedSharding(ctx.mesh, base)  # already data-sharded (e.g. EP
        # expert ffn over data) — ZeRO-1 extra sharding would collide
    spec = list(base) + [None] * (len(shape) - len(base))
    for i, dim in enumerate(shape):
        if spec[i] is None and dim % dsize == 0:
            spec[i] = data if len(data) > 1 else data[0]
            break
    return NamedSharding(ctx.mesh, PS(*spec))


def opt_shardings(ctx: DistContext, axes_tree, abstract_params) -> OptState:
    """Shardings for OptState(step, m, v, master)."""
    flat_axes = jax.tree_util.tree_leaves(
        axes_tree, is_leaf=lambda x: isinstance(x, tuple))
    flat_p = jax.tree_util.tree_leaves(abstract_params)
    treedef = jax.tree_util.tree_structure(abstract_params)
    if ctx.mode == "fsdp":
        shards = [fsdp_sharding(ctx, a, p.shape)
                  for a, p in zip(flat_axes, flat_p)]
    else:
        shards = [zero1_sharding(ctx, a, p.shape)
                  for a, p in zip(flat_axes, flat_p)]
    tree = jax.tree_util.tree_unflatten(treedef, shards)
    rep = NamedSharding(ctx.mesh, PS())
    return OptState(rep, tree, tree, tree)


def batch_pspec(ctx: DistContext, global_batch: int) -> PS | None:
    """Batch dim over the data axes when divisible, else replicated."""
    data = _data_axes(ctx)
    dsize = int(np.prod([ctx.mesh.shape[a] for a in data])) if data else 1
    if dsize > 1 and global_batch % dsize == 0:
        return data if len(data) > 1 else data[0]
    return None


def batch_shardings(ctx: DistContext, batch_tree, global_batch: int):
    b = batch_pspec(ctx, global_batch)

    def one(leaf):
        spec = [b] + [None] * (len(leaf.shape) - 1)
        return NamedSharding(ctx.mesh, PS(*spec))

    return jax.tree_util.tree_map(one, batch_tree)


def cache_shardings(ctx: DistContext, cache_axes_tree, abstract_cache,
                    global_batch: int):
    """Cache axes -> shardings, with the batch rule adjusted for small B."""
    b = batch_pspec(ctx, global_batch)

    def one(axes, leaf):
        spec = []
        used = set()
        for ax in axes:
            if ax == "batch":
                val = b
            else:
                val = ctx.rules.get(ax) if ax is not None else None
            if isinstance(val, (tuple, list)):
                val = tuple(a for a in val if a not in used) or None
            if isinstance(val, str) and val in used:
                val = None
            if val is not None:
                used.update((val,) if isinstance(val, str) else val)
            spec.append(val)
        return NamedSharding(ctx.mesh, PS(*spec))

    return jax.tree_util.tree_map(
        one, cache_axes_tree, abstract_cache,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(e, (str, type(None))) for e in x))
