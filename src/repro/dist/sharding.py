"""Sharding inference for the ("agent", "replica", "model") training mesh
and the ("pod",) ("data", "model") production serving mesh.

The workhorse is `greedy_spec`: given an array shape and a dict of mesh
axis sizes, assign each mesh axis (largest first) to the largest
still-unassigned dimension it divides exactly.  Dimensions nothing
divides stay replicated — whisper's 51865-token vocab, odd head counts,
biases, scalars all fall out naturally instead of needing per-leaf
special cases.

Concrete sharding trees built on top of it:

  param_shardings       — generic pytree -> NamedSharding tree,
                          optional leading (agent) axis.
  state_shardings       — the API-BCD train-state dict
                          {"params", "token", "zhat", "gacc"}.
  batch_shardings       — batch dim over the data-parallel axes.
  train_batch_shardings — [A, B, ...] batches: ("agent", "replica").
  cache_shardings       — stacked KV caches: batch over data axes,
                          kv-head / latent dims over "model".
  pool_shardings        — paged KV block pools: blocks replicated over
                          the data axes (tables gather across blocks),
                          kv-head / latent dims over "model".
"""
from __future__ import annotations

import math

import jax
from jax.sharding import NamedSharding, PartitionSpec as P


def greedy_spec(shape, axis_sizes, skip_leading=0) -> P:
    """Greedy divisible-dim assignment of mesh axes to array dims.

    Axes are considered largest-size first; each is placed on the largest
    dimension (index >= skip_leading) that it divides exactly and that no
    other axis already claimed.  Size-1 axes are never assigned (sharding
    over them is a no-op) and no axis is ever assigned twice.  Dims with
    no divisible axis stay None (replicated) — e.g. whisper's 51865
    vocab.  Returns a PartitionSpec of length == len(shape).
    """
    entries = [None] * len(shape)
    order = sorted(axis_sizes.items(), key=lambda kv: (-kv[1], kv[0]))
    for axis, size in order:
        if size <= 1:
            continue
        best = None
        for i in range(skip_leading, len(shape)):
            if entries[i] is None and shape[i] % size == 0:
                if best is None or shape[i] >= shape[best]:
                    best = i
        if best is not None:
            entries[best] = axis
    return P(*entries)


def _mesh_axes(mesh, names):
    return {a: mesh.shape[a] for a in names if a in mesh.shape}


def _leaf_name(path):
    """Last dict key on a tree path (None for positional-only paths)."""
    for k in reversed(path):
        if hasattr(k, "key"):
            return k.key
    return None


def _prod(xs):
    return math.prod(xs) if xs else 1


def param_shardings(mesh, shapes, leading_axis="agent", axes=None):
    """NamedSharding tree for a parameter pytree.

    leading_axis: mesh axis pinned to dim 0 of every leaf (the agent
    stack), or None for unstacked params (the DP baseline / serving).
    axes: {axis_name: size} candidates for the remaining dims; defaults
    to the mesh's replica/model axes.
    """
    if axes is None:
        axes = _mesh_axes(mesh, ("replica", "model"))
    return jax.tree.map(
        lambda s: NamedSharding(mesh, stacked_spec(s.shape, axes,
                                                   leading_axis)),
        shapes)


def stacked_spec(shape, axes, leading_axis="agent") -> P:
    """PartitionSpec of one leaf: `leading_axis` on dim 0 (None for an
    unstacked leaf), `greedy_spec` over `axes` for the rest."""
    entries = list(greedy_spec(shape, axes,
                               skip_leading=1 if leading_axis else 0))
    if leading_axis and entries:
        entries[0] = leading_axis
    return P(*entries)


def state_shardings(mesh, state_shapes):
    """Shardings for the API-BCD train state.

    params / gacc: agent-stacked, FSDP over "replica" + TP over "model".
    token:         agent-stacked (one token slot per ring position).
    zhat:          [A, M, ...] — agent axis sharded, M replicated.
    """
    axes = _mesh_axes(mesh, ("replica", "model"))

    def zhat_spec(s):
        entries = list(greedy_spec(s.shape, axes, skip_leading=2))
        if entries:
            entries[0] = "agent"
        return NamedSharding(mesh, P(*entries))

    return {
        "params": param_shardings(mesh, state_shapes["params"],
                                  leading_axis="agent", axes=axes),
        "token": param_shardings(mesh, state_shapes["token"],
                                 leading_axis="agent", axes=axes),
        "zhat": jax.tree.map(zhat_spec, state_shapes["zhat"]),
        "gacc": param_shardings(mesh, state_shapes["gacc"],
                                leading_axis="agent", axes=axes),
    }


def batch_shardings(mesh, shapes, batch_axes=None):
    """Shard dim 0 (the batch) over `batch_axes`, replicate the rest.

    batch_axes defaults to the data-parallel axes present in the mesh
    (("pod", "data") on the production mesh).  Falls back to replication
    when the batch does not divide the axis product (e.g. batch 1 on the
    long_500k shape).
    """
    if batch_axes is None:
        batch_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    batch_axes = tuple(a for a in batch_axes
                       if a in mesh.shape and mesh.shape[a] > 1)
    total = _prod([mesh.shape[a] for a in batch_axes])

    def one(s):
        if s.ndim == 0 or not batch_axes or s.shape[0] % total != 0:
            return NamedSharding(mesh, P())
        lead = batch_axes if len(batch_axes) > 1 else batch_axes[0]
        return NamedSharding(mesh, P(lead))

    return jax.tree.map(one, shapes)


def train_batch_shardings(mesh, shapes):
    """[A, B, ...] per-agent batches: agent axis + FSDP rows within."""
    replica = mesh.shape.get("replica", 1)

    def one(s):
        if s.ndim == 0:
            return NamedSharding(mesh, P())
        if s.ndim >= 2 and replica > 1 and s.shape[1] % replica == 0:
            return NamedSharding(mesh, P("agent", "replica"))
        return NamedSharding(mesh, P("agent"))

    return jax.tree.map(one, shapes)


def cache_shardings(mesh, cache_shapes):
    """Shardings for stacked decode caches (leaves [stack, B, ...]).

    Batch (dim 1) goes over the data axes when divisible; attention
    kv-head / MLA latent entries additionally put their per-position
    feature dim over "model" when it divides.  `ptr` scalars and
    recurrent-state leaves that don't fit the pattern replicate.
    """
    daxes = tuple(a for a in ("pod", "data")
                  if a in mesh.shape and mesh.shape[a] > 1)
    dtotal = _prod([mesh.shape[a] for a in daxes])
    model = mesh.shape.get("model", 1)

    def spec_for(path, leaf):
        name = _leaf_name(path)
        if leaf.ndim <= 1 or name == "ptr":
            return P()
        entries = [None] * leaf.ndim
        if daxes and leaf.shape[1] % dtotal == 0:
            entries[1] = daxes if len(daxes) > 1 else daxes[0]
        if (name in ("k", "v") and leaf.ndim >= 4 and model > 1
                and leaf.shape[3] % model == 0):
            entries[3] = "model"            # kv-head axis
        elif (name in ("ckv", "kpe") and leaf.ndim >= 4 and model > 1
                and leaf.shape[3] % model == 0):
            entries[3] = "model"            # latent feature axis
        return P(*entries)

    return jax.tree_util.tree_map_with_path(
        lambda p, leaf: NamedSharding(mesh, spec_for(p, leaf)), cache_shapes)


def pool_shardings(mesh, pool_shapes):
    """Shardings for paged KV block pools (leaves [layers, NB, bs, ...]).

    Block tables index arbitrary blocks each step, so the block dim
    stays replicated over the data axes (sharding it would turn every
    gather into a cross-device shuffle); the per-entry kv-head
    ([layers, NB, bs, KV, hd] k/v) or latent feature dim
    ([layers, NB, bs, r] ckv / kpe) shards over "model" when it
    divides — the paged decode kernel then runs on the local shard,
    exactly like the arena's cache_shardings.
    """
    model = mesh.shape.get("model", 1)

    def spec_for(path, leaf):
        name = _leaf_name(path)
        entries = [None] * leaf.ndim
        if (name in ("k", "v") and leaf.ndim >= 5 and model > 1
                and leaf.shape[3] % model == 0):
            entries[3] = "model"            # kv-head axis
        elif (name in ("ckv", "kpe") and leaf.ndim >= 4 and model > 1
                and leaf.shape[3] % model == 0):
            entries[3] = "model"            # latent feature axis
        return P(*entries)

    return jax.tree_util.tree_map_with_path(
        lambda p, leaf: NamedSharding(mesh, spec_for(p, leaf)), pool_shapes)
