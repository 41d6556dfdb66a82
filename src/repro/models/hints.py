"""GSPMD sharding hints for model internals.

GSPMD occasionally partitions a contraction dimension inside scan bodies
(the stacked loop buffers lose the propagated head sharding), turning every
attention chunk into a partial-sum all-reduce. `shard_hint` pins the
preferred layout when a mesh is set (`jax.set_mesh`) and one of its axes
fits; without a mesh, or when no axis divides the dimension, it returns x
unchanged, so model code stays mesh-agnostic.  A constraint that is
placed and then fails raises.
"""
from __future__ import annotations

import os

import jax
from jax.sharding import PartitionSpec


def _active_mesh():
    if os.environ.get("REPRO_DISABLE_HINTS"):
        return None
    mesh = jax.sharding.get_abstract_mesh()
    return None if mesh.empty else mesh


def shard_hint(x, *dim_axes):
    """Constrain x's sharding: dim_axes[i] = mesh axis name, a tuple of
    candidate names (first match wins), or None. Dims beyond len(dim_axes)
    stay unspecified. No-op when no mesh is set or nothing matches."""
    mesh = _active_mesh()
    if mesh is None:
        return x
    shape = dict(mesh.shape)
    spec = []
    used = set()
    for dim, cand in zip(x.shape, dim_axes):
        if cand is None:
            spec.append(None)
            continue
        cands = cand if isinstance(cand, tuple) else (cand,)
        pick = None
        for ax in cands:
            if (ax in shape and ax not in used and shape[ax] > 1
                    and dim % shape[ax] == 0 and dim >= shape[ax]):
                pick = ax
                break
        spec.append(pick)
        if pick:
            used.add(pick)
    spec += [None] * (x.ndim - len(spec))
    if not any(spec):
        return x
    return jax.lax.with_sharding_constraint(x, PartitionSpec(*spec))
