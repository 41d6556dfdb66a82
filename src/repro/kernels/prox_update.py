"""Fused gAPI-BCD closed-form update kernel.

The paper's per-superstep hot spot: for every parameter element,
    x_new  = (rho * x - g + tau * zsum) / (rho + tau * M)       (eq. 15)
    delta  = (x_new - x) / N                                    (eq. 12b)
Unfused, this reads x three times and writes twice across four jnp ops;
the kernel does one VMEM pass producing both outputs.

Layout: parameters are flattened and tiled as [rows, 1024] (8*128 lanes,
MXU/VPU aligned); the grid walks row blocks.  The Pallas call is named
"prox_update", so the kernel keeps its name in a profiler trace.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 1024          # 8 sublanes x 128 lanes
DEF_BLOCK_ROWS = 256


def _kernel(x_ref, g_ref, z_ref, xo_ref, do_ref, *, tau, rho, m, n):
    x = x_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    z = z_ref[...].astype(jnp.float32)
    denom = rho + tau * m
    x_new = (rho * x - g + tau * z) / denom
    xo_ref[...] = x_new.astype(xo_ref.dtype)
    do_ref[...] = ((x_new - x) / n).astype(do_ref.dtype)


def prox_update_2d(x, g, zsum, *, tau, rho, num_walks, num_agents,
                   block_rows=DEF_BLOCK_ROWS, interpret=False):
    """x, g, zsum: [rows, LANE] tiles. Returns (x_new, delta[f32])."""
    rows = x.shape[0]
    block_rows = min(block_rows, rows)
    grid = (pl.cdiv(rows, block_rows),)
    spec = pl.BlockSpec((block_rows, LANE), lambda i: (i, 0))
    kern = functools.partial(_kernel, tau=float(tau), rho=float(rho),
                             m=float(num_walks), n=float(num_agents))
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[spec, spec, spec],
        out_specs=(spec, spec),
        out_shape=(jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(x.shape, jnp.float32)),
        interpret=interpret,
        name="prox_update",
    )(x, g, zsum)
