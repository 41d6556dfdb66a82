"""Fused gAPI-BCD closed-form update kernel.

The paper's per-superstep hot spot: for every parameter element,
    x_new  = (rho * x - g + tau * zsum) / (rho + tau * M)       (eq. 15)
    delta  = (x_new - x) / N                                    (eq. 12b)
Unfused, this reads x three times and writes twice across four jnp ops;
the kernel does one VMEM pass producing both outputs.

Layout: the kernel is elementwise, so it reads and writes each array in
its own shape and layout.  Blocks cover the last two dims (the TPU's
tiled ones); any leading dims are walked by the grid, one index per
step.  A block takes the whole last dim where 8 rows of it fit the block
budget, else a lane-aligned slice of it, and as many rows as the budget
then allows (a multiple of 8, or all of them), counting the block as
VMEM pads it to whole (8, 128) tiles; edge blocks may be ragged.  The
Pallas call is named "prox_update", so the kernel keeps its name in a
profiler trace.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

SUBLANES = 8
LANES = 128
# elements of one operand's block as VMEM holds it (rows padded to 8,
# the last dim to whole 128-lane tiles): 5 operands x 2 buffers x 1 MiB
# (f32) stay within the default scoped VMEM
BLOCK_ELEMS = 256 * 1024


def _kernel(x_ref, g_ref, z_ref, xo_ref, do_ref, *, tau, rho, m, n):
    x = x_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    z = z_ref[...].astype(jnp.float32)
    denom = rho + tau * m
    x_new = (rho * x - g + tau * z) / denom
    xo_ref[...] = x_new.astype(xo_ref.dtype)
    do_ref[...] = ((x_new - x) / n).astype(do_ref.dtype)


def _block_shape(rows, cols):
    """(block_rows, block_cols) for a [..., rows, cols] operand, sized
    by the block's padded footprint, so a narrow last dim (64 lanes of a
    128-lane tile) gets fewer rows, not a larger block."""
    lanes = pl.cdiv(cols, LANES) * LANES
    bc = cols if lanes * SUBLANES <= BLOCK_ELEMS else BLOCK_ELEMS // SUBLANES
    lanes = pl.cdiv(bc, LANES) * LANES
    br = BLOCK_ELEMS // lanes // SUBLANES * SUBLANES
    return min(br, rows), bc


def prox_update_nd(x, g, zsum, *, tau, rho, num_walks, num_agents,
                   in_place=False, interpret=False):
    """x, g, zsum: one shape, rank >= 2. Returns (x_new, delta[f32]).

    in_place: x_new takes x's buffer (each block is read before it is
    written), for callers that no longer need x."""
    *lead, rows, cols = x.shape
    br, bc = _block_shape(rows, cols)
    grid = (*lead, pl.cdiv(rows, br), pl.cdiv(cols, bc))
    spec = pl.BlockSpec((None,) * len(lead) + (br, bc), lambda *idx: idx)
    kern = functools.partial(_kernel, tau=float(tau), rho=float(rho),
                             m=float(num_walks), n=float(num_agents))
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[spec, spec, spec],
        out_specs=(spec, spec),
        out_shape=(jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(x.shape, jnp.float32)),
        input_output_aliases={0: 0} if in_place else {},
        interpret=interpret,
        name="prox_update",
    )(x, g, zsum)
