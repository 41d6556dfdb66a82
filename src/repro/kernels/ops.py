"""Jit-ready wrappers around the Pallas kernels.

Shape plumbing between model layouts ([B,S,H,hd] etc.) and kernel layouts
([BH,S,hd] etc.).  Kernels run compiled on TPU and in interpret mode on
CPU (so the whole suite runs, and is tested, there); any other backend
is refused rather than silently interpreted.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.decode_attention import (decode_attention_grouped,
                                            decode_attention_paged_grouped,
                                            decode_attention_ring_grouped)
from repro.kernels.flash_attention import flash_attention_bhsd
from repro.kernels.prox_update import SUBLANES, prox_update_nd
from repro.kernels.rglru_scan import rglru_scan_bsw
from repro.kernels.rwkv6_scan import rwkv6_scan_bh


def _interpret_default(interpret):
    if interpret is not None:
        return interpret
    platform = jax.default_backend()
    if platform == "tpu":
        return False
    if platform == "cpu":
        return True
    raise NotImplementedError(
        f"Pallas kernels run compiled on TPU or interpreted on CPU; "
        f"backend {platform!r} is neither")


# ---------------------------------------------------------------------------
# prox update
# ---------------------------------------------------------------------------


def prox_update(x, g, zsum, *, tau, rho, num_walks, num_agents,
                in_place=False, interpret=None):
    """Fused gAPI-BCD update on a single array (any shape).

    Returns (x_new, delta) — see kernels/prox_update.py.  The kernel
    blocks the array as it lies: leading dims fold into rows only where
    the second-minor dim fills whole 8-row tiles, so the fold is a
    bitcast, and are otherwise walked by the kernel's grid; a 0-d or 1-D
    array is viewed as [1, n].  in_place: x_new takes x's buffer, for
    callers that no longer read x."""
    interpret = _interpret_default(interpret)
    shape = x.shape
    if x.ndim < 2:
        view = (1, x.size)
    elif shape[-2] % SUBLANES == 0:
        view = (-1, shape[-1])
    else:
        view = shape
    x_new, delta = prox_update_nd(
        x.reshape(view), g.reshape(view), zsum.reshape(view), tau=tau,
        rho=rho, num_walks=num_walks, num_agents=num_agents,
        in_place=in_place, interpret=interpret)
    return x_new.reshape(shape), delta.reshape(shape)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------


def flash_attention(q, k, v, *, causal=True, window=0, scale=None,
                    block_q=128, block_k=128, interpret=None):
    """q: [B,S,H,hd]; k, v: [B,T,KV,hd]. Returns [B,S,H,hd]."""
    interpret = _interpret_default(interpret)
    b, s, h, hd = q.shape
    t, kv = k.shape[1], k.shape[2]
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, s, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(b * kv, t, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(b * kv, t, hd)
    out = flash_attention_bhsd(qf, kf, vf, causal=causal, window=window,
                               scale=scale, block_q=block_q,
                               block_k=block_k, interpret=interpret)
    return out.reshape(b, h, s, hd).transpose(0, 2, 1, 3)


def decode_attention(q, k, v, *, scale=None, valid_len=None, lengths=None,
                     block_k=512, interpret=None):
    """q: [B,H,hd]; k, v: [B,T,KV,hd]. Returns [B,H,hd].

    lengths: int32 [B] per-row valid KV lengths (slot-arena decode where
    each batch row is at its own depth); valid_len: legacy scalar."""
    interpret = _interpret_default(interpret)
    b, h, hd = q.shape
    t, kv = k.shape[1], k.shape[2]
    g = h // kv
    qf = q.reshape(b, kv, g, hd).reshape(b * kv, g, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(b * kv, t, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(b * kv, t, hd)
    if lengths is not None:
        lengths = jnp.repeat(jnp.asarray(lengths, jnp.int32), kv)
    out = decode_attention_grouped(qf, kf, vf, scale=scale,
                                   valid_len=valid_len, lengths=lengths,
                                   block_k=block_k, interpret=interpret)
    return out.reshape(b, kv, g, hd).reshape(b, h, hd)


def decode_attention_paged(q, k_pool, v_pool, block_tables, lengths, *,
                           scale=None, interpret=None):
    """q: [B,H,hd]; k_pool, v_pool: [NB, bs, KV, hd] (shared paged pool);
    block_tables: int32 [B, W]; lengths: int32 [B].  Returns [B,H,hd].

    The paged analogue of `decode_attention`: row b's KV lives in pool
    blocks block_tables[b] and only positions < lengths[b] are valid.
    Tables/lengths are repeated per kv head for the [B*KV] kernel grid.
    """
    interpret = _interpret_default(interpret)
    b, h, hd = q.shape
    kv = k_pool.shape[2]
    g = h // kv
    qf = q.reshape(b, kv, g, hd).reshape(b * kv, g, hd)
    tables = jnp.repeat(jnp.asarray(block_tables, jnp.int32), kv, axis=0)
    lens = jnp.repeat(jnp.asarray(lengths, jnp.int32), kv)
    out = decode_attention_paged_grouped(qf, k_pool, v_pool, tables, lens,
                                         scale=scale, interpret=interpret)
    return out.reshape(b, kv, g, hd).reshape(b, h, hd)


def decode_attention_ring(q, k_pool, v_pool, block_tables, ring_starts,
                          lengths, *, window, scale=None, interpret=None):
    """q: [B,H,hd]; k_pool, v_pool: [NB, bs, KV, hd]; block_tables: int32
    [B, W] ring tables (W = ceil(window / bs)); ring_starts: int32 [B];
    lengths: int32 [B].  Returns [B,H,hd].

    Sliding-window analogue of `decode_attention_paged`: row b's last
    min(lengths[b], window) tokens live in a fixed ring of blocks
    (position p at ring slot p % window), with ring_starts[b] rotating
    the table lookup.  Tables/starts/lengths are repeated per kv head
    for the [B*KV] kernel grid."""
    interpret = _interpret_default(interpret)
    b, h, hd = q.shape
    kv = k_pool.shape[2]
    g = h // kv
    qf = q.reshape(b, kv, g, hd).reshape(b * kv, g, hd)
    tables = jnp.repeat(jnp.asarray(block_tables, jnp.int32), kv, axis=0)
    starts = jnp.repeat(jnp.asarray(ring_starts, jnp.int32), kv)
    lens = jnp.repeat(jnp.asarray(lengths, jnp.int32), kv)
    out = decode_attention_ring_grouped(qf, k_pool, v_pool, tables, starts,
                                        lens, window=window, scale=scale,
                                        interpret=interpret)
    return out.reshape(b, kv, g, hd).reshape(b, h, hd)


# ---------------------------------------------------------------------------
# recurrences
# ---------------------------------------------------------------------------


def rwkv6_scan(r, k, v, w, u, *, chunk=128, interpret=None):
    """r,k,v,w: [B,H,S,hd]; u: [H,hd]. Returns out [B,H,S,hd]."""
    interpret = _interpret_default(interpret)
    b, h, s, hd = r.shape
    def fold(a):
        return a.reshape(b * h, s, hd)
    ub = jnp.broadcast_to(u[None, :, None, :], (b, h, 1, hd)
                          ).reshape(b * h, 1, hd)
    out = rwkv6_scan_bh(fold(r), fold(k), fold(v), fold(w), ub,
                        chunk=chunk, interpret=interpret)
    return out.reshape(b, h, s, hd)


def rglru_scan(a, u, *, chunk=128, block_w=512, interpret=None):
    """a, u: [B,S,W] -> h [B,S,W]."""
    interpret = _interpret_default(interpret)
    return rglru_scan_bsw(a, u, chunk=chunk, block_w=block_w,
                          interpret=interpret)
