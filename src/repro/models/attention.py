"""Attention: GQA (full/sliding-window/local), MLA, cross-attention.

Prefill/training uses a memory-efficient chunked ("flash-style") reference
in pure jnp — peak activation is O(q_chunk * kv_chunk) instead of O(S^2) —
so 32k prefill lowers within HBM even before the Pallas kernel is used.
The Pallas TPU kernels in repro.kernels implement the same math; model code
switches via use_pallas (CPU/dry-run keeps the jnp path).

Decode uses single-token attention against a KV cache; for MLA the decode
path uses the *absorbed* formulation (attention in the compressed latent
space, O(kv_lora) per position instead of materializing K/V).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.hints import shard_hint
from repro.models.layers import (
    _he, apply_rope, rmsnorm, rmsnorm_init, yarn_mscale,
)

_NEG_INF = -1e30


# ---------------------------------------------------------------------------
# GQA projections
# ---------------------------------------------------------------------------


def gqa_init(key, cfg, dtype):
    d, h, kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": _he(ks[0], (d, h * hd), dtype),
        "wk": _he(ks[1], (d, kv * hd), dtype),
        "wv": _he(ks[2], (d, kv * hd), dtype),
        "wo": _he(ks[3], (h * hd, d), dtype, fan_in=h * hd),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h * hd,), dtype)
        p["bk"] = jnp.zeros((kv * hd,), dtype)
        p["bv"] = jnp.zeros((kv * hd,), dtype)
    if cfg.qk_norm:
        p["q_norm"] = rmsnorm_init(hd, dtype)
        p["k_norm"] = rmsnorm_init(hd, dtype)
    return p


def _project_qkv(params, cfg, x, positions):
    """x [B,S,D] -> q [B,S,KV,G,hd], k/v [B,S,KV,hd] with rope applied."""
    b, s, _ = x.shape
    h, kv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    g = h // kv
    q = x @ params["wq"]
    k = x @ params["wk"]
    v = x @ params["wv"]
    if cfg.qkv_bias:
        q = q + params["bq"]
        k = k + params["bk"]
        v = v + params["bv"]
    q = q.reshape(b, s, h, hd)
    k = k.reshape(b, s, kv, hd)
    v = v.reshape(b, s, kv, hd)
    if cfg.qk_norm:
        q = rmsnorm(params["q_norm"], q)
        k = rmsnorm(params["k_norm"], k)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    q = q.reshape(b, s, kv, g, hd)
    return q, k, v


# ---------------------------------------------------------------------------
# chunked (flash-style) attention — jnp reference used for train/prefill
# ---------------------------------------------------------------------------


def chunked_attention(q, k, v, *, causal=True, window=0,
                      q_chunk=1024, kv_chunk=1024, q_offset=0, scale=None):
    """Online-softmax attention with O(chunk^2) activation memory.

    q: [B, S, KV, G, hd]; k, v: [B, T, KV, hd].
    window > 0 limits attention to the last `window` positions (inclusive).
    q_offset: absolute position of q[0] (for cross-chunk decode/prefill).
    scale: the softmax scale, 1/sqrt(hd) unless given.
    Returns [B, S, KV, G, hd].

    Internally everything is head-major [B, H, s, hd] (k/v broadcast over
    the GQA group): head dims shard cleanly under GSPMD, so the chunk
    loops stay collective-free on a TP mesh (the [b,s,kv,g,hd] layout
    provoked contraction-sharded score all-reduces every chunk).
    """
    b, s, kvh, g, hd = q.shape
    hd_v = v.shape[-1]                 # may differ from qk dim (MLA)
    t = k.shape[1]
    h = kvh * g
    q_chunk = min(q_chunk, s)
    kv_chunk = min(kv_chunk, t)

    # head-major layouts
    qh = q.reshape(b, s, h, hd).transpose(0, 2, 1, 3)       # [b,h,s,hd]
    kh = jnp.broadcast_to(k[:, :, :, None, :],
                          (b, t, kvh, g, hd)).reshape(b, t, h, hd)
    kh = kh.transpose(0, 2, 1, 3)                            # [b,h,t,hd]
    vh = jnp.broadcast_to(v[:, :, :, None, :],
                          (b, t, kvh, g, hd_v)).reshape(b, t, h, hd_v)
    vh = vh.transpose(0, 2, 1, 3)                            # [b,h,t,hd_v]

    s_pad = -s % q_chunk
    t_pad = -t % kv_chunk
    if s_pad:
        qh = jnp.pad(qh, ((0, 0), (0, 0), (0, s_pad), (0, 0)))
    if t_pad:
        kh = jnp.pad(kh, ((0, 0), (0, 0), (0, t_pad), (0, 0)))
        vh = jnp.pad(vh, ((0, 0), (0, 0), (0, t_pad), (0, 0)))
    nq, nkv = (s + s_pad) // q_chunk, (t + t_pad) // kv_chunk

    if scale is None:
        scale = float(1.0 / np.sqrt(hd))
    qh = qh.reshape(b, h, nq, q_chunk, hd)
    kh = kh.reshape(b, h, nkv, kv_chunk, hd)
    vh = vh.reshape(b, h, nkv, kv_chunk, hd_v)
    # pin head sharding on the loop-stacked buffers (GSPMD otherwise may
    # shard the contraction dim and all-reduce every score chunk)
    qh = shard_hint(qh, ("replica", "data"), "model")
    kh = shard_hint(kh, ("replica", "data"), "model")
    vh = shard_hint(vh, ("replica", "data"), "model")

    def q_block(carry_q):
        qi, qblk = carry_q                       # qblk [b,h,qc,hd]
        q_idx = q_offset + qi * q_chunk + jnp.arange(q_chunk)

        def kv_step(carry, inp):
            m, l, acc = carry
            ki, kblk, vblk = inp                 # [b,h,kc,hd]
            kv_idx = ki * kv_chunk + jnp.arange(kv_chunk)
            logits = jnp.einsum("bhqd,bhcd->bhqc",
                                qblk.astype(jnp.float32),
                                kblk.astype(jnp.float32)) * scale
            logits = shard_hint(logits, ("replica", "data"), "model")
            mask = jnp.ones((q_chunk, kv_chunk), bool)
            if causal:
                mask &= kv_idx[None, :] <= q_idx[:, None]
            if window > 0:
                mask &= kv_idx[None, :] > q_idx[:, None] - window
            mask &= (kv_idx < t)[None, :]        # padding
            logits = jnp.where(mask[None, None], logits, _NEG_INF)
            m_new = jnp.maximum(m, logits.max(axis=-1))
            p = jnp.exp(logits - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhqc,bhcd->bhqd", p, vblk.astype(jnp.float32))
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, h, q_chunk), _NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, h, q_chunk), jnp.float32)
        a0 = jnp.zeros((b, h, q_chunk, hd_v), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0),
            (jnp.arange(nkv), jnp.moveaxis(kh, 2, 0),
             jnp.moveaxis(vh, 2, 0)))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return out                               # [b,h,qc,hd_v]

    # flash semantics: recompute the inner kv scan in backward instead of
    # saving per-chunk probabilities (otherwise backward holds O(S^2))
    outs = jax.lax.map(jax.checkpoint(q_block),
                       (jnp.arange(nq), jnp.moveaxis(qh, 2, 0)))
    # outs: [nq, b, h, qc, hd_v] -> [b, s, kv, g, hd_v]
    out = jnp.moveaxis(outs, 0, 2)               # [b,h,nq,qc,hd_v]
    out = out.reshape(b, h, nq * q_chunk, hd_v)[:, :, :s]
    out = out.transpose(0, 2, 1, 3).reshape(b, s, kvh, g, hd_v)
    return out.astype(v.dtype)


def gqa_prefill(params, cfg, x, positions, window=0):
    """Full prefill/training attention. Returns [B,S,D]."""
    b, s, d = x.shape
    q, k, v = _project_qkv(params, cfg, x, positions)
    win = window if window else cfg.attn_window
    out = chunked_attention(q, k, v, causal=True, window=win)
    out = out.reshape(b, s, cfg.num_heads * cfg.head_dim)
    return out @ params["wo"], (k, v)


# ---------------------------------------------------------------------------
# decode attention (one new token vs KV cache)
# ---------------------------------------------------------------------------


def ring_insert(buf, entry, ptr):
    """buf [B,T,...], entry [B,...] -> write at slot ptr % T.

    ptr is the running token count, so slot i%T always holds token i —
    ring eviction drops the oldest cached token.  ptr may be a scalar
    (all rows at the same depth — the wave/legacy path) or an int vector
    [B] (slot-arena continuous batching: each row writes at its own
    per-row position).
    """
    t = buf.shape[1]
    if jnp.ndim(ptr) == 0:
        return jax.lax.dynamic_update_index_in_dim(
            buf, entry[:, None].astype(buf.dtype), ptr % t, axis=1
        ).reshape(buf.shape)
    return jax.vmap(
        lambda row, e, p: jax.lax.dynamic_update_index_in_dim(
            row, e[None].astype(row.dtype), p % t, axis=0)
    )(buf, entry, ptr).reshape(buf.shape)


def prefill_cache_entries(seq_entries, capacity, s):
    """Store the last `capacity` of s prefill entries so slot i%T holds
    token i (consistent ring eviction in subsequent decode). Pads with
    zeros when the prompt is shorter than the capacity (slots >= s are
    masked out by the decode validity mask until written)."""
    t = capacity
    if s < t:
        pad = [(0, 0)] * seq_entries.ndim
        pad[1] = (0, t - s)
        return jnp.pad(seq_entries, pad)
    kept = seq_entries[:, -t:]
    if s > t:
        kept = jnp.roll(kept, shift=s % t, axis=1)
    return kept


def gqa_decode(params, cfg, x, cache, position, window=0):
    """x: [B,1,D]; cache: {k, v: [B,T,KV,hd], ptr} (ptr = tokens written).

    Inserts the new token's K/V first, then attends over all valid slots
    (so the token attends to itself); returns ([B,1,D], new cache).
    ptr (and position) may be scalar or per-row [B] — the latter is the
    slot-arena continuous-batching path where every row decodes at its
    own depth.
    """
    del window
    b = x.shape[0]
    h, kv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    pos = jnp.full((b, 1), position) if jnp.ndim(position) == 0 else position
    q, k_new, v_new = _project_qkv(params, cfg, x, pos)
    q = q[:, 0]                                   # [B,KV,G,hd]

    t = cache["k"].shape[1]
    ck = ring_insert(cache["k"], k_new[:, 0], cache["ptr"])
    cv = ring_insert(cache["v"], v_new[:, 0], cache["ptr"])
    num_valid = jnp.minimum(cache["ptr"] + 1, t)

    logits = jnp.einsum("bkgh,btkh->bkgt", q.astype(jnp.float32),
                        ck.astype(jnp.float32)) * float(1.0 / np.sqrt(hd))
    valid = jnp.arange(t) < jnp.reshape(num_valid, (-1, 1))  # [1|B, T]
    logits = jnp.where(valid[:, None, None, :], logits, _NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgt,btkh->bkgh", p, cv.astype(jnp.float32))
    out = out.reshape(b, 1, h * hd).astype(x.dtype)
    new_cache = {"k": ck, "v": cv, "ptr": cache["ptr"] + 1}
    return out @ params["wo"], new_cache


# ---------------------------------------------------------------------------
# paged KV (block-pool) attention
#
# The pool stores KV in fixed-size blocks ([num_blocks + 1, block_size, ...]
# per layer; block 0 is the never-attended null block) and a per-slot block
# table maps logical position p to physical entry (table[p // bs], p % bs).
# The slot arena is the degenerate case of one contiguous block per slot:
# GQA and MLA decode/prefill math below is identical to the arena path, so
# the two modes are bit-compatible (tests assert token-level identity).
#
# Ring-paged layout (window > 0, GQA only): the table is a FIXED ring of
# ceil(window / bs) blocks and logical position p lives at ring slot
# p % window — physical entry (table[(p % window) // bs], (p % window) % bs).
# This is exactly the arena's ring (`ring_insert` writes at ptr % T with
# T = window), so ring slot i always holds the latest token with position
# ≡ i (mod window) and the decode validity mask min(length, window) over
# ring-slot indices is the arena's mask verbatim: the paged ring stays
# bit-compatible with the arena sliding-window path, and once the ring is
# full decode REUSES blocks instead of allocating (the whole point).  Two
# ring-specific hazards the window paths below handle:
#   * a chunk's PAD entries map to ring slots that hold valid wrapped
#     context (in the linear layout pads land harmlessly past validity),
#     so the windowed chunk scatter routes pads to the null block via the
#     chunk's valid length;
#   * ring context entries are not "valid below ctx_len": ring slot j
#     holds position p_j = ctx_len-1 - ((ctx_len-1-j) % window), and a
#     chunk query at position q sees it only when j < min(ctx_len, window)
#     AND p_j > q - window.  Chunk self-attention additionally masks
#     kv more than `window` behind the query (inert while the engine
#     clamps chunks to <= window — which it must anyway, or two chunk
#     positions would scatter to the same ring slot with unspecified
#     winner).
#
# Overwrite-before-valid: every KV position is written (scatter_chunk_pages
# during prefill, scatter_token_pages as decode crosses it) strictly before
# the validity length covers it, and positions at or above the validity
# length contribute exp(-1e30 - max) == 0.0 exactly to the softmax — so any
# stale content below a future write is bitwise inert, and so is the table
# width itself (a wider slice only adds masked null-block lanes).  The
# overlapped admission scheduler (repro.serve.engine) leans on this twice:
#   * a dead slot's zeroed device table row routes the fused decode's
#     writes to the null block while the slot's real prompt blocks fill
#     through a private table riding the same launch generation;
#   * preempting a mid-admission slot frees blocks that in-flight prefill
#     launches still write to — whatever re-allocates them rewrites every
#     entry before any position becomes valid, so the stale writes never
#     surface.
# The ARENA decode does not share this property: ring_insert advances a
# cache-carried per-(layer, slot) ptr and writes at it, so a dead arena
# slot is only inert until something stores real content in its row.  The
# engine therefore admits arena requests through the mixed step only,
# whose trace runs decode (dead-row garbage write) before the prefill's
# _write_slot fully overwrites the row and resets the ptr.
# ---------------------------------------------------------------------------


def gather_pages(pool, table):
    """pool [NB, bs, ...]; table int32 [B, W] -> linear [B, W * bs, ...].

    Position p of row b lands at index p: logical order is preserved, so
    the gathered buffer is exactly the arena row the block table encodes
    (unallocated entries gather the null block and are masked by the
    caller's validity length)."""
    b, w = table.shape
    g = pool[table]                             # [B, W, bs, ...]
    return g.reshape((b, w * pool.shape[1]) + pool.shape[2:])


def scatter_chunk_pages(pool, entries, table, start, window=0, valid=None):
    """Write a prefill chunk's entries into one slot's blocks.

    pool [NB, bs, ...]; entries [C, ...]; table int32 [W]; start = traced
    absolute position of entries[0].  Positions past the table's range
    are routed to the null block 0 (the engine sizes tables so only the
    padded chunk tail can land there; pad entries written into real
    blocks are inert — they sit beyond the slot's validity length and
    are overwritten by decode before ever becoming valid).

    window > 0 makes the table a ring: position p writes ring slot
    p % window.  Pads are NOT inert in a wrapped ring (their ring slots
    hold valid earlier context), so `valid` — the chunk's true length —
    must be given and routes entries at or past it to the null block."""
    bs, w = pool.shape[1], table.shape[0]
    c = entries.shape[0]
    p = start + jnp.arange(c)
    if window:
        p = p % window
    bi = p // bs
    in_range = bi < w
    if window:
        in_range &= jnp.arange(c) < valid
    blk = jnp.where(in_range, table[jnp.minimum(bi, w - 1)], 0)
    return pool.at[blk, p % bs].set(entries.astype(pool.dtype))


def scatter_token_pages(pool, entries, tables, positions, window=0):
    """Per-row single-token write: entries [B, ...] at positions[b].

    tables int32 [B, W].  Dead rows (engine: zeroed table + position 0)
    write the null block; live rows write distinct allocated blocks, so
    the batched scatter has no cross-row collisions that matter.
    window > 0: ring layout — position p writes ring slot p % window,
    overwriting the evicted token exactly as the arena's ring_insert."""
    bs = pool.shape[1]
    if window:
        positions = positions % window
    blk = jnp.take_along_axis(tables, (positions // bs)[:, None], 1)[:, 0]
    return pool.at[blk, positions % bs].set(entries.astype(pool.dtype))


def _paged_context_attention(q, k_ctx, v_ctx, k_new, v_new, ctx_len, scale,
                             window=0):
    """Chunk queries vs (gathered context ++ the chunk's own K/V).

    q [B,C,KV,G,hd]; k_ctx/v_ctx [B,T,KV,hd*]; k_new/v_new [B,C,KV,hd*].
    Context keys are valid below ctx_len; chunk keys are causally masked
    within the chunk (padded tail keys sit above every valid query, so
    the causal mask already hides them).  Returns [B,C,KV,G,hd_v].

    window > 0: the context is a RING over ring slots (position p at
    slot p % window).  Ring slot j holds the latest context position
    congruent to j, p_j = ctx_len-1 - ((ctx_len-1-j) % window); chunk
    query q_i = ctx_len + i sees it when j < min(ctx_len, window) and
    p_j > q_i - window, and sees chunk key jj when additionally within
    `window` behind it — together exactly the arena's sliding-window
    causal mask over each query's live positions."""
    t = k_ctx.shape[1]
    c = q.shape[1]
    qf = q.astype(jnp.float32)
    ctx_logits = jnp.einsum("bskgh,btkh->bskgt", qf,
                            k_ctx.astype(jnp.float32)) * scale
    if window:
        j = jnp.arange(t)
        p_j = ctx_len - 1 - (ctx_len - 1 - j) % window        # [T]
        q_pos = ctx_len + jnp.arange(c)                       # [C]
        ctx_valid = ((j < jnp.minimum(ctx_len, window))[None, :]
                     & (p_j[None, :] > q_pos[:, None] - window))  # [C, T]
        ctx_logits = jnp.where(ctx_valid[None, :, None, None, :],
                               ctx_logits, _NEG_INF)
    else:
        ctx_valid = jnp.arange(t) < ctx_len                   # [T]
        ctx_logits = jnp.where(ctx_valid[None, None, None, None, :],
                               ctx_logits, _NEG_INF)
    self_logits = jnp.einsum("bskgh,btkh->bskgt", qf,
                             k_new.astype(jnp.float32)) * scale
    causal = jnp.arange(c)[:, None] >= jnp.arange(c)[None, :]  # [C, C]
    if window:
        causal &= (jnp.arange(c)[:, None] - jnp.arange(c)[None, :]) < window
    self_logits = jnp.where(causal[None, :, None, None, :],
                            self_logits, _NEG_INF)
    logits = jnp.concatenate([ctx_logits, self_logits], axis=-1)
    p = jax.nn.softmax(logits, axis=-1)
    v_all = jnp.concatenate([v_ctx, v_new], axis=1).astype(jnp.float32)
    return jnp.einsum("bskgt,btkh->bskgh", p, v_all)


def gqa_prefill_paged(params, cfg, x, cache, table, ctx_len, window=0,
                      valid=None):
    """One prefill chunk against a paged pool (batch-1 admission).

    x [1,C,D]; cache {k, v: [NB, bs, KV, hd]}; table int32 [W]; ctx_len =
    tokens already in the slot's blocks.  Attends chunk queries to the
    gathered context plus the chunk itself (insert-then-attend, same
    semantics as the arena prefill), scatters the chunk's K/V into the
    slot's blocks.  Returns ([1,C,D], new cache).

    window > 0: the table is a ring over ring slots (position p at slot
    p % window); attention reads the pre-scatter pool (so the ring still
    holds positions ctx_len-window .. ctx_len-1) and the scatter routes
    only the chunk's `valid` true tokens (pads would land on live
    wrapped ring slots)."""
    b, c, _ = x.shape
    h, hd = cfg.num_heads, cfg.head_dim
    positions = ctx_len + jnp.broadcast_to(jnp.arange(c)[None], (b, c))
    q, k_new, v_new = _project_qkv(params, cfg, x, positions)
    k_ctx = gather_pages(cache["k"], table[None])
    v_ctx = gather_pages(cache["v"], table[None])
    out = _paged_context_attention(q, k_ctx, v_ctx, k_new, v_new, ctx_len,
                                   float(1.0 / np.sqrt(hd)), window=window)
    out = out.reshape(b, c, h * hd).astype(x.dtype)
    new_cache = {
        "k": scatter_chunk_pages(cache["k"], k_new[0], table, ctx_len,
                                 window=window, valid=valid),
        "v": scatter_chunk_pages(cache["v"], v_new[0], table, ctx_len,
                                 window=window, valid=valid),
    }
    return out @ params["wo"], new_cache


def gqa_decode_paged(params, cfg, x, cache, tables, lengths, window=0):
    """Per-row decode against a paged pool.

    x [B,1,D]; cache {k, v: [NB, bs, KV, hd]}; tables int32 [B, W];
    lengths int32 [B] = tokens already cached per row (== the absolute
    position of the incoming token).  Inserts the new token's K/V at
    position lengths[b], then attends over the gathered valid entries —
    the same insert-then-attend masked softmax as the arena's
    `gqa_decode`.  Returns ([B,1,D], new cache).

    window > 0: the table is a ring — the token scatters to ring slot
    lengths[b] % window and min(lengths[b]+1, window) ring slots are
    live, exactly the arena's `ring_insert` + capped-mask decode."""
    b = x.shape[0]
    h, hd = cfg.num_heads, cfg.head_dim
    pos = jnp.reshape(lengths, (b, 1))
    q, k_new, v_new = _project_qkv(params, cfg, x, pos)
    q = q[:, 0]                                   # [B,KV,G,hd]

    ck = scatter_token_pages(cache["k"], k_new[:, 0], tables, lengths,
                             window=window)
    cv = scatter_token_pages(cache["v"], v_new[:, 0], tables, lengths,
                             window=window)
    kf = gather_pages(ck, tables)                 # [B, T, KV, hd]
    vf = gather_pages(cv, tables)
    t = kf.shape[1]
    num_valid = lengths + 1
    if window:
        num_valid = jnp.minimum(num_valid, window)

    logits = jnp.einsum("bkgh,btkh->bkgt", q.astype(jnp.float32),
                        kf.astype(jnp.float32)) * float(1.0 / np.sqrt(hd))
    valid = jnp.arange(t) < jnp.reshape(num_valid, (-1, 1))   # [B, T]
    logits = jnp.where(valid[:, None, None, :], logits, _NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgt,btkh->bkgh", p, vf.astype(jnp.float32))
    out = out.reshape(b, 1, h * hd).astype(x.dtype)
    return out @ params["wo"], {"k": ck, "v": cv}


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2 multi-head latent attention)
# ---------------------------------------------------------------------------


def mla_init(key, cfg, dtype):
    """Without a query LoRA (q_lora_rank 0) one `wq` [d, h * qk] takes
    the place of wq_a, q_norm and wq_b."""
    m = cfg.mla
    d, h = cfg.d_model, cfg.num_heads
    qk = m.qk_nope_head_dim + m.qk_rope_head_dim
    ks = jax.random.split(key, 7)
    if m.q_lora_rank:
        q = {"wq_a": _he(ks[0], (d, m.q_lora_rank), dtype),
             "q_norm": rmsnorm_init(m.q_lora_rank, dtype),
             "wq_b": _he(ks[1], (m.q_lora_rank, h * qk), dtype)}
    else:
        q = {"wq": _he(ks[0], (d, h * qk), dtype)}
    return {
        **q,
        "wkv_a": _he(ks[2], (d, m.kv_lora_rank + m.qk_rope_head_dim), dtype),
        "kv_norm": rmsnorm_init(m.kv_lora_rank, dtype),
        "wk_b": _he(ks[3], (m.kv_lora_rank, h * m.qk_nope_head_dim), dtype),
        "wv_b": _he(ks[4], (m.kv_lora_rank, h * m.v_head_dim), dtype),
        "wo": _he(ks[5], (h * m.v_head_dim, d), dtype, fan_in=h * m.v_head_dim),
    }


def _mla_scale(cfg):
    """The softmax scale of every MLA path: 1/sqrt(qk head dim), times
    YaRN's mscale(mscale_all_dim)^2 where the rope is scaled (as
    DeepSeek-V2's reference code sets it)."""
    m = cfg.mla
    scale = float(1.0 / np.sqrt(m.qk_nope_head_dim + m.qk_rope_head_dim))
    rs = cfg.rope_scaling
    if rs is not None and rs.mscale_all_dim:
        scale *= yarn_mscale(rs.factor, rs.mscale_all_dim) ** 2
    return scale


def _mla_q_proj(params, x):
    if "wq" in params:
        return x @ params["wq"]
    return rmsnorm(params["q_norm"], x @ params["wq_a"]) @ params["wq_b"]


def _mla_q(params, cfg, x, positions):
    m = cfg.mla
    b, s, _ = x.shape
    h = cfg.num_heads
    qk = m.qk_nope_head_dim + m.qk_rope_head_dim
    q = _mla_q_proj(params, x)
    q = q.reshape(b, s, h, qk)
    q_nope, q_pe = jnp.split(q, [m.qk_nope_head_dim], axis=-1)
    q_pe = apply_rope(q_pe, positions, cfg.rope_theta, cfg.rope_scaling)
    return q_nope, q_pe


def _mla_ckv(params, cfg, x, positions):
    m = cfg.mla
    kv = x @ params["wkv_a"]
    c_kv, k_pe = jnp.split(kv, [m.kv_lora_rank], axis=-1)
    c_kv = rmsnorm(params["kv_norm"], c_kv)
    k_pe = apply_rope(k_pe[:, :, None, :], positions, cfg.rope_theta,
                      cfg.rope_scaling)[:, :, 0]
    return c_kv, k_pe                              # [B,S,r], [B,S,rope]


def mla_prefill(params, cfg, x, positions):
    """Non-absorbed MLA for prefill (materializes K/V, chunked attention)."""
    m = cfg.mla
    b, s, _ = x.shape
    h = cfg.num_heads
    q_nope, q_pe = _mla_q(params, cfg, x, positions)
    c_kv, k_pe = _mla_ckv(params, cfg, x, positions)
    k_nope = (c_kv @ params["wk_b"]).reshape(b, s, h, m.qk_nope_head_dim)
    v = (c_kv @ params["wv_b"]).reshape(b, s, h, m.v_head_dim)
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe[:, :, None, :],
                                  (b, s, h, m.qk_rope_head_dim))], axis=-1)
    # MLA has no KV grouping: kv heads == heads, group g=1
    out = chunked_attention(q[:, :, :, None, :].reshape(
        b, s, h, 1, q.shape[-1]), k, v, causal=True, scale=_mla_scale(cfg))
    out = out.reshape(b, s, h * m.v_head_dim)
    return out @ params["wo"], (c_kv, k_pe)


def mla_decode(params, cfg, x, cache, position):
    """Absorbed MLA decode: attention in the compressed latent space.

    cache: {ckv [B,T,r], kpe [B,T,rope], ptr}. Inserts the new token's
    latents, then attends over valid slots; per head the nope logits are
    (q_nope W_kb^T) . c_kv — O(r) per position, never materializing K/V.
    ptr/position may be scalar or per-row [B] (slot-arena decode).
    Returns ([B,1,D], new cache).
    """
    m = cfg.mla
    b = x.shape[0]
    h = cfg.num_heads
    pos = jnp.full((b, 1), position) if jnp.ndim(position) == 0 else position
    q_nope, q_pe = _mla_q(params, cfg, x, pos)      # [B,1,H,*]
    new_ckv, new_kpe = _mla_ckv(params, cfg, x, pos)

    t = cache["ckv"].shape[1]
    ckv = ring_insert(cache["ckv"], new_ckv[:, 0], cache["ptr"])
    kpe = ring_insert(cache["kpe"], new_kpe[:, 0], cache["ptr"])
    num_valid = jnp.minimum(cache["ptr"] + 1, t)

    wk_b = params["wk_b"].reshape(m.kv_lora_rank, h, m.qk_nope_head_dim)
    # absorb: q' = q_nope @ wk_b^T  -> [B,H,r]
    q_lat = jnp.einsum("bxhd,rhd->bhr", q_nope.astype(jnp.float32),
                       wk_b.astype(jnp.float32))
    scale = _mla_scale(cfg)
    logits = (jnp.einsum("bhr,btr->bht", q_lat,
                         ckv.astype(jnp.float32))
              + jnp.einsum("bxhd,btd->bht", q_pe.astype(jnp.float32),
                           kpe.astype(jnp.float32))) * scale
    valid = jnp.arange(t) < jnp.reshape(num_valid, (-1, 1))  # [1|B, T]
    logits = jnp.where(valid[:, None, :], logits, _NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    ctx = jnp.einsum("bht,btr->bhr", p, ckv.astype(jnp.float32))
    wv_b = params["wv_b"].reshape(m.kv_lora_rank, h, m.v_head_dim)
    out = jnp.einsum("bhr,rhd->bhd", ctx, wv_b.astype(jnp.float32))
    out = out.reshape(b, 1, h * m.v_head_dim).astype(x.dtype)
    new_cache = {"ckv": ckv, "kpe": kpe, "ptr": cache["ptr"] + 1}
    return out @ params["wo"], new_cache


def mla_prefill_paged(params, cfg, x, cache, table, ctx_len):
    """One MLA prefill chunk against a paged latent pool (batch-1).

    cache {ckv: [NB, bs, r], kpe: [NB, bs, rope]} stores the compressed
    latents (kpe post-rope, as the arena does).  Context K/V are
    reconstructed from the gathered latents via wk_b/wv_b — the same
    non-absorbed math as `mla_prefill` — then the chunk attends to
    context ++ itself and its latents are scattered into the blocks."""
    m = cfg.mla
    b, c, _ = x.shape
    h = cfg.num_heads
    positions = ctx_len + jnp.broadcast_to(jnp.arange(c)[None], (b, c))
    q_nope, q_pe = _mla_q(params, cfg, x, positions)
    new_ckv, new_kpe = _mla_ckv(params, cfg, x, positions)

    ckv_ctx = gather_pages(cache["ckv"], table[None])   # [1, T, r]
    kpe_ctx = gather_pages(cache["kpe"], table[None])   # [1, T, rope]
    t = ckv_ctx.shape[1]

    def expand(ckv, kpe, s):
        # same dtype discipline as mla_prefill: reconstruct K/V in the
        # compute dtype; the attention core casts to f32 for the logits
        ckv = ckv.astype(x.dtype)
        k_nope = (ckv @ params["wk_b"]).reshape(b, s, h, m.qk_nope_head_dim)
        v = (ckv @ params["wv_b"]).reshape(b, s, h, m.v_head_dim)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(kpe[:, :, None, :].astype(k_nope.dtype),
                                      (b, s, h, m.qk_rope_head_dim))], -1)
        return k, v

    k_ctx, v_ctx = expand(ckv_ctx, kpe_ctx, t)
    k_new, v_new = expand(new_ckv, new_kpe, c)
    q = jnp.concatenate([q_nope, q_pe], axis=-1)        # [1,C,H,qk]
    out = _paged_context_attention(
        q.reshape(b, c, h, 1, q.shape[-1]), k_ctx, v_ctx, k_new, v_new,
        ctx_len, _mla_scale(cfg))
    out = out.reshape(b, c, h * m.v_head_dim).astype(x.dtype)
    new_cache = {
        "ckv": scatter_chunk_pages(cache["ckv"], new_ckv[0], table, ctx_len),
        "kpe": scatter_chunk_pages(cache["kpe"], new_kpe[0], table, ctx_len),
    }
    return out @ params["wo"], new_cache


def mla_decode_paged(params, cfg, x, cache, tables, lengths):
    """Absorbed MLA decode against a paged latent pool.

    Identical math to `mla_decode` (latent-space attention, O(r) per
    position) with the linear cache replaced by a block-table gather;
    inserts the incoming token's latents at position lengths[b] first.
    Returns ([B,1,D], new cache)."""
    m = cfg.mla
    b = x.shape[0]
    h = cfg.num_heads
    pos = jnp.reshape(lengths, (b, 1))
    q_nope, q_pe = _mla_q(params, cfg, x, pos)          # [B,1,H,*]
    new_ckv, new_kpe = _mla_ckv(params, cfg, x, pos)

    cc = scatter_token_pages(cache["ckv"], new_ckv[:, 0], tables, lengths)
    cp = scatter_token_pages(cache["kpe"], new_kpe[:, 0], tables, lengths)
    ckv = gather_pages(cc, tables)                      # [B, T, r]
    kpe = gather_pages(cp, tables)
    t = ckv.shape[1]
    num_valid = lengths + 1

    wk_b = params["wk_b"].reshape(m.kv_lora_rank, h, m.qk_nope_head_dim)
    q_lat = jnp.einsum("bxhd,rhd->bhr", q_nope.astype(jnp.float32),
                       wk_b.astype(jnp.float32))
    scale = _mla_scale(cfg)
    logits = (jnp.einsum("bhr,btr->bht", q_lat, ckv.astype(jnp.float32))
              + jnp.einsum("bxhd,btd->bht", q_pe.astype(jnp.float32),
                           kpe.astype(jnp.float32))) * scale
    valid = jnp.arange(t) < jnp.reshape(num_valid, (-1, 1))   # [B, T]
    logits = jnp.where(valid[:, None, :], logits, _NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    ctx = jnp.einsum("bht,btr->bhr", p, ckv.astype(jnp.float32))
    wv_b = params["wv_b"].reshape(m.kv_lora_rank, h, m.v_head_dim)
    out = jnp.einsum("bhr,rhd->bhd", ctx, wv_b.astype(jnp.float32))
    out = out.reshape(b, 1, h * m.v_head_dim).astype(x.dtype)
    return out @ params["wo"], {"ckv": cc, "kpe": cp}


# ---------------------------------------------------------------------------
# fused mixed prefill+decode attention (one projection, two cores)
#
# The unified mixed step's layer body: the incoming hidden states are ONE
# token batch [1, nd + S, D] — nd decode tokens (one per slot, in slot
# order) followed by the admission prompt's S tokens — so the q/k/v
# projections, the output projection, and (in the caller) the MLP and
# unembed run ONCE over all tokens.  Those dense matmuls carry the
# model-parallel collectives; running decode-then-prefill as two
# subgraphs in one jit (the obvious composition) pays them twice and
# makes the "fused" launch cost exactly the sum of its parts.  Only the
# attention cores — a few collective-free per-head contractions — split
# the token batch.
#
# Bit-identity discipline: every per-token op (matmul rows, rope, norms)
# is row-stable across batch shapes on our backends, the decode core
# below is copied from gqa_decode/mla_decode(+_paged) verbatim after the
# projection split, and the prefill core from gqa_prefill/mla_prefill
# (+_paged) likewise — so each side produces bitwise the values the
# standalone launches would, and the serialized-vs-overlapped digest
# gates in tests/benchmarks hold exactly.  Cache writes keep the
# sequential trace's order: decode inserts first (the dead arena slot's
# garbage ring write, the paged null-block routing), then the prefill
# entries land — arena rows are fully overwritten, pool write sets are
# disjoint.
# ---------------------------------------------------------------------------


def _rope_mixed(t, nd, pos_d, pos_p, theta, scaling=None):
    """apply_rope over the concat token axis, one half at a time.

    pos_d [1, nd] / pos_p [1, S] are the halves' own position vectors,
    never concatenated: roping with a position vector built by an
    in-jit concat miscompiles under GSPMD on data x model meshes (the
    same pathology as gathering with a concatenated token-id vector;
    see transformer._mixed_embed).  Rope is elementwise and
    row-stable, so the per-half results are bitwise the concat-rope
    values."""
    return jnp.concatenate([apply_rope(t[:, :nd], pos_d, theta, scaling),
                            apply_rope(t[:, nd:], pos_p, theta, scaling)],
                           axis=1)


def _project_qkv_mixed(params, cfg, x, nd, pos_d, pos_p):
    """`_project_qkv` for the fused mixed batch: ONE set of q/k/v
    matmuls over [1, nd+S, D] (that is the collective win), rope
    applied per half via `_rope_mixed`."""
    b, s, _ = x.shape
    h, kv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    g = h // kv
    q = x @ params["wq"]
    k = x @ params["wk"]
    v = x @ params["wv"]
    if cfg.qkv_bias:
        q = q + params["bq"]
        k = k + params["bk"]
        v = v + params["bv"]
    q = q.reshape(b, s, h, hd)
    k = k.reshape(b, s, kv, hd)
    v = v.reshape(b, s, kv, hd)
    if cfg.qk_norm:
        q = rmsnorm(params["q_norm"], q)
        k = rmsnorm(params["k_norm"], k)
    q = _rope_mixed(q, nd, pos_d, pos_p, cfg.rope_theta)
    k = _rope_mixed(k, nd, pos_d, pos_p, cfg.rope_theta)
    q = q.reshape(b, s, kv, g, hd)
    return q, k, v


def _mla_q_mixed(params, cfg, x, nd, pos_d, pos_p):
    """`_mla_q` for the fused mixed batch (per-half rope)."""
    m = cfg.mla
    b, s, _ = x.shape
    h = cfg.num_heads
    qk = m.qk_nope_head_dim + m.qk_rope_head_dim
    q = _mla_q_proj(params, x)
    q = q.reshape(b, s, h, qk)
    q_nope, q_pe = jnp.split(q, [m.qk_nope_head_dim], axis=-1)
    q_pe = _rope_mixed(q_pe, nd, pos_d, pos_p, cfg.rope_theta,
                       cfg.rope_scaling)
    return q_nope, q_pe


def _mla_ckv_mixed(params, cfg, x, nd, pos_d, pos_p):
    """`_mla_ckv` for the fused mixed batch (per-half rope)."""
    m = cfg.mla
    kv = x @ params["wkv_a"]
    c_kv, k_pe = jnp.split(kv, [m.kv_lora_rank], axis=-1)
    c_kv = rmsnorm(params["kv_norm"], c_kv)
    k_pe = _rope_mixed(k_pe[:, :, None, :], nd, pos_d, pos_p,
                       cfg.rope_theta, cfg.rope_scaling)[:, :, 0]
    return c_kv, k_pe


def gqa_mixed(params, cfg, x, nd, pos_d, pos_p, cache, p_len, p_slot,
              window=0):
    """Fused arena layer: decode rows [:nd] + whole-prompt prefill [nd:].

    x: [1, nd+S, D] (already normed); pos_d [1, nd] / pos_p [1, S]:
    the decode rows' absolute depths and 0..S-1.  cache: one arena layer
    {k, v: [nd, T, KV, hd], ptr [nd]}.  The prefilled slot `p_slot` must
    be dead to decode; its row is fully overwritten (prompt entries +
    ptr = p_len) after the decode-side ring insert, exactly like
    `decode_rows` followed by `prefill_into_slot`.

    Returns ([1, nd+S, D], new cache)."""
    _, s_tot, _ = x.shape
    sp = s_tot - nd
    h, hd = cfg.num_heads, cfg.head_dim
    q, k, v = _project_qkv_mixed(params, cfg, x, nd, pos_d, pos_p)

    # decode core (== gqa_decode after projection)
    qd = q[0, :nd]                                # [nd,KV,G,hd]
    t = cache["k"].shape[1]
    ck = ring_insert(cache["k"], k[0, :nd], cache["ptr"])
    cv = ring_insert(cache["v"], v[0, :nd], cache["ptr"])
    num_valid = jnp.minimum(cache["ptr"] + 1, t)
    logits = jnp.einsum("bkgh,btkh->bkgt", qd.astype(jnp.float32),
                        ck.astype(jnp.float32)) * float(1.0 / np.sqrt(hd))
    valid = jnp.arange(t) < jnp.reshape(num_valid, (-1, 1))
    logits = jnp.where(valid[:, None, None, :], logits, _NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    out_d = jnp.einsum("bkgt,btkh->bkgh", p, cv.astype(jnp.float32))
    out_d = out_d.reshape(1, nd, h * hd).astype(x.dtype)

    # prefill core (== gqa_prefill after projection)
    win = window if window else cfg.attn_window
    out_p = chunked_attention(q[:, nd:], k[:, nd:], v[:, nd:],
                              causal=True, window=win)
    out_p = out_p.reshape(1, sp, h * hd)

    # splice the prompt's cache row over the decode-side insert
    row_k = prefill_cache_entries(k[:, nd:], t, sp).astype(ck.dtype)
    row_v = prefill_cache_entries(v[:, nd:], t, sp).astype(cv.dtype)
    new_cache = {
        "k": jax.lax.dynamic_update_slice_in_dim(ck, row_k, p_slot, axis=0),
        "v": jax.lax.dynamic_update_slice_in_dim(cv, row_v, p_slot, axis=0),
        "ptr": (cache["ptr"] + 1).at[p_slot].set(
            jnp.asarray(p_len, cache["ptr"].dtype)),
    }
    out = jnp.concatenate([out_d, out_p], axis=1)
    return out @ params["wo"], new_cache


def gqa_mixed_paged(params, cfg, x, nd, pos_d, pos_p, cache, tables, lengths,
                    ctx_len, c_table, window=0, c_valid=None):
    """Fused paged layer: decode rows [:nd] + one prefill chunk [nd:].

    cache: one pool layer {k, v: [NB, bs, KV, hd]}.  Decode scatters
    first (dead rows route to the null block), the chunk then gathers
    its context from the updated pool and scatters its own entries —
    the same op order as `decode_rows_paged` followed by
    `prefill_chunk_into_blocks`, whose write sets are disjoint.

    window > 0: both cores run ring-paged — see `gqa_decode_paged` /
    `gqa_prefill_paged`.  Write sets stay disjoint (the chunk's table
    is private to its stream).

    Returns ([1, nd+C, D], new cache)."""
    _, s_tot, _ = x.shape
    c = s_tot - nd
    h, hd = cfg.num_heads, cfg.head_dim
    scale = float(1.0 / np.sqrt(hd))
    q, k, v = _project_qkv_mixed(params, cfg, x, nd, pos_d, pos_p)

    # decode core (== gqa_decode_paged after projection)
    qd = q[0, :nd]
    ck = scatter_token_pages(cache["k"], k[0, :nd], tables, lengths,
                             window=window)
    cv = scatter_token_pages(cache["v"], v[0, :nd], tables, lengths,
                             window=window)
    kf = gather_pages(ck, tables)
    vf = gather_pages(cv, tables)
    t = kf.shape[1]
    num_valid = lengths + 1
    if window:
        num_valid = jnp.minimum(num_valid, window)
    logits = jnp.einsum("bkgh,btkh->bkgt", qd.astype(jnp.float32),
                        kf.astype(jnp.float32)) * scale
    valid = jnp.arange(t) < jnp.reshape(num_valid, (-1, 1))
    logits = jnp.where(valid[:, None, None, :], logits, _NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    out_d = jnp.einsum("bkgt,btkh->bkgh", p, vf.astype(jnp.float32))
    out_d = out_d.reshape(1, nd, h * hd).astype(x.dtype)

    # chunk core (== gqa_prefill_paged after projection, on the
    # decode-updated pool)
    k_new, v_new = k[:, nd:], v[:, nd:]
    k_ctx = gather_pages(ck, c_table[None])
    v_ctx = gather_pages(cv, c_table[None])
    out_p = _paged_context_attention(q[:, nd:], k_ctx, v_ctx, k_new, v_new,
                                     ctx_len, scale, window=window)
    out_p = out_p.reshape(1, c, h * hd).astype(x.dtype)

    new_cache = {
        "k": scatter_chunk_pages(ck, k_new[0], c_table, ctx_len,
                                 window=window, valid=c_valid),
        "v": scatter_chunk_pages(cv, v_new[0], c_table, ctx_len,
                                 window=window, valid=c_valid),
    }
    out = jnp.concatenate([out_d, out_p], axis=1)
    return out @ params["wo"], new_cache


def mla_mixed(params, cfg, x, nd, pos_d, pos_p, cache, p_len, p_slot):
    """Fused arena MLA layer: absorbed decode [:nd] + prefill [nd:].

    cache: one arena layer {ckv [nd,T,r], kpe [nd,T,rope], ptr [nd]}.
    Same contract as `gqa_mixed`."""
    m = cfg.mla
    _, s_tot, _ = x.shape
    sp = s_tot - nd
    h = cfg.num_heads
    q_nope, q_pe = _mla_q_mixed(params, cfg, x, nd, pos_d, pos_p)
    c_kv, k_pe = _mla_ckv_mixed(params, cfg, x, nd, pos_d, pos_p)

    # decode core (== mla_decode after projection; x-axis is size 1)
    t = cache["ckv"].shape[1]
    ckv = ring_insert(cache["ckv"], c_kv[0, :nd], cache["ptr"])
    kpe = ring_insert(cache["kpe"], k_pe[0, :nd], cache["ptr"])
    num_valid = jnp.minimum(cache["ptr"] + 1, t)
    wk_b = params["wk_b"].reshape(m.kv_lora_rank, h, m.qk_nope_head_dim)
    qd_nope = q_nope[0, :nd][:, None]                    # [nd,1,H,dn]
    qd_pe = q_pe[0, :nd][:, None]
    q_lat = jnp.einsum("bxhd,rhd->bhr", qd_nope.astype(jnp.float32),
                       wk_b.astype(jnp.float32))
    scale = _mla_scale(cfg)
    logits = (jnp.einsum("bhr,btr->bht", q_lat, ckv.astype(jnp.float32))
              + jnp.einsum("bxhd,btd->bht", qd_pe.astype(jnp.float32),
                           kpe.astype(jnp.float32))) * scale
    valid = jnp.arange(t) < jnp.reshape(num_valid, (-1, 1))
    logits = jnp.where(valid[:, None, :], logits, _NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    ctx = jnp.einsum("bht,btr->bhr", p, ckv.astype(jnp.float32))
    wv_b = params["wv_b"].reshape(m.kv_lora_rank, h, m.v_head_dim)
    out_d = jnp.einsum("bhr,rhd->bhd", ctx, wv_b.astype(jnp.float32))
    out_d = out_d.reshape(1, nd, h * m.v_head_dim).astype(x.dtype)

    # prefill core (== mla_prefill after projection: non-absorbed)
    cp, pp = c_kv[:, nd:], k_pe[:, nd:]
    k_nope = (cp @ params["wk_b"]).reshape(1, sp, h, m.qk_nope_head_dim)
    vp = (cp @ params["wv_b"]).reshape(1, sp, h, m.v_head_dim)
    qp = jnp.concatenate([q_nope[:, nd:], q_pe[:, nd:]], axis=-1)
    kp = jnp.concatenate(
        [k_nope, jnp.broadcast_to(pp[:, :, None, :],
                                  (1, sp, h, m.qk_rope_head_dim))], axis=-1)
    out_p = chunked_attention(qp[:, :, :, None, :].reshape(
        1, sp, h, 1, qp.shape[-1]), kp, vp, causal=True,
        scale=_mla_scale(cfg))
    out_p = out_p.reshape(1, sp, h * m.v_head_dim)

    row_c = prefill_cache_entries(cp, t, sp).astype(ckv.dtype)
    row_p = prefill_cache_entries(pp, t, sp).astype(kpe.dtype)
    new_cache = {
        "ckv": jax.lax.dynamic_update_slice_in_dim(ckv, row_c, p_slot,
                                                   axis=0),
        "kpe": jax.lax.dynamic_update_slice_in_dim(kpe, row_p, p_slot,
                                                   axis=0),
        "ptr": (cache["ptr"] + 1).at[p_slot].set(
            jnp.asarray(p_len, cache["ptr"].dtype)),
    }
    out = jnp.concatenate([out_d, out_p], axis=1)
    return out @ params["wo"], new_cache


def mla_mixed_paged(params, cfg, x, nd, pos_d, pos_p, cache, tables, lengths,
                    ctx_len, c_table):
    """Fused paged MLA layer: absorbed decode [:nd] + one chunk [nd:].

    cache: one latent pool layer {ckv [NB,bs,r], kpe [NB,bs,rope]}.
    Same contract and op order as `gqa_mixed_paged`."""
    m = cfg.mla
    _, s_tot, _ = x.shape
    c = s_tot - nd
    h = cfg.num_heads
    q_nope, q_pe = _mla_q_mixed(params, cfg, x, nd, pos_d, pos_p)
    c_kv, k_pe = _mla_ckv_mixed(params, cfg, x, nd, pos_d, pos_p)

    # decode core (== mla_decode_paged after projection)
    cc = scatter_token_pages(cache["ckv"], c_kv[0, :nd], tables, lengths)
    cp_pool = scatter_token_pages(cache["kpe"], k_pe[0, :nd], tables,
                                  lengths)
    ckv = gather_pages(cc, tables)
    kpe = gather_pages(cp_pool, tables)
    t = ckv.shape[1]
    wk_b = params["wk_b"].reshape(m.kv_lora_rank, h, m.qk_nope_head_dim)
    qd_nope = q_nope[0, :nd][:, None]
    qd_pe = q_pe[0, :nd][:, None]
    q_lat = jnp.einsum("bxhd,rhd->bhr", qd_nope.astype(jnp.float32),
                       wk_b.astype(jnp.float32))
    scale = _mla_scale(cfg)
    logits = (jnp.einsum("bhr,btr->bht", q_lat, ckv.astype(jnp.float32))
              + jnp.einsum("bxhd,btd->bht", qd_pe.astype(jnp.float32),
                           kpe.astype(jnp.float32))) * scale
    valid = jnp.arange(t) < jnp.reshape(lengths + 1, (-1, 1))
    logits = jnp.where(valid[:, None, :], logits, _NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    ctx = jnp.einsum("bht,btr->bhr", p, ckv.astype(jnp.float32))
    wv_b = params["wv_b"].reshape(m.kv_lora_rank, h, m.v_head_dim)
    out_d = jnp.einsum("bhr,rhd->bhd", ctx, wv_b.astype(jnp.float32))
    out_d = out_d.reshape(1, nd, h * m.v_head_dim).astype(x.dtype)

    # chunk core (== mla_prefill_paged after projection, on the
    # decode-updated pool)
    new_ckv, new_kpe = c_kv[:, nd:], k_pe[:, nd:]
    ckv_ctx = gather_pages(cc, c_table[None])
    kpe_ctx = gather_pages(cp_pool, c_table[None])
    tc = ckv_ctx.shape[1]

    def expand(ckv_in, kpe_in, s):
        ckv_in = ckv_in.astype(x.dtype)
        k_nope = (ckv_in @ params["wk_b"]).reshape(1, s, h,
                                                   m.qk_nope_head_dim)
        vv = (ckv_in @ params["wv_b"]).reshape(1, s, h, m.v_head_dim)
        kk = jnp.concatenate(
            [k_nope, jnp.broadcast_to(
                kpe_in[:, :, None, :].astype(k_nope.dtype),
                (1, s, h, m.qk_rope_head_dim))], -1)
        return kk, vv

    k_ctx, v_ctx = expand(ckv_ctx, kpe_ctx, tc)
    k_new, v_new = expand(new_ckv, new_kpe, c)
    qp = jnp.concatenate([q_nope[:, nd:], q_pe[:, nd:]], axis=-1)
    out_p = _paged_context_attention(
        qp.reshape(1, c, h, 1, qp.shape[-1]), k_ctx, v_ctx, k_new, v_new,
        ctx_len, _mla_scale(cfg))
    out_p = out_p.reshape(1, c, h * m.v_head_dim).astype(x.dtype)

    new_cache = {
        "ckv": scatter_chunk_pages(cc, new_ckv[0], c_table, ctx_len),
        "kpe": scatter_chunk_pages(cp_pool, new_kpe[0], c_table, ctx_len),
    }
    out = jnp.concatenate([out_d, out_p], axis=1)
    return out @ params["wo"], new_cache


# ---------------------------------------------------------------------------
# cross-attention (whisper decoder)
# ---------------------------------------------------------------------------


def cross_init(key, cfg, dtype):
    d, h, hd = cfg.d_model, cfg.num_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    return {
        "wq": _he(ks[0], (d, h * hd), dtype),
        "wk": _he(ks[1], (d, h * hd), dtype),
        "wv": _he(ks[2], (d, h * hd), dtype),
        "wo": _he(ks[3], (h * hd, d), dtype, fan_in=h * hd),
    }


def cross_attention(params, cfg, x, enc_k, enc_v):
    """x: [B,S,D]; enc_k/enc_v: [B,T,H,hd] (precomputed from encoder)."""
    b, s, _ = x.shape
    h, hd = cfg.num_heads, cfg.head_dim
    q = (x @ params["wq"]).reshape(b, s, h, 1, hd)
    out = chunked_attention(q, enc_k, enc_v, causal=False)
    out = out.reshape(b, s, h * hd)
    return out @ params["wo"]


def cross_kv(params, cfg, enc_out):
    b, t, _ = enc_out.shape
    h, hd = cfg.num_heads, cfg.head_dim
    k = (enc_out @ params["wk"]).reshape(b, t, h, hd)
    v = (enc_out @ params["wv"]).reshape(b, t, h, hd)
    return k, v


def bidir_attention(params, cfg, x, positions):
    """Encoder self-attention (no causal mask)."""
    b, s, _ = x.shape
    q, k, v = _project_qkv(params, cfg, x, positions)
    out = chunked_attention(q, k, v, causal=False)
    out = out.reshape(b, s, cfg.num_heads * cfg.head_dim)
    return out @ params["wo"]
