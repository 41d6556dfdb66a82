"""Decoder-only transformer family: dense GQA / MoE / MLA / RWKV6 / RG-LRU.

The stack is a *program* of segments: consecutive layers of the same kind
are stacked on a leading axis and executed with jax.lax.scan (compact HLO —
one layer body per kind regardless of depth), which keeps multi-hundred-
layer configs compilable. Hybrids (recurrentgemma) interleave kinds and get
one scan per homogeneous run.

Cache semantics are uniform across kinds:
  * attention (full or sliding): ring buffer {k, v, ptr} of capacity T
    (T = seq_len, or window for sliding) — softmax is order-invariant so
    ring order needs no re-sorting; decode overwrites slot ptr.
  * MLA: ring {ckv, kpe, ptr} in the compressed latent space.
  * rwkv / rglru: O(1) recurrent state.

Modes: 'train' (no cache), 'prefill' (build cache), 'decode' (one token).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import attention as A
from repro.models.attention import prefill_cache_entries, ring_insert
import os

from repro.models import moe as MOE
from repro.models import rglru as RG
from repro.models import rwkv6 as RW
from repro.models.layers import (
    embed, embedding_init, make_norm, mlp_apply, mlp_init, unembed, _he,
)


# ---------------------------------------------------------------------------
# block init / apply
# ---------------------------------------------------------------------------


def block_init(key, cfg, kind, dtype):
    norm_init, _ = make_norm(cfg.norm_type)
    ks = jax.random.split(key, 4)
    if kind in ("attn", "moe"):
        attn = (A.mla_init(ks[0], cfg, dtype) if cfg.mla is not None
                else A.gqa_init(ks[0], cfg, dtype))
        p = {"ln1": norm_init(cfg.d_model, dtype), "attn": attn,
             "ln2": norm_init(cfg.d_model, dtype)}
        if kind == "moe":
            p["moe"] = MOE.moe_init(ks[1], cfg, dtype)
        else:
            p["mlp"] = mlp_init(ks[1], cfg.d_model, cfg.d_ff,
                                cfg.mlp_type, dtype)
        return p
    if kind == "rwkv":
        return {"ln1": norm_init(cfg.d_model, dtype),
                "mix": RW.rwkv_init(ks[0], cfg, dtype),
                "ln2": norm_init(cfg.d_model, dtype)}
    if kind == "rglru":
        return {"ln1": norm_init(cfg.d_model, dtype),
                "rnn": RG.rglru_init(ks[0], cfg, dtype),
                "ln2": norm_init(cfg.d_model, dtype),
                "mlp": mlp_init(ks[1], cfg.d_model, cfg.d_ff,
                                cfg.mlp_type, dtype)}
    raise ValueError(kind)


def init_cache_layer(cfg, kind, batch, capacity, dtype):
    """Zero cache for one layer of the given kind."""
    if kind in ("attn", "moe"):
        if cfg.mla is not None:
            m = cfg.mla
            return {"ckv": jnp.zeros((batch, capacity, m.kv_lora_rank), dtype),
                    "kpe": jnp.zeros((batch, capacity, m.qk_rope_head_dim),
                                     dtype),
                    "ptr": jnp.zeros((), jnp.int32)}
        kv, hd = cfg.num_kv_heads, cfg.head_dim
        return {"k": jnp.zeros((batch, capacity, kv, hd), dtype),
                "v": jnp.zeros((batch, capacity, kv, hd), dtype),
                "ptr": jnp.zeros((), jnp.int32)}
    if kind == "rwkv":
        return RW.init_state(cfg, batch)
    if kind == "rglru":
        return RG.init_state(cfg, batch)
    raise ValueError(kind)


_ring_insert = ring_insert   # back-compat alias


def block_apply(cfg, kind, params, x, *, positions, mode, cache=None,
                window=0, paged=None):
    """Returns (x_out, new_cache, aux). aux = scalar (moe load-balance).

    paged: None for the arena/linear cache paths; otherwise a dict that
    routes attention through the block-pool variants — for prefill
    {"table": [W], "ctx_len": scalar}, for decode {"tables": [B, W],
    "lengths": [B]} — with `cache` holding the layer's pool leaves."""
    _, norm = make_norm(cfg.norm_type)
    aux = jnp.zeros((), jnp.float32)

    if kind in ("attn", "moe"):
        h = norm(params["ln1"], x)
        if paged is not None:
            if mode == "prefill":
                if cfg.mla is not None:
                    attn_out, new_cache = A.mla_prefill_paged(
                        params["attn"], cfg, h, cache,
                        paged["table"], paged["ctx_len"])
                else:
                    attn_out, new_cache = A.gqa_prefill_paged(
                        params["attn"], cfg, h, cache,
                        paged["table"], paged["ctx_len"],
                        window=window, valid=paged.get("valid"))
            else:
                if cfg.mla is not None:
                    attn_out, new_cache = A.mla_decode_paged(
                        params["attn"], cfg, h, cache,
                        paged["tables"], paged["lengths"])
                else:
                    attn_out, new_cache = A.gqa_decode_paged(
                        params["attn"], cfg, h, cache,
                        paged["tables"], paged["lengths"], window=window)
            x = x + attn_out
        elif mode in ("train", "prefill"):
            if cfg.mla is not None:
                attn_out, (ckv, kpe) = A.mla_prefill(params["attn"], cfg, h,
                                                     positions)
            else:
                attn_out, (k, v) = A.gqa_prefill(params["attn"], cfg, h,
                                                 positions, window=window)
            x = x + attn_out
            new_cache = ()
            if mode == "prefill":
                s_len = x.shape[1]
                ptr = jnp.full((), s_len, jnp.int32)
                if cfg.mla is not None:
                    t = cache["ckv"].shape[1]
                    new_cache = {
                        "ckv": prefill_cache_entries(
                            ckv, t, s_len).astype(cache["ckv"].dtype),
                        "kpe": prefill_cache_entries(
                            kpe, t, s_len).astype(cache["kpe"].dtype),
                        "ptr": ptr}
                else:
                    t = cache["k"].shape[1]
                    new_cache = {
                        "k": prefill_cache_entries(
                            k, t, s_len).astype(cache["k"].dtype),
                        "v": prefill_cache_entries(
                            v, t, s_len).astype(cache["v"].dtype),
                        "ptr": ptr}
        else:  # decode: insert-then-attend (token attends to itself)
            pos = positions                         # [B,1] absolute position
            if cfg.mla is not None:
                attn_out, new_cache = A.mla_decode(
                    params["attn"], cfg, h, cache, pos)
            else:
                attn_out, new_cache = A.gqa_decode(
                    params["attn"], cfg, h, cache, pos, window=window)
            x = x + attn_out

        h2 = norm(params["ln2"], x)
        if kind == "moe":
            moe_fn = (MOE.moe_apply_scatter
                      if os.environ.get("REPRO_MOE_SCATTER")
                      else MOE.moe_apply)
            ff, aux = moe_fn(params["moe"], cfg, h2)
        else:
            ff = mlp_apply(params["mlp"], h2, cfg.mlp_type)
        return x + ff, new_cache, aux

    if kind == "rwkv":
        state = cache if cache is not None else RW.init_state(cfg, x.shape[0])
        h = norm(params["ln1"], x)
        tm_out, state = RW.time_mix(params["mix"], cfg, h, state)
        x = x + tm_out
        h2 = norm(params["ln2"], x)
        cm_out, state = RW.channel_mix(params["mix"], cfg, h2, state)
        x = x + cm_out
        new_cache = state if mode != "train" else ()
        return x, new_cache, aux

    if kind == "rglru":
        state = cache if cache is not None else RG.init_state(cfg, x.shape[0])
        h = norm(params["ln1"], x)
        rnn_out, state = RG.rglru_block(params["rnn"], cfg, h, state)
        x = x + rnn_out
        h2 = norm(params["ln2"], x)
        x = x + mlp_apply(params["mlp"], h2, cfg.mlp_type)
        new_cache = state if mode != "train" else ()
        return x, new_cache, aux

    raise ValueError(kind)


# ---------------------------------------------------------------------------
# segments (runs of identical layer kinds -> lax.scan)
# ---------------------------------------------------------------------------


def build_segments(layer_types):
    """[(kind, count), ...] for consecutive runs."""
    segs = []
    for t in layer_types:
        if segs and segs[-1][0] == t:
            segs[-1][1] += 1
        else:
            segs.append([t, 1])
    return [(k, c) for k, c in segs]


def transformer_init(cfg, key, dtype=None):
    dtype = dtype or jnp.dtype(cfg.param_dtype)
    segs = build_segments(cfg.layer_types)
    keys = jax.random.split(key, len(segs) + 2)
    norm_init, _ = make_norm(cfg.norm_type)
    seg_params = []
    for (kind, count), k in zip(segs, keys[:-2]):
        lk = jax.random.split(k, count)
        seg_params.append(jax.vmap(
            lambda kk: block_init(kk, cfg, kind, dtype))(lk))
    params = {
        "embed": embedding_init(keys[-2], cfg.vocab_size, cfg.d_model, dtype),
        "segments": seg_params,
        "final_norm": norm_init(cfg.d_model, dtype),
    }
    if not cfg.tie_embeddings:
        params["head"] = _he(keys[-1], (cfg.d_model, cfg.vocab_size), dtype)
    return params


def init_cache(cfg, batch, seq_len, window=0, dtype=jnp.bfloat16):
    """Stacked per-segment caches for decode. window>0 caps attn capacity."""
    segs = build_segments(cfg.layer_types)
    caches = []
    for kind, count in segs:
        if kind in ("attn", "moe"):
            native_win = cfg.attn_window or window
            cap = min(seq_len, native_win) if native_win else seq_len
        else:
            cap = 0
        one = init_cache_layer(cfg, kind, batch, max(cap, 1), dtype)
        caches.append(jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (count,) + a.shape), one))
    return caches


def _segment_apply(cfg, kind, seg_params, x, *, positions, mode,
                   seg_cache=None, window=0, remat=False, paged=None):
    """Scan one homogeneous run of `count` layers."""

    def body(carry, inp):
        xx = carry
        if seg_cache is None:
            p_layer = inp
            c_layer = None
        else:
            p_layer, c_layer = inp

        def blk(p, h):
            return block_apply(cfg, kind, p, h, positions=positions,
                               mode=mode, cache=c_layer, window=window,
                               paged=paged)

        if remat and mode == "train":
            blk = jax.checkpoint(blk)   # activation checkpointing per block
        xx, new_c, aux = blk(p_layer, xx)
        return xx, (new_c, aux)

    xs = seg_params if seg_cache is None else (seg_params, seg_cache)
    x, (new_caches, auxs) = jax.lax.scan(body, x, xs)
    return x, new_caches, jnp.sum(auxs)


def forward(cfg, params, x, *, positions, mode, caches=None, window=0,
            remat=False, paged=None):
    """Run the full stack on embeddings x. Returns (x, new_caches, aux)."""
    segs = build_segments(cfg.layer_types)
    new_caches = []
    aux_total = jnp.zeros((), jnp.float32)
    for si, (kind, count) in enumerate(segs):
        seg_cache = None if caches is None else caches[si]
        x, nc, aux = _segment_apply(cfg, kind, params["segments"][si], x,
                                    positions=positions, mode=mode,
                                    seg_cache=seg_cache, window=window,
                                    remat=remat, paged=paged)
        new_caches.append(nc)
        aux_total = aux_total + aux
    _, norm = make_norm(cfg.norm_type)
    x = norm(params["final_norm"], x)
    return x, new_caches, aux_total


def logits_fn(cfg, params, x):
    if cfg.tie_embeddings:
        return unembed(params["embed"], x)
    return x @ params["head"]


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------


def _cast(cfg, params):
    cd = jnp.dtype(cfg.compute_dtype)
    return jax.tree.map(
        lambda a: a.astype(cd) if jnp.issubdtype(a.dtype, jnp.floating)
        else a, params)


def train_loss(cfg, params, batch, window=0, remat=True):
    """batch: {tokens [B,S], targets [B,S], loss_mask [B,S](opt),
    patches [B,P,D](opt, VLM prefix)}. Returns (loss, metrics).

    Its phases are named in the compiled program (`jax.named_scope`):
    model.embed, model.blocks (the layer scans) and model.head (logits
    and loss)."""
    params = _cast(cfg, params)
    tokens = batch["tokens"]
    with jax.named_scope("model.embed"):
        x = embed(params["embed"], tokens).astype(
            jnp.dtype(cfg.compute_dtype))
        n_prefix = 0
        if "patches" in batch and batch["patches"] is not None:
            patches = batch["patches"].astype(x.dtype)
            n_prefix = patches.shape[1]
            x = jnp.concatenate([patches, x], axis=1)
        b, s, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    with jax.named_scope("model.blocks"):
        x, _, aux = forward(cfg, params, x, positions=positions,
                            mode="train", window=window, remat=remat)
    with jax.named_scope("model.head"):
        x = x[:, n_prefix:]
        logits = logits_fn(cfg, params, x).astype(jnp.float32)
        targets = batch["targets"]
        # shard-friendly CE: reductions over the (vocab-sharded) last axis
        # partition cleanly; take_along_axis would force logits replication
        m = jax.lax.stop_gradient(logits.max(axis=-1))
        logz = m + jnp.log(jnp.sum(jnp.exp(logits - m[..., None]),
                                   axis=-1))
        onehot = jax.nn.one_hot(targets, logits.shape[-1],
                                dtype=logits.dtype)
        gold = jnp.sum(logits * onehot, axis=-1)
        nll = logz - gold
        mask = batch.get("loss_mask")
        if mask is None:
            mask = jnp.ones_like(nll)
        loss = jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return loss + aux, {"nll": loss, "aux": aux}


def prefill(cfg, params, batch, window=0, cache_dtype=jnp.bfloat16,
            cache_len=None):
    """Build caches from a full prompt. Returns (logits_last, caches).

    cache_len: total cache capacity (>= prompt length) to leave headroom
    for subsequent decode steps; defaults to the prompt length."""
    params = _cast(cfg, params)
    tokens = batch["tokens"]
    x = embed(params["embed"], tokens).astype(jnp.dtype(cfg.compute_dtype))
    if "patches" in batch and batch["patches"] is not None:
        x = jnp.concatenate([batch["patches"].astype(x.dtype), x], axis=1)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    caches = init_cache(cfg, b, max(cache_len or s, s), window=window,
                        dtype=cache_dtype)
    x, caches, _ = forward(cfg, params, x, positions=positions,
                           mode="prefill", caches=caches, window=window)
    logits = logits_fn(cfg, params, x[:, -1:]).astype(jnp.float32)
    return logits, caches


def decode_step(cfg, params, token, caches, position, window=0):
    """token: [B,1] int32; position: scalar absolute position.

    Returns (logits [B,1,V], new caches)."""
    params = _cast(cfg, params)
    x = embed(params["embed"], token).astype(jnp.dtype(cfg.compute_dtype))
    b = x.shape[0]
    positions = jnp.full((b, 1), position, jnp.int32)
    x, caches, _ = forward(cfg, params, x, positions=positions,
                           mode="decode", caches=caches, window=window)
    logits = logits_fn(cfg, params, x).astype(jnp.float32)
    return logits, caches


# ---------------------------------------------------------------------------
# slot-arena entry points (repro.serve continuous batching)
#
# The arena holds `slots` independent in-flight requests in one cache
# pytree: array leaves are the usual stacked [layers, B, T, ...] buffers,
# but `ptr` is per-row int32 [layers, B] so every slot decodes at its own
# depth.  Admission prefills ONE request (batch-1 forward) and writes the
# resulting cache row into its slot between decode steps; the decode step
# is a single jitted function over all slots with per-row positions.
# ---------------------------------------------------------------------------


def _leaf_name(path):
    for k in reversed(path):
        if hasattr(k, "key"):
            return k.key
    return None


def init_arena(cfg, slots, capacity, window=0, dtype=jnp.bfloat16):
    """Slot-arena caches: init_cache with per-row ptr [layers, slots]."""
    caches = init_cache(cfg, slots, capacity, window=window, dtype=dtype)
    return jax.tree_util.tree_map_with_path(
        lambda p, a: (jnp.zeros(a.shape + (slots,), jnp.int32)
                      if _leaf_name(p) == "ptr" else a),
        caches)


def _write_slot(arena, row, slot, length):
    """Write a batch-1 cache `row` into arena slot `slot` (traced index);
    the slot's ptr is set to `length` (tokens actually in the cache)."""
    def upd(path, a, r):
        if _leaf_name(path) == "ptr":
            return a.at[:, slot].set(jnp.asarray(length, a.dtype))
        return jax.lax.dynamic_update_slice_in_dim(
            a, r.astype(a.dtype), slot, axis=1)
    return jax.tree_util.tree_map_with_path(upd, arena, row)


def prefill_into_slot(cfg, params, tokens, length, slot, caches, window=0):
    """Admit one request into arena slot `slot` between decode steps.

    tokens: [1, Sp] int32, right-padded to a bucketed length Sp (pad
    entries are masked out downstream: causal attention means positions
    < length never see them, and the slot's ptr/validity is `length`).
    length: true prompt length (traced scalar — no recompile per length).
    slot: arena row to overwrite (traced scalar).
    caches: arena from init_arena (leaves [layers, B, T, ...], ptr
    [layers, B]).

    Returns (logits [1,1,V] at position length-1, updated arena).
    """
    params = _cast(cfg, params)
    x = embed(params["embed"], tokens).astype(jnp.dtype(cfg.compute_dtype))
    _, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s)[None], (1, s))
    # batch-1 cache row with the arena's per-segment capacities/dtypes
    row = jax.tree_util.tree_map_with_path(
        lambda p, a: (jnp.zeros(a.shape[:1], jnp.int32)
                      if _leaf_name(p) == "ptr"
                      else jnp.zeros((a.shape[0], 1) + a.shape[2:], a.dtype)),
        caches)
    x, row, _ = forward(cfg, params, x, positions=positions, mode="prefill",
                        caches=row, window=window)
    h_last = jax.lax.dynamic_slice_in_dim(x, length - 1, 1, axis=1)
    logits = logits_fn(cfg, params, h_last).astype(jnp.float32)
    return logits, _write_slot(caches, row, slot, length)


def decode_rows(cfg, params, token, caches, positions, window=0):
    """One decode step over all arena slots.

    token: [B,1] int32 (one current token per slot); positions: int32 [B]
    absolute positions (== tokens already in each slot's cache).  Dead
    slots compute garbage that the engine masks host-side; their cache
    rows are fully overwritten at the next admission.

    Returns (logits [B,1,V], new caches)."""
    params = _cast(cfg, params)
    x = embed(params["embed"], token).astype(jnp.dtype(cfg.compute_dtype))
    b = x.shape[0]
    positions = jnp.reshape(jnp.asarray(positions, jnp.int32), (b, 1))
    x, caches, _ = forward(cfg, params, x, positions=positions,
                           mode="decode", caches=caches, window=window)
    logits = logits_fn(cfg, params, x).astype(jnp.float32)
    return logits, caches


# ---------------------------------------------------------------------------
# token-returning serving steps
#
# The serving engine is greedy-only, so the full-vocab logits the entry
# points above return are pure device->host overhead: the host argmaxes
# and throws them away.  On a mesh the cost is worse than bandwidth —
# the vocab dim is model-sharded, so fetching logits is a cross-host
# gather every decode step.  These variants fold the argmax into the
# jitted step: the host receives int32 token ids ([] for batch-1
# admission, [B] for the row-wise decode steps), and the decode steps
# also return the advanced positions/lengths so steady-state decoding
# feeds device outputs straight back in with no host->device uploads.
# ---------------------------------------------------------------------------


def _greedy_last(logits):
    """argmax over the last position of batch-1 logits -> [] int32."""
    return jnp.argmax(logits[0, -1], -1).astype(jnp.int32)


def prefill_into_slot_token(cfg, params, tokens, length, slot, caches,
                            window=0):
    """`prefill_into_slot` returning ([] int32 greedy token, arena)."""
    logits, caches = prefill_into_slot(cfg, params, tokens, length, slot,
                                       caches, window=window)
    return _greedy_last(logits), caches


def decode_rows_tokens(cfg, params, tokens, caches, positions, window=0):
    """`decode_rows` returning token ids and advanced positions.

    tokens: [B] int32 (one incoming token per slot — the previous step's
    output, so steady-state decode is a pure device-side feedback loop);
    positions: int32 [B].  Returns (next [B] int32, new caches,
    positions + 1).  Dead rows advance too; the engine re-uploads exact
    host values whenever admission/finish/preemption touches a row."""
    positions = jnp.asarray(positions, jnp.int32)
    logits, caches = decode_rows(cfg, params, tokens[:, None], caches,
                                 positions, window=window)
    nxt = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
    return nxt, caches, positions + 1


def prefill_chunk_into_blocks_token(cfg, params, tokens, length, ctx_len,
                                    block_table, pool, window=0):
    """`prefill_chunk_into_blocks` returning ([] int32 token, pool).

    The token is only meaningful for the prompt's final chunk (earlier
    chunks' last positions are mid-prompt); computing it every chunk is
    a vocab-length argmax, far cheaper than shipping logits."""
    logits, pool = prefill_chunk_into_blocks(cfg, params, tokens, length,
                                             ctx_len, block_table, pool,
                                             window=window)
    return _greedy_last(logits), pool


def decode_rows_paged_tokens(cfg, params, tokens, pool, block_tables,
                             lengths, window=0):
    """`decode_rows_paged` returning token ids and advanced lengths.

    tokens: [B] int32; lengths: int32 [B].  Returns (next [B] int32,
    new pool, lengths + 1).  Dead rows' lengths drift upward on device,
    which is inert: their zeroed block tables route every gather and
    scatter to the null block (out-of-range block indices clamp there
    too), and the engine masks their tokens host-side."""
    lengths = jnp.asarray(lengths, jnp.int32)
    logits, pool = decode_rows_paged(cfg, params, tokens[:, None], pool,
                                     block_tables, lengths, window=window)
    nxt = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
    return nxt, pool, lengths + 1


# ---------------------------------------------------------------------------
# unified mixed prefill+decode steps (Sarathi/vLLM mixed batch)
#
# One launch = one decode step over all live rows PLUS one admission
# prefill unit (a whole bucketed prompt on the arena, one fixed-size
# chunk on the paged pool).  Admission then rides the decode launch the
# live rows were going to pay for anyway, instead of serializing an
# extra prefill launch in front of it.
#
# The fusion is a *token concatenation*, not a subgraph composition:
# the B decode tokens and the S prompt/chunk tokens run as ONE token
# batch [1, B+S, D] through every dense op — embed, norms, qkv/latent
# projections, the output projection, the MLP, the unembed — and split
# only inside the attention core (repro.models.attention gqa_mixed /
# mla_mixed and their _paged variants).  The dense matmuls are where
# the model-parallel collectives live, so an admission step pays ONE
# set of per-layer collectives instead of decode's plus prefill's; a
# decode+prefill composition in a single jit would conserve the
# collective count and make the mixed step cost exactly the sum of its
# parts (measured: no overlap win at all on collective-bound meshes).
#
# Bit-identity argument (the house discipline): per-token ops (matmul
# rows, rope, rmsnorm, embedding gathers) are row-stable across batch
# shapes, and the attention cores are copied from the standalone
# decode/prefill functions verbatim after the projection split — so
# both halves produce bitwise the values the serialized launches
# would.  The two halves also touch disjoint state: the slot being
# prefilled is DEAD to the decode side — the engine keeps its
# decode-visible length/table at zero until the prefill completes.
# Order inside the cores is decode-then-prefill: on the arena the
# decode's dead-row garbage insert (ring ptr of the previous occupant)
# must land BEFORE the prefill row splice overwrites the whole row; on
# the pool the two write sets are disjoint (the dead row's decode
# writes route to the null block), so either order works and we keep
# one convention.
#
# Only all-attention stacks reach this path (FamilyCaps.pad_prompts
# gates supports_mixed_step), so the scan below assumes one
# homogeneous "attn" segment.
# ---------------------------------------------------------------------------


def _mixed_forward(cfg, params, x, caches, attn_fn):
    """Shared trunk of the fused mixed steps.

    Scans the (single, homogeneous) attention segment over x
    [1, B+S, D] with `attn_fn(p_attn, h_normed, cache_layer) ->
    (attn_out, new_cache_layer)` as the attention, then applies the
    final norm.  Returns (x, new_caches) with the per-segment list
    structure `forward` uses."""
    segs = build_segments(cfg.layer_types)
    assert segs == [("attn", len(cfg.layer_types))], (
        f"mixed step needs a pure attention stack, got {segs}")
    _, norm = make_norm(cfg.norm_type)

    def body(xx, inp):
        p_layer, c_layer = inp
        h = norm(p_layer["ln1"], xx)
        attn_out, new_c = attn_fn(p_layer["attn"], h, c_layer)
        xx = xx + attn_out
        h2 = norm(p_layer["ln2"], xx)
        xx = xx + mlp_apply(p_layer["mlp"], h2, cfg.mlp_type)
        return xx, new_c

    x, new_seg = jax.lax.scan(body, x, (params["segments"][0], caches[0]))
    return norm(params["final_norm"], x), [new_seg]


def _mixed_outputs(cfg, params, x, b, last_idx):
    """Greedy tokens from the fused trunk's output x [1, B+S, D]:
    (decode next-tokens [B] int32, admission token [] int32 at
    position `last_idx` of the concat axis)."""
    h_sel = jnp.concatenate(
        [x[0, :b],
         jax.lax.dynamic_slice_in_dim(x[0], last_idx, 1, axis=0)],
        axis=0)[None]                                  # [1, B+1, D]
    logits = logits_fn(cfg, params, h_sel).astype(jnp.float32)
    nxt = jnp.argmax(logits[0, :b], -1).astype(jnp.int32)
    p_tok = jnp.argmax(logits[0, b], -1).astype(jnp.int32)
    return nxt, p_tok


def _mixed_embed(cfg, params, dec_tokens, adm_tokens):
    """Embed the decode rows and the admission tokens as two separate
    gathers and concatenate the *embeddings* into the [1, B+S, D] fused
    token batch.

    A single gather of the concatenated token-id vector against the
    vocab-sharded embedding table miscompiles under XLA SPMD on
    data x model meshes (NaN rows in the gather output); the two
    standalone-shaped gathers — [B, 1] as in decode_rows, [1, S] as in
    prefill — are the exact shapes the serialized launches use and
    compile cleanly everywhere.  Gathers are row-stable, so the concat
    of the two results is bitwise the same token batch either way."""
    dt = jnp.dtype(cfg.compute_dtype)
    xd = embed(params["embed"], dec_tokens[:, None]).astype(dt)    # [B,1,D]
    xa = embed(params["embed"], adm_tokens).astype(dt)             # [1,S,D]
    return jnp.concatenate([jnp.transpose(xd, (1, 0, 2)), xa], axis=1)


def mixed_step_tokens(cfg, params, tokens, caches, positions,
                      p_tokens, p_len, p_slot, window=0):
    """One fused arena launch: decode all rows + prefill one request.

    tokens/positions: the decode operands ([B] int32 each); the slot
    being prefilled must be dead to decode (its position is garbage and
    its row is fully overwritten by the prefill below).
    p_tokens [1, Sp] / p_len / p_slot: the `prefill_into_slot` operands.

    Returns (next [B] int32, caches, positions + 1, p_tok [] int32)."""
    params = _cast(cfg, params)
    b = tokens.shape[0]
    sp = p_tokens.shape[1]
    positions = jnp.asarray(positions, jnp.int32)
    x = _mixed_embed(cfg, params, tokens, p_tokens)            # [1, B+Sp, D]
    pos_d = positions[None]                                    # [1, B]
    pos_p = jnp.arange(sp, dtype=jnp.int32)[None]              # [1, Sp]

    if cfg.mla is not None:
        def attn_fn(p, h, c):
            return A.mla_mixed(p, cfg, h, b, pos_d, pos_p, c, p_len, p_slot)
    else:
        def attn_fn(p, h, c):
            return A.gqa_mixed(p, cfg, h, b, pos_d, pos_p, c, p_len, p_slot,
                               window=window)

    x, caches = _mixed_forward(cfg, params, x, caches, attn_fn)
    nxt, p_tok = _mixed_outputs(cfg, params, x, b, b + p_len - 1)
    return nxt, caches, positions + 1, p_tok


def mixed_step_paged_tokens(cfg, params, tokens, pool, block_tables, lengths,
                            c_tokens, c_len, ctx_len, c_table, window=0):
    """One fused pool launch: decode all rows + stream one prefill chunk.

    tokens/block_tables/lengths: the paged decode operands; the slot
    being streamed must carry a zeroed table row and length 0 (dead to
    decode — its writes route to the null block).
    c_tokens [1, C] / c_len / ctx_len / c_table [W]: the
    `prefill_chunk_into_blocks` operands; c_table's width must match
    block_tables' so the mixed step stays one jit family per width.

    Returns (next [B] int32, pool, lengths + 1, c_tok [] int32 — only
    meaningful when this was the prompt's final chunk)."""
    params = _cast(cfg, params)
    win = cfg.attn_window or window
    b = tokens.shape[0]
    c = c_tokens.shape[1]
    lengths = jnp.asarray(lengths, jnp.int32)
    x = _mixed_embed(cfg, params, tokens, c_tokens)            # [1, B+C, D]
    pos_d = lengths[None]                                      # [1, B]
    pos_p = (ctx_len + jnp.arange(c, dtype=jnp.int32))[None]   # [1, C]

    if cfg.mla is not None:
        def attn_fn(p, h, cc):
            return A.mla_mixed_paged(p, cfg, h, b, pos_d, pos_p, cc,
                                     block_tables, lengths, ctx_len, c_table)
    else:
        def attn_fn(p, h, cc):
            return A.gqa_mixed_paged(p, cfg, h, b, pos_d, pos_p, cc,
                                     block_tables, lengths, ctx_len, c_table,
                                     window=win, c_valid=c_len)

    x, pool = _mixed_forward(cfg, params, x, pool, attn_fn)
    nxt, c_tok = _mixed_outputs(cfg, params, x, b, b + c_len - 1)
    return nxt, pool, lengths + 1, c_tok


# ---------------------------------------------------------------------------
# paged-KV entry points (repro.serve block-pool continuous batching)
#
# The arena above dedicates a full capacity-T cache row to every slot; the
# paged pool instead shares `num_blocks` fixed-size blocks across all slots
# ([layers, num_blocks + 1, block_size, ...] per segment leaf — block 0 is
# the null block unallocated table entries point at) with host-side block
# tables mapping logical position p -> (table[p // bs], p % bs).  The
# arena is the degenerate 1-contiguous-block-per-slot case: attention math
# is identical, only the storage indirection differs.  Long prompts stream
# in through `prefill_chunk_into_blocks` (fixed-size chunks, one compile)
# instead of one padded batch-1 launch.  Only pure attention stacks
# (GQA / MLA full-causal, GQA sliding-window) are paged — recurrent
# state has no pages, and moe expert capacity depends on the static
# chunk length (chunking would change routing); the engine
# auto-selects the arena for those.
#
# Sliding-window GQA pages as a RING: a slot's table is a fixed
# ceil(window / bs)-block ring over ring slots (position p at slot
# p % window), so eviction is just overwrite and long generations
# allocate zero blocks beyond the ring — see models/attention.py
# "Ring-paged layout".  MLA + window is NOT paged (the arena's
# mla_prefill ignores the window, so there is no windowed-MLA family
# to stay bit-identical with); init_pool keeps raising for it.
# ---------------------------------------------------------------------------


def init_pool(cfg, num_blocks, block_size, window=0, dtype=jnp.bfloat16):
    """Shared paged-KV block pool; leaves [layers, num_blocks + 1, bs, ...].

    Block 0 is the reserved null block (never attended; masked writes are
    routed into it), so allocatable ids are 1..num_blocks."""
    if any(t != "attn" for t in cfg.layer_types):
        # moe is excluded on purpose, not just recurrent kinds: chunked
        # prefill would change expert capacity (it depends on the static
        # chunk length), silently breaking bit-identity with the
        # unchunked prefill
        raise NotImplementedError(
            f"paged KV needs a pure attention stack, got "
            f"{set(cfg.layer_types)} ({cfg.name})")
    if (window or cfg.attn_window) and cfg.mla is not None:
        raise NotImplementedError(
            "paged KV + sliding window is GQA-only: the arena mla_prefill "
            "ignores the window, so there is no windowed-MLA family for a "
            "ring to stay bit-identical with (use the slot arena)")
    segs = build_segments(cfg.layer_types)
    pools = []
    for kind, count in segs:
        one = init_cache_layer(cfg, kind, num_blocks + 1, block_size, dtype)
        one = {k: v for k, v in one.items() if k != "ptr"}   # tables rule
        pools.append(jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (count,) + a.shape), one))
    return pools


def prefill_chunk_into_blocks(cfg, params, tokens, length, ctx_len,
                              block_table, pool, window=0):
    """Stream one prompt chunk into a slot's blocks (batch-1 admission).

    tokens: [1, C] int32, the next chunk right-padded to the fixed chunk
    size C (pads are causally invisible to valid positions and their
    writes land beyond the slot's validity length, so they are inert —
    on a ring, `length` additionally routes their scatter to the null
    block, since a pad's ring slot can hold live wrapped context).
    length: valid tokens in this chunk (traced scalar).
    ctx_len: tokens already streamed into the slot's blocks (traced).
    block_table: int32 [W] physical block ids for this slot (traced
    values, static W — no recompile as tables change).
    pool: from init_pool.

    Returns (logits [1,1,V] at chunk position length-1 — only meaningful
    for the final chunk — and the updated pool)."""
    params = _cast(cfg, params)
    win = cfg.attn_window or window
    x = embed(params["embed"], tokens).astype(jnp.dtype(cfg.compute_dtype))
    _, c, _ = x.shape
    positions = ctx_len + jnp.broadcast_to(jnp.arange(c)[None], (1, c))
    x, pool, _ = forward(cfg, params, x, positions=positions, mode="prefill",
                         caches=pool, window=win,
                         paged={"table": block_table, "ctx_len": ctx_len,
                                "valid": length})
    h_last = jax.lax.dynamic_slice_in_dim(x, length - 1, 1, axis=1)
    logits = logits_fn(cfg, params, h_last).astype(jnp.float32)
    return logits, pool


def decode_rows_paged(cfg, params, token, pool, block_tables, lengths,
                      window=0):
    """One decode step over all slots against the shared block pool.

    token: [B,1] int32; block_tables: int32 [B, W]; lengths: int32 [B]
    tokens already cached per row (the incoming token's position).  Dead
    rows carry a zeroed table + length 0: they read/write only the null
    block and the engine masks their logits host-side.

    Returns (logits [B,1,V], new pool)."""
    params = _cast(cfg, params)
    win = cfg.attn_window or window
    x = embed(params["embed"], token).astype(jnp.dtype(cfg.compute_dtype))
    b = x.shape[0]
    lengths = jnp.reshape(jnp.asarray(lengths, jnp.int32), (b,))
    positions = jnp.reshape(lengths, (b, 1))
    x, pool, _ = forward(cfg, params, x, positions=positions, mode="decode",
                         caches=pool, window=win,
                         paged={"tables": block_tables, "lengths": lengths})
    logits = logits_fn(cfg, params, x).astype(jnp.float32)
    return logits, pool
