"""Mixture-of-Experts: top-k routing, GShard-style grouped dispatch.

Dispatch follows the GShard/GSPMD einsum formulation (arXiv:2006.16668):
tokens are grouped (group = sequence), positions within each (group,
expert) bucket come from a per-group cumulative sum, and dispatch/combine
are one-hot einsums

    buf[g,e,c,d]  = sum_s dispatch[g,s,e,c] * x[g,s,d]
    out[g,s,d]    = sum_{e,c} combine[g,s,e,c] * y[g,e,c,d]

which GSPMD partitions cleanly: groups over the data axes, experts over
"model" (the relayout between the two IS the canonical MoE all-to-all).
A sort/scatter dispatch (kept below as moe_apply_scatter for comparison)
defeats the partitioner — it manufactures capacity-sized partial-sum
all-reduces (measured in EXPERIMENTS.md §Perf, dbrx iterations 1-3).

Tokens above per-group capacity are dropped (standard GShard semantics).
Expert weights are stacked on a leading E axis so expert parallelism is a
pure sharding annotation.  A layer may also be told that it holds only
experts [expert_offset, expert_offset + experts_held): one chip's share
when the experts are divided over chips.  It routes over all of them and
gives the held experts' part of the routed sum (the shares, with the
shared experts counted once, add up to the whole layer).

The work is named in the compiled program: model.moe.router (scores,
top-k, balance loss), model.moe.dispatch (bucket positions, one-hots,
dispatch and combine), model.moe.experts and model.moe.shared.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.hints import shard_hint
from repro.models.layers import _he


def _experts(key, m, shape, dtype, fan_in):
    """The routed experts' stack [experts_held, *shape].  A layer that
    holds every expert draws the stack at once; one that holds a share
    draws each expert from its own key (the layer's key folded with the
    expert's index), so a chip never draws the experts it does not
    hold, and every share gets the experts of that index."""
    if m.experts_held == m.num_experts:
        return _he(key, (m.num_experts,) + shape, dtype, fan_in=fan_in)
    ids = m.expert_offset + jnp.arange(m.experts_held)
    return jax.vmap(lambda i: _he(jax.random.fold_in(key, i), shape, dtype,
                                  fan_in=fan_in))(ids)


def moe_init(key, cfg, dtype):
    """Router [d, num_experts]; routed expert stacks [experts_held, ...]
    (`_experts`); shared experts."""
    m = cfg.moe
    d = cfg.d_model
    f = m.d_ff_expert
    ks = jax.random.split(key, 5)
    p = {
        "router": _he(ks[0], (d, m.num_experts), dtype),
        "w_gate": _experts(ks[1], m, (d, f), dtype, d),
        "w_up": _experts(ks[2], m, (d, f), dtype, d),
        "w_down": _experts(ks[3], m, (f, d), dtype, f),
    }
    if m.num_shared_experts:
        fs = f * m.num_shared_experts
        ks2 = jax.random.split(ks[4], 3)
        p["shared"] = {
            "w_gate": _he(ks2[0], (d, fs), dtype),
            "w_up": _he(ks2[1], (d, fs), dtype),
            "w_down": _he(ks2[2], (fs, d), dtype),
        }
    return p


def _route(params, m, x):
    """x [..., S, D] -> (gate weights and expert ids [..., S, k], the
    load-balance loss), over all m.num_experts."""
    e, k = m.num_experts, m.top_k
    logits = (x @ params["router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_w, gate_i = jax.lax.top_k(probs, k)
    if m.norm_topk_prob:
        gate_w = gate_w / jnp.maximum(gate_w.sum(-1, keepdims=True), 1e-9)
    picked = jax.nn.one_hot(gate_i, e, dtype=jnp.float32).sum(-2)  # [..,S,E]
    if m.aux_kind == "seq":
        # per sequence: alpha sum_i f_i P_i, f_i = E / (k S) * (times
        # expert i was picked), P_i = its mean score; the mean over
        # sequences
        s = x.shape[-2]
        f_i = picked.sum(-2) * (e / (k * s))
        aux = m.router_aux_loss * jnp.mean(
            jnp.sum(f_i * probs.mean(-2), axis=-1))
    else:
        # Switch-style, over every token of the batch
        lead = tuple(range(picked.ndim - 1))
        aux = m.router_aux_loss * e * jnp.sum(
            jnp.mean(picked, axis=lead) * probs.mean(axis=lead))
    return gate_w, gate_i, aux


def _shared(params, x):
    sp = params["shared"]
    shape = x.shape
    xf = x.reshape(-1, shape[-1])
    hs = jax.nn.silu(xf @ sp["w_gate"]) * (xf @ sp["w_up"])
    return (hs @ sp["w_down"]).reshape(shape)


def moe_apply(params, cfg, x):
    """x: [B, S, D] -> (out [B, S, D], aux_loss, dropped): dropped
    counts the held experts' assignments over capacity.  Groups =
    sequences.

    The router scores all num_experts and picks the top k of them; the
    layer dispatches to, computes and combines only the experts it holds
    (`MoEConfig.experts_held` from `expert_offset`), so its output is
    that share of the routed experts' sum, plus the shared experts.
    Capacity per (sequence, expert) is ceil(S k / num_experts * cf)
    whichever experts are held, and a token's place in an expert's
    bucket counts that expert's assignments alone, so what an expert
    drops does not depend on which others are held."""
    m = cfg.moe
    b, s, d = x.shape
    k = m.top_k
    e = m.experts_held

    with jax.named_scope("model.moe.router"):
        gate_w, gate_i, aux = _route(params, m, x)

    capacity = max(int(np.ceil(s * k / m.num_experts * m.capacity_factor)),
                   4)

    with jax.named_scope("model.moe.dispatch"):
        # position of each (token, slot) within its (group, expert)
        # bucket: flatten slots in token-major order and cumsum the
        # held experts' one-hots (an expert not held has none)
        held_i = gate_i.reshape(b, s * k) - m.expert_offset
        oh_e = jax.nn.one_hot(held_i, e, dtype=jnp.float32)      # [B,sk,E]
        pos = (jnp.cumsum(oh_e, axis=1) * oh_e).sum(-1) - 1.0    # [B,sk]
        keep = (pos < capacity) & (pos >= 0)
        dropped = jnp.sum(pos >= capacity)
        oh_c = jax.nn.one_hot(pos.astype(jnp.int32), capacity,
                              dtype=jnp.float32)                # [B,sk,C]
        oh_c = oh_c * keep[..., None]

        gates_flat = gate_w.reshape(b, s * k)
        # combine[g,s,e,c]: contract the k slots (token-major flatten)
        combine = jnp.einsum("gre,grc->grec", oh_e,
                             oh_c * gates_flat[..., None])
        combine = combine.reshape(b, s, k, e, capacity).sum(axis=2)
        dispatch = (combine > 0).astype(x.dtype)                 # [B,S,E,C]

        # canonical GShard einsums: groups over data axes, experts over
        # model
        buf = jnp.einsum("gsec,gsd->gecd", dispatch, x)          # [B,E,C,D]
        buf = shard_hint(buf, ("replica", "data"), "model", None, None)

    with jax.named_scope("model.moe.experts"):
        h = jax.nn.silu(jnp.einsum("gecd,edf->gecf", buf, params["w_gate"]))
        h = h * jnp.einsum("gecd,edf->gecf", buf, params["w_up"])
        y = jnp.einsum("gecf,efd->gecd", h, params["w_down"])
        y = shard_hint(y, ("replica", "data"), "model", None, None)

    with jax.named_scope("model.moe.dispatch"):
        out = jnp.einsum("gsec,gecd->gsd", combine.astype(x.dtype), y)

    if m.num_shared_experts:
        with jax.named_scope("model.moe.shared"):
            out = out + _shared(params, x)

    return out, aux, dropped


def moe_apply_scatter(params, cfg, x):
    """Sort/scatter dispatch (MaxText-style). Kept for comparison: compute-
    optimal per token but GSPMD-hostile — see EXPERIMENTS.md §Perf.
    Capacity is per batch here, and every expert must be held."""
    m = cfg.moe
    if m.experts_held != m.num_experts:
        raise NotImplementedError("scatter dispatch holds every expert")
    b, s, d = x.shape
    t = b * s
    k = m.top_k
    e = m.num_experts
    xf = x.reshape(t, d)

    gate_w, gate_i, aux = _route(params, m, x)
    gate_w, gate_i = gate_w.reshape(t, k), gate_i.reshape(t, k)

    capacity = max(int(np.ceil(t * k / e * m.capacity_factor)), 4)

    e_flat = gate_i.reshape(-1)                                 # [T*k]
    w_flat = gate_w.reshape(-1)
    tok_idx = jnp.repeat(jnp.arange(t), k)

    order = jnp.argsort(e_flat)
    e_sorted = e_flat[order]
    counts = jnp.zeros((e,), jnp.int32).at[e_flat].add(1)
    seg_starts = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(counts)[:-1]])
    pos_sorted = jnp.arange(t * k, dtype=jnp.int32) - seg_starts[e_sorted]
    pos = jnp.zeros((t * k,), jnp.int32).at[order].set(pos_sorted)

    buf = jnp.zeros((e, capacity, d), x.dtype)
    buf = buf.at[e_flat, pos].add(xf[tok_idx], mode="drop")

    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, params["w_gate"]))
    h = h * jnp.einsum("ecd,edf->ecf", buf, params["w_up"])
    y_buf = jnp.einsum("ecf,efd->ecd", h, params["w_down"])

    valid = (pos < capacity)
    dropped = jnp.sum(~valid)
    y_tok = y_buf[e_flat, jnp.minimum(pos, capacity - 1)]
    y_tok = jnp.where(valid[:, None], y_tok, 0.0)
    out = jnp.zeros((t, d), x.dtype).at[tok_idx].add(
        y_tok * w_flat[:, None].astype(x.dtype))

    if m.num_shared_experts:
        out = out + _shared(params, xf)

    out = out.reshape(b, s, d)
    return out, aux, dropped
