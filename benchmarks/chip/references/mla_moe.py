"""Plain reference of a DeepSeek-V2 decoder: multi-head latent attention
and fine-grained routed experts with shared ones (deepseek-v2-lite).

Imports nothing of the program.  `init` draws the weights from the seed
in the same order and with the same distributions as the program's
initialisation, so that both sides start from the same numbers without
one handing its weights to the other.  The forward pass is written out
layer by layer from the published architecture (arXiv:2405.04434 and
DeepSeek-V2-Lite's config.json), one sequence at a time:

  * RMSNorm (eps 1e-6).
  * MLA: q = x Wq (no query LoRA where q_lora_rank is 0, else
    RMSNorm(x Wq_a) Wq_b); [c_kv, k_rope] = x Wkv_a, c_kv normed;
    k_nope = c_kv Wk_b, v = c_kv Wv_b; the rope dims of q and the one
    shared k_rope rotated; causal softmax attention over
    [q_nope, q_rope] . [k_nope, k_rope] at scale 1/sqrt(qk) times
    YaRN's mscale(mscale_all_dim)^2.
  * YaRN rope (arXiv:2309.00071, as DeepSeek's code computes it): the
    rotation frequencies blend theta's and theta's over `factor` by a
    linear ramp between the dims that turn beta_fast and beta_slow times
    over the original context; cos and sin unscaled (they take mscale /
    mscale_all_dim, which is 1 in DeepSeek-V2's configs).
  * The first `layer_types` "attn" layers have a dense SwiGLU MLP; "moe"
    layers: softmax router over all experts, top-k, the gates
    renormalised only if norm_topk_prob, the held experts (a plain loop)
    plus the shared experts; the sequence's balance loss
    alpha sum_i f_i P_i (alpha: config.json's aux_loss_alpha, 0.001;
    f_i = E / (k S) times the picks of expert i, P_i its mean score),
    the mean over sequences added to the loss.

Departures from the published model, each the program's too:

  * capacity dropping: an expert takes at most
    max(ceil(S k / E * capacity_factor), 4) of a sequence's assignments,
    in token order (the published model drops none in training;
    capacity_factor 1.25 is assumed);
  * the rope rotates the two halves of the rope dims, where DeepSeek's
    code rotates interleaved pairs: with random weights, a fixed
    permutation of the rope columns of Wq and Wkv_a;
  * a chip's share: the layer holds experts [expert_offset,
    expert_offset + experts_held) and gives only their part of the
    routed sum, as one of the chips that divide the experts would.

`precision` picks how every matrix product is computed: "f32" at
`HIGHEST` (the reference), or "fp8", both operands quantised to float8
e4m3 with a per-tensor scale (the control).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0
NORM_EPS = 1e-6


# ---------------------------------------------------------------------------
# initialisation (bit for bit the program's, from the same seed)
# ---------------------------------------------------------------------------


def _he(key, shape, fan_in=None):
    fan_in = fan_in if fan_in is not None else shape[0]
    return (jax.random.normal(key, shape) / np.sqrt(fan_in)).astype(
        jnp.float32)


def _ones(n):
    return {"scale": jnp.ones((n,), jnp.float32)}


def _attn_init(cfg, key):
    d, h = cfg["d_model"], cfg["num_heads"]
    m = cfg["mla"]
    nope, rope, vd = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                      m["v_head_dim"])
    r, ql = m["kv_lora_rank"], m["q_lora_rank"] or 0
    ks = jax.random.split(key, 7)
    p = {"wkv_a": _he(ks[2], (d, r + rope)), "kv_norm": _ones(r),
         "wk_b": _he(ks[3], (r, h * nope)), "wv_b": _he(ks[4], (r, h * vd)),
         "wo": _he(ks[5], (h * vd, d), fan_in=h * vd)}
    if ql:
        p.update(wq_a=_he(ks[0], (d, ql)), q_norm=_ones(ql),
                 wq_b=_he(ks[1], (ql, h * (nope + rope))))
    else:
        p["wq"] = _he(ks[0], (d, h * (nope + rope)))
    return p


def _experts(key, moe, shape, fan_in):
    """Held experts: the whole stack from one draw where every expert
    is held, else each held expert from the key folded with its index."""
    e, held = moe["num_experts"], moe.get("experts_held") or moe["num_experts"]
    if held == e:
        return _he(key, (e,) + shape, fan_in=fan_in)
    off = moe.get("expert_offset", 0)
    return jnp.stack([_he(jax.random.fold_in(key, off + i), shape,
                          fan_in=fan_in) for i in range(held)])


def _moe_init(cfg, key):
    d, moe = cfg["d_model"], cfg["moe"]
    f = moe["d_ff_expert"]
    ks = jax.random.split(key, 5)
    p = {"router": _he(ks[0], (d, moe["num_experts"])),
         "w_gate": _experts(ks[1], moe, (d, f), d),
         "w_up": _experts(ks[2], moe, (d, f), d),
         "w_down": _experts(ks[3], moe, (f, d), f)}
    if moe.get("num_shared_experts"):
        fs = f * moe["num_shared_experts"]
        k2 = jax.random.split(ks[4], 3)
        p["shared"] = {"w_gate": _he(k2[0], (d, fs)),
                       "w_up": _he(k2[1], (d, fs)),
                       "w_down": _he(k2[2], (fs, d))}
    return p


def _layer_init(cfg, kind, key):
    d = cfg["d_model"]
    ks = jax.random.split(key, 4)
    p = {"ln1": _ones(d), "attn": _attn_init(cfg, ks[0]), "ln2": _ones(d)}
    if kind == "moe":
        p["moe"] = _moe_init(cfg, ks[1])
    else:
        km = jax.random.split(ks[1], 3)
        f = cfg["d_ff"]
        p["mlp"] = {"w_gate": _he(km[0], (d, f)), "w_up": _he(km[1], (d, f)),
                    "w_down": _he(km[2], (f, d))}
    return p


def segments(cfg):
    """[(kind, count)] of the runs of `layer_types`."""
    out = []
    for kind in cfg["layer_types"]:
        if out and out[-1][0] == kind:
            out[-1][1] += 1
        else:
            out.append([kind, 1])
    return [tuple(s) for s in out]


def init(cfg, key):
    """float32 weights: {"embed", "segments": [one stack per run of
    layer kinds], "final_norm", "head" (untied only)}."""
    segs = segments(cfg)
    keys = jax.random.split(key, len(segs) + 2)
    params = {
        "embed": {"table": (jax.random.normal(
            keys[-2], (cfg["vocab_size"], cfg["d_model"])) * 0.02
        ).astype(jnp.float32)},
        "segments": [
            jax.vmap(functools.partial(_layer_init, cfg, kind))(
                jax.random.split(k, count))
            for (kind, count), k in zip(segs, keys[:-2])],
        "final_norm": _ones(cfg["d_model"]),
    }
    if not cfg["tie_embeddings"]:
        params["head"] = _he(keys[-1], (cfg["d_model"], cfg["vocab_size"]))
    return params


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _quantise(a):
    scale = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / F8_MAX
    return (a / scale).astype(F8).astype(jnp.float32), scale


def dot_fn(precision):
    """(spec, a, b) -> einsum in the given precision, f32 out."""
    if precision == "f32":
        return lambda spec, a, b: jnp.einsum(spec, a, b, precision=HIGHEST)
    if precision == "fp8":
        def dot(spec, a, b):
            qa, sa = _quantise(a)
            qb, sb = _quantise(b)
            return jnp.einsum(spec, qa, qb, precision=HIGHEST) * (sa * sb)
        return dot
    raise ValueError(precision)


def _rmsnorm(x, scale):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + NORM_EPS) * scale


def _mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def _rope_freqs(cfg, dim):
    theta = cfg["rope_theta"]
    freqs = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    ys = cfg.get("rope_scaling")
    if not ys:
        return freqs

    def dim_of(turns):
        return (dim * math.log(ys["original_max_position_embeddings"]
                               / (turns * 2 * math.pi))) / (2 * math.log(theta))

    low = max(math.floor(dim_of(ys["beta_fast"])), 0)
    high = min(math.ceil(dim_of(ys["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return freqs / ys["factor"] * ramp + freqs * (1.0 - ramp)


def _rope(cfg, x):
    """x [S, heads, dim]: rotate the two halves of each head's rope dims."""
    s, _, dim = x.shape
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * _rope_freqs(cfg, dim)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :dim // 2], x[..., dim // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def softmax_scale(cfg):
    m = cfg["mla"]
    scale = 1.0 / math.sqrt(m["qk_nope_head_dim"] + m["qk_rope_head_dim"])
    ys = cfg.get("rope_scaling")
    if ys and ys.get("mscale_all_dim"):
        scale *= _mscale(ys["factor"], ys["mscale_all_dim"]) ** 2
    return scale


def _attention(cfg, dot, x, a):
    """MLA over one sequence x [S, D] (already normed)."""
    s = x.shape[0]
    h = cfg["num_heads"]
    m = cfg["mla"]
    nope, rope, vd = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                      m["v_head_dim"])
    if "wq" in a:
        q = dot("sd,de->se", x, a["wq"])
    else:
        q = dot("sd,de->se",
                _rmsnorm(dot("sd,dr->sr", x, a["wq_a"]), a["q_norm"]["scale"]),
                a["wq_b"])
    q = q.reshape(s, h, nope + rope)
    q_nope, q_rope = q[..., :nope], _rope(cfg, q[..., nope:])
    kv = dot("sd,de->se", x, a["wkv_a"])
    r = m["kv_lora_rank"]
    c_kv = _rmsnorm(kv[:, :r], a["kv_norm"]["scale"])
    k_rope = _rope(cfg, kv[:, None, r:])                     # [S, 1, rope]
    k_nope = dot("sr,re->se", c_kv, a["wk_b"]).reshape(s, h, nope)
    v = dot("sr,re->se", c_kv, a["wv_b"]).reshape(s, h, vd)
    logits = (dot("shd,thd->hst", q_nope, k_nope)
              + dot("shd,tzd->hst", q_rope, k_rope)) * softmax_scale(cfg)
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    logits = jnp.where(causal[None], logits, -jnp.inf)
    att = jax.nn.softmax(logits, axis=-1)
    o = dot("hst,thd->shd", att, v).reshape(s, h * vd)
    return dot("se,ed->sd", o, a["wo"])


def _swiglu(dot, x, w_gate, w_up, w_down):
    return dot("sf,fd->sd", jax.nn.silu(dot("sd,df->sf", x, w_gate))
               * dot("sd,df->sf", x, w_up), w_down)


def _moe(cfg, dot, x, p):
    """(routed share + shared experts [S, D], balance loss) of one
    sequence x [S, D] (already normed)."""
    moe = cfg["moe"]
    s = x.shape[0]
    e, k = moe["num_experts"], moe["top_k"]
    held = moe.get("experts_held") or e
    off = moe.get("expert_offset", 0)
    probs = jax.nn.softmax(dot("sd,de->se", x, p["router"]), axis=-1)
    gate, pick = jax.lax.top_k(probs, k)                       # [S, k]
    if moe.get("norm_topk_prob", True):
        gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
    picks = jnp.sum(jax.nn.one_hot(pick, e), axis=1)           # [S, E]
    f_i = jnp.sum(picks, axis=0) * (e / (k * s))
    aux = moe["router_aux_loss"] * jnp.sum(f_i * jnp.mean(probs, axis=0))
    capacity = max(math.ceil(s * k / e * moe["capacity_factor"]), 4)
    out = jnp.zeros_like(x)
    for j in range(held):
        mine = (pick == off + j).reshape(-1)                   # token order
        place = jnp.cumsum(mine) - 1
        taken = (mine & (place < capacity)).reshape(s, k)
        weight = jnp.sum(jnp.where(taken, gate, 0.0), axis=-1)  # [S]
        y = _swiglu(dot, x, p["w_gate"][j], p["w_up"][j], p["w_down"][j])
        out = out + weight[:, None] * y
    if "shared" in p:
        sp = p["shared"]
        out = out + _swiglu(dot, x, sp["w_gate"], sp["w_up"], sp["w_down"])
    return out, aux


def _layer(cfg, kind, dot, x, p):
    """One decoder layer over a single sequence x [S, D] -> (x, aux)."""
    x = x + _attention(cfg, dot, _rmsnorm(x, p["ln1"]["scale"]), p["attn"])
    y = _rmsnorm(x, p["ln2"]["scale"])
    if kind == "moe":
        ff, aux = _moe(cfg, dot, y, p["moe"])
    else:
        m = p["mlp"]
        ff, aux = _swiglu(dot, y, m["w_gate"], m["w_up"], m["w_down"]), 0.0
    return x + ff, aux


def logits_and_aux(cfg, params, tokens, precision="f32"):
    """(f32 logits [S, V], the sequence's balance loss summed over the
    layers) of one sequence of token ids [S]."""
    dot = dot_fn(precision)
    x = params["embed"]["table"][tokens]
    aux = jnp.zeros((), jnp.float32)
    for (kind, _), stack in zip(segments(cfg), params["segments"]):
        # each layer's activations are recomputed for the gradient
        # rather than kept, so that a chip holds the reference beside
        # the state
        layer = jax.checkpoint(functools.partial(_layer, cfg, kind, dot))
        x, auxs = jax.lax.scan(lambda c, p: layer(c, p), x, stack)
        aux = aux + jnp.sum(auxs)
    x = _rmsnorm(x, params["final_norm"]["scale"])
    if cfg["tie_embeddings"]:
        return dot("sd,vd->sv", x, params["embed"]["table"]), aux
    return dot("sd,dv->sv", x, params["head"]), aux


def logits(cfg, params, tokens, precision="f32"):
    """f32 logits [S, V] of one sequence of token ids [S]."""
    return logits_and_aux(cfg, params, tokens, precision)[0]


def loss(cfg, params, tokens, targets, precision="f32"):
    """Mean next-token cross-entropy over a batch [B, S], plus the
    balance loss (each sequence's, the mean over the batch)."""
    def one(t, y):
        lg, aux = logits_and_aux(cfg, params, t, precision)
        logz = jax.nn.logsumexp(lg, axis=-1)
        gold = jnp.take_along_axis(lg, y[:, None], axis=-1)[:, 0]
        return jnp.mean(logz - gold) + aux
    return jnp.mean(jax.vmap(one)(tokens, targets))
