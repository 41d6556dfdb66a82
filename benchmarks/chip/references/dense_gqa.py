"""Plain reference of a dense GQA decoder (qwen2, internlm2).

Imports nothing of the program.  The weights come from `init`, which
draws them from the seed in the same order and with the same
distributions as the program's initialisation, so that both sides
start from the same numbers without one handing its weights to the
other.  The forward pass is written out layer by layer from the
published architecture: RMSNorm (eps 1e-6), rotary embeddings on
half-split head dimensions, grouped-query causal attention, a SwiGLU
MLP, and a tied or untied output head.

`Precision` picks how every matrix product is computed: "f32" at
`HIGHEST` (the reference), or "fp8", both operands quantised to
float8 e4m3 with a per-tensor scale (the control: one step below the
bfloat16 compute the configurations state).
"""
from __future__ import annotations

import functools

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


def _layer_init(cfg, key):
    d, h, kv, hd, f = (cfg["d_model"], cfg["num_heads"], cfg["num_kv_heads"],
                       cfg["head_dim"], cfg["d_ff"])
    ks = jax.random.split(key, 4)
    ka = jax.random.split(ks[0], 4)
    attn = {"wq": _he(ka[0], (d, h * hd)),
            "wk": _he(ka[1], (d, kv * hd)),
            "wv": _he(ka[2], (d, kv * hd)),
            "wo": _he(ka[3], (h * hd, d), fan_in=h * hd)}
    if cfg["qkv_bias"]:
        attn["bq"] = jnp.zeros((h * hd,), jnp.float32)
        attn["bk"] = jnp.zeros((kv * hd,), jnp.float32)
        attn["bv"] = jnp.zeros((kv * hd,), jnp.float32)
    km = jax.random.split(ks[1], 3)
    mlp = {"w_gate": _he(km[0], (d, f)), "w_up": _he(km[1], (d, f)),
           "w_down": _he(km[2], (f, d))}
    ones = {"scale": jnp.ones((d,), jnp.float32)}
    return {"ln1": ones, "attn": attn, "ln2": dict(ones), "mlp": mlp}


def init(cfg, key):
    """float32 weights: {"embed", "segments": [stacked layers],
    "final_norm", "head" (untied only)}."""
    keys = jax.random.split(key, 3)
    layers = jax.random.split(keys[0], cfg["num_layers"])
    params = {
        "embed": {"table": (jax.random.normal(
            keys[1], (cfg["vocab_size"], cfg["d_model"])) * 0.02
        ).astype(jnp.float32)},
        "segments": [jax.vmap(functools.partial(_layer_init, cfg))(layers)],
        "final_norm": {"scale": jnp.ones((cfg["d_model"],), jnp.float32)},
    }
    if not cfg["tie_embeddings"]:
        params["head"] = _he(keys[2], (cfg["d_model"], cfg["vocab_size"]))
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


def _rope(x, theta):
    """x [S, heads, hd]: rotate the two halves of each head."""
    s, _, hd = x.shape
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _layer(cfg, dot, x, p):
    """One decoder layer over a single sequence x [S, D]."""
    s = x.shape[0]
    h, kv, hd = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    a = p["attn"]
    y = _rmsnorm(x, p["ln1"]["scale"])
    q = dot("sd,de->se", y, a["wq"])
    k = dot("sd,de->se", y, a["wk"])
    v = dot("sd,de->se", y, a["wv"])
    if cfg["qkv_bias"]:
        q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
    q = _rope(q.reshape(s, h, hd), cfg["rope_theta"])
    k = _rope(k.reshape(s, kv, hd), cfg["rope_theta"])
    v = v.reshape(s, kv, hd)
    # query head j reads key/value head j // (h // kv)
    k = jnp.repeat(k, h // kv, axis=1)
    v = jnp.repeat(v, h // kv, axis=1)
    logits = dot("shd,thd->hst", q, k) / float(np.sqrt(hd))
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    logits = jnp.where(causal[None], logits, -jnp.inf)
    att = jax.nn.softmax(logits, axis=-1)
    o = dot("hst,thd->shd", att, v).reshape(s, h * hd)
    x = x + dot("se,ed->sd", o, a["wo"])
    y = _rmsnorm(x, p["ln2"]["scale"])
    m = p["mlp"]
    gate = dot("sd,df->sf", y, m["w_gate"])
    up = dot("sd,df->sf", y, m["w_up"])
    return x + dot("sf,fd->sd", jax.nn.silu(gate) * up, m["w_down"])


def logits(cfg, params, tokens, precision="f32"):
    """f32 logits [S, V] of one sequence of token ids [S]."""
    dot = dot_fn(precision)
    x = params["embed"]["table"][tokens]
    # each layer's activations are recomputed for the gradient rather
    # than kept, so that a chip holds the reference beside the state
    layer = jax.checkpoint(lambda c, p: _layer(cfg, dot, c, p))
    x, _ = jax.lax.scan(lambda c, p: (layer(c, p), None), x,
                        params["segments"][0])
    x = _rmsnorm(x, params["final_norm"]["scale"])
    if cfg["tie_embeddings"]:
        return dot("sd,vd->sv", x, params["embed"]["table"])
    return dot("sd,dv->sv", x, params["head"])


def loss(cfg, params, tokens, targets, precision="f32"):
    """Mean next-token cross-entropy over a batch [B, S]."""
    def one(t, y):
        lg = logits(cfg, params, t, precision)
        logz = jax.nn.logsumexp(lg, axis=-1)
        gold = jnp.take_along_axis(lg, y[:, None], axis=-1)[:, 0]
        return jnp.mean(logz - gold)
    return jnp.mean(jax.vmap(one)(tokens, targets))
