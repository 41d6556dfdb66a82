"""Shared building blocks: norms, RoPE, MLPs, embeddings.

All layers are pure functions over explicit param pytrees (dicts), so they
compose with jax.vmap (agent axis), jax.lax.scan (layer stacking) and GSPMD
sharding without framework machinery.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def _he(key, shape, dtype, fan_in=None):
    fan_in = fan_in if fan_in is not None else shape[0]
    return (jax.random.normal(key, shape) / np.sqrt(fan_in)).astype(dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------


def rmsnorm_init(d, dtype):
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm(params, x, eps=1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * params["scale"].astype(jnp.float32)).astype(dt)


def layernorm_init(d, dtype):
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


def layernorm(params, x, eps=1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    x = (x - mu) * jax.lax.rsqrt(var + eps)
    out = x * params["scale"].astype(jnp.float32) + params["bias"].astype(
        jnp.float32)
    return out.astype(dt)


def make_norm(norm_type):
    if norm_type == "rmsnorm":
        return rmsnorm_init, rmsnorm
    if norm_type == "layernorm":
        return layernorm_init, layernorm
    raise ValueError(norm_type)


# ---------------------------------------------------------------------------
# rotary position embeddings
# ---------------------------------------------------------------------------


def rope_frequencies(head_dim, theta, scaling=None):
    """[head_dim / 2] rotation frequencies; with `scaling` (a YaRN
    `RopeScaling`), YaRN's blend (arXiv:2309.00071, as DeepSeek-V2's
    reference code has it): the dims that turn more than beta_fast times
    over the original context keep theta's frequency, those that turn
    fewer than beta_slow times take it divided by the factor, and a
    linear ramp joins the two."""
    freqs = 1.0 / (theta ** (jnp.arange(0, head_dim, 2,
                                        dtype=jnp.float32) / head_dim))
    if scaling is None:
        return freqs

    def turns_dim(turns):       # the dim that turns `turns` times
        return (head_dim * math.log(
            scaling.original_max_position_embeddings
            / (turns * 2 * math.pi))) / (2 * math.log(theta))

    low = max(math.floor(turns_dim(scaling.beta_fast)), 0)
    high = min(math.ceil(turns_dim(scaling.beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return freqs / scaling.factor * ramp + freqs * (1.0 - ramp)


def yarn_mscale(factor, mscale):
    """YaRN's attention temperature term, 0.1 mscale ln(factor) + 1."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def apply_rope(x, positions, theta=1e4, scaling=None):
    """x: [..., seq, heads, head_dim]; positions: [..., seq].  `scaling`:
    YaRN's frequencies (`rope_frequencies`); cos and sin stay unscaled,
    as mscale == mscale_all_dim makes them (`RopeScaling`)."""
    hd = x.shape[-1]
    freqs = rope_frequencies(hd, theta, scaling)              # [hd/2]
    angles = positions[..., None].astype(jnp.float32) * freqs  # [..., s, hd/2]
    cos = jnp.cos(angles)[..., :, None, :]                     # [..., s, 1, hd/2]
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(seq_len, d_model):
    pos = np.arange(seq_len)[:, None]
    dim = np.arange(0, d_model, 2)[None, :]
    angle = pos / np.power(10000.0, dim / d_model)
    out = np.zeros((seq_len, d_model), dtype=np.float32)
    out[:, 0::2] = np.sin(angle)
    out[:, 1::2] = np.cos(angle)
    return jnp.asarray(out)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------


def mlp_init(key, d_model, d_ff, mlp_type, dtype):
    ks = jax.random.split(key, 3)
    if mlp_type == "swiglu":
        return {
            "w_gate": _he(ks[0], (d_model, d_ff), dtype),
            "w_up": _he(ks[1], (d_model, d_ff), dtype),
            "w_down": _he(ks[2], (d_ff, d_model), dtype),
        }
    # gelu / sq_relu: single up projection
    return {
        "w_up": _he(ks[0], (d_model, d_ff), dtype),
        "w_down": _he(ks[1], (d_ff, d_model), dtype),
    }


def mlp_apply(params, x, mlp_type):
    if mlp_type == "swiglu":
        h = jax.nn.silu(x @ params["w_gate"]) * (x @ params["w_up"])
    elif mlp_type == "gelu":
        h = jax.nn.gelu(x @ params["w_up"])
    elif mlp_type == "sq_relu":
        h = jnp.square(jax.nn.relu(x @ params["w_up"]))
    else:
        raise ValueError(mlp_type)
    return h @ params["w_down"]


# ---------------------------------------------------------------------------
# embeddings
# ---------------------------------------------------------------------------


def embedding_init(key, vocab, d_model, dtype):
    return {"table": (jax.random.normal(key, (vocab, d_model)) * 0.02
                      ).astype(dtype)}


def embed(params, tokens):
    return jnp.take(params["table"], tokens, axis=0)


def unembed(params, x):
    return x @ params["table"].T
