"""Model FLOPs of a decoder of latent attention (MLA) and routed
experts, from the shapes of a configuration's model dict, as
`yardstick.py` counts the dense decoder's: nothing here reads the
program or its compiled code.

Per token, 2 FLOPs for each parameter that takes part in a matrix
product: each layer's MLA projections, the dense layers' MLP, each
expert layer's router and shared experts, and of its routed experts
those the token is expected to reach among the ones held here
(top_k * experts_held / num_experts of them), and the output head.
Attention adds QK^T at the qk head width (nope + rope) and PV at the
value width over the causal keys.  Backward is twice the forward; no
recomputation is counted.

`all_params` counts every parameter one chip holds (norm scales
included), for the update's bytes.
"""
from __future__ import annotations


def attention_params(m):
    d, h, a = m["d_model"], m["num_heads"], m["mla"]
    qk = a["qk_nope_head_dim"] + a["qk_rope_head_dim"]
    r = a["kv_lora_rank"]
    ql = a.get("q_lora_rank") or 0
    q = d * ql + ql * h * qk if ql else d * h * qk
    return (q + d * (r + a["qk_rope_head_dim"])
            + r * h * (a["qk_nope_head_dim"] + a["v_head_dim"])
            + h * a["v_head_dim"] * d)


def moe_params_per_token(m):
    """An expert layer's parameters that a token multiplies: router,
    shared experts, and the held routed experts it is expected to reach."""
    d, e = m["d_model"], m["moe"]
    held = e.get("experts_held") or e["num_experts"]
    expert = 3 * d * e["d_ff_expert"]
    reached = e["top_k"] * held / e["num_experts"]
    return (d * e["num_experts"] + e.get("num_shared_experts", 0) * expert
            + reached * expert)


def layer_kinds(m):
    return m.get("layer_types") or ["moe"] * m["num_layers"]


def matmul_params_per_token(m):
    kinds = layer_kinds(m)
    dense = sum(k != "moe" for k in kinds)
    moe = sum(k == "moe" for k in kinds)
    return (len(kinds) * attention_params(m) + dense * 3 * m["d_model"]
            * m["d_ff"] + moe * moe_params_per_token(m)
            + m["vocab_size"] * m["d_model"])


def all_params(m):
    """Every parameter of the model dict: embedding, head (untied),
    norms, each layer's MLA projections and latent norm, the dense
    layers' MLP, and each expert layer's router (all experts' outputs),
    shared experts and held routed experts."""
    d, a = m["d_model"], m["mla"]
    kinds = layer_kinds(m)
    per_layer = (attention_params(m) + a["kv_lora_rank"]
                 + (a.get("q_lora_rank") or 0) + 2 * d)
    total = len(kinds) * per_layer + d + m["vocab_size"] * d * (
        1 if m.get("tie_embeddings") else 2)
    for k in kinds:
        if k == "moe":
            e = m["moe"]
            held = e.get("experts_held") or e["num_experts"]
            total += d * e["num_experts"] + 3 * d * e["d_ff_expert"] * (
                held + e.get("num_shared_experts", 0))
        else:
            total += 3 * d * m["d_ff"]
    return total


def prox_update_bytes(m):
    """The update's least traffic: read x, g, zsum; write x_new and the
    token credit; float32, 20 bytes a parameter."""
    return 20 * all_params(m)


def attention_flops_fwd(m, seq):
    """QK^T and PV of one causal sequence, every layer."""
    a = m["mla"]
    keys = seq * (seq + 1) / 2
    width = a["qk_nope_head_dim"] + a["qk_rope_head_dim"] + a["v_head_dim"]
    return 2 * len(layer_kinds(m)) * m["num_heads"] * width * keys


def train_step_flops(m, agents, batch, seq):
    """Model FLOPs of one superstep: forward and backward (3x the
    forward) of every agent's batch."""
    fwd = (2 * matmul_params_per_token(m) * seq
           + attention_flops_fwd(m, seq)) * agents * batch
    return 3 * fwd
