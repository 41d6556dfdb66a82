"""deepseek-v2-lite [moe]: MLA without a query LoRA, YaRN rope, one dense
layer, then 64 fine-grained routed experts (top-6) with 2 shared.
[arXiv:2405.04434; hf:deepseek-ai/DeepSeek-V2-Lite config.json]

27L, d_model=2048, 16H (MLA: kv_lora 512, qk 128 nope + 64 rope, v 128),
first_k_dense_replace=1 (dense SwiGLU, d_ff 10944), per-expert d_ff
1408, softmax scores, gates not renormalised (norm_topk_prob false),
routed_scaling_factor 1, sequence-wise balance loss (seq_aux,
aux_loss_alpha 0.001), YaRN
(factor 40 over 4096 positions, mscale = mscale_all_dim = 0.707),
rope_theta 1e4, vocab 102400, untied head.

Its departures (capacity dropping, the rope's half-split layout) are listed in the registry, `repro.configs`.

Agent grouping: 4 agents of 8 chips, 8-way expert parallel inside an
agent (each chip holds 8 of a layer's 64 experts, `MoEConfig.experts_held`
from `expert_offset`), M=2 walks.
"""
from repro.configs.base import (ArchConfig, MLAConfig, MoEConfig, RopeScaling,
                                TrainConfig)

NUM_LAYERS = 27

CONFIG = ArchConfig(
    name="deepseek-v2-lite",
    family="moe",
    source="arXiv:2405.04434",
    num_layers=NUM_LAYERS,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    d_ff=10944,
    vocab_size=102400,
    layer_types=("attn",) + ("moe",) * (NUM_LAYERS - 1),
    rope_theta=10000.0,
    rope_scaling=RopeScaling(factor=40, original_max_position_embeddings=4096,
                             beta_fast=32, beta_slow=1, mscale=0.707,
                             mscale_all_dim=0.707),
    moe=MoEConfig(num_experts=64, top_k=6, num_shared_experts=2,
                  d_ff_expert=1408, capacity_factor=1.25,
                  router_aux_loss=0.001, norm_topk_prob=False,
                  aux_kind="seq"),
    mla=MLAConfig(kv_lora_rank=512, q_lora_rank=0, qk_nope_head_dim=128,
                  qk_rope_head_dim=64, v_head_dim=128),
)

# model_parallel is the agent's expert-parallel width: the expert stacks
# shard over the mesh's "model" axis
TRAIN = TrainConfig(num_agents=4, model_parallel=8, num_walks=2, tau=0.05,
                    rho=20.0)


def smoke() -> ArchConfig:
    return ArchConfig(
        name="deepseek-v2-lite-smoke", family="moe", source=CONFIG.source,
        num_layers=3, d_model=64, num_heads=4, num_kv_heads=4, d_ff=128,
        vocab_size=256, layer_types=("attn", "moe", "moe"),
        rope_theta=10000.0,
        rope_scaling=RopeScaling(factor=4, original_max_position_embeddings=64,
                                 beta_fast=32, beta_slow=1, mscale=0.707,
                                 mscale_all_dim=0.707),
        moe=MoEConfig(num_experts=8, top_k=3, num_shared_experts=2,
                      d_ff_expert=32, router_aux_loss=0.001,
                      norm_topk_prob=False, aux_kind="seq"),
        mla=MLAConfig(kv_lora_rank=16, q_lora_rank=0, qk_nope_head_dim=16,
                      qk_rope_head_dim=8, v_head_dim=16))
