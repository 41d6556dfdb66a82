"""Architecture + run configuration system.

One `ArchConfig` describes any architecture in the zoo (dense GQA, MoE,
MLA, RWKV6, RG-LRU hybrid, encoder-decoder, VLM/audio-stub). Each assigned
architecture gets a `src/repro/configs/<id>.py` exporting `CONFIG` plus a
`smoke()` reduced variant for CPU tests.

`layer_types` generalizes the stack: a tuple of per-layer block kinds
('attn' | 'moe' | 'rwkv' | 'rglru'), letting hybrids interleave recurrent
and attention blocks. Homogeneous runs of layers are scanned (compact HLO).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    num_experts: int
    top_k: int
    num_shared_experts: int = 0
    d_ff_expert: int = 0            # per-expert FFN width
    capacity_factor: float = 1.25
    router_aux_loss: float = 0.01   # load-balance loss weight
    # expert parallelism: this layer holds routed experts
    # [expert_offset, expert_offset + experts_held) of num_experts (0 =>
    # all); the router still scores and picks among all of them
    experts_held: int = 0
    expert_offset: int = 0
    norm_topk_prob: bool = True     # renormalise the top-k gates to sum 1
    # "switch": over the batch (Switch Transformer); "seq": DeepSeek-V2's
    # expert balance loss per sequence, averaged (arXiv:2405.04434 2.2.3)
    aux_kind: str = "switch"

    def __post_init__(self):
        if self.experts_held == 0:
            object.__setattr__(self, "experts_held", self.num_experts)
        if not (0 <= self.expert_offset and self.expert_offset
                + self.experts_held <= self.num_experts):
            raise ValueError(f"experts held out of range: {self}")
        if self.aux_kind not in ("switch", "seq"):
            raise ValueError(f"unknown aux_kind {self.aux_kind!r}")


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    """DeepSeek-V2 multi-head latent attention.  q_lora_rank 0 (or None,
    as DeepSeek-V2-Lite's config.json has it): queries come straight
    from one projection, with no low-rank query path."""
    kv_lora_rank: int = 512
    q_lora_rank: int = 1536
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128

    def __post_init__(self):
        if self.q_lora_rank is None:
            object.__setattr__(self, "q_lora_rank", 0)


@dataclasses.dataclass(frozen=True)
class RopeScaling:
    """YaRN rope scaling (arXiv:2309.00071), with the keys of a Hugging
    Face config's `rope_scaling` group as DeepSeek-V2 writes them.  Only
    mscale == mscale_all_dim is implemented (DeepSeek-V2's): cos and sin
    then stay unscaled, and the softmax scale takes mscale^2."""
    factor: float
    original_max_position_embeddings: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0
    type: str = "yarn"

    def __post_init__(self):
        if self.type != "yarn" or self.mscale != self.mscale_all_dim:
            raise NotImplementedError(f"rope scaling {self}")


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str                     # dense|moe|ssm|hybrid|encdec|vlm|audio
    source: str                     # citation for the config
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0               # 0 => d_model // num_heads

    # layer stack: None => all 'attn' ('moe' if moe config set)
    layer_types: Optional[Tuple[str, ...]] = None

    # attention options
    qk_norm: bool = False
    qkv_bias: bool = False
    rope_theta: float = 1e4
    rope_scaling: Optional[RopeScaling] = None   # MLA only
    attn_window: int = 0            # 0 = full causal; >0 = sliding window
    # sliding-window override used only for the long_500k shape on archs
    # whose native attention is full (see DESIGN.md long-context policy)
    long_context_window: int = 8192

    # MLP
    mlp_type: str = "swiglu"        # swiglu | gelu | sq_relu
    norm_type: str = "rmsnorm"      # rmsnorm | layernorm
    tie_embeddings: bool = False

    moe: Optional[MoEConfig] = None
    mla: Optional[MLAConfig] = None

    # rwkv6
    rwkv_head_dim: int = 64

    # rg-lru (recurrentgemma)
    rnn_width: int = 0              # lru hidden width (0 => d_model)
    conv_width: int = 4

    # encoder-decoder (whisper): decoder uses the main fields
    encoder_layers: int = 0
    encoder_seq: int = 0            # e.g. 1500 audio frames
    # modality frontend stub: 'none' | 'audio' | 'vision'
    frontend: str = "none"
    num_patches: int = 0            # vision stub: prefix patch embeddings

    # training
    param_dtype: str = "float32"
    compute_dtype: str = "bfloat16"

    def __post_init__(self):
        # the nested groups of a JSON config arrive as dicts, lists
        # where tuples are meant
        for name, kind in (("moe", MoEConfig), ("mla", MLAConfig),
                           ("rope_scaling", RopeScaling)):
            if isinstance(getattr(self, name), dict):
                object.__setattr__(self, name, kind(**getattr(self, name)))
        if self.layer_types is not None:
            object.__setattr__(self, "layer_types", tuple(self.layer_types))
        if self.rope_scaling is not None and self.mla is None:
            raise NotImplementedError(
                f"{self.name}: rope scaling is implemented for MLA only")
        if self.head_dim == 0:
            object.__setattr__(self, "head_dim",
                               self.d_model // self.num_heads)
        if self.layer_types is None:
            kind = "moe" if self.moe is not None else "attn"
            object.__setattr__(self, "layer_types",
                               tuple([kind] * self.num_layers))
        assert len(self.layer_types) == self.num_layers, (
            self.name, len(self.layer_types), self.num_layers)

    @property
    def supports_long_context(self) -> bool:
        """True if decode at 500k is feasible: recurrent state or windowed
        attention (native or via long_context_window override)."""
        if self.family in ("encdec", "audio"):
            return False            # whisper decoder: short trained context
        return True                 # ssm/hybrid native; attention via window

    @property
    def is_decoder(self) -> bool:
        return True                 # every zoo member has a decode path


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    """An assigned input shape."""
    name: str
    seq_len: int
    global_batch: int
    kind: str                       # 'train' | 'prefill' | 'decode'


INPUT_SHAPES = {
    "train_4k": ShapeConfig("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524288, 1, "decode"),
}


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    """API-BCD decentralized training hyper-parameters (mesh runtime)."""
    num_agents: int = 16            # A: agents on the mesh agent axis
    model_parallel: int = 16        # TP width within an agent (must divide
                                    # heads/ffn dims; rest of 256/A becomes
                                    # the FSDP "replica" axis)
    num_walks: int = 4              # M tokens
    tau: float = 0.1                # penalty parameter
    rho: float = 20.0               # gAPI-BCD proximal parameter (Thm 3
                                    # wants rho >= L/2; NN losses need
                                    # step 1/(rho+tau*M) ~ 5e-2)
    accumulate_between_visits: bool = True   # beyond-paper: no idle agents
    store_copy_sum: bool = True     # memory-lean zhat storage (sum only)
    zero_shard_tokens: bool = False # §Perf: shard token/zhat over replica axis
    microbatch_per_agent: int = 0   # 0 = whole shard in one step
    learning_rate: float = 3e-4     # only for the all-reduce DP baseline
