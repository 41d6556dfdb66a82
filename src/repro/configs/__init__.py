"""Config registry: one module per assigned architecture.

`get_config(name)` -> full ArchConfig (exact assigned hyper-parameters);
`get_smoke(name)`  -> reduced same-family variant for CPU smoke tests;
`get_train(name)`  -> per-arch API-BCD TrainConfig defaults (agent grouping
                      sized by replica memory, walks M).

Departures from the published models, where a config cannot say them:

- deepseek-v2-236b models its first layer as MoE like the rest (one
  uniform scan).  The published model's first layer is dense (d_ff
  12288, first_k_dense_replace 1), which `layer_types` can now express
  (as deepseek-v2-lite does); its CONFIG is kept as it is.  MLA decode
  uses the absorbed latent formulation.
- deepseek-v2-lite and deepseek-v2-236b rotate the two halves of the
  rope dims where DeepSeek's code rotates interleaved pairs: with random
  weights, a fixed permutation of the rope columns of the query and
  latent projections.
- The MoE layers drop tokens over a capacity per (sequence, expert) of
  capacity_factor times the mean load; DeepSeek-V2 drops none in
  training.
"""
from __future__ import annotations

import importlib

from repro.configs.base import (  # noqa: F401
    ArchConfig, INPUT_SHAPES, MLAConfig, MoEConfig, RopeScaling, ShapeConfig,
    TrainConfig,
)

ARCH_NAMES = (
    "whisper_small",
    "rwkv6_1p6b",
    "qwen3_8b",
    "deepseek_v2_236b",
    "deepseek_v2_lite",
    "recurrentgemma_2b",
    "qwen2_0p5b",
    "internlm2_1p8b",
    "phi3_vision_4p2b",
    "nemotron4_15b",
    "dbrx_132b",
)

# user-facing ids (as assigned) -> module names
ARCH_IDS = {
    "whisper-small": "whisper_small",
    "rwkv6-1.6b": "rwkv6_1p6b",
    "qwen3-8b": "qwen3_8b",
    "deepseek-v2-236b": "deepseek_v2_236b",
    "deepseek-v2-lite": "deepseek_v2_lite",
    "recurrentgemma-2b": "recurrentgemma_2b",
    "qwen2-0.5b": "qwen2_0p5b",
    "internlm2-1.8b": "internlm2_1p8b",
    "phi-3-vision-4.2b": "phi3_vision_4p2b",
    "nemotron-4-15b": "nemotron4_15b",
    "dbrx-132b": "dbrx_132b",
}


def _module(name: str):
    mod_name = ARCH_IDS.get(name, name)
    return importlib.import_module(f"repro.configs.{mod_name}")


def get_config(name: str) -> ArchConfig:
    return _module(name).CONFIG


def get_smoke(name: str) -> ArchConfig:
    return _module(name).smoke()


def get_train(name: str) -> TrainConfig:
    return getattr(_module(name), "TRAIN", TrainConfig())


def list_archs():
    return list(ARCH_IDS)
