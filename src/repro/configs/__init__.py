"""Architecture registry: ``--arch <id>`` -> ModelConfig.

The 10 assigned architectures (public-literature pool) plus the paper's own
two MLLM backbones. ``get_config(id)`` returns the FULL config;
``get_smoke_config(id)`` the reduced same-family smoke variant.
"""
from __future__ import annotations

from typing import Callable, Dict

from repro.configs.base import (
    INPUT_SHAPES,
    AdapterConfig,
    InputShape,
    ModelConfig,
    MLAConfig,
    MoEConfig,
    RGLRUConfig,
    SSMConfig,
    reduced,
)

from repro.configs import (  # noqa: E402
    glm4_9b,
    grok_1_314b,
    h2o_danube_1_8b,
    internlm2_20b,
    kimi_vl_a3b,
    llama4_scout_17b_a16e,
    llava15_7b,
    mamba2_130m,
    minigpt4_7b,
    qwen1_5_4b,
    qwen2_vl_72b,
    recurrentgemma_9b,
    whisper_base,
)

_REGISTRY: Dict[str, Callable[[], ModelConfig]] = {
    "h2o-danube-1.8b": h2o_danube_1_8b.config,
    "qwen1.5-4b": qwen1_5_4b.config,
    "llama4-scout-17b-a16e": llama4_scout_17b_a16e.config,
    "recurrentgemma-9b": recurrentgemma_9b.config,
    "qwen2-vl-72b": qwen2_vl_72b.config,
    "grok-1-314b": grok_1_314b.config,
    "mamba2-130m": mamba2_130m.config,
    "glm4-9b": glm4_9b.config,
    "whisper-base": whisper_base.config,
    "internlm2-20b": internlm2_20b.config,
    "kimi-vl-a3b": kimi_vl_a3b.config,
    # the paper's own backbones
    "llava-1.5-7b": llava15_7b.config,
    "minigpt4-7b": minigpt4_7b.config,
}

ASSIGNED_ARCHS = [
    "h2o-danube-1.8b",
    "qwen1.5-4b",
    "llama4-scout-17b-a16e",
    "recurrentgemma-9b",
    "qwen2-vl-72b",
    "grok-1-314b",
    "mamba2-130m",
    "glm4-9b",
    "whisper-base",
    "internlm2-20b",
]

PAPER_ARCHS = ["llava-1.5-7b", "minigpt4-7b"]


def list_archs():
    return list(_REGISTRY)


def get_config(arch: str) -> ModelConfig:
    if arch not in _REGISTRY:
        raise KeyError(f"unknown arch {arch!r}; known: {sorted(_REGISTRY)}")
    return _REGISTRY[arch]()


def get_smoke_config(arch: str, **overrides) -> ModelConfig:
    return reduced(get_config(arch), **overrides)


__all__ = [
    "ModelConfig",
    "MLAConfig",
    "MoEConfig",
    "SSMConfig",
    "RGLRUConfig",
    "AdapterConfig",
    "InputShape",
    "INPUT_SHAPES",
    "ASSIGNED_ARCHS",
    "PAPER_ARCHS",
    "list_archs",
    "get_config",
    "get_smoke_config",
    "reduced",
]
