"""Architecture registry.

``get_config("yi-34b")`` returns the full assigned config;
``get_config("yi-34b", reduced=True)`` the smoke-test variant.
"""
from __future__ import annotations

from repro.configs.base import (INPUT_SHAPES, NETWORK_PROFILES, DPConfig,
                                MeshConfig, MLAConfig, ModelConfig, MoEConfig,
                                NetworkConfig, RuntimeConfig, ServingConfig,
                                ShapeConfig, SSMConfig, TrainConfig,
                                VFLConfig)
from repro.configs import (chameleon_34b, deepseek_7b, deepseek_v2_lite,
                           hymba_15b, minicpm_2b, phi35_moe_42b, qwen15_05b,
                           qwen3_moe_30b, rwkv6_16b, whisper_small, yi_34b)
from repro.configs.paper_models import PaperFCNConfig, PaperLRConfig

_REGISTRY: dict[str, ModelConfig] = {}
for _mod in (yi_34b, minicpm_2b, phi35_moe_42b, qwen15_05b, hymba_15b,
             deepseek_7b, chameleon_34b, qwen3_moe_30b, whisper_small,
             rwkv6_16b, deepseek_v2_lite):
    _REGISTRY[_mod.CONFIG.name] = _mod.CONFIG

ARCH_IDS = tuple(sorted(_REGISTRY))


def get_config(name: str, reduced: bool = False) -> ModelConfig:
    if name not in _REGISTRY:
        raise KeyError(f"unknown arch {name!r}; known: {ARCH_IDS}")
    cfg = _REGISTRY[name]
    return cfg.reduced() if reduced else cfg


__all__ = ["ARCH_IDS", "get_config", "ModelConfig", "MoEConfig", "MLAConfig",
           "SSMConfig",
           "ShapeConfig", "TrainConfig", "MeshConfig", "VFLConfig",
           "NetworkConfig", "NETWORK_PROFILES", "INPUT_SHAPES",
           "RuntimeConfig", "ServingConfig", "DPConfig", "PaperLRConfig",
           "PaperFCNConfig"]
