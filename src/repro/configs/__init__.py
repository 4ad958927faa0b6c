"""Config registry: ``get_config(name)`` / ``--arch <id>`` resolution."""
from __future__ import annotations

from typing import Dict, List

from repro.configs.base import (
    ATTENTION_KINDS,
    FAMILIES,
    MLP_VARIANTS,
    SHAPES,
    TPU_V5E,
    EncDecConfig,
    FrontendStub,
    HardwareModel,
    HybridConfig,
    InputShape,
    LSTMConfig,
    ModelConfig,
    MoEConfig,
    RWKVConfig,
    SSMConfig,
    TFTConfig,
    shape_applicable,
)

from repro.configs.paligemma_3b import CONFIG as _paligemma
from repro.configs.h2o_danube_3_4b import CONFIG as _danube
from repro.configs.codeqwen1_5_7b import CONFIG as _codeqwen
from repro.configs.nemotron_4_15b import CONFIG as _nemotron
from repro.configs.grok_1_314b import CONFIG as _grok
from repro.configs.kimi_k2_1t_a32b import CONFIG as _kimi
from repro.configs.tinyllama_1_1b import CONFIG as _tinyllama
from repro.configs.rwkv6_3b import CONFIG as _rwkv6
from repro.configs.zamba2_1_2b import CONFIG as _zamba2
from repro.configs.seamless_m4t_medium import CONFIG as _seamless
from repro.configs.lstm_paper import CONFIG as _lstm_paper
from repro.configs.tft_electricity import CONFIG as _tft_electricity

# the ten assigned architectures, in assignment order
ASSIGNED: List[ModelConfig] = [
    _paligemma,
    _danube,
    _codeqwen,
    _nemotron,
    _grok,
    _kimi,
    _tinyllama,
    _rwkv6,
    _zamba2,
    _seamless,
]

REGISTRY: Dict[str, ModelConfig] = {c.name: c for c in ASSIGNED}
REGISTRY[_lstm_paper.name] = _lstm_paper
REGISTRY[_tft_electricity.name] = _tft_electricity


def get_config(name: str) -> ModelConfig:
    if name not in REGISTRY:
        raise KeyError(
            f"unknown arch {name!r}; available: {sorted(REGISTRY)}"
        )
    return REGISTRY[name]


def get_shape(name: str) -> InputShape:
    if name not in SHAPES:
        raise KeyError(f"unknown shape {name!r}; available: {sorted(SHAPES)}")
    return SHAPES[name]


__all__ = [
    "ASSIGNED",
    "REGISTRY",
    "SHAPES",
    "TPU_V5E",
    "get_config",
    "get_shape",
    "shape_applicable",
    "ModelConfig",
    "MoEConfig",
    "SSMConfig",
    "RWKVConfig",
    "HybridConfig",
    "EncDecConfig",
    "FrontendStub",
    "LSTMConfig",
    "TFTConfig",
    "InputShape",
    "HardwareModel",
    "FAMILIES",
    "ATTENTION_KINDS",
    "MLP_VARIANTS",
]
