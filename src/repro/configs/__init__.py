from repro.configs.base import (
    EncoderConfig,
    MLAConfig,
    ModelConfig,
    MoEConfig,
    RGLRUConfig,
    RopeScaling,
    SHAPE_CELLS,
    SSMConfig,
    ShapeCell,
    VisionConfig,
    cells_for,
)
from repro.configs.registry import ARCH_IDS, get_config, get_smoke_config

__all__ = [
    "ARCH_IDS",
    "EncoderConfig",
    "MLAConfig",
    "ModelConfig",
    "MoEConfig",
    "RGLRUConfig",
    "RopeScaling",
    "SHAPE_CELLS",
    "SSMConfig",
    "ShapeCell",
    "VisionConfig",
    "cells_for",
    "get_config",
    "get_smoke_config",
]
