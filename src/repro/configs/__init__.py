from repro.configs.base import (
    ModelConfig,
    ShapeConfig,
    TrainConfig,
)
from repro.configs.registry import ASSIGNED_ARCHS, all_configs, get_config

__all__ = [
    "ModelConfig",
    "ShapeConfig",
    "TrainConfig",
    "ASSIGNED_ARCHS",
    "all_configs",
    "get_config",
]
