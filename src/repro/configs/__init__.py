"""repro.configs — architecture registry and run configuration."""
from repro.configs.base import (
    MLAConfig,
    ModelConfig,
    MoEConfig,
    SHAPES,
    SSMConfig,
    ShapeConfig,
    YarnConfig,
    cell_is_runnable,
    get_config,
    list_archs,
    register,
)

__all__ = [
    "ModelConfig", "MoEConfig", "MLAConfig", "SSMConfig", "YarnConfig",
    "ShapeConfig", "SHAPES",
    "register", "get_config", "list_archs", "cell_is_runnable",
]
