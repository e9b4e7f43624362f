from repro.config.base import (
    FULL,
    WINDOW,
    ArchFamily,
    InputShape,
    MeshConfig,
    ModelConfig,
    MoEConfig,
    OptimizerConfig,
    PositionEmbedding,
    RopeConfig,
    SHAPES,
    ServeConfig,
    SSMConfig,
    ThinKVConfig,
    ThoughtType,
    TrainConfig,
    config_to_dict,
    reduced,
    shape_cells,
)

__all__ = [
    "FULL", "WINDOW", "ArchFamily", "InputShape", "MeshConfig",
    "ModelConfig", "MoEConfig", "OptimizerConfig", "PositionEmbedding",
    "RopeConfig", "SHAPES", "ServeConfig", "SSMConfig", "ThinKVConfig",
    "ThoughtType", "TrainConfig",
    "config_to_dict", "reduced", "shape_cells",
]
