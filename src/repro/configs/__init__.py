from .base import (
    SHAPES,
    AttnSpec,
    BlockSpec,
    LayoutGroup,
    MelinoeSpec,
    MLASpec,
    ModelConfig,
    MoESpec,
    ShapeSpec,
    SSMSpec,
    YarnSpec,
    make_smoke,
)
from .registry import ASSIGNED, PAPER, get_config, list_archs

__all__ = [
    "SHAPES",
    "AttnSpec",
    "BlockSpec",
    "LayoutGroup",
    "MelinoeSpec",
    "MLASpec",
    "ModelConfig",
    "MoESpec",
    "ShapeSpec",
    "SSMSpec",
    "YarnSpec",
    "make_smoke",
    "ASSIGNED",
    "PAPER",
    "get_config",
    "list_archs",
]
