"""repro.configs — architecture registry + benchmark shapes.

``get_config('<arch-id>')`` returns the exact assigned config; arch ids use
dashes (underscores accepted).  ``smoke_variant(cfg)`` shrinks any config for
CPU tests while preserving family structure.  ``published(cfg)`` states a
config under the published ``config.json`` key names.
"""
from repro.configs import archs  # noqa: F401  (registers all builders)
from repro.configs.archs import smoke_variant  # noqa: F401
from repro.configs.published import published  # noqa: F401
from repro.configs.base import (  # noqa: F401
    SHAPES,
    MLACfg,
    MambaCfg,
    ModelConfig,
    MoECfg,
    ShapeCfg,
    XLSTMCfg,
    YarnCfg,
    get_config,
    list_configs,
)
