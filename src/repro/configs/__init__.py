"""Architecture registry: ``--arch <id>`` resolves here."""

from __future__ import annotations

import importlib
from typing import Dict

from repro.configs.base import (
    SHAPES,
    ModelConfig,
    ShapeCell,
    cell_applicable,
    shape_cell,
)

_MODULES: Dict[str, str] = {
    "llama4-scout-17b-a16e": "llama4_scout_17b_a16e",
    "grok-1-314b": "grok_1_314b",
    "internvl2-2b": "internvl2_2b",
    "nemotron-4-340b": "nemotron_4_340b",
    "minicpm3-4b": "minicpm3_4b",
    "deepseek-v2-lite": "deepseek_v2_lite",
    "h2o-danube-1.8b": "h2o_danube_1p8b",
    "smollm-360m": "smollm_360m",
    "mamba2-2.7b": "mamba2_2p7b",
    "zamba2-7b": "zamba2_7b",
    "whisper-tiny": "whisper_tiny",
}

ARCH_NAMES = tuple(_MODULES)


def get_config(name: str) -> ModelConfig:
    if name not in _MODULES:
        raise KeyError(f"unknown arch {name!r}; known: {sorted(_MODULES)}")
    mod = importlib.import_module(f"repro.configs.{_MODULES[name]}")
    return mod.config


# imported after get_config exists: get_ep_preset resolves presets against
# the registry (lazily, at call time)
from repro.configs.presets import (  # noqa: E402
    EP_PRESET_NAMES,
    EP_PRESETS,
    EPPreset,
    TP_PRESET_NAMES,
    TP_PRESETS,
    TPPreset,
    get_ep_preset,
    get_tp_preset,
)


__all__ = [
    "ARCH_NAMES", "SHAPES", "ModelConfig", "ShapeCell",
    "cell_applicable", "get_config", "shape_cell",
    "EPPreset", "EP_PRESETS", "EP_PRESET_NAMES", "get_ep_preset",
    "TPPreset", "TP_PRESETS", "TP_PRESET_NAMES", "get_tp_preset",
]
