"""Architecture registry.

Arch ids contain ``-``/``.`` so modules use underscores; the registry maps
the exact published ids (``--arch mixtral-8x7b``) to their configs.
"""
from __future__ import annotations

import importlib

from repro.configs.base import (  # noqa: F401
    ModelConfig,
    ShapeConfig,
    SHAPES,
    shape_applicable,
    sub_quadratic,
)

_MODULES: dict[str, str] = {
    "mixtral-8x7b": "mixtral_8x7b",
    "llama4-maverick-400b-a17b": "llama4_maverick_400b_a17b",
    "stablelm-12b": "stablelm_12b",
    "minitron-8b": "minitron_8b",
    "nemotron-4-15b": "nemotron_4_15b",
    "llama3.2-3b": "llama3_2_3b",
    "jamba-1.5-large-398b": "jamba_1_5_large_398b",
    "pixtral-12b": "pixtral_12b",
    "rwkv6-1.6b": "rwkv6_1_6b",
    "whisper-medium": "whisper_medium",
    "step3": "step3",
}

ARCH_IDS: tuple[str, ...] = tuple(_MODULES)


def _module(arch_id: str):
    if arch_id not in _MODULES:
        raise KeyError(f"unknown arch {arch_id!r}; known: {sorted(_MODULES)}")
    return importlib.import_module(f"repro.configs.{_MODULES[arch_id]}")


def get_config(arch_id: str) -> ModelConfig:
    """Full published config for ``--arch <id>``."""
    return _module(arch_id).CONFIG


def get_tiny(arch_id: str) -> ModelConfig:
    """Reduced same-family config for CPU smoke tests."""
    return _module(arch_id).TINY


def all_configs() -> dict[str, ModelConfig]:
    return {a: get_config(a) for a in ARCH_IDS}
