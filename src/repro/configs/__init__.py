"""Config registry: the assigned architectures and the 4 input shapes.

Each ``<arch>.py`` module defines ``CONFIG`` with the exact assigned
hyperparameters and a source citation; ``CONFIG.reduced()`` is the smoke-test
variant (<= 2 layers / d_model <= 512 / <= 4 experts).
"""

from __future__ import annotations

import importlib
from typing import Dict, List

from repro.models.config import SHAPES, ArchConfig, InputShape

_ARCH_MODULES = {
    "llama3-8b": "llama3_8b",
    "granite-moe-1b-a400m": "granite_moe_1b_a400m",
    "tinyllama-1.1b": "tinyllama_1_1b",
    "rwkv6-3b": "rwkv6_3b",
    "dbrx-132b": "dbrx_132b",
    "whisper-medium": "whisper_medium",
    "qwen2-vl-72b": "qwen2_vl_72b",
    "recurrentgemma-9b": "recurrentgemma_9b",
    "gemma3-1b": "gemma3_1b",
    "yi-34b": "yi_34b",
    "deepseek-v2-lite": "deepseek_v2_lite",
}

#: (arch, shape) pairs skipped with justification (DESIGN.md Sec. 4):
#: long_500k requires a sub-quadratic decode path; pure full-attention
#: architectures have none.
SKIPS: Dict[tuple, str] = {
    ("llama3-8b", "long_500k"): "pure full attention; no sub-quadratic path",
    ("granite-moe-1b-a400m", "long_500k"): "pure full attention; no sub-quadratic path",
    ("tinyllama-1.1b", "long_500k"): "pure full attention; no sub-quadratic path",
    ("dbrx-132b", "long_500k"): "pure full attention; no sub-quadratic path",
    ("whisper-medium", "long_500k"): "full attention enc-dec; 500k ctx out of family scope",
    ("qwen2-vl-72b", "long_500k"): "pure full attention; no sub-quadratic path",
    ("yi-34b", "long_500k"): "pure full attention; no sub-quadratic path",
    ("deepseek-v2-lite", "long_500k"): "pure full attention; no sub-quadratic path",
}


def arch_names() -> List[str]:
    return list(_ARCH_MODULES)


def get_config(name: str) -> ArchConfig:
    if name not in _ARCH_MODULES:
        raise KeyError(f"unknown arch {name!r}; known: {sorted(_ARCH_MODULES)}")
    mod = importlib.import_module(f"repro.configs.{_ARCH_MODULES[name]}")
    return mod.CONFIG


def get_shape(name: str) -> InputShape:
    return SHAPES[name]


def is_skipped(arch: str, shape: str) -> str | None:
    return SKIPS.get((arch, shape))


__all__ = ["arch_names", "get_config", "get_shape", "is_skipped", "SHAPES", "SKIPS"]
