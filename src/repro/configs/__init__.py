"""Assigned-architecture registry: ``get_config("<arch-id>")``."""
from __future__ import annotations

import importlib

from repro.models.config import SHAPES, ModelConfig, ShapeConfig

ARCH_IDS = [
    "qwen3-4b",
    "qwen3-0.6b",
    "smollm-360m",
    "granite-8b",
    "deepseek-moe-16b",
    "qwen2-moe-a2.7b",
    "whisper-medium",
    "hymba-1.5b",
    "internvl2-2b",
    "mamba2-1.3b",
    "moonlight-16b-a3b",
]


def _module(arch_id: str):
    return importlib.import_module(f"repro.configs.{arch_id.replace('-', '_').replace('.', '_')}")


def get_config(arch_id: str) -> ModelConfig:
    if arch_id not in ARCH_IDS:
        raise KeyError(f"unknown arch {arch_id!r}; known: {ARCH_IDS}")
    return _module(arch_id).CONFIG


def get_reduced_config(arch_id: str) -> ModelConfig:
    """Small same-family config for CPU smoke tests."""
    return _module(arch_id).reduced()


def get_shape(shape_id: str) -> ShapeConfig:
    return SHAPES[shape_id]


def applicable_shapes(arch_id: str) -> list[str]:
    """Assigned shape cells actually runnable for this arch (DESIGN.md §4).

    long_500k requires sub-quadratic sequence mixing: only the SSM/hybrid archs
    qualify; the 8 pure full-attention archs record a 'skip' cell.
    """
    cfg = get_config(arch_id)
    shapes = ["train_4k", "prefill_32k", "decode_32k"]
    if cfg.family in ("ssm", "hybrid"):
        shapes.append("long_500k")
    return shapes
