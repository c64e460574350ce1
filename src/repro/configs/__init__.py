"""Assigned-architecture registry: ``get(name)`` -> (FULL, SMOKE) configs.

Each module defines FULL (the exact public-literature config from the
assignment) and SMOKE (same family, reduced dims, CPU-runnable).
"""
from __future__ import annotations

import importlib
from typing import Dict

from repro.models.config import ModelConfig

ARCHS = (
    "falcon-mamba-7b",
    "llama4-scout-17b-a16e",
    "moonshot-v1-16b-a3b",
    "llama3.2-1b",
    "phi4-mini-3.8b",
    "qwen2-1.5b",
    "internlm2-20b",
    "hymba-1.5b",
    "seamless-m4t-medium",
    "llava-next-mistral-7b",
    "jamba2-3b",
)


def _module_name(arch: str) -> str:
    return arch.replace("-", "_").replace(".", "_")


def get(arch: str) -> ModelConfig:
    """The FULL (exact assigned) config."""
    mod = importlib.import_module(f"repro.configs.{_module_name(arch)}")
    return mod.FULL


def get_smoke(arch: str) -> ModelConfig:
    """The reduced same-family smoke config (CPU-runnable)."""
    mod = importlib.import_module(f"repro.configs.{_module_name(arch)}")
    return mod.SMOKE


def all_full() -> Dict[str, ModelConfig]:
    return {a: get(a) for a in ARCHS}
