"""Architecture registry: ``get_config(arch_id)`` / ``get_reduced(arch_id)``.

The 10 assigned architectures, AI21-Jamba2-Mini and the paper's own
anytime family.
"""

from __future__ import annotations

import importlib

from repro.configs.base import ModelConfig

_MODULES = {
    "qwen2-vl-2b": "qwen2_vl_2b",
    "qwen2.5-32b": "qwen2_5_32b",
    "gemma3-1b": "gemma3_1b",
    "qwen2.5-14b": "qwen2_5_14b",
    "stablelm-12b": "stablelm_12b",
    "jamba-v0.1-52b": "jamba_v01_52b",
    "qwen3-moe-30b-a3b": "qwen3_moe_30b_a3b",
    "olmoe-1b-7b": "olmoe_1b_7b",
    "whisper-tiny": "whisper_tiny",
    "rwkv6-3b": "rwkv6_3b",
    "AI21-Jamba2-Mini": "jamba2_mini",
    "alert-anytime-120m": "alert_anytime",
}

ARCH_IDS = [a for a in _MODULES if a != "alert-anytime-120m"]
ALL_IDS = list(_MODULES)


def _mod(arch_id: str):
    if arch_id not in _MODULES:
        raise KeyError(f"unknown arch {arch_id!r}; known: {ALL_IDS}")
    return importlib.import_module(f"repro.configs.{_MODULES[arch_id]}")


def get_config(arch_id: str) -> ModelConfig:
    return _mod(arch_id).CONFIG


def get_reduced(arch_id: str) -> ModelConfig:
    return _mod(arch_id).reduced()
