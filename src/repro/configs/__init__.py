"""Config registry: ``get_arch(name)`` / ``get_smoke(name)`` for the
assigned architectures (+ the paper's own ECG network via
repro.models.ecg.ECGConfig), and the 4 canonical input shapes."""
from __future__ import annotations

import importlib

from repro.configs.base import SHAPES, ArchConfig, RunConfig, ShapeConfig

_MODULES = {
    "stablelm-3b": "stablelm_3b",
    "phi4-mini-3.8b": "phi4_mini_3p8b",
    "glm4-9b": "glm4_9b",
    "minitron-4b": "minitron_4b",
    "qwen2-vl-7b": "qwen2_vl_7b",
    "rwkv6-7b": "rwkv6_7b",
    "llama4-maverick-400b-a17b": "llama4_maverick_400b",
    "qwen3-moe-30b-a3b": "qwen3_moe_30b",
    "zamba2-2.7b": "zamba2_2p7b",
    "musicgen-medium": "musicgen_medium",
    "lfm2-8b-a1b": "lfm2_8b_a1b",
}

ARCH_NAMES = list(_MODULES)


def _module(name: str):
    if name not in _MODULES:
        raise KeyError(
            f"unknown arch {name!r}; available: {', '.join(ARCH_NAMES)}"
        )
    return importlib.import_module(f"repro.configs.{_MODULES[name]}")


def get_arch(name: str) -> ArchConfig:
    return _module(name).FULL


def get_smoke(name: str) -> ArchConfig:
    return _module(name).SMOKE


def cells(arch: str) -> list[str]:
    """Shape names applicable to one arch (long_500k: sub-quadratic only)."""
    cfg = get_arch(arch)
    out = ["train_4k", "prefill_32k", "decode_32k"]
    if cfg.supports_long_context:
        out.append("long_500k")
    return out


def all_cells() -> list[tuple[str, str]]:
    return [(a, s) for a in ARCH_NAMES for s in cells(a)]
