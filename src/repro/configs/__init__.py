"""Config registry: ``get_config("<arch-id>")`` / ``--arch <id>``.

Each assigned architecture (public-literature pool) has one module here with
the exact assigned config; ``sdxl_dit`` / ``tiny_dit`` / ``tiny_unet`` are the
paper's own diffusion models, ``sd3_medium`` the MMDiT denoiser of SD3.
"""
from __future__ import annotations

import importlib
from typing import Dict, List

from repro.configs.base import ArchConfig

# assigned architecture ids (module name = id with - -> _)
ASSIGNED: List[str] = [
    "xlstm-125m",
    "olmoe-1b-7b",
    "seamless-m4t-medium",
    "yi-9b",
    "minitron-8b",
    "hymba-1.5b",
    "llama3-405b",
    "gemma-2b",
    "deepseek-moe-16b",
    "internvl2-76b",
]

DIFFUSION: List[str] = ["sdxl-dit", "sd3-medium", "tiny-dit"]

ALL_ARCHS: List[str] = ASSIGNED + DIFFUSION

_cache: Dict[str, ArchConfig] = {}


def get_config(arch_id: str) -> ArchConfig:
    if arch_id not in _cache:
        modname = arch_id.replace("-", "_").replace(".", "_")
        mod = importlib.import_module(f"repro.configs.{modname}")
        _cache[arch_id] = mod.CONFIG
    return _cache[arch_id]


def list_archs() -> List[str]:
    return list(ALL_ARCHS)
