"""Architecture registry: ``--arch <id>`` resolution."""
from __future__ import annotations

import importlib
from typing import Dict, List

from repro.configs.base import ModelConfig

_ARCH_MODULES = {
    "seamless-m4t-large-v2": "repro.configs.seamless_m4t_large_v2",
    "recurrentgemma-2b": "repro.configs.recurrentgemma_2b",
    "smollm-360m": "repro.configs.smollm_360m",
    "starcoder2-15b": "repro.configs.starcoder2_15b",
    "qwen1.5-110b": "repro.configs.qwen1_5_110b",
    "mistral-large-123b": "repro.configs.mistral_large_123b",
    "mamba2-2.7b": "repro.configs.mamba2_2_7b",
    "llama4-scout-17b-a16e": "repro.configs.llama4_scout_17b_a16e",
    "olmoe-1b-7b": "repro.configs.olmoe_1b_7b",
    "internvl2-1b": "repro.configs.internvl2_1b",
}

#: configurations priced for serving traffic only
#: (``repro.traces.model_traffic``): ``repro.models`` builds none of
#: them, so they stay out of ``arch_ids()`` and the arch-parametrized
#: tests
_TRAFFIC_MODULES = {
    "deepseek-v3": "repro.configs.deepseek_v3",
}


def arch_ids() -> List[str]:
    return list(_ARCH_MODULES)


def get(arch_id: str) -> ModelConfig:
    if arch_id not in _ARCH_MODULES:
        raise KeyError(f"unknown arch {arch_id!r}; known: {arch_ids()}")
    mod = importlib.import_module(_ARCH_MODULES[arch_id])
    return mod.CONFIG


def traffic_config(arch_id: str) -> ModelConfig:
    """A registered architecture or a traffic-only configuration."""
    if arch_id in _TRAFFIC_MODULES:
        return importlib.import_module(_TRAFFIC_MODULES[arch_id]).CONFIG
    return get(arch_id)


def all_configs() -> Dict[str, ModelConfig]:
    return {a: get(a) for a in arch_ids()}
