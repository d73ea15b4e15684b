"""Architecture config registry: ``--arch <id>`` resolves here."""
from __future__ import annotations

import importlib
from typing import Dict, Tuple

from repro.configs.base import (
    ALL_SHAPES,
    SHAPES_BY_NAME,
    DECODE_32K,
    LONG_500K,
    PREFILL_32K,
    TRAIN_4K,
    HardwareTier,
    InputShape,
    ModelConfig,
    TIERS,
    TPU_V5E,
    shape_grid,
)

_ARCH_MODULES = {
    "qwen3-0.6b": "repro.configs.qwen3_0_6b",
    "starcoder2-15b": "repro.configs.starcoder2_15b",
    "llama3-405b": "repro.configs.llama3_405b",
    "qwen3-4b": "repro.configs.qwen3_4b",
    "hubert-xlarge": "repro.configs.hubert_xlarge",
    "arctic-480b": "repro.configs.arctic_480b",
    "mixtral-8x22b": "repro.configs.mixtral_8x22b",
    "rwkv6-7b": "repro.configs.rwkv6_7b",
    "zamba2-2.7b": "repro.configs.zamba2_2_7b",
    "llava-next-mistral-7b": "repro.configs.llava_next_mistral_7b",
    "mellum2-12b-a2.5b": "repro.configs.mellum2_12b_a2_5b",
}

ARCH_IDS: Tuple[str, ...] = tuple(_ARCH_MODULES)


def get_config(arch: str) -> ModelConfig:
    if arch not in _ARCH_MODULES:
        raise KeyError(f"unknown arch {arch!r}; known: {sorted(_ARCH_MODULES)}")
    return importlib.import_module(_ARCH_MODULES[arch]).CONFIG


# Serving arches that are NOT token LMs: they resolve to a job-engine
# descriptor (the paper's Table-1 deployment units) instead of a
# ``ModelConfig``.  ``resolve_serving_arch`` is the one lookup the fleet
# uses to validate ``TierSpec.arch`` up front.
JOB_ARCHES: Tuple[str, ...] = ("sd21",)


def resolve_serving_arch(arch: str):
    """name -> what serves it: a ``ModelConfig`` for token-LM arches, or
    the DU-profile tuple for diffusion-style job arches (``sd21``).

    This is the fleet's fail-fast registry: an unknown ``TierSpec.arch``
    raises here, at fleet construction, with the full known-name list —
    instead of a deep ``KeyError`` inside lazy engine builds.
    """
    if arch in JOB_ARCHES:
        mod = importlib.import_module(f"repro.configs.{arch}")
        return mod.paper_deployment_units()
    try:
        return get_config(arch)
    except KeyError:
        known = sorted(_ARCH_MODULES) + sorted(JOB_ARCHES)
        raise KeyError(
            f"unknown serving arch {arch!r}; known: {known}"
        ) from None


def serving_family(arch: str) -> str:
    """Model family string for a serving arch (``"job"`` for job-engine
    arches like sd21) — what model-compatibility routing keys on."""
    if arch in JOB_ARCHES:
        return "job"
    return get_config(arch).family


def all_configs() -> Dict[str, ModelConfig]:
    return {a: get_config(a) for a in ARCH_IDS}


def grid_cells() -> Tuple[Tuple[ModelConfig, InputShape], ...]:
    """Every runnable (arch × shape) cell after DESIGN.md §4 skips."""
    cells = []
    for arch in ARCH_IDS:
        cfg = get_config(arch)
        for shape in shape_grid(cfg):
            cells.append((cfg, shape))
    return tuple(cells)


__all__ = [
    "ALL_SHAPES",
    "ARCH_IDS",
    "SHAPES_BY_NAME",
    "DECODE_32K",
    "LONG_500K",
    "PREFILL_32K",
    "TRAIN_4K",
    "HardwareTier",
    "InputShape",
    "JOB_ARCHES",
    "ModelConfig",
    "TIERS",
    "TPU_V5E",
    "all_configs",
    "get_config",
    "grid_cells",
    "resolve_serving_arch",
    "serving_family",
    "shape_grid",
]
