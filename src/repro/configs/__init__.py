"""Architecture registry: one module per assigned architecture.

``get_config(arch_id)`` returns the full-size :class:`ModelConfig`;
``get_smoke_config(arch_id)`` returns the reduced same-family variant used by
the per-arch CPU smoke tests.  ``ARCHS`` lists every selectable ``--arch``.
"""
from __future__ import annotations

import importlib
from typing import Dict, List

from repro.config import ModelConfig, reduced

# assigned pool (10) + the paper's own evaluation model (bonus)
ARCHS: List[str] = [
    "yi-6b",
    "yi-9b",
    "qwen2-7b",
    "mistral-large-123b",
    "mixtral-8x7b",
    "llama4-scout-17b-a16e",
    "paligemma-3b",
    "whisper-medium",
    "falcon-mamba-7b",
    "zamba2-7b",
    "r1-llama-8b",
    "mellum2-12b-a2.5b",
]

_MODULES: Dict[str, str] = {
    "yi-6b": "yi_6b",
    "yi-9b": "yi_9b",
    "qwen2-7b": "qwen2_7b",
    "mistral-large-123b": "mistral_large_123b",
    "mixtral-8x7b": "mixtral_8x7b",
    "llama4-scout-17b-a16e": "llama4_scout_17b_a16e",
    "paligemma-3b": "paligemma_3b",
    "whisper-medium": "whisper_medium",
    "falcon-mamba-7b": "falcon_mamba_7b",
    "zamba2-7b": "zamba2_7b",
    "r1-llama-8b": "r1_llama_8b",
    "mellum2-12b-a2.5b": "mellum2_12b_a2p5b",
}


def get_config(arch: str) -> ModelConfig:
    if arch not in _MODULES:
        raise KeyError(f"unknown arch {arch!r}; available: {sorted(_MODULES)}")
    mod = importlib.import_module(f"repro.configs.{_MODULES[arch]}")
    return mod.CONFIG


def get_smoke_config(arch: str) -> ModelConfig:
    return reduced(get_config(arch))


def assigned_archs() -> List[str]:
    """The 10 assigned architectures (excludes the bonus paper model and
    the hybrid-attention MoE added for the serving benchmark)."""
    return [a for a in ARCHS if a not in ("r1-llama-8b",
                                          "mellum2-12b-a2.5b")]
