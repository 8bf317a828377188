"""Architecture registry: the 10 assigned archs, mellum2 and its one-chip
share, and the paper's own jobs.

``get_arch(name)`` returns the full-size ArchConfig; ``get_smoke(name)``
returns a reduced same-family config for CPU smoke tests (small width/depth,
few experts, tiny vocab) — the full configs are exercised only via the
allocation-free dry-run.
"""

from __future__ import annotations

from repro.models.config import ArchConfig

from repro.configs import (
    whisper_base,
    phi4_mini,
    gemma3_12b,
    qwen15_32b,
    starcoder2_7b,
    mixtral_8x22b,
    phi35_moe,
    recurrentgemma_9b,
    xlstm_13b,
    paligemma_3b,
    mellum2_12b,
)

_MODULES = {
    "whisper-base": whisper_base,
    "phi4-mini-3.8b": phi4_mini,
    "gemma3-12b": gemma3_12b,
    "qwen1.5-32b": qwen15_32b,
    "starcoder2-7b": starcoder2_7b,
    "mixtral-8x22b": mixtral_8x22b,
    "phi3.5-moe-42b-a6.6b": phi35_moe,
    "recurrentgemma-9b": recurrentgemma_9b,
    "xlstm-1.3b": xlstm_13b,
    "paligemma-3b": paligemma_3b,
    "mellum2-12b-a2.5b": mellum2_12b,
    # one chip's share of a 4-chip expert-parallel deployment
    "mellum2-12b-a2.5b.ep4": mellum2_12b.EP4,
}

ARCH_NAMES = tuple(_MODULES)


def get_arch(name: str) -> ArchConfig:
    if name not in _MODULES:
        raise KeyError(f"unknown arch {name!r}; have {ARCH_NAMES}")
    cfg = _MODULES[name].CONFIG
    cfg.validate()
    return cfg


def get_smoke(name: str) -> ArchConfig:
    if name not in _MODULES:
        raise KeyError(f"unknown arch {name!r}; have {ARCH_NAMES}")
    cfg = _MODULES[name].SMOKE
    cfg.validate()
    return cfg
