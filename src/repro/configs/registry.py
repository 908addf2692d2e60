"""Architecture registry: `--arch <id>` resolution for launch scripts."""
from __future__ import annotations

import importlib
from typing import Dict, Tuple

from repro.models.config import ModelConfig

_MODULES = {
    "minitron-8b": "repro.configs.minitron_8b",
    "gemma-7b": "repro.configs.gemma_7b",
    "deepseek-v2-236b": "repro.configs.deepseek_v2_236b",
    "xlstm-1.3b": "repro.configs.xlstm_1_3b",
    "internvl2-76b": "repro.configs.internvl2_76b",
    "yi-9b": "repro.configs.yi_9b",
    "whisper-large-v3": "repro.configs.whisper_large_v3",
    "deepseek-v3-671b": "repro.configs.deepseek_v3_671b",
    "hymba-1.5b": "repro.configs.hymba_1_5b",
    "qwen3-32b": "repro.configs.qwen3_32b",
    "gpt2-small": "repro.configs.gpt2_small",
}

ARCH_IDS = tuple(_MODULES)


def get_config(arch_id: str, smoke: bool = False) -> ModelConfig:
    if arch_id not in _MODULES:
        raise KeyError(f"unknown arch {arch_id!r}; known: {ARCH_IDS}")
    mod = importlib.import_module(_MODULES[arch_id])
    return mod.SMOKE if smoke else mod.CONFIG


def all_configs(smoke: bool = False) -> Dict[str, ModelConfig]:
    return {a: get_config(a, smoke) for a in ARCH_IDS}


# shape applicability (DESIGN.md §4): long_500k needs sub-quadratic attention.
# ssm/hybrid are native; dense/moe/vlm run it through the sliding-window
# variant (window below); whisper skips (448-token decoder context).
LONG_CONTEXT_WINDOW = 8192


def long_500k_mode(arch_id: str) -> str:
    """'native' | 'sliding_window' | 'skip'."""
    fam = get_config(arch_id).family
    if fam in ("ssm", "hybrid"):
        return "native"
    if arch_id == "whisper-large-v3":
        return "skip"
    return "sliding_window"
