"""Assigned architecture configs (``--arch <id>``).

Every entry is the exact published configuration.  ``get(name)`` returns
the ModelConfig; ``smoke(name)`` its reduced same-family variant for CPU
tests.
"""

from __future__ import annotations

import importlib

ARCHS = [
    "qwen3_moe_235b", "arctic_480b", "rwkv6_3b", "pixtral_12b", "gemma_7b",
    "qwen3_0_6b", "granite_34b", "starcoder2_3b", "musicgen_medium",
    "recurrentgemma_9b", "qwen3_1_7b",
]

ALIASES = {
    "qwen3-moe-235b-a22b": "qwen3_moe_235b",
    "arctic-480b": "arctic_480b",
    "rwkv6-3b": "rwkv6_3b",
    "pixtral-12b": "pixtral_12b",
    "gemma-7b": "gemma_7b",
    "qwen3-0.6b": "qwen3_0_6b",
    "granite-34b": "granite_34b",
    "starcoder2-3b": "starcoder2_3b",
    "musicgen-medium": "musicgen_medium",
    "recurrentgemma-9b": "recurrentgemma_9b",
    "qwen3-1.7b": "qwen3_1_7b",
}


def get(name: str):
    key = ALIASES.get(name, name.replace("-", "_").replace(".", "_"))
    mod = importlib.import_module(f"repro.configs.{key}")
    return mod.CONFIG


def smoke(name: str):
    return get(name).smoke()

