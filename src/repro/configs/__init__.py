"""Architecture registry: one module per assigned architecture.

``get_config(name)`` / ``get_smoke_config(name)`` resolve ``--arch`` ids.
"""
from __future__ import annotations

import importlib

ARCHS = [
    "llava_next_34b",
    "mamba2_780m",
    "granite_moe_1b_a400m",
    "granite_moe_3b_a800m",
    "glm4_9b",
    "qwen3_1_7b",
    "deepseek_coder_33b",
    "h2o_danube_3_4b",
    "hubert_xlarge",
    "hymba_1_5b",
    "moonlight_16b_a3b",
]

_ALIASES = {a.replace("_", "-"): a for a in ARCHS}


def _module(name: str):
    name = _ALIASES.get(name, name)
    if name not in ARCHS:
        raise KeyError(f"unknown arch {name!r}; choose from {ARCHS}")
    return importlib.import_module(f"repro.configs.{name}")


def get_config(name: str):
    return _module(name).CONFIG


def get_smoke_config(name: str):
    return _module(name).SMOKE


def all_configs():
    return {a: get_config(a) for a in ARCHS}
