"""Architecture config registry.

Each ``<arch>.py`` module defines ``SPEC`` (the full published configuration,
source cited in the module docstring) and ``SMOKE`` (a reduced variant of the
same family: <=2 layers, d_model<=512, <=4 experts) used by CPU smoke tests.

``get_spec(name, smoke=False)`` is the single lookup the launcher, dry-run,
benchmarks and tests all use (``--arch <id>``).
"""

from __future__ import annotations

import importlib
from typing import Dict, List

from repro.core.notation import ModelSpec

ARCHS: List[str] = [
    "deepseek_v3",        # the paper's reference model
    "deepseek_v2",        # paper also covers v2
    "deepseek_v2_lite",   # V2's small sibling: no query LoRA, YaRN
    "olmoe_1b_7b",
    "qwen2_vl_72b",
    "minitron_4b",
    "hymba_1_5b",
    "whisper_tiny",
    "rwkv6_1_6b",
    "gemma_2b",
    "qwen3_moe_235b_a22b",
    "gemma_7b",
    "qwen2_1_5b",
    # beyond the assigned pool: the small "qwen2-moe"-shaped probe arch the
    # EP dispatch-buffer validation pair runs on (dryrun --pp --tp --ep)
    "qwen2_moe_a2_7b",
]

# assigned pool ids (dashes) -> module names (underscores)
_ALIASES = {a.replace("_", "-"): a for a in ARCHS}
_ALIASES.update({
    "olmoe-1b-7b": "olmoe_1b_7b",
    "qwen2-vl-72b": "qwen2_vl_72b",
    "minitron-4b": "minitron_4b",
    "hymba-1.5b": "hymba_1_5b",
    "whisper-tiny": "whisper_tiny",
    "rwkv6-1.6b": "rwkv6_1_6b",
    "gemma-2b": "gemma_2b",
    "qwen3-moe-235b-a22b": "qwen3_moe_235b_a22b",
    "gemma-7b": "gemma_7b",
    "qwen2-1.5b": "qwen2_1_5b",
    "deepseek-v3": "deepseek_v3",
    "deepseek-v2": "deepseek_v2",
    "deepseek-v2-lite": "deepseek_v2_lite",
    "qwen2-moe-a2.7b": "qwen2_moe_a2_7b",
})

ASSIGNED: List[str] = [
    "olmoe-1b-7b", "qwen2-vl-72b", "minitron-4b", "hymba-1.5b",
    "whisper-tiny", "rwkv6-1.6b", "gemma-2b", "qwen3-moe-235b-a22b",
    "gemma-7b", "qwen2-1.5b",
]


def canonical(name: str) -> str:
    key = name.strip().lower()
    if key in _ALIASES:
        return _ALIASES[key]
    key = key.replace("-", "_").replace(".", "_")
    if key in ARCHS:
        return key
    raise KeyError(f"unknown architecture {name!r}; known: {sorted(_ALIASES)}")


def get_spec(name: str, smoke: bool = False) -> ModelSpec:
    mod = importlib.import_module(f"repro.configs.{canonical(name)}")
    return mod.SMOKE if smoke else mod.SPEC


def all_specs(smoke: bool = False) -> Dict[str, ModelSpec]:
    return {a: get_spec(a, smoke=smoke) for a in ARCHS}
