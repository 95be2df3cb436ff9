"""Architecture registry: --arch <id> resolution."""

from __future__ import annotations

import importlib

_ARCHS = {
    "jamba-1.5-large-398b": "jamba_1_5_large_398b",
    "codeqwen1.5-7b": "codeqwen1_5_7b",
    "qwen2-72b": "qwen2_72b",
    "nemotron-4-15b": "nemotron_4_15b",
    "deepseek-67b": "deepseek_67b",
    "whisper-small": "whisper_small",
    "granite-moe-3b-a800m": "granite_moe_3b_a800m",
    "qwen3-moe-30b-a3b": "qwen3_moe_30b_a3b",
    "rwkv6-7b": "rwkv6_7b",
    "internvl2-2b": "internvl2_2b",
    "hla-1b": "hla_1b",
    "olmo-hybrid-7b": "olmo_hybrid_7b",
}


def list_archs():
    return sorted(_ARCHS)


def get_config(name: str, *, reduced: bool = False, mixer: str | None = None):
    """Resolve an arch id to its ModelConfig.

    mixer: optional override — swaps the attention sublayer for an HLA
    variant (the paper's drop-in claim, §5.2).  Attention-free archs
    (rwkv6) reject overrides.
    """
    if name not in _ARCHS:
        raise KeyError(f"unknown arch {name!r}; known: {list_archs()}")
    mod = importlib.import_module(f".{_ARCHS[name]}", __package__)
    cfg = mod.reduced() if reduced else mod.CONFIG
    if mixer is not None and mixer != cfg.mixer:
        if cfg.mixer == "rwkv6":
            raise ValueError(
                "rwkv6 is attention-free; HLA mixer override is inapplicable "
                "(DESIGN.md §Arch-applicability)"
            )
        cfg = cfg.replace(mixer=mixer)
    return cfg
