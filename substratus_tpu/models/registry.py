"""Model-family registry: the single dispatch point for multi-architecture
support.

Every family is a module implementing the engine/trainer protocol
(CONFIGS / init_params / param_logical_axes / init_cache /
cache_logical_axes / forward / decode_step). Adding a family means one
entry here; serve/load/checkpoint code looks up, never type-switches.
"""
from __future__ import annotations

from typing import Any, Optional, Tuple

from substratus_tpu.models import (
    brumby, deepseek_v3, exaone_moe, falcon, granitemoehybrid, lfm2_moe,
    llama, opt,
)

FAMILIES = {
    "llama": llama,  # Llama 2/3, Mistral, Mixtral (MoE), TinyLlama
    "opt": opt,  # facebook/opt-*
    "falcon": falcon,  # falcon-7b[-instruct], falcon-40b
    # K-EXAONE-236B-A23B: sigmoid-routed experts beside a shared one (a
    # program may hold a share of them), window layers beside global ones
    "exaone_moe": exaone_moe,
    # LFM2-24B-A2B: gated short convolutions that keep two rows of state a
    # decode slot beside attention layers in pages, sigmoid-routed experts
    "lfm2_moe": lfm2_moe,
    # Brumby-14B-Base: every layer's operator is power retention, which
    # keeps a float32 state a decode slot and layer and no page at all
    "brumby": brumby,
    # DeepSeek-V3 and the language model of dots.vlm1: multi-head latent
    # attention (one shared row a token and layer in the pages, read
    # absorbed by a decode step and expanded by a chunk), a low-rank query,
    # YaRN, sigmoid-routed experts under a group limit; and GLM-5, the same
    # block under a learned index that picks the rows a query attends
    # (index_n_heads > 0: the index keys lie in the pages beside the rows)
    "deepseek_v3": deepseek_v3,
    # Granite-4.0-H-Micro: Mamba-2 mixers that keep a float32 state and
    # three convolution rows a decode slot and layer, beside a few attention
    # layers without positions in pages; dense MLPs, three multipliers
    "granitemoehybrid": granitemoehybrid,
}

# transformers `model_type` -> family name (HF checkpoint dispatch).
HF_MODEL_TYPES = {
    "llama": "llama",
    "mistral": "llama",
    "mixtral": "llama",
    "opt": "opt",
    "falcon": "falcon",
    "exaone_moe": "exaone_moe",
    "lfm2_moe": "lfm2_moe",
    "brumby": "brumby",
    "deepseek_v3": "deepseek_v3",
    # dots.vlm1: its text part; the vision tower is not built
    "dots_vlm": "deepseek_v3",
    # GLM-5: DeepSeek-V3's block with DeepSeek Sparse Attention's indexer
    "glm_moe_dsa": "deepseek_v3",
    # Granite-4.0-H with `num_local_experts` 0 (load/hf.py refuses the rest)
    "granitemoehybrid": "granitemoehybrid",
}

_CONFIG_CLASS_TO_FAMILY = {
    llama.LlamaConfig: "llama",
    opt.OPTConfig: "opt",
    falcon.FalconConfig: "falcon",
    exaone_moe.ExaoneMoeConfig: "exaone_moe",
    lfm2_moe.Lfm2MoeConfig: "lfm2_moe",
    brumby.BrumbyConfig: "brumby",
    deepseek_v3.DeepseekV3Config: "deepseek_v3",
    granitemoehybrid.GraniteHybridConfig: "granitemoehybrid",
}


def family_of(cfg: Any) -> str:
    for cls, name in _CONFIG_CLASS_TO_FAMILY.items():
        if isinstance(cfg, cls):
            return name
    raise TypeError(f"unknown model config type {type(cfg)!r}")


def module_of(cfg: Any):
    return FAMILIES[family_of(cfg)]


def config_class(name: str):
    return {v: k for k, v in _CONFIG_CLASS_TO_FAMILY.items()}[name]


def module_for(name: str):
    if name not in FAMILIES:
        raise KeyError(f"unknown model family {name!r} (known: {sorted(FAMILIES)})")
    return FAMILIES[name]


def find_named_config(name: str) -> Tuple[Any, Any]:
    """Named smoke/test config -> (family_module, config)."""
    for fam in FAMILIES.values():
        if name in fam.CONFIGS:
            return fam, fam.CONFIGS[name]
    known = sorted(
        cfg for fam in FAMILIES.values() for cfg in fam.CONFIGS
    )
    raise KeyError(f"unknown model config {name!r} (known: {known})")
