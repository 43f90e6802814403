"""HuggingFace checkpoint import -> substratus_tpu params.

This is the in-repo replacement for the reference's external
`substratusai/model-loader-huggingface` image (SURVEY.md §2.2;
examples/llama2-7b/base-model.yaml:7): it turns HF Llama-family weights
(safetensors) into the framework's stacked-layer pytree, ready to be sharded
onto a mesh and/or written to `/content/artifacts` as an Orbax checkpoint
(train/checkpoints.py).

Weight-layout notes: HF Linear stores [out, in]; we store [in, ...out] so the
forward pass is `x @ w` without transposes. RoPE uses the HF rotate-half
convention (ops/basics.py), so no head permutation is needed.
"""
from __future__ import annotations

import json
import os
from types import SimpleNamespace
from typing import Any, Dict, Mapping, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from substratus_tpu.models.llama import CONFIGS, LlamaConfig, Params


def config_from_hf(hf_cfg: Any) -> LlamaConfig:
    """Map a transformers Llama/Mistral/MixtralConfig(-like) to LlamaConfig."""
    get = lambda name, default=None: getattr(hf_cfg, name, default)
    return LlamaConfig(
        vocab_size=hf_cfg.vocab_size,
        dim=hf_cfg.hidden_size,
        n_layers=hf_cfg.num_hidden_layers,
        n_heads=hf_cfg.num_attention_heads,
        n_kv_heads=get("num_key_value_heads") or hf_cfg.num_attention_heads,
        hidden_dim=hf_cfg.intermediate_size,
        head_dim=get("head_dim"),
        rope_theta=get("rope_theta", 10000.0),
        norm_eps=get("rms_norm_eps", 1e-5),
        max_seq_len=get("max_position_embeddings", 4096),
        tie_embeddings=bool(get("tie_word_embeddings", False)),
        # Mixtral MoE fields
        n_experts=get("num_local_experts", 0) or 0,
        n_experts_per_token=get("num_experts_per_tok", 2) or 2,
        router_aux_weight=get("router_aux_loss_coef", 0.01) or 0.01,
    )


def _np(t: Any) -> np.ndarray:
    if hasattr(t, "detach"):  # torch tensor
        t = t.detach().to("cpu").float().numpy()
    return np.asarray(t)


def convert_llama_state_dict(
    sd: Mapping[str, Any], cfg: LlamaConfig, dtype=jnp.bfloat16
) -> Params:
    """HF Llama state dict -> stacked-layer params pytree."""
    hd = cfg.head_size
    L, D, H, KH, M = cfg.n_layers, cfg.dim, cfg.n_heads, cfg.n_kv_heads, cfg.hidden_dim

    def get(name: str) -> np.ndarray:
        for prefix in ("", "model."):
            if prefix + name in sd:
                return _np(sd[prefix + name])
        raise KeyError(name)

    def stack(fmt: str, transform) -> jnp.ndarray:
        return jnp.asarray(
            np.stack([transform(get(fmt.format(i=i))) for i in range(L)]), dtype
        )

    params: Params = {
        "tok_embed": jnp.asarray(get("embed_tokens.weight"), dtype),
        "layers": {
            "attn_norm": stack("layers.{i}.input_layernorm.weight", lambda w: w),
            "wq": stack(
                "layers.{i}.self_attn.q_proj.weight",
                lambda w: w.T.reshape(D, H, hd),
            ),
            "wk": stack(
                "layers.{i}.self_attn.k_proj.weight",
                lambda w: w.T.reshape(D, KH, hd),
            ),
            "wv": stack(
                "layers.{i}.self_attn.v_proj.weight",
                lambda w: w.T.reshape(D, KH, hd),
            ),
            "wo": stack(
                "layers.{i}.self_attn.o_proj.weight",
                lambda w: w.T.reshape(H, hd, D),
            ),
            "mlp_norm": stack("layers.{i}.post_attention_layernorm.weight", lambda w: w),
        },
        "out_norm": jnp.asarray(get("norm.weight"), dtype),
    }
    if cfg.n_experts > 0:
        # Mixtral MoE: block_sparse_moe.gate -> router, experts.N.{w1,w3,w2}
        # -> gate/up/down stacked on a leading expert dim.
        E = cfg.n_experts

        def stack_experts(w_name: str, transform) -> jnp.ndarray:
            # Convert expert-by-expert straight into the target dtype: a
            # whole-tensor float32 numpy transient would be ~60 GB for
            # mixtral-8x7b ([32,8,4096,14336] f32) on top of the resident
            # state dict.
            per_layer = []
            for i in range(L):
                per_layer.append(
                    jnp.stack(
                        [
                            jnp.asarray(
                                transform(
                                    get(
                                        f"layers.{i}.block_sparse_moe."
                                        f"experts.{e}.{w_name}.weight"
                                    )
                                ),
                                dtype,
                            )
                            for e in range(E)
                        ]
                    )
                )
            return jnp.stack(per_layer)

        params["layers"].update(
            {
                "router": stack(
                    "layers.{i}.block_sparse_moe.gate.weight", lambda w: w.T
                ),
                "w_gate": stack_experts("w1", lambda w: w.T),
                "w_up": stack_experts("w3", lambda w: w.T),
                "w_down": stack_experts("w2", lambda w: w.T),
            }
        )
    else:
        params["layers"].update(
            {
                "w_gate": stack("layers.{i}.mlp.gate_proj.weight", lambda w: w.T),
                "w_up": stack("layers.{i}.mlp.up_proj.weight", lambda w: w.T),
                "w_down": stack("layers.{i}.mlp.down_proj.weight", lambda w: w.T),
            }
        )
    if not cfg.tie_embeddings:
        params["lm_head"] = jnp.asarray(get("lm_head.weight").T, dtype)
    return params


def config_from_hf_opt(hf_cfg: Any):
    from substratus_tpu.models.opt import OPTConfig

    # Architecture variants models/opt.py does not implement; fail loudly
    # rather than convert to silently-wrong logits (opt-350m is post-LN with
    # a projected embedding dim).
    if not getattr(hf_cfg, "do_layer_norm_before", True):
        raise NotImplementedError(
            "post-LN OPT variants (do_layer_norm_before=false, e.g. "
            "opt-350m) are not supported"
        )
    act = getattr(hf_cfg, "activation_function", "relu")
    if act != "relu":
        raise NotImplementedError(
            f"OPT activation {act!r} not supported (e.g. Galactica uses "
            "gelu); models/opt.py implements relu"
        )
    proj = getattr(hf_cfg, "word_embed_proj_dim", hf_cfg.hidden_size)
    if proj != hf_cfg.hidden_size:
        raise NotImplementedError(
            f"OPT word_embed_proj_dim={proj} != hidden_size="
            f"{hf_cfg.hidden_size} (embedding projection) is not supported"
        )
    return OPTConfig(
        vocab_size=hf_cfg.vocab_size,
        dim=hf_cfg.hidden_size,
        n_layers=hf_cfg.num_hidden_layers,
        n_heads=hf_cfg.num_attention_heads,
        hidden_dim=hf_cfg.ffn_dim,
        max_seq_len=hf_cfg.max_position_embeddings,
    )


def convert_opt_state_dict(sd: Mapping[str, Any], cfg, dtype=jnp.bfloat16) -> Params:
    """HF OPTForCausalLM state dict -> models/opt.py params. Note HF's
    per-layer `final_layer_norm` is the pre-FFN norm (ln2 here); the
    top-level decoder final_layer_norm is the real final norm."""
    hd = cfg.head_size
    L, D, H = cfg.n_layers, cfg.dim, cfg.n_heads

    def get(name: str) -> np.ndarray:
        for prefix in ("model.decoder.", "decoder.", ""):
            if prefix + name in sd:
                return _np(sd[prefix + name])
        raise KeyError(name)

    def stack(fmt: str, transform) -> jnp.ndarray:
        return jnp.asarray(
            np.stack([transform(get(fmt.format(i=i))) for i in range(L)]), dtype
        )

    return {
        "tok_embed": jnp.asarray(get("embed_tokens.weight"), dtype),
        "pos_embed": jnp.asarray(get("embed_positions.weight"), dtype),
        "layers": {
            "ln1_scale": stack("layers.{i}.self_attn_layer_norm.weight", lambda w: w),
            "ln1_bias": stack("layers.{i}.self_attn_layer_norm.bias", lambda w: w),
            "wq": stack("layers.{i}.self_attn.q_proj.weight", lambda w: w.T.reshape(D, H, hd)),
            "bq": stack("layers.{i}.self_attn.q_proj.bias", lambda w: w.reshape(H, hd)),
            "wk": stack("layers.{i}.self_attn.k_proj.weight", lambda w: w.T.reshape(D, H, hd)),
            "bk": stack("layers.{i}.self_attn.k_proj.bias", lambda w: w.reshape(H, hd)),
            "wv": stack("layers.{i}.self_attn.v_proj.weight", lambda w: w.T.reshape(D, H, hd)),
            "bv": stack("layers.{i}.self_attn.v_proj.bias", lambda w: w.reshape(H, hd)),
            "wo": stack("layers.{i}.self_attn.out_proj.weight", lambda w: w.T.reshape(H, hd, D)),
            "bo": stack("layers.{i}.self_attn.out_proj.bias", lambda w: w),
            "ln2_scale": stack("layers.{i}.final_layer_norm.weight", lambda w: w),
            "ln2_bias": stack("layers.{i}.final_layer_norm.bias", lambda w: w),
            "fc1": stack("layers.{i}.fc1.weight", lambda w: w.T),
            "fc1_b": stack("layers.{i}.fc1.bias", lambda w: w),
            "fc2": stack("layers.{i}.fc2.weight", lambda w: w.T),
            "fc2_b": stack("layers.{i}.fc2.bias", lambda w: w),
        },
        "final_ln_scale": jnp.asarray(get("final_layer_norm.weight"), dtype),
        "final_ln_bias": jnp.asarray(get("final_layer_norm.bias"), dtype),
    }


def config_from_hf_falcon(hf_cfg: Any):
    from substratus_tpu.models.falcon import FalconConfig

    get = lambda n, d=None: getattr(hf_cfg, n, d)
    if not get("parallel_attn", True):
        raise NotImplementedError("non-parallel Falcon blocks not supported")
    if get("alibi", False):
        raise NotImplementedError("Falcon alibi positioning not supported")
    if get("bias", False):
        raise NotImplementedError("biased Falcon projections not supported")
    if not get("tie_word_embeddings", True):
        raise NotImplementedError(
            "untied Falcon LM heads not supported (forward scores against "
            "the tied token embedding)"
        )
    new_arch = bool(get("new_decoder_architecture", False))
    if new_arch:
        kv = get("num_kv_heads") or hf_cfg.num_attention_heads
    elif get("multi_query", True):
        kv = 1
    else:
        kv = hf_cfg.num_attention_heads
    return FalconConfig(
        vocab_size=hf_cfg.vocab_size,
        dim=hf_cfg.hidden_size,
        n_layers=hf_cfg.num_hidden_layers,
        n_heads=hf_cfg.num_attention_heads,
        n_kv_heads=kv,
        rope_theta=get("rope_theta", 10000.0),
        norm_eps=get("layer_norm_epsilon", 1e-5),
        max_seq_len=get("max_position_embeddings", 2048),
        separate_ln=new_arch,
    )


def convert_falcon_state_dict(sd: Mapping[str, Any], cfg, dtype=jnp.bfloat16) -> Params:
    """HF FalconForCausalLM state dict -> models/falcon.py params. The fused
    query_key_value weight interleaves per kv-group: (H/KH) query heads, one
    key head, one value head."""
    hd = cfg.head_size
    L, D, H, KH = cfg.n_layers, cfg.dim, cfg.n_heads, cfg.n_kv_heads
    G = H // KH

    def get(name: str) -> np.ndarray:
        for prefix in ("transformer.", "model.transformer.", ""):
            if prefix + name in sd:
                return _np(sd[prefix + name])
        raise KeyError(name)

    def split_qkv(w: np.ndarray):
        # w: [(H + 2*KH)*hd, D] -> per-group [G q | k | v]
        grouped = w.reshape(KH, G + 2, hd, D)
        q = grouped[:, :G].reshape(H, hd, D).transpose(2, 0, 1)  # [D,H,hd]
        k = grouped[:, G].transpose(2, 0, 1)  # [D,KH,hd]
        v = grouped[:, G + 1].transpose(2, 0, 1)
        return q, k, v

    qs, ks, vs = [], [], []
    for i in range(L):
        q, k, v = split_qkv(get(f"h.{i}.self_attention.query_key_value.weight"))
        qs.append(q)
        ks.append(k)
        vs.append(v)

    def stack(fmt: str, transform) -> jnp.ndarray:
        return jnp.asarray(
            np.stack([transform(get(fmt.format(i=i))) for i in range(L)]), dtype
        )

    ln1 = "h.{i}.ln_attn" if cfg.separate_ln else "h.{i}.input_layernorm"
    layers = {
        "ln1_scale": stack(ln1 + ".weight", lambda w: w),
        "ln1_bias": stack(ln1 + ".bias", lambda w: w),
        "wq": jnp.asarray(np.stack(qs), dtype),
        "wk": jnp.asarray(np.stack(ks), dtype),
        "wv": jnp.asarray(np.stack(vs), dtype),
        "wo": stack(
            "h.{i}.self_attention.dense.weight",
            lambda w: w.T.reshape(H, hd, D),
        ),
        "fc1": stack("h.{i}.mlp.dense_h_to_4h.weight", lambda w: w.T),
        "fc2": stack("h.{i}.mlp.dense_4h_to_h.weight", lambda w: w.T),
    }
    if cfg.separate_ln:
        layers["ln2_scale"] = stack("h.{i}.ln_mlp.weight", lambda w: w)
        layers["ln2_bias"] = stack("h.{i}.ln_mlp.bias", lambda w: w)
    return {
        "tok_embed": jnp.asarray(get("word_embeddings.weight"), dtype),
        "layers": layers,
        "final_ln_scale": jnp.asarray(get("ln_f.weight"), dtype),
        "final_ln_bias": jnp.asarray(get("ln_f.bias"), dtype),
    }


def config_from_hf_exaone_moe(hf_cfg: Any):
    """A transformers `exaone_moe` config.json (K-EXAONE) -> ExaoneMoeConfig:
    the layer kinds come from its `layer_types` / `mlp_layer_types` lists.
    Every expert is held (one program serving the whole checkpoint)."""
    from substratus_tpu.models.exaone_moe import ExaoneMoeConfig

    get = lambda name, default=None: getattr(hf_cfg, name, default)
    n = hf_cfg.num_hidden_layers
    rope = get("rope_parameters") or {}
    if not isinstance(rope, dict):
        rope = vars(rope)
    return ExaoneMoeConfig(
        vocab_size=hf_cfg.vocab_size,
        dim=hf_cfg.hidden_size,
        n_layers=n,
        n_heads=hf_cfg.num_attention_heads,
        n_kv_heads=get("num_key_value_heads") or hf_cfg.num_attention_heads,
        head_dim=get("head_dim")
        or hf_cfg.hidden_size // hf_cfg.num_attention_heads,
        hidden_dim=hf_cfg.intermediate_size,
        moe_hidden_dim=hf_cfg.moe_intermediate_size,
        n_experts=hf_cfg.num_experts,
        n_experts_per_token=hf_cfg.num_experts_per_tok,
        n_shared_experts=get("num_shared_experts", 1),
        routed_scaling_factor=float(get("routed_scaling_factor", 1.0)),
        norm_topk_prob=bool(get("norm_topk_prob", True)),
        layer_types=tuple(hf_cfg.layer_types[:n]),
        mlp_layer_types=tuple(hf_cfg.mlp_layer_types[:n]),
        sliding_window=hf_cfg.sliding_window,
        rope_theta=float(rope.get("rope_theta", get("rope_theta", 1e6))),
        norm_eps=get("rms_norm_eps", 1e-5),
        max_seq_len=get("max_position_embeddings", 4096),
        tie_embeddings=bool(get("tie_word_embeddings", False)),
    )


def convert_exaone_moe_state_dict(sd: Mapping[str, Any], cfg: Any,
                                  dtype=jnp.bfloat16) -> Params:
    """Not written: the tensor names of the published exaone_moe checkpoint
    were not at hand when the family was added (no network), and a guessed
    mapping under a real model's name is worse than none. The family is
    served from a named config (random weights) or an orbax checkpoint of
    models/exaone_moe.py's own tree (docs/cli.md)."""
    raise NotImplementedError(
        "exaone_moe: the config.json is read (config_from_hf_exaone_moe) "
        "but no converter maps the checkpoint's tensors onto "
        "models/exaone_moe.py's tree yet"
    )


def config_from_hf_lfm2_moe(hf_cfg: Any):
    """A transformers `lfm2_moe` config.json (LFM2-24B-A2B) -> Lfm2MoeConfig:
    the operators come from its `layer_types` list, the dense layers from
    `num_dense_layers`. Every expert is held."""
    from substratus_tpu.models.lfm2_moe import Lfm2MoeConfig

    get = lambda name, default=None: getattr(hf_cfg, name, default)
    n = hf_cfg.num_hidden_layers
    rope = get("rope_parameters") or {}
    if not isinstance(rope, dict):
        rope = vars(rope)
    if get("conv_bias", False) or not get("use_expert_bias", True):
        raise NotImplementedError(
            "lfm2_moe: conv_bias and a router without its expert bias are "
            "not written")
    return Lfm2MoeConfig(
        vocab_size=hf_cfg.vocab_size,
        dim=hf_cfg.hidden_size,
        n_layers=n,
        n_heads=hf_cfg.num_attention_heads,
        n_kv_heads=get("num_key_value_heads") or hf_cfg.num_attention_heads,
        head_dim=get("head_dim")
        or hf_cfg.hidden_size // hf_cfg.num_attention_heads,
        hidden_dim=hf_cfg.intermediate_size,
        moe_hidden_dim=hf_cfg.moe_intermediate_size,
        n_dense_layers=get("num_dense_layers", 0),
        n_experts=hf_cfg.num_experts,
        n_experts_per_token=hf_cfg.num_experts_per_tok,
        routed_scaling_factor=float(get("routed_scaling_factor", 1.0)),
        norm_topk_prob=bool(get("norm_topk_prob", True)),
        layer_types=tuple(hf_cfg.layer_types[:n]),
        conv_taps=get("conv_L_cache", 3),
        rope_theta=float(rope.get("rope_theta", get("rope_theta", 1e6))),
        norm_eps=get("norm_eps", 1e-5),
        max_seq_len=get("max_position_embeddings", 4096),
        tie_embeddings=bool(get("tie_embedding", True)),
    )


def convert_lfm2_moe_state_dict(sd: Mapping[str, Any], cfg: Any,
                                dtype=jnp.bfloat16) -> Params:
    """Not written, as convert_exaone_moe_state_dict is not: the tensor
    names of the published lfm2_moe checkpoint were not at hand (no
    network). The family is served from a named config (random weights) or
    an orbax checkpoint of models/lfm2_moe.py's own tree."""
    raise NotImplementedError(
        "lfm2_moe: the config.json is read (config_from_hf_lfm2_moe) but no "
        "converter maps the checkpoint's tensors onto models/lfm2_moe.py's "
        "tree yet"
    )


def config_from_hf_brumby(hf_cfg: Any):
    """A transformers `brumby` config.json (Brumby-14B-Base) -> BrumbyConfig.
    The file carries Qwen3's keys; what the retention operator adds to them
    is models/brumby.py's `assumed`."""
    from substratus_tpu.models.brumby import BrumbyConfig

    get = lambda name, default=None: getattr(hf_cfg, name, default)
    if (get("attention_bias", False) or get("rope_scaling")
            or get("use_sliding_window", False)):
        raise NotImplementedError(
            "brumby: attention_bias, rope_scaling and a sliding window are "
            "not written")
    return BrumbyConfig(
        vocab_size=hf_cfg.vocab_size,
        dim=hf_cfg.hidden_size,
        n_layers=hf_cfg.num_hidden_layers,
        n_heads=hf_cfg.num_attention_heads,
        n_kv_heads=get("num_key_value_heads") or hf_cfg.num_attention_heads,
        head_dim=get("head_dim")
        or hf_cfg.hidden_size // hf_cfg.num_attention_heads,
        hidden_dim=hf_cfg.intermediate_size,
        rope_theta=float(get("rope_theta", 1e6)),
        norm_eps=get("rms_norm_eps", 1e-6),
        max_seq_len=get("max_position_embeddings", 32768),
        tie_embeddings=bool(get("tie_word_embeddings", False)),
    )


def convert_brumby_state_dict(sd: Mapping[str, Any], cfg: Any,
                              dtype=jnp.bfloat16) -> Params:
    """Not written, as convert_exaone_moe_state_dict is not: the tensor
    names of the published brumby checkpoint were not at hand (no
    network). The family is served from a named config (random weights) or
    an orbax checkpoint of models/brumby.py's own tree."""
    raise NotImplementedError(
        "brumby: the config.json is read (config_from_hf_brumby) but no "
        "converter maps the checkpoint's tensors onto models/brumby.py's "
        "tree yet"
    )


def config_from_hf_granitemoehybrid(hf_cfg: Any):
    """A transformers `granitemoehybrid` config.json with no experts
    (Granite-4.0-H-Micro) -> GraniteHybridConfig, key for key. What the
    mixer's published code leaves to be read off it is
    models/granitemoehybrid.py's `assumed`."""
    from substratus_tpu.models.granitemoehybrid import GraniteHybridConfig

    get = lambda name, default=None: getattr(hf_cfg, name, default)
    if (get("num_local_experts", 0) or get("attention_bias", False)
            or get("mamba_proj_bias", False)
            or not get("mamba_conv_bias", True)
            or get("position_embedding_type", "nope") != "nope"
            or get("hidden_act", "silu") != "silu"
            or get("normalization_function", "rmsnorm") != "rmsnorm"):
        raise NotImplementedError(
            "granitemoehybrid: routed experts, attention_bias, "
            "mamba_proj_bias, a convolution without its bias, rotary "
            "positions and another activation or norm are not written")
    d = hf_cfg.hidden_size
    if get("mamba_n_heads") * get("mamba_d_head") != get("mamba_expand", 2) * d:
        raise NotImplementedError(
            "granitemoehybrid: mamba_n_heads x mamba_d_head is not "
            "mamba_expand x hidden_size")
    n = hf_cfg.num_hidden_layers
    return GraniteHybridConfig(
        vocab_size=hf_cfg.vocab_size,
        dim=d,
        n_layers=n,
        n_heads=hf_cfg.num_attention_heads,
        n_kv_heads=get("num_key_value_heads") or hf_cfg.num_attention_heads,
        head_dim=get("head_dim") or d // hf_cfg.num_attention_heads,
        hidden_dim=get("shared_intermediate_size"),
        layer_types=tuple(hf_cfg.layer_types[:n]),
        mamba_n_heads=get("mamba_n_heads"),
        mamba_d_head=get("mamba_d_head"),
        mamba_d_state=get("mamba_d_state"),
        mamba_d_conv=get("mamba_d_conv"),
        mamba_n_groups=get("mamba_n_groups", 1),
        embedding_multiplier=float(get("embedding_multiplier", 1.0)),
        residual_multiplier=float(get("residual_multiplier", 1.0)),
        attention_multiplier=float(get("attention_multiplier")),
        logits_scaling=float(get("logits_scaling", 1.0)),
        norm_eps=get("rms_norm_eps", 1e-5),
        max_seq_len=get("max_position_embeddings", 131072),
        tie_embeddings=bool(get("tie_word_embeddings", True)),
    )


def convert_granitemoehybrid_state_dict(sd: Mapping[str, Any], cfg: Any,
                                        dtype=jnp.bfloat16) -> Params:
    """The published tensor names (`mamba.{in_proj, conv1d, A_log, D,
    dt_bias, norm, out_proj}`, `self_attn.{q, k, v, o}_proj`,
    `shared_mlp.{input_linear, output_linear}`) -> models/
    granitemoehybrid.py's tree. Written from the names and
    torch.nn.Linear's [out, in] alone and never run on the published
    checkpoint (none is at hand and none is fetched: the family is served
    on seeded weights); tests/test_granitemoehybrid.py turns a tree into
    these names and back."""
    from substratus_tpu.models.granitemoehybrid import ATTN, MAMBA

    def get(name: str) -> np.ndarray:
        for prefix in ("", "model."):
            if prefix + name in sd:
                return _np(sd[prefix + name])
        raise KeyError(name)

    def stack(kind, fmt: str, transform=lambda w: w, to=dtype) -> jnp.ndarray:
        return jnp.asarray(np.stack(
            [transform(get(fmt.format(i=i)))
             for i, k in enumerate(cfg.layer_types) if kind in (None, k)]), to)

    m = cfg.hidden_dim
    f32 = jnp.float32
    params: Params = {
        "tok_embed": jnp.asarray(get("embed_tokens.weight"), dtype),
        "out_norm": jnp.asarray(get("norm.weight"), dtype),
        "layers": {
            "input_norm": stack(None, "layers.{i}.input_layernorm.weight"),
            "post_norm": stack(
                None, "layers.{i}.post_attention_layernorm.weight"),
            # input_linear [2 M, D]: the gate's rows, then the up's
            "w_gate": stack(None, "layers.{i}.shared_mlp.input_linear.weight",
                            lambda w: w[:m].T),
            "w_up": stack(None, "layers.{i}.shared_mlp.input_linear.weight",
                          lambda w: w[m:].T),
            "w_down": stack(
                None, "layers.{i}.shared_mlp.output_linear.weight",
                lambda w: w.T),
        },
    }
    if cfg.count(MAMBA):
        mm = "layers.{i}.mamba."
        params["ssm"] = {
            "w_in": stack(MAMBA, mm + "in_proj.weight", lambda w: w.T),
            # conv1d.weight [W, 1, K]: tap j of the equations is column j
            "taps": stack(MAMBA, mm + "conv1d.weight", lambda w: w[:, 0].T),
            "conv_bias": stack(MAMBA, mm + "conv1d.bias", to=f32),
            "a_log": stack(MAMBA, mm + "A_log", to=f32),
            "d_skip": stack(MAMBA, mm + "D", to=f32),
            "dt_bias": stack(MAMBA, mm + "dt_bias", to=f32),
            "norm": stack(MAMBA, mm + "norm.weight"),
            "w_out": stack(MAMBA, mm + "out_proj.weight", lambda w: w.T),
        }
    if cfg.count(ATTN):
        aa = "layers.{i}.self_attn."
        params["attn"] = {
            # [heads * hd, D], as torch.nn.Linear keeps them
            "wq": stack(ATTN, aa + "q_proj.weight"),
            "wk": stack(ATTN, aa + "k_proj.weight"),
            "wv": stack(ATTN, aa + "v_proj.weight"),
            "wo": stack(ATTN, aa + "o_proj.weight", lambda w: w.T),
        }
    return params


def config_from_hf_deepseek_v3(hf_cfg: Any):
    """A transformers `deepseek_v3` config.json, or the text part of a
    `dots_vlm` one (dots.vlm1 carries DeepSeek-V3's keys one for one; its
    vision tower is not built), -> DeepseekV3Config."""
    from substratus_tpu.models.deepseek_v3 import DeepseekV3Config

    get = lambda name, default=None: getattr(hf_cfg, name, default)
    if (get("attention_bias", False) or get("moe_layer_freq", 1) != 1
            or get("scoring_func", "sigmoid") != "sigmoid"
            or get("topk_method", "noaux_tc") != "noaux_tc"
            or not get("q_lora_rank")):
        raise NotImplementedError(
            "deepseek_v3: attention_bias, a sparse layer every other layer, "
            "a softmax router, a router without the group limit's bias and "
            "a full-rank query are not written")
    scaling = get("rope_scaling") or {}
    if scaling and scaling.get("type", scaling.get("rope_type")) != "yarn":
        raise NotImplementedError(f"deepseek_v3: rope_scaling {scaling}")
    return DeepseekV3Config(
        vocab_size=hf_cfg.vocab_size,
        dim=hf_cfg.hidden_size,
        n_layers=hf_cfg.num_hidden_layers,
        n_heads=hf_cfg.num_attention_heads,
        q_lora_rank=hf_cfg.q_lora_rank,
        kv_lora_rank=hf_cfg.kv_lora_rank,
        qk_nope_head_dim=hf_cfg.qk_nope_head_dim,
        qk_rope_head_dim=hf_cfg.qk_rope_head_dim,
        v_head_dim=hf_cfg.v_head_dim,
        hidden_dim=hf_cfg.intermediate_size,
        moe_hidden_dim=hf_cfg.moe_intermediate_size,
        first_k_dense=get("first_k_dense_replace", 0),
        n_experts=hf_cfg.n_routed_experts,
        n_experts_per_token=hf_cfg.num_experts_per_tok,
        n_shared_experts=get("n_shared_experts", 0) or 0,
        n_group=get("n_group", 1) or 1,
        topk_group=get("topk_group", 1) or 1,
        routed_scaling_factor=float(get("routed_scaling_factor", 1.0)),
        norm_topk_prob=bool(get("norm_topk_prob", True)),
        rope_theta=float(get("rope_theta", 1e4)),
        rope_factor=float(scaling.get("factor", 1.0)),
        rope_original_max=int(scaling.get(
            "original_max_position_embeddings", 4096)),
        rope_beta_fast=float(scaling.get("beta_fast", 32)),
        rope_beta_slow=float(scaling.get("beta_slow", 1)),
        rope_mscale=float(scaling.get("mscale", 1.0)),
        rope_mscale_all_dim=float(scaling.get("mscale_all_dim", 1.0)),
        norm_eps=get("rms_norm_eps", 1e-6),
        max_seq_len=get("max_position_embeddings", 163840),
        tie_embeddings=bool(get("tie_word_embeddings", False)),
    )


def config_from_hf_glm_moe_dsa(hf_cfg: Any):
    """A transformers `glm_moe_dsa` config.json (GLM-5) -> DeepseekV3Config
    with its indexer: DeepSeek-V3's keys, the rotary table under
    `rope_parameters`, no group limit by default, and the four keys of
    DeepSeek Sparse Attention's index. The checkpoint names the indexer's
    tensors `self_attn.indexer.{wq_b, wk, k_norm, weights_proj}` = the
    tree's `w_iq`, `w_ik`, `ik_norm` / `ik_norm_bias`, `w_iw`; like the
    rest of the family's, no converter maps them yet."""
    get = lambda name, default=None: getattr(hf_cfg, name, default)
    rope = dict(get("rope_parameters") or {})
    kind = rope.get("rope_type", rope.get("type", "default"))
    if kind != "default":
        raise NotImplementedError(f"glm_moe_dsa: rope_type {kind!r}")
    if (get("n_group", 1) or 1) > 1 and not get("topk_group"):
        raise NotImplementedError(
            f"glm_moe_dsa: n_group {get('n_group')} without topk_group")
    if int(get("index_topk", 0) or 0) < 1 or not get("index_n_heads"):
        raise NotImplementedError(
            f"glm_moe_dsa: index_topk {get('index_topk')!r} over "
            f"{get('index_n_heads')!r} index heads")
    plain = SimpleNamespace(**{
        **vars(hf_cfg), "rope_scaling": None,
        "rope_theta": rope.get("rope_theta", get("rope_theta", 1e4))})
    return config_from_hf_deepseek_v3(plain).replace(
        index_n_heads=int(hf_cfg.index_n_heads),
        index_head_dim=int(hf_cfg.index_head_dim),
        index_topk=int(hf_cfg.index_topk),
    )


def convert_deepseek_v3_state_dict(sd: Mapping[str, Any], cfg: Any,
                                   dtype=jnp.bfloat16) -> Params:
    """Not written, as convert_exaone_moe_state_dict is not: a checkpoint's
    tensors would need their rotary channels de-interleaved and kv_b_proj
    laid out a head (models/deepseek_v3.py, "Departures"), and none was at
    hand to check that against (no network). The family is served from a
    named config (random weights) or an orbax checkpoint of
    models/deepseek_v3.py's own tree."""
    raise NotImplementedError(
        "deepseek_v3: the config.json is read (config_from_hf_deepseek_v3) "
        "but no converter maps the checkpoint's tensors onto "
        "models/deepseek_v3.py's tree yet"
    )


def _dispatch_hf(model_type: str):
    """transformers model_type -> (config_fn, convert_fn), via the family
    registry (models/registry.py is the single dispatch table)."""
    from substratus_tpu.models.registry import HF_MODEL_TYPES

    family = HF_MODEL_TYPES.get(model_type)
    if model_type == "glm_moe_dsa":
        return config_from_hf_glm_moe_dsa, convert_deepseek_v3_state_dict
    if family == "opt":
        return config_from_hf_opt, convert_opt_state_dict
    if family == "llama":
        return config_from_hf, convert_llama_state_dict
    if family == "falcon":
        return config_from_hf_falcon, convert_falcon_state_dict
    if family == "exaone_moe":
        return config_from_hf_exaone_moe, convert_exaone_moe_state_dict
    if family == "lfm2_moe":
        return config_from_hf_lfm2_moe, convert_lfm2_moe_state_dict
    if family == "brumby":
        return config_from_hf_brumby, convert_brumby_state_dict
    if family == "deepseek_v3":
        return config_from_hf_deepseek_v3, convert_deepseek_v3_state_dict
    if family == "granitemoehybrid":
        return (config_from_hf_granitemoehybrid,
                convert_granitemoehybrid_state_dict)
    raise NotImplementedError(
        f"unsupported HF model_type {model_type!r} "
        f"(supported: {sorted(HF_MODEL_TYPES)})"
    )


def load_pretrained(
    path_or_name: str, dtype=jnp.bfloat16
) -> Tuple[LlamaConfig, Params]:
    """Load an HF Llama-family checkpoint from a local dir (safetensors or
    torch bin via transformers)."""
    if os.path.isdir(path_or_name) and os.path.exists(
        os.path.join(path_or_name, "config.json")
    ):
        with open(os.path.join(path_or_name, "config.json")) as f:
            raw = json.load(f)
        hf_ns = SimpleNamespace(**raw)
        cfg, convert = _dispatch_hf(raw.get("model_type", "llama"))
        cfg = cfg(hf_ns)
        sd: Dict[str, np.ndarray] = {}
        st_files = [
            f for f in os.listdir(path_or_name) if f.endswith(".safetensors")
        ]
        if st_files:
            # framework="torch" rather than "numpy": numpy has no bfloat16,
            # which is what Llama checkpoints ship in.
            from safetensors import safe_open

            for fname in sorted(st_files):
                with safe_open(
                    os.path.join(path_or_name, fname), framework="torch"
                ) as f:
                    for key in f.keys():
                        sd[key] = f.get_tensor(key)
        else:
            import torch

            for fname in sorted(os.listdir(path_or_name)):
                if fname.endswith(".bin"):
                    sd.update(
                        torch.load(
                            os.path.join(path_or_name, fname),
                            map_location="cpu",
                            weights_only=True,
                        )
                    )
        return cfg, convert(sd, cfg, dtype)

    # Fall back to transformers hub loading (requires network or cache).
    from transformers import AutoConfig, AutoModelForCausalLM

    hf_cfg = AutoConfig.from_pretrained(path_or_name)
    model = AutoModelForCausalLM.from_pretrained(path_or_name)
    cfg_fn, convert = _dispatch_hf(getattr(hf_cfg, "model_type", "llama"))
    cfg = cfg_fn(hf_cfg)
    return cfg, convert(model.state_dict(), cfg, dtype)
