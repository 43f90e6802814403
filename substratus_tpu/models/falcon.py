"""Falcon model family (falcon-7b[-instruct], falcon-40b).

BASELINE.json's config list includes falcon-7b-instruct serving and the
falcon-40b finetune (the reference's largest example,
examples/falcon-40b/finetuned-model.yaml). Architectural differences from
Llama, implemented TPU-first in the same stacked-scan style:

  * parallel block: x + attn(ln(x)) + mlp(ln(x)) — one residual add, and on
    7b-style models attention and MLP share a single LayerNorm
    (new_decoder_architecture=False); 40b-style models use separate ln_attn
    / ln_mlp (new_decoder_architecture=True);
  * multi-query (7b: 1 kv head) / grouped-query (40b: 8) attention with
    rotary embeddings;
  * GELU MLP, biasless projections (config.bias=False in released models),
    tied LM head.

Same module interface as models/llama.py / models/opt.py (see
serve/engine.py and models/registry.py).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from substratus_tpu.ops import scopes
from substratus_tpu.ops.attention import dot_product_attention
from substratus_tpu.ops.basics import layer_norm, rope, lora_delta

Params = Dict[str, Any]

# train/lora.py adapters attach to the attention projections (wq/wk/wv/wo).
SUPPORTS_LORA = True


@dataclass(frozen=True)
class FalconConfig:
    vocab_size: int = 65024
    dim: int = 4544
    n_layers: int = 32
    n_heads: int = 71
    n_kv_heads: int = 1
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    max_seq_len: int = 2048
    separate_ln: bool = False  # True = 40b-style ln_attn/ln_mlp
    dtype: Any = jnp.bfloat16

    @property
    def head_size(self) -> int:
        return self.dim // self.n_heads

    @property
    def hidden_dim(self) -> int:
        return 4 * self.dim

    def replace(self, **kw) -> "FalconConfig":
        return dataclasses.replace(self, **kw)


CONFIGS: Dict[str, FalconConfig] = {
    "tiny-falcon": FalconConfig(
        vocab_size=256, dim=64, n_layers=2, n_heads=4, n_kv_heads=1,
        max_seq_len=128,
    ),
    "tiny-falcon-40b-style": FalconConfig(
        vocab_size=256, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
        max_seq_len=128, separate_ln=True,
    ),
    "falcon-7b": FalconConfig(),
    "falcon-40b": FalconConfig(
        dim=8192, n_layers=60, n_heads=128, n_kv_heads=8, separate_ln=True
    ),
}


def param_logical_axes(cfg: FalconConfig) -> Params:
    layers = {
        "ln1_scale": ("layers", "embed"),
        "ln1_bias": ("layers", "embed"),
        "wq": ("layers", "embed", "heads", "head_dim"),
        "wk": ("layers", "embed", "kv_heads", "head_dim"),
        "wv": ("layers", "embed", "kv_heads", "head_dim"),
        "wo": ("layers", "heads", "head_dim", "embed"),
        "fc1": ("layers", "embed", "mlp"),
        "fc2": ("layers", "mlp", "embed"),
    }
    if cfg.separate_ln:
        layers["ln2_scale"] = ("layers", "embed")
        layers["ln2_bias"] = ("layers", "embed")
    return {
        "tok_embed": ("vocab", "embed"),
        "layers": layers,
        "final_ln_scale": ("embed",),
        "final_ln_bias": ("embed",),
    }


def init_params(cfg: FalconConfig, key: jax.Array) -> Params:
    hd = cfg.head_size
    L, D, H, KH, M = (
        cfg.n_layers, cfg.dim, cfg.n_heads, cfg.n_kv_heads, cfg.hidden_dim
    )
    k = iter(jax.random.split(key, 10))

    def dense(key, shape, fan_in):
        return (
            jax.random.truncated_normal(key, -2, 2, shape, jnp.float32)
            * (fan_in**-0.5)
        ).astype(cfg.dtype)

    layers = {
        "ln1_scale": jnp.ones((L, D), cfg.dtype),
        "ln1_bias": jnp.zeros((L, D), cfg.dtype),
        "wq": dense(next(k), (L, D, H, hd), D),
        "wk": dense(next(k), (L, D, KH, hd), D),
        "wv": dense(next(k), (L, D, KH, hd), D),
        "wo": dense(next(k), (L, H, hd, D), H * hd),
        "fc1": dense(next(k), (L, D, M), D),
        "fc2": dense(next(k), (L, M, D), M),
    }
    if cfg.separate_ln:
        layers["ln2_scale"] = jnp.ones((L, D), cfg.dtype)
        layers["ln2_bias"] = jnp.zeros((L, D), cfg.dtype)
    return {
        "tok_embed": dense(next(k), (cfg.vocab_size, D), D),
        "layers": layers,
        "final_ln_scale": jnp.ones((D,), cfg.dtype),
        "final_ln_bias": jnp.zeros((D,), cfg.dtype),
    }


def init_cache(
    cfg: FalconConfig, batch: int, max_len: Optional[int] = None, dtype=None
) -> Params:
    """Decode KV cache [L, B, KH, S, head_dim] — per-head sequence-
    contiguous, same convention as llama.init_cache (KH=1 for MQA)."""
    S = max_len or cfg.max_seq_len
    dtype = dtype or cfg.dtype
    shape = (cfg.n_layers, batch, cfg.n_kv_heads, S, cfg.head_size)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def cache_logical_axes(cfg: FalconConfig, quantized: bool = False) -> Params:
    ax = ("layers", "cache_batch", "kv_heads", "cache_seq", "head_dim")
    return {"k": ax, "v": ax}


def _block(x, lp, positions, cfg, layer_cache, kv_length=None,
           lora_layers=None, lora_scale=1.0):
    lora = lora_layers or {}
    with jax.named_scope(scopes.NORM):
        h_attn = layer_norm(x, lp["ln1_scale"], lp["ln1_bias"], cfg.norm_eps)
        h_mlp = (
            layer_norm(x, lp["ln2_scale"], lp["ln2_bias"], cfg.norm_eps)
            if cfg.separate_ln
            else h_attn
        )

    def proj(name, eq, lora_eq):
        out = jnp.einsum(eq, h_attn, lp[name])
        if name in lora:
            out = out + lora_delta(h_attn, lora[name], lora_scale, lora_eq)
        return out

    with jax.named_scope(scopes.ATTN_QKV):
        q = proj("wq", "bsd,dhk->bshk", "bsr,rhk->bshk")
        kk = proj("wk", "bsd,dhk->bshk", "bsr,rhk->bshk")
        vv = proj("wv", "bsd,dhk->bshk", "bsr,rhk->bshk")
        q = rope(q, positions, cfg.rope_theta)
        kk = rope(kk, positions, cfg.rope_theta)

    if layer_cache is None:
        with jax.named_scope(scopes.ATTN_CORE):
            attn = dot_product_attention(
                q, kk, vv, causal=True, q_positions=positions
            )
        kv_out = {"k": kk, "v": vv}
    else:
        from substratus_tpu.ops.decode_attention import update_cache_and_attend

        attn, kv_out = update_cache_and_attend(
            layer_cache, q, kk, vv, positions, kv_length=kv_length,
        )

    with jax.named_scope(scopes.ATTN_OUT):
        attn_out = jnp.einsum("bshk,hkd->bsd", attn, lp["wo"])
        if "wo" in lora:
            b, s = x.shape[:2]
            attn_out = attn_out + lora_delta(
                attn.reshape(b, s, -1), lora["wo"], lora_scale, "bsr,rd->bsd"
            )
    with jax.named_scope(scopes.MLP):
        mlp_out = jnp.einsum(
            "bsm,md->bsd",
            jax.nn.gelu(
                jnp.einsum("bsd,dm->bsm", h_mlp, lp["fc1"]), approximate=False
            ),
            lp["fc2"],
        )
        # Parallel block: one residual add for both sublayers.
        return x + attn_out + mlp_out, kv_out


def forward(
    params: Params,
    tokens: jnp.ndarray,
    cfg: FalconConfig,
    *,
    positions: Optional[jnp.ndarray] = None,
    cache: Optional[Params] = None,
    kv_length: Optional[jnp.ndarray] = None,  # [B] valid cache prefix
    lora: Optional[Params] = None,  # {"layers": adapters, "scale": s}
    remat: bool = False,
    train: bool = False,
) -> Tuple[jnp.ndarray, Params]:
    B, S = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))

    with jax.named_scope(scopes.EMBED):
        x = params["tok_embed"][tokens]

    lora_scale = lora["scale"] if lora is not None else 1.0

    def body(carry, layer_in):
        x_out, kv = _block(
            carry, layer_in["lp"], positions, cfg, layer_in.get("cache"),
            kv_length, layer_in.get("lora"), lora_scale,
        )
        return x_out, kv

    xs: Dict[str, Any] = {"lp": params["layers"]}
    if cache is not None:
        xs["cache"] = cache
    if lora is not None:
        xs["lora"] = lora["layers"]
    if remat:
        body = jax.checkpoint(body)
    with jax.named_scope(scopes.LAYERS):
        x, kv = lax.scan(body, x, xs)

    with jax.named_scope(scopes.LM_HEAD):
        x = layer_norm(
            x, params["final_ln_scale"], params["final_ln_bias"], cfg.norm_eps
        )
        logits = jnp.einsum("bsd,vd->bsv", x, params["tok_embed"])  # tied head
        return logits.astype(jnp.float32), kv


@partial(jax.jit, static_argnames=("cfg",), donate_argnames=("cache",))
def decode_step(params, cache, tokens, positions, cfg):
    logits, new_cache = forward(
        params, tokens[:, None], cfg, positions=positions[:, None], cache=cache
    )
    return logits[:, 0, :], new_cache
