"""OPT model family (facebook/opt-125m .. opt-66b).

The reference's CPU smoke model is facebook/opt-125m (test/system.sh,
examples/facebook-opt-125m); this makes it a first-class citizen rather
than a stand-in. Same TPU-first structure as models/llama.py — stacked
layers scanned with lax.scan, logical-axis annotations, KV-cache decode —
with the OPT architectural differences: learned positional embeddings
(offset by 2, an OPT quirk), LayerNorm with bias, biased projections, ReLU
MLP, tied LM head.

Implements the same module interface the serving engine consumes:
CONFIGS / init_params / param_logical_axes / init_cache / forward /
decode_step (see serve/engine.py).
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
from substratus_tpu.ops.basics import layer_norm, lora_delta

Params = Dict[str, Any]

POS_OFFSET = 2  # OPT reserves the first two position-embedding rows.

# train/lora.py adapters attach to the attention projections (wq/wk/wv/wo).
SUPPORTS_LORA = True


@dataclass(frozen=True)
class OPTConfig:
    vocab_size: int = 50272
    dim: int = 768
    n_layers: int = 12
    n_heads: int = 12
    hidden_dim: int = 3072
    max_seq_len: int = 2048
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16

    @property
    def head_size(self) -> int:
        return self.dim // self.n_heads

    # The engine treats kv heads uniformly; OPT is MHA.
    @property
    def n_kv_heads(self) -> int:
        return self.n_heads

    def replace(self, **kw) -> "OPTConfig":
        return dataclasses.replace(self, **kw)


CONFIGS: Dict[str, OPTConfig] = {
    "tiny-opt": OPTConfig(
        vocab_size=256, dim=64, n_layers=2, n_heads=4, hidden_dim=128,
        max_seq_len=128,
    ),
    "opt-125m": OPTConfig(),
    "opt-1.3b": OPTConfig(dim=2048, n_layers=24, n_heads=32, hidden_dim=8192),
    "opt-6.7b": OPTConfig(dim=4096, n_layers=32, n_heads=32, hidden_dim=16384),
}


def param_logical_axes(cfg: OPTConfig) -> Params:
    return {
        "tok_embed": ("vocab", "embed"),
        "pos_embed": (None, "embed"),
        "layers": {
            "ln1_scale": ("layers", "embed"),
            "ln1_bias": ("layers", "embed"),
            "wq": ("layers", "embed", "heads", "head_dim"),
            "bq": ("layers", "heads", "head_dim"),
            "wk": ("layers", "embed", "heads", "head_dim"),
            "bk": ("layers", "heads", "head_dim"),
            "wv": ("layers", "embed", "heads", "head_dim"),
            "bv": ("layers", "heads", "head_dim"),
            "wo": ("layers", "heads", "head_dim", "embed"),
            "bo": ("layers", "embed"),
            "ln2_scale": ("layers", "embed"),
            "ln2_bias": ("layers", "embed"),
            "fc1": ("layers", "embed", "mlp"),
            "fc1_b": ("layers", "mlp"),
            "fc2": ("layers", "mlp", "embed"),
            "fc2_b": ("layers", "embed"),
        },
        "final_ln_scale": ("embed",),
        "final_ln_bias": ("embed",),
    }


def init_params(cfg: OPTConfig, key: jax.Array) -> Params:
    hd = cfg.head_size
    L, D, H, M = cfg.n_layers, cfg.dim, cfg.n_heads, cfg.hidden_dim
    k = iter(jax.random.split(key, 12))

    def dense(key, shape, fan_in):
        return (
            jax.random.truncated_normal(key, -2, 2, shape, jnp.float32)
            * (fan_in**-0.5)
        ).astype(cfg.dtype)

    return {
        "tok_embed": dense(next(k), (cfg.vocab_size, D), D),
        "pos_embed": dense(next(k), (cfg.max_seq_len + POS_OFFSET, D), D),
        "layers": {
            "ln1_scale": jnp.ones((L, D), cfg.dtype),
            "ln1_bias": jnp.zeros((L, D), cfg.dtype),
            "wq": dense(next(k), (L, D, H, hd), D),
            "bq": jnp.zeros((L, H, hd), cfg.dtype),
            "wk": dense(next(k), (L, D, H, hd), D),
            "bk": jnp.zeros((L, H, hd), cfg.dtype),
            "wv": dense(next(k), (L, D, H, hd), D),
            "bv": jnp.zeros((L, H, hd), cfg.dtype),
            "wo": dense(next(k), (L, H, hd, D), D),
            "bo": jnp.zeros((L, D), cfg.dtype),
            "ln2_scale": jnp.ones((L, D), cfg.dtype),
            "ln2_bias": jnp.zeros((L, D), cfg.dtype),
            "fc1": dense(next(k), (L, D, M), D),
            "fc1_b": jnp.zeros((L, M), cfg.dtype),
            "fc2": dense(next(k), (L, M, D), M),
            "fc2_b": jnp.zeros((L, D), cfg.dtype),
        },
        "final_ln_scale": jnp.ones((D,), cfg.dtype),
        "final_ln_bias": jnp.zeros((D,), cfg.dtype),
    }


def init_cache(
    cfg: OPTConfig, batch: int, max_len: Optional[int] = None, dtype=None
) -> Params:
    """Decode KV cache [L, B, KH, S, head_dim] — per-head sequence-
    contiguous, same convention as llama.init_cache."""
    S = max_len or cfg.max_seq_len
    dtype = dtype or cfg.dtype
    shape = (cfg.n_layers, batch, cfg.n_heads, S, cfg.head_size)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def cache_logical_axes(cfg: OPTConfig, quantized: bool = False) -> Params:
    ax = ("layers", "cache_batch", "kv_heads", "cache_seq", "head_dim")
    return {"k": ax, "v": ax}


def _block(x, lp, positions, cfg, layer_cache, kv_length=None,
           lora_layers=None, lora_scale=1.0):
    lora = lora_layers or {}
    with jax.named_scope(scopes.NORM):
        h = layer_norm(x, lp["ln1_scale"], lp["ln1_bias"], cfg.norm_eps)

    def proj(name, bias, eq, lora_eq):
        out = jnp.einsum(eq, h, lp[name]) + lp[bias]
        if name in lora:
            out = out + lora_delta(h, lora[name], lora_scale, lora_eq)
        return out

    with jax.named_scope(scopes.ATTN_QKV):
        q = proj("wq", "bq", "bsd,dhk->bshk", "bsr,rhk->bshk")
        kk = proj("wk", "bk", "bsd,dhk->bshk", "bsr,rhk->bshk")
        vv = proj("wv", "bv", "bsd,dhk->bshk", "bsr,rhk->bshk")

    if layer_cache is None:
        with jax.named_scope(scopes.ATTN_CORE):
            attn = dot_product_attention(
                q, kk, vv, causal=True, q_positions=positions
            )
        kv_out = (kk, vv)
    else:
        from substratus_tpu.ops.decode_attention import update_cache_and_attend

        k_cache, v_cache = layer_cache  # [B, KH, S_cache, D]
        attn, kv = update_cache_and_attend(
            {"k": k_cache, "v": v_cache}, q, kk, vv, positions,
            kv_length=kv_length,
        )
        kv_out = (kv["k"], kv["v"])

    with jax.named_scope(scopes.ATTN_OUT):
        o = jnp.einsum("bshk,hkd->bsd", attn, lp["wo"]) + lp["bo"]
        if "wo" in lora:
            b, s = x.shape[:2]
            o = o + lora_delta(
                attn.reshape(b, s, -1), lora["wo"], lora_scale, "bsr,rd->bsd"
            )
        x = x + o
    with jax.named_scope(scopes.NORM):
        h = layer_norm(x, lp["ln2_scale"], lp["ln2_bias"], cfg.norm_eps)
    with jax.named_scope(scopes.MLP):
        h = jax.nn.relu(jnp.einsum("bsd,dm->bsm", h, lp["fc1"]) + lp["fc1_b"])
        x = x + jnp.einsum("bsm,md->bsd", h, lp["fc2"]) + lp["fc2_b"]
    return x, kv_out


def forward(
    params: Params,
    tokens: jnp.ndarray,
    cfg: OPTConfig,
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
        x = (
            params["tok_embed"][tokens]
            + params["pos_embed"][positions + POS_OFFSET]
        )

    lora_scale = lora["scale"] if lora is not None else 1.0

    def body(carry, layer_in):
        lp = layer_in["lp"]
        x_out, kv = _block(
            carry, lp, positions, cfg, layer_in.get("cache"), kv_length,
            layer_in.get("lora"), lora_scale,
        )
        return x_out, kv

    xs: Dict[str, Any] = {"lp": params["layers"]}
    if cache is not None:
        xs["cache"] = (cache["k"], cache["v"])
    if lora is not None:
        xs["lora"] = lora["layers"]
    if remat:
        body = jax.checkpoint(body)
    with jax.named_scope(scopes.LAYERS):
        x, (ks, vs) = lax.scan(body, x, xs)

    with jax.named_scope(scopes.LM_HEAD):
        x = layer_norm(
            x, params["final_ln_scale"], params["final_ln_bias"], cfg.norm_eps
        )
        logits = jnp.einsum("bsd,vd->bsv", x, params["tok_embed"])  # tied head
        return logits.astype(jnp.float32), {"k": ks, "v": vs}


@partial(jax.jit, static_argnames=("cfg",), donate_argnames=("cache",))
def decode_step(params, cache, tokens, positions, cfg):
    logits, new_cache = forward(
        params, tokens[:, None], cfg, positions=positions[:, None], cache=cache
    )
    return logits[:, 0, :], new_cache
