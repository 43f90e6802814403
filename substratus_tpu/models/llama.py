"""Llama model family (Llama 2/3, TinyLlama, and shape-compatible configs).

Flagship compute path of the framework. The reference operator ran Llama via
external CUDA images (examples/llama2-7b/*.yaml -> substratusai/model-*
images, SURVEY.md §2.2); here the model is in-repo, TPU-first:

  * params are plain pytrees with per-layer weights STACKED on a leading
    `layers` axis and the block applied via `lax.scan` — compile time is O(1)
    in depth and XLA sees one fused block;
  * every array carries a logical-axis annotation (parallel/sharding.py), so
    dp/fsdp/tp/sp strategies are rules-table changes, not model edits;
  * matmuls run in bfloat16 on the MXU with float32 softmax/norm accumulation;
  * weights may be int8-quantized per-channel (ops/quant.py) — decode is
    HBM-bandwidth-bound, so int8 weights nearly double decode throughput;
  * RoPE follows the HF rotate-half convention so HF checkpoints convert
    without permutation (load/hf.py).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from substratus_tpu.ops.attention import dot_product_attention
from substratus_tpu.ops.basics import (
    lora_delta,
    lora_delta_indexed,
    rms_norm,
    rope,
    swiglu,
)
from substratus_tpu.ops import scopes
from substratus_tpu.ops.quant import QTensor, materialize, qeinsum, qeinsum_w8a8

Params = Dict[str, Any]

# The engine may store this family's KV cache int8-quantized (init_cache).
SUPPORTS_INT8_KV = True
# train/lora.py adapters are implemented for this family's projections.
SUPPORTS_LORA = True
# forward() accepts slot-stacked adapter trees + a per-row adapter_ids
# gather — multi-tenant adapter serving (serve/adapters.py).
SUPPORTS_INDEXED_LORA = True


@dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 32
    hidden_dim: int = 11008
    head_dim: Optional[int] = None
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    max_seq_len: int = 4096
    tie_embeddings: bool = False
    dtype: Any = jnp.bfloat16
    # Self-attention (no-cache path) implementation:
    #   "xla"   — einsum + masked softmax (always correct; CPU tests)
    #   "flash" — Pallas blockwise kernel (ops/flash_attention.py, TPU)
    #   "ring"  — sequence-parallel ring attention (ops/ring_attention.py);
    #             requires an ambient mesh (jax.set_mesh) with a
    #             "sequence" axis
    attn_impl: str = "xla"
    # W8A8: dynamically quantize activations per token so quantized matmuls
    # run in the MXU's native s8xs8 mode (ops/quant.py::qeinsum_w8a8).
    # Opt-in; weight-only int8 (qeinsum) is the default quantized path.
    quant_activations: bool = False
    # Mixture-of-experts (Mixtral family): n_experts == 0 means dense MLP.
    # Routed top-k; training uses GShard-style capacity dispatch with the
    # expert dim sharded over the "expert" mesh axis. This layer always
    # holds every expert: a layer that is told which share of the experts
    # it holds (what a rank of expert parallelism serves with) is
    # models/exaone_moe.py::_moe.
    n_experts: int = 0
    n_experts_per_token: int = 2
    capacity_factor: float = 1.25
    router_aux_weight: float = 0.01

    @property
    def head_size(self) -> int:
        return self.head_dim if self.head_dim is not None else self.dim // self.n_heads

    def replace(self, **kw) -> "LlamaConfig":
        return dataclasses.replace(self, **kw)


# Shape-accurate configs for the model sizes the reference's examples exercise
# (examples/llama2-7b, llama2-13b-chat-gguf, llama2-70b) plus test sizes.
CONFIGS: Dict[str, LlamaConfig] = {
    "tiny": LlamaConfig(
        vocab_size=256, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
        hidden_dim=128, max_seq_len=128, norm_eps=1e-6,
    ),
    "debug-1b": LlamaConfig(
        vocab_size=32000, dim=2048, n_layers=16, n_heads=16, n_kv_heads=8,
        hidden_dim=5632, max_seq_len=2048,
    ),
    "llama2-7b": LlamaConfig(),
    "llama2-13b": LlamaConfig(dim=5120, n_layers=40, n_heads=40, n_kv_heads=40, hidden_dim=13824),
    "llama2-70b": LlamaConfig(dim=8192, n_layers=80, n_heads=64, n_kv_heads=8, hidden_dim=28672),
    "llama3-8b": LlamaConfig(
        vocab_size=128256, dim=4096, n_layers=32, n_heads=32, n_kv_heads=8,
        hidden_dim=14336, rope_theta=500000.0, max_seq_len=8192,
    ),
    "tinyllama-1.1b": LlamaConfig(
        vocab_size=32000, dim=2048, n_layers=22, n_heads=32, n_kv_heads=4,
        hidden_dim=5632, max_seq_len=2048,
    ),
    "tiny-moe": LlamaConfig(
        vocab_size=256, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
        hidden_dim=128, max_seq_len=128, norm_eps=1e-6, n_experts=4,
    ),
    "mixtral-8x7b": LlamaConfig(
        vocab_size=32000, dim=4096, n_layers=32, n_heads=32, n_kv_heads=8,
        hidden_dim=14336, rope_theta=1000000.0, max_seq_len=32768,
        n_experts=8, n_experts_per_token=2,
    ),
}


def param_logical_axes(cfg: LlamaConfig) -> Params:
    """Logical axis names for every param leaf (see parallel/sharding.py)."""
    layers = {
        "attn_norm": ("layers", "embed"),
        "wq": ("layers", "embed", "heads", "head_dim"),
        "wk": ("layers", "embed", "kv_heads", "head_dim"),
        "wv": ("layers", "embed", "kv_heads", "head_dim"),
        "wo": ("layers", "heads", "head_dim", "embed"),
        "mlp_norm": ("layers", "embed"),
    }
    if cfg.n_experts > 0:
        layers.update(
            {
                "router": ("layers", "embed", None),
                "w_gate": ("layers", "expert", "embed", "mlp"),
                "w_up": ("layers", "expert", "embed", "mlp"),
                "w_down": ("layers", "expert", "mlp", "embed"),
            }
        )
    else:
        layers.update(
            {
                "w_gate": ("layers", "embed", "mlp"),
                "w_up": ("layers", "embed", "mlp"),
                "w_down": ("layers", "mlp", "embed"),
            }
        )
    axes = {
        "tok_embed": ("vocab", "embed"),
        "layers": layers,
        "out_norm": ("embed",),
    }
    if not cfg.tie_embeddings:
        axes["lm_head"] = ("embed", "vocab")
    return axes


def quant_contracting(cfg: LlamaConfig) -> Params:
    """Contracting dims per leaf for ops.quant.quantize_params; () = dense.

    Axes are for the STACKED layer leaves (leading layer dim from
    init_params), e.g. wq [L, d, h, k] contracts d=1. The resulting scales
    are per-output-channel — the standard quality choice, and what lets
    qeinsum commute the scale out of the dot after lax.scan slices the
    layer dim off (scale-after-dot keeps the int8 bytes on the MXU operand
    path; see ops/quant.py).
    """
    moe = cfg.n_experts > 0
    layers = {
        "attn_norm": (),
        "wq": (1,),
        "wk": (1,),
        "wv": (1,),
        "wo": (1, 2),
        "mlp_norm": (),
        # Expert weights carry a leading expert dim; contracting shifts by 1.
        "w_gate": (2,) if moe else (1,),
        "w_up": (2,) if moe else (1,),
        "w_down": (2,) if moe else (1,),
    }
    if moe:
        layers["router"] = ()
    q = {"tok_embed": (), "layers": layers, "out_norm": ()}
    if not cfg.tie_embeddings:
        q["lm_head"] = (0,)
    return q


def init_params(cfg: LlamaConfig, key: jax.Array) -> Params:
    """Random init (truncated-normal fan-in scaling), stacked layers.

    This is the published form, the one checkpoints, training, LoRA and
    load/hf.py keep: wq, wk, wv [L, D, heads, hd] (HF's [heads * hd, D]
    transposed, so x @ w needs no transpose), wo [L, heads, hd, D]. It is
    not the form a serving program reads an int8 q, k or v leaf in: sliced
    off its stack inside the layer scan, a [D, heads, hd] int8 layer is
    first staged in VMEM and laid out anew, contracted dim last, before
    its dot reads it (1.2 ms of Mistral-7B's 12.1 ms decode step and 3.9
    of a 512 chunk's 49 on a v5e; flat [D, heads * hd] is no better:
    PERF.md section 6, PR 41). `serving_layout` turns those three leaves
    heads-first, contracted dim last, once, where the engine takes the
    tree; `_block` reads either form."""
    hd = cfg.head_size
    k = iter(jax.random.split(key, 16))

    def dense(key, shape, fan_in):
        return (
            jax.random.truncated_normal(key, -2, 2, shape, jnp.float32)
            * (fan_in**-0.5)
        ).astype(cfg.dtype)

    L, D, H, KH, M = cfg.n_layers, cfg.dim, cfg.n_heads, cfg.n_kv_heads, cfg.hidden_dim
    E = cfg.n_experts
    if E > 0:
        mlp = {
            "router": dense(next(k), (L, D, E), D),
            "w_gate": dense(next(k), (L, E, D, M), D),
            "w_up": dense(next(k), (L, E, D, M), D),
            "w_down": dense(next(k), (L, E, M, D), M),
        }
    else:
        mlp = {
            "w_gate": dense(next(k), (L, D, M), D),
            "w_up": dense(next(k), (L, D, M), D),
            "w_down": dense(next(k), (L, M, D), M),
        }
    params: Params = {
        "tok_embed": dense(next(k), (cfg.vocab_size, D), D),
        "layers": {
            "attn_norm": jnp.ones((L, D), cfg.dtype),
            "wq": dense(next(k), (L, D, H, hd), D),
            "wk": dense(next(k), (L, D, KH, hd), D),
            "wv": dense(next(k), (L, D, KH, hd), D),
            "wo": dense(next(k), (L, H, hd, D), H * hd),
            "mlp_norm": jnp.ones((L, D), cfg.dtype),
            **mlp,
        },
        "out_norm": jnp.ones((D,), cfg.dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense(next(k), (D, cfg.vocab_size), D)
    return params


# The int8 projections the serving form re-lays, and where their contracted
# dim lies: published [L, D, heads, hd] (scale [L, 1, heads, hd]), served
# [L, heads, hd, D] (scale [L, heads, hd, 1]).
_HEADS_LAST = ("wq", "wk", "wv")
_TO_SERVED = (0, 2, 3, 1)


def _published(w: Any) -> bool:
    """An int8 leaf of `_HEADS_LAST`, stacked, still in the published form:
    told by where the scale's contracted dim of size 1 lies."""
    return (isinstance(w, QTensor) and len(w.q.shape) == 4
            and tuple(w.scale.shape) == (w.q.shape[0], 1) + tuple(w.q.shape[2:])
            and w.q.shape[1] != 1)


def _served(w: Any) -> bool:
    """A q, k or v leaf (a layer of it, or the stack) that `serving_layout`
    has turned: an int8 leaf whose scale is 1 wide along the last dim."""
    return (isinstance(w, QTensor) and w.scale.shape[-1] == 1
            and w.q.shape[-1] != 1)


def serving_layout(params: Params, cfg: LlamaConfig,
                   donate: bool = False) -> Params:
    """The tree as the serving programs read it (the engine's door:
    serve/engine.py::Engine.serving_tree): every name and kind kept, the
    int8 q, k and v stacks [L, D, heads, hd] turned to [L, heads, hd, D]
    with their scales, the same numbers transposed once, so that a layer's
    slice feeds its dot from the stack (see `init_params`). The identity
    on a leaf that already has that form, on dense and `Q4Tensor` leaves
    and on every other leaf; works on arrays, host arrays and tracers
    (`jax.eval_shape` gives the served shapes). With `donate` a turned
    leaf's old arrays are deleted before the next leaf is turned: the tree
    never holds two stacks of one projection."""
    layers = dict(params["layers"])
    for name in _HEADS_LAST:
        w = layers.get(name)
        if not _published(w):
            continue
        layers[name] = QTensor(q=w.q.transpose(_TO_SERVED),
                               scale=w.scale.transpose(_TO_SERVED))
        if donate:
            jax.block_until_ready(layers[name])
            for old in (w.q, w.scale):
                if isinstance(old, jax.Array):
                    old.delete()
    return {**params, "layers": layers}


def serving_logical_axes(params: Params, cfg: LlamaConfig) -> Params:
    """`param_logical_axes` for a tree `serving_layout` returned: the
    turned leaves' axes turned with them (`heads` / `kv_heads` stay the
    sharded ones)."""
    axes = param_logical_axes(cfg)
    layers = dict(axes["layers"])
    for name in _HEADS_LAST:
        if _served(params["layers"].get(name)):
            layers[name] = tuple(layers[name][i] for i in _TO_SERVED)
    return {**axes, "layers": layers}


def init_cache(
    cfg: LlamaConfig, batch: int, max_len: Optional[int] = None, dtype=None
) -> Params:
    """Decode KV cache, layers-stacked: k/v [L, B, KH, S, head_dim].

    The per-head sequence-contiguous layout (KH before S) makes each kv
    head's history one contiguous HBM stream for the decode-attention
    read (ops/decode_attention.py) — the [B, S, KH, D] activation layout
    would interleave heads every D elements.

    dtype=jnp.int8 stores entries quantized per-vector (ops/quant.py
    quantize_kv) with f32 scales alongside ([L, B, KH, S]) — decode is
    bandwidth-bound on the cache read, so int8 roughly halves its HBM
    traffic.
    """
    S = max_len or cfg.max_seq_len
    dtype = dtype or cfg.dtype
    shape = (cfg.n_layers, batch, cfg.n_kv_heads, S, cfg.head_size)
    cache = {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
    if dtype == jnp.int8:
        cache["k_scale"] = jnp.ones(shape[:-1], jnp.float32)
        cache["v_scale"] = jnp.ones(shape[:-1], jnp.float32)
    return cache


def cache_logical_axes(cfg: LlamaConfig, quantized: bool = False) -> Params:
    ax = ("layers", "cache_batch", "kv_heads", "cache_seq", "head_dim")
    axes = {"k": ax, "v": ax}
    if quantized:
        axes["k_scale"] = ax[:-1]
        axes["v_scale"] = ax[:-1]
    return axes


# The engine may use a paged (block) KV layout for this family (serve/
# paged_kv.py owns the allocator; ops/kvcache.py owns the device ops).
SUPPORTS_PAGED = True


def init_paged_cache(
    cfg: LlamaConfig, pages: int, page_size: int, dtype=None,
    kv_shards: int = 1,
) -> Params:
    """Paged decode cache: a global page pool k/v [L, P, bs, KH, head_dim]
    addressed through a per-sequence block table (ops/kvcache.py, which
    decides the stored row: heads of 64 lie two to a row of 128)."""
    from substratus_tpu.ops import kvcache

    dtype = dtype or cfg.dtype
    return kvcache.init_paged_cache(
        cfg.n_layers, pages, page_size, cfg.n_kv_heads, cfg.head_size,
        dtype, quantized=dtype == jnp.int8, kv_shards=kv_shards,
    )


def paged_cache_logical_axes(cfg: LlamaConfig, quantized: bool = False) -> Params:
    from substratus_tpu.ops import kvcache

    return kvcache.paged_cache_logical_axes(quantized)


def _self_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    positions: jnp.ndarray,
    cfg: LlamaConfig,
) -> jnp.ndarray:
    """No-cache causal attention, dispatched per cfg.attn_impl. The fused
    kernels assume standard positions (row r attends 0..r within the same
    sequence), which holds for training and full prefill."""
    if cfg.attn_impl == "flash":
        from substratus_tpu.ops.flash_attention import flash_attention

        return flash_attention(q, k, v, True)
    if cfg.attn_impl in ("ring", "ulysses"):
        from jax.sharding import PartitionSpec as P

        if cfg.attn_impl == "ring":
            from substratus_tpu.ops.ring_attention import ring_attention as fn
        else:
            from substratus_tpu.ops.ulysses_attention import (
                ulysses_attention as fn,
            )

        spec = P(None, "sequence", None, None)
        sharded = jax.shard_map(
            lambda q, k, v: fn(q, k, v, axis_name="sequence"),
            in_specs=(spec, spec, spec),
            out_specs=spec,
            axis_names={"sequence"},
        )
        return sharded(q, k, v)
    return dot_product_attention(q, k, v, causal=True, q_positions=positions)


def _moe_ffn(
    h: jnp.ndarray,  # [B, S, D] (post-norm)
    lp: Params,
    cfg: LlamaConfig,
    train: bool,
    lora: Optional[Params] = None,  # per-layer adapters (may hold expert-
    # routed pairs a [E, in, r] / b [E, r, out], train/lora.py)
    lora_scale: float = 1.0,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Routed top-k expert FFN (Mixtral-style).

    Two execution strategies, same routing:

    * train=True: GShard-style capacity dispatch — dense one-hot dispatch/
      combine einsums keep shapes static, the expert dim shards over the
      "expert" mesh axis (XLA inserts the expert-parallel all-to-alls), and
      tokens beyond an expert's capacity drop (combine weight 0) — the
      standard trade for static shapes at training batch sizes.
    * train=False: exact dropless top-k — every expert computed for every
      token, mixed by routing weights. E/k more FLOPs than dispatch, but
      decode is HBM-bandwidth-bound (all expert weights stream from HBM
      regardless of routing), and exactness makes prefill and cached decode
      consistent — capacity dropping would make them diverge. (With many
      small experts that product is the larger part of a prefill chunk:
      models/exaone_moe.py::_experts_grouped multiplies pairs sorted by
      expert instead, still dropless, and holds a share of the experts.)

    Returns (output [B,S,D], load-balancing aux scalar).
    """
    dt = cfg.dtype
    b, s, d = h.shape
    E, k = cfg.n_experts, cfg.n_experts_per_token
    lora = lora or {}

    qe = qeinsum_w8a8 if cfg.quant_activations else qeinsum

    def eproj(name, x, eq_w, eq_a, eq_b):
        """Per-expert projection with optional expert-routed LoRA delta."""
        out = qe(eq_w, x, lp[name], dt)
        if name in lora:
            down = jnp.einsum(eq_a, x, lora[name]["a"].astype(dt))
            out = out + jnp.einsum(
                eq_b, down, lora[name]["b"].astype(dt)
            ) * lora_scale
        return out

    with jax.named_scope(scopes.MOE_ROUTER):
        logits = jnp.einsum(
            "bsd,de->bse", h.astype(jnp.float32),
            materialize(lp["router"], jnp.float32),
        )
        probs = jax.nn.softmax(logits, axis=-1)  # [B,S,E]
        top_w, top_idx = jax.lax.top_k(probs, k)  # [B,S,k]
        top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)  # Mixtral renorm

        # Switch-style load-balancing aux: fraction of tokens routed to each
        # expert (top-1 assignment) x mean router prob, scaled by E.
        assigned = jax.nn.one_hot(top_idx[..., 0], E, dtype=jnp.float32)
        aux = jnp.sum(
            assigned.mean(axis=(0, 1)) * probs.mean(axis=(0, 1))
        ) * E

    if not train:
        with jax.named_scope(scopes.MOE_ROUTER):
            # Exact dropless mix: per-token expert weights [B,S,E].
            w_full = jnp.sum(
                jax.nn.one_hot(top_idx, E, dtype=jnp.float32)
                * top_w[..., None],
                axis=2,
            )
        with jax.named_scope(scopes.MOE_EXPERTS):
            gate = eproj("w_gate", h, "bsd,edm->bsem", "bsd,edr->bser",
                         "bser,erm->bsem")
            up = eproj("w_up", h, "bsd,edm->bsem", "bsd,edr->bser",
                       "bser,erm->bsem")
            out = eproj("w_down", swiglu(gate, up), "bsem,emd->bsed",
                        "bsem,emr->bser", "bser,erd->bsed")
            y = jnp.einsum("bsed,bse->bsd", out, w_full.astype(dt))
        return y.astype(dt), aux

    t = s * k
    capacity = max(1, int(cfg.capacity_factor * s * k / E))
    with jax.named_scope(scopes.MOE_ROUTER):
        # Flatten (token, choice) pairs; compute each pair's slot within
        # its expert's capacity buffer.
        onehot = jax.nn.one_hot(top_idx, E, dtype=jnp.float32)  # [B,S,k,E]
        flat = onehot.reshape(b, t, E)
        pos = jnp.cumsum(flat, axis=1) - flat  # arrival order per expert
        keep = (pos < capacity).astype(jnp.float32) * flat  # [B,T,E]
        dispatch = keep[..., None] * jax.nn.one_hot(
            pos.astype(jnp.int32), capacity, dtype=jnp.float32
        )  # [B,T,E,C]
        combine = dispatch * top_w.reshape(b, t)[..., None, None]

    with jax.named_scope(scopes.MOE_EXPERTS):
        h_rep = jnp.repeat(h, k, axis=1)  # [B,T,D] (token order matches flatten)
        expert_in = jnp.einsum(
            "btec,btd->ebcd", dispatch.astype(dt), h_rep
        )  # [E,B,C,D]
        gate = eproj("w_gate", expert_in, "ebcd,edm->ebcm", "ebcd,edr->ebcr",
                     "ebcr,erm->ebcm")
        up = eproj("w_up", expert_in, "ebcd,edm->ebcm", "ebcd,edr->ebcr",
                   "ebcr,erm->ebcm")
        out = eproj("w_down", swiglu(gate, up), "ebcm,emd->ebcd",
                    "ebcm,emr->ebcr", "ebcr,erd->ebcd")
        y = jnp.einsum("ebcd,btec->btd", out, combine.astype(dt))  # [B,T,D]
        y = y.reshape(b, s, k, d).sum(axis=2)
    return y.astype(dt), aux


def _block(
    x: jnp.ndarray,  # [B, S, D]
    lp: Params,  # single-layer params (leading L axis removed by scan)
    positions: jnp.ndarray,  # [B, S]
    cfg: LlamaConfig,
    layer_cache: Optional[Params],  # this layer's cache rows (k, v,
    # [scales]); with a block_table, the whole stacked pool
    kv_length: Optional[jnp.ndarray] = None,  # [B] valid cache prefix
    lora_layers: Optional[Params] = None,  # single-layer adapter tree
    lora_scale: float = 1.0,
    train: bool = False,
    block_table: Optional[jnp.ndarray] = None,  # [B, M]: paged cache layout
    adapter_ids: Optional[jnp.ndarray] = None,  # [B]: slot-stacked adapters
    layer: Optional[jnp.ndarray] = None,  # this block's index (paged only)
) -> Tuple[jnp.ndarray, Params, jnp.ndarray]:
    """One transformer block. Returns (x_out, kv_out, aux): kv_out is a dict
    of either the freshly computed seq entries {k, v} (no cache: training /
    prefill), the updated full cache rows (dense decode — including
    k_scale/v_scale when the cache is int8-quantized) or the stacked paged
    pool with this layer's rows written in place; aux is the MoE
    load-balancing loss (0 for dense layers).

    With adapter_ids, the lora leaves carry a leading adapter-slot axis
    (serve/adapters.py stacks N tenants' adapters) and every row gathers
    its own pair — one dispatch serves a mixed-tenant batch."""
    dt = cfg.dtype
    lora = lora_layers or {}

    qe = qeinsum_w8a8 if cfg.quant_activations else qeinsum

    def proj(name: str, inp: jnp.ndarray, eq: str, lora_eq: str) -> jnp.ndarray:
        if name in _HEADS_LAST and _served(lp[name]):
            eq = "bsd,hkd->bshk"
        out = qe(eq, inp, lp[name], dt)
        if name in lora:
            if adapter_ids is not None:
                out = out + lora_delta_indexed(
                    inp, lora[name], lora_scale, lora_eq, adapter_ids
                )
            else:
                out = out + lora_delta(inp, lora[name], lora_scale, lora_eq)
        return out

    with jax.named_scope(scopes.NORM):
        h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
    with jax.named_scope(scopes.ATTN_QKV):
        q = proj("wq", h, "bsd,dhk->bshk", "bsr,rhk->bshk")
        kk = proj("wk", h, "bsd,dhk->bshk", "bsr,rhk->bshk")
        vv = proj("wv", h, "bsd,dhk->bshk", "bsr,rhk->bshk")
        q = rope(q, positions, cfg.rope_theta)
        kk = rope(kk, positions, cfg.rope_theta)

    if layer_cache is None:
        with jax.named_scope(scopes.ATTN_CORE):
            attn = _self_attention(q, kk, vv, positions, cfg)
        kv_out = {"k": kk, "v": vv}
    elif block_table is not None:
        from substratus_tpu.ops.kvcache import paged_attention

        kv_out, attn = paged_attention(
            layer_cache, layer, block_table, positions, q, kk, vv, dt,
            kv_length,
        )
    else:
        from substratus_tpu.ops.decode_attention import update_cache_and_attend

        attn, kv_out = update_cache_and_attend(
            layer_cache, q, kk, vv, positions, kv_length=kv_length,
        )

    b, s = x.shape[:2]
    with jax.named_scope(scopes.ATTN_OUT):
        attn_flat = attn.reshape(b, s, -1)
        o = qeinsum("bshk,hkd->bsd", attn, lp["wo"], dt)
        if "wo" in lora:
            if adapter_ids is not None:
                o = o + lora_delta_indexed(
                    attn_flat, lora["wo"], lora_scale, "bsr,rd->bsd",
                    adapter_ids,
                )
            else:
                o = o + lora_delta(
                    attn_flat, lora["wo"], lora_scale, "bsr,rd->bsd"
                )
        x = x + o
    with jax.named_scope(scopes.NORM):
        h = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
    if cfg.n_experts > 0:
        y, aux = _moe_ffn(h, lp, cfg, train, lora, lora_scale)
        with jax.named_scope(scopes.MOE_EXPERTS):
            x = x + y
    else:
        with jax.named_scope(scopes.MLP):
            gate = proj("w_gate", h, "bsd,dm->bsm", "bsr,rm->bsm")
            up = proj("w_up", h, "bsd,dm->bsm", "bsr,rm->bsm")
            x = x + proj(
                "w_down", swiglu(gate, up), "bsm,md->bsd", "bsr,rd->bsd"
            )
        aux = jnp.zeros((), jnp.float32)
    return x, kv_out, aux


def forward(
    params: Params,
    tokens: jnp.ndarray,  # [B, S] int32
    cfg: LlamaConfig,
    *,
    positions: Optional[jnp.ndarray] = None,  # [B, S] absolute positions
    cache: Optional[Params] = None,  # decode cache from init_cache (dense)
    # or init_paged_cache (pass block_table too)
    block_table: Optional[jnp.ndarray] = None,  # [B, M] page ids: selects
    # the paged cache layout (ops/kvcache.py)
    kv_length: Optional[jnp.ndarray] = None,  # [B] valid cache prefix; use
    # when slots <= position may hold stale data (e.g. resumed caches)
    lora: Optional[Params] = None,  # adapter tree from train.lora.init_lora
    adapter_ids: Optional[jnp.ndarray] = None,  # [B] int32 — lora leaves
    # carry a leading adapter-slot axis and each row gathers its own pair
    # (multi-tenant serving; serve/adapters.py::AdapterStore.device_tree)
    remat: bool = False,  # rematerialize each block (training memory saver)
    train: bool = False,  # MoE: capacity dispatch (train) vs exact (infer)
) -> Tuple[jnp.ndarray, Params]:
    """Returns (logits [B, S, vocab], kv).

    Without cache: training/prefill; kv = fresh entries [L, B, S, KH, hd]
    (a cache fragment the serving engine can insert into a decode cache).
    With cache: decode/continued generation; tokens are written at
    `positions` (per-row) and attention runs over the full cache; kv = the
    updated cache.
    """
    B, S = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))

    with jax.named_scope(scopes.EMBED):
        x = materialize(params["tok_embed"], cfg.dtype)[tokens]

    lora_scale = lora["scale"] if lora is not None else 1.0

    # A paged pool is the scan's carry: one buffer from the donated argument
    # to the result, written in place at each layer's offset. A dense slot
    # cache is sliced per layer (xs) and re-stacked (ys).
    paged = cache is not None and block_table is not None

    def body(carry, layer_in):
        x, pool = carry
        x_out, kv, aux = _block(
            x,
            layer_in["lp"],
            positions,
            cfg,
            layer_in.get("cache", pool),
            kv_length,
            layer_in.get("lora"),
            lora_scale,
            train,
            block_table,
            adapter_ids,
            layer_in.get("layer"),
        )
        if paged:
            return (x_out, kv), {"aux": aux}
        return (x_out, None), {"kv": kv, "aux": aux}

    xs: Dict[str, Any] = {"lp": params["layers"]}
    if paged:
        xs["layer"] = jnp.arange(cfg.n_layers, dtype=jnp.int32)
    elif cache is not None:
        xs["cache"] = cache
    if lora is not None:
        xs["lora"] = lora["layers"]
    if remat:
        body = jax.checkpoint(body)
    with jax.named_scope(scopes.LAYERS):
        (x, pool), ys = lax.scan(body, (x, cache if paged else None), xs)

    with jax.named_scope(scopes.LM_HEAD):
        x = rms_norm(x, params["out_norm"], cfg.norm_eps)
        if cfg.tie_embeddings:
            logits = jnp.einsum(
                "bsd,vd->bsv", x, materialize(params["tok_embed"], cfg.dtype)
            )
        else:
            logits = (qeinsum_w8a8 if cfg.quant_activations else qeinsum)(
                "bsd,dv->bsv", x, params["lm_head"], cfg.dtype
            )
        logits = logits.astype(jnp.float32)
    # Same structure as the cache: the carried pool, or the layers' stack.
    kv = pool if paged else ys["kv"]
    if cfg.n_experts > 0 and cache is None:
        # Per-layer router load-balancing losses (training/prefill only —
        # the decode cache must keep a stable structure for buffer
        # donation); the trainer adds router_aux_weight * mean.
        kv["moe_aux"] = ys["aux"]
    return logits, kv


@partial(jax.jit, static_argnames=("cfg",), donate_argnames=("cache",))
def decode_step(
    params: Params,
    cache: Params,
    tokens: jnp.ndarray,  # [B] current token per row
    positions: jnp.ndarray,  # [B] position to write/attend at
    cfg: LlamaConfig,
) -> Tuple[jnp.ndarray, Params]:
    """One greedy-decode-ready step: logits for the next token + updated
    cache. Cache buffer is donated -> updated in place on device."""
    logits, new_cache = forward(
        params, tokens[:, None], cfg, positions=positions[:, None], cache=cache
    )
    return logits[:, 0, :], new_cache
