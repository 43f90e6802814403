"""LFM2-MoE family (LFM2-24B-A2B, transformers `lfm2_moe`).

A block is `r = x + Op(RMSNorm(x; operator_norm))`, `y = r + FFN(RMSNorm(r;
ffn_norm))`, with `eps` = `norm_eps`; `layer_types` says which operator a
layer has, and the first `n_dense_layers` layers have a dense FFN:

  * `conv`, a gated short convolution: `[B, C, X] = split3(h W_in)` (`W_in`
    [D, 3D], no bias); `u = B * X`; `v_t = sum_j w[:, j] * u_{t-(L-1)+j}`
    over the `conv_taps` = L taps, `u_s = 0` for `s < 0` (depthwise,
    causal, no bias); `Op = (C * v) W_out`. Its state is `u` at the L - 1
    positions below the next one;
  * `full_attention`: `q`, `k` RMSNorm over the head dimension (`q_norm`,
    `k_norm`), then rotary on both; `v = h W_v`; grouped causal attention
    with a float32 softmax; `W_o`. No bias anywhere;
  * dense FFN: `(silu(h W_1) * (h W_3)) W_2`, `hidden_dim` wide;
  * sparse FFN (models/hybrid.py::moe, the layer models/exaone_moe.py runs
    too): `s = sigmoid(h W_g)` in float32; the k experts of largest `s +
    expert_bias`; weights `s_e / (sum of the chosen + route_norm_eps)`
    times `routed_scaling_factor`; no shared expert. The layer is told
    which experts it holds (`held_experts`) and passes on their part;
  * after the last layer RMSNorm (`out_norm`), then logits against the
    tied embedding.

Where each piece lives: the stack is hybrid.layer_plan's unrolled head and
scanned periods, a layer's weights indexed out of their stacks (`conv`,
`attn`, `dense`, `moe`) by number. Two kinds of history share one cache
dict: attention layers write the paged pool (`k`, `v`: [La, P, bs, KH, hd],
ops/kvcache.py::paged_attention, as Llama), convolution layers L - 1 rows a
decode slot (`conv`: [Lc, slots, L - 1, D], ops/kvcache.py::
conv_read_and_update). The engine says which slot a batch row is (`slots`)
and which tokens are real (`valid`).

Assumed, the published `modeling_lfm2_moe.py` not being at hand: the order
B, C, X of `W_in`'s thirds, the tied embedding, the normaliser's 1e-6.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from substratus_tpu.models import hybrid
from substratus_tpu.ops import kvcache, scopes
from substratus_tpu.ops.attention import dot_product_attention
from substratus_tpu.ops.basics import rms_norm, rope
from substratus_tpu.ops.quant import materialize, qeinsum, qeinsum_w8a8

Params = Dict[str, Any]

CONV, ATTN = "conv", "full_attention"
DENSE, SPARSE = "dense", "sparse"

SUPPORTS_INT8_KV = False
SUPPORTS_LORA = False
# The engine may use the paged layout for this family, and only that one.
SUPPORTS_PAGED = True
# The paged cache also holds state addressed by decode slot (the
# convolution layers' rows): init_paged_cache takes `slots`, forward takes
# `slots` and `valid`, and `step_counters` takes the step's counters out of
# the cache dict forward returned (serve/engine.py calls it inside its jit,
# before the cache is carried on).
PAGED_SLOT_STATE = True
# Tokens a page of the pool holds where the engine is given no size
# (serve/paged_kv.py::page_tokens). Heads of 64 lie two to a stored row, so
# a page of 16 tokens is [16, 4, 128] bfloat16 = 16 KB, half of what a pool
# of 8 heads of 128 copies at once, and a page's copy costs its issue and
# not its bytes. Measured on the chip, `paged_decode_attention` alone over
# the assist cell's shape (64 rows: 31 of 150-1,030 tokens, 33 idle), ms a
# layer at pages of 16 / 32 / 64 / 128 tokens (PERF.md section 6, PR 48):
# 0.195 / 0.170 / 0.166 / 0.164 for 2,376 / 1,238 / 668 / 382 copies (20-25
# ns a copy over 0.16 ms of folds). 128 reads 1 % under 64, and a slot's
# last page is half empty on average (256 KB at 64).
PAGE_TOKENS = 64
_STEP_STATS = "step_stats"


@dataclass(frozen=True)
class Lfm2MoeConfig:
    vocab_size: int = 65536
    dim: int = 2048
    n_layers: int = 40
    n_heads: int = 32
    n_kv_heads: int = 8
    head_dim: int = 64
    hidden_dim: int = 11776  # a dense layer's FFN width
    moe_hidden_dim: int = 1536  # an expert's width
    n_dense_layers: int = 2  # the leading layers whose FFN is dense
    # The router's width: every expert of the model, held here or not.
    n_experts: int = 64
    n_experts_per_token: int = 4
    n_shared_experts: int = 0  # hybrid.moe reads it: this family has none
    # (first, count): the routed experts this program holds. None: all.
    held_experts: Optional[Tuple[int, int]] = None
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    route_norm_eps: float = 1e-6  # added to the sum the weights divide by
    # One entry a layer. None: the published pattern, conv conv attn conv.
    layer_types: Optional[Tuple[str, ...]] = None
    conv_taps: int = 3  # `conv_L_cache`
    rope_theta: float = 1e6
    norm_eps: float = 1e-5
    max_seq_len: int = 128000
    tie_embeddings: bool = True
    dtype: Any = jnp.bfloat16
    # W8A8 (ops/quant.py::qeinsum_w8a8); opt-in, as in LlamaConfig.
    quant_activations: bool = False

    def __post_init__(self):
        if self.layer_types is None:
            object.__setattr__(self, "layer_types", tuple(
                ATTN if i % 4 == 2 else CONV for i in range(self.n_layers)))
        if self.held_experts is None:
            object.__setattr__(self, "held_experts", (0, self.n_experts))
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        object.__setattr__(self, "held_experts", tuple(self.held_experts))
        if len(self.layer_types) != self.n_layers:
            raise ValueError("layer_types needs one entry for each of the "
                             f"{self.n_layers} layers")
        if set(self.layer_types) - {CONV, ATTN}:
            raise ValueError(f"layer_types {set(self.layer_types)}")
        if not 0 <= self.n_dense_layers <= self.n_layers:
            raise ValueError(f"n_dense_layers {self.n_dense_layers}")
        if self.conv_taps < 2:
            raise ValueError(f"conv_taps {self.conv_taps}")
        if not self.tie_embeddings:
            raise ValueError("lfm2_moe ties its output head to the embedding")
        first, count = self.held_experts
        if not (0 <= first and count >= 1 and first + count <= self.n_experts):
            raise ValueError(f"held_experts {self.held_experts} of "
                             f"{self.n_experts}")

    @property
    def head_size(self) -> int:
        return self.head_dim

    @property
    def mlp_layer_types(self) -> Tuple[str, ...]:
        return tuple(DENSE if i < self.n_dense_layers else SPARSE
                     for i in range(self.n_layers))

    def count(self, kind: str) -> int:
        """Layers of a kind (CONV, ATTN, DENSE or SPARSE)."""
        return (self.layer_types + self.mlp_layer_types).count(kind)

    def replace(self, **kw) -> "Lfm2MoeConfig":
        return dataclasses.replace(self, **kw)


CONFIGS: Dict[str, Lfm2MoeConfig] = {
    # Four periods with two leading dense layers, as the benchmark's cut (a
    # head of four layers, then three scanned periods); all 64 experts at
    # a small width, so the router is the published one.
    "tiny-lfm2-moe": Lfm2MoeConfig(
        vocab_size=256, dim=64, n_layers=16, n_heads=4, n_kv_heads=2,
        head_dim=16, hidden_dim=128, moe_hidden_dim=32, max_seq_len=128,
    ),
    "lfm2-24b-a2b": Lfm2MoeConfig(),
}


def layer_plan(cfg: Lfm2MoeConfig) -> Tuple[int, int, int]:
    """(head, period, periods) of hybrid.layer_plan, a layer's kind being
    its (operator, FFN kind)."""
    return hybrid.layer_plan(zip(cfg.layer_types, cfg.mlp_layer_types))


# -- parameters ----------------------------------------------------------------

def param_logical_axes(cfg: Lfm2MoeConfig) -> Params:
    axes: Params = {
        "tok_embed": ("vocab", "embed"),
        "out_norm": ("embed",),
        "layers": {
            "operator_norm": ("layers", "embed"),
            "ffn_norm": ("layers", "embed"),
        },
    }
    if cfg.count(CONV):
        axes["conv"] = {
            "w_in": ("layers", "embed", "mlp"),
            "taps": ("layers", None, "embed"),
            "w_out": ("layers", "mlp", "embed"),
        }
    if cfg.count(ATTN):
        axes["attn"] = {
            "q_norm": ("layers", "head_dim"),
            "k_norm": ("layers", "head_dim"),
            # heads x head_dim is one dim and leads (models/exaone_moe.py)
            "wq": ("layers", "heads", "embed"),
            "wk": ("layers", "kv_heads", "embed"),
            "wv": ("layers", "kv_heads", "embed"),
            "wo": ("layers", "heads", "embed"),
        }
    if cfg.count(DENSE):
        axes["dense"] = {
            "w_gate": ("layers", "embed", "mlp"),
            "w_up": ("layers", "embed", "mlp"),
            "w_down": ("layers", "mlp", "embed"),
        }
    if cfg.count(SPARSE):
        axes["moe"] = {
            "router": ("layers", "embed", None),
            "router_bias": ("layers", None),
            "w_gate": ("layers", "expert", "embed", "mlp"),
            "w_up": ("layers", "expert", "embed", "mlp"),
            "w_down": ("layers", "expert", "mlp", "embed"),
        }
    return axes


def quant_contracting(cfg: Lfm2MoeConfig) -> Params:
    """Contracting dims of the stacked leaves for ops.quant.quantize_params;
    () = kept dense (embedding, norms, taps, router and its bias)."""
    q: Params = {
        "tok_embed": (), "out_norm": (),
        "layers": {"operator_norm": (), "ffn_norm": ()},
    }
    if cfg.count(CONV):
        q["conv"] = {"w_in": (1,), "taps": (), "w_out": (1,)}
    if cfg.count(ATTN):
        q["attn"] = {"q_norm": (), "k_norm": (),
                     "wq": (2,), "wk": (2,), "wv": (2,), "wo": (1,)}
    if cfg.count(DENSE):
        q["dense"] = {"w_gate": (1,), "w_up": (1,), "w_down": (1,)}
    if cfg.count(SPARSE):
        q["moe"] = {"router": (), "router_bias": (),
                    "w_gate": (2,), "w_up": (2,), "w_down": (2,)}
    return q


def init_params(cfg: Lfm2MoeConfig, key: jax.Array) -> Params:
    """Random init, fan-in scaled; every stack's layer dim leads. The
    attention projections are stored as models/exaone_moe.py stores them
    (q, k, v and the output projection [heads * hd, D]); the taps [L, D],
    tap j of the equations row j. The expert bias is drawn, not zero, so
    that it moves the choice in a test."""
    k = iter(jax.random.split(key, 24))

    def dense(shape, fan_in):
        return (jax.random.truncated_normal(next(k), -2, 2, shape, jnp.float32)
                * fan_in ** -0.5).astype(cfg.dtype)

    L, D, H, KH, hd = (cfg.n_layers, cfg.dim, cfg.n_heads, cfg.n_kv_heads,
                       cfg.head_dim)
    M, Mm, T = cfg.hidden_dim, cfg.moe_hidden_dim, cfg.conv_taps
    Lc, La, Ld, Ls = (cfg.count(kind) for kind in (CONV, ATTN, DENSE, SPARSE))
    Eh = cfg.held_experts[1]
    params: Params = {
        "tok_embed": dense((cfg.vocab_size, D), D),
        "out_norm": jnp.ones((D,), cfg.dtype),
        "layers": {
            "operator_norm": jnp.ones((L, D), cfg.dtype),
            "ffn_norm": jnp.ones((L, D), cfg.dtype),
        },
    }
    if Lc:
        params["conv"] = {
            "w_in": dense((Lc, D, 3 * D), D),
            "taps": dense((Lc, T, D), T),
            "w_out": dense((Lc, D, D), D),
        }
    if La:
        params["attn"] = {
            "q_norm": jnp.ones((La, hd), cfg.dtype),
            "k_norm": jnp.ones((La, hd), cfg.dtype),
            "wq": dense((La, H * hd, D), D),
            "wk": dense((La, KH * hd, D), D),
            "wv": dense((La, KH * hd, D), D),
            "wo": dense((La, H * hd, D), H * hd),
        }
    if Ld:
        params["dense"] = {
            "w_gate": dense((Ld, D, M), D), "w_up": dense((Ld, D, M), D),
            "w_down": dense((Ld, M, D), M),
        }
    if Ls:
        params["moe"] = {
            "router": dense((Ls, D, cfg.n_experts), D),
            "router_bias": 0.1 * jax.random.normal(
                next(k), (Ls, cfg.n_experts), jnp.float32),
            "w_gate": dense((Ls, Eh, D, Mm), D),
            "w_up": dense((Ls, Eh, D, Mm), D),
            "w_down": dense((Ls, Eh, Mm, D), Mm),
        }
    return params


def init_paged_cache(cfg: Lfm2MoeConfig, pages: int, page_size: int,
                     dtype=None, slots: int = 1, kv_shards: int = 1) -> Params:
    """The attention layers' page pool (`k`, `v`: [La, P, bs, KH, hd],
    stored two heads of 64 to a row of 128: ops/kvcache.py) and the
    convolution layers' rows (`conv`: [Lc, slots, L - 1, D]), one dict."""
    dtype = dtype or cfg.dtype
    if dtype == jnp.int8:
        raise ValueError("lfm2_moe keeps no int8 KV cache")
    cache = kvcache.init_paged_cache(
        max(cfg.count(ATTN), 1), pages, page_size, cfg.n_kv_heads,
        cfg.head_dim, dtype, kv_shards=kv_shards)
    cache.update(kvcache.init_conv_state(
        max(cfg.count(CONV), 1), slots, cfg.conv_taps, cfg.dim, dtype))
    return cache


def paged_cache_logical_axes(cfg: Lfm2MoeConfig,
                             quantized: bool = False) -> Params:
    return {**kvcache.paged_cache_logical_axes(False),
            **kvcache.conv_state_logical_axes()}


# -- the block -----------------------------------------------------------------

def _short_conv(h, cp, idx, positions, cfg, cache, slots, valid, qe):
    """The gated short convolution of one layer; cp its `conv` leaves, idx
    its index among the convolution layers. Returns (Op(h), cache)."""
    dt, taps = cfg.dtype, cfg.conv_taps
    s = h.shape[1]
    with jax.named_scope(scopes.CONV_IN):
        b_gate, c_gate, x_in = jnp.split(
            qe("bsd,dn->bsn", h, cp["w_in"], dt), 3, axis=-1)
        u = b_gate * x_in
    if cache is None:
        # the whole sequence from position 0: nothing comes before it
        ctx = jnp.pad(u, ((0, 0), (taps - 1, 0), (0, 0)))
    else:
        state, ctx = kvcache.conv_read_and_update(
            cache[kvcache.CONV_STATE], idx, slots, positions, valid, u)
        cache = {**cache, kvcache.CONV_STATE: state}
    with jax.named_scope(scopes.CONV_STATE):
        w = cp["taps"].astype(jnp.float32)  # [L, D]
        v = sum(ctx[:, j:j + s].astype(jnp.float32) * w[j]
                for j in range(taps)).astype(dt)
    with jax.named_scope(scopes.CONV_OUT):
        return qe("bsd,dn->bsn", c_gate * v, cp["w_out"], dt), cache


def _attention(h, ap, idx, positions, cfg, cache, block_table, qe):
    """Grouped causal attention of one layer over the paged pool; ap its
    `attn` leaves, idx its index among the attention layers."""
    dt = cfg.dtype
    with jax.named_scope(scopes.ATTN_QKV):
        q = hybrid.heads_proj(h, ap["wq"], cfg.n_heads, qe, dt)
        kk = hybrid.heads_proj(h, ap["wk"], cfg.n_kv_heads, qe, dt)
        vv = hybrid.heads_proj(h, ap["wv"], cfg.n_kv_heads, qe, dt)
    with jax.named_scope(scopes.NORM):
        q = rms_norm(q, ap["q_norm"], cfg.norm_eps)
        kk = rms_norm(kk, ap["k_norm"], cfg.norm_eps)
    with jax.named_scope(scopes.ATTN_QKV):
        q = rope(q, positions, cfg.rope_theta)
        kk = rope(kk, positions, cfg.rope_theta)
    if cache is None:
        with jax.named_scope(scopes.ATTN_CORE):
            attn = dot_product_attention(q, kk, vv, causal=True,
                                         q_positions=positions)
    else:
        pool, attn = kvcache.paged_attention(
            {"k": cache["k"], "v": cache["v"]}, idx, block_table, positions,
            q, kk, vv, dt)
        cache = {**cache, **pool}
    with jax.named_scope(scopes.ATTN_OUT):
        flat = attn.reshape(attn.shape[:2] + (-1,))
        return qeinsum("bsn,nd->bsd", flat, ap["wo"], dt), cache


def _block(x, lp, op, ffn, kinds, positions, cfg, cache, block_table, slots,
           valid):
    """One layer. kinds = (operator, FFN kind), static; op = (the stack of
    its operator's layers, its index among them, traced: the index into
    the cache's stack too), ffn likewise for its FFN kind. Returns (x,
    cache, counters of a sparse layer or None)."""
    dt = cfg.dtype
    qe = qeinsum_w8a8 if cfg.quant_activations else qeinsum
    with jax.named_scope(scopes.NORM):
        h = rms_norm(x, lp["operator_norm"], cfg.norm_eps)
    weights = hybrid.take(*op)
    if kinds[0] == CONV:
        y, cache = _short_conv(h, weights, op[1], positions, cfg, cache,
                               slots, valid, qe)
        with jax.named_scope(scopes.CONV_OUT):
            x = x + y
    else:
        y, cache = _attention(h, weights, op[1], positions, cfg, cache,
                              block_table, qe)
        with jax.named_scope(scopes.ATTN_OUT):
            x = x + y
    with jax.named_scope(scopes.NORM):
        h = rms_norm(x, lp["ffn_norm"], cfg.norm_eps)
    if kinds[1] == DENSE:
        mp = hybrid.take(*ffn)
        with jax.named_scope(scopes.MLP):
            x = x + hybrid.gated(h, mp["w_gate"], mp["w_up"], mp["w_down"],
                                 "bsd,dm->bsm", "bsm,md->bsd", qe, dt)
        return x, cache, None
    y, stats = hybrid.moe(h, *ffn, cfg, valid, qe)
    with jax.named_scope(scopes.MOE_EXPERTS):
        x = x + y
    return x, cache, stats


def forward(
    params: Params,
    tokens: jnp.ndarray,  # [B, S] int32
    cfg: Lfm2MoeConfig,
    *,
    positions: Optional[jnp.ndarray] = None,  # [B, S] absolute positions
    cache: Optional[Params] = None,  # init_paged_cache's dict
    block_table: Optional[jnp.ndarray] = None,  # [B, M] page ids
    slots: Optional[jnp.ndarray] = None,  # [B] the decode slot of each row
    valid: Optional[jnp.ndarray] = None,  # [B, S] real tokens
) -> Tuple[jnp.ndarray, Params]:
    """Returns (logits [B, S, vocab] float32, cache).

    Without a cache: the whole sequence at once, from position 0 (tests, a
    trainer); the dict returned is empty. With one (and its block table):
    tokens are written at `positions`, attention layers into the pages of
    `block_table`, convolution layers into the rows of `slots` (the real
    tokens of a row lead it), and the dict returned is the cache with the
    step's counters in it (`step_counters` takes them out): hybrid.COUNTERS
    over the real tokens, summed over the sparse layers (the most pairs of
    one expert by maximum).
    """
    b, s = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    if valid is None:
        valid = jnp.ones((b, s), bool)
    if cache is not None and block_table is None:
        raise ValueError("lfm2_moe has a paged cache only: pass block_table")
    if slots is None:
        slots = jnp.arange(b, dtype=jnp.int32)

    with jax.named_scope(scopes.EMBED):
        x = materialize(params["tok_embed"], cfg.dtype)[tokens]

    kinds = list(zip(cfg.layer_types, cfg.mlp_layer_types))
    stack_of = {CONV: "conv", ATTN: "attn", DENSE: "dense", SPARSE: "moe"}

    def layer(carry, j, l, at):
        x, cache, stats = carry
        op_kind, ffn_kind = kinds[j]
        lp = hybrid.take(params["layers"], l)
        x, cache, st = _block(
            x, lp, (params[stack_of[op_kind]], at(op_kind)),
            (params[stack_of[ffn_kind]], at(ffn_kind)),
            kinds[j], positions, cfg, cache, block_table, slots, valid)
        return x, cache, hybrid.fold(stats, st)

    x, cache, stats = hybrid.run_stack(
        kinds, layer, (x, cache, hybrid.zero_counters()))

    with jax.named_scope(scopes.LM_HEAD):
        x = rms_norm(x, params["out_norm"], cfg.norm_eps)
        logits = jnp.einsum(
            "bsd,vd->bsv", x, materialize(params["tok_embed"], cfg.dtype)
        ).astype(jnp.float32)
    if cache is None:
        return logits, {}
    return logits, {**cache, _STEP_STATS: stats}


def step_counters(cache: Params) -> Params:
    """Takes the counters of the step that made `cache` out of it (in the
    caller's jit: the cache carried on is the one `init_paged_cache` made,
    leaf for leaf) and returns them."""
    return cache.pop(_STEP_STATS)


@partial(jax.jit, static_argnames=("cfg",), donate_argnames=("cache",))
def decode_step(params: Params, cache: Params, tokens: jnp.ndarray,
                positions: jnp.ndarray, cfg: Lfm2MoeConfig,
                block_table: jnp.ndarray) -> Tuple[jnp.ndarray, Params]:
    """One step for a batch whose row i is decode slot i: next-token logits
    [B, vocab] and the cache, updated in place (donated)."""
    logits, cache = forward(
        params, tokens[:, None], cfg, positions=positions[:, None],
        cache=cache, block_table=block_table)
    step_counters(cache)
    return logits[:, 0, :], cache
