"""Brumby family (Brumby-14B-Base, transformers `brumby`): a Qwen3-shaped
decoder whose every layer's operator is power retention, so the model
keeps no keys and values at all.

For layer l with input `x [T, D]`, `eps` = `rms_norm_eps`, H query heads
over KH KV heads of d, power 2:

  * `r = x + Ret(RMSNorm(x; input_norm))`, `y = r + MLP(RMSNorm(r;
    post_norm))`; `MLP = (silu(h W_gate) * (h W_up)) W_down`; no bias
    anywhere but the gate's;
  * `Ret`: `q = rope(RMSNorm_d(h W_q))`, `k = rope(RMSNorm_d(h W_k))` per
    head over its d dimensions (`q_norm`, `k_norm`), `v = h W_v`; `log g =
    log sigmoid(h W_gamma + b_gamma + gate_shift)` in float32, `W_gamma
    [D, KH]`: one gate a KV head and token. Query head i reads KV head
    `i // (H / KH)`. With `s = 1 / sqrt(d)` and `G_t = sum_{r <= t} log
    g_r`: `a_tj = (s q_t . k_j)^2 exp(G_t - G_j)` for `j <= t`, 0 above;
    `o_t = sum_j a_tj v_j / (sum_j a_tj + 1e-6)`; `Ret = concat_heads(o)
    W_o`. ops/retention.py computes it in three forms: this one without a
    cache, the recurrent one for a decode step (`phi(x)`, d (d + 1) / 2
    wide with `phi(q) . phi(k) = (q . k)^2`; a KV head keeps `S [F, d]`
    and `z [F]`: `S_t = g_t S_{t-1} + phi(k_t) v_t^T`, `z_t = g_t z_{t-1}
    + phi(k_t)`, `o_t = phi(s q_t)^T S_t / (phi(s q_t)^T z_t + 1e-6)`) and
    the chunked one for a prefill chunk, whose carry is that state;
  * after the last layer RMSNorm (`out_norm`), then logits against
    `lm_head` (the embedding is not tied).

All layers are of one kind, so hybrid.run_stack scans them. The cache dict
is the paged layout's, and what it holds says what the family keeps:
`ret_s` [L, slots, KH, F, d] and `ret_z` [L, slots, KH, F], float32
whatever the activations' type, addressed by decode slot
(ops/kvcache.py::retention_read_and_update; the engine says which slot a
batch row is, `slots`, and which tokens are real, `valid`), beside a page
pool of no layers (`k`, `v`: [0, P, bs, KH, d], no byte): no attention
reads a page, and memory is slots x state, not tokens x bytes.

Assumed, the catalog's config carrying Qwen3's keys only and the published
`modeling_brumby.py` not being at hand: the power 2; the gate as above, a
KV head wide, with a bias (with `b_gamma` = 0 the equations are those of
a gate without one); the scale `1 / sqrt(d)`; the normaliser (the sum of
the weights + 1e-6); QK RMSNorm and rotary kept from the Qwen3 block the
model was retrained from. `gate_shift` is a constant of the configuration
added to the gate's pre-activation, 0 for a trained tree: it stands for
the mean a trained `b_gamma` carries where a tree's bias was drawn around
zero (the benchmark's seeded weights), so that `g` sits near 1 and the
state carries thousands of tokens as a trained gate's does. A stopgap:
it goes when the benchmark can seed a bias with a mean (ROADMAP.md R-B 9).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from substratus_tpu.models import hybrid
from substratus_tpu.ops import kvcache, retention, scopes
from substratus_tpu.ops.basics import rms_norm, rope
from substratus_tpu.ops.quant import materialize, qeinsum, qeinsum_w8a8

Params = Dict[str, Any]

SUPPORTS_INT8_KV = False
SUPPORTS_LORA = False
# The engine may use the paged layout for this family, and only that one.
SUPPORTS_PAGED = True
# The paged cache holds state addressed by decode slot (and here nothing
# else): init_paged_cache takes `slots`, forward takes `slots` and `valid`.
# The family counts nothing on the device, so it has no `step_counters`.
PAGED_SLOT_STATE = True


@dataclass(frozen=True)
class BrumbyConfig:
    vocab_size: int = 151936
    dim: int = 5120
    n_layers: int = 40
    n_heads: int = 40
    n_kv_heads: int = 8
    head_dim: int = 128
    hidden_dim: int = 17408
    rope_theta: float = 1e6
    norm_eps: float = 1e-6
    max_seq_len: int = 32768
    tie_embeddings: bool = False
    gate_shift: float = 0.0  # added to the gate's pre-activation (docstring)
    dtype: Any = jnp.bfloat16
    # W8A8 (ops/quant.py::qeinsum_w8a8); opt-in, as in LlamaConfig.
    quant_activations: bool = False

    def __post_init__(self):
        if self.tie_embeddings:
            raise ValueError("brumby has an output head of its own")
        if self.n_heads % self.n_kv_heads or self.head_dim % 2:
            raise ValueError(
                f"{self.n_heads} heads over {self.n_kv_heads} of "
                f"{self.head_dim}")

    @property
    def head_size(self) -> int:
        return self.head_dim

    def replace(self, **kw) -> "BrumbyConfig":
        return dataclasses.replace(self, **kw)


CONFIGS: Dict[str, BrumbyConfig] = {
    # heads of 16: phi is 136 wide, a slot's state 4 x 2 x 136 x 17 floats
    "tiny-brumby": BrumbyConfig(
        vocab_size=256, dim=64, n_layers=4, n_heads=4, n_kv_heads=2,
        head_dim=16, hidden_dim=128, max_seq_len=128,
    ),
    "brumby-14b-base": BrumbyConfig(),
}


# -- parameters ----------------------------------------------------------------

def param_logical_axes(cfg: BrumbyConfig) -> Params:
    return {
        "tok_embed": ("vocab", "embed"),
        "out_norm": ("embed",),
        "lm_head": ("embed", "vocab"),
        "layers": {
            "input_norm": ("layers", "embed"),
            "post_norm": ("layers", "embed"),
            "q_norm": ("layers", "head_dim"),
            "k_norm": ("layers", "head_dim"),
            # heads x head_dim is one dim and leads (models/exaone_moe.py)
            "wq": ("layers", "heads", "embed"),
            "wk": ("layers", "kv_heads", "embed"),
            "wv": ("layers", "kv_heads", "embed"),
            "wo": ("layers", "heads", "embed"),
            "w_gamma": ("layers", "embed", "kv_heads"),
            "b_gamma": ("layers", "kv_heads"),
            "w_gate": ("layers", "embed", "mlp"),
            "w_up": ("layers", "embed", "mlp"),
            "w_down": ("layers", "mlp", "embed"),
        },
    }


def quant_contracting(cfg: BrumbyConfig) -> Params:
    """Contracting dims of the stacked leaves for ops.quant.quantize_params;
    () = kept dense (embedding, norms, the gate's projection and bias)."""
    return {
        "tok_embed": (), "out_norm": (), "lm_head": (0,),
        "layers": {
            "input_norm": (), "post_norm": (), "q_norm": (), "k_norm": (),
            "wq": (2,), "wk": (2,), "wv": (2,), "wo": (1,),
            "w_gamma": (), "b_gamma": (),
            "w_gate": (1,), "w_up": (1,), "w_down": (1,),
        },
    }


def init_params(cfg: BrumbyConfig, key: jax.Array) -> Params:
    """Random init, fan-in scaled; the layer dim leads every stacked leaf.
    The gate's bias is drawn uniform in [3, 7] (`g` between 0.953 and
    0.999, as a trained gate's): around zero `g` is about 0.5, the state
    forgets in a few tokens and a test could not tell a broken carry from
    a sound one. The four projections are published flat, [L, heads * hd,
    D] with the contracted dim last (models/exaone_moe.py::init_params);
    `forward` views them [L, heads, hd, D] before it slices a layer off
    (`hybrid.projections_heads_first`), or each program stages the int8
    layer in VMEM before its dot reads it (PERF.md section 6, PR 41)."""
    k = iter(jax.random.split(key, 16))

    def dense(shape, fan_in):
        return (jax.random.truncated_normal(next(k), -2, 2, shape, jnp.float32)
                * fan_in ** -0.5).astype(cfg.dtype)

    L, D, H, KH, hd, M = (cfg.n_layers, cfg.dim, cfg.n_heads, cfg.n_kv_heads,
                          cfg.head_dim, cfg.hidden_dim)
    return {
        "tok_embed": dense((cfg.vocab_size, D), D),
        "out_norm": jnp.ones((D,), cfg.dtype),
        "lm_head": dense((D, cfg.vocab_size), D),
        "layers": {
            "input_norm": jnp.ones((L, D), cfg.dtype),
            "post_norm": jnp.ones((L, D), cfg.dtype),
            "q_norm": jnp.ones((L, hd), cfg.dtype),
            "k_norm": jnp.ones((L, hd), cfg.dtype),
            "wq": dense((L, H * hd, D), D),
            "wk": dense((L, KH * hd, D), D),
            "wv": dense((L, KH * hd, D), D),
            "wo": dense((L, H * hd, D), H * hd),
            "w_gamma": dense((L, D, KH), D),
            "b_gamma": jax.random.uniform(next(k), (L, KH), jnp.float32,
                                          3.0, 7.0),
            "w_gate": dense((L, D, M), D),
            "w_up": dense((L, D, M), D),
            "w_down": dense((L, M, D), M),
        },
    }


def init_paged_cache(cfg: BrumbyConfig, pages: int, page_size: int,
                     dtype=None, slots: int = 1, kv_shards: int = 1) -> Params:
    """The retention layers' state (`ret_s`, `ret_z`: float32, a slot and
    layer) beside a page pool of no layers (`k`, `v`: [0, P, bs, KH, d] of
    the activations' type): the dict says that nothing is kept in pages."""
    dtype = dtype or cfg.dtype
    if dtype == jnp.int8:
        raise ValueError("brumby keeps no KV cache, int8 or other")
    cache = kvcache.init_paged_cache(
        0, pages, page_size, cfg.n_kv_heads, cfg.head_dim, dtype,
        kv_shards=kv_shards)
    cache.update(kvcache.init_retention_state(
        cfg.n_layers, slots, cfg.n_kv_heads, cfg.head_dim, cfg.head_dim))
    return cache


def paged_cache_logical_axes(cfg: BrumbyConfig,
                             quantized: bool = False) -> Params:
    return {**kvcache.paged_cache_logical_axes(False),
            **kvcache.retention_state_logical_axes()}


# -- the block -----------------------------------------------------------------

def _retention(h, lp, l, positions, cfg, cache, slots, valid, qe):
    """The retention operator of layer l; lp its leaves. Returns (Ret(h),
    cache)."""
    dt = cfg.dtype
    with jax.named_scope(scopes.ATTN_QKV):
        q = hybrid.heads_proj(h, lp["wq"], cfg.n_heads, qe, dt)
        k = hybrid.heads_proj(h, lp["wk"], cfg.n_kv_heads, qe, dt)
        v = hybrid.heads_proj(h, lp["wv"], cfg.n_kv_heads, qe, dt)
        gate = jnp.einsum(
            "bsd,dk->bsk", h, materialize(lp["w_gamma"], dt),
            preferred_element_type=jnp.float32)
        log_g = jax.nn.log_sigmoid(
            gate + lp["b_gamma"].astype(jnp.float32) + cfg.gate_shift)
    with jax.named_scope(scopes.NORM):
        q = rms_norm(q, lp["q_norm"], cfg.norm_eps)
        k = rms_norm(k, lp["k_norm"], cfg.norm_eps)
    with jax.named_scope(scopes.ATTN_QKV):
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    if cache is None:
        # the whole sequence from position 0: the attention form, no state
        o = retention.chunk(
            None, q, jnp.where(valid[..., None, None], k, 0), v,
            jnp.where(valid[..., None], log_g, 0.0))
    else:
        s, z, o = kvcache.retention_read_and_update(
            cache[kvcache.RET_S], cache[kvcache.RET_Z], l, slots, positions,
            valid, q, k, v, log_g)
        cache = {**cache, kvcache.RET_S: s, kvcache.RET_Z: z}
    with jax.named_scope(scopes.ATTN_OUT):
        return hybrid.out_proj(o.astype(dt), lp["wo"], dt), cache


def _block(x, lp, l, positions, cfg, cache, slots, valid):
    dt = cfg.dtype
    qe = qeinsum_w8a8 if cfg.quant_activations else qeinsum
    with jax.named_scope(scopes.NORM):
        h = rms_norm(x, lp["input_norm"], cfg.norm_eps)
    y, cache = _retention(h, lp, l, positions, cfg, cache, slots, valid, qe)
    with jax.named_scope(scopes.ATTN_OUT):
        x = x + y
    with jax.named_scope(scopes.NORM):
        h = rms_norm(x, lp["post_norm"], cfg.norm_eps)
    with jax.named_scope(scopes.MLP):
        x = x + hybrid.gated(h, lp["w_gate"], lp["w_up"], lp["w_down"],
                             "bsd,dm->bsm", "bsm,md->bsd", qe, dt)
    return x, cache


def forward(
    params: Params,
    tokens: jnp.ndarray,  # [B, S] int32
    cfg: BrumbyConfig,
    *,
    positions: Optional[jnp.ndarray] = None,  # [B, S] absolute positions
    cache: Optional[Params] = None,  # init_paged_cache's dict
    block_table: Optional[jnp.ndarray] = None,  # [B, M] page ids: not read
    slots: Optional[jnp.ndarray] = None,  # [B] the decode slot of each row
    valid: Optional[jnp.ndarray] = None,  # [B, S] real tokens
) -> Tuple[jnp.ndarray, Params]:
    """Returns (logits [B, S, vocab] float32, cache).

    Without a cache: the whole sequence at once, from position 0 (tests, a
    trainer); the dict returned is empty. With one: the state of slot
    `slots[b]` takes row b's real tokens in order (they lead the row), a
    row whose first token is at position 0 starting from zero; without
    `slots` row i is decode slot i (a decode step over every slot). The
    block table is the paged protocol's and nothing reads it."""
    b, s = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    if valid is None:
        valid = jnp.ones((b, s), bool)

    with jax.named_scope(scopes.EMBED):
        x = materialize(params["tok_embed"], cfg.dtype)[tokens]

    # the four projection stacks with their heads a dim of their own,
    # before a layer is sliced off them (see `init_params`)
    layers = hybrid.projections_heads_first(
        params["layers"], cfg.n_heads, cfg.n_kv_heads)

    def layer(carry, j, l, at):
        x, cache = carry
        return _block(x, hybrid.take(layers, l), l, positions, cfg,
                      cache, slots, valid)

    x, cache = hybrid.run_stack([("retention",)] * cfg.n_layers, layer,
                                (x, cache))

    with jax.named_scope(scopes.LM_HEAD):
        x = rms_norm(x, params["out_norm"], cfg.norm_eps)
        logits = (qeinsum_w8a8 if cfg.quant_activations else qeinsum)(
            "bsd,dv->bsv", x, params["lm_head"], cfg.dtype
        ).astype(jnp.float32)
    return logits, ({} if cache is None else cache)


@partial(jax.jit, static_argnames=("cfg",), donate_argnames=("cache",))
def decode_step(params: Params, cache: Params, tokens: jnp.ndarray,
                positions: jnp.ndarray, cfg: BrumbyConfig,
                block_table: Optional[jnp.ndarray] = None
                ) -> Tuple[jnp.ndarray, Params]:
    """One step for a batch whose row i is decode slot i: next-token logits
    [B, vocab] and the cache, updated in place (donated)."""
    logits, cache = forward(
        params, tokens[:, None], cfg, positions=positions[:, None],
        cache=cache, block_table=block_table)
    return logits[:, 0, :], cache
