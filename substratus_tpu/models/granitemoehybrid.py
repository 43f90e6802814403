"""Granite-4.0-H family (Granite-4.0-H-Micro, transformers
`granitemoehybrid` with no experts): Mamba-2 mixers beside a few attention
layers that carry no positions, every layer's feed-forward part one dense
gated MLP, and three multipliers around the residual stream.

D = `dim`; inner width E = H x P (`mamba_n_heads` heads of `mamba_d_head`);
state N = `mamba_d_state`; one group; K = `mamba_d_conv` taps; the
convolution's width W = E + 2 N; `eps` = `norm_eps`:

    x_0 = embedding_multiplier * Emb[token]
    every layer:  r = x + residual_multiplier * Op(RMSNorm(x; input_norm))
                  y = r + residual_multiplier * MLP(RMSNorm(r; post_norm))
    MLP(h) = (silu(h W_gate) * (h W_up)) W_down      (`shared_mlp`; the
        published `input_linear` is [W_gate; W_up] as one matrix)
    logits = RMSNorm(x_L; out_norm) Emb^T / logits_scaling

    Op, `mamba`, token t:
      [z_t (E); u_t (W); d_t (H)] = h_t W_in              no bias
      c_t = silu(sum_{j < K} w[j] * u_{t-K+1+j} + b)      depthwise, causal,
                                                          u_s = 0 for s < 0
      [x_t (H x P); B_t (N); C_t (N)] = c_t
      dt_t = softplus(d_t + dt_bias + dt_shift)   [H], float32
      A = -exp(A_log)                             [H], float32
      S_t = exp(dt_t A) S_{t-1} + (dt_t x_t) B_t^T     [H, P, N] float32,
                                                       S = 0 before position 0
      o_t = S_t C_t + D_skip * x_t                     [H, P]
      g_t = RMSNorm(o_t * silu(z_t); norm)             over all E values
      Op = g_t W_o
    Op, `attention`: q, k, v = h W_q, h W_k, h W_v (no bias, no norm, NO
      rotation: `position_embedding_type` nope); softmax_{s <= t}(q_t . k_s
      * attention_multiplier) in float32; W_o.

ops/ssd.py computes the recurrence in two forms: the recurrent one for a
decode step and the chunked one for a prefill chunk and for the pass
without a cache, which starts from a zero state.

Where each piece lives: the stack is hybrid.layer_plan's scanned periods (a
period of Granite-4.0-H-Micro is ten layers, `mamba` x 5, `attention`,
`mamba` x 4), a layer's weights indexed out of their stacks (`ssm`, `attn`,
`layers`) by number. Three kinds of history share one cache dict: attention
layers write the paged pool (`k`, `v`, two heads of 64 to a stored row:
ops/kvcache.py::paged_attention, as LFM2's), Mamba layers K - 1 rows of `u`
a decode slot (`conv`: [Lm, slots, (K - 1) x W], a slot's rows end to end;
ops/kvcache.py::conv_rows_read_and_update: a decode step shifts the layer's
slab where it lies, a chunk hands its slot's rows to LFM2's
conv_read_and_update) and the state `S` a decode slot, float32
whatever the activations' type (`ssm`: [Lm, slots, N, H x P], ops/ssd.py's
layout; ops/kvcache.py::ssm_read_and_update). The engine says which slot a
batch row is (`slots`; without it row i is slot i: a decode step over every
slot) and which tokens are real (`valid`): a token that is not real has `dt`
= 0, adds nothing to `S`, decays nothing, and never enters the rows.

`paged_attention` takes no scale (the kernels' is head_dim ** -0.5): the
query is multiplied by `attention_multiplier * sqrt(head_dim)` ahead of it,
1 / 8 at the published 1 / 64 over heads of 64, which is exact in bfloat16.

Assumed, the published `modeling_granitemoehybrid.py` not being at hand
(transformers' `bamba` mixer is its source): the order `[z; x B C; dt]` of
`W_in`'s columns and `[x; B; C]` of the convolution's channels; `dt` is not
clamped (`time_step_limit` (0, inf)); the gated norm multiplies by `silu(z)`
before it normalises, over all E values (one group). `dt_shift` is a
constant of the configuration, 0 for a trained tree: it stands for the mean
a trained `dt_bias` carries where a tree's was drawn around zero (the
benchmark's seeded weights), so that the decay `exp(dt A)` sits near 1 and
the state sums many tokens as a trained one does. A stopgap, as
models/brumby.py's `gate_shift`: it goes when the benchmark can seed a bias
with a mean (ROADMAP.md R-B 9).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from substratus_tpu.models import hybrid
from substratus_tpu.ops import kvcache, scopes, ssd
from substratus_tpu.ops.attention import dot_product_attention
from substratus_tpu.ops.basics import rms_norm
from substratus_tpu.ops.quant import materialize, qeinsum, qeinsum_w8a8

Params = Dict[str, Any]

MAMBA, ATTN = "mamba", "attention"

SUPPORTS_INT8_KV = False
SUPPORTS_LORA = False
# The engine may use the paged layout for this family, and only that one.
SUPPORTS_PAGED = True
# The paged cache also holds state addressed by decode slot (the Mamba
# layers' rows and state): init_paged_cache takes `slots`, forward takes
# `slots` and `valid`. The family counts nothing on the device, so it has
# no `step_counters`.
PAGED_SLOT_STATE = True
# Tokens a page of the pool holds where the engine is given no size
# (serve/paged_kv.py::page_tokens): LFM2's stored row, [bs, 4, 128]
# bfloat16, and its reason (models/lfm2_moe.py::PAGE_TOKENS), over contexts
# six times as long. Measured on the chip, `paged_decode_attention` alone
# over the rag cell's shape (48 rows: 17 of 2,048-4,800 tokens, 31 idle), ms
# a layer at pages of 16 / 32 / 64 / 128 tokens (PERF.md section 6, PR 48):
# 0.429 / 0.336 / 0.296 / 0.279 for 7,350 / 3,714 / 1,896 / 990 copies (19-25
# ns a copy over 0.26 ms of folds); in the cell `decode_attn_ms` read 1.480 /
# - / 0.976 / 0.922. 128 reads 5.7 % and 5.6 % under 64, and 256 could buy 3
# % at most. The price: a slot's last page is half empty on average (512 KB
# beside 75 MB of state a slot); the prefix registry is off for this family.
PAGE_TOKENS = 128


@dataclass(frozen=True)
class GraniteHybridConfig:
    vocab_size: int = 100352
    dim: int = 2048
    n_layers: int = 40
    n_heads: int = 32
    n_kv_heads: int = 8
    head_dim: int = 64
    hidden_dim: int = 8192  # `shared_intermediate_size`
    # One entry a layer. None: the published pattern, attention at 5 of 10.
    layer_types: Optional[Tuple[str, ...]] = None
    mamba_n_heads: int = 64
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_d_conv: int = 4
    mamba_n_groups: int = 1
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.015625
    logits_scaling: float = 8.0
    norm_eps: float = 1e-5
    max_seq_len: int = 131072
    tie_embeddings: bool = True
    dt_shift: float = 0.0  # added to dt's pre-activation (docstring)
    dtype: Any = jnp.bfloat16
    # W8A8 (ops/quant.py::qeinsum_w8a8); opt-in, as in LlamaConfig.
    quant_activations: bool = False

    def __post_init__(self):
        if self.layer_types is None:
            object.__setattr__(self, "layer_types", tuple(
                ATTN if i % 10 == 5 else MAMBA for i in range(self.n_layers)))
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        if len(self.layer_types) != self.n_layers:
            raise ValueError("layer_types needs one entry for each of the "
                             f"{self.n_layers} layers")
        if set(self.layer_types) - {MAMBA, ATTN}:
            raise ValueError(f"layer_types {set(self.layer_types)}")
        if self.mamba_n_groups != 1:
            raise ValueError("granitemoehybrid: one group of B and C is "
                             f"written, not {self.mamba_n_groups}")
        if self.mamba_d_conv < 2:
            raise ValueError(f"mamba_d_conv {self.mamba_d_conv}")
        if not self.tie_embeddings:
            raise ValueError(
                "granitemoehybrid ties its output head to the embedding")
        if self.n_heads % self.n_kv_heads:
            raise ValueError(
                f"{self.n_heads} heads over {self.n_kv_heads}")

    @property
    def head_size(self) -> int:
        return self.head_dim

    @property
    def inner(self) -> int:
        """E: the mixer's inner width, heads x values."""
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def conv_dim(self) -> int:
        """W: the channels the convolution runs over, [x; B; C]."""
        return self.inner + 2 * self.mamba_d_state

    def count(self, kind: str) -> int:
        """Layers of a kind (MAMBA or ATTN)."""
        return self.layer_types.count(kind)

    def replace(self, **kw) -> "GraniteHybridConfig":
        return dataclasses.replace(self, **kw)


CONFIGS: Dict[str, GraniteHybridConfig] = {
    # Two periods of `m m a m`; 4 heads of 16 over a state of 8: the
    # state's lanes are 64, its rows 8, the convolution 80 channels wide
    "tiny-granite-hybrid": GraniteHybridConfig(
        vocab_size=256, dim=64, n_layers=8, n_heads=4, n_kv_heads=2,
        head_dim=16, hidden_dim=128,
        layer_types=(MAMBA, MAMBA, ATTN, MAMBA) * 2,
        mamba_n_heads=4, mamba_d_head=16, mamba_d_state=8,
        attention_multiplier=1.0 / 16, max_seq_len=128,
    ),
    "granite-4.0-h-micro": GraniteHybridConfig(),
}


def layer_plan(cfg: GraniteHybridConfig) -> Tuple[int, int, int]:
    """(head, period, periods) of hybrid.layer_plan."""
    return hybrid.layer_plan((kind,) for kind in cfg.layer_types)


# -- parameters ----------------------------------------------------------------

def param_logical_axes(cfg: GraniteHybridConfig) -> Params:
    axes: Params = {
        "tok_embed": ("vocab", "embed"),
        "out_norm": ("embed",),
        "layers": {
            "input_norm": ("layers", "embed"),
            "post_norm": ("layers", "embed"),
            "w_gate": ("layers", "embed", "mlp"),
            "w_up": ("layers", "embed", "mlp"),
            "w_down": ("layers", "mlp", "embed"),
        },
    }
    if cfg.count(MAMBA):
        axes["ssm"] = {
            "w_in": ("layers", "embed", "mlp"),
            "taps": ("layers", None, "mlp"),
            "conv_bias": ("layers", "mlp"),
            "a_log": ("layers", None),
            "d_skip": ("layers", None),
            "dt_bias": ("layers", None),
            "norm": ("layers", "mlp"),
            "w_out": ("layers", "mlp", "embed"),
        }
    if cfg.count(ATTN):
        axes["attn"] = {
            # heads x head_dim is one dim and leads (models/exaone_moe.py)
            "wq": ("layers", "heads", "embed"),
            "wk": ("layers", "kv_heads", "embed"),
            "wv": ("layers", "kv_heads", "embed"),
            "wo": ("layers", "heads", "embed"),
        }
    return axes


def quant_contracting(cfg: GraniteHybridConfig) -> Params:
    """Contracting dims of the stacked leaves for ops.quant.quantize_params;
    () = kept dense (embedding, norms, taps and their bias, the three
    float32 vectors a head)."""
    q: Params = {
        "tok_embed": (), "out_norm": (),
        "layers": {"input_norm": (), "post_norm": (),
                   "w_gate": (1,), "w_up": (1,), "w_down": (1,)},
    }
    if cfg.count(MAMBA):
        q["ssm"] = {"w_in": (1,), "taps": (), "conv_bias": (), "a_log": (),
                    "d_skip": (), "dt_bias": (), "norm": (), "w_out": (1,)}
    if cfg.count(ATTN):
        q["attn"] = {"wq": (2,), "wk": (2,), "wv": (2,), "wo": (1,)}
    return q


def init_params(cfg: GraniteHybridConfig, key: jax.Array) -> Params:
    """Random init, fan-in scaled; every stack's layer dim leads. `dt_bias`
    and `A_log` are drawn as Mamba-2 initialises them (`dt` log-uniform in
    0.001-0.1 through the inverse of softplus, `A` uniform in 1-16), so the
    decay is 0.2-0.999 and a test can tell a broken carry from a sound one;
    `D` around 1. The attention projections are stored as
    models/exaone_moe.py stores them; the taps [K, W], tap j of the
    equations row j."""
    k = iter(jax.random.split(key, 24))

    def dense(shape, fan_in):
        return (jax.random.truncated_normal(next(k), -2, 2, shape, jnp.float32)
                * fan_in ** -0.5).astype(cfg.dtype)

    L, D, H, KH, hd, M = (cfg.n_layers, cfg.dim, cfg.n_heads, cfg.n_kv_heads,
                          cfg.head_dim, cfg.hidden_dim)
    Lm, La = cfg.count(MAMBA), cfg.count(ATTN)
    E, W, Hm, T = cfg.inner, cfg.conv_dim, cfg.mamba_n_heads, cfg.mamba_d_conv
    params: Params = {
        "tok_embed": dense((cfg.vocab_size, D), D),
        "out_norm": jnp.ones((D,), cfg.dtype),
        "layers": {
            "input_norm": jnp.ones((L, D), cfg.dtype),
            "post_norm": jnp.ones((L, D), cfg.dtype),
            "w_gate": dense((L, D, M), D),
            "w_up": dense((L, D, M), D),
            "w_down": dense((L, M, D), M),
        },
    }
    if Lm:
        dt = jnp.exp(jax.random.uniform(
            next(k), (Lm, Hm), jnp.float32, jnp.log(1e-3), jnp.log(1e-1)))
        params["ssm"] = {
            "w_in": dense((Lm, D, E + W + Hm), D),
            "taps": dense((Lm, T, W), T),
            "conv_bias": 0.1 * jax.random.normal(
                next(k), (Lm, W), jnp.float32),
            "a_log": jnp.log(jax.random.uniform(
                next(k), (Lm, Hm), jnp.float32, 1.0, 16.0)),
            "d_skip": 1.0 + 0.1 * jax.random.normal(
                next(k), (Lm, Hm), jnp.float32),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),  # softplus^-1(dt)
            "norm": jnp.ones((Lm, E), cfg.dtype),
            "w_out": dense((Lm, E, D), E),
        }
    if La:
        params["attn"] = {
            "wq": dense((La, H * hd, D), D),
            "wk": dense((La, KH * hd, D), D),
            "wv": dense((La, KH * hd, D), D),
            "wo": dense((La, H * hd, D), H * hd),
        }
    return params


def init_paged_cache(cfg: GraniteHybridConfig, pages: int, page_size: int,
                     dtype=None, slots: int = 1, kv_shards: int = 1) -> Params:
    """The attention layers' page pool (`k`, `v`: [La, P, bs, KH, hd],
    stored two heads of 64 to a row of 128: ops/kvcache.py), the Mamba
    layers' rows (`conv`: [Lm, slots, (K - 1) x W]) and their state (`ssm`:
    [Lm, slots, N, H x P], float32), one dict."""
    dtype = dtype or cfg.dtype
    if dtype == jnp.int8:
        raise ValueError("granitemoehybrid keeps no int8 KV cache")
    cache = kvcache.init_paged_cache(
        max(cfg.count(ATTN), 1), pages, page_size, cfg.n_kv_heads,
        cfg.head_dim, dtype, kv_shards=kv_shards)
    lm = max(cfg.count(MAMBA), 1)
    rows = kvcache.init_conv_state(
        lm, slots, cfg.mamba_d_conv, cfg.conv_dim, dtype)[kvcache.CONV_STATE]
    cache[kvcache.CONV_STATE] = rows.reshape(lm, slots, -1)
    cache.update(kvcache.init_ssm_state(
        lm, slots, cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state))
    return cache


def paged_cache_logical_axes(cfg: GraniteHybridConfig,
                             quantized: bool = False) -> Params:
    return {**kvcache.paged_cache_logical_axes(False),
            kvcache.CONV_STATE: ("layers", None, None),
            **kvcache.ssm_state_logical_axes()}


# -- the block -----------------------------------------------------------------

def _mixer(h, sp, idx, positions, cfg, cache, slots, valid, qe):
    """The Mamba-2 mixer of one layer; sp its `ssm` leaves, idx its index
    among the Mamba layers. Returns (Op(h), cache)."""
    dt_, taps = cfg.dtype, cfg.mamba_d_conv
    bsz, s = h.shape[:2]
    E, N, Hm, P = (cfg.inner, cfg.mamba_d_state, cfg.mamba_n_heads,
                   cfg.mamba_d_head)
    with jax.named_scope(scopes.SSM_IN):
        z, u, d = jnp.split(qe("bsd,dn->bsn", h, sp["w_in"], dt_),
                            [E, E + cfg.conv_dim], axis=-1)
    if cache is None:
        # the whole sequence from position 0: nothing comes before it
        ctx = jnp.pad(u, ((0, 0), (taps - 1, 0), (0, 0)))
    else:
        state, ctx = kvcache.conv_rows_read_and_update(
            cache[kvcache.CONV_STATE], idx, slots, positions, valid, u)
        cache = {**cache, kvcache.CONV_STATE: state}
    with jax.named_scope(scopes.CONV_STATE):
        w = sp["taps"].astype(jnp.float32)  # [K, W]
        c = sum(ctx[:, j:j + s].astype(jnp.float32) * w[j]
                for j in range(taps))
        c = jax.nn.silu(c + sp["conv_bias"].astype(jnp.float32)).astype(dt_)
        x, b, cc = jnp.split(c, [E, E + N], axis=-1)
        x = x.reshape(bsz, s, Hm, P)
    with jax.named_scope(scopes.SSM_STATE):
        step = jax.nn.softplus(
            d.astype(jnp.float32) + sp["dt_bias"].astype(jnp.float32)
            + cfg.dt_shift)
        a_log = sp["a_log"].astype(jnp.float32)
        d_skip = sp["d_skip"].astype(jnp.float32)
    if cache is None:
        with jax.named_scope(scopes.SSM_STATE):
            _, o = ssd.chunk(None, x, b, cc,
                             jnp.where(valid[..., None], step, 0.0), a_log,
                             d_skip)
    else:
        state, o = kvcache.ssm_read_and_update(
            cache[kvcache.SSM_STATE], idx, slots, positions, valid, x, b, cc,
            step, a_log, d_skip)
        cache = {**cache, kvcache.SSM_STATE: state}
    with jax.named_scope(scopes.SSM_OUT):
        g = o.reshape(bsz, s, E) * jax.nn.silu(z.astype(jnp.float32))
        g = rms_norm(g, sp["norm"], cfg.norm_eps).astype(dt_)
        return qe("bsn,nd->bsd", g, sp["w_out"], dt_), cache


def _attention(h, ap, idx, positions, cfg, cache, block_table, qe):
    """Grouped causal attention of one layer over the paged pool, with no
    positions in it; ap its `attn` leaves, idx its index among the
    attention layers."""
    dt = cfg.dtype
    with jax.named_scope(scopes.ATTN_QKV):
        q = hybrid.heads_proj(h, ap["wq"], cfg.n_heads, qe, dt)
        kk = hybrid.heads_proj(h, ap["wk"], cfg.n_kv_heads, qe, dt)
        vv = hybrid.heads_proj(h, ap["wv"], cfg.n_kv_heads, qe, dt)
        # the attention ahead scales by head_dim ** -0.5 (module docstring)
        q = q * jnp.asarray(
            cfg.attention_multiplier * cfg.head_dim ** 0.5, dt)
    if cache is None:
        # the whole sequence in order: row t sees rows 0 .. t, and no
        # position enters anything
        with jax.named_scope(scopes.ATTN_CORE):
            attn = dot_product_attention(q, kk, vv, causal=True)
    else:
        pool, attn = kvcache.paged_attention(
            {"k": cache["k"], "v": cache["v"]}, idx, block_table, positions,
            q, kk, vv, dt)
        cache = {**cache, **pool}
    with jax.named_scope(scopes.ATTN_OUT):
        return hybrid.out_proj(attn, ap["wo"], dt), cache


def _block(x, lp, op, kind, positions, cfg, cache, block_table, slots,
           valid):
    """One layer. kind its operator's, static; op = (the stack of its
    operator's layers, its index among them, traced: the index into the
    cache's stacks too)."""
    dt = cfg.dtype
    qe = qeinsum_w8a8 if cfg.quant_activations else qeinsum
    res = jnp.asarray(cfg.residual_multiplier, dt)
    with jax.named_scope(scopes.NORM):
        h = rms_norm(x, lp["input_norm"], cfg.norm_eps)
    weights = hybrid.take(*op)
    if kind == MAMBA:
        y, cache = _mixer(h, weights, op[1], positions, cfg, cache, slots,
                          valid, qe)
        with jax.named_scope(scopes.SSM_OUT):
            x = x + res * y
    else:
        y, cache = _attention(h, weights, op[1], positions, cfg, cache,
                              block_table, qe)
        with jax.named_scope(scopes.ATTN_OUT):
            x = x + res * y
    with jax.named_scope(scopes.NORM):
        h = rms_norm(x, lp["post_norm"], cfg.norm_eps)
    with jax.named_scope(scopes.MLP):
        x = x + res * hybrid.gated(
            h, lp["w_gate"], lp["w_up"], lp["w_down"], "bsd,dm->bsm",
            "bsm,md->bsd", qe, dt)
    return x, cache


def forward(
    params: Params,
    tokens: jnp.ndarray,  # [B, S] int32
    cfg: GraniteHybridConfig,
    *,
    positions: Optional[jnp.ndarray] = None,  # [B, S] absolute positions
    cache: Optional[Params] = None,  # init_paged_cache's dict
    block_table: Optional[jnp.ndarray] = None,  # [B, M] page ids
    slots: Optional[jnp.ndarray] = None,  # [B] the decode slot of each row
    valid: Optional[jnp.ndarray] = None,  # [B, S] real tokens
) -> Tuple[jnp.ndarray, Params]:
    """Returns (logits [B, S, vocab] float32, cache).

    Without a cache: the whole sequence at once, from position 0 and a zero
    state (tests, a trainer); the dict returned is empty. With one (and its
    block table): tokens are written at `positions`, attention layers into
    the pages of `block_table`, Mamba layers into the rows and the state of
    `slots` (the real tokens of a row lead it; a row whose first token is
    at position 0 starts from zero); without `slots` row i is decode slot i
    (a decode step over every slot)."""
    b, s = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    if valid is None:
        valid = jnp.ones((b, s), bool)
    if cache is not None and block_table is None:
        raise ValueError(
            "granitemoehybrid has a paged cache only: pass block_table")

    with jax.named_scope(scopes.EMBED):
        x = (materialize(params["tok_embed"], cfg.dtype)[tokens]
             * jnp.asarray(cfg.embedding_multiplier, cfg.dtype))

    # the attention layers' projection stacks with their heads a dim of
    # their own, before a layer is sliced off them (models/brumby.py)
    stacks = {MAMBA: params.get("ssm")}
    if cfg.count(ATTN):
        stacks[ATTN] = hybrid.projections_heads_first(
            params["attn"], cfg.n_heads, cfg.n_kv_heads)
    kinds = [(kind,) for kind in cfg.layer_types]

    def layer(carry, j, l, at):
        x, cache = carry
        kind = cfg.layer_types[j]
        return _block(x, hybrid.take(params["layers"], l),
                      (stacks[kind], at(kind)), kind, positions, cfg, cache,
                      block_table, slots, valid)

    x, cache = hybrid.run_stack(kinds, layer, (x, cache))

    with jax.named_scope(scopes.LM_HEAD):
        x = rms_norm(x, params["out_norm"], cfg.norm_eps)
        logits = jnp.einsum(
            "bsd,vd->bsv", x, materialize(params["tok_embed"], cfg.dtype)
        ).astype(jnp.float32) / cfg.logits_scaling
    return logits, ({} if cache is None else cache)


@partial(jax.jit, static_argnames=("cfg",), donate_argnames=("cache",))
def decode_step(params: Params, cache: Params, tokens: jnp.ndarray,
                positions: jnp.ndarray, cfg: GraniteHybridConfig,
                block_table: jnp.ndarray) -> Tuple[jnp.ndarray, Params]:
    """One step for a batch whose row i is decode slot i: next-token logits
    [B, vocab] and the cache, updated in place (donated)."""
    logits, cache = forward(
        params, tokens[:, None], cfg, positions=positions[:, None],
        cache=cache, block_table=block_table)
    return logits[:, 0, :], cache
