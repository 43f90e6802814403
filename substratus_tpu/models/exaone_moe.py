"""EXAONE-MoE family (K-EXAONE-236B-A23B, transformers `exaone_moe`).

What this block has that models/llama.py's does not, and where each lives:

  * a stack that is not uniform: `mlp_layer_types` gives leading dense
    layers and then sparse ones, `layer_types` a period of window layers
    and one global layer. `layer_plan` (models/hybrid.py) unrolls the
    layers before the first whole period and scans over the periods; a
    layer's weights are indexed out of their stacks by layer number, never
    sliced off beforehand;
  * two kinds of history in one cache: global layers write the paged pool
    (`k`, `v`: [Lg, P, bs, KH, hd], ops/kvcache.py::paged_attention,
    as Llama), window layers a ring of `sliding_window` rows a decode slot
    (`wk`, `wv`: [Lw, slots, W, KH, hd], ops/kvcache.py::
    ring_read_and_update). The engine says which slot a batch row is
    (`slots`) and which tokens are real (`valid`);
  * RMSNorm over the head dimension of q and k; rotary on window layers
    only (global layers carry no position);
  * sigmoid-routed experts beside a shared one (models/hybrid.py::moe,
    the layer models/lfm2_moe.py runs too): scores in float32, the top k
    of score + bias chosen, weights the chosen scores normalised over all
    k and scaled. The layer is told which experts it holds
    (`held_experts` = (first, count) of `n_experts`): it routes over all
    of them, computes the part its own experts give, adds the shared
    expert, and passes that partial sum on.

The multi-token-prediction layer of the published model (an extra head for
self-drafting) is not part of the forward pass and is not built.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from substratus_tpu.models import hybrid
from substratus_tpu.models.hybrid import gated as _gated, take as _take
from substratus_tpu.ops import kvcache, scopes
from substratus_tpu.ops.attention import dot_product_attention
from substratus_tpu.ops.basics import rms_norm, rope
from substratus_tpu.ops.quant import materialize, qeinsum, qeinsum_w8a8

Params = Dict[str, Any]

WINDOW, GLOBAL = "sliding_attention", "full_attention"
DENSE, SPARSE = "dense", "sparse"

SUPPORTS_INT8_KV = False
SUPPORTS_LORA = False
# The engine may use the paged layout for this family, and only that one.
SUPPORTS_PAGED = True
# The paged cache also holds state addressed by decode slot (the window
# layers' rings): init_paged_cache takes `slots`, forward takes `slots` and
# `valid`, `slot_rows` says how many rows a slot keeps, and `step_counters`
# takes the step's counters out of the cache dict forward returned
# (serve/engine.py calls it inside its jit, before the cache is carried on).
PAGED_SLOT_STATE = True
_STEP_STATS = "step_stats"


@dataclass(frozen=True)
class ExaoneMoeConfig:
    vocab_size: int = 153600
    dim: int = 6144
    n_layers: int = 48
    n_heads: int = 64
    n_kv_heads: int = 8
    head_dim: int = 128
    hidden_dim: int = 18432  # a dense layer's MLP width
    moe_hidden_dim: int = 2048  # an expert's width, routed or shared
    # The router's width: every expert of the model, held here or not.
    n_experts: int = 128
    n_experts_per_token: int = 8
    n_shared_experts: int = 1
    # (first, count): the routed experts this program holds. None: all.
    held_experts: Optional[Tuple[int, int]] = None
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    route_norm_eps: float = 1e-20  # added to the sum the weights divide by
    # One entry a layer. None: the published pattern, three window layers
    # and a global one, the first layer's MLP dense.
    layer_types: Optional[Tuple[str, ...]] = None
    mlp_layer_types: Optional[Tuple[str, ...]] = None
    sliding_window: int = 128
    rope_theta: float = 1e6
    norm_eps: float = 1e-5
    max_seq_len: int = 262144
    tie_embeddings: bool = False
    dtype: Any = jnp.bfloat16
    # W8A8 (ops/quant.py::qeinsum_w8a8); opt-in, as in LlamaConfig.
    quant_activations: bool = False

    def __post_init__(self):
        if self.layer_types is None:
            object.__setattr__(self, "layer_types", tuple(
                GLOBAL if i % 4 == 3 else WINDOW for i in range(self.n_layers)))
        if self.mlp_layer_types is None:
            object.__setattr__(self, "mlp_layer_types", tuple(
                DENSE if i == 0 else SPARSE for i in range(self.n_layers)))
        if self.held_experts is None:
            object.__setattr__(self, "held_experts", (0, self.n_experts))
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        object.__setattr__(self, "mlp_layer_types", tuple(self.mlp_layer_types))
        object.__setattr__(self, "held_experts", tuple(self.held_experts))
        if not (len(self.layer_types) == len(self.mlp_layer_types)
                == self.n_layers):
            raise ValueError("layer_types and mlp_layer_types need one entry "
                             f"for each of the {self.n_layers} layers")
        if set(self.layer_types) - {WINDOW, GLOBAL}:
            raise ValueError(f"layer_types {set(self.layer_types)}")
        if set(self.mlp_layer_types) - {DENSE, SPARSE}:
            raise ValueError(f"mlp_layer_types {set(self.mlp_layer_types)}")
        first, count = self.held_experts
        if not (0 <= first and count >= 1 and first + count <= self.n_experts):
            raise ValueError(f"held_experts {self.held_experts} of "
                             f"{self.n_experts}")

    @property
    def head_size(self) -> int:
        return self.head_dim

    def count(self, kind: str) -> int:
        """Layers of a kind (WINDOW, GLOBAL, DENSE or SPARSE)."""
        return (self.layer_types + self.mlp_layer_types).count(kind)

    def replace(self, **kw) -> "ExaoneMoeConfig":
        return dataclasses.replace(self, **kw)


CONFIGS: Dict[str, ExaoneMoeConfig] = {
    # Three periods with the first layer dense, as the benchmark's cut (a
    # head of four layers, then two scanned periods); contexts of the tests
    # cross the window of 8 and a prefill chunk.
    "tiny-exaone-moe": ExaoneMoeConfig(
        vocab_size=256, dim=64, n_layers=12, n_heads=4, n_kv_heads=2,
        head_dim=16, hidden_dim=128, moe_hidden_dim=32, n_experts=16,
        n_experts_per_token=4, sliding_window=8, max_seq_len=128,
    ),
    "k-exaone-236b-a23b": ExaoneMoeConfig(),
}


# -- the stack's shape ---------------------------------------------------------

def layer_plan(cfg: ExaoneMoeConfig) -> Tuple[int, int, int]:
    """(head, period, periods) of hybrid.layer_plan, a layer's kind being
    its (attention kind, MLP kind)."""
    return hybrid.layer_plan(zip(cfg.layer_types, cfg.mlp_layer_types))


# -- parameters ----------------------------------------------------------------

def param_logical_axes(cfg: ExaoneMoeConfig) -> Params:
    axes: Params = {
        "tok_embed": ("vocab", "embed"),
        "out_norm": ("embed",),
        "layers": {
            "attn_norm": ("layers", "embed"),
            "mlp_norm": ("layers", "embed"),
            "q_norm": ("layers", "head_dim"),
            "k_norm": ("layers", "head_dim"),
            # heads x head_dim is one dim, and for q, k, v it leads (see
            # init_params): sharding it over "tensor" still splits whole
            # heads
            "wq": ("layers", "heads", "embed"),
            "wk": ("layers", "kv_heads", "embed"),
            "wv": ("layers", "kv_heads", "embed"),
            "wo": ("layers", "heads", "embed"),
        },
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
            "shared_gate": ("layers", "embed", "mlp"),
            "shared_up": ("layers", "embed", "mlp"),
            "shared_down": ("layers", "mlp", "embed"),
        }
    if not cfg.tie_embeddings:
        axes["lm_head"] = ("embed", "vocab")
    return axes


def quant_contracting(cfg: ExaoneMoeConfig) -> Params:
    """Contracting dims of the stacked leaves for ops.quant.quantize_params;
    () = kept dense (norms, embedding, router and its bias)."""
    q: Params = {
        "tok_embed": (), "out_norm": (),
        "layers": {"attn_norm": (), "mlp_norm": (), "q_norm": (), "k_norm": (),
                   "wq": (2,), "wk": (2,), "wv": (2,), "wo": (1,)},
    }
    if cfg.count(DENSE):
        q["dense"] = {"w_gate": (1,), "w_up": (1,), "w_down": (1,)}
    if cfg.count(SPARSE):
        q["moe"] = {"router": (), "router_bias": (),
                    "w_gate": (2,), "w_up": (2,), "w_down": (2,),
                    "shared_gate": (1,), "shared_up": (1,), "shared_down": (1,)}
    if not cfg.tie_embeddings:
        q["lm_head"] = (0,)
    return q


def init_params(cfg: ExaoneMoeConfig, key: jax.Array) -> Params:
    """Random init, fan-in scaled; every stack's layer dim leads. The
    attention projections are stored as the compiler multiplies them: q, k
    and v [heads * hd, D] with the contracted dim last, the output
    projection [heads * hd, D] too. As int8 [D, heads, hd], and as [D,
    heads * hd], the compiler laid every layer's q, k and v weights out
    anew in each program, contracted dim last (2.9 ms of a 30 ms decode
    step, outside every region: PERF.md section 6, PR 27). That is the
    published form, and what the engine holds. It is not yet what a layer's
    dot reads from the stack: sliced by layer from the flat [L, heads * hd,
    D], the int8 q and output weights of every layer were written out anew
    before their dots read them (`layers` 1.94 ms of a 16.5 ms decode step:
    PERF.md section 6, PR 41). `forward` therefore views the four stacks
    [L, heads, hd, D] before it slices them (`hybrid.projections_heads_first`:
    a reshape that moves no byte while 128 divides hd) and multiplies
    "bsd,hkd->bshk" / "bshk,hkd->bsd"; a `Q4Tensor` leaf stays flat. The
    router bias is drawn, not zero, so that it moves the choice in a
    test."""
    k = iter(jax.random.split(key, 24))

    def dense(shape, fan_in):
        return (jax.random.truncated_normal(next(k), -2, 2, shape, jnp.float32)
                * fan_in ** -0.5).astype(cfg.dtype)

    L, D, H, KH, hd = (cfg.n_layers, cfg.dim, cfg.n_heads, cfg.n_kv_heads,
                       cfg.head_dim)
    M, Mm, Ms = (cfg.hidden_dim, cfg.moe_hidden_dim,
                 cfg.moe_hidden_dim * cfg.n_shared_experts)
    Ld, Ls, Eh = cfg.count(DENSE), cfg.count(SPARSE), cfg.held_experts[1]
    params: Params = {
        "tok_embed": dense((cfg.vocab_size, D), D),
        "out_norm": jnp.ones((D,), cfg.dtype),
        "layers": {
            "attn_norm": jnp.ones((L, D), cfg.dtype),
            "mlp_norm": jnp.ones((L, D), cfg.dtype),
            "q_norm": jnp.ones((L, hd), cfg.dtype),
            "k_norm": jnp.ones((L, hd), cfg.dtype),
            "wq": dense((L, H * hd, D), D),
            "wk": dense((L, KH * hd, D), D),
            "wv": dense((L, KH * hd, D), D),
            "wo": dense((L, H * hd, D), H * hd),
        },
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
            "shared_gate": dense((Ls, D, Ms), D),
            "shared_up": dense((Ls, D, Ms), D),
            "shared_down": dense((Ls, Ms, D), Ms),
        }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense((D, cfg.vocab_size), D)
    return params


def init_paged_cache(cfg: ExaoneMoeConfig, pages: int, page_size: int,
                     dtype=None, slots: int = 1, kv_shards: int = 1) -> Params:
    """The global layers' page pool (`k`, `v`: [Lg, P, bs, KH, hd]) and the
    window layers' rings (`wk`, `wv`: [Lw, slots, W, KH, hd]), one dict."""
    dtype = dtype or cfg.dtype
    if dtype == jnp.int8:
        raise ValueError("exaone_moe keeps no int8 KV cache")
    cache = kvcache.init_paged_cache(
        max(cfg.count(GLOBAL), 1), pages, page_size, cfg.n_kv_heads,
        cfg.head_dim, dtype, kv_shards=kv_shards)
    cache.update(kvcache.init_ring_cache(
        max(cfg.count(WINDOW), 1), slots, cfg.sliding_window, cfg.n_kv_heads,
        cfg.head_dim, dtype))
    return cache


def paged_cache_logical_axes(cfg: ExaoneMoeConfig,
                             quantized: bool = False) -> Params:
    return {**kvcache.paged_cache_logical_axes(False),
            **kvcache.ring_cache_logical_axes()}


# -- the block -----------------------------------------------------------------

def window_attention(q, k, v, q_pos, k_pos, window: int):
    """q [B, Sq, H, d] against k/v [B, Sk, KH, d]: key j is visible to
    query i iff 0 <= i - j < window, by absolute positions (k_pos < 0: the
    row holds nothing). float32 softmax, as ops/attention.py."""
    b, sq, h, d = q.shape
    kh = k.shape[2]
    qf = (q.astype(jnp.float32) * d ** -0.5).reshape(b, sq, kh, h // kh, d)
    logits = jnp.einsum("bqkgd,bskd->bkgqs", qf, k.astype(jnp.float32))
    gap = q_pos[:, :, None] - k_pos[:, None, :]  # [B, Sq, Sk]
    seen = (gap >= 0) & (gap < window) & (k_pos[:, None, :] >= 0)
    logits = jnp.where(seen[:, None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v.astype(jnp.float32))
    return out.reshape(b, sq, h, d).astype(q.dtype)


def _block(x, lp, mlp, kinds, idx, positions, cfg, cache, block_table, slots,
           valid):
    """One layer. kinds = (attention kind, MLP kind), static; idx the
    layer's index among its attention kind's cache layers (traced); mlp =
    (the stack of its MLP kind's layers, its index among them).
    Returns (x, cache, counters of a sparse layer or None)."""
    dt = cfg.dtype
    qe = qeinsum_w8a8 if cfg.quant_activations else qeinsum
    attn_kind, mlp_kind = kinds
    with jax.named_scope(scopes.NORM):
        h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
    with jax.named_scope(scopes.ATTN_QKV):
        q = hybrid.heads_proj(h, lp["wq"], cfg.n_heads, qe, dt)
        kk = hybrid.heads_proj(h, lp["wk"], cfg.n_kv_heads, qe, dt)
        vv = hybrid.heads_proj(h, lp["wv"], cfg.n_kv_heads, qe, dt)
    with jax.named_scope(scopes.NORM):
        q = rms_norm(q, lp["q_norm"], cfg.norm_eps)
        kk = rms_norm(kk, lp["k_norm"], cfg.norm_eps)
    if attn_kind == WINDOW:
        with jax.named_scope(scopes.ATTN_QKV):
            q = rope(q, positions, cfg.rope_theta)
            kk = rope(kk, positions, cfg.rope_theta)
        if cache is None:
            k_ctx, v_ctx = kk, vv
            k_pos = jnp.where(valid, positions, -1)
        else:
            ring, k_ctx, v_ctx, k_pos = kvcache.ring_read_and_update(
                {"wk": cache["wk"], "wv": cache["wv"]}, idx, slots,
                positions, valid, kk, vv)
            cache = {**cache, **ring}
        with jax.named_scope(scopes.ATTN_WINDOW):
            attn = window_attention(q, k_ctx, v_ctx, positions, k_pos,
                                    cfg.sliding_window)
    elif cache is None:
        with jax.named_scope(scopes.ATTN_CORE):
            attn = dot_product_attention(q, kk, vv, causal=True,
                                         q_positions=positions)
    else:
        pool, attn = kvcache.paged_attention(
            {"k": cache["k"], "v": cache["v"]}, idx, block_table, positions,
            q, kk, vv, dt)
        cache = {**cache, **pool}
    with jax.named_scope(scopes.ATTN_OUT):
        x = x + hybrid.out_proj(attn, lp["wo"], dt)
    with jax.named_scope(scopes.NORM):
        h = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
    if mlp_kind == DENSE:
        mp = _take(*mlp)
        with jax.named_scope(scopes.MLP):
            x = x + _gated(h, mp["w_gate"], mp["w_up"], mp["w_down"],
                           "bsd,dm->bsm", "bsm,md->bsd", qe, dt)
        return x, cache, None
    y, stats = hybrid.moe(h, *mlp, cfg, valid, qe)
    with jax.named_scope(scopes.MOE_EXPERTS):
        x = x + y
    return x, cache, stats


# Of hybrid.COUNTERS, those this family's forward carries.
_COUNTERS = ("moe_pairs_held", "moe_pairs_all", "moe_expert_pairs_max")


def forward(
    params: Params,
    tokens: jnp.ndarray,  # [B, S] int32
    cfg: ExaoneMoeConfig,
    *,
    positions: Optional[jnp.ndarray] = None,  # [B, S] absolute positions
    cache: Optional[Params] = None,  # init_paged_cache's dict
    block_table: Optional[jnp.ndarray] = None,  # [B, M] page ids
    slots: Optional[jnp.ndarray] = None,  # [B] the decode slot of each row
    valid: Optional[jnp.ndarray] = None,  # [B, S] real tokens
) -> Tuple[jnp.ndarray, Params]:
    """Returns (logits [B, S, vocab] float32, cache).

    Without a cache: the whole sequence at once (tests, a trainer); the
    dict returned is empty. With one (and its block table): tokens are
    written at `positions`, global layers into the pages of `block_table`,
    window layers into the rings of `slots`, and the dict returned is the
    cache with the step's counters in it (`step_counters` takes them out): token-expert pairs of
    the real tokens that landed on a held expert, pairs in all, and the
    most pairs any held expert of any layer received (the mean is
    pairs_held / (sparse layers x held experts)).
    """
    b, s = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    if valid is None:
        valid = jnp.ones((b, s), bool)
    if cache is not None and block_table is None:
        raise ValueError("exaone_moe has a paged cache only: pass block_table")
    if slots is None:
        slots = jnp.arange(b, dtype=jnp.int32)

    with jax.named_scope(scopes.EMBED):
        x = materialize(params["tok_embed"], cfg.dtype)[tokens]

    kinds = list(zip(cfg.layer_types, cfg.mlp_layer_types))
    # The four projection stacks with their heads a dim of their own,
    # before a layer is sliced off them (see `init_params`).
    layers = hybrid.projections_heads_first(
        params["layers"], cfg.n_heads, cfg.n_kv_heads)

    def layer(carry, j, l, at):
        x, cache, stats = carry
        attn_kind, mlp_kind = kinds[j]
        # (the index opaque to the compiler: folded, as layer 0's is in
        # the head, its slices of the four stacks stayed plain copies in
        # `main`, 113 MB a step at the benchmark's cut)
        lp = _take(layers, jax.lax.optimization_barrier(l))
        mlp = (params["dense" if mlp_kind == DENSE else "moe"], at(mlp_kind))
        x, cache, st = _block(
            x, lp, mlp, kinds[j], at(attn_kind), positions, cfg, cache,
            block_table, slots, valid)
        return x, cache, hybrid.fold(stats, st)

    x, cache, stats = hybrid.run_stack(
        kinds, layer, (x, cache, hybrid.zero_counters(_COUNTERS)))

    with jax.named_scope(scopes.LM_HEAD):
        x = rms_norm(x, params["out_norm"], cfg.norm_eps)
        if cfg.tie_embeddings:
            logits = jnp.einsum(
                "bsd,vd->bsv", x, materialize(params["tok_embed"], cfg.dtype))
        else:
            logits = (qeinsum_w8a8 if cfg.quant_activations else qeinsum)(
                "bsd,dv->bsv", x, params["lm_head"], cfg.dtype)
        logits = logits.astype(jnp.float32)
    if cache is None:
        return logits, {}
    return logits, {**cache, _STEP_STATS: stats}


def step_counters(cache: Params) -> Params:
    """Takes the counters of the step that made `cache` out of it (in the
    caller's jit: the cache carried on is the one `init_paged_cache` made,
    leaf for leaf) and returns them."""
    return cache.pop(_STEP_STATS)


def slot_rows(cfg: ExaoneMoeConfig) -> int:
    """Rows of history a window layer keeps for one decode slot."""
    return cfg.sliding_window


@partial(jax.jit, static_argnames=("cfg",), donate_argnames=("cache",))
def decode_step(params: Params, cache: Params, tokens: jnp.ndarray,
                positions: jnp.ndarray, cfg: ExaoneMoeConfig,
                block_table: jnp.ndarray) -> Tuple[jnp.ndarray, Params]:
    """One step for a batch whose row i is decode slot i: next-token logits
    [B, vocab] and the cache, updated in place (donated)."""
    logits, cache = forward(
        params, tokens[:, None], cfg, positions=positions[:, None],
        cache=cache, block_table=block_table)
    step_counters(cache)
    return logits[:, 0, :], cache
