"""EXAONE-MoE family (K-EXAONE-236B-A23B, transformers `exaone_moe`).

What this block has that models/llama.py's does not, and where each lives:

  * a stack that is not uniform: `mlp_layer_types` gives leading dense
    layers and then sparse ones, `layer_types` a period of window layers
    and one global layer. `layer_plan` unrolls the layers before the first
    whole period and scans over the periods; a layer's weights are indexed
    out of their stacks by layer number, never sliced off beforehand;
  * two kinds of history in one cache: global layers write the paged pool
    (`k`, `v`: [Lg, P, bs, KH, hd], ops/kvcache.py::paged_attention,
    as Llama), window layers a ring of `sliding_window` rows a decode slot
    (`wk`, `wv`: [Lw, slots, W, KH, hd], ops/kvcache.py::
    ring_read_and_update). The engine says which slot a batch row is
    (`slots`) and which tokens are real (`valid`);
  * RMSNorm over the head dimension of q and k; rotary on window layers
    only (global layers carry no position);
  * sigmoid-routed experts beside a shared one (`_moe`): scores in
    float32, the top k of score + bias chosen, weights the chosen scores
    normalised over all k and scaled. The layer is told which experts it
    holds (`held_experts` = (first, count) of `n_experts`): it routes over
    all of them, computes the part its own experts give, adds the shared
    expert, and passes that partial sum on. With every expert held that is
    the whole layer; with a share it is what one rank of expert
    parallelism computes before the exchange, and no code here stands in
    for the other ranks. Dropless and exact in every path.

The multi-token-prediction layer of the published model (an extra head for
self-drafting) is not part of the forward pass and is not built.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from substratus_tpu.ops import kvcache, scopes
from substratus_tpu.ops.attention import dot_product_attention
from substratus_tpu.ops.basics import rms_norm, rope, swiglu
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
    """(head, period, periods): the first `head` layers run one by one,
    the rest as `periods` repeats of `period` layers, scanned. Of all such
    splits the one that traces the fewest blocks; on a tie the most
    repeats, then the shortest head."""
    kinds = list(zip(cfg.layer_types, cfg.mlp_layer_types))
    n = len(kinds)

    def cost(split):
        head, period, reps = split
        return (head + period, -reps, head)

    best = (n, 0, 0)
    for period in range(1, n + 1):
        for head in range(n - period, -1, -1):
            if head < n - period and kinds[head] != kinds[head + period]:
                break  # a longer run of this period only adds mismatches
            if (n - head) % period == 0:
                best = min(best, (head, period, (n - head) // period),
                           key=cost)
    return best


def _index_of_kind(cfg: ExaoneMoeConfig) -> List[Dict[str, int]]:
    """For every layer, its index within each stack it reads: among the
    window or global layers (cache), among the dense or sparse (MLP)."""
    seen = {WINDOW: 0, GLOBAL: 0, DENSE: 0, SPARSE: 0}
    out = []
    for a, m in zip(cfg.layer_types, cfg.mlp_layer_types):
        out.append({"attn": seen[a], "mlp": seen[m]})
        seen[a] += 1
        seen[m] += 1
    return out


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
    step, outside every region: PERF.md section 6, PR 27). The router bias is drawn, not zero, so
    that it moves the choice in a test."""
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
                     dtype=None, slots: int = 1) -> Params:
    """The global layers' page pool (`k`, `v`: [Lg, P, bs, KH, hd]) and the
    window layers' rings (`wk`, `wv`: [Lw, slots, W, KH, hd]), one dict."""
    dtype = dtype or cfg.dtype
    if dtype == jnp.int8:
        raise ValueError("exaone_moe keeps no int8 KV cache")
    cache = kvcache.init_paged_cache(
        max(cfg.count(GLOBAL), 1), pages, page_size, cfg.n_kv_heads,
        cfg.head_dim, dtype)
    cache.update(kvcache.init_ring_cache(
        max(cfg.count(WINDOW), 1), slots, cfg.sliding_window, cfg.n_kv_heads,
        cfg.head_dim, dtype))
    return cache


def paged_cache_logical_axes(cfg: ExaoneMoeConfig,
                             quantized: bool = False) -> Params:
    return {**kvcache.paged_cache_logical_axes(False),
            **kvcache.ring_cache_logical_axes()}


# -- the block -----------------------------------------------------------------

def _take(tree, i):
    """Layer i of a stack of leaves (QTensor scales ride along)."""
    return jax.tree.map(
        lambda a: lax.dynamic_index_in_dim(a, i, 0, keepdims=False), tree)


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


def route(h, router, bias, cfg: ExaoneMoeConfig):
    """h [T, D] -> (chosen experts [T, k] int32, their weights [T, k]
    float32). The bias moves the choice and never the weight; the weights
    are normalised over all k chosen, held here or not."""
    s = jax.nn.sigmoid(jnp.einsum(
        "td,de->te", h.astype(jnp.float32), materialize(router, jnp.float32)))
    _, idx = lax.top_k(s + bias.astype(jnp.float32), cfg.n_experts_per_token)
    w = jnp.take_along_axis(s, idx, axis=-1)
    if cfg.norm_topk_prob:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return idx.astype(jnp.int32), w * cfg.routed_scaling_factor


_EXPERT_LEAVES = ("w_gate", "w_up", "w_down")
# How a call multiplies its held experts is chosen by its static token
# count alone (`_moe`): up to _EVERY_AT_MOST tokens, every token through
# every held expert; above it, token-expert pairs grouped by expert,
# _BLOCK_ROWS rows of one expert at a time. On a v5e at the published
# widths, 16 held of 128, a layer: 64 tokens 0.99 ms every / 1.19 ms
# grouped, 512 tokens 4.25 / 1.69 (chip run, PR 27; PERF.md §6).
_EVERY_AT_MOST = 64
_BLOCK_ROWS = 64


def _gated(x, gate, up, down, eq_in, eq_out, qe, dt):
    return qe(eq_out, swiglu(qe(eq_in, x, gate, dt), qe(eq_in, x, up, dt)),
              down, dt)


def _experts_every(h, local, w, mp, cfg, qe):
    """Every token through every held expert, mixed by the routing weights
    (zero where a token did not choose the expert)."""
    eh = cfg.held_experts[1]
    mix = jnp.sum(jax.nn.one_hot(local, eh, dtype=jnp.float32)
                  * w[..., None], axis=1)  # [T, Eh]; one_hot(-1) is zero
    out = _gated(h, mp["w_gate"], mp["w_up"], mp["w_down"],
                 "td,edm->tem", "tem,emd->ted", qe, cfg.dtype)
    return jnp.einsum("ted,te->td", out, mix.astype(cfg.dtype))


def _experts_grouped(h, local, w, stack, layer, cfg, qe):
    """Token-expert pairs sorted by expert, each held expert multiplying
    its own rows `_BLOCK_ROWS` at a time: the work follows the pairs that
    landed here, not tokens x held experts. A pair routed elsewhere sorts
    last and is never multiplied. An expert's weights are indexed out of
    the stack of all sparse layers inside the loop, by (layer, expert) at
    once: sliced by layer beforehand, the loop would be handed a copy of
    the layer's every expert."""
    t, k = local.shape
    eh, bm, dt = cfg.held_experts[1], _BLOCK_ROWS, cfg.dtype
    n = t * k
    key = jnp.where(local >= 0, local, eh).reshape(n)
    order = jnp.argsort(key, stable=True)
    tok = (order // k).astype(jnp.int32)  # the token of each sorted pair
    counts = jnp.sum(jax.nn.one_hot(key, eh, dtype=jnp.int32), axis=0)
    ends = jnp.cumsum(counts)
    starts = ends - counts
    blocks = -(-counts // bm)
    block_ends = jnp.cumsum(blocks)
    # block b belongs to expert e_b and starts at sorted row r0_b
    n_max = -(-n // bm) + eh
    b_ids = jnp.arange(n_max, dtype=jnp.int32)
    e_b = jnp.minimum(
        jnp.searchsorted(block_ends, b_ids, side="right"), eh - 1
    ).astype(jnp.int32)
    r0_b = starts[e_b] + (b_ids - (block_ends[e_b] - blocks[e_b])) * bm
    tok_pad = jnp.concatenate([tok, jnp.zeros((bm,), jnp.int32)])

    def one(b, out):
        rows = lax.dynamic_slice_in_dim(tok_pad, r0_b[b], bm)
        we = jax.tree.map(
            lambda a: lax.dynamic_slice(
                a, (layer, e_b[b]) + (0,) * (a.ndim - 2),
                (1, 1) + a.shape[2:]).reshape(a.shape[2:]),
            {name: stack[name] for name in _EXPERT_LEAVES})
        y = _gated(h[rows], we["w_gate"], we["w_up"], we["w_down"],
                   "td,dm->tm", "tm,md->td", qe, dt)
        # rows past this expert's end are the next expert's: its own block
        # overwrites them, and the last expert's spill lands past `ends`
        return lax.dynamic_update_slice_in_dim(out, y, r0_b[b], axis=0)

    out = lax.fori_loop(0, block_ends[-1], one,
                        jnp.zeros((n + bm, h.shape[-1]), dt))
    back = jnp.argsort(order)  # sorted row of pair (token, choice)
    y = out[back].reshape(t, k, -1)
    w = jnp.where(local >= 0, w, 0.0).astype(dt)  # hides the spill too
    return jnp.einsum("tkd,tk->td", y, w)


def _moe(h, stack, layer, cfg: ExaoneMoeConfig, valid, qe):
    """The sparse layer's partial sum over the held experts plus the shared
    expert. h [B, S, D]; `stack` the leaves of every sparse layer, `layer`
    this one's index among them; returns (y [B, S, D], counters)."""
    b, s, d = h.shape
    first, eh = cfg.held_experts
    flat = h.reshape(b * s, d)
    mp = _take({k: v for k, v in stack.items() if k not in _EXPERT_LEAVES},
               layer)
    with jax.named_scope(scopes.MOE_ROUTER):
        idx, w = route(flat, mp["router"], mp["router_bias"], cfg)
        here = (idx >= first) & (idx < first + eh)
        local = jnp.where(here, idx - first, -1)
        real = valid.reshape(b * s, 1)
        per_expert = jnp.sum(
            jax.nn.one_hot(jnp.where(real, local, -1), eh, dtype=jnp.int32),
            axis=(0, 1))
        stats = {
            "moe_pairs_held": jnp.sum(per_expert),
            "moe_pairs_all": jnp.sum(real) * cfg.n_experts_per_token,
            "moe_expert_pairs_max": jnp.max(per_expert),
        }
    with jax.named_scope(scopes.MOE_EXPERTS):
        if b * s > _EVERY_AT_MOST:
            y = _experts_grouped(flat, local, w, stack, layer, cfg, qe)
        else:
            held = _take({k: stack[k] for k in _EXPERT_LEAVES}, layer)
            y = _experts_every(flat, local, w, held, cfg, qe)
    with jax.named_scope(scopes.MOE_SHARED):
        y = y + _gated(flat, mp["shared_gate"], mp["shared_up"],
                       mp["shared_down"], "td,dm->tm", "tm,md->td", qe,
                       cfg.dtype)
    return y.reshape(b, s, d), stats


def _heads_proj(h, w, heads: int, qe, dt):
    """h [B, S, D] through w [heads * hd, D] -> [B, S, heads, hd]."""
    out = qe("bsd,nd->bsn", h, w, dt)
    return out.reshape(out.shape[:2] + (heads, out.shape[-1] // heads))


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
        q = _heads_proj(h, lp["wq"], cfg.n_heads, qe, dt)
        kk = _heads_proj(h, lp["wk"], cfg.n_kv_heads, qe, dt)
        vv = _heads_proj(h, lp["wv"], cfg.n_kv_heads, qe, dt)
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
        flat = attn.reshape(attn.shape[:2] + (-1,))
        x = x + qeinsum("bsn,nd->bsd", flat, lp["wo"], dt)
    with jax.named_scope(scopes.NORM):
        h = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
    if mlp_kind == DENSE:
        mp = _take(*mlp)
        with jax.named_scope(scopes.MLP):
            x = x + _gated(h, mp["w_gate"], mp["w_up"], mp["w_down"],
                           "bsd,dm->bsm", "bsm,md->bsd", qe, dt)
        return x, cache, None
    y, stats = _moe(h, *mlp, cfg, valid, qe)
    with jax.named_scope(scopes.MOE_EXPERTS):
        x = x + y
    return x, cache, stats


def _fold(total, stats):
    if stats is None:
        return total
    return {
        "moe_pairs_held": total["moe_pairs_held"] + stats["moe_pairs_held"],
        "moe_pairs_all": total["moe_pairs_all"] + stats["moe_pairs_all"],
        "moe_expert_pairs_max": jnp.maximum(
            total["moe_expert_pairs_max"], stats["moe_expert_pairs_max"]),
    }


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
    at = _index_of_kind(cfg)
    head, period, reps = layer_plan(cfg)
    zero = jnp.zeros((), jnp.int32)
    stats = {"moe_pairs_held": zero, "moe_pairs_all": zero,
             "moe_expert_pairs_max": zero}

    # how many layers of each kind one period adds to its stacks
    span = kinds[head:head + period]
    per = {kind: sum(kind in pair for pair in span)
           for kind in (WINDOW, GLOBAL, DENSE, SPARSE)}

    def layer(carry, j, i):
        """Layer j, i periods further along (i = 0: layer j itself)."""
        x, cache, stats = carry
        attn_kind, mlp_kind = kinds[j]
        lp = _take(params["layers"], j + i * period)
        mlp = (params["dense" if mlp_kind == DENSE else "moe"],
               at[j]["mlp"] + i * per[mlp_kind])
        x, cache, st = _block(
            x, lp, mlp, kinds[j], at[j]["attn"] + i * per[attn_kind],
            positions, cfg, cache, block_table, slots, valid)
        return x, cache, _fold(stats, st)

    carry = (x, cache, stats)
    with jax.named_scope(scopes.LAYERS):
        for j in range(head):
            carry = layer(carry, j, zero)
        if reps:
            def body(carry, i):
                for j in range(head, head + period):
                    carry = layer(carry, j, i)
                return carry, None

            carry, _ = lax.scan(body, carry,
                                jnp.arange(reps, dtype=jnp.int32))
    x, cache, stats = carry

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
