"""DeepSeek-V3 family (transformers `deepseek_v3`; the language model of
`dots_vlm`, dots.vlm1.inst, carries its keys one for one).

The equations. Layer l, input x [T, D], H heads, dn = qk_nope_head_dim,
dr = qk_rope_head_dim, dv = v_head_dim, rq = q_lora_rank, rkv =
kv_lora_rank; r = x + MLA(RMSNorm(x)), y = r + FFN(RMSNorm(r)); no bias
anywhere but the router's.

  MLA on h = RMSNorm(x; attn_norm):
    cq = RMSNorm(h W_DQ; q_a_norm) [rq]; [q_nope_i (dn); q_rope_i (dr)] =
    (cq W_UQ)_i a head; [ckv_raw (rkv); kr_raw (dr)] = h W_DKV; ckv =
    RMSNorm(ckv_raw; kv_a_norm); kr = rope(kr_raw), one for all heads;
    q_rope_i = rope(q_rope_i); [k_nope_i (dn); v_i (dv)] = (ckv W_UKV)_i.
    * expanded: s_tj = scale (q_nope_i,t . k_nope_i,j + q_rope_i,t . kr_j)
      for j <= t, p = softmax_j(s) in float32, o_i,t = sum_j p_tj v_i,j,
      MLA = concat_i(o_i) W_O.
    * absorbed (the same numbers up to rounding): with W_UKV_i = [W_UK_i |
      W_UV_i], qa_i,t = q_nope_i,t W_UK_i^T [rkv]; s_tj = scale (qa_i,t .
      ckv_j + q_rope_i,t . kr_j); ol_i,t = sum_j p_tj ckv_j; o_i,t = ol_i,t
      W_UV_i.
    What a token keeps is [ckv_j; kr_j]: rkv + dr values a layer, after
    the norm and after the rotation, for all H heads.
    scale = (dn + dr)^(-1/2) m^2, m = 0.1 mscale_all_dim ln(factor) + 1
    (`yarn_get_mscale`); the rotary table is YaRN's (ops/basics.py::
    rope_freqs), its own amplitude yarn_get_mscale(factor, mscale) / m,
    which is 1 in every published configuration and is held to 1 here.
  A learned index over what the layer has kept (DeepSeek Sparse
  Attention; transformers `glm_moe_dsa`, GLM-5), where `index_n_heads` > 0.
  Hi = index_n_heads, di = index_head_dim, k = index_topk:
    qi_t,j = (cq_t W_IQ)_j [di], j = 1..Hi; ki_s = LayerNorm(h_s W_IK; g,
    b) [di], one a token; of both the first dr channels rotated at the
    token's position (the MLA's table), the other di - dr not;
    w_t,j = (h_t W_IW)_j Hi^(-1/2) di^(-1/2), float32;
    I_t,s = sum_j w_t,j ReLU(qi_t,j . ki_s) for s <= t, float32;
    S_t = the min(k, t + 1) positions s <= t of largest I_t,s, ties toward
    the lower position; the MLA's softmax runs over s in S_t only.
    What a token keeps is then [ckv; kr] and ki. For t < k the set is
    everything and the layer is plain MLA. Left out of the published
    inference code: the Hadamard rotation of qi and ki (orthogonal: every
    qi . ki is the same number) and their FP8 storage.
  FFN, l < first_k_dense: (silu(h W_gate) * (h W_up)) W_down. Beyond:
    sigmoid-routed experts under a group limit beside a shared one
    (models/hybrid.py::route, ::moe), the layer told which experts it
    holds (`held_experts`), as models/exaone_moe.py.
  After the last layer RMSNorm(x; out_norm), logits against lm_head.

Where each lives. The stack is walked by hybrid.run_stack: the leading
dense layers one by one, the sparse ones under one scan. The cache is the
paged pool alone (`k`: [L, P, bs, 1, w] latent rows; `v`: no layers, or
with an indexer the tokens' index keys [L, P, bs, 1, di];
ops/kvcache.py::init_latent_cache): pages carry everything a sequence has,
so the family keeps no per-slot state. Two paths, chosen by the call's own
shape (ops/kvcache.py::latent_attention): a decode step runs absorbed over
the row's live pages, a chunk expanded, its context's keys and values made
from the pool's latents a block of pages at a time; without a cache the
whole sequence at once, expanded. With an indexer each of the three runs
over every query's own set (ops/sparse_index.py): the decode step reads
the set's rows by position, the other two mask what is outside it.

Departures from the published checkpoints: the rotary pairs are taken
de-interleaved (rotate-half; a checkpoint interleaves them, a permutation
of W_UQ's and W_DKV's rotary channels at load); W_UQ's rows lie in two
leaves (the heads' q_nope, the heads' q_rope) and W_UKV's in two (W_UK_i
[dn, rkv], W_UV_i^T [dv, rkv]), each a head apart, contracted dimension
last, so that both paths read each as it lies (`init_params`). The
multi-token-prediction layer (an extra head for self-drafting) and
dots.vlm1's vision tower are not part of this forward pass and are not
built: requests carry token ids.
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
from substratus_tpu.ops import kvcache, scopes, sparse_index
from substratus_tpu.ops.basics import layer_norm, rms_norm, rope, yarn_mscale
from substratus_tpu.ops.quant import materialize, qeinsum, qeinsum_w8a8

Params = Dict[str, Any]

MLA = "mla"
DENSE, SPARSE = "dense", "sparse"

SUPPORTS_INT8_KV = False
SUPPORTS_LORA = False
# The engine may use the paged layout for this family, and only that one.
# Pages carry everything a sequence has: no PAGED_SLOT_STATE, so the prefix
# registry stays on. What the engine refuses for it at start-up, by name:
# a verify round of speculation would run the chunk's form over every
# slot at once, and the disaggregated roles ship pages as rows of
# head_size; neither is written or tested for a latent row.
SUPPORTS_PAGED = True
SUPPORTS_SPECULATION = False
SUPPORTS_ROLES = False
# Tokens a page of the latent pool holds where the engine is given no size
# (serve/paged_kv.py::page_tokens; a pool of per-head rows keeps 16, or 64
# and 128 where a stored row holds two heads of 64: models/lfm2_moe.py,
# models/granitemoehybrid.py). A token
# keeps 1,280 B a layer here and 256 B of index key, a twentieth of a
# per-head page's, and a page's copy costs its issue and not its bytes.
# Measured on the chip, each kernel alone, ms a layer at pages of 16 / 32 /
# 64 / 128 tokens (PERF.md section 6, PR 43): `latent_decode_attention`
# over 12 rows x 10.7k tokens 0.561 / 0.438 / 0.380 / 0.350 (about 30 ns a
# copy over 0.32 ms of fold and bytes), `index_decode_scores` over 4 x
# 17.2k 0.229 / 0.148 / 0.108 / 0.090. 128 reads 8 % and 17 % under 64,
# and 256 could buy 4 % at most. The price: the prefix registry shares a
# prompt in steps of a page, and a slot's last page is half empty on
# average (1.3 MB of a 16-layer pool).
PAGE_TOKENS = 128
_STEP_STATS = "step_stats"


@dataclass(frozen=True)
class DeepseekV3Config:
    vocab_size: int = 129280
    dim: int = 7168
    n_layers: int = 61
    n_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    hidden_dim: int = 18432  # a dense layer's MLP width
    moe_hidden_dim: int = 2048  # an expert's width, routed or shared
    first_k_dense: int = 3  # leading layers whose FFN is dense
    # The router's width: every expert of the model, held here or not.
    n_experts: int = 256
    n_experts_per_token: int = 8
    n_shared_experts: int = 1
    # The choice is limited to `topk_group` of `n_group` groups of
    # neighbouring experts (models/hybrid.py::route); 1: no limit.
    n_group: int = 8
    topk_group: int = 4
    # (first, count): the routed experts this program holds. None: all.
    held_experts: Optional[Tuple[int, int]] = None
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    route_norm_eps: float = 1e-20  # added to the sum the weights divide by
    rope_theta: float = 1e4
    # YaRN (ops/basics.py::rope_freqs); a factor of 1 or less: plain rotary.
    rope_factor: float = 40.0
    rope_original_max: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 1.0
    norm_eps: float = 1e-6
    # The learned index over a layer's kept rows (the docstring's
    # equations); 0 heads: none, every query attends all it can see.
    index_n_heads: int = 0
    index_head_dim: int = 128
    index_topk: int = 2048
    index_norm_eps: float = 1e-6  # of the key's LayerNorm
    max_seq_len: int = 163840
    tie_embeddings: bool = False
    dtype: Any = jnp.bfloat16
    # W8A8 (ops/quant.py::qeinsum_w8a8); opt-in, as in LlamaConfig.
    quant_activations: bool = False

    def __post_init__(self):
        if self.held_experts is None:
            object.__setattr__(self, "held_experts", (0, self.n_experts))
        object.__setattr__(self, "held_experts", tuple(self.held_experts))
        first, count = self.held_experts
        if not (0 <= first and count >= 1 and first + count <= self.n_experts):
            raise ValueError(f"held_experts {self.held_experts} of "
                             f"{self.n_experts}")
        if not 0 <= self.first_k_dense <= self.n_layers:
            raise ValueError(f"first_k_dense {self.first_k_dense} of "
                             f"{self.n_layers} layers")
        if self.n_experts % self.n_group or not (
                1 <= self.topk_group <= self.n_group):
            raise ValueError(f"{self.n_experts} experts in {self.n_group} "
                             f"groups, {self.topk_group} kept")
        if self.index_n_heads and not (
                self.index_topk >= 1
                and self.qk_rope_head_dim <= self.index_head_dim):
            raise ValueError(
                f"an index of top {self.index_topk} over keys of "
                f"{self.index_head_dim} ({self.qk_rope_head_dim} rotated)")
        if self.rope_factor > 1 and self.rope_mscale != self.rope_mscale_all_dim:
            raise ValueError(
                "a rotary amplitude other than 1 (rope_mscale != "
                "rope_mscale_all_dim) is not written")

    @property
    def head_size(self) -> int:
        """Of a query head: qk_nope_head_dim + qk_rope_head_dim."""
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_row(self) -> int:
        """Values a token keeps a layer, for all heads: [ckv; kr]."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def yarn(self) -> Optional[Tuple[float, int, float, float]]:
        if self.rope_factor <= 1:
            return None
        return (self.rope_factor, self.rope_original_max,
                self.rope_beta_fast, self.rope_beta_slow)

    @property
    def softmax_scale(self) -> float:
        return (self.head_size ** -0.5
                * yarn_mscale(self.rope_factor, self.rope_mscale_all_dim) ** 2)

    @property
    def mlp_layer_types(self) -> Tuple[str, ...]:
        return tuple(DENSE if l < self.first_k_dense else SPARSE
                     for l in range(self.n_layers))

    def count(self, kind: str) -> int:
        """Layers of a kind (MLA, DENSE or SPARSE)."""
        return ((MLA,) * self.n_layers + self.mlp_layer_types).count(kind)

    def replace(self, **kw) -> "DeepseekV3Config":
        return dataclasses.replace(self, **kw)


CONFIGS: Dict[str, DeepseekV3Config] = {
    # Every mechanism at a size the CPU tests run: a dense head of one
    # layer and three scanned sparse ones, 8 experts in 4 groups of which
    # 2 are kept, YaRN stretching 32 positions by 4 (contexts of the tests
    # pass the 32).
    "tiny-deepseek-v3": DeepseekV3Config(
        vocab_size=256, dim=64, n_layers=4, n_heads=4, q_lora_rank=32,
        kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, hidden_dim=128, moe_hidden_dim=32, first_k_dense=1,
        n_experts=8, n_experts_per_token=2, n_group=4, topk_group=2,
        rope_factor=4.0, rope_original_max=32, max_seq_len=256,
    ),
    "deepseek-v3": DeepseekV3Config(),
    # The same block under a learned index that bites at the tests' sizes:
    # 2 index heads of 16, the 8 best of a context kept; no group limit and
    # no YaRN, as the published configuration that has the index (GLM-5).
    "tiny-glm-dsa": DeepseekV3Config(
        vocab_size=256, dim=64, n_layers=4, n_heads=4, q_lora_rank=32,
        kv_lora_rank=16, qk_nope_head_dim=24, qk_rope_head_dim=8,
        v_head_dim=32, hidden_dim=128, moe_hidden_dim=32, first_k_dense=1,
        n_experts=8, n_experts_per_token=2, n_group=1, topk_group=1,
        rope_theta=1e6, rope_factor=1.0, norm_eps=1e-5, max_seq_len=256,
        index_n_heads=2, index_head_dim=16, index_topk=8,
    ),
    "glm-5": DeepseekV3Config(
        vocab_size=154880, dim=6144, n_layers=78, n_heads=64,
        q_lora_rank=2048, kv_lora_rank=512, qk_nope_head_dim=192,
        qk_rope_head_dim=64, v_head_dim=256, hidden_dim=12288,
        moe_hidden_dim=2048, first_k_dense=3, n_experts=256,
        n_experts_per_token=8, n_group=1, topk_group=1, rope_theta=1e6,
        rope_factor=1.0, norm_eps=1e-5, max_seq_len=202752,
        index_n_heads=32, index_head_dim=128, index_topk=2048,
    ),
}


# -- the stack's shape ---------------------------------------------------------

def _kinds(cfg: DeepseekV3Config):
    return [(MLA, kind) for kind in cfg.mlp_layer_types]


def layer_plan(cfg: DeepseekV3Config) -> Tuple[int, int, int]:
    """(head, period, periods) of hybrid.layer_plan: the leading dense
    layers one by one, the sparse ones as one scanned body."""
    return hybrid.layer_plan(_kinds(cfg))


# -- parameters ----------------------------------------------------------------

def param_logical_axes(cfg: DeepseekV3Config) -> Params:
    axes: Params = {
        "tok_embed": ("vocab", "embed"),
        "out_norm": ("embed",),
        "layers": {
            "attn_norm": ("layers", "embed"),
            "mlp_norm": ("layers", "embed"),
            "q_a_norm": ("layers", None),
            "kv_a_norm": ("layers", None),
            # the two down-projections are every head's; the heads are a
            # dim of their own in w_uq_*, w_uk, w_uv, and heads x head size
            # is w_o's leading dim (see init_params)
            "w_dq": ("layers", None, "embed"),
            "w_uq_nope": ("layers", "heads", None, None),
            "w_uq_rope": ("layers", "heads", None, None),
            "w_dkv": ("layers", None, "embed"),
            "w_uk": ("layers", "heads", None, None),
            "w_uv": ("layers", "heads", None, None),
            "w_o": ("layers", "heads", "embed"),
        },
    }
    if cfg.index_n_heads:
        axes["layers"].update({
            # every chip scores every key: nothing of the index shards
            "w_iq": ("layers", None, None, None),
            "w_ik": ("layers", None, "embed"),
            "w_iw": ("layers", None, "embed"),
            "ik_norm": ("layers", None),
            "ik_norm_bias": ("layers", None),
        })
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


def quant_contracting(cfg: DeepseekV3Config) -> Params:
    """Contracting dims of the stacked leaves for ops.quant.quantize_params;
    () = kept dense (norms, embedding, router and its bias)."""
    q: Params = {
        "tok_embed": (), "out_norm": (),
        "layers": {"attn_norm": (), "mlp_norm": (), "q_a_norm": (),
                   "kv_a_norm": (), "w_dq": (2,), "w_uq_nope": (3,),
                   "w_uq_rope": (3,), "w_dkv": (2,), "w_uk": (3,),
                   "w_uv": (3,), "w_o": (1,)},
    }
    if cfg.index_n_heads:  # W_IW stays dense: its product is float32
        q["layers"].update({"w_iq": (3,), "w_ik": (2,), "w_iw": (),
                            "ik_norm": (), "ik_norm_bias": ()})
    if cfg.count(DENSE):
        q["dense"] = {"w_gate": (1,), "w_up": (1,), "w_down": (1,)}
    if cfg.count(SPARSE):
        q["moe"] = {"router": (), "router_bias": (),
                    "w_gate": (2,), "w_up": (2,), "w_down": (2,),
                    "shared_gate": (1,), "shared_up": (1,), "shared_down": (1,)}
    if not cfg.tie_embeddings:
        q["lm_head"] = (0,)
    return q


def init_params(cfg: DeepseekV3Config, key: jax.Array) -> Params:
    """Random init, fan-in scaled; every stack's layer dim leads. The
    projections are stored as the compiler multiplies them, contracted dim
    last (models/exaone_moe.py::init_params says what the other way cost):
    w_dq [rq, D], w_dkv [rkv + dr, D], w_o [H dv, D]. W_UQ's rows lie in
    two leaves, w_uq_nope [H, dn, rq] and w_uq_rope [H, dr, rq], and
    W_UKV's in two, w_uk [H, dn, rkv] = W_UK_i (a decode step carries
    q_nope through it into the latent's space) and w_uv [H, dv, rkv] =
    W_UV_i^T: as one leaf each, the step sliced a head's rows apart and
    the compiler laid 37.7 MB of W_UQ out anew and wrote both halves of
    W_UKV out again, every layer and step (optimised HLO for a described
    v5e, PR 40). Each leaf has its heads as a dimension of their own: as
    [H dn, rq] the scanned body copied the layer's slice of W_UQ out of
    the stack before it split the heads (1.8 ms of a 26 ms step on the
    chip, PR 40). That published form is the served one: the engine's
    door (Engine.serving_tree) has nothing to turn here. What is still
    laid out anew every layer and step is w_uq_rope's 12.6 MB (0.64 ms of
    a 23 ms step, under no region's name): viewed two heads to a row of
    128, before the layer is sliced off or after, the compiled step keeps
    the same `constant_dynamic-slice_fusion` (described v5e, PR 41), so
    the leaf stays as it is. The router bias is drawn, not zero, so that
    it moves the choice in a test."""
    k = iter(jax.random.split(key, 32))

    def dense(shape, fan_in):
        return (jax.random.truncated_normal(next(k), -2, 2, shape, jnp.float32)
                * fan_in ** -0.5).astype(cfg.dtype)

    L, D, H = cfg.n_layers, cfg.dim, cfg.n_heads
    rq, rkv = cfg.q_lora_rank, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    M, Mm, Ms = (cfg.hidden_dim, cfg.moe_hidden_dim,
                 cfg.moe_hidden_dim * cfg.n_shared_experts)
    Ld, Ls, Eh = cfg.count(DENSE), cfg.count(SPARSE), cfg.held_experts[1]
    params: Params = {
        "tok_embed": dense((cfg.vocab_size, D), D),
        "out_norm": jnp.ones((D,), cfg.dtype),
        "layers": {
            "attn_norm": jnp.ones((L, D), cfg.dtype),
            "mlp_norm": jnp.ones((L, D), cfg.dtype),
            "q_a_norm": jnp.ones((L, rq), cfg.dtype),
            "kv_a_norm": jnp.ones((L, rkv), cfg.dtype),
            "w_dq": dense((L, rq, D), D),
            "w_uq_nope": dense((L, H, dn, rq), rq),
            "w_uq_rope": dense((L, H, dr, rq), rq),
            "w_dkv": dense((L, rkv + dr, D), D),
            "w_uk": dense((L, H, dn, rkv), rkv),
            "w_uv": dense((L, H, dv, rkv), rkv),
            "w_o": dense((L, H * dv, D), H * dv),
        },
    }
    if cfg.index_n_heads:
        # as the MLA's own: W_IQ a head apart, contracted dimension last.
        # The key's LayerNorm has a bias, drawn so that it shows in a test.
        Hi, di = cfg.index_n_heads, cfg.index_head_dim
        params["layers"].update({
            "w_iq": dense((L, Hi, di, rq), rq),
            "w_ik": dense((L, di, D), D),
            "w_iw": dense((L, Hi, D), D),
            "ik_norm": jnp.ones((L, di), cfg.dtype),
            "ik_norm_bias": (0.1 * jax.random.normal(
                next(k), (L, di), jnp.float32)).astype(cfg.dtype),
        })
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


def init_paged_cache(cfg: DeepseekV3Config, pages: int, page_size: int,
                     dtype=None, kv_shards: int = 1) -> Params:
    """The latent pool: `k` [L, P, bs, 1, w], one row [ckv; kr] a token and
    layer for all heads (ops/kvcache.py::init_latent_cache decides the
    stored width w), and `v` a pool of no layers: the values are the keys'
    leading part. With an indexer `v` is the tokens' index keys, [L, P,
    bs, 1, index_head_dim], under the same page ids."""
    dtype = dtype or cfg.dtype
    if dtype == jnp.int8:
        raise ValueError("deepseek_v3 keeps no int8 latent pool")
    return kvcache.init_latent_cache(
        cfg.n_layers, pages, page_size, cfg.latent_row, dtype,
        cfg.index_head_dim if cfg.index_n_heads else 0)


def paged_cache_logical_axes(cfg: DeepseekV3Config,
                             quantized: bool = False) -> Params:
    return kvcache.latent_cache_logical_axes()


def index_topk(cfg: DeepseekV3Config) -> int:
    """Rows a query attends at most, where the layers pick them by a
    learned index; 0: every row it can see (for the engine's counters)."""
    return cfg.index_topk if cfg.index_n_heads else 0


def kv_heads_per_pool_row(cfg: DeepseekV3Config, pool: Params) -> int:
    """Heads that share one stored row of the pool: all of them."""
    return cfg.n_heads


# -- the block -----------------------------------------------------------------

def index_of(h, cq, lp, positions, cfg, qe):
    """The indexer's projections for the tokens of the call: (qi [B, S,
    Hi, di], ki [B, S, di], wi [B, S, Hi] float32, k), both rotated in
    their first dr channels; what ops/kvcache.py::latent_attention takes
    as `index`."""
    dt, dr = cfg.dtype, cfg.qk_rope_head_dim

    def rotated(x):  # [B, S, heads, di]
        return jnp.concatenate([
            rope(x[..., :dr], positions, cfg.rope_theta, cfg.yarn),
            x[..., dr:]], axis=-1)

    qi = rotated(qe("bsr,jnr->bsjn", cq, lp["w_iq"], dt))
    ki = layer_norm(qe("bsd,nd->bsn", h, lp["w_ik"], dt), lp["ik_norm"],
                    lp["ik_norm_bias"], cfg.index_norm_eps)
    ki = rotated(ki[:, :, None, :])[:, :, 0]
    wi = jnp.einsum(
        "bsd,jd->bsj", h.astype(jnp.float32),
        materialize(lp["w_iw"], jnp.float32),
    ) * (cfg.index_n_heads * cfg.index_head_dim) ** -0.5
    return qi, ki.astype(dt), wi, cfg.index_topk


def expanded_attention(q, ckv, kr, w_uk, w_uv, positions, cfg, index=None):
    """The expanded form over a whole sequence held in the call (no
    cache): q [B, S, H, dn + dr], ckv [B, S, rkv], kr [B, S, dr] against
    themselves, key j visible to query i iff positions j <= i. float32
    softmax, as ops/attention.py. With `index` (`index_of`'s) a query
    attends its own set of the visible keys alone."""
    dn = cfg.qk_nope_head_dim
    ckv = ckv.astype(jnp.float32)
    k_nope = jnp.einsum("bsc,hnc->bshn", ckv, materialize(w_uk, jnp.float32))
    v = jnp.einsum("bsc,hvc->bshv", ckv, materialize(w_uv, jnp.float32))
    qf = q.astype(jnp.float32)
    s = (jnp.einsum("bqhn,bkhn->bhqk", qf[..., :dn], k_nope)
         + jnp.einsum("bqhr,bkr->bhqk", qf[..., dn:], kr.astype(jnp.float32))
         ) * cfg.softmax_scale
    seen = positions[:, None, :] <= positions[:, :, None]  # [B, q, k]
    if index is not None:
        qi, ki, wi, topk = index
        seen = sparse_index.select(
            sparse_index.scores(qi, ki, wi), seen, topk, axis=2)
    p = jax.nn.softmax(jnp.where(seen[:, None], s, -1e30), axis=-1)
    return jnp.einsum("bhqk,bkhv->bqhv", p, v).astype(q.dtype)


def _block(x, lp, mlp, mlp_kind, idx, positions, cfg, cache, block_table,
           valid):
    """One layer. `mlp_kind` static; idx the layer's index in the pool
    (traced); mlp = (the stack of its FFN kind's layers, its index among
    them). Returns (x, cache, counters of a sparse layer or None)."""
    dt = cfg.dtype
    qe = qeinsum_w8a8 if cfg.quant_activations else qeinsum
    rkv = cfg.kv_lora_rank
    with jax.named_scope(scopes.NORM):
        h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
    with jax.named_scope(scopes.ATTN_QKV):
        cq = rms_norm(qe("bsd,rd->bsr", h, lp["w_dq"], dt), lp["q_a_norm"],
                      cfg.norm_eps)
        q_nope = qe("bsr,hnr->bshn", cq, lp["w_uq_nope"], dt)
        q_rope = qe("bsr,hnr->bshn", cq, lp["w_uq_rope"], dt)
        down = qe("bsd,rd->bsr", h, lp["w_dkv"], dt)
        ckv = rms_norm(down[..., :rkv], lp["kv_a_norm"], cfg.norm_eps)
        kr = rope(down[..., None, rkv:], positions, cfg.rope_theta,
                  cfg.yarn)[..., 0, :]
        q = jnp.concatenate([
            q_nope, rope(q_rope, positions, cfg.rope_theta, cfg.yarn)],
            axis=-1)
    index = None
    if cfg.index_n_heads:
        with jax.named_scope(scopes.ATTN_INDEX):
            index = index_of(h, cq, lp, positions, cfg, qe)
    if cache is None:
        with jax.named_scope(scopes.ATTN_CORE):
            attn = expanded_attention(q, ckv, kr, lp["w_uk"], lp["w_uv"],
                                      positions, cfg, index)
    else:
        pool, attn = kvcache.latent_attention(
            {"k": cache["k"], "v": cache["v"]}, idx, block_table, positions,
            q, jnp.concatenate([ckv, kr], axis=-1), lp["w_uk"], lp["w_uv"],
            cfg.softmax_scale, dt, index)
        cache = {**cache, **pool}
    with jax.named_scope(scopes.ATTN_OUT):
        flat = attn.reshape(attn.shape[:2] + (-1,))
        x = x + qeinsum("bsn,nd->bsd", flat, lp["w_o"], dt)
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
    cfg: DeepseekV3Config,
    *,
    positions: Optional[jnp.ndarray] = None,  # [B, S] absolute positions
    cache: Optional[Params] = None,  # init_paged_cache's dict
    block_table: Optional[jnp.ndarray] = None,  # [B, M] page ids
    valid: Optional[jnp.ndarray] = None,  # [B, S] real tokens
) -> Tuple[jnp.ndarray, Params]:
    """Returns (logits [B, S, vocab] float32, cache).

    Without a cache: the whole sequence at once, expanded (tests, a
    trainer); the dict returned is empty. With one (and its block table):
    the tokens' latent rows are written at `positions` into the pages of
    `block_table`, one token a row attends absorbed and more expanded
    (ops/kvcache.py::latent_attention), and the dict returned is the cache
    with the step's counters in it (`step_counters` takes them out), as
    models/exaone_moe.py's. `valid` says which tokens are real, for the
    counters alone: a token that is not (an idle row's filler, a bucket's
    padded tail) still writes where the engine points it."""
    b, s = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    if valid is None:
        valid = jnp.ones((b, s), bool)
    if cache is not None and block_table is None:
        raise ValueError("deepseek_v3 has a paged cache only: pass block_table")

    with jax.named_scope(scopes.EMBED):
        x = materialize(params["tok_embed"], cfg.dtype)[tokens]

    kinds = _kinds(cfg)

    def layer(carry, j, l, at):
        x, cache, stats = carry
        mlp_kind = kinds[j][1]
        lp = _take(params["layers"], l)
        mlp = (params["dense" if mlp_kind == DENSE else "moe"], at(mlp_kind))
        x, cache, st = _block(x, lp, mlp, mlp_kind, at(MLA), positions, cfg,
                              cache, block_table, valid)
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


@partial(jax.jit, static_argnames=("cfg",), donate_argnames=("cache",))
def decode_step(params: Params, cache: Params, tokens: jnp.ndarray,
                positions: jnp.ndarray, cfg: DeepseekV3Config,
                block_table: jnp.ndarray) -> Tuple[jnp.ndarray, Params]:
    """One step for a batch whose row i is decode slot i: next-token logits
    [B, vocab] and the cache, updated in place (donated)."""
    logits, cache = forward(
        params, tokens[:, None], cfg, positions=positions[:, None],
        cache=cache, block_table=block_table)
    step_counters(cache)
    return logits[:, 0, :], cache
