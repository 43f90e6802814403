"""The GLM-5 decoder family (transformers `glm_moe_dsa`), as the harness
needs it.

DeepSeek-V3's block (`benchmarks/families/deepseek_v3.py`, whose sizes,
tree and counts this file takes and adds to) at other widths, with no group
limit and no YaRN (the rotary table's `rope_theta` lies under
`rope_parameters`), and with **a learned index in every layer** (DeepSeek
Sparse Attention's `Indexer`; keys `index_n_heads` Hi, `index_head_dim` di,
`index_topk` k): `qi_t,j = (cq_t W_IQ)_j`, one key a token `ki_s =
LayerNorm(h_s W_IK; g, b)`, of both the first `qk_rope_head_dim` channels
rotated; `w_t,j = (h_t W_IW)_j Hi^(-1/2) di^(-1/2)` in float32; `I_t,s =
sum_j w_t,j ReLU(qi_t,j . ki_s)`; the MLA's softmax runs over the min(k, t +
1) positions s <= t of largest `I_t,s` alone, ties toward the lower
position. A token keeps `[ckv; kr]` and `ki` a layer.

Plain functions of the configuration's dict; nothing of the program is
imported. The counts are of what the equations need, whichever form
computes them: a decode step must read every live index key once and
min(k, context) latent rows a slot, not the context.
"""
from __future__ import annotations

from typing import Any, Dict, Sequence, Tuple

from benchmarks.families import deepseek_v3 as v3
from benchmarks.harness.counts import weight_bytes
from benchmarks.harness.weights import Leaf

DENSE, SPARSE = v3.DENSE, v3.SPARSE

# Regions the block opens beyond the base vocabulary (substratus_tpu/ops/
# scopes.py::EXTRA, ::LATENT, ::INDEXED): deepseek_v3's, the index (its
# projections and the scores over the live keys) and the set (the top-k or
# threshold, positions into rows).
SCOPES: Tuple[str, ...] = v3.SCOPES + ("attn.index", "attn.select")
# Regions whose time is a stream of weights (attn.index: W_IQ, W_IK, W_IW,
# beside the live keys it scores).
MATMUL_SCOPES = v3.MATMUL_SCOPES + ("attn.index",)

_INDEX_LEAVES = ("layers/w_iq", "layers/w_ik", "layers/w_iw")
_NOT_STREAMED = v3._NOT_STREAMED + ("layers/ik_norm", "layers/ik_norm_bias")


def _v3(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The configuration under DeepSeek-V3's keys: theta at the top, no
    YaRN."""
    rope = cfg.get("rope_parameters") or {}
    kind = rope.get("rope_type", "default")
    if kind != "default":
        raise ValueError(f"glm_moe_dsa: rope_type {kind!r}")
    return {**cfg, "rope_scaling": None,
            "rope_theta": rope.get("rope_theta", cfg.get("rope_theta", 1e4))}


def dims(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes a builder needs, from the published keys."""
    s = v3.dims(_v3(cfg))
    s.update(Hi=int(cfg["index_n_heads"]), di=int(cfg["index_head_dim"]),
             topk=int(cfg["index_topk"]),
             index_eps=float(cfg.get("assumed", {}).get(
                 "index_norm_eps", 1e-6)))
    return s


softmax_scale = v3.softmax_scale


def leaf_table(cfg: Dict[str, Any]) -> Dict[str, Leaf]:
    """deepseek_v3's tree and the indexer's five leaves a layer: `w_iq`
    [Hi, di, rq] and `w_ik` [di, D] int8 with a scale per output channel,
    contracted dim last; `w_iw` [Hi, D] bfloat16 (its product is taken in
    float32); the key's LayerNorm weight bfloat16 and bias float32."""
    s = dims(cfg)
    L, D, rq, Hi, di = (s[k] for k in ("L", "D", "rq", "Hi", "di"))
    t = v3.leaf_table(_v3(cfg))
    t["layers/w_iq"] = Leaf((L, Hi, di, rq), (3,), rq, "int8", True)
    t["layers/w_ik"] = Leaf((L, di, D), (2,), D, "int8", True)
    t["layers/w_iw"] = Leaf((L, Hi, D), (), D, "normal", True)
    t["layers/ik_norm"] = Leaf((L, di), (), 0, "norm", True)
    t["layers/ik_norm_bias"] = Leaf((L, di), (), 0, "bias", True)
    return t


def program(cfg: Dict[str, Any]) -> Tuple[str, Dict[str, Any]]:
    """models/registry.py "deepseek_v3" (`glm_moe_dsa` is that module's),
    models/deepseek_v3.py::DeepseekV3Config with its indexer, from the
    published keys."""
    s = dims(cfg)
    name, kw = v3.program(_v3(cfg))
    kw.update(index_n_heads=s["Hi"], index_head_dim=s["di"],
              index_topk=s["topk"], index_norm_eps=s["index_eps"])
    return name, kw


# -- the least work a step asks of the chip, from shapes -------------------------

def _streamed(cfg: Dict[str, Any], active: int, only=None) -> float:
    s = dims(cfg)
    need = 0.0
    for name, b in weight_bytes(leaf_table(cfg)).items():
        if name in _NOT_STREAMED or (only is not None and name not in only):
            continue
        need += v3._routable(b, s, active) if name in v3._EXPERT_LEAVES else b
    return need


def decode_matmul_weight_bytes(cfg: Dict[str, Any], active: int) -> float:
    """Bytes of the weights one decode step's matmul regions must read,
    each once: deepseek_v3's and the indexer's three projections."""
    return _streamed(cfg, active)


def decode_moe_weight_bytes(cfg: Dict[str, Any], active: int) -> float:
    """Of those, the held experts and the shared expert."""
    return _streamed(cfg, active, v3._EXPERT_LEAVES + v3._SHARED_LEAVES)


def latent_row_bytes(cfg: Dict[str, Any], kv_itemsize: int = 2) -> int:
    return v3.latent_row_bytes(_v3(cfg), kv_itemsize)


def index_key_bytes(cfg: Dict[str, Any], kv_itemsize: int = 2) -> int:
    """What a token keeps a layer beside its latent row: one index key."""
    return dims(cfg)["di"] * kv_itemsize


def attended(cfg: Dict[str, Any], ctx_lens: Sequence[int]) -> int:
    """Rows the queries of one decode step attend a layer: min(k, context)
    a slot."""
    k = dims(cfg)["topk"]
    return sum(min(k, int(c)) for c in ctx_lens)


def index_decode_bytes(cfg: Dict[str, Any], tokens: float,
                       kv_itemsize: int = 2) -> float:
    """Bytes the index of one decode step must read: every layer's key of
    each of the `tokens` live tokens, once, and the indexer's weights."""
    return (dims(cfg)["L"] * tokens * index_key_bytes(cfg, kv_itemsize)
            + _streamed(cfg, 1, _INDEX_LEAVES))


def sparse_decode_bytes(cfg: Dict[str, Any], ctx_lens: Sequence[int],
                        kv_itemsize: int = 2) -> float:
    """Bytes the attention of one decode step must read: every layer's
    latent row of the min(k, context) tokens a slot's query attends, once
    (keys and values are the same bytes; every head shares them)."""
    return (dims(cfg)["L"] * attended(cfg, ctx_lens)
            * latent_row_bytes(cfg, kv_itemsize))


def latent_decode_bytes(cfg: Dict[str, Any], tokens: float,
                        kv_itemsize: int = 2) -> float:
    """deepseek_v3's count, of a step that attended every live row: what
    the index spares a step is this less `sparse_decode_bytes`."""
    return v3.latent_decode_bytes(_v3(cfg), tokens, kv_itemsize)


def latent_decode_flops(cfg: Dict[str, Any], tokens: float) -> float:
    """FLOPs of the absorbed form over `tokens` attended rows (the sum of
    min(k, context) over the slots, for a step under the index)."""
    return v3.latent_decode_flops(_v3(cfg), tokens)


def _attended_pairs(k: int, queries: int, offset: int) -> float:
    """Pairs of a chunk's queries at positions offset.. and the keys they
    attend: min(k, position + 1) a query."""
    return float(sum(min(k, offset + q + 1) for q in range(queries)))


def latent_chunk_flops(cfg: Dict[str, Any], queries: int, context: float
                       ) -> float:
    """FLOPs the attention of one prefill chunk needs, in the expanded
    form under the index: each query against the min(k, position + 1) keys
    of its set, and the chunk's own latents through W_UKV once. A program
    that computes every pair under a mask does work above this need."""
    s = dims(cfg)
    pairs = _attended_pairs(s["topk"], queries, int(context) - queries)
    return s["L"] * (
        2.0 * s["H"] * (s["dn"] + s["dr"] + s["dv"]) * pairs
        + 2.0 * s["rkv"] * s["H"] * (s["dn"] + s["dv"]) * queries)


def index_chunk_flops(cfg: Dict[str, Any], queries: int, context: float
                      ) -> float:
    """FLOPs of a chunk's index scores: every query against every key it
    can see, Hi heads of di multiply-adds a pair."""
    s = dims(cfg)
    seen = queries * (context - queries) + queries * (queries + 1) / 2
    return s["L"] * 2.0 * s["Hi"] * s["di"] * seen


def decode_step_bytes(cfg: Dict[str, Any], ctx_lens: Sequence[int],
                      kv_itemsize: int = 2) -> float:
    """Bytes one decode step must move: the streamed weights (the
    indexer's among them), the embedding rows of the active slots, the live
    index keys of the active slots read once, min(k, context) latent rows a
    slot, and one new row and one new key a slot and layer written."""
    s = dims(cfg)
    active = len(ctx_lens)
    total = _streamed(cfg, active) + 2 * s["D"] * active
    total += s["L"] * sum(int(c) for c in ctx_lens) * index_key_bytes(
        cfg, kv_itemsize)
    total += sparse_decode_bytes(cfg, ctx_lens, kv_itemsize)
    total += s["L"] * active * (latent_row_bytes(cfg, kv_itemsize)
                                + index_key_bytes(cfg, kv_itemsize))
    return total


def matmul_params_per_token(cfg: Dict[str, Any]) -> float:
    """deepseek_v3's, and the indexer's three projections a layer."""
    s = dims(cfg)
    index = s["rq"] * s["Hi"] * s["di"] + s["D"] * s["di"] + s["D"] * s["Hi"]
    return v3.matmul_params_per_token(_v3(cfg)) + s["L"] * index


def prefill_chunk_flops(cfg: Dict[str, Any], tokens: int, offset: int) -> float:
    """FLOPs a prefill chunk of `tokens` real tokens at absolute positions
    offset.. needs: 2 per multiply-add of the matmuls, the index scores of
    every pair a query can see, attention against each query's set alone,
    and the head for one row."""
    s = dims(cfg)
    flops = 2.0 * tokens * matmul_params_per_token(cfg)
    flops += index_chunk_flops(cfg, tokens, offset + tokens)
    flops += (2.0 * s["L"] * s["H"] * (s["dn"] + s["dr"] + s["dv"])
              * _attended_pairs(s["topk"], tokens, offset))
    flops += 2.0 * s["D"] * s["V"]
    return flops
