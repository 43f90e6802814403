"""The DeepSeek-V3 decoder family (the language model of dots.vlm1.inst), as
the harness needs it.

Everything the benchmark knows about this family's block lives here and in
its reference (`benchmarks/reference/deepseek_v3.py`); see
`benchmarks/families/llama_family.py` for what a family file gives. Plain
functions of the configuration's dict; nothing of the program is imported.

The block (substratus_tpu/models/deepseek_v3.py): multi-head latent
attention in every layer (a query of rank `q_lora_rank` with a norm of its
own; a latent of `kv_lora_rank` with a norm of its own and a rotary key of
`qk_rope_head_dim` shared by all heads: what a token keeps, `rkv + dr`
values a layer; per head `qk_nope_head_dim` + `qk_rope_head_dim` of query
and key and `v_head_dim` of value, the keys' and values' other part made
from the latent through W_UKV); rotary under YaRN (`rope_scaling`); the
first `first_k_dense_replace` layers one gated MLP of `intermediate_size`,
the rest a router over the model's experts under a group limit (`n_group`
groups, `topk_group` kept), top `num_experts_per_tok` by sigmoid score,
beside `n_shared_experts` shared ones, each `moe_intermediate_size` wide.

A configuration may hold one chip's share of a deployment (model-configs
guide, section 4): `num_hidden_layers` counts the layers held (the model's
first), `n_routed_experts` the routed experts held, starting at
`layout.experts_held[0]`, `vocab_size` the rows of the vocabulary held;
`published` gives the model's own counts, of which the router keeps
`published.n_routed_experts` outputs.

The counts below are of what the equations need, whichever form computes
them: a share built on them means the same after the kernels change.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Sequence, Tuple

from benchmarks.harness.counts import weight_bytes
from benchmarks.harness.weights import Leaf

DENSE, SPARSE = "dense", "sparse"

# Regions this block opens beyond the base vocabulary
# (substratus_tpu/ops/scopes.py::EXTRA, ::LATENT): the shared expert's
# matmuls; a decode step's way into the latent's space and back; what of a
# chunk's expansion of the latents stays outside the kernel.
SCOPES: Tuple[str, ...] = ("moe.shared", "attn.absorb", "attn.expand")
# Regions whose time is a stream of weights.
MATMUL_SCOPES = ("attn.qkv", "attn.absorb", "attn.expand", "attn.out", "mlp",
                 "moe.router", "moe.experts", "moe.shared", "lm_head")

_EXPERT_LEAVES = ("moe/w_gate", "moe/w_up", "moe/w_down")
_SHARED_LEAVES = ("moe/shared_gate", "moe/shared_up", "moe/shared_down")
# bfloat16 leaves no matmul region reads: the embedding's rows are gathered,
# the norms scale activations
_NOT_STREAMED = ("tok_embed", "layers/attn_norm", "layers/mlp_norm",
                 "layers/q_a_norm", "layers/kv_a_norm")


def dims(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes a builder needs, from the published keys."""
    n = int(cfg["num_hidden_layers"])
    held = int(cfg["n_routed_experts"])
    first_dense = min(int(cfg["first_k_dense_replace"]), n)
    rope = cfg.get("rope_scaling") or {}
    return {
        "D": int(cfg["hidden_size"]),
        "H": int(cfg["num_attention_heads"]),
        "rq": int(cfg["q_lora_rank"]),
        "rkv": int(cfg["kv_lora_rank"]),
        "dn": int(cfg["qk_nope_head_dim"]),
        "dr": int(cfg["qk_rope_head_dim"]),
        "dv": int(cfg["v_head_dim"]),
        "M": int(cfg["intermediate_size"]),
        "Mm": int(cfg["moe_intermediate_size"]),
        "Ms": int(cfg["moe_intermediate_size"]) * int(cfg["n_shared_experts"]),
        "V": int(cfg["vocab_size"]),
        "L": n,
        "E": int(cfg.get("published", {}).get("n_routed_experts", held)),
        "Eh": held,
        "first": int(cfg.get("layout", {}).get("experts_held", [0])[0]),
        "K": int(cfg["num_experts_per_tok"]),
        "G": int(cfg["n_group"]),
        "Gk": int(cfg["topk_group"]),
        "mlp": (DENSE,) * first_dense + (SPARSE,) * (n - first_dense),
        "Ld": first_dense, "Ls": n - first_dense,
        "theta": float(cfg["rope_theta"]),
        "yarn": (float(rope["factor"]),
                 int(rope["original_max_position_embeddings"]),
                 float(rope["beta_fast"]), float(rope["beta_slow"]),
                 float(rope["mscale"]), float(rope["mscale_all_dim"]))
        if rope else None,
    }


def softmax_scale(s: Dict[str, Any]) -> float:
    """(dn + dr)^(-1/2) m^2, m = 0.1 mscale_all_dim ln(factor) + 1."""
    scale = (s["dn"] + s["dr"]) ** -0.5
    if s["yarn"] and s["yarn"][0] > 1:
        scale *= (0.1 * s["yarn"][5] * math.log(s["yarn"][0]) + 1.0) ** 2
    return scale


def leaf_table(cfg: Dict[str, Any]) -> Dict[str, Leaf]:
    """The weight tree: `layers/` holds every layer's attention and norms,
    `dense/` the MLPs of the dense layers, `moe/` the router, its bias, the
    held experts and the shared expert of the sparse layers; each stack's
    layer dim leads. Matmul weights are int8 with a scale per output
    channel; embedding, norms and router bfloat16; the router's bias
    float32. The projections lie as the program multiplies them,
    contracted dim last; W_UQ's rows in two leaves (every head's q_nope
    [H, dn, rq], every head's q_rope [H, dr, rq]), W_UKV's in two:
    `w_uk` [H, dn, rkv] = W_UK_i, `w_uv` [H, dv, rkv] = W_UV_i^T."""
    s = dims(cfg)
    D, H, rq, rkv, dn, dr, dv = (s[k] for k in (
        "D", "H", "rq", "rkv", "dn", "dr", "dv"))
    M, Mm, Ms, V, L, E, Eh, Ld, Ls = (s[k] for k in (
        "M", "Mm", "Ms", "V", "L", "E", "Eh", "Ld", "Ls"))
    t: Dict[str, Leaf] = {
        "tok_embed": Leaf((V, D), (), 1, "normal"),
        "out_norm": Leaf((D,), (), 0, "norm"),
        "lm_head": Leaf((D, V), (0,), D, "int8"),
        "layers/attn_norm": Leaf((L, D), (), 0, "norm", True),
        "layers/mlp_norm": Leaf((L, D), (), 0, "norm", True),
        "layers/q_a_norm": Leaf((L, rq), (), 0, "norm", True),
        "layers/kv_a_norm": Leaf((L, rkv), (), 0, "norm", True),
        "layers/w_dq": Leaf((L, rq, D), (2,), D, "int8", True),
        "layers/w_uq_nope": Leaf((L, H, dn, rq), (3,), rq, "int8", True),
        "layers/w_uq_rope": Leaf((L, H, dr, rq), (3,), rq, "int8", True),
        "layers/w_dkv": Leaf((L, rkv + dr, D), (2,), D, "int8", True),
        "layers/w_uk": Leaf((L, H, dn, rkv), (3,), rkv, "int8", True),
        "layers/w_uv": Leaf((L, H, dv, rkv), (3,), rkv, "int8", True),
        "layers/w_o": Leaf((L, H * dv, D), (1,), H * dv, "int8", True),
    }
    if Ld:
        t["dense/w_gate"] = Leaf((Ld, D, M), (1,), D, "int8", True)
        t["dense/w_up"] = Leaf((Ld, D, M), (1,), D, "int8", True)
        t["dense/w_down"] = Leaf((Ld, M, D), (1,), M, "int8", True)
    if Ls:
        t["moe/router"] = Leaf((Ls, D, E), (), D, "normal", True)
        t["moe/router_bias"] = Leaf((Ls, E), (), 0, "bias", True)
        t["moe/w_gate"] = Leaf((Ls, Eh, D, Mm), (2,), D, "int8", True)
        t["moe/w_up"] = Leaf((Ls, Eh, D, Mm), (2,), D, "int8", True)
        t["moe/w_down"] = Leaf((Ls, Eh, Mm, D), (2,), Mm, "int8", True)
        t["moe/shared_gate"] = Leaf((Ls, D, Ms), (1,), D, "int8", True)
        t["moe/shared_up"] = Leaf((Ls, D, Ms), (1,), D, "int8", True)
        t["moe/shared_down"] = Leaf((Ls, Ms, D), (1,), Ms, "int8", True)
    return t


def program(cfg: Dict[str, Any]) -> Tuple[str, Dict[str, Any]]:
    """models/registry.py "deepseek_v3", models/deepseek_v3.py::
    DeepseekV3Config, from the published keys."""
    s = dims(cfg)
    yarn = s["yarn"] or (1.0, 4096, 32.0, 1.0, 1.0, 1.0)
    return "deepseek_v3", dict(
        vocab_size=s["V"], dim=s["D"], n_layers=s["L"], n_heads=s["H"],
        q_lora_rank=s["rq"], kv_lora_rank=s["rkv"],
        qk_nope_head_dim=s["dn"], qk_rope_head_dim=s["dr"],
        v_head_dim=s["dv"], hidden_dim=s["M"], moe_hidden_dim=s["Mm"],
        first_k_dense=s["Ld"], n_experts=s["E"],
        n_experts_per_token=s["K"],
        n_shared_experts=int(cfg["n_shared_experts"]),
        n_group=s["G"], topk_group=s["Gk"],
        held_experts=(s["first"], s["Eh"]),
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        norm_topk_prob=bool(cfg["norm_topk_prob"]),
        rope_theta=s["theta"], rope_factor=yarn[0],
        rope_original_max=yarn[1], rope_beta_fast=yarn[2],
        rope_beta_slow=yarn[3], rope_mscale=yarn[4],
        rope_mscale_all_dim=yarn[5],
        norm_eps=float(cfg["rms_norm_eps"]),
        max_seq_len=int(cfg["max_position_embeddings"]),
        tie_embeddings=bool(cfg.get("tie_word_embeddings", False)),
    )


# -- the least work a step asks of the chip, from shapes -------------------------

def _routable(b: float, s: Dict[str, Any], active: int) -> float:
    """Of a held-expert leaf's bytes, those of the held experts the active
    slots can route to (a slot chooses K experts of E; at most all held)."""
    return b * min(s["Eh"], active * s["K"]) / s["Eh"]


def _streamed(cfg: Dict[str, Any], active: int, only=None) -> float:
    s = dims(cfg)
    need = 0.0
    for name, b in weight_bytes(leaf_table(cfg)).items():
        if name in _NOT_STREAMED or (only is not None and name not in only):
            continue
        need += _routable(b, s, active) if name in _EXPERT_LEAVES else b
    return need


def decode_matmul_weight_bytes(cfg: Dict[str, Any], active: int) -> float:
    """Bytes of the weights one decode step's matmul regions must read,
    each once: every leaf but the embedding table and the norms (W_UKV
    among them: the absorbed form reads both its halves); of the held
    experts only as many as `active` slots can route to."""
    return _streamed(cfg, active)


def decode_moe_weight_bytes(cfg: Dict[str, Any], active: int) -> float:
    """Of those, the held experts and the shared expert: what the regions
    `moe.experts` and `moe.shared` must read in a step."""
    return _streamed(cfg, active, _EXPERT_LEAVES + _SHARED_LEAVES)


def latent_row_bytes(cfg: Dict[str, Any], kv_itemsize: int = 2) -> int:
    """What a token keeps a layer: [ckv; kr], for every head."""
    s = dims(cfg)
    return (s["rkv"] + s["dr"]) * kv_itemsize


def latent_decode_bytes(cfg: Dict[str, Any], tokens: float,
                        kv_itemsize: int = 2) -> float:
    """Bytes the attention of one decode step must read: every layer's
    latent row of each of the `tokens` live tokens, once (keys and values
    are the same bytes; every head shares them)."""
    return dims(cfg)["L"] * tokens * latent_row_bytes(cfg, kv_itemsize)


def latent_decode_flops(cfg: Dict[str, Any], tokens: float) -> float:
    """FLOPs the attention of one decode step needs over `tokens` live
    tokens, in the absorbed form (the one that reads a row once for all
    heads): a head and token, rkv + dr multiply-adds of score and rkv of
    read-out."""
    s = dims(cfg)
    return s["L"] * tokens * 2.0 * s["H"] * (2 * s["rkv"] + s["dr"])


def latent_chunk_flops(cfg: Dict[str, Any], queries: int, context: float
                       ) -> float:
    """FLOPs the attention of one prefill chunk needs, in the expanded
    form: `queries` tokens whose last sees `context` tokens (itself
    among them), each pair a head dn + dr multiply-adds of score and dv of
    value, and the chunk's own latents through W_UKV once. Expanding the
    earlier context again, as a program that keeps latents alone must, is
    work above this need and shows as a lower share."""
    s = dims(cfg)
    pairs = queries * (context - queries) + queries * (queries + 1) / 2
    return s["L"] * (
        2.0 * s["H"] * (s["dn"] + s["dr"] + s["dv"]) * pairs
        + 2.0 * s["rkv"] * s["H"] * (s["dn"] + s["dv"]) * queries)


def decode_step_bytes(cfg: Dict[str, Any], ctx_lens: Sequence[int],
                      kv_itemsize: int = 2) -> float:
    """Bytes one decode step must move: the streamed weights, the embedding
    rows of the active slots, the live latent rows of the active slots read
    once, and one new row a slot and layer written."""
    s = dims(cfg)
    active = len(ctx_lens)
    total = _streamed(cfg, active) + 2 * s["D"] * active
    total += latent_decode_bytes(cfg, sum(int(c) for c in ctx_lens),
                                 kv_itemsize)
    total += s["L"] * active * latent_row_bytes(cfg, kv_itemsize)
    return total


def matmul_params_per_token(cfg: Dict[str, Any]) -> float:
    """Matmul weights one token's forward pass multiplies by in the layer
    stack: the attention's five projections (W_UKV once: the token's own
    keys and values); a dense layer's MLP; a sparse layer's router, shared
    expert, and the routed experts held here that an average token chooses
    (K x held / all: the useful work of this share)."""
    s = dims(cfg)
    attn = (s["D"] * s["rq"] + s["rq"] * s["H"] * (s["dn"] + s["dr"])
            + s["D"] * (s["rkv"] + s["dr"])
            + s["rkv"] * s["H"] * (s["dn"] + s["dv"])
            + s["H"] * s["dv"] * s["D"])
    sparse = (s["D"] * s["E"] + 3 * s["D"] * s["Ms"]
              + 3 * s["D"] * s["Mm"] * s["K"] * s["Eh"] / s["E"])
    return s["L"] * attn + s["Ld"] * 3 * s["D"] * s["M"] + s["Ls"] * sparse


def prefill_chunk_flops(cfg: Dict[str, Any], tokens: int, offset: int) -> float:
    """FLOPs a prefill chunk of `tokens` real tokens at absolute positions
    offset.. needs: 2 per multiply-add of the matmuls (W_UKV among them,
    once a token), attention against what each query may see (offset + q +
    1 keys) in the expanded form, and the head for one row."""
    s = dims(cfg)
    flops = 2.0 * tokens * matmul_params_per_token(cfg)
    seen = tokens * offset + tokens * (tokens + 1) / 2
    flops += 2.0 * s["L"] * s["H"] * (s["dn"] + s["dr"] + s["dv"]) * seen
    flops += 2.0 * s["D"] * s["V"]
    return flops
