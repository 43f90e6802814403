"""The EXAONE-MoE decoder family (K-EXAONE-236B-A23B), as the harness needs it.

Everything the benchmark knows about this family's block lives here and in
its reference (`benchmarks/reference/exaone_moe.py`); see
`benchmarks/families/llama_family.py` for what a family file gives. Plain
functions of the configuration's dict; nothing of the program is imported.

The block (substratus_tpu/models/exaone_moe.py): `layer_types` says which
layers attend to a window of `sliding_window` positions (rotary, a ring of
that many rows a decode slot) and which to everything (no rotary, the
paged pool); `mlp_layer_types` which have one gated MLP of
`intermediate_size` and which a router over the model's experts, top
`num_experts_per_tok` by sigmoid score, beside `num_shared_experts` shared
ones, each `moe_intermediate_size` wide; RMSNorm over the head dimension
of q and k.

A configuration may hold one chip's share of a deployment (model-configs
guide, section 4): `num_hidden_layers` counts the layers held (the first
of the published lists), `num_experts` the routed experts held, starting at
`layout.experts_held[0]`, `vocab_size` the rows of the vocabulary held;
`published` gives the model's own counts, of which the router keeps
`published.num_experts` outputs.
"""
from __future__ import annotations

from typing import Any, Dict, Sequence, Tuple

from benchmarks.harness.counts import weight_bytes
from benchmarks.harness.weights import Leaf

WINDOW, GLOBAL, DENSE, SPARSE = ("sliding_attention", "full_attention",
                                 "dense", "sparse")

# Regions this block opens beyond the base vocabulary
# (substratus_tpu/ops/scopes.py::EXTRA): the shared expert's matmuls, a
# window layer's ring (read and scatter), a window layer's attention.
SCOPES: Tuple[str, ...] = ("moe.shared", "kv.ring", "attn.window")
# Regions whose time is a stream of weights.
MATMUL_SCOPES = ("attn.qkv", "attn.out", "mlp", "moe.router", "moe.experts",
                 "moe.shared", "lm_head")

_EXPERT_LEAVES = ("moe/w_gate", "moe/w_up", "moe/w_down")
_SHARED_LEAVES = ("moe/shared_gate", "moe/shared_up", "moe/shared_down")
# bfloat16 leaves no matmul region reads: the embedding's rows are gathered,
# the norms scale activations
_NOT_STREAMED = ("tok_embed", "layers/attn_norm", "layers/mlp_norm",
                 "layers/q_norm", "layers/k_norm")


def dims(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes a builder needs, from the published keys."""
    n = int(cfg["num_hidden_layers"])
    attn = tuple(cfg["layer_types"][:n])
    mlp = tuple(cfg["mlp_layer_types"][:n])
    held = int(cfg["num_experts"])
    return {
        "D": int(cfg["hidden_size"]),
        "H": int(cfg["num_attention_heads"]),
        "KH": int(cfg["num_key_value_heads"]),
        "hd": int(cfg["head_dim"]),
        "M": int(cfg["intermediate_size"]),
        "Mm": int(cfg["moe_intermediate_size"]),
        "Ms": int(cfg["moe_intermediate_size"]) * int(cfg["num_shared_experts"]),
        "V": int(cfg["vocab_size"]),
        "L": n,
        "E": int(cfg.get("published", {}).get("num_experts", held)),
        "Eh": held,
        "first": int(cfg.get("layout", {}).get("experts_held", [0])[0]),
        "K": int(cfg["num_experts_per_tok"]),
        "W": int(cfg["sliding_window"]),
        "attn": attn,
        "mlp": mlp,
        "Lw": attn.count(WINDOW), "Lg": attn.count(GLOBAL),
        "Ld": mlp.count(DENSE), "Ls": mlp.count(SPARSE),
    }


def leaf_table(cfg: Dict[str, Any]) -> Dict[str, Leaf]:
    """The weight tree: `layers/` holds every layer's attention and norms,
    `dense/` the MLPs of the dense layers, `moe/` the router, its bias, the
    held experts and the shared expert of the sparse layers; each stack's
    layer dim leads. Matmul weights are int8 with a scale per output
    channel; embedding, norms and router bfloat16; the router's bias
    float32."""
    s = dims(cfg)
    D, H, KH, hd, M, Mm, Ms, V, L, E, Eh, Ld, Ls = (s[k] for k in (
        "D", "H", "KH", "hd", "M", "Mm", "Ms", "V", "L", "E", "Eh", "Ld", "Ls"))
    t: Dict[str, Leaf] = {
        "tok_embed": Leaf((V, D), (), 1, "normal"),
        "out_norm": Leaf((D,), (), 0, "norm"),
        "lm_head": Leaf((D, V), (0,), D, "int8"),
        "layers/attn_norm": Leaf((L, D), (), 0, "norm", True),
        "layers/mlp_norm": Leaf((L, D), (), 0, "norm", True),
        "layers/q_norm": Leaf((L, hd), (), 0, "norm", True),
        "layers/k_norm": Leaf((L, hd), (), 0, "norm", True),
        # the attention projections as the program stores them: heads x
        # head size one dim, for q, k and v ahead of the contracted one
        "layers/wq": Leaf((L, H * hd, D), (2,), D, "int8", True),
        "layers/wk": Leaf((L, KH * hd, D), (2,), D, "int8", True),
        "layers/wv": Leaf((L, KH * hd, D), (2,), D, "int8", True),
        "layers/wo": Leaf((L, H * hd, D), (1,), H * hd, "int8", True),
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
    """models/registry.py "exaone_moe", models/exaone_moe.py::
    ExaoneMoeConfig, from the published keys."""
    s = dims(cfg)
    return "exaone_moe", dict(
        vocab_size=s["V"], dim=s["D"], n_layers=s["L"], n_heads=s["H"],
        n_kv_heads=s["KH"], head_dim=s["hd"], hidden_dim=s["M"],
        moe_hidden_dim=s["Mm"], n_experts=s["E"],
        n_experts_per_token=s["K"],
        n_shared_experts=int(cfg["num_shared_experts"]),
        held_experts=(s["first"], s["Eh"]),
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        norm_topk_prob=bool(cfg["norm_topk_prob"]),
        layer_types=s["attn"], mlp_layer_types=s["mlp"],
        sliding_window=s["W"],
        rope_theta=float(cfg["rope_parameters"]["rope_theta"]),
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
    each once: every leaf but the embedding table and the norms; of the
    held experts only as many as `active` slots can route to."""
    return _streamed(cfg, active)


def decode_moe_weight_bytes(cfg: Dict[str, Any], active: int) -> float:
    """Of those, the held experts and the shared expert: what the regions
    `moe.experts` and `moe.shared` must read in a step."""
    return _streamed(cfg, active, _EXPERT_LEAVES + _SHARED_LEAVES)


def decode_step_bytes(cfg: Dict[str, Any], ctx_lens: Sequence[int],
                      kv_itemsize: int = 2) -> float:
    """Bytes one decode step must move: the streamed weights, the embedding
    rows of the active slots, the context of the active slots read once (a
    global layer's whole context, a window layer's newest `sliding_window`
    rows at most), and one new K/V row a slot and layer written."""
    s = dims(cfg)
    active = len(ctx_lens)
    row = 2 * s["KH"] * s["hd"] * kv_itemsize
    total = _streamed(cfg, active) + 2 * s["D"] * active
    for c in ctx_lens:
        total += row * (s["Lg"] * int(c) + s["Lw"] * min(int(c), s["W"])
                        + s["L"])
    return total


def matmul_params_per_token(cfg: Dict[str, Any]) -> float:
    """Matmul weights one token's forward pass multiplies by in the layer
    stack: attention projections; a dense layer's MLP; a sparse layer's
    router, shared expert, and the routed experts held here that an
    average token chooses (K x held / all: the useful work of this share)."""
    s = dims(cfg)
    attn = s["D"] * (s["H"] + 2 * s["KH"]) * s["hd"] + s["H"] * s["hd"] * s["D"]
    sparse = (s["D"] * s["E"] + 3 * s["D"] * s["Ms"]
              + 3 * s["D"] * s["Mm"] * s["K"] * s["Eh"] / s["E"])
    return s["L"] * attn + s["Ld"] * 3 * s["D"] * s["M"] + s["Ls"] * sparse


def prefill_chunk_flops(cfg: Dict[str, Any], tokens: int, offset: int) -> float:
    """FLOPs a prefill chunk of `tokens` real tokens at absolute positions
    offset.. needs: 2 per multiply-add of the matmuls, attention against
    what each query may see (a global layer: offset + q + 1 keys; a window
    layer: at most `sliding_window`), and the head for one row."""
    s = dims(cfg)
    flops = 2.0 * tokens * matmul_params_per_token(cfg)
    seen_all = tokens * offset + tokens * (tokens + 1) / 2
    seen_window = sum(min(offset + q + 1, s["W"]) for q in range(tokens))
    flops += 4.0 * s["H"] * s["hd"] * (s["Lg"] * seen_all
                                       + s["Lw"] * seen_window)
    flops += 2.0 * s["D"] * s["V"]
    return flops
