"""The LFM2-MoE decoder family (LFM2-24B-A2B), as the harness needs it.

Everything the benchmark knows about this family's block lives here and in
its reference (`benchmarks/reference/lfm2_moe.py`); see
`benchmarks/families/llama_family.py` for what a family file gives. Plain
functions of the configuration's dict; nothing of the program is imported.

The block (substratus_tpu/models/lfm2_moe.py): `layer_types` says which
layers' operator is a gated short convolution of `conv_L_cache` taps (two
rows of state a decode slot, no pages) and which full attention (rotary,
RMSNorm over the head dimension of q and k, the paged pool); the first
`num_dense_layers` layers have one gated MLP of `intermediate_size`, the
others a router over `num_experts` experts, top `num_experts_per_tok` by
sigmoid score plus a bias, each `moe_intermediate_size` wide, no shared
expert; the output head is the embedding.

A configuration may hold one stage of a pipeline (model-configs guide,
section 4): `num_hidden_layers` counts the layers held, the first of the
published list; `published` gives the model's own count. Every layer held
is whole: all experts, the whole vocabulary.
"""
from __future__ import annotations

from typing import Any, Dict, Sequence, Tuple

from benchmarks.harness.counts import weight_bytes
from benchmarks.harness.weights import Leaf

CONV, ATTN, DENSE, SPARSE = "conv", "full_attention", "dense", "sparse"

# Regions this block opens beyond the base vocabulary
# (substratus_tpu/ops/scopes.py::CONV): a convolution layer's input
# projection and gate, its state and taps, its output gate and projection.
SCOPES: Tuple[str, ...] = ("conv.in", "conv.state", "conv.out")
# Regions whose time is a stream of weights.
MATMUL_SCOPES = ("attn.qkv", "attn.out", "mlp", "moe.router", "moe.experts",
                 "lm_head", "conv.in", "conv.out")

_EXPERT_LEAVES = ("moe/w_gate", "moe/w_up", "moe/w_down")
_CONV_LEAVES = ("conv/w_in", "conv/taps", "conv/w_out")
# bfloat16 leaves no matmul region reads: the norms scale activations, the
# taps are read in `conv.state`. The embedding is read whole by `lm_head`
# (the head is tied to it), so it is streamed.
_NOT_STREAMED = ("out_norm", "layers/operator_norm", "layers/ffn_norm",
                 "attn/q_norm", "attn/k_norm", "conv/taps")


def dims(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes a builder needs, from the published keys."""
    n = int(cfg["num_hidden_layers"])
    ops = tuple(cfg["layer_types"][:n])
    dense = min(int(cfg["num_dense_layers"]), n)
    d, h = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    held = int(cfg["num_experts"])
    return {
        "D": d,
        "H": h,
        "KH": int(cfg["num_key_value_heads"]),
        "hd": int(cfg.get("head_dim") or d // h),
        "M": int(cfg["intermediate_size"]),
        "Mm": int(cfg["moe_intermediate_size"]),
        "V": int(cfg["vocab_size"]),
        "L": n,
        "E": int(cfg.get("published", {}).get("num_experts", held)),
        "Eh": held,
        "first": int(cfg.get("layout", {}).get("experts_held", [0])[0]),
        "K": int(cfg["num_experts_per_tok"]),
        "T": int(cfg["conv_L_cache"]),
        "ops": ops,
        "mlp": (DENSE,) * dense + (SPARSE,) * (n - dense),
        "Lc": ops.count(CONV), "La": ops.count(ATTN),
        "Ld": dense, "Ls": n - dense,
    }


def leaf_table(cfg: Dict[str, Any]) -> Dict[str, Leaf]:
    """The weight tree: `layers/` holds every layer's two norms, `conv/`
    and `attn/` the operators of the convolution and attention layers,
    `dense/` the MLPs of the dense layers, `moe/` the router, its bias and
    the experts of the sparse layers; each stack's layer dim leads. Matmul
    weights are int8 with a scale per output channel; embedding (the head
    too), norms, taps and router bfloat16; the router's bias float32."""
    s = dims(cfg)
    D, H, KH, hd, M, Mm, V, L, E, Eh, T = (s[k] for k in (
        "D", "H", "KH", "hd", "M", "Mm", "V", "L", "E", "Eh", "T"))
    Lc, La, Ld, Ls = (s[k] for k in ("Lc", "La", "Ld", "Ls"))
    t: Dict[str, Leaf] = {
        # fan-in D: the head multiplies by these rows
        "tok_embed": Leaf((V, D), (), D, "normal"),
        "out_norm": Leaf((D,), (), 0, "norm"),
        "layers/operator_norm": Leaf((L, D), (), 0, "norm", True),
        "layers/ffn_norm": Leaf((L, D), (), 0, "norm", True),
    }
    if Lc:
        t["conv/w_in"] = Leaf((Lc, D, 3 * D), (1,), D, "int8", True)
        t["conv/taps"] = Leaf((Lc, T, D), (), T, "normal", True)
        t["conv/w_out"] = Leaf((Lc, D, D), (1,), D, "int8", True)
    if La:
        t["attn/q_norm"] = Leaf((La, hd), (), 0, "norm", True)
        t["attn/k_norm"] = Leaf((La, hd), (), 0, "norm", True)
        # as the program stores them: heads x head size one dim, for q, k
        # and v ahead of the contracted one
        t["attn/wq"] = Leaf((La, H * hd, D), (2,), D, "int8", True)
        t["attn/wk"] = Leaf((La, KH * hd, D), (2,), D, "int8", True)
        t["attn/wv"] = Leaf((La, KH * hd, D), (2,), D, "int8", True)
        t["attn/wo"] = Leaf((La, H * hd, D), (1,), H * hd, "int8", True)
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
    return t


def program(cfg: Dict[str, Any]) -> Tuple[str, Dict[str, Any]]:
    """models/registry.py "lfm2_moe", models/lfm2_moe.py::Lfm2MoeConfig,
    from the published keys."""
    s = dims(cfg)
    return "lfm2_moe", dict(
        vocab_size=s["V"], dim=s["D"], n_layers=s["L"], n_heads=s["H"],
        n_kv_heads=s["KH"], head_dim=s["hd"], hidden_dim=s["M"],
        moe_hidden_dim=s["Mm"], n_dense_layers=s["Ld"], n_experts=s["E"],
        n_experts_per_token=s["K"], held_experts=(s["first"], s["Eh"]),
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        norm_topk_prob=bool(cfg["norm_topk_prob"]),
        layer_types=s["ops"], conv_taps=s["T"],
        rope_theta=float(cfg["rope_parameters"]["rope_theta"]),
        norm_eps=float(cfg["norm_eps"]),
        max_seq_len=int(cfg["max_position_embeddings"]),
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
    each once: every leaf but the norms and the taps (the embedding is the
    head's weight); of the experts only as many as `active` slots can
    route to."""
    return _streamed(cfg, active)


def decode_moe_weight_bytes(cfg: Dict[str, Any], active: int) -> float:
    """Of those, the experts: what the region `moe.experts` must read in a
    step."""
    return _streamed(cfg, active, _EXPERT_LEAVES)


def _conv_rows_bytes(s: Dict[str, Any], itemsize: int) -> int:
    """A slot's convolution state, read and written once a layer."""
    return 2 * s["Lc"] * (s["T"] - 1) * s["D"] * itemsize


def decode_conv_bytes(cfg: Dict[str, Any], active: int,
                      itemsize: int = 2) -> float:
    """Bytes the convolution layers' three regions must move in a decode
    step: `W_in`, `W_out` and the taps of every convolution layer once;
    for each active slot the state rows read and written, and the
    activations that pass between the regions (the normed input read; u
    and C written; u read, v written; C and v read, the output written:
    8 rows of D a layer)."""
    s = dims(cfg)
    table = weight_bytes(leaf_table(cfg))
    weights = sum(table[n] for n in _CONV_LEAVES if n in table)
    acts = 8 * s["Lc"] * s["D"] * itemsize
    return weights + active * (_conv_rows_bytes(s, itemsize) + acts)


def decode_step_bytes(cfg: Dict[str, Any], ctx_lens: Sequence[int],
                      kv_itemsize: int = 2) -> float:
    """Bytes one decode step must move: the streamed weights (the embedding
    among them, once, for the head), the taps, the whole context of the
    active slots read once in every attention layer (what attention needs:
    a gather of every table position reads more, and that is the program's
    cost, not the algorithm's), one new K/V row a slot and attention layer
    written, and the active slots' convolution state read and written."""
    s = dims(cfg)
    active = len(ctx_lens)
    row = 2 * s["KH"] * s["hd"] * kv_itemsize
    table = weight_bytes(leaf_table(cfg))
    total = _streamed(cfg, active) + table.get("conv/taps", 0)
    total += active * _conv_rows_bytes(s, kv_itemsize)
    for c in ctx_lens:
        total += row * s["La"] * (int(c) + 1)
    return total


def matmul_params_per_token(cfg: Dict[str, Any]) -> float:
    """Matmul weights one token's forward pass multiplies by in the layer
    stack: a convolution layer's two projections, an attention layer's
    four, a dense layer's MLP, a sparse layer's router and the K experts a
    token chooses (of those held here: K x held / all)."""
    s = dims(cfg)
    attn = s["D"] * (s["H"] + 2 * s["KH"]) * s["hd"] + s["H"] * s["hd"] * s["D"]
    conv = 4 * s["D"] * s["D"]
    sparse = s["D"] * s["E"] + 3 * s["D"] * s["Mm"] * s["K"] * s["Eh"] / s["E"]
    return (s["La"] * attn + s["Lc"] * conv + s["Ld"] * 3 * s["D"] * s["M"]
            + s["Ls"] * sparse)


def prefill_chunk_flops(cfg: Dict[str, Any], tokens: int, offset: int) -> float:
    """FLOPs a prefill chunk of `tokens` real tokens at absolute positions
    offset.. needs: 2 per multiply-add of the matmuls, attention against
    the offset + q + 1 keys each query may see, the taps, and the head for
    one row."""
    s = dims(cfg)
    flops = 2.0 * tokens * matmul_params_per_token(cfg)
    seen = tokens * offset + tokens * (tokens + 1) / 2
    flops += 4.0 * s["H"] * s["hd"] * s["La"] * seen
    flops += 2.0 * tokens * s["Lc"] * s["T"] * s["D"]
    flops += 2.0 * s["D"] * s["V"]
    return flops
