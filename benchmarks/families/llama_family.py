"""The Mistral / Mixtral decoder family, as the harness needs it.

Everything the benchmark knows about this family's block lives here and in
its reference (`benchmarks/reference/llama_family.py`): the sizes, the
weight tree, the program's family and config, the least work a step asks
of the chip, and the regions the block opens in a trace. Plain functions
of the configuration's dict; nothing of the program is imported (the name
`program` returns is looked up by `harness/system.py`).

A family file gives (see PERF.md section 3):

    dims(cfg)                     the sizes its functions and reference read
    leaf_table(cfg)               path -> weights.Leaf, the whole tree
    program(cfg)                  (family name in models/registry.py,
                                  keyword arguments of its config class)
    decode_step_bytes(cfg, ctx_lens, kv_itemsize)    | counts: a reader whose
    decode_matmul_weight_bytes(cfg, active)          | count is not defined
    prefill_chunk_flops(cfg, tokens, offset)         | returns nothing
    SCOPES, MATMUL_SCOPES         regions the block adds to the base
                                  vocabulary; which regions stream weights

The block (models/llama.py::_block, _moe_ffn): full causal attention in
every layer, grouped KV heads, one gated MLP or `num_local_experts` equal
experts of which `num_experts_per_tok` are routed to.
"""
from __future__ import annotations

from typing import Any, Dict, Sequence, Tuple

from benchmarks.harness.counts import weight_bytes
from benchmarks.harness.weights import Leaf

# The block opens no region beyond the base vocabulary
# (harness/trace_scopes.py::SCOPES, the copy of substratus_tpu/ops/scopes.py).
SCOPES: Tuple[str, ...] = ()
# Regions whose time is a stream of weights: the projections, the MLP or
# the router and experts (models/llama.py::_block, _moe_ffn), the head.
MATMUL_SCOPES = ("attn.qkv", "attn.out", "mlp", "moe.router", "moe.experts",
                 "lm_head")

_EXPERT_LEAVES = ("w_gate", "w_up", "w_down")
# bfloat16 leaves no matmul region reads: the embedding's rows are
# gathered, the blocks' norms scale activations
_NOT_STREAMED = ("tok_embed", "attn_norm", "mlp_norm")


def dims(cfg: Dict[str, Any]) -> Dict[str, int]:
    """The sizes a builder needs, from the published keys."""
    d = int(cfg["hidden_size"])
    h = int(cfg["num_attention_heads"])
    return {
        "D": d,
        "H": h,
        "KH": int(cfg["num_key_value_heads"]),
        "hd": int(cfg.get("head_dim") or d // h),
        "M": int(cfg["intermediate_size"]),
        "V": int(cfg["vocab_size"]),
        "L": int(cfg["num_hidden_layers"]),
        "E": int(cfg.get("num_local_experts", 0)),
        "K": int(cfg.get("num_experts_per_tok", 0)),
    }


def leaf_table(cfg: Dict[str, Any]) -> Dict[str, Leaf]:
    """The weight tree: one stack, "layers/<name>" with the layer dim
    leading, between the embedding and the output norm and head. Matmul
    weights are int8 with a scale per output channel; embedding, norms and
    the router stay bfloat16."""
    s = dims(cfg)
    D, H, KH, hd, M, V, L, E = (s[k] for k in "D H KH hd M V L E".split())
    t: Dict[str, Leaf] = {
        "tok_embed": Leaf((V, D), (), 1, "normal"),
        "out_norm": Leaf((D,), (), 0, "norm"),
        "layers/attn_norm": Leaf((L, D), (), 0, "norm", True),
        "layers/mlp_norm": Leaf((L, D), (), 0, "norm", True),
        "layers/wq": Leaf((L, D, H, hd), (1,), D, "int8", True),
        "layers/wk": Leaf((L, D, KH, hd), (1,), D, "int8", True),
        "layers/wv": Leaf((L, D, KH, hd), (1,), D, "int8", True),
        "layers/wo": Leaf((L, H, hd, D), (1, 2), H * hd, "int8", True),
    }
    if E:
        t["layers/router"] = Leaf((L, D, E), (), D, "normal", True)
        t["layers/w_gate"] = Leaf((L, E, D, M), (2,), D, "int8", True)
        t["layers/w_up"] = Leaf((L, E, D, M), (2,), D, "int8", True)
        t["layers/w_down"] = Leaf((L, E, M, D), (2,), M, "int8", True)
    else:
        t["layers/w_gate"] = Leaf((L, D, M), (1,), D, "int8", True)
        t["layers/w_up"] = Leaf((L, D, M), (1,), D, "int8", True)
        t["layers/w_down"] = Leaf((L, M, D), (1,), M, "int8", True)
    if not cfg.get("tie_word_embeddings", False):
        t["lm_head"] = Leaf((D, V), (0,), D, "int8")
    return t


def program(cfg: Dict[str, Any]) -> Tuple[str, Dict[str, Any]]:
    """The program's family and its model configuration from the published
    keys (there is no preset for Mistral-7B and none is added to the
    program): models/registry.py "llama", models/llama.py::LlamaConfig."""
    s = dims(cfg)
    return "llama", dict(
        vocab_size=s["V"], dim=s["D"], n_layers=s["L"], n_heads=s["H"],
        n_kv_heads=s["KH"], hidden_dim=s["M"], head_dim=s["hd"],
        rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]),
        max_seq_len=int(cfg["max_position_embeddings"]),
        tie_embeddings=bool(cfg.get("tie_word_embeddings", False)),
        n_experts=s["E"], n_experts_per_token=s["K"] or 2,
    )


# -- the least work a step asks of the chip, from shapes -------------------------

def _routable(b: float, s: Dict[str, int], active: int) -> float:
    """Of an expert leaf's bytes, those of the experts the active slots
    can route to."""
    return b * min(s["E"], active * s["K"]) / s["E"]


def decode_step_bytes(cfg: Dict[str, Any], ctx_lens: Sequence[int],
                      kv_itemsize: int = 2) -> float:
    """Bytes one decode step must move, over all chips: every weight once
    (of the embedding only the rows of the active slots; of Mixtral's
    experts only as many as the active slots can route to), the real
    context of the active slots read once, and one new K/V row a slot
    written."""
    s = dims(cfg)
    active = len(ctx_lens)
    total = 0.0
    for name, b in weight_bytes(leaf_table(cfg)).items():
        if name == "tok_embed":
            total += 2 * s["D"] * active
        elif s["E"] and name.split("/")[-1] in _EXPERT_LEAVES:
            total += _routable(b, s, active)
        else:
            total += b
    kv_row = s["L"] * 2 * s["KH"] * s["hd"] * kv_itemsize
    total += kv_row * (sum(int(c) for c in ctx_lens) + active)
    return total


def decode_matmul_weight_bytes(cfg: Dict[str, Any], active: int) -> float:
    """Bytes of the weights one decode step's matmul regions must read,
    each once: every leaf but the embedding table and the blocks' norms; of
    Mixtral's experts only as many as `active` slots can route to."""
    s = dims(cfg)
    need = 0.0
    for name, b in weight_bytes(leaf_table(cfg)).items():
        leaf = name.split("/")[-1]
        if leaf in _NOT_STREAMED:
            continue
        if s["E"] and leaf in _EXPERT_LEAVES:
            b = _routable(b, s, active)
        need += b
    return need


def matmul_params_per_token(cfg: Dict[str, Any]) -> int:
    """Matmul weights one token's forward pass multiplies by, in a layer
    stack: attention projections, the MLP (for Mixtral the router and the
    routed top-k experts, the useful work), without the output head."""
    s = dims(cfg)
    attn = s["D"] * (s["H"] + 2 * s["KH"]) * s["hd"] + s["H"] * s["hd"] * s["D"]
    mlp = 3 * s["D"] * s["M"]
    if s["E"]:
        mlp = mlp * s["K"] + s["D"] * s["E"]
    return s["L"] * (attn + mlp)


def prefill_chunk_flops(cfg: Dict[str, Any], tokens: int, offset: int) -> float:
    """FLOPs a prefill chunk of `tokens` real tokens at absolute positions
    offset.. needs: 2 per multiply-add of the matmuls, causal attention
    against the real context (query q sees offset + q + 1 keys: QK^T and
    PV), and the output head for the one row whose logits are used."""
    s = dims(cfg)
    flops = 2.0 * tokens * matmul_params_per_token(cfg)
    keys = tokens * offset + tokens * (tokens + 1) / 2
    flops += s["L"] * 4.0 * s["H"] * s["hd"] * keys
    flops += 2.0 * s["D"] * s["V"]
    return flops
