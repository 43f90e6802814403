"""Operations and bytes a step needs, computed from shapes.

These are the least work the algorithm asks of the chip, not what the
current program executes: a roofline share built on them cannot pass 100 %
unless a count here is wrong.
"""
from __future__ import annotations

from typing import Any, Dict, Sequence

from benchmarks.harness.weights import leaf_table, model_dims

KV_ITEMSIZE = {"bfloat16": 2, "int8": 1}


def weight_bytes(cfg: Dict[str, Any]) -> Dict[str, int]:
    """Bytes of every leaf as stored: int8 values + float32 scales for a
    quantized leaf, bfloat16 otherwise."""
    out = {}
    for name, (shape, contr, _) in leaf_table(cfg).items():
        n = 1
        for d in shape:
            n *= d
        if contr:
            s = 1
            for i, d in enumerate(shape):
                s *= 1 if i in contr else d
            out[name] = n + 4 * s
        else:
            out[name] = 2 * n
    return out


def decode_step_bytes(cfg: Dict[str, Any], ctx_lens: Sequence[int],
                      kv_itemsize: int = 2) -> float:
    """Bytes one decode step must move, over all chips: every weight once
    (of the embedding only the rows of the active slots; of Mixtral's
    experts only as many as the active slots can route to), the real
    context of the active slots read once, and one new K/V row a slot
    written."""
    s = model_dims(cfg)
    wb = weight_bytes(cfg)
    active = len(ctx_lens)
    total = 0.0
    for name, b in wb.items():
        if name == "tok_embed":
            total += 2 * s["D"] * active
        elif s["E"] and name.split("/")[-1] in ("w_gate", "w_up", "w_down"):
            total += b * min(s["E"], active * s["K"]) / s["E"]
        else:
            total += b
    kv_row = s["L"] * 2 * s["KH"] * s["hd"] * kv_itemsize
    total += kv_row * (sum(int(c) for c in ctx_lens) + active)
    return total


def matmul_params_per_token(cfg: Dict[str, Any]) -> int:
    """Matmul weights one token's forward pass multiplies by, in a layer
    stack: attention projections, the MLP (for Mixtral the router and the
    routed top-k experts, the useful work), without the output head."""
    s = model_dims(cfg)
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
    s = model_dims(cfg)
    flops = 2.0 * tokens * matmul_params_per_token(cfg)
    keys = tokens * offset + tokens * (tokens + 1) / 2
    flops += s["L"] * 4.0 * s["H"] * s["hd"] * keys
    flops += 2.0 * s["D"] * s["V"]
    return flops
