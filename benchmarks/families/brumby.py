"""The Brumby decoder family (Brumby-14B-Base), as the harness needs it.

Everything the benchmark knows about this family's block lives here and in
its reference (`benchmarks/reference/brumby.py`); see
`benchmarks/families/llama_family.py` for what a family file gives. Plain
functions of the configuration's dict; nothing of the program is imported.

The block (substratus_tpu/models/brumby.py): Qwen3's, pre-norm with
RMSNorm over the head dimension of q and k and rotary on both, a gated MLP
and an output head of its own, and in every layer power retention of
degree 2 where attention was: a learned gate a KV head and token, and for
each decode slot, layer and KV head a float32 state `S [F, hd]` and `z
[F]`, `F = hd (hd + 1) / 2`, in place of any key or value. No pages, no
window, no experts.

A configuration may hold one stage of a pipeline (model-configs guide,
section 4): `num_hidden_layers` counts the layers held, `published` gives
the model's own count. Every layer held is whole.

The counts below are of what the equations need, not of what the program
does, so a share built on them cannot pass 100 % and a later kernel is
measured by the same yardstick.
"""
from __future__ import annotations

from typing import Any, Dict, Sequence, Tuple

from benchmarks.harness.counts import weight_bytes
from benchmarks.harness.weights import Leaf

# Regions this block opens beyond the base vocabulary
# (substratus_tpu/ops/scopes.py::RET): the per-slot state's step (in a
# chunk the carried state's read-out and the state's update), and a
# chunk's in-chunk scores under the decay with their product with v.
SCOPES: Tuple[str, ...] = ("ret.state", "ret.intra")
# Regions whose time is a stream of weights.
MATMUL_SCOPES = ("attn.qkv", "attn.out", "mlp", "lm_head")

# bfloat16 and float32 leaves no matmul region streams: the embedding's
# rows are gathered, the norms scale activations. (The gate's projection
# and bias are read in `attn.qkv` and are streamed.)
_NOT_STREAMED = ("tok_embed", "out_norm", "layers/input_norm",
                 "layers/post_norm", "layers/q_norm", "layers/k_norm")
STATE_ITEMSIZE = 4  # the state is float32 (the configuration's `precision`)


def dims(cfg: Dict[str, Any]) -> Dict[str, int]:
    """The sizes a builder needs, from the published keys."""
    d, h = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    hd = int(cfg.get("head_dim") or d // h)
    return {
        "D": d,
        "H": h,
        "KH": int(cfg["num_key_value_heads"]),
        "hd": hd,
        "F": hd * (hd + 1) // 2,
        "M": int(cfg["intermediate_size"]),
        "V": int(cfg["vocab_size"]),
        "L": int(cfg["num_hidden_layers"]),
    }


def leaf_table(cfg: Dict[str, Any]) -> Dict[str, Leaf]:
    """The weight tree: one stack, "layers/<name>" with the layer dim
    leading, between the embedding and the output norm and head. Matmul
    weights are int8 with a scale per output channel; embedding, norms and
    the gate's projection bfloat16; the gate's bias float32 (drawn around
    zero: the configuration's `assumed.gate_shift` is its mean)."""
    s = dims(cfg)
    D, H, KH, hd, M, V, L = (s[k] for k in "D H KH hd M V L".split())
    return {
        "tok_embed": Leaf((V, D), (), 1, "normal"),
        "out_norm": Leaf((D,), (), 0, "norm"),
        "lm_head": Leaf((D, V), (0,), D, "int8"),
        "layers/input_norm": Leaf((L, D), (), 0, "norm", True),
        "layers/post_norm": Leaf((L, D), (), 0, "norm", True),
        "layers/q_norm": Leaf((L, hd), (), 0, "norm", True),
        "layers/k_norm": Leaf((L, hd), (), 0, "norm", True),
        # as the program stores them: heads x head size one dim, for q, k
        # and v ahead of the contracted one
        "layers/wq": Leaf((L, H * hd, D), (2,), D, "int8", True),
        "layers/wk": Leaf((L, KH * hd, D), (2,), D, "int8", True),
        "layers/wv": Leaf((L, KH * hd, D), (2,), D, "int8", True),
        "layers/wo": Leaf((L, H * hd, D), (1,), H * hd, "int8", True),
        "layers/w_gamma": Leaf((L, D, KH), (), D, "normal", True),
        "layers/b_gamma": Leaf((L, KH), (), 0, "bias", True),
        "layers/w_gate": Leaf((L, D, M), (1,), D, "int8", True),
        "layers/w_up": Leaf((L, D, M), (1,), D, "int8", True),
        "layers/w_down": Leaf((L, M, D), (1,), M, "int8", True),
    }


def program(cfg: Dict[str, Any]) -> Tuple[str, Dict[str, Any]]:
    """models/registry.py "brumby", models/brumby.py::BrumbyConfig, from
    the published keys and `assumed.gate_shift`."""
    s = dims(cfg)
    return "brumby", dict(
        vocab_size=s["V"], dim=s["D"], n_layers=s["L"], n_heads=s["H"],
        n_kv_heads=s["KH"], head_dim=s["hd"], hidden_dim=s["M"],
        rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]),
        max_seq_len=int(cfg["max_position_embeddings"]),
        gate_shift=float(cfg["assumed"]["gate_shift"]),
    )


# -- the least work a step asks of the chip, from shapes -------------------------

def decode_matmul_weight_bytes(cfg: Dict[str, Any], active: int) -> float:
    """Bytes of the weights one decode step's matmul regions must read,
    each once: every leaf but the embedding table and the norms."""
    return float(sum(b for name, b in weight_bytes(leaf_table(cfg)).items()
                     if name not in _NOT_STREAMED))


def decode_retention_bytes(cfg: Dict[str, Any], active: int,
                           act_itemsize: int = 2) -> float:
    """Bytes the state's step must move in a decode step: `S` and `z` of
    the active slots in every layer once read and once written at the
    stated type, and for each such row and layer `q`, `k`, `v` read, `log
    g` (float32) read and the output written."""
    s = dims(cfg)
    state = 2 * s["KH"] * s["F"] * (s["hd"] + 1) * STATE_ITEMSIZE
    acts = ((2 * s["H"] + 2 * s["KH"]) * s["hd"] * act_itemsize
            + 4 * s["KH"])
    return float(active * s["L"] * (state + acts))


def decode_step_bytes(cfg: Dict[str, Any], ctx_lens: Sequence[int],
                      kv_itemsize: int = 2) -> float:
    """Bytes one decode step must move: the streamed weights and the active
    slots' state read and written. No term follows a slot's context: the
    family keeps no key or value (`ctx_lens` gives the count of slots)."""
    active = len(ctx_lens)
    return (decode_matmul_weight_bytes(cfg, active)
            + decode_retention_bytes(cfg, active, kv_itemsize))


def matmul_params_per_token(cfg: Dict[str, Any]) -> float:
    """Matmul weights one token's forward pass multiplies by in the layer
    stack: the four projections, the gate's and the MLP."""
    s = dims(cfg)
    proj = 2 * s["D"] * (s["H"] + s["KH"]) * s["hd"] + s["D"] * s["KH"]
    return s["L"] * (proj + 3 * s["D"] * s["M"])


def chunk_retention_flops(cfg: Dict[str, Any], tokens: int) -> float:
    """FLOPs the retention operator needs in a prefill chunk of `tokens`
    real tokens that carries a state in and out, 2 per multiply-add, a
    layer: the carried state's read-out `phi(q)^T S` and `phi(q) . z` for
    every query head, the state's update `phi(k) v^T` and `phi(k)` for
    every KV head, and the in-chunk part (scores q . k and the weights'
    product with v over the j <= i half)."""
    s = dims(cfg)
    H, KH, F, hd = s["H"], s["KH"], s["F"], s["hd"]
    read_out = 2.0 * tokens * H * F * (hd + 1)
    update = 2.0 * tokens * KH * F * (hd + 1)
    intra = 2.0 * H * (tokens * (tokens + 1) / 2) * 2 * hd
    return s["L"] * (read_out + update + intra)


def prefill_chunk_flops(cfg: Dict[str, Any], tokens: int, offset: int) -> float:
    """FLOPs a prefill chunk of `tokens` real tokens needs: 2 per
    multiply-add of the matmuls, the retention operator (the same at every
    offset: it reads a state, not a context) and the head for one row."""
    s = dims(cfg)
    return (2.0 * tokens * matmul_params_per_token(cfg)
            + chunk_retention_flops(cfg, tokens) + 2.0 * s["D"] * s["V"])
