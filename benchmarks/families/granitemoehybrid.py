"""The Granite-4.0-H decoder family (Granite-4.0-H-Micro: `granitemoehybrid`
with no experts), as the harness needs it.

Everything the benchmark knows about this family's block lives here and in
its reference (`benchmarks/reference/granitemoehybrid.py`); see
`benchmarks/families/llama_family.py` for what a family file gives. Plain
functions of the configuration's dict; nothing of the program is imported.

The block (substratus_tpu/models/granitemoehybrid.py): `layer_types` says
which layers' operator is a Mamba-2 mixer (one group; for each decode slot
and layer a float32 state of `mamba_d_state` x `mamba_n_heads` x
`mamba_d_head` values and `mamba_d_conv` - 1 rows of the convolution's
input, no pages) and which full attention (grouped, no bias, no norm, NO
rotation, the scale `attention_multiplier`; the paged pool); every layer's
feed-forward part is one gated MLP of `shared_intermediate_size`; the
embedding is scaled by `embedding_multiplier`, each branch by
`residual_multiplier`, the logits by 1 / `logits_scaling`; the output head
is the embedding.

The configuration of this family's one cell holds the model whole: nothing
is cut, `num_hidden_layers` is the model's own.

The counts below are of what the equations need, not of what the program
does, so a share built on them cannot pass 100 % and a later kernel is
measured by the same yardstick.
"""
from __future__ import annotations

from typing import Any, Dict, Sequence, Tuple

from benchmarks.harness.counts import weight_bytes
from benchmarks.harness.weights import Leaf

MAMBA, ATTN = "mamba", "attention"

# Regions this block opens beyond the base vocabulary
# (substratus_tpu/ops/scopes.py::SSM, and CONV's `conv.state`): a mixer's
# input projection, its convolution (rows, taps, bias, SiLU), its per-slot
# state's step (in a chunk the carried state's read-out and the state's
# update), a chunk's in-block scores and their product with dt x, the gated
# norm and the output projection.
SCOPES: Tuple[str, ...] = ("ssm.in", "conv.state", "ssm.state", "ssm.intra",
                           "ssm.out")
# Regions whose time is a stream of weights.
MATMUL_SCOPES = ("ssm.in", "ssm.out", "attn.qkv", "attn.out", "mlp",
                 "lm_head")

# Leaves no matmul region streams: the norms scale activations, the taps
# and their bias are read in `conv.state`, the three vectors a head in
# `ssm.state`. The embedding is read whole by `lm_head` (the head is tied
# to it), so it is streamed.
_NOT_STREAMED = ("out_norm", "layers/input_norm", "layers/post_norm",
                 "ssm/taps", "ssm/conv_bias", "ssm/a_log", "ssm/d_skip",
                 "ssm/dt_bias", "ssm/norm")
STATE_ITEMSIZE = 4  # the state is float32 (the configuration's `precision`)


def dims(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes a builder needs, from the published keys."""
    n = int(cfg["num_hidden_layers"])
    ops = tuple(cfg["layer_types"][:n])
    d, h = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    hm, p = int(cfg["mamba_n_heads"]), int(cfg["mamba_d_head"])
    ns = int(cfg["mamba_d_state"])
    if (int(cfg["mamba_n_groups"]) != 1 or int(cfg["num_local_experts"])
            or hm * p != int(cfg["mamba_expand"]) * d):
        raise ValueError("granitemoehybrid: one group, no experts and an "
                         "inner width of mamba_expand x hidden_size are "
                         "what this family file counts")
    return {
        "D": d,
        "H": h,
        "KH": int(cfg["num_key_value_heads"]),
        "hd": int(cfg.get("head_dim") or d // h),
        "M": int(cfg["shared_intermediate_size"]),
        "V": int(cfg["vocab_size"]),
        "L": n,
        "ops": ops,
        "Lm": ops.count(MAMBA), "La": ops.count(ATTN),
        "Hm": hm, "P": p, "N": ns, "T": int(cfg["mamba_d_conv"]),
        "E": hm * p,  # the mixer's inner width
        "W": hm * p + 2 * ns,  # the convolution's channels, [x; B; C]
        "block": int(cfg["mamba_chunk_size"]),
    }


def leaf_table(cfg: Dict[str, Any]) -> Dict[str, Leaf]:
    """The weight tree: `layers/` holds every layer's two norms and its
    MLP, `ssm/` the mixers of the Mamba layers, `attn/` the projections of
    the attention layers; each stack's layer dim leads. Matmul weights are
    int8 with a scale per output channel; embedding (the head too), norms,
    taps and `D` bfloat16; the taps' bias, `A_log` and `dt_bias` float32,
    drawn around zero (the configuration's `assumed.dt_shift` is the
    last one's mean; `A` = exp(0 +- 0.01) = 1 stands as drawn).

    The embedding's spread is the one choice here that the tied head and
    `embedding_multiplier` force: the stream starts as 12 x a token's row
    and ends against the same rows, so the current token's own logit
    stands `12 |row|^2 / rms(x_L)` above the rest, and with rows drawn at
    fan-in hidden_size (LFM2's choice) that is six spreads of the logits:
    every served token would be the prompt's last one again, whatever the
    layers computed, and `correct` would compare nothing (the first
    rehearsal read a gap of exactly 0 with int4 weights). The rows are
    drawn so that it is about one spread: |row| = r / 12, r^2 the variance
    the layers add to the stream as reckoned from the multipliers
    (`_layers_variance`); the logits then spread by r / 96 (0.016 at the
    published depth), and the limits of `correct` are read at that scale."""
    s = dims(cfg)
    D, H, KH, hd, M, V, L = (s[k] for k in "D H KH hd M V L".split())
    Lm, La, Hm, N, T, E, W = (s[k] for k in "Lm La Hm N T E W".split())
    embed_fan_in = round(D * float(cfg["embedding_multiplier"]) ** 2
                         / _layers_variance(cfg, s))
    t: Dict[str, Leaf] = {
        "tok_embed": Leaf((V, D), (), embed_fan_in, "normal"),
        "out_norm": Leaf((D,), (), 0, "norm"),
        "layers/input_norm": Leaf((L, D), (), 0, "norm", True),
        "layers/post_norm": Leaf((L, D), (), 0, "norm", True),
        "layers/w_gate": Leaf((L, D, M), (1,), D, "int8", True),
        "layers/w_up": Leaf((L, D, M), (1,), D, "int8", True),
        "layers/w_down": Leaf((L, M, D), (1,), M, "int8", True),
    }
    if Lm:
        t["ssm/w_in"] = Leaf((Lm, D, E + W + Hm), (1,), D, "int8", True)
        t["ssm/taps"] = Leaf((Lm, T, W), (), T, "normal", True)
        t["ssm/conv_bias"] = Leaf((Lm, W), (), 0, "bias", True)
        t["ssm/a_log"] = Leaf((Lm, Hm), (), 0, "bias", True)
        t["ssm/d_skip"] = Leaf((Lm, Hm), (), 0, "norm", True)
        t["ssm/dt_bias"] = Leaf((Lm, Hm), (), 0, "bias", True)
        t["ssm/norm"] = Leaf((Lm, E), (), 0, "norm", True)
        t["ssm/w_out"] = Leaf((Lm, E, D), (1,), E, "int8", True)
    if La:
        # as the program stores them: heads x head size one dim, for q, k
        # and v ahead of the contracted one
        t["attn/wq"] = Leaf((La, H * hd, D), (2,), D, "int8", True)
        t["attn/wk"] = Leaf((La, KH * hd, D), (2,), D, "int8", True)
        t["attn/wv"] = Leaf((La, KH * hd, D), (2,), D, "int8", True)
        t["attn/wo"] = Leaf((La, H * hd, D), (1,), H * hd, "int8", True)
    return t


def _layers_variance(cfg: Dict[str, Any], s: Dict[str, Any]) -> float:
    """The variance seeded layers add to a value of the residual stream,
    reckoned: each branch is scaled by `residual_multiplier`; a mixer's
    output has unit variance (a normed vector through fan-in weights), an
    MLP's 0.36 (E[silu(a)^2] for a unit a, times a unit b), an attention
    layer's next to none (a near-uniform softmax averages v away)."""
    return float(cfg["residual_multiplier"]) ** 2 * (
        1.36 * s["Lm"] + 0.36 * s["La"])


def program(cfg: Dict[str, Any]) -> Tuple[str, Dict[str, Any]]:
    """models/registry.py "granitemoehybrid", models/granitemoehybrid.py::
    GraniteHybridConfig, from the published keys and `assumed`'s two
    constants."""
    s = dims(cfg)
    return "granitemoehybrid", dict(
        vocab_size=s["V"], dim=s["D"], n_layers=s["L"], n_heads=s["H"],
        n_kv_heads=s["KH"], head_dim=s["hd"], hidden_dim=s["M"],
        layer_types=s["ops"], mamba_n_heads=s["Hm"], mamba_d_head=s["P"],
        mamba_d_state=s["N"], mamba_d_conv=s["T"],
        mamba_n_groups=int(cfg["mamba_n_groups"]),
        embedding_multiplier=float(cfg["embedding_multiplier"]),
        residual_multiplier=float(cfg["residual_multiplier"]),
        attention_multiplier=float(cfg["attention_multiplier"]),
        logits_scaling=float(cfg["logits_scaling"]),
        norm_eps=float(cfg["rms_norm_eps"]),
        max_seq_len=int(cfg["max_position_embeddings"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        dt_shift=float(cfg["assumed"]["dt_shift"]),
    )


# -- the least work a step asks of the chip, from shapes -------------------------

def decode_matmul_weight_bytes(cfg: Dict[str, Any], active: int) -> float:
    """Bytes of the weights one decode step's matmul regions must read,
    each once: every leaf but the norms, the taps and the vectors a head
    (the embedding is the head's weight)."""
    return float(sum(b for name, b in weight_bytes(leaf_table(cfg)).items()
                     if name not in _NOT_STREAMED))


def _state_bytes(s: Dict[str, Any]) -> int:
    """A slot's state, read and written once a Mamba layer."""
    return 2 * s["Lm"] * s["N"] * s["E"] * STATE_ITEMSIZE


def _conv_rows_bytes(s: Dict[str, Any], itemsize: int) -> int:
    """A slot's convolution rows, read and written once a Mamba layer."""
    return 2 * s["Lm"] * (s["T"] - 1) * s["W"] * itemsize


def decode_ssm_bytes(cfg: Dict[str, Any], active: int,
                     act_itemsize: int = 2) -> float:
    """Bytes the state's step must move in a decode step: `S` of the active
    slots in every Mamba layer once read and once written at the stated
    type, and for each such row and layer `x`, `B`, `C` read, `dt`
    (float32) read and the output (float32) written."""
    s = dims(cfg)
    acts = s["Lm"] * ((s["E"] + 2 * s["N"]) * act_itemsize
                      + 4 * s["Hm"] + 4 * s["E"])
    return float(active * (_state_bytes(s) + acts))


def decode_step_bytes(cfg: Dict[str, Any], ctx_lens: Sequence[int],
                      kv_itemsize: int = 2) -> float:
    """Bytes one decode step must move: the streamed weights (the embedding
    among them, once, for the head), the active slots' state and
    convolution rows read and written, the whole context of the active
    slots read once in every attention layer and one new K/V row a slot
    and attention layer written."""
    s = dims(cfg)
    active = len(ctx_lens)
    row = 2 * s["KH"] * s["hd"] * kv_itemsize
    total = decode_matmul_weight_bytes(cfg, active)
    total += active * (_state_bytes(s) + _conv_rows_bytes(s, kv_itemsize))
    for c in ctx_lens:
        total += row * s["La"] * (int(c) + 1)
    return total


def matmul_params_per_token(cfg: Dict[str, Any]) -> float:
    """Matmul weights one token's forward pass multiplies by in the layer
    stack: a mixer's two projections, an attention layer's four, every
    layer's MLP."""
    s = dims(cfg)
    mixer = s["D"] * (s["E"] + s["W"] + s["Hm"]) + s["E"] * s["D"]
    attn = s["D"] * (s["H"] + 2 * s["KH"]) * s["hd"] + s["H"] * s["hd"] * s["D"]
    return s["Lm"] * mixer + s["La"] * attn + s["L"] * 3 * s["D"] * s["M"]


def chunk_ssm_flops(cfg: Dict[str, Any], tokens: int) -> float:
    """FLOPs the state-space recurrence needs in a prefill chunk of
    `tokens` real tokens that carries a state in and out, 2 per
    multiply-add, every Mamba layer: the carried state's read-out `S C` and
    the state's update `u B^T` for every token (N x H x P each), and the
    in-chunk part within blocks of the published `mamba_chunk_size` (the
    scores `C . B`, N wide and shared by all heads, and the weights'
    product with `dt x`, H x P wide, over the j <= i half of each
    block)."""
    s = dims(cfg)
    state = 2 * 2.0 * tokens * s["N"] * s["E"]
    full, rest = divmod(tokens, s["block"])
    pairs = (full * s["block"] * (s["block"] + 1) / 2
             + rest * (rest + 1) / 2)
    intra = 2.0 * pairs * (s["N"] + s["E"])
    return s["Lm"] * (state + intra)


def prefill_chunk_flops(cfg: Dict[str, Any], tokens: int, offset: int) -> float:
    """FLOPs a prefill chunk of `tokens` real tokens at absolute positions
    offset.. needs: 2 per multiply-add of the matmuls, attention against
    the offset + q + 1 keys each query may see, the recurrence (the same at
    every offset: it reads a state, not a context), the taps, and the head
    for one row."""
    s = dims(cfg)
    flops = 2.0 * tokens * matmul_params_per_token(cfg)
    seen = tokens * offset + tokens * (tokens + 1) / 2
    flops += 4.0 * s["H"] * s["hd"] * s["La"] * seen
    flops += chunk_ssm_flops(cfg, tokens)
    flops += 2.0 * tokens * s["Lm"] * s["T"] * s["W"]
    flops += 2.0 * s["D"] * s["V"]
    return flops
