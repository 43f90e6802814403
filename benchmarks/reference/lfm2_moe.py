"""Plain float32 reference for the LFM2-MoE decoder family.

Written from the published configuration (`model_type` lfm2_moe) and the
LFM2 family's conventions, in straightforward `jax.numpy`, float32 with
`jax.default_matmul_precision("highest")`. It imports nothing of the
program and takes nothing the program has made: it reads the harness's own
seeded weight tree (`harness/weights.py` from the table of
`benchmarks/families/lfm2_moe.py`, whose `dims` it shares) and dequantizes
one layer at a time. One sequence `x [T, D]` from position 0, `eps` =
`norm_eps`:

    x = embed[tokens]
    per layer l: h = rmsnorm(x) * g_operator
      conv:      [B, C, X] = split3(h W_in)            (W_in [D, 3D], no bias)
                 u = B * X
                 v_t = sum_{j=0..L-1} w[j] * u_{t-(L-1)+j},  u_s = 0 for s < 0
                 x += (C * v) W_out                    (depthwise, causal)
      attention: q, k, v = h Wq, h Wk, h Wv; q, k = rmsnorm over the head
                 dimension, * g_q, * g_k; rotary on q, k (HF rotate-half)
                 x += softmax(q k^T / sqrt(d)) v Wo    (causal, grouped KV)
      h = rmsnorm(x) * g_ffn
      l < num_dense_layers: x += (silu(h W1) * (h W3)) W2
      otherwise: s = sigmoid(h Wg)                     [all experts, float32]
                 C = the k experts of largest s + b
                 w_e = factor * s_e / (sum_{c in C} s_c + 1e-6)
                 x += sum_{e in C, e held} w_e E_e(h)  (no shared expert)
    logits = (rmsnorm(x) * g_out) embed^T              (the head is tied)

No cache, no batching, no kernels, no state: the convolution is computed
over the whole sequence by shifting it. Attention runs in blocks of query
rows, so the scores of a long sequence fit; the held experts run one after
another in a loop over the stack (one expert's float32 weights at a time).

Departures from the published model, each assumed (the configuration's
`assumed`): the order B, C, X of W_in's thirds, the tied head, the 1e-6
of the normaliser. Weights are the benchmark's seeded ones (int8 with
per-channel scales, dequantized exactly), since the cell states weight-only
int8.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.families.lfm2_moe import CONV, DENSE, dims as model_dims

QUERY_BLOCK = 512
ROUTE_NORM_EPS = 1e-6


def _dq(leaf):
    """float32 values of a weight leaf ({"q","scale"} or an array)."""
    if not isinstance(leaf, dict):
        return leaf.astype(jnp.float32)
    return leaf["q"].astype(jnp.float32) * leaf["scale"]


def _rmsnorm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)


def _rotary(x, positions, theta):
    """x [T, heads, d]; HF rotate-half convention."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, block: int):
    """q [T, H, d], k/v [T, KH, d], row = position, in query blocks. Key j
    is visible to query i iff j <= i."""
    t, h, d = q.shape
    kh = k.shape[1]
    q = q.reshape(t, kh, h // kh, d)
    out = []
    for start in range(0, t, block):
        stop = min(start + block, t)
        s = jnp.einsum("bkgd,skd->kgbs", q[start:stop], k[:stop]) / np.sqrt(d)
        seen = jnp.arange(start, stop)[:, None] >= jnp.arange(stop)[None, :]
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        out.append(jnp.einsum("kgbs,skd->bkgd", p, v[:stop])
                   .reshape(stop - start, h, d))
    return jnp.concatenate(out, 0)


def short_conv(h, cw):
    """The gated short convolution over a whole sequence h [T, D]; cw the
    layer's `conv/` leaves (taps [L, D], tap j of the equation row j)."""
    b, c, x = jnp.split(h @ _dq(cw["w_in"]), 3, axis=-1)
    u = b * x
    w = cw["taps"].astype(jnp.float32)
    taps, t = w.shape[0], u.shape[0]
    v = jnp.zeros_like(u)
    for j in range(taps):
        back = taps - 1 - j  # tap j reads u at t - back
        v = v + w[j] * jnp.pad(u, ((back, 0), (0, 0)))[:t]
    return (c * v) @ _dq(cw["w_out"])


def _gated(h, gate, up, down):
    act = jax.nn.silu(h @ _dq(gate)) * (h @ _dq(up))
    return act @ _dq(down)


def route(h, router, bias, k: int, factor: float, norm: bool = True):
    """h [T, D] -> per-token weight of every expert [T, E]: zero where not
    chosen. The bias chooses and is no part of the weight."""
    s = jax.nn.sigmoid(h @ router.astype(jnp.float32))
    _, chosen = jax.lax.top_k(s + bias.astype(jnp.float32), k)
    w = jnp.take_along_axis(s, chosen, axis=-1)
    if norm:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + ROUTE_NORM_EPS)
    rows = jnp.arange(h.shape[0])[:, None]
    return jnp.zeros_like(s).at[rows, chosen].set(w * factor)


def routed_part(h, mw, dims, factor: float, norm: bool):
    """The held experts' part of the sparse layer's sum: every token through
    every held expert, one expert after another, weighed by the router
    (zero where the token did not choose it)."""
    w = route(h, mw["router"], mw["router_bias"], dims["K"], factor, norm)
    held = w[:, dims["first"]:dims["first"] + dims["Eh"]]
    experts = {n: mw[n] for n in ("w_gate", "w_up", "w_down")}

    def one(y, e):
        expert = jax.tree.map(lambda a: a[e], experts)
        out = _gated(h, expert["w_gate"], expert["w_up"], expert["w_down"])
        return y + out * held[:, e][:, None], None

    y, _ = jax.lax.scan(one, jnp.zeros_like(h), jnp.arange(dims["Eh"]))
    return y


@partial(jax.jit, static_argnames=("kinds", "dims_items", "theta", "eps",
                                   "factor", "norm", "block"))
def layer(x, norms, ow, fw, positions, *, kinds, dims_items, theta, eps,
          factor, norm, block):
    """One layer: norms its `layers/` leaves, ow its `conv/` or `attn/`
    ones, fw its `dense/` or `moe/` ones; kinds = (operator, FFN kind)."""
    dims = dict(dims_items)
    h = _rmsnorm(x, norms["operator_norm"], eps)
    if kinds[0] == CONV:
        x = x + short_conv(h, ow)
    else:
        t, hd = x.shape[0], dims["hd"]
        q = (h @ _dq(ow["wq"]).T).reshape(t, dims["H"], hd)  # [H hd, D]
        k = (h @ _dq(ow["wk"]).T).reshape(t, dims["KH"], hd)
        v = (h @ _dq(ow["wv"]).T).reshape(t, dims["KH"], hd)
        q = _rotary(_rmsnorm(q, ow["q_norm"], eps), positions, theta)
        k = _rotary(_rmsnorm(k, ow["k_norm"], eps), positions, theta)
        a = attention(q, k, v, block)
        x = x + a.reshape(t, dims["H"] * hd) @ _dq(ow["wo"])
    h = _rmsnorm(x, norms["ffn_norm"], eps)
    if kinds[1] == DENSE:
        return x + _gated(h, fw["w_gate"], fw["w_up"], fw["w_down"])
    return x + routed_part(h, fw, dims, factor, norm)


@partial(jax.jit, static_argnames=("eps",))
def _head(x, out_norm, embed, *, eps):
    return _rmsnorm(x, out_norm, eps) @ embed.astype(jnp.float32).T


def logits_at(weights: Dict[str, Any], cfg: Dict[str, Any],
              tokens: Sequence[int], rows: Sequence[int],
              pad_to: Optional[int] = None,
              block: int = QUERY_BLOCK) -> jnp.ndarray:
    """float32 logits [len(rows), vocab] of one sequence at the given
    positions. The sequence is right-padded (a real row never sees the
    padding behind it: attention and the convolution are causal): to a
    multiple of `pad_to`, or by default to 1,024 or 2,048 tokens and
    multiples of 2,048 beyond, so that a cell compiles two programs a kind
    of layer and no more."""
    dims = model_dims(cfg)
    t = len(tokens)
    if pad_to is not None:
        padded = -(-t // pad_to) * pad_to
    else:
        padded = 1024 if t <= 1024 else -(-t // 2048) * 2048
    ids = np.zeros((padded,), np.int32)
    ids[:t] = np.asarray(tokens, np.int32)
    positions = jnp.arange(padded, dtype=jnp.int32)
    seen = {kind: 0 for kind in set(dims["ops"]) | set(dims["mlp"])}
    stack = {CONV: "conv", "full_attention": "attn", DENSE: "dense",
             "sparse": "moe"}
    with jax.default_matmul_precision("highest"):
        x = weights["tok_embed"][jnp.asarray(ids)].astype(jnp.float32)
        for l, kinds in enumerate(zip(dims["ops"], dims["mlp"])):
            i, j = seen[kinds[0]], seen[kinds[1]]
            seen[kinds[0]] += 1
            seen[kinds[1]] += 1
            x = layer(
                x, jax.tree.map(lambda a: a[l], weights["layers"]),
                jax.tree.map(lambda a: a[i], weights[stack[kinds[0]]]),
                jax.tree.map(lambda a: a[j], weights[stack[kinds[1]]]),
                positions, kinds=kinds,
                dims_items=tuple(sorted(dims.items())),
                theta=float(cfg["rope_parameters"]["rope_theta"]),
                eps=float(cfg["norm_eps"]),
                factor=float(cfg["routed_scaling_factor"]),
                norm=bool(cfg["norm_topk_prob"]), block=min(block, padded),
            )
        xr = x[jnp.asarray(np.asarray(rows, np.int32))]
        return _head(xr, weights["out_norm"], weights["tok_embed"],
                     eps=float(cfg["norm_eps"]))


def served_gaps(weights, cfg, prompt: Sequence[int], served: Sequence[int]):
    """How far each served token's reference logit lies below the
    reference's best, at its own position (teacher-forced on the served
    tokens). Returns a numpy array [len(served)]."""
    p, n = len(prompt), len(served)
    seq = list(prompt) + list(served[:-1])
    ref = logits_at(weights, cfg, seq, list(range(p - 1, p - 1 + n)))
    chosen = jnp.asarray(np.asarray(served, np.int32))
    got = jnp.take_along_axis(ref, chosen[:, None], axis=-1)[:, 0]
    return np.asarray(jnp.max(ref, axis=-1) - got)
