"""Plain float32 reference for the EXAONE-MoE decoder family.

Written from the published configuration (`model_type` exaone_moe) and the
EXAONE 4.0 family's conventions, in straightforward `jax.numpy`, float32
with `jax.default_matmul_precision("highest")`. It imports nothing of the
program and takes nothing the program has made: it reads the harness's own
seeded weight tree (`harness/weights.py` from the table of
`benchmarks/families/exaone_moe.py`, whose `dims` it shares) and
dequantizes one layer at a time. `pos` are absolute positions:

    x = embed[tokens]
    per layer: h = rmsnorm(x) * g_attn
               q, k, v = h Wq, h Wk, h Wv; q, k = rmsnorm over the head
               dimension, * g_q, * g_k
               window layer: rotary on q, k (HF rotate-half); key j visible
                             to query i iff 0 <= i - j < sliding_window
               global layer: no rotary; key j visible iff j <= i
               x += softmax(q k^T / sqrt(d)) v Wo        (grouped KV heads)
               h = rmsnorm(x) * g_mlp
               dense layer:  x += (silu(h Wg) * (h Wu)) Wd
               sparse layer: s = sigmoid(h Wr)                 [all experts]
                             C = the k experts of largest s + b
                             w_e = factor * s_e / (sum_{c in C} s_c + 1e-20)
                             x += sum_{e in C, e held} w_e E_e(h) + E_shared(h)
    logits = (rmsnorm(x) * g_out) W_head

No cache, no batching, no kernels. Attention runs in blocks of query rows
(a window layer's block sees only the keys its window can reach), so the
scores of a 4k-token sequence fit; the held experts run one after another
in a loop over the stack (one expert's float32 weights at a time).

A configuration that holds one chip's share (families/exaone_moe.py) gives
this reference the same share: the sum over the chosen experts skips those
not held (the partial sum one rank of expert parallelism passes on), and
the vocabulary is the slice. With every expert held it is the whole model.

Departures from the published model: the two block norms are applied
before each sub-layer (the configuration's `assumed` says why); the
multi-token-prediction layer is not part of the forward pass. Weights are
the benchmark's seeded ones (int8 with per-channel scales, dequantized
exactly), since the cell states weight-only int8.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.families.exaone_moe import DENSE, WINDOW, dims as model_dims

QUERY_BLOCK = 512


def _dq(leaf):
    """float32 values of a weight leaf ({"q","scale"} or an array)."""
    if not isinstance(leaf, dict):
        return leaf.astype(jnp.float32)
    return leaf["q"].astype(jnp.float32) * leaf["scale"]


def _at(leaf, i):
    if isinstance(leaf, dict):
        return {k: v[i] for k, v in leaf.items()}
    return leaf[i]


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


def attention(q, k, v, block: int, window: Optional[int] = None):
    """q [T, H, d], k/v [T, KH, d], row = position, in query blocks. Key j
    is visible to query i iff j <= i and, with a window, i - j < window."""
    t, h, d = q.shape
    kh = k.shape[1]
    q = q.reshape(t, kh, h // kh, d)
    out = []
    for start in range(0, t, block):
        stop = min(start + block, t)
        lo = 0 if window is None else max(0, start - window + 1)
        s = jnp.einsum("bkgd,skd->kgbs", q[start:stop], k[lo:stop]) / np.sqrt(d)
        gap = jnp.arange(start, stop)[:, None] - jnp.arange(lo, stop)[None, :]
        seen = gap >= 0
        if window is not None:
            seen &= gap < window
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        out.append(jnp.einsum("kgbs,skd->bkgd", p, v[lo:stop])
                   .reshape(stop - start, h, d))
    return jnp.concatenate(out, 0)


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
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
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


def shared_part(h, mw):
    return _gated(h, mw["shared_gate"], mw["shared_up"], mw["shared_down"])


@partial(jax.jit, static_argnames=("kinds", "dims_items", "theta", "eps",
                                   "factor", "norm", "block"))
def layer(x, lw, mw, positions, *, kinds, dims_items, theta, eps, factor,
          norm, block):
    """One layer: lw its `layers/` leaves, mw its `dense/` or `moe/` ones;
    kinds = (attention kind, MLP kind)."""
    dims = dict(dims_items)
    h = _rmsnorm(x, lw["attn_norm"], eps)
    t, hd = x.shape[0], dims["hd"]
    q = (h @ _dq(lw["wq"]).T).reshape(t, dims["H"], hd)  # stored [H hd, D]
    k = (h @ _dq(lw["wk"]).T).reshape(t, dims["KH"], hd)
    v = (h @ _dq(lw["wv"]).T).reshape(t, dims["KH"], hd)
    q = _rmsnorm(q, lw["q_norm"], eps)
    k = _rmsnorm(k, lw["k_norm"], eps)
    if kinds[0] == WINDOW:
        q, k = _rotary(q, positions, theta), _rotary(k, positions, theta)
        a = attention(q, k, v, block, dims["W"])
    else:
        a = attention(q, k, v, block)
    x = x + a.reshape(t, dims["H"] * hd) @ _dq(lw["wo"])
    h = _rmsnorm(x, lw["mlp_norm"], eps)
    if kinds[1] == DENSE:
        return x + _gated(h, mw["w_gate"], mw["w_up"], mw["w_down"])
    return x + routed_part(h, mw, dims, factor, norm) + shared_part(h, mw)


@partial(jax.jit, static_argnames=("eps",))
def _head(x, out_norm, lm_head, *, eps):
    return _rmsnorm(x, out_norm, eps) @ _dq(lm_head)


def logits_at(weights: Dict[str, Any], cfg: Dict[str, Any],
              tokens: Sequence[int], rows: Sequence[int],
              pad_to: Optional[int] = None,
              block: int = QUERY_BLOCK) -> jnp.ndarray:
    """float32 logits [len(rows), vocab] of one sequence at the given
    positions. The sequence is right-padded (a real row never sees the
    padding behind it): to a multiple of `pad_to`, or by default to 1,024,
    2,048 or 4,096 tokens and multiples of 4,096 beyond, so that a cell
    compiles three programs a kind of layer and no more."""
    dims = model_dims(cfg)
    t = len(tokens)
    if pad_to is not None:
        padded = -(-t // pad_to) * pad_to
    else:
        padded = next((n for n in (1024, 2048) if t <= n),
                      -(-t // 4096) * 4096)
    ids = np.zeros((padded,), np.int32)
    ids[:t] = np.asarray(tokens, np.int32)
    positions = jnp.arange(padded, dtype=jnp.int32)
    seen = {DENSE: 0, "sparse": 0}
    with jax.default_matmul_precision("highest"):
        x = weights["tok_embed"][jnp.asarray(ids)].astype(jnp.float32)
        for l, kinds in enumerate(zip(dims["attn"], dims["mlp"])):
            stack = weights["dense" if kinds[1] == DENSE else "moe"]
            i = seen[kinds[1]]
            seen[kinds[1]] += 1
            x = layer(
                x, jax.tree.map(lambda a: a[l], weights["layers"]),
                jax.tree.map(lambda a: a[i], stack), positions, kinds=kinds,
                dims_items=tuple(sorted(dims.items())),
                theta=float(cfg["rope_parameters"]["rope_theta"]),
                eps=float(cfg["rms_norm_eps"]),
                factor=float(cfg["routed_scaling_factor"]),
                norm=bool(cfg["norm_topk_prob"]), block=min(block, padded),
            )
        xr = x[jnp.asarray(np.asarray(rows, np.int32))]
        return _head(xr, weights["out_norm"], weights["lm_head"],
                     eps=float(cfg["rms_norm_eps"]))


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
