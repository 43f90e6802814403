"""Plain float32 reference for the DeepSeek-V3 decoder family (the language
model of dots.vlm1.inst).

Written from the published configuration's keys and the published
DeepSeek-V3 block, in straightforward `jax.numpy`, float32 with
`jax.default_matmul_precision("highest")`. It imports nothing of the
program and takes nothing the program has made: it reads the harness's own
seeded weight tree (`harness/weights.py` from the table of
`benchmarks/families/deepseek_v3.py`, whose `dims` it shares) and
dequantizes one layer at a time. `pos` are absolute positions; H heads,
dn = qk_nope_head_dim, dr = qk_rope_head_dim, dv = v_head_dim:

    x = embed[tokens]
    per layer: h = rmsnorm(x) * g_attn
               cq = rmsnorm(h W_DQ) * g_qa          [q_lora_rank]
               [q_nope_i (dn); q_rope_i (dr)] = (cq W_UQ)_i      a head
               [ckv_raw; kr_raw (dr)] = h W_DKV
               ckv = rmsnorm(ckv_raw) * g_kva        [kv_lora_rank]
               kr = rotary(kr_raw), one for all heads; q_rope_i =
               rotary(q_rope_i)      (rotate-half; YaRN's frequencies)
               [k_nope_i (dn); v_i (dv)] = (ckv W_UKV)_i     the EXPANDED
               form: every token's keys and values are made, none absorbed
               s_tj = scale (q_nope_i,t . k_nope_i,j + q_rope_i,t . kr_j),
               j <= t; scale = (dn + dr)^(-1/2) m^2,
               m = 0.1 mscale_all_dim ln(factor) + 1
               x += concat_i(softmax_j(s) v_i) W_O
               h = rmsnorm(x) * g_mlp
               dense layer:  x += (silu(h Wg) * (h Wu)) Wd
               sparse layer: s = sigmoid(h Wr)                 [all experts]
                             c = s + b; a group of E / n_group neighbours
                             scores the sum of its two largest c; the
                             topk_group best groups stay, c is masked to 0
                             outside them; C = the k experts of largest
                             masked c
                             w_e = factor * s_e / (sum_{c in C} s_c + 1e-20)
                             x += sum_{e in C, e held} w_e E_e(h) + E_shared(h)
    logits = (rmsnorm(x) * g_out) W_head

YaRN: pair i of dr / 2 turns at f_i = theta^(-2i/dr); lo, hi = the pair
indices at which the original positions make beta_fast and beta_slow whole
rotations (dr ln(P / (beta 2 pi)) / (2 ln theta), floor and ceiling,
clipped to [0, dr / 2 - 1]); ramp_i = clip((i - lo) / (hi - lo), 0, 1);
f'_i = f_i / factor * ramp_i + f_i (1 - ramp_i).

No cache, no batching, no kernels. Attention runs a few heads at a time and
in blocks of query rows, so that the expanded keys, the values and the
scores of a 13k-token sequence fit beside the weights; the held experts run one
after another in a loop over the stack.

A configuration that holds one chip's share (families/deepseek_v3.py)
gives this reference the same share: the sum over the chosen experts skips
those not held, and the vocabulary is the slice. With every expert held it
is the whole model.

Departures from the published model: the rotary pairs are de-interleaved
(a permutation of seeded weights); the multi-token-prediction layer and
dots.vlm1's vision tower are not part of the forward pass. Weights are the
benchmark's seeded ones (int8 with per-channel scales, dequantized
exactly), since the cell states weight-only int8.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.families.deepseek_v3 import (
    DENSE, dims as model_dims, softmax_scale,
)

QUERY_BLOCK = 2048
HEAD_GROUP = 4


def _dq(leaf):
    """float32 values of a weight leaf ({"q","scale"} or an array)."""
    if not isinstance(leaf, dict):
        return leaf.astype(jnp.float32)
    return leaf["q"].astype(jnp.float32) * leaf["scale"]


def _rmsnorm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)


def yarn_frequencies(d: int, theta: float, yarn) -> np.ndarray:
    """The d / 2 inverse frequencies; `yarn` = (factor, original positions,
    beta_fast, beta_slow, ...) or None for the plain table."""
    f = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    if not yarn or yarn[0] <= 1:
        return f.astype(np.float32)
    factor, original, beta_fast, beta_slow = yarn[:4]

    def pair_at(rotations):
        return d * math.log(original / (rotations * 2 * math.pi)) / (
            2 * math.log(theta))

    lo = max(math.floor(pair_at(beta_fast)), 0)
    hi = min(math.ceil(pair_at(beta_slow)), d // 2 - 1)
    if lo == hi:
        hi += 0.001
    ramp = np.clip((np.arange(d // 2) - lo) / (hi - lo), 0, 1)
    return (f / factor * ramp + f * (1 - ramp)).astype(np.float32)


def _rotary(x, positions, inv):
    """x [T, ..., d]; rotate-half over the last dimension."""
    d = x.shape[-1]
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (d // 2,)
    cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k_nope, kr, v, scale: float, block: int):
    """The expanded form for some heads. q [T, g, dn + dr], k_nope [T, g,
    dn], kr [T, dr], v [T, g, dv]; row = position; key j visible to query
    t iff j <= t. Query rows `block` at a time, each against the keys up
    to its own last row."""
    t = q.shape[0]
    dn = k_nope.shape[-1]
    rows = []
    for start in range(0, t, block):
        stop = min(start + block, t)
        s = (jnp.einsum("bgn,sgn->gbs", q[start:stop, :, :dn], k_nope[:stop])
             + jnp.einsum("bgr,sr->gbs", q[start:stop, :, dn:], kr[:stop])
             ) * scale
        seen = jnp.arange(stop)[None, :] <= jnp.arange(start, stop)[:, None]
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        rows.append(jnp.einsum("gbs,sgv->bgv", p, v[:stop]))
    return jnp.concatenate(rows, 0)  # [T, g, dv]


def _gated(h, gate, up, down):
    act = jax.nn.silu(h @ _dq(gate)) * (h @ _dq(up))
    return act @ _dq(down)


def route(h, router, bias, k: int, factor: float, norm: bool = True,
          groups: int = 1, groups_kept: int = 1):
    """h [T, D] -> per-token weight of every expert [T, E]: zero where not
    chosen. The bias chooses and is no part of the weight; with `groups` >
    1 the choice is limited to the `groups_kept` best groups."""
    s = jax.nn.sigmoid(h @ router.astype(jnp.float32))
    c = s + bias.astype(jnp.float32)
    if groups > 1:
        t, e = c.shape
        by_group = c.reshape(t, groups, e // groups)
        two, _ = jax.lax.top_k(by_group, 2)
        _, kept = jax.lax.top_k(two.sum(-1), groups_kept)
        stays = jnp.zeros((t, groups), bool).at[
            jnp.arange(t)[:, None], kept].set(True)
        c = jnp.where(stays[:, :, None], by_group, 0.0).reshape(t, e)
    _, chosen = jax.lax.top_k(c, k)
    w = jnp.take_along_axis(s, chosen, axis=-1)
    if norm:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    rows = jnp.arange(h.shape[0])[:, None]
    return jnp.zeros_like(s).at[rows, chosen].set(w * factor)


def routed_part(h, mw, dims, factor: float, norm: bool):
    """The held experts' part of the sparse layer's sum: every token through
    every held expert, one expert after another, weighed by the router
    (zero where the token did not choose it)."""
    w = route(h, mw["router"], mw["router_bias"], dims["K"], factor, norm,
              dims["G"], dims["Gk"])
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


def mla(h, lw, positions, dims, eps, block, group):
    """The attention sub-layer: MLA(h) [T, D], W_O included. The heads run
    `group` at a time, one group after another (their queries, keys,
    values and scores at 13k tokens would not fit all at once), each
    adding its rows of W_O's product."""
    t = h.shape[0]
    H, dn, dr, dv, rkv = (dims[k] for k in ("H", "dn", "dr", "dv", "rkv"))
    inv = jnp.asarray(yarn_frequencies(dr, dims["theta"], dims["yarn"]))
    cq = _rmsnorm(h @ _dq(lw["w_dq"]).T, lw["q_a_norm"], eps)
    down = h @ _dq(lw["w_dkv"]).T
    ckv = _rmsnorm(down[:, :rkv], lw["kv_a_norm"], eps)
    kr = _rotary(down[:, rkv:], positions, inv)
    scale = softmax_scale(dims)
    n = H // group
    per_group = (  # each stored heads-major: a group is a slab of rows
        _dq(lw["w_uq_nope"]).reshape(n, group * dn, -1),
        _dq(lw["w_uq_rope"]).reshape(n, group * dr, -1),
        _dq(lw["w_uk"]).reshape(n, group, dn, rkv),
        _dq(lw["w_uv"]).reshape(n, group, dv, rkv),
        _dq(lw["w_o"]).reshape(n, group * dv, -1),
    )

    def one(out, w):
        w_uq_nope, w_uq_rope, w_uk, w_uv, w_o = w
        q_nope = (cq @ w_uq_nope.T).reshape(t, group, dn)
        q_rope = (cq @ w_uq_rope.T).reshape(t, group, dr)
        q = jnp.concatenate([q_nope, _rotary(q_rope, positions, inv)], -1)
        k_nope = jnp.einsum("tc,gnc->tgn", ckv, w_uk)
        v = jnp.einsum("tc,gvc->tgv", ckv, w_uv)
        a = attention(q, k_nope, kr, v, scale, block)
        return out + a.reshape(t, group * dv) @ w_o, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(h), per_group)
    return out


@partial(jax.jit, static_argnames=("kind", "dims_items", "eps", "factor",
                                   "norm", "block", "group"))
def layer(x, lw, mw, positions, *, kind, dims_items, eps, factor, norm,
          block, group):
    """One layer: lw its `layers/` leaves, mw its `dense/` or `moe/` ones;
    kind its FFN's."""
    dims = dict(dims_items)
    x = x + mla(_rmsnorm(x, lw["attn_norm"], eps), lw, positions, dims, eps,
                block, group)
    h = _rmsnorm(x, lw["mlp_norm"], eps)
    if kind == DENSE:
        return x + _gated(h, mw["w_gate"], mw["w_up"], mw["w_down"])
    return x + routed_part(h, mw, dims, factor, norm) + shared_part(h, mw)


@partial(jax.jit, static_argnames=("eps",))
def _head(x, out_norm, lm_head, *, eps):
    return _rmsnorm(x, out_norm, eps) @ _dq(lm_head)


def logits_at(weights: Dict[str, Any], cfg: Dict[str, Any],
              tokens: Sequence[int], rows: Sequence[int],
              pad_to: Optional[int] = None, block: int = QUERY_BLOCK,
              group: int = HEAD_GROUP) -> jnp.ndarray:
    """float32 logits [len(rows), vocab] of one sequence at the given
    positions. The sequence is right-padded (a real row never sees the
    padding behind it): to a multiple of `pad_to`, or by default to 1,024,
    2,048 or 4,096 tokens and multiples of 4,096 beyond, so that a cell
    compiles few programs a kind of layer."""
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
        for l, kind in enumerate(dims["mlp"]):
            stack = weights["dense" if kind == DENSE else "moe"]
            i = seen[kind]
            seen[kind] += 1
            x = layer(
                x, jax.tree.map(lambda a: a[l], weights["layers"]),
                jax.tree.map(lambda a: a[i], stack), positions, kind=kind,
                dims_items=tuple(sorted(dims.items())),
                eps=float(cfg["rms_norm_eps"]),
                factor=float(cfg["routed_scaling_factor"]),
                norm=bool(cfg["norm_topk_prob"]), block=min(block, padded),
                group=min(group, dims["H"]),
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
