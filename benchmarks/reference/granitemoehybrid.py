"""Plain float32 reference for the Granite-4.0-H decoder family.

Written from the published configuration (`model_type` granitemoehybrid,
no experts) and Mamba-2's recurrence ("Transformers are SSMs", arXiv:
2405.21060, as transformers' `bamba` / `granitemoehybrid` mixer states it),
in straightforward `jax.numpy`, float32 with
`jax.default_matmul_precision("highest")`. It imports nothing of the
program and takes nothing the program has made: it reads the harness's own
seeded weight tree (`harness/weights.py` from the table of
`benchmarks/families/granitemoehybrid.py`, whose `dims` it shares) and
dequantizes one layer at a time. One sequence `x [T, D]` from position 0,
`eps` = `rms_norm_eps`, E = heads x values, W = E + 2 N, `c_dt` =
`assumed.dt_shift`:

    x = embedding_multiplier * embed[tokens]
    per layer l: h = rmsnorm(x) * g_input
      mamba:     [z (E); u (W); d (H)] = h W_in            (no bias)
                 c_t = silu(sum_{j=0..K-1} w[j] * u_{t-(K-1)+j} + b),
                       u_s = 0 for s < 0                  (depthwise, causal)
                 [x (H x P); B (N); C (N)] = c
                 dt = softplus(d + dt_bias + c_dt)        [T, H]
                 A = -exp(A_log)                          [H]
                 S_t = exp(dt_t A) S_{t-1} + (dt_t x_t) B_t^T   [H, P, N],
                       S = 0 before position 0
                 o_t = S_t C_t + D * x_t
                 x += residual_multiplier *
                      (rmsnorm(o * silu(z)) * g_norm) W_out  (over all E)
      attention: q, k, v = h Wq, h Wk, h Wv  (no norm, no rotation)
                 x += residual_multiplier *
                      softmax(q k^T * attention_multiplier) v Wo
                                                         (causal, grouped KV)
      h = rmsnorm(x) * g_post
      x += residual_multiplier * (silu(h W_gate) * (h W_up)) W_down
    logits = (rmsnorm(x) * g_out) embed^T / logits_scaling  (the head is tied)

The recurrence is a `lax.scan` over tokens that builds `S` the published
way, one rank-one term a token: no chunk, no dual form, no cache, no
batching, no kernel, and its state lies [H, P, N], not as the program's
does. The convolution is computed over the whole sequence by shifting it.
Attention runs in blocks of query rows, so the scores of a 9,000-token
sequence fit.

Departures from the published model, each assumed (the configuration's
`assumed`): the order [z; x B C; dt] of W_in's columns and [x; B; C] of the
convolution's channels, `dt` unclamped, the gated norm's order (gate, then
normalise), the two constants. Weights are the benchmark's seeded ones
(int8 with per-channel scales, dequantized exactly), since the cell states
weight-only int8.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.families.granitemoehybrid import MAMBA, dims as model_dims

QUERY_BLOCK = 512


def _dq(leaf):
    """float32 values of a weight leaf ({"q","scale"} or an array)."""
    if not isinstance(leaf, dict):
        return leaf.astype(jnp.float32)
    return leaf["q"].astype(jnp.float32) * leaf["scale"]


def _rmsnorm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)


def attention(q, k, v, scale: float, block: int):
    """q [T, H, d], k/v [T, KH, d], row = position, in query blocks. Key j
    is visible to query i iff j <= i. No position enters but through that
    mask."""
    t, h, d = q.shape
    kh = k.shape[1]
    q = q.reshape(t, kh, h // kh, d)
    out = []
    for start in range(0, t, block):
        stop = min(start + block, t)
        s = jnp.einsum("bkgd,skd->kgbs", q[start:stop], k[:stop]) * scale
        seen = jnp.arange(start, stop)[:, None] >= jnp.arange(stop)[None, :]
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        out.append(jnp.einsum("kgbs,skd->bkgd", p, v[:stop])
                   .reshape(stop - start, h, d))
    return jnp.concatenate(out, 0)


def recurrence(x, b, c, dt, a, d_skip):
    """x [T, H, P], b/c [T, N], dt [T, H], a [H] (negative), d_skip [H] ->
    o [T, H, P]: the state built token by token from zero."""
    h, p = x.shape[1:]

    def one(s, tok):
        xt, bt, ct, dtt = tok
        s = (jnp.exp(dtt * a)[:, None, None] * s
             + (dtt[:, None] * xt)[:, :, None] * bt[None, None, :])
        return s, jnp.einsum("hpn,n->hp", s, ct) + d_skip[:, None] * xt

    _, o = jax.lax.scan(one, jnp.zeros((h, p, b.shape[1]), jnp.float32),
                        (x, b, c, dt))
    return o


def mixer(h, mw, dims, eps, dt_shift):
    """The Mamba-2 mixer over a whole sequence h [T, D]; mw the layer's
    `ssm/` leaves (taps [K, W], tap j of the equation row j)."""
    t = h.shape[0]
    E, N, Hm, P = dims["E"], dims["N"], dims["Hm"], dims["P"]
    z, u, d = jnp.split(h @ _dq(mw["w_in"]), [E, E + dims["W"]], axis=-1)
    w = mw["taps"].astype(jnp.float32)
    taps = w.shape[0]
    conv = jnp.zeros_like(u)
    for j in range(taps):
        back = taps - 1 - j  # tap j reads u at t - back
        conv = conv + w[j] * jnp.pad(u, ((back, 0), (0, 0)))[:t]
    conv = jax.nn.silu(conv + mw["conv_bias"].astype(jnp.float32))
    x, b, c = jnp.split(conv, [E, E + N], axis=-1)
    dt = jax.nn.softplus(d + mw["dt_bias"].astype(jnp.float32) + dt_shift)
    a = -jnp.exp(mw["a_log"].astype(jnp.float32))
    o = recurrence(x.reshape(t, Hm, P), b, c, dt, a,
                   mw["d_skip"].astype(jnp.float32))
    g = _rmsnorm(o.reshape(t, E) * jax.nn.silu(z), mw["norm"], eps)
    return g @ _dq(mw["w_out"])


@partial(jax.jit, static_argnames=("kind", "dims_items", "eps", "res",
                                   "scale", "dt_shift", "block"))
def layer(x, lw, ow, *, kind, dims_items, eps, res, scale, dt_shift, block):
    """One layer: lw its `layers/` leaves, ow its `ssm/` or `attn/` ones."""
    dims = dict(dims_items)
    h = _rmsnorm(x, lw["input_norm"], eps)
    if kind == MAMBA:
        x = x + res * mixer(h, ow, dims, eps, dt_shift)
    else:
        t, hd = x.shape[0], dims["hd"]
        q = (h @ _dq(ow["wq"]).T).reshape(t, dims["H"], hd)  # [H hd, D]
        k = (h @ _dq(ow["wk"]).T).reshape(t, dims["KH"], hd)
        v = (h @ _dq(ow["wv"]).T).reshape(t, dims["KH"], hd)
        a = attention(q, k, v, scale, block)
        x = x + res * (a.reshape(t, dims["H"] * hd) @ _dq(ow["wo"]))
    h = _rmsnorm(x, lw["post_norm"], eps)
    act = jax.nn.silu(h @ _dq(lw["w_gate"])) * (h @ _dq(lw["w_up"]))
    return x + res * (act @ _dq(lw["w_down"]))


@partial(jax.jit, static_argnames=("eps", "scaling"))
def _head(x, out_norm, embed, *, eps, scaling):
    return _rmsnorm(x, out_norm, eps) @ embed.astype(jnp.float32).T / scaling


def logits_at(weights: Dict[str, Any], cfg: Dict[str, Any],
              tokens: Sequence[int], rows: Sequence[int],
              pad_to: Optional[int] = None,
              block: int = QUERY_BLOCK) -> jnp.ndarray:
    """float32 logits [len(rows), vocab] of one sequence at the given
    positions. The sequence is right-padded (a real row never sees the
    padding behind it: attention, the convolution and the recurrence are
    causal) to a multiple of `pad_to`, by default of 2,048, so that a cell
    compiles a handful of programs."""
    dims = model_dims(cfg)
    t = len(tokens)
    pad_to = pad_to or 2048
    padded = -(-t // pad_to) * pad_to
    ids = np.zeros((padded,), np.int32)
    ids[:t] = np.asarray(tokens, np.int32)
    seen = {kind: 0 for kind in set(dims["ops"])}
    stack = {MAMBA: "ssm", "attention": "attn"}
    static = dict(
        dims_items=tuple(sorted(dims.items())),
        eps=float(cfg["rms_norm_eps"]),
        res=float(cfg["residual_multiplier"]),
        scale=float(cfg["attention_multiplier"]),
        dt_shift=float(cfg["assumed"]["dt_shift"]),
        block=min(block, padded))
    with jax.default_matmul_precision("highest"):
        x = (weights["tok_embed"][jnp.asarray(ids)].astype(jnp.float32)
             * float(cfg["embedding_multiplier"]))
        for l, kind in enumerate(dims["ops"]):
            i = seen[kind]
            seen[kind] += 1
            x = layer(
                x, jax.tree.map(lambda a: a[l], weights["layers"]),
                jax.tree.map(lambda a: a[i], weights[stack[kind]]),
                kind=kind, **static)
        xr = x[jnp.asarray(np.asarray(rows, np.int32))]
        return _head(xr, weights["out_norm"], weights["tok_embed"],
                     eps=static["eps"],
                     scaling=float(cfg["logits_scaling"]))


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
