"""Plain float32 reference for the Brumby decoder family.

Written from the published configuration (`model_type` brumby: Qwen3's
keys) and the power-retention recurrence of Manifest AI's "Scaling Context
Requires Rethinking Attention" (arXiv:2507.04239), in straightforward
`jax.numpy`, float32 with `jax.default_matmul_precision("highest")`. It
imports nothing of the program and takes nothing the program has made: it
reads the harness's own seeded weight tree (`harness/weights.py` from the
table of `benchmarks/families/brumby.py`, whose `dims` it shares) and
dequantizes one layer at a time. One sequence `x [T, D]` from position 0,
`eps` = `rms_norm_eps`, `s = 1 / sqrt(d)`, `c` = `assumed.gate_shift`:

    x = embed[tokens]
    per layer: h = rmsnorm(x) * g_input
      q, k, v = h Wq, h Wk, h Wv; q, k = rmsnorm over the head dimension,
      * g_q, * g_k; rotary on q, k (HF rotate-half)
      log g = log sigmoid(h W_gamma + b_gamma + c)      [T, KH], float32
      G_t = sum_{r <= t} log g_r
      a_tj = (s q_t . k_j)^2 exp(G_t - G_j) for j <= t, 0 above
      o_t = sum_j a_tj v_j / (sum_j a_tj + 1e-6)        (query head i reads
      x += concat_heads(o) Wo                            KV head i // (H / KH))
      h = rmsnorm(x) * g_post;  x += (silu(h W1) * (h W3)) W2
    logits = (rmsnorm(x) * g_out) W_head                (the head is its own)

This is the attention form: no state is ever built, no `phi`, no chunk, no
cache, no batching, no kernel; the program's recurrent and chunked forms
have to agree with it. The weights `a` are computed in blocks of query rows
against blocks of keys and summed (every exponent is <= 0, so no running
maximum is needed), so the scores of a 9,000-token sequence fit; the head
is applied to the rows asked for, a block of its columns at a time.

Departures from the published model, each assumed (the configuration's
`assumed`): the power 2, the gate (a KV head wide, with a bias) and its
shift, the scale, the normaliser's 1e-6, QK RMSNorm and rotary as Qwen3's.
Weights are the benchmark's seeded ones (int8 with per-channel scales,
dequantized exactly), since the cell states weight-only int8.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.families.brumby import dims as model_dims

QUERY_BLOCK = 512
KEY_BLOCK = 2048
HEAD_BLOCK = 32768  # columns of the head dequantized at a time
NORMALISER_EPS = 1e-6


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


def retention(q, k, v, log_g, q_block: int, k_block: int):
    """The attention form. q [T, H, d], k/v [T, KH, d], log_g [T, KH], row
    = position; in blocks of query rows against blocks of keys."""
    t, h, d = q.shape
    kh = k.shape[1]
    q = q.reshape(t, kh, h // kh, d)
    total = jnp.cumsum(log_g, axis=0).T  # G: [KH, T]
    out = []
    for start in range(0, t, q_block):
        stop = min(start + q_block, t)
        num = jnp.zeros((kh, h // kh, stop - start, d), jnp.float32)
        den = jnp.zeros((kh, h // kh, stop - start), jnp.float32)
        for k0 in range(0, stop, k_block):
            k1 = min(k0 + k_block, stop)
            s = jnp.einsum("bkgd,skd->kgbs", q[start:stop], k[k0:k1])
            seen = (jnp.arange(start, stop)[:, None]
                    >= jnp.arange(k0, k1)[None, :])
            lag = total[:, start:stop, None] - total[:, None, k0:k1]
            a = jnp.where(seen, jnp.square(s) / d
                          * jnp.exp(jnp.where(seen, lag, 0.0))[:, None], 0.0)
            num = num + jnp.einsum("kgbs,skd->kgbd", a, v[k0:k1])
            den = den + jnp.sum(a, axis=-1)
        o = num / (den[..., None] + NORMALISER_EPS)
        out.append(jnp.moveaxis(o, 2, 0).reshape(stop - start, h, d))
    return jnp.concatenate(out, 0)


@partial(jax.jit, static_argnames=("dims_items", "theta", "eps", "shift",
                                   "q_block", "k_block"))
def layer(x, lw, positions, *, dims_items, theta, eps, shift, q_block,
          k_block):
    """One layer; lw its `layers/` leaves."""
    dims = dict(dims_items)
    t, hd = x.shape[0], dims["hd"]
    h = _rmsnorm(x, lw["input_norm"], eps)
    q = (h @ _dq(lw["wq"]).T).reshape(t, dims["H"], hd)  # stored [H hd, D]
    k = (h @ _dq(lw["wk"]).T).reshape(t, dims["KH"], hd)
    v = (h @ _dq(lw["wv"]).T).reshape(t, dims["KH"], hd)
    q = _rotary(_rmsnorm(q, lw["q_norm"], eps), positions, theta)
    k = _rotary(_rmsnorm(k, lw["k_norm"], eps), positions, theta)
    log_g = jax.nn.log_sigmoid(
        h @ _dq(lw["w_gamma"]) + lw["b_gamma"].astype(jnp.float32) + shift)
    o = retention(q, k, v, log_g, q_block, k_block)
    x = x + o.reshape(t, dims["H"] * hd) @ _dq(lw["wo"])
    h = _rmsnorm(x, lw["post_norm"], eps)
    act = jax.nn.silu(h @ _dq(lw["w_gate"])) * (h @ _dq(lw["w_up"]))
    return x + act @ _dq(lw["w_down"])


@partial(jax.jit, static_argnames=("eps",))
def _head(x, out_norm, head, *, eps):
    """Logits against the head [D, V], a block of its columns at a time."""
    h = _rmsnorm(x, out_norm, eps)
    v = head["q"].shape[1] if isinstance(head, dict) else head.shape[1]
    cols = [jax.tree.map(lambda a: a[:, c:c + HEAD_BLOCK], head)
            for c in range(0, v, HEAD_BLOCK)]
    return jnp.concatenate([h @ _dq(c) for c in cols], axis=-1)


def logits_at(weights: Dict[str, Any], cfg: Dict[str, Any],
              tokens: Sequence[int], rows: Sequence[int],
              pad_to: Optional[int] = None,
              q_block: int = QUERY_BLOCK,
              k_block: int = KEY_BLOCK) -> jnp.ndarray:
    """float32 logits [len(rows), vocab] of one sequence at the given
    positions. The sequence is right-padded (a real row never sees the
    padding behind it: the operator is causal) to a multiple of `pad_to`,
    by default of 2,048, so that a cell compiles a handful of programs."""
    dims = model_dims(cfg)
    t = len(tokens)
    pad_to = pad_to or 2048
    padded = -(-t // pad_to) * pad_to
    ids = np.zeros((padded,), np.int32)
    ids[:t] = np.asarray(tokens, np.int32)
    positions = jnp.arange(padded, dtype=jnp.int32)
    with jax.default_matmul_precision("highest"):
        x = weights["tok_embed"][jnp.asarray(ids)].astype(jnp.float32)
        for l in range(dims["L"]):
            x = layer(
                x, jax.tree.map(lambda a: a[l], weights["layers"]), positions,
                dims_items=tuple(sorted(dims.items())),
                theta=float(cfg["rope_theta"]),
                eps=float(cfg["rms_norm_eps"]),
                shift=float(cfg["assumed"]["gate_shift"]),
                q_block=min(q_block, padded), k_block=min(k_block, padded),
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
