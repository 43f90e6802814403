"""Plain float32 reference for the Mistral / Mixtral decoder family.

Written from the published architecture, in straightforward `jax.numpy`,
float32 with `jax.default_matmul_precision("highest")`. It imports nothing
of the program and takes nothing the program has made: it reads the
harness's own seeded weight tree (`harness/weights.py` from the table of
`benchmarks/families/llama_family.py`, whose `dims` it shares: int8 values
and float32 scales, or bfloat16) and dequantizes one layer at a time, so a
7B model in float32 is never resident.

    x = embed[tokens]
    per layer: h = rmsnorm(x) * w
               q, k, v = h @ wq, h @ wk, h @ wv; rotary on q, k (HF
               rotate-half: the halves are [:d/2] and [d/2:])
               causal softmax(q k^T / sqrt(d)) v, grouped KV heads
               x += attn @ wo
               h = rmsnorm(x) * w
               dense:   x += (silu(h @ gate) * (h @ up)) @ down
               mixtral: p = softmax(h @ router); top-k p, renormalised to
                        sum to one; x += sum_k p_k * expert_k(h)
    logits = (rmsnorm(x) * w) @ lm_head

No cache, no batching, no kernels. Attention runs in blocks of query rows
so the score matrix of a long document fits.

Departures from the published model: none in the mathematics. Weights are
the benchmark's seeded ones (int8 with per-channel scales, dequantized
exactly), since the cell states weight-only int8.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.families.llama_family import dims as model_dims

QUERY_BLOCK = 512


def _dq(leaf):
    """float32 values of a weight leaf ({"q","scale"} or an array)."""
    if not isinstance(leaf, dict):
        return leaf.astype(jnp.float32)
    return leaf["q"].astype(jnp.float32) * leaf["scale"]


def _rmsnorm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def _rotary(x, positions, theta):
    """x [T, heads, d]; HF rotate-half convention."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, block):
    """Causal attention, q [T, H, d], k/v [T, KH, d], in query blocks."""
    t, h, d = q.shape
    kh = k.shape[1]
    g = h // kh
    q = q.reshape(t, kh, g, d)
    out = []
    for start in range(0, t, block):
        stop = min(start + block, t)
        qb = q[start:stop]  # [b, KH, g, d]
        kb, vb = k[:stop], v[:stop]
        s = jnp.einsum("bkgd,skd->kgbs", qb, kb) / np.sqrt(d)
        qpos = jnp.arange(start, stop)[:, None]
        kpos = jnp.arange(stop)[None, :]
        s = jnp.where(kpos <= qpos, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        out.append(jnp.einsum("kgbs,skd->bkgd", p, vb).reshape(stop - start, h, d))
    return jnp.concatenate(out, 0)


def _mlp(h, lw, dims):
    if not dims["E"]:
        gate = jnp.einsum("td,dm->tm", h, _dq(lw["w_gate"]))
        up = jnp.einsum("td,dm->tm", h, _dq(lw["w_up"]))
        act = jax.nn.silu(gate) * up
        return jnp.einsum("tm,md->td", act, _dq(lw["w_down"]))
    # Mixtral: router in float32, softmax over all experts, top-k,
    # renormalised so the chosen experts' weights sum to one.
    logits = jnp.einsum("td,de->te", h, lw["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, dims["K"])
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    y = jnp.zeros_like(h)
    for e in range(dims["E"]):
        we = jnp.sum(jnp.where(top_i == e, top_p, 0.0), axis=-1)  # [T]
        gate = jnp.einsum("td,dm->tm", h, _dq(_at(lw["w_gate"], e)))
        up = jnp.einsum("td,dm->tm", h, _dq(_at(lw["w_up"], e)))
        act = jax.nn.silu(gate) * up
        out = jnp.einsum("tm,md->td", act, _dq(_at(lw["w_down"], e)))
        y = y + out * we[:, None]
    return y


def _at(leaf, i):
    if isinstance(leaf, dict):
        return {k: v[i] for k, v in leaf.items()}
    return leaf[i]


@partial(jax.jit, static_argnames=("dims_items", "theta", "eps", "block"))
def _layer(x, lw, positions, *, dims_items, theta, eps, block):
    dims = dict(dims_items)
    h = _rmsnorm(x, lw["attn_norm"].astype(jnp.float32), eps)
    q = jnp.einsum("td,dhk->thk", h, _dq(lw["wq"]))
    k = jnp.einsum("td,dhk->thk", h, _dq(lw["wk"]))
    v = jnp.einsum("td,dhk->thk", h, _dq(lw["wv"]))
    q = _rotary(q, positions, theta)
    k = _rotary(k, positions, theta)
    a = _attention(q, k, v, block)
    x = x + jnp.einsum("thk,hkd->td", a, _dq(lw["wo"]))
    h = _rmsnorm(x, lw["mlp_norm"].astype(jnp.float32), eps)
    return x + _mlp(h, lw, dims)


@partial(jax.jit, static_argnames=("eps",))
def _head(x, out_norm, lm_head, *, eps):
    h = _rmsnorm(x, out_norm.astype(jnp.float32), eps)
    return jnp.einsum("td,dv->tv", h, _dq(lm_head))


def logits_at(weights: Dict[str, Any], cfg: Dict[str, Any],
              tokens: Sequence[int], rows: Sequence[int],
              pad_to: Optional[int] = None,
              block: int = QUERY_BLOCK) -> jnp.ndarray:
    """float32 logits [len(rows), vocab] of one sequence at the given
    positions. The sequence is right-padded to a multiple of `pad_to`
    (default: 256 up to 1,024 tokens, 1,024 beyond; a causal model's real
    rows do not see the padding), so that few distinct programs are ever
    compiled and a checkout's compile cache soon holds them all."""
    dims = model_dims(cfg)
    t = len(tokens)
    if pad_to is None:
        pad_to = 256 if t <= 1024 else 1024
    padded = -(-t // pad_to) * pad_to
    ids = np.zeros((padded,), np.int32)
    ids[:t] = np.asarray(tokens, np.int32)
    positions = jnp.arange(padded, dtype=jnp.int32)
    with jax.default_matmul_precision("highest"):
        x = weights["tok_embed"][jnp.asarray(ids)].astype(jnp.float32)
        layers = weights["layers"]
        for l in range(dims["L"]):
            lw = jax.tree.map(lambda a: a[l], layers)
            x = _layer(
                x, lw, positions, dims_items=tuple(sorted(dims.items())),
                theta=float(cfg["rope_theta"]), eps=float(cfg["rms_norm_eps"]),
                block=min(block, padded),
            )
        xr = x[jnp.asarray(np.asarray(rows, np.int32))]
        head = weights["tok_embed"].T if cfg.get("tie_word_embeddings") \
            else weights["lm_head"]
        return _head(xr, weights["out_norm"], head,
                     eps=float(cfg["rms_norm_eps"]))


def served_gaps(weights, cfg, prompt: Sequence[int], served: Sequence[int]):
    """How far each served token's reference logit lies below the
    reference's best, at its own position (teacher-forced on the served
    tokens). Returns a numpy array [len(served)]."""
    p, n = len(prompt), len(served)
    seq = list(prompt) + list(served[:-1])
    rows = list(range(p - 1, p - 1 + n))
    ref = logits_at(weights, cfg, seq, rows)
    chosen = jnp.asarray(np.asarray(served, np.int32))
    best = jnp.max(ref, axis=-1)
    got = jnp.take_along_axis(ref, chosen[:, None], axis=-1)[:, 0]
    return np.asarray(best - got)
