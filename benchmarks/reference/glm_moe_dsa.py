"""Plain float32 reference for the GLM-5 decoder family (`glm_moe_dsa`).

Written from the published configuration's keys, the published DeepSeek-V3
block and DeepSeek Sparse Attention's published indexer, in straightforward
`jax.numpy`, float32 with `jax.default_matmul_precision("highest")`. It
imports nothing of the program and takes nothing the program has made: it
reads the harness's own seeded weight tree (`harness/weights.py` from the
table of `benchmarks/families/glm_moe_dsa.py`, whose `dims` it shares) and
dequantizes one layer at a time. What is DeepSeek-V3's (norms, rotary, the
router without a group limit, the experts, the head) is taken from that
family's reference, `benchmarks/reference/deepseek_v3.py`, function by
function; this file states the attention sub-layer. `pos` are absolute
positions; H heads, dn = qk_nope_head_dim, dr = qk_rope_head_dim, dv =
v_head_dim, Hi = index_n_heads, di = index_head_dim, k = index_topk:

    per layer: h = rmsnorm(x) * g_attn
               cq = rmsnorm(h W_DQ) * g_qa          [q_lora_rank]
               [q_nope_i (dn); q_rope_i (dr)] = (cq W_UQ)_i      a head
               [ckv_raw; kr_raw (dr)] = h W_DKV
               ckv = rmsnorm(ckv_raw) * g_kva        [kv_lora_rank]
               kr = rotary(kr_raw); q_rope_i = rotary(q_rope_i)
               [k_nope_i (dn); v_i (dv)] = (ckv W_UKV)_i      EXPANDED
       the index:
               qi_j = (cq W_IQ)_j  [di], j = 1..Hi
               ki = layernorm(h W_IK) * g_ik + b_ik  [di], eps 1e-6
               of both, the first dr channels rotated (rotate-half, the
               MLA's own frequencies), the other di - dr not
               w_j = (h W_IW)_j Hi^(-1/2) di^(-1/2)
               I_t,s = sum_j w_t,j relu(qi_t,j . ki_s),  s <= t
               S_t = the min(k, t + 1) positions s <= t of largest I_t,s
                     (`lax.top_k`: of equal scores the lower position)
       attention:
               s_tj = scale (q_nope_i,t . k_nope_i,j + q_rope_i,t . kr_j)
               for j in S_t, scale = (dn + dr)^(-1/2) (no YaRN)
               x += concat_i(softmax_{j in S_t}(s) v_i) W_O
       then the dense or sparse FFN, as deepseek_v3's reference.

No cache, no batching, no kernels. `I`, its top-k and the scores run in
blocks of query rows, the index heads one after another and the attention
heads a few at a time, so that 18k tokens fit beside the weights; the
sets are kept as one mask [T, T] a layer, made once for all heads.

A configuration that holds one chip's share gives this reference the same
share, as deepseek_v3's. Departures from the published model: the rotary
pairs are de-interleaved (a permutation of seeded weights); the published
inference code's Hadamard rotation of qi and ki (orthogonal: every qi . ki
is the same number) and their FP8 storage are left out; the
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

from benchmarks.families.glm_moe_dsa import (
    DENSE, dims as model_dims, softmax_scale,
)
from benchmarks.reference.deepseek_v3 import (
    _dq, _gated, _head, _rmsnorm, _rotary, routed_part, shared_part,
    yarn_frequencies,
)

QUERY_BLOCK = 2048
HEAD_GROUP = 4


def _layernorm(x, g, b, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return ((x - mean) * jax.lax.rsqrt(var + eps) * g.astype(jnp.float32)
            + b.astype(jnp.float32))


def _rotated_head(x, positions, inv, dr):
    """x [T, ..., di]: the first dr channels rotated, the rest as they are."""
    return jnp.concatenate(
        [_rotary(x[..., :dr], positions, inv), x[..., dr:]], -1)


def index_scores(qi, ki, w, start: int, stop: int):
    """I [stop - start, stop] for query rows start..stop against keys
    0..stop: the index heads one after another. qi [T, Hi, di], ki [T, di],
    w [T, Hi]."""
    keys = ki[:stop]

    def one(acc, head):
        q, wj = head  # [b, di], [b]
        return acc + wj[:, None] * jax.nn.relu(q @ keys.T), None

    rows = slice(start, stop)
    acc, _ = jax.lax.scan(
        one, jnp.zeros((stop - start, stop), jnp.float32),
        (qi[rows].transpose(1, 0, 2), w[rows].T))
    return acc


def attended_sets(qi, ki, w, k: int, block: int):
    """The sets as a mask [T, T]: row t holds S_t. Query rows `block` at a
    time, each against the keys up to its own last row."""
    t = qi.shape[0]
    rows = []
    for start in range(0, t, block):
        stop = min(start + block, t)
        seen = jnp.arange(stop)[None, :] <= jnp.arange(start, stop)[:, None]
        scored = jnp.where(seen, index_scores(qi, ki, w, start, stop),
                           -jnp.inf)
        _, best = jax.lax.top_k(scored, min(k, stop))
        chosen = jnp.zeros(scored.shape, bool).at[
            jnp.arange(stop - start)[:, None], best].set(True) & seen
        rows.append(jnp.pad(chosen, ((0, 0), (0, t - stop))))
    return jnp.concatenate(rows, 0)


def attention(q, k_nope, kr, v, sets, scale: float, block: int):
    """The expanded form for some heads, each query over its own set. q [T,
    g, dn + dr], k_nope [T, g, dn], kr [T, dr], v [T, g, dv], sets [T, T]."""
    t = q.shape[0]
    dn = k_nope.shape[-1]
    rows = []
    for start in range(0, t, block):
        stop = min(start + block, t)
        s = (jnp.einsum("bgn,sgn->gbs", q[start:stop, :, :dn], k_nope[:stop])
             + jnp.einsum("bgr,sr->gbs", q[start:stop, :, dn:], kr[:stop])
             ) * scale
        p = jax.nn.softmax(
            jnp.where(sets[start:stop, :stop], s, -jnp.inf), axis=-1)
        rows.append(jnp.einsum("gbs,sgv->bgv", p, v[:stop]))
    return jnp.concatenate(rows, 0)  # [T, g, dv]


def mla(h, lw, positions, dims, eps, block, group):
    """The attention sub-layer under the index: MLA(h) [T, D], W_O
    included; the sets are made first, once for all heads."""
    t = h.shape[0]
    H, dn, dr, dv, rkv = (dims[k] for k in ("H", "dn", "dr", "dv", "rkv"))
    Hi, di = dims["Hi"], dims["di"]
    inv = jnp.asarray(yarn_frequencies(dr, dims["theta"], dims["yarn"]))
    cq = _rmsnorm(h @ _dq(lw["w_dq"]).T, lw["q_a_norm"], eps)
    down = h @ _dq(lw["w_dkv"]).T
    ckv = _rmsnorm(down[:, :rkv], lw["kv_a_norm"], eps)
    kr = _rotary(down[:, rkv:], positions, inv)
    scale = softmax_scale(dims)

    qi = (cq @ _dq(lw["w_iq"]).reshape(Hi * di, -1).T).reshape(t, Hi, di)
    qi = _rotated_head(qi, positions, inv, dr)
    ki = _layernorm(h @ _dq(lw["w_ik"]).T, lw["ik_norm"],
                    lw["ik_norm_bias"], dims["index_eps"])
    ki = _rotated_head(ki, positions, inv, dr)
    w = (h @ lw["w_iw"].astype(jnp.float32).T) * (Hi * di) ** -0.5
    sets = attended_sets(qi, ki, w, dims["topk"], block)

    n = H // group
    per_group = (  # each stored heads-major: a group is a slab of rows
        _dq(lw["w_uq_nope"]).reshape(n, group * dn, -1),
        _dq(lw["w_uq_rope"]).reshape(n, group * dr, -1),
        _dq(lw["w_uk"]).reshape(n, group, dn, rkv),
        _dq(lw["w_uv"]).reshape(n, group, dv, rkv),
        _dq(lw["w_o"]).reshape(n, group * dv, -1),
    )

    def one(out, wg):
        w_uq_nope, w_uq_rope, w_uk, w_uv, w_o = wg
        q_nope = (cq @ w_uq_nope.T).reshape(t, group, dn)
        q_rope = (cq @ w_uq_rope.T).reshape(t, group, dr)
        q = jnp.concatenate([q_nope, _rotary(q_rope, positions, inv)], -1)
        k_nope = jnp.einsum("tc,gnc->tgn", ckv, w_uk)
        v = jnp.einsum("tc,gvc->tgv", ckv, w_uv)
        a = attention(q, k_nope, kr, v, sets, scale, block)
        return out + a.reshape(t, group * dv) @ w_o, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(h), per_group)
    return out, sets


@partial(jax.jit, static_argnames=("kind", "dims_items", "eps", "factor",
                                   "norm", "block", "group", "with_sets"))
def layer(x, lw, mw, positions, *, kind, dims_items, eps, factor, norm,
          block, group, with_sets=False):
    """One layer: lw its `layers/` leaves, mw its `dense/` or `moe/` ones;
    kind its FFN's. `with_sets`: also the layer's sets [T, T]."""
    dims = dict(dims_items)
    a, sets = mla(_rmsnorm(x, lw["attn_norm"], eps), lw, positions, dims,
                  eps, block, group)
    x = x + a
    h = _rmsnorm(x, lw["mlp_norm"], eps)
    if kind == DENSE:
        x = x + _gated(h, mw["w_gate"], mw["w_up"], mw["w_down"])
    else:
        x = x + routed_part(h, mw, dims, factor, norm) + shared_part(h, mw)
    return (x, sets) if with_sets else x


def logits_at(weights: Dict[str, Any], cfg: Dict[str, Any],
              tokens: Sequence[int], rows: Sequence[int],
              pad_to: Optional[int] = None, block: int = QUERY_BLOCK,
              group: int = HEAD_GROUP, sets_out: Optional[list] = None
              ) -> jnp.ndarray:
    """float32 logits [len(rows), vocab] of one sequence at the given
    positions. The sequence is right-padded (a real row never sees the
    padding behind it, and no padded position is in a real row's set): to
    a multiple of `pad_to`, or by default to 1,024, 2,048 or 4,096 tokens
    and multiples of 4,096 beyond. `sets_out`, a list, is given every
    layer's sets as a numpy mask [T, T] (tests)."""
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
            out = layer(
                x, jax.tree.map(lambda a: a[l], weights["layers"]),
                jax.tree.map(lambda a: a[i], stack), positions, kind=kind,
                dims_items=tuple(sorted(dims.items())),
                eps=float(cfg["rms_norm_eps"]),
                factor=float(cfg["routed_scaling_factor"]),
                norm=bool(cfg["norm_topk_prob"]), block=min(block, padded),
                group=min(group, dims["H"]),
                with_sets=sets_out is not None,
            )
            if sets_out is not None:
                x, sets = out
                sets_out.append(np.asarray(sets)[:t, :t])
            else:
                x = out
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
