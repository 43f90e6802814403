"""Decode-step attention over the slot KV cache, int8-aware.

The reference delegates decode attention to closed CUDA serving images
(SURVEY.md §2.2 model-server-basaran / llama-cpp); here it is a
first-class op designed around TPU HBM bandwidth, which is what bounds
single-token decode.

Cache layout is [B, KH, S, D] (per-head sequence-contiguous) rather than
the [B, S, KH, D] activation layout: each kv head's history is then one
contiguous HBM stream, which is what XLA's fusions want to read.

Two scale tricks keep int8 dequantization off the critical path (the
naive dequant materializes a bf16 copy of the whole cache in HBM every
step):

* k_scale commutes out of the QK contraction (it is per (kv-head, pos),
  constant over head_dim): scores = (q . k_int8) * k_scale.
* v_scale folds into the probabilities: out = (p * v_scale) . v_int8.

So the int8 tensors feed the dots directly and the only full-size
conversion is the operand read itself.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from substratus_tpu.ops import scopes

NEG_INF = -1e30


def decode_attention(
    q: jnp.ndarray,  # [B, 1, H, D]
    k: jnp.ndarray,  # [B, KH, S, D] (int8 when k_scale given)
    v: jnp.ndarray,  # [B, KH, S, D]
    positions: jnp.ndarray,  # [B] absolute position of the query token
    k_scale: Optional[jnp.ndarray] = None,  # [B, KH, S] f32
    v_scale: Optional[jnp.ndarray] = None,  # [B, KH, S] f32
) -> jnp.ndarray:
    """Single-token attention against the full cache: einsums with f32
    accumulation. Slots at position > positions[b] are masked (freshly
    written current token included via <=). Returns [B, 1, H, D] in
    q.dtype."""
    b, sq, h, d = q.shape
    assert sq == 1
    kh, s = k.shape[1], k.shape[2]
    g = h // kh
    dt = q.dtype
    qf = (q.astype(dt) * (d ** -0.5)).reshape(b, kh, g, d)
    # bf16 dot with f32 accumulation: the int8->bf16 operand convert is
    # the only whole-cache conversion; no scaled copy is materialized.
    logits = jnp.einsum(
        "bkgd,bksd->bkgs", qf, k.astype(dt),
        preferred_element_type=jnp.float32,
    )
    if k_scale is not None:
        logits = logits * k_scale[:, :, None, :]
    mask = jnp.arange(s)[None, :] <= positions[:, None]  # [B, S]
    logits = jnp.where(mask[:, None, None, :], logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    if v_scale is not None:
        p = p * v_scale[:, :, None, :]
    out = jnp.einsum(
        "bkgs,bksd->bkgd", p.astype(dt), v.astype(dt),
        preferred_element_type=jnp.float32,
    )
    return out.reshape(b, 1, h, d).astype(dt)


def update_cache_and_attend(
    layer_cache,  # {k, v[, k_scale, v_scale]} in [B, KH, S, D] layout
    q: jnp.ndarray,  # [B, S, H, D] new queries (S=1 on the decode path)
    kk: jnp.ndarray,  # [B, S, KH, D] new keys (activation layout)
    vv: jnp.ndarray,  # [B, S, KH, D]
    positions: jnp.ndarray,  # [B, S] absolute positions
    *,
    kv_length: Optional[jnp.ndarray] = None,  # [B] valid prefix override
):
    """Scatter fresh kv entries into a per-layer slot cache and attend.

    The one cached-attention path shared by every model family: quantizes
    on the way in when the cache is int8, runs the bandwidth-critical
    decode_attention for single-token steps, and dequantizes for
    dot_product_attention on a multi-token continuation (chunked prefill /
    speculative verify) or a kv_length-masked resume.

    Returns (attn [B, S, H, D], kv_out — the updated cache dict).
    """
    from substratus_tpu.ops.attention import dot_product_attention
    from substratus_tpu.ops.quant import dequantize_kv, quantize_kv

    b, s = kk.shape[:2]
    kh = layer_cache["k"].shape[1]
    dt = q.dtype
    bidx = jnp.arange(b)[:, None, None]
    hidx = jnp.arange(kh)[None, :, None]
    sidx = positions[:, None, :]  # [B, 1, S] -> broadcast [B, KH, S]
    kkT = kk.transpose(0, 2, 1, 3)  # [B, KH, S, D]
    vvT = vv.transpose(0, 2, 1, 3)
    quantized = "k_scale" in layer_cache

    kv_out = {}
    with jax.named_scope(scopes.KV_WRITE):
        if quantized:
            kq, kscale = quantize_kv(kkT)  # scale [B, KH, S, 1]
            vq, vscale = quantize_kv(vvT)
            new = {"k": kq, "v": vq,
                   "k_scale": kscale[..., 0], "v_scale": vscale[..., 0]}
        else:
            new = {"k": kkT, "v": vvT}
        for name, vals in new.items():
            slot = layer_cache[name]
            kv_out[name] = slot.at[bidx, hidx, sidx].set(
                vals.astype(slot.dtype)
            )
    if s == 1 and kv_length is None:
        with jax.named_scope(scopes.ATTN_CORE):
            attn = decode_attention(
                q, kv_out["k"], kv_out["v"], positions[:, 0],
                kv_out.get("k_scale"), kv_out.get("v_scale"),
            )
    else:
        k_cache, v_cache = kv_out["k"], kv_out["v"]
        if quantized:
            # the dense layout's stand-in for the paged gather: a model-
            # dtype copy of the whole slot cache
            with jax.named_scope(scopes.KV_GATHER):
                k_cache = dequantize_kv(
                    k_cache, kv_out["k_scale"][..., None], dt
                )
                v_cache = dequantize_kv(
                    v_cache, kv_out["v_scale"][..., None], dt
                )
        with jax.named_scope(scopes.ATTN_CORE):
            attn = dot_product_attention(
                q, k_cache.transpose(0, 2, 1, 3),
                v_cache.transpose(0, 2, 1, 3),
                causal=True, q_positions=positions, kv_length=kv_length,
            )
    return attn, kv_out


def pack_fragment(cache, kv):
    """Convert an activation-layout prefill fragment {k, v: [..., S, KH, D]}
    into the slot-cache layout {k, v: [..., KH, S, D][, scales [..., KH, S]]},
    quantizing when `cache` is int8. Shared by the engine's per-slot insert
    and ops.kvcache.insert_prefill."""
    from substratus_tpu.ops.quant import quantize_kv

    nd = kv["k"].ndim
    perm = tuple(range(nd - 3)) + (nd - 2, nd - 3, nd - 1)
    kT = jnp.transpose(kv["k"], perm)
    vT = jnp.transpose(kv["v"], perm)
    if "k_scale" in cache:
        kq, ks = quantize_kv(kT)
        vq, vs = quantize_kv(vT)
        return {
            "k": kq, "k_scale": ks[..., 0],
            "v": vq, "v_scale": vs[..., 0],
        }
    return {
        "k": kT.astype(cache["k"].dtype),
        "v": vT.astype(cache["v"].dtype),
    }
