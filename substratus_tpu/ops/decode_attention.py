"""Decode-step attention over the slot KV cache, int8-aware.

The reference delegates decode attention to closed CUDA serving images
(SURVEY.md §2.2 model-server-basaran / llama-cpp); here it is a
first-class op designed around TPU HBM bandwidth, which is what bounds
single-token decode.

Cache layout is [B, KH, S, D] (per-head sequence-contiguous) rather than
the [B, S, KH, D] activation layout: each kv head's history is then one
contiguous HBM stream, which is what both XLA fusions and the Pallas
kernel want to read.

Two scale tricks keep int8 dequantization off the critical path (the
naive dequant materializes a bf16 copy of the whole cache in HBM every
step):

* k_scale commutes out of the QK contraction (it is per (kv-head, pos),
  constant over head_dim): scores = (q . k_int8) * k_scale.
* v_scale folds into the probabilities: out = (p * v_scale) . v_int8.

So the int8 tensors feed the dots directly and the only full-size
conversion is the operand read itself.

Implementations:
* impl="xla": einsums with f32 accumulation; always correct, runs
  everywhere; the serving default.
* impl="pallas": fused Mosaic kernel — one program per (batch, s-block),
  all kv heads per program (leading-dim slices are relayout-free),
  online softmax in VMEM scratch, causal/validity masking from the
  per-row position. The sequence block comes from a VMEM budget
  (pick_block_s); compiled and compared with the XLA path on the chip by
  chip_smoke.py's kernel phase (ops/kernel_cases.py).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from substratus_tpu.ops import scopes

NEG_INF = -1e30


def decode_attention(
    q: jnp.ndarray,  # [B, 1, H, D]
    k: jnp.ndarray,  # [B, KH, S, D] (int8 when k_scale given)
    v: jnp.ndarray,  # [B, KH, S, D]
    positions: jnp.ndarray,  # [B] absolute position of the query token
    k_scale: Optional[jnp.ndarray] = None,  # [B, KH, S] f32
    v_scale: Optional[jnp.ndarray] = None,  # [B, KH, S] f32
    *,
    impl: str = "xla",
    block_s: Optional[int] = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Single-token attention against the full cache. Slots at position
    > positions[b] are masked (freshly written current token included via
    <=). Returns [B, 1, H, D] in q.dtype. block_s=None sizes the Pallas
    kernel's sequence block from its VMEM budget (pick_block_s); tests
    pass a small one to force several blocks.

    impl="pallas" routes through a custom_partitioning rule (the kernel
    is local per (batch, kv-head) shard), so it survives GSPMD-sharded
    serving instead of requiring the xla fallback."""
    if impl == "pallas":
        quantized = k_scale is not None
        args = (q, k, v, positions)
        if quantized:
            args = args + (k_scale, v_scale)
        return _pallas_sp(quantized, block_s, interpret)(*args)
    assert impl == "xla", impl
    return _xla(q, k, v, positions, k_scale, v_scale)


_PALLAS_SP_CACHE: dict = {}


def _pallas_sp(quantized: bool, block_s: int, interpret):
    """SPMD rule for the unfused decode kernel (ops/kernel_partition.py):
    same per-(batch, kv-head) locality argument as fused_decode._fused_sp;
    the cache (index 1) is the committed reference."""
    key = (quantized, block_s, interpret)
    if key in _PALLAS_SP_CACHE:
        return _PALLAS_SP_CACHE[key]
    from substratus_tpu.ops.kernel_partition import bh_partitioned

    def impl_fn(*args):
        if quantized:
            q, k, v, pos, ks, vs = args
        else:
            (q, k, v, pos), ks, vs = args, None, None
        return _pallas(
            q, k, v, pos, ks, vs, block_s=block_s, interpret=interpret
        )

    arg_dims = [(0, 2), (0, 1), (0, 1), (0, None)]  # q, k, v, positions
    rule_in = ["b u h d", "b k s d", "b k s d", "b"]
    if quantized:
        arg_dims += [(0, 1), (0, 1)]  # k_scale, v_scale
        rule_in += ["b k s2", "b k s3"]
    f = bh_partitioned(
        impl_fn,
        arg_dims=arg_dims,
        out_dims=[(0, 2)],
        sharding_rule=", ".join(rule_in) + " -> b u h d",
        ref=1,
    )
    _PALLAS_SP_CACHE[key] = f
    return f


def _xla(q, k, v, positions, k_scale, v_scale):
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


def _kernel(
    pos_ref,  # scalar prefetch: [B] int32
    q_ref,    # [1, KH, G, D]
    k_ref,    # [1, KH, bs, D]
    *rest,    # quantized: ks [1,KH,bs] f32, v, vs, out, 3 scratches;
    #           unquantized: v, out, 3 scratches (no scale operands at all)
    scale: float,
    kh: int,
    group: int,
    block_s: int,
    num_s_blocks: int,
    quantized: bool,
):
    if quantized:
        ks_ref, v_ref, vs_ref, o_ref = rest[:4]
    else:
        ks_ref = vs_ref = None
        v_ref, o_ref = rest[:2]
    m_scratch, l_scratch, acc_scratch = rest[-3:]
    ib = pl.program_id(0)
    isb = pl.program_id(1)
    pos = pos_ref[ib]
    g8 = max(group, 8)

    @pl.when(isb == 0)
    def _init():
        m_scratch[:] = jnp.full_like(m_scratch, NEG_INF)
        l_scratch[:] = jnp.zeros_like(l_scratch)
        acc_scratch[:] = jnp.zeros_like(acc_scratch)

    s_start = isb * block_s

    @pl.when(s_start <= pos)
    def _compute():
        cols = jax.lax.broadcasted_iota(jnp.int32, (1, block_s), 1) + s_start
        live = cols <= pos
        for h in range(kh):
            kf = k_ref[0, h].astype(jnp.float32)  # [bs, D]
            vf = v_ref[0, h].astype(jnp.float32)
            qh = q_ref[0, h].astype(jnp.float32) * scale  # [G, D]
            s = jax.lax.dot_general(
                qh, kf, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [G, bs]
            if quantized:
                s = s * ks_ref[0, pl.ds(h, 1), :]
            s = jnp.where(live, s, NEG_INF)
            sl = slice(h * g8, h * g8 + group)
            m_prev = m_scratch[sl, :1]
            m_cur = jnp.max(s, axis=-1, keepdims=True)
            m_new = jnp.maximum(m_prev, m_cur)
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_scratch[sl, :1] = alpha * l_scratch[sl, :1] + jnp.sum(
                p, axis=-1, keepdims=True
            )
            if quantized:
                p = p * vs_ref[0, pl.ds(h, 1), :]
            acc_scratch[sl, :] = acc_scratch[sl, :] * alpha + (
                jax.lax.dot_general(
                    p, vf, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
            )
            m_scratch[sl, :] = jnp.broadcast_to(m_new, (group, 128))

    @pl.when(isb == num_s_blocks - 1)
    def _finalize():
        for h in range(kh):
            sl = slice(h * g8, h * g8 + group)
            l = l_scratch[sl, :1]
            l = jnp.where(l == 0.0, 1.0, l)
            o_ref[0, h] = (acc_scratch[sl] / l).astype(o_ref.dtype)


# VMEM the pipelined K/V (and scale) blocks may take, both buffers of
# each counted: half of the 16 MiB a v5e kernel gets by default. The other
# half is left to the per-head f32 temporaries and the scratch.
_KV_VMEM_BUDGET = 8 * 1024 * 1024


def pick_block_s(s_len: int, kh: int, d: int, itemsize: int,
                 quantized: bool) -> int:
    """Sequence block of the Pallas decode kernel: the whole cache when it
    fits the budget, else the largest multiple of 128 that divides it and
    fits (the [B, KH, S] scale block puts S on the lanes, where Mosaic
    takes a multiple of 128 or the whole axis). Raises ValueError for a
    cache length no such block divides, so Engine construction can refuse
    it before the compiler does."""
    row = 2 * 2 * kh * (d * itemsize + (4 if quantized else 0))
    fit = _KV_VMEM_BUDGET // row
    if s_len <= fit:
        return s_len
    for block in range(fit // 128 * 128, 0, -128):
        if s_len % block == 0:
            return block
    raise ValueError(
        f"decode_attn_impl=pallas cannot tile a cache of length {s_len} "
        f"({kh} kv heads of {d}): it needs {row * s_len} bytes of VMEM "
        f"whole (budget {_KV_VMEM_BUDGET}) and no multiple of 128 within "
        "the budget divides it; make max_seq_len a multiple of 128"
    )


def _pallas(q, k, v, positions, k_scale, v_scale, block_s, interpret):
    from jax.experimental.pallas import tpu as pltpu

    b, sq, h, d = q.shape
    assert sq == 1
    kh, s_len = k.shape[1], k.shape[2]
    group = h // kh
    g8 = max(group, 8)
    quantized = k_scale is not None
    if block_s is None:
        block_s = pick_block_s(s_len, kh, d, k.dtype.itemsize, quantized)
    elif s_len % block_s:
        raise ValueError(f"block_s {block_s} does not divide cache {s_len}")
    nsb = s_len // block_s
    qr = q.reshape(b, kh, group, d)
    kernel = functools.partial(
        _kernel, scale=d ** -0.5, kh=kh, group=group,
        block_s=block_s, num_s_blocks=nsb, quantized=quantized,
    )
    kv_spec = pl.BlockSpec(
        (1, kh, block_s, d), lambda ib, isb, pos: (ib, 0, isb, 0)
    )
    scale_spec = pl.BlockSpec(
        (1, kh, block_s), lambda ib, isb, pos: (ib, 0, isb)
    )
    q_spec = pl.BlockSpec(
        (1, kh, group, d), lambda ib, isb, pos: (ib, 0, 0, 0)
    )
    if quantized:
        in_specs = [q_spec, kv_spec, scale_spec, kv_spec, scale_spec]
        operands = (qr, k, k_scale, v, v_scale)
    else:
        in_specs = [q_spec, kv_spec, kv_spec]
        operands = (qr, k, v)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, nsb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, kh, group, d), lambda ib, isb, pos: (ib, 0, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((kh * g8, 128), jnp.float32),
            pltpu.VMEM((kh * g8, 128), jnp.float32),
            pltpu.VMEM((kh * g8, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kh, group, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name=scopes.ATTN_CORE,
    )(positions.astype(jnp.int32), *operands)
    return out.reshape(b, 1, h, d)


def check_cache_tiling(decode_impl: str, chunk_impl: str, kh: int, d: int,
                       cache_len: int, itemsize: int, quantized: bool) -> None:
    """Raise at Engine construction what the chip's compiler would
    otherwise raise inside the first jitted step: a dense cache length the
    selected Pallas kernel cannot tile (ValueError), or the fused kernel
    on a TPU backend (NotImplementedError, ops/fused_decode.py)."""
    if decode_impl == "fused":
        from substratus_tpu.ops.fused_decode import check_lowers

        check_lowers()
    elif decode_impl == "pallas":
        pick_block_s(cache_len, kh, d, itemsize, quantized)
    if chunk_impl == "flash":
        from substratus_tpu.ops.flash_attention import (
            DEFAULT_BLOCK_K, cached_block_k,
        )

        cached_block_k(DEFAULT_BLOCK_K, cache_len, quantized)


def update_cache_and_attend(
    layer_cache,  # {k, v[, k_scale, v_scale]} in [B, KH, S, D] layout
    q: jnp.ndarray,  # [B, S, H, D] new queries (S=1 on the decode path)
    kk: jnp.ndarray,  # [B, S, KH, D] new keys (activation layout)
    vv: jnp.ndarray,  # [B, S, KH, D]
    positions: jnp.ndarray,  # [B, S] absolute positions
    *,
    kv_length: Optional[jnp.ndarray] = None,  # [B] valid prefix override
    impl: str = "xla",
    chunk_impl: str = "xla",
):
    """Scatter fresh kv entries into a per-layer slot cache and attend.

    The one cached-attention path shared by every model family: quantizes
    on the way in when the cache is int8, runs the bandwidth-critical
    decode_attention for single-token steps, and — for multi-token
    continuation (chunked prefill / speculative verify) or
    kv_length-masked resumes — either the blockwise Pallas kernel
    (chunk_impl="flash": int8 operands convert per-block in VMEM, no
    dequantized HBM copy, no [Sq, Sk] score matrix) or the
    dequantize-and-reference fallback (chunk_impl="xla").

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

    if s == 1 and kv_length is None and impl == "fused":
        # Flash-decode: the k/v scatter happens INSIDE the kernel (one
        # dispatch, no HBM re-read of the fresh row); only the tiny
        # [B, KH] scale scatters stay in XLA where they fuse with the
        # projections (ops/fused_decode.py).
        from substratus_tpu.ops.fused_decode import fused_decode_attention

        # One clamp shared by the scale scatters AND the kernel's k/v
        # write: a drifted position (inactive engine slot) must hit the
        # same row S-1 everywhere, or a quantized cache pairs fresh int8
        # data with a stale scale (XLA drops OOB scatter updates; the
        # kernel clamps — they must agree on the index).
        positions = jnp.minimum(positions, layer_cache["k"].shape[2] - 1)
        sidx = positions[:, None, :]

        kv_out = {}
        if quantized:
            with jax.named_scope(scopes.KV_WRITE):
                kq, kscale = quantize_kv(kkT)
                vq, vscale = quantize_kv(vvT)
                kv_out["k_scale"] = (
                    layer_cache["k_scale"].at[bidx, hidx, sidx]
                    .set(kscale[..., 0])
                )
                kv_out["v_scale"] = (
                    layer_cache["v_scale"].at[bidx, hidx, sidx]
                    .set(vscale[..., 0])
                )
            with jax.named_scope(scopes.ATTN_CORE):
                attn, kv_out["k"], kv_out["v"] = fused_decode_attention(
                    q, kq, vq, layer_cache["k"], layer_cache["v"],
                    positions[:, 0], kscale[..., 0], vscale[..., 0],
                    kv_out["k_scale"], kv_out["v_scale"],
                )
        else:
            with jax.named_scope(scopes.ATTN_CORE):
                attn, kv_out["k"], kv_out["v"] = fused_decode_attention(
                    q,
                    kkT.astype(layer_cache["k"].dtype),
                    vvT.astype(layer_cache["v"].dtype),
                    layer_cache["k"], layer_cache["v"], positions[:, 0],
                )
        return attn, kv_out

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
                impl=impl,
            )
    elif chunk_impl == "flash":
        from substratus_tpu.ops.flash_attention import flash_cached_attention

        with jax.named_scope(scopes.ATTN_CORE):
            attn = flash_cached_attention(
                q, kv_out["k"], kv_out["v"], positions,
                kv_out.get("k_scale"), kv_out.get("v_scale"), kv_length,
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
