"""Pallas (Mosaic) flash attention for TPU — forward AND backward kernels.

The reference's attention ran inside closed CUDA images; here it is a real
kernel: blockwise causal attention with online softmax so the [Sq, Sk] score
matrix never materializes in HBM — the classic memory win that makes long
context affordable.

Forward layout: grid (batch*heads, q_blocks, k_blocks) with the k dimension
sequential ("arbitrary") so VMEM scratch (running max m, normalizer l, and
the f32 accumulator) persists across k steps; the output tile and the
row logsumexp L = m + log(l) are written once on the final k step. GQA is
handled in the k/v index maps (query head h reads kv head h // group) — no
KV duplication in HBM. Fully-masked diagonal-above blocks are skipped via
pl.when, so causal attention does ~half the work.

Backward (standard flash bwd, recompute-from-stats):
  D  = rowsum(dO * O)                      (XLA, one fused pass)
  p  = exp(s * scale - L)                  (recomputed per block in VMEM)
  dV = p^T dO
  dS = p * (dO V^T - D) * scale
  dQ = dS K     — kernel over (bh, q_blocks) accumulating across k blocks
  dK = dS^T Q   — kernel over (bh, k_blocks) accumulating across q blocks
Neither kernel materializes p in HBM. For GQA the dK/dV kernel runs per
query head and the per-head partials are summed over the group afterwards
(group-sized HBM transient; zero-cost for MHA).

Numerics: dots run in the input dtype (bf16 is the MXU's native mode; an
f32 upcast would be truncated back to bf16 under default precision) with
f32 accumulation; genuine f32 inputs request Precision.HIGHEST. On a v5e
(chip_smoke.py, PR 21) the bf16 forward is within 0.005 and the backward
within 0.007 of the f32 XLA reference, relative to the largest value, at
TinyLlama-1.1B's and Llama-2-7B's widths up to 2048 tokens.

One chip compiles these kernels. Under a mesh of several chips the
compiler refuses the custom_partitioning wrapper that carries them
(tests/test_chip_compile.py), so flash stays a one-chip path for now.
"""
from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from substratus_tpu.ops import scopes

DEFAULT_BLOCK_Q = 256
DEFAULT_BLOCK_K = 256

# Independent grid tune for the backward dK/dV kernel (the builders'
# round-2 self-report had dkv at 0.92x of XLA at 8k/16h while dq won — the
# dkv kernel loops over q blocks per kv block, so its sweet spot differs
# from dq's; not measured since). None =
# inherit (block_q, block_k); set via set_dkv_blocks() or the env var
# SUBSTRATUS_FLASH_DKV_BLOCKS="bq,bk".
_DKV_BLOCKS = None
if os.environ.get("SUBSTRATUS_FLASH_DKV_BLOCKS"):
    _parts = os.environ["SUBSTRATUS_FLASH_DKV_BLOCKS"].split(",")
    if len(_parts) != 2:
        raise ValueError(
            "SUBSTRATUS_FLASH_DKV_BLOCKS must be 'block_q,block_k', got "
            f"{os.environ['SUBSTRATUS_FLASH_DKV_BLOCKS']!r}"
        )
    _DKV_BLOCKS = (int(_parts[0]), int(_parts[1]))


def set_dkv_blocks(blocks) -> None:
    """Override the backward dK/dV kernel's (block_q, block_k); None
    reverts to inheriting the forward/dq blocks."""
    global _DKV_BLOCKS
    assert blocks is None or len(blocks) == 2, blocks
    _DKV_BLOCKS = tuple(blocks) if blocks else None


def _fit_block(block: int, size: int) -> int:
    """Clamp a requested block to the dimension: no larger than size,
    halved until it divides (one invariant for dq AND dkv grids)."""
    block = min(block, size)
    while size % block:
        block //= 2
    return block
NEG_INF = -1e30


def _precision(dtype):
    return jax.lax.Precision.HIGHEST if dtype == jnp.float32 else None


def _dot(a, b, dims, prec):
    return jax.lax.dot_general(
        a, b, (dims, ((), ())),
        preferred_element_type=jnp.float32, precision=prec,
    )


def _flash_kernel(
    q_ref,  # [1, bq, D]
    k_ref,  # [1, bk, D]
    v_ref,  # [1, bk, D]
    o_ref,  # [1, bq, D]
    *rest,  # emit_lse: lse_ref [1, bq, 8] f32 (row value broadcast across
    #         8 lanes — the narrowest block Mosaic accepts for a per-row
    #         vector; written only for the custom_vjp forward, the
    #         inference path skips the dead HBM write); then 3 scratches
    #         m [bq,128], l [bq,128], acc [bq,D] f32
    scale: float,
    causal: bool,
    block_q: int,
    block_k: int,
    num_k_blocks: int,
    emit_lse: bool,
):
    lse_ref = rest[0] if emit_lse else None
    m_scratch, l_scratch, acc_scratch = rest[-3:]
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_scratch[:] = jnp.full_like(m_scratch, NEG_INF)
        l_scratch[:] = jnp.zeros_like(l_scratch)
        acc_scratch[:] = jnp.zeros_like(acc_scratch)

    # Causal: block is live unless it is entirely above the diagonal.
    q_start = iq * block_q
    k_start = ik * block_k
    live = True
    if causal:
        live = k_start <= q_start + block_q - 1

    @pl.when(live)
    def _compute():
        q = q_ref[0]  # [bq, D] input dtype
        k = k_ref[0]  # [bk, D]
        v = v_ref[0]
        prec = _precision(q.dtype)
        s = _dot(q, k, ((1,), (1,)), prec) * scale  # [bq, bk] f32
        if causal:
            rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) + q_start
            cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + k_start
            s = jnp.where(cols <= rows, s, NEG_INF)

        m_prev = m_scratch[:, :1]  # [bq, 1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)  # [bq, bk]
        alpha = jnp.exp(m_prev - m_new)  # [bq, 1]
        l_new = alpha * l_scratch[:, :1] + jnp.sum(p, axis=-1, keepdims=True)
        pv = p if v.dtype == jnp.float32 else p.astype(v.dtype)
        acc_scratch[:] = acc_scratch[:] * alpha + _dot(
            pv, v, ((1,), (0,)), prec
        )
        m_scratch[:] = jnp.broadcast_to(m_new, m_scratch.shape)
        l_scratch[:] = jnp.broadcast_to(l_new, l_scratch.shape)

    @pl.when(ik == num_k_blocks - 1)
    def _finalize():
        m = m_scratch[:, :1]
        l = l_scratch[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scratch[:] / l_safe).astype(o_ref.dtype)
        if emit_lse:
            # logsumexp per row; NEG_INF rows (nothing live) stay NEG_INF
            # so the backward's exp(s - L) underflows to 0 instead of
            # exploding.
            lse = jnp.where(l == 0.0, NEG_INF, m + jnp.log(l_safe))
            lse_ref[0] = jnp.broadcast_to(lse, lse_ref[0].shape)


def _flash_forward(
    q: jnp.ndarray,  # [B, Sq, H, D]
    k: jnp.ndarray,  # [B, Sk, KH, D]
    v: jnp.ndarray,
    scale: float,
    causal: bool,
    block_q: int,
    block_k: int,
    interpret: bool,
    need_lse: bool = True,
):
    b, sq, h, d = q.shape
    sk, kh = k.shape[1], k.shape[2]
    assert h % kh == 0
    group = h // kh
    # Shrink blocks to divide the sequence (non-power-of-two prefill
    # buckets like 384 must not crash; a smaller block only costs a bit
    # of grid overhead).
    block_q = _fit_block(block_q, sq)
    block_k = _fit_block(block_k, sk)
    nq, nk = sq // block_q, sk // block_k

    # [B, S, H, D] -> [B*H, S, D] view via BlockSpec index maps.
    qt = q.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    kt = k.transpose(0, 2, 1, 3).reshape(b * kh, sk, d)
    vt = v.transpose(0, 2, 1, 3).reshape(b * kh, sk, d)

    def q_index(bh, iq, ik):
        return (bh, iq, 0)

    def kv_index(bh, iq, ik):
        batch = bh // h
        head = bh % h
        return (batch * kh + head // group, ik, 0)

    def lse_index(bh, iq, ik):
        return (bh, iq, 0)

    kernel = functools.partial(
        _flash_kernel,
        scale=scale,
        causal=causal,
        block_q=block_q,
        block_k=block_k,
        num_k_blocks=nk,
        emit_lse=need_lse,
    )
    if need_lse:
        out_specs = [
            pl.BlockSpec((1, block_q, d), q_index),
            pl.BlockSpec((1, block_q, 8), lse_index),
        ]
        out_shape = [
            jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, sq, 8), jnp.float32),
        ]
    else:
        out_specs = pl.BlockSpec((1, block_q, d), q_index)
        out_shape = jax.ShapeDtypeStruct((b * h, sq, d), q.dtype)
    res = pl.pallas_call(
        kernel,
        grid=(b * h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), q_index),
            pl.BlockSpec((1, block_k, d), kv_index),
            pl.BlockSpec((1, block_k, d), kv_index),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            _vmem((block_q, 128), jnp.float32),
            _vmem((block_q, 128), jnp.float32),
            _vmem((block_q, d), jnp.float32),
        ],
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(qt, kt, vt)
    out = res[0] if need_lse else res
    lse = res[1] if need_lse else None
    return out.reshape(b, h, sq, d).transpose(0, 2, 1, 3), lse


def _bwd_dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,  # lse/delta [1, bq, 8]
    dq_ref,  # [1, bq, D] output
    dq_scratch,  # [bq, D] f32
    *,
    scale, causal, block_q, block_k, num_k_blocks,
):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        dq_scratch[:] = jnp.zeros_like(dq_scratch)

    q_start = iq * block_q
    k_start = ik * block_k
    live = True
    if causal:
        live = k_start <= q_start + block_q - 1

    @pl.when(live)
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        prec = _precision(q.dtype)
        s = _dot(q, k, ((1,), (1,)), prec) * scale  # [bq, bk] f32
        if causal:
            rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) + q_start
            cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + k_start
            s = jnp.where(cols <= rows, s, NEG_INF)
        p = jnp.exp(s - lse_ref[0][:, :1])  # [bq, bk]
        dp = _dot(do, v, ((1,), (1,)), prec)  # [bq, bk]
        ds = p * (dp - delta_ref[0][:, :1]) * scale
        dsq = ds if q.dtype == jnp.float32 else ds.astype(q.dtype)
        dq_scratch[:] += _dot(dsq, k, ((1,), (0,)), prec)

    @pl.when(ik == num_k_blocks - 1)
    def _finalize():
        dq_ref[0] = dq_scratch[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,  # lse/delta [1, bq, 8]
    dk_ref, dv_ref,  # [1, bk, D] outputs (per query head)
    dk_scratch, dv_scratch,  # [bk, D] f32
    *,
    scale, causal, block_q, block_k, num_q_blocks,
):
    ik = pl.program_id(1)
    iq = pl.program_id(2)

    @pl.when(iq == 0)
    def _init():
        dk_scratch[:] = jnp.zeros_like(dk_scratch)
        dv_scratch[:] = jnp.zeros_like(dv_scratch)

    q_start = iq * block_q
    k_start = ik * block_k
    live = True
    if causal:
        live = k_start <= q_start + block_q - 1

    @pl.when(live)
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        prec = _precision(q.dtype)
        s = _dot(q, k, ((1,), (1,)), prec) * scale  # [bq, bk]
        if causal:
            rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) + q_start
            cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + k_start
            s = jnp.where(cols <= rows, s, NEG_INF)
        p = jnp.exp(s - lse_ref[0][:, :1])  # [bq, bk]
        pq = p if q.dtype == jnp.float32 else p.astype(q.dtype)
        dv_scratch[:] += _dot(pq, do, ((0,), (0,)), prec)  # p^T dO
        dp = _dot(do, v, ((1,), (1,)), prec)  # [bq, bk]
        ds = p * (dp - delta_ref[0][:, :1]) * scale
        dsq = ds if q.dtype == jnp.float32 else ds.astype(q.dtype)
        dk_scratch[:] += _dot(dsq, q, ((0,), (0,)), prec)  # dS^T Q

    @pl.when(iq == num_q_blocks - 1)
    def _finalize():
        dk_ref[0] = dk_scratch[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scratch[:].astype(dv_ref.dtype)


def _flash_backward(
    q, k, v, out, lse, g, scale, causal, block_q, block_k, interpret
):
    b, sq, h, d = q.shape
    sk, kh = k.shape[1], k.shape[2]
    group = h // kh
    block_q = _fit_block(block_q, sq)
    block_k = _fit_block(block_k, sk)
    nq, nk = sq // block_q, sk // block_k

    qt = q.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    kt = k.transpose(0, 2, 1, 3).reshape(b * kh, sk, d)
    vt = v.transpose(0, 2, 1, 3).reshape(b * kh, sk, d)
    dot = g.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    # D_i = rowsum(dO * O): one fused elementwise+reduce pass in XLA,
    # broadcast to the same [bh, sq, 8] lane layout as lse.
    delta = jnp.broadcast_to(
        jnp.sum(
            g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1
        ).transpose(0, 2, 1).reshape(b * h, sq)[:, :, None],
        (b * h, sq, 8),
    )

    def dq_q_index(bh, iq, ik):
        return (bh, iq, 0)

    def dq_kv_index(bh, iq, ik):
        batch = bh // h
        head = bh % h
        return (batch * kh + head // group, ik, 0)

    def dq_lse_index(bh, iq, ik):
        return (bh, iq, 0)

    dq_kernel = functools.partial(
        _bwd_dq_kernel, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, num_k_blocks=nk,
    )
    dqt = pl.pallas_call(
        dq_kernel,
        grid=(b * h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), dq_q_index),
            pl.BlockSpec((1, block_k, d), dq_kv_index),
            pl.BlockSpec((1, block_k, d), dq_kv_index),
            pl.BlockSpec((1, block_q, d), dq_q_index),
            pl.BlockSpec((1, block_q, 8), dq_lse_index),
            pl.BlockSpec((1, block_q, 8), dq_lse_index),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), dq_q_index),
        out_shape=jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
        scratch_shapes=[_vmem((block_q, d), jnp.float32)],
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(qt, kt, vt, dot, lse, delta)
    dq = dqt.reshape(b, h, sq, d).transpose(0, 2, 1, 3)

    # dK/dV per QUERY head (grid bh), then reduced over the GQA group —
    # parallel programs must not accumulate into a shared kv block.
    # Block sizes tune independently of dq's (see _DKV_BLOCKS).
    dkv_bq, dkv_bk = _DKV_BLOCKS or (block_q, block_k)
    dkv_bq = _fit_block(dkv_bq, sq)
    dkv_bk = _fit_block(dkv_bk, sk)
    dkv_nq, dkv_nk = sq // dkv_bq, sk // dkv_bk

    def dkv_q_index(bh, ik, iq):
        return (bh, iq, 0)

    def dkv_kv_index(bh, ik, iq):
        batch = bh // h
        head = bh % h
        return (batch * kh + head // group, ik, 0)

    def dkv_out_index(bh, ik, iq):
        return (bh, ik, 0)

    def dkv_lse_index(bh, ik, iq):
        return (bh, iq, 0)

    dkv_kernel = functools.partial(
        _bwd_dkv_kernel, scale=scale, causal=causal,
        block_q=dkv_bq, block_k=dkv_bk, num_q_blocks=dkv_nq,
    )
    dkt, dvt = pl.pallas_call(
        dkv_kernel,
        grid=(b * h, dkv_nk, dkv_nq),
        in_specs=[
            pl.BlockSpec((1, dkv_bq, d), dkv_q_index),
            pl.BlockSpec((1, dkv_bk, d), dkv_kv_index),
            pl.BlockSpec((1, dkv_bk, d), dkv_kv_index),
            pl.BlockSpec((1, dkv_bq, d), dkv_q_index),
            pl.BlockSpec((1, dkv_bq, 8), dkv_lse_index),
            pl.BlockSpec((1, dkv_bq, 8), dkv_lse_index),
        ],
        out_specs=[
            pl.BlockSpec((1, dkv_bk, d), dkv_out_index),
            pl.BlockSpec((1, dkv_bk, d), dkv_out_index),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, sk, d), jnp.float32),
            jax.ShapeDtypeStruct((b * h, sk, d), jnp.float32),
        ],
        scratch_shapes=[
            _vmem((dkv_bk, d), jnp.float32),
            _vmem((dkv_bk, d), jnp.float32),
        ],
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(qt, kt, vt, dot, lse, delta)
    # heads are kv-major (h = khead * group + r) -> sum the group axis.
    dk = dkt.reshape(b, kh, group, sk, d).sum(2).astype(k.dtype)
    dv = dvt.reshape(b, kh, group, sk, d).sum(2).astype(v.dtype)
    return dq, dk.transpose(0, 2, 1, 3), dv.transpose(0, 2, 1, 3)


def _vmem(shape, dtype):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.VMEM(shape, dtype)


def _compiler_params():
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary")
    )


# SPMD rules (ops/kernel_partition.py): every flash entry is local per
# (batch, head) shard, so GSPMD runs the kernels per-shard under TP/DP
# serving and training meshes instead of choking on the opaque
# pallas_call. The custom_vjp sits OUTSIDE the partitioned cores, so
# autodiff still sees the hand-written backward. Cached per static
# configuration (wrappers carry compiled partition rules).
_SP_CACHE: dict = {}


def _fwd_sp(scale, causal, block_q, block_k, interpret, need_lse):
    key = ("fwd", scale, causal, block_q, block_k, interpret, need_lse)
    if key in _SP_CACHE:
        return _SP_CACHE[key]
    from substratus_tpu.ops.kernel_partition import bh_partitioned

    if need_lse:
        def impl(q, k, v):
            out, lse = _flash_forward(
                q, k, v, scale, causal, block_q, block_k, interpret,
                need_lse=True,
            )
            b, sq, h, _ = q.shape
            # lse leaves the core as [B, H, Sq, 8] so its head axis can
            # shard like q's.
            return out, lse.reshape(b, h, sq, 8)

        f = bh_partitioned(
            impl,
            arg_dims=[(0, 2), (0, 2), (0, 2)],
            out_dims=[(0, 2), (0, 1)],
            sharding_rule=(
                "b s h d, b s2 k d, b s3 k d -> b s h d, b h s4 e"
            ),
        )
    else:
        def impl(q, k, v):
            out, _ = _flash_forward(
                q, k, v, scale, causal, block_q, block_k, interpret,
                need_lse=False,
            )
            return out

        f = bh_partitioned(
            impl,
            arg_dims=[(0, 2), (0, 2), (0, 2)],
            out_dims=[(0, 2)],
            sharding_rule="b s h d, b s2 k d, b s3 k d -> b s h d",
        )
    _SP_CACHE[key] = f
    return f


def _bwd_sp(scale, causal, block_q, block_k, interpret):
    key = ("bwd", scale, causal, block_q, block_k, interpret)
    if key in _SP_CACHE:
        return _SP_CACHE[key]
    from substratus_tpu.ops.kernel_partition import bh_partitioned

    def impl(q, k, v, out, lse4, g):
        b, sq, h, _ = q.shape
        lse = lse4.reshape(b * h, sq, 8)
        return _flash_backward(
            q, k, v, out, lse, g, scale, causal, block_q, block_k,
            interpret,
        )

    f = bh_partitioned(
        impl,
        arg_dims=[(0, 2), (0, 2), (0, 2), (0, 2), (0, 1), (0, 2)],
        out_dims=[(0, 2), (0, 2), (0, 2)],
        sharding_rule=(
            "b s h d, b s2 k d, b s3 k d, b s4 h d, b h s5 e, b s6 h d "
            "-> b s h d, b s2 k d, b s3 k d"
        ),
    )
    _SP_CACHE[key] = f
    return f


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7)
)
def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    causal: bool = True,
    scale: Optional[float] = None,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = False,
) -> jnp.ndarray:
    """Drop-in for ops.attention.dot_product_attention on the self-attention
    (no-cache) path. Shapes [B, S, H|KH, D]."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    return _fwd_sp(scale, causal, block_q, block_k, interpret, False)(
        q, k, v
    )


def _fwd(q, k, v, causal, scale, block_q, block_k, interpret):
    if scale is None:
        scale = q.shape[-1] ** -0.5
    out, lse4 = _fwd_sp(scale, causal, block_q, block_k, interpret, True)(
        q, k, v
    )
    return out, (q, k, v, out, lse4)


def _bwd(causal, scale, block_q, block_k, interpret, res, g):
    q, k, v, out, lse4 = res
    if scale is None:
        scale = q.shape[-1] ** -0.5
    return _bwd_sp(scale, causal, block_q, block_k, interpret)(
        q, k, v, out, lse4, g
    )


flash_attention.defvjp(_fwd, _bwd)
