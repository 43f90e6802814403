"""Fused KV-cache-write + decode attention (the flash-decode kernel).

The unfused decode step (ops/decode_attention.py) scatters the fresh
token's k/v into the [B, KH, S, D] slot cache with XLA `.at[].set()` ops
and then runs attention over the updated cache. That costs extra kernel
dispatches per layer and re-reads the freshly written row from HBM.

This kernel folds both into ONE Pallas program per (batch, kv-head):

* the caches stay in HBM (`memory_space=ANY`, aliased input->output);
  history streams through a double-buffered VMEM pipeline with explicit
  `make_async_copy` DMAs — int8 rows dequantize in VMEM right next to
  the MXU dot, and no [B, S] mask or bf16 cache copy is ever
  materialized;
* the fresh k/v row is DMA'd into its slot directly from VMEM while the
  history streams (write-write ordering with the history reads is free:
  history is masked STRICTLY below `pos`, and the fresh token's
  contribution comes from the VMEM operands, not from re-reading HBM);
* online softmax runs over ceil(pos/bs) blocks — a *dynamic* trip count,
  so short sequences do proportionally little work instead of scanning
  the whole cache the way a static XLA mask does.

Scale handling matches decode_attention: k_scale commutes out of the QK
dot, v_scale folds into the probabilities (reference for the layout
rationale: ops/decode_attention.py module docstring). The tiny per-step
scale scatters ([B, KH] floats) stay in XLA where they fuse with the
projections.

Cited parity surface: reference serving images do decode attention in
closed CUDA kernels (SURVEY.md §2.2 model-server-basaran / llama-cpp);
this is the TPU-native equivalent of their fused decode path.

Status: the v5e compiler refuses this kernel at every shape tried
(MOSAIC_REFUSAL; tests/test_chip_compile.py keeps the cases as strict
xfail). It runs in interpret mode only, is opt-in, and selecting it on a
TPU backend raises (check_lowers) until it is rewritten or deleted.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from substratus_tpu.ops import scopes

NEG_INF = -1e30

# What Mosaic (jax 0.9.0, libtpu 0.0.34, described v5e) says of this
# kernel. bf16 cache: the one-row DMA of the fresh k/v into the tiled HBM
# cache; int8 cache: the [B, KH, 1] fresh-scale operand's (1, 1, 1) block.
MOSAIC_REFUSAL = (
    "fused_decode_attention does not lower on TPU: Mosaic refuses the "
    "bf16 cache ('Slice shape along dimension 2 must be aligned to tiling "
    "(2), but is 1') and the int8 cache (block (1, 1, 1) on the [B, KH, 1] "
    "scale: 'the last two dimensions of your block shape' must be "
    "'divisible by 8 and 128 respectively, or be equal to the respective "
    "dimensions of the overall array')"
)


def check_lowers() -> None:
    """Raise where decode_attn_impl="fused" is selected on a TPU backend,
    with the compiler's message, before the first jitted step meets it."""
    if jax.default_backend() == "tpu":
        raise NotImplementedError(MOSAIC_REFUSAL)


def _kernel(
    pos_ref,   # scalar prefetch [B] int32
    q_ref,     # [1, 1, G8, D] VMEM (zero-padded groups)
    nk_ref,    # [1, 1, 1, D] VMEM fresh k (cache dtype)
    nv_ref,    # [1, 1, 1, D] VMEM fresh v
    *rest,
    scale: float,
    block_s: int,
    quantized: bool,
):
    if quantized:
        (nks_ref, nvs_ref, ck_ref, cv_ref, cks_ref, cvs_ref,
         o_ref, cko_ref, cvo_ref,
         kbuf, vbuf, ksbuf, vsbuf, rsem, wsem) = rest
    else:
        nks_ref = nvs_ref = cks_ref = cvs_ref = ksbuf = vsbuf = None
        (ck_ref, cv_ref, o_ref, cko_ref, cvo_ref,
         kbuf, vbuf, rsem, wsem) = rest
    del ck_ref, cv_ref  # aliased with cko/cvo; read via the output refs

    ib = pl.program_id(0)
    ih = pl.program_id(1)
    pos = pos_ref[ib]
    bs = block_s
    nblk = (pos + bs - 1) // bs  # history blocks (cols < pos), dynamic

    # Fresh-row writeback: straight from the VMEM operands into the HBM
    # slot. COMPLETED before any history read starts: when pos % bs != 0
    # the last history block covers row pos, and although that row is
    # masked to probability zero, a torn concurrent read could decode as
    # NaN and 0 * NaN would poison the p@V accumulation. The row is one
    # [1, D] burst, so serializing it ahead of the (much larger) history
    # stream costs nothing measurable.
    wk = pltpu.make_async_copy(
        nk_ref.at[0, 0], cko_ref.at[ib, ih, pl.ds(pos, 1), :], wsem.at[0]
    )
    wv = pltpu.make_async_copy(
        nv_ref.at[0, 0], cvo_ref.at[ib, ih, pl.ds(pos, 1), :], wsem.at[1]
    )
    wk.start()
    wv.start()
    wk.wait()
    wv.wait()

    def dma_k(i, slot):
        return pltpu.make_async_copy(
            cko_ref.at[ib, ih, pl.ds(i * bs, bs), :],
            kbuf.at[slot], rsem.at[0, slot],
        )

    def dma_v(i, slot):
        return pltpu.make_async_copy(
            cvo_ref.at[ib, ih, pl.ds(i * bs, bs), :],
            vbuf.at[slot], rsem.at[1, slot],
        )

    def dma_ks(i, slot):
        return pltpu.make_async_copy(
            cks_ref.at[ib, pl.ds(ih, 1), pl.ds(i * bs, bs)],
            ksbuf.at[slot], rsem.at[2, slot],
        )

    def dma_vs(i, slot):
        return pltpu.make_async_copy(
            cvs_ref.at[ib, pl.ds(ih, 1), pl.ds(i * bs, bs)],
            vsbuf.at[slot], rsem.at[3, slot],
        )

    def start(i, slot):
        dma_k(i, slot).start()
        dma_v(i, slot).start()
        if quantized:
            dma_ks(i, slot).start()
            dma_vs(i, slot).start()

    def wait(i, slot):
        dma_k(i, slot).wait()
        dma_v(i, slot).wait()
        if quantized:
            dma_ks(i, slot).wait()
            dma_vs(i, slot).wait()

    @pl.when(nblk > 0)
    def _prologue():
        start(0, 0)

    qh = q_ref[0, 0].astype(jnp.float32) * scale  # [G8, D]
    g8 = qh.shape[0]

    def body(i, carry):
        m, l, acc = carry
        slot = lax.rem(i, 2)

        @pl.when(i + 1 < nblk)
        def _prefetch():
            start(i + 1, lax.rem(i + 1, 2))

        wait(i, slot)
        kf = kbuf[slot].astype(jnp.float32)  # [bs, D]
        vf = vbuf[slot].astype(jnp.float32)
        s = lax.dot_general(
            qh, kf, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [G8, bs]
        if quantized:
            s = s * ksbuf[slot]  # [1, bs] broadcast
        cols = lax.broadcasted_iota(jnp.int32, (1, bs), 1) + i * bs
        s = jnp.where(cols < pos, s, NEG_INF)  # STRICT history mask
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        if quantized:
            p = p * vsbuf[slot]
        acc = acc * alpha + lax.dot_general(
            p, vf, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return m_new, l, acc

    d = qh.shape[1]
    m0 = jnp.full((g8, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((g8, 1), jnp.float32)
    a0 = jnp.zeros((g8, d), jnp.float32)
    m, l, acc = lax.fori_loop(0, nblk, body, (m0, l0, a0))

    # Epilogue: the CURRENT token, straight from the VMEM operands. It
    # always contributes (its own query attends to it), so l > 0 and no
    # empty-row guard is needed.
    kf = nk_ref[0, 0].astype(jnp.float32)  # [1, D]
    vf = nv_ref[0, 0].astype(jnp.float32)
    s = lax.dot_general(
        qh, kf, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [G8, 1]
    if quantized:
        s = s * nks_ref[0, 0]
    m_new = jnp.maximum(m, s)
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m - m_new)
    l = alpha * l + p
    if quantized:
        p = p * nvs_ref[0, 0]
    acc = acc * alpha + p * vf
    o_ref[0, 0] = (acc / l).astype(o_ref.dtype)


def _pad_groups(q: jnp.ndarray, kh: int) -> Tuple[jnp.ndarray, int, int]:
    """[B, 1, H, D] -> [B, KH, G8, D] with zero-padded group rows (sublane
    tiles want >= 8 query rows; padded rows renormalize to garbage that is
    sliced away)."""
    b, _, h, d = q.shape
    g = h // kh
    g8 = max(g, 8)
    qr = q.reshape(b, kh, g, d)
    if g8 != g:
        qr = jnp.pad(qr, ((0, 0), (0, 0), (0, g8 - g), (0, 0)))
    return qr, g, g8


def fused_decode_attention(
    q: jnp.ndarray,        # [B, 1, H, D]
    new_k: jnp.ndarray,    # [B, KH, 1, D] fresh row, cache dtype
    new_v: jnp.ndarray,    # [B, KH, 1, D]
    cache_k: jnp.ndarray,  # [B, KH, S, D] WITHOUT the fresh row
    cache_v: jnp.ndarray,  # [B, KH, S, D]
    positions: jnp.ndarray,  # [B] slot of the fresh token
    new_ks: Optional[jnp.ndarray] = None,   # [B, KH, 1] f32
    new_vs: Optional[jnp.ndarray] = None,
    cache_ks: Optional[jnp.ndarray] = None,  # [B, KH, S] f32 (fresh scale
    cache_vs: Optional[jnp.ndarray] = None,  # already scattered by caller)
    *,
    block_s: int = 256,
    interpret: bool = False,
):
    """Write the fresh kv row into its cache slot AND attend, one kernel.

    Returns (attn [B, 1, H, D], cache_k', cache_v') — the caches with the
    fresh row written (aliased in-place on TPU).

    Under GSPMD sharding this routes through a custom_partitioning rule
    (decode attention is local per (batch, kv-head) shard, zero
    collectives), so the kernel survives sharded serving instead of
    being pinned to the XLA fallback (round-4 gap)."""
    if not interpret:
        check_lowers()
    quantized = new_ks is not None
    args = (q, new_k, new_v, cache_k, cache_v, positions)
    if quantized:
        args = args + (new_ks, new_vs, cache_ks, cache_vs)
    return _fused_sp(quantized, block_s, interpret)(*args)


def _fused_impl(
    q, new_k, new_v, cache_k, cache_v, positions,
    new_ks=None, new_vs=None, cache_ks=None, cache_vs=None,
    *,
    block_s: int = 256,
    interpret: bool = False,
):
    b, _, h, d = q.shape
    kh, s_len = cache_k.shape[1], cache_k.shape[2]
    quantized = new_ks is not None
    # Halve-until-divides (same invariant as flash_attention._fit_block):
    # keeps the block lane-aligned for the usual power-of-two cache
    # lengths instead of walking down to odd sizes Mosaic lowers badly.
    bs = min(block_s, s_len)
    while s_len % bs:
        bs //= 2
    # Defense in depth against position drift (see engine._decode_step):
    # a position at/past the cache length would DMA-write outside the
    # slot's rows, corrupting a neighbouring head's cache.
    positions = jnp.clip(positions.astype(jnp.int32), 0, s_len - 1)
    qr, g, g8 = _pad_groups(q, kh)

    kernel = functools.partial(
        _kernel, scale=d ** -0.5, block_s=bs, quantized=quantized,
    )
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    q_spec = pl.BlockSpec((1, 1, g8, d), lambda ib, ih, pos: (ib, ih, 0, 0))
    nkv_spec = pl.BlockSpec((1, 1, 1, d), lambda ib, ih, pos: (ib, ih, 0, 0))
    ns_spec = pl.BlockSpec((1, 1, 1), lambda ib, ih, pos: (ib, ih, 0))

    if quantized:
        in_specs = [q_spec, nkv_spec, nkv_spec, ns_spec, ns_spec,
                    any_spec, any_spec, any_spec, any_spec]
        operands = (qr, new_k, new_v, new_ks, new_vs,
                    cache_k, cache_v, cache_ks, cache_vs)
        # operand indices INCLUDING the scalar-prefetch arg: pos=0, q=1,
        # nk=2, nv=3, nks=4, nvs=5, ck=6, cv=7
        aliases = {6: 1, 7: 2}
        scratch = [
            pltpu.VMEM((2, bs, d), cache_k.dtype),
            pltpu.VMEM((2, bs, d), cache_v.dtype),
            pltpu.VMEM((2, 1, bs), jnp.float32),
            pltpu.VMEM((2, 1, bs), jnp.float32),
            pltpu.SemaphoreType.DMA((4, 2)),
            pltpu.SemaphoreType.DMA((2,)),
        ]
    else:
        in_specs = [q_spec, nkv_spec, nkv_spec, any_spec, any_spec]
        operands = (qr, new_k, new_v, cache_k, cache_v)
        aliases = {4: 1, 5: 2}
        scratch = [
            pltpu.VMEM((2, bs, d), cache_k.dtype),
            pltpu.VMEM((2, bs, d), cache_v.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),  # k/v rows only (no scales)
            pltpu.SemaphoreType.DMA((2,)),
        ]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, kh),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, g8, d), lambda ib, ih, pos: (ib, ih, 0, 0)),
            any_spec,
            any_spec,
        ],
        scratch_shapes=scratch,
    )
    out, ck, cv = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, kh, g8, d), q.dtype),
            jax.ShapeDtypeStruct(cache_k.shape, cache_k.dtype),
            jax.ShapeDtypeStruct(cache_v.shape, cache_v.dtype),
        ],
        input_output_aliases=aliases,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name=scopes.ATTN_CORE,
    )(positions.astype(jnp.int32), *operands)
    attn = out[:, :, :g, :].reshape(b, 1, h, d)
    return attn, ck, cv


_FUSED_SP_CACHE: dict = {}


def _fused_sp(quantized: bool, block_s: int, interpret):
    """SPMD rule (ops/kernel_partition.py): the kernel is local once
    batch and kv-head axes shard — every operand either carries those
    axes or is per-batch (positions). The cache (index 3) is the
    committed reference; sequence and head-dim axes stay unsharded."""
    key = (quantized, block_s, interpret)
    if key in _FUSED_SP_CACHE:
        return _FUSED_SP_CACHE[key]
    from substratus_tpu.ops.kernel_partition import bh_partitioned

    def impl(*args):
        if quantized:
            q, nk, nv, ck, cv, pos, nks, nvs, cks, cvs = args
            return _fused_impl(
                q, nk, nv, ck, cv, pos, nks, nvs, cks, cvs,
                block_s=block_s, interpret=interpret,
            )
        q, nk, nv, ck, cv, pos = args
        return _fused_impl(
            q, nk, nv, ck, cv, pos, block_s=block_s, interpret=interpret,
        )

    arg_dims = [
        (0, 2),     # q [B, 1, H, D]
        (0, 1),     # new_k [B, KH, 1, D]
        (0, 1),     # new_v
        (0, 1),     # cache_k [B, KH, S, D]
        (0, 1),     # cache_v
        (0, None),  # positions [B]
    ]
    rule_in = [
        "b u h d", "b k v d", "b k w d", "b k s d", "b k s d", "b",
    ]
    if quantized:
        arg_dims += [(0, 1)] * 4  # new_ks, new_vs, cache_ks, cache_vs
        rule_in += ["b k v2", "b k w2", "b k s2", "b k s3"]
    f = bh_partitioned(
        impl,
        arg_dims=arg_dims,
        out_dims=[(0, 2), (0, 1), (0, 1)],  # attn, cache_k', cache_v'
        sharding_rule=(
            ", ".join(rule_in) + " -> b u h d, b k s d, b k s d"
        ),
        ref=3,
    )
    _FUSED_SP_CACHE[key] = f
    return f
