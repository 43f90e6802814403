"""Decode attention that reads the paged pool where it lies (Pallas, Mosaic).

One query token a row against the stacked pool of ops/kvcache.py,

    k/v  [layers, pages, page_size, kv_heads, head_dim]

taken as an HBM operand as it is: no BlockSpec copies it and nothing views
it in another shape, so the compiler has no reason to lay it out anew. Row b
reads pages block_table[b, 0 .. positions[b] // page_size] of `layer` by its
own DMAs and stops: the work a row costs follows its own length, shapes stay
static, and nothing of size max_batch x max_seq_len is written anywhere.

One invocation walks every row. Pages arrive a block at a time into one of
two VMEM buffers while the block before is folded into a running softmax
(the next block, of this row or the next, is on its way meanwhile: a row of
one page would otherwise wait out a DMA's latency alone).

A page [page_size, kv_heads, head_dim] is, read as a matrix, page_size *
kv_heads rows of head_dim: row r holds token r // kv_heads of head
r % kv_heads. So all query heads meet a whole block in one plain matmul,
q [H, hd] x K^T [hd, rows], and a mask keeps of each score row the columns
of its own KV head (and of positions <= the query's); the probabilities,
zero elsewhere, meet V the same way. Seven eighths of the products are
thrown away, and the MXU, idle in a decode step, has them to spare: no head
is ever sliced out of a page (a sublane-strided read) and no page is
transposed.

Arithmetic is ops/attention.py::dot_product_attention's: K, V and q enter
the dots as stored, scores, maximum, sum and output accumulate in float32,
the scale is head_dim ** -0.5, masking is k_pos <= positions[b]. The
probabilities meet a bfloat16 V as two bfloat16 parts (value and rounding
remainder) stacked into one left operand, so the float32 softmax loses no
more than 2**-16 of a weight on the way and V passes the MXU once.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# Pages one fold of the running softmax takes: the smallest of these that
# holds what a block has of its row (a fold costs 0.35 us however small, then
# 0.08 us a page: an idle row's one page is folded as 2, a row's full
# blocks as 32). The largest is also the pages of a DMA block, of which two
# are in VMEM. Measured on the chip: PERF.md section 6, PR 28.
FOLD_PAGES = (2, 8, 32)
LANES = 128


def _div(x, n: int):
    return x >> (n.bit_length() - 1) if n & (n - 1) == 0 else x // n


def _mod(x, n: int):
    return x & (n - 1) if n & (n - 1) == 0 else x % n


def _kernel(layer_ref, pos_ref, bt_ref, q_ref, k_hbm, v_hbm, o_ref,
            kbuf, vbuf, sem, m_ref, l_ref, acc_ref, *, scale: float):
    n_rows, n_heads, hd = q_ref.shape
    bs, kh = k_hbm.shape[2:4]
    ppb = kbuf.shape[1]
    group = n_heads // kh
    max_pages = bt_ref.shape[1]
    layer = layer_ref[0]

    def pages_of(b):
        return jnp.minimum(_div(pos_ref[b], bs) + 1, max_pages)

    def each_copy(b, j, slot, do):
        """Block j of row b <-> buffer `slot`: one K and one V copy a page
        the row holds there (the last block of a row may hold fewer)."""
        def page(i, _):
            at = bt_ref[b, j * ppb + i]
            for s, (pool, buf) in enumerate(((k_hbm, kbuf), (v_hbm, vbuf))):
                do(pltpu.make_async_copy(
                    pool.at[layer, at], buf.at[slot, i], sem.at[s, slot]))

        lax.fori_loop(0, jnp.minimum(pages_of(b) - j * ppb, ppb), page, None)

    def fold(b, j, slot, pages: int, pos):
        """Fold the first `pages` pages of block j of row b, arrived in
        `slot`, into m, l, acc."""
        first = j * (ppb * bs)  # position of the block's first token
        cols = pages * bs * kh  # rows of the pages read as a matrix

        @pl.when(first + pages * bs > pos + 1)
        def _():
            # What lies past the row's own position (the rest of its last
            # page, pages of the buffer no copy filled) is not the row's:
            # zero it, since a probability of 0 times a NaN is a NaN.
            shape = (pages,) + vbuf.shape[2:]
            tok = (first + lax.broadcasted_iota(jnp.int32, shape, 0) * bs
                   + lax.broadcasted_iota(jnp.int32, shape, 1))
            vbuf[slot, :pages] = jnp.where(
                tok <= pos, vbuf[slot, :pages], 0).astype(vbuf.dtype)

        k = kbuf.at[slot, :pages].reshape(cols, hd)[...]
        v = vbuf.at[slot, :pages].reshape(cols, hd)[...]
        s = lax.dot_general(
            q_ref[b], k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [H, cols]
        col = lax.broadcasted_iota(jnp.int32, s.shape, 1)
        head = _div(lax.broadcasted_iota(jnp.int32, s.shape, 0), group)
        own = (_mod(col, kh) == head) & (first + _div(col, kh) <= pos)
        s = jnp.where(own, s, NEG_INF)
        m_prev = m_ref[...]  # [H, LANES], every lane the same
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new[:, :1])  # 0 where masked
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        m_ref[...] = m_new
        if v.dtype == jnp.float32:
            pv = jnp.dot(p, v, preferred_element_type=jnp.float32)
        else:
            hi = p.astype(v.dtype)
            lo = (p - hi.astype(jnp.float32)).astype(v.dtype)
            both = jnp.dot(jnp.concatenate([hi, lo], axis=0), v,
                           preferred_element_type=jnp.float32)
            pv = both[:n_heads] + both[n_heads:]
        acc_ref[...] = acc_ref[...] * alpha[:, :1] + pv

    each_copy(0, 0, 0, lambda c: c.start())

    def row(b, slot):
        pos = pos_ref[b]
        n_blocks = pl.cdiv(pages_of(b), ppb)
        m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

        def block(j, slot):
            more = j + 1 < n_blocks

            @pl.when(more)
            def _():
                each_copy(b, j + 1, 1 - slot, lambda c: c.start())

            @pl.when(jnp.logical_not(more) & (b + 1 < n_rows))
            def _():
                each_copy(b + 1, 0, 1 - slot, lambda c: c.start())

            each_copy(b, j, slot, lambda c: c.wait())
            held = pages_of(b) - j * ppb  # may pass ppb
            fewer = 0
            for pages in FOLD_PAGES:
                fits = held > fewer
                if pages < ppb:
                    fits &= held <= pages

                @pl.when(fits)
                def _():
                    fold(b, j, slot, pages, pos)

                fewer = pages
            return 1 - slot

        slot = lax.fori_loop(0, n_blocks, block, slot)
        o_ref[b] = (acc_ref[...] / l_ref[:, :1]).astype(o_ref.dtype)
        return slot

    lax.fori_loop(0, n_rows, row, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention(
    q: jnp.ndarray,  # [B, H, hd]: one query token a row
    k_pool: jnp.ndarray,  # [L, P, bs, KH, hd]
    v_pool: jnp.ndarray,
    layer: jnp.ndarray,  # scalar int32: the layer of the stack to read
    block_table: jnp.ndarray,  # [B, M] int32 page ids
    positions: jnp.ndarray,  # [B] the query's position = the last to see
    interpret: bool = False,
) -> jnp.ndarray:
    """softmax(q . K / sqrt(hd)) V over positions 0..positions[b] of row b,
    read through its block table out of `layer` of the pool; [B, H, hd] in
    q.dtype. Row b reads positions[b] // bs + 1 pages, whatever the table
    or the other rows hold."""
    n_rows, n_heads, hd = q.shape
    bs, kh = k_pool.shape[2:4]
    assert n_heads % kh == 0, (n_heads, kh)
    block = (2, FOLD_PAGES[-1], bs, kh, hd)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        functools.partial(_kernel, scale=hd ** -0.5),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        in_specs=[smem, smem, smem, vmem, hbm, hbm],
        out_specs=vmem,
        scratch_shapes=[
            pltpu.VMEM(block, k_pool.dtype),
            pltpu.VMEM(block, v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM((n_heads, LANES), jnp.float32),
            pltpu.VMEM((n_heads, LANES), jnp.float32),
            pltpu.VMEM((n_heads, hd), jnp.float32),
        ],
        interpret=interpret,
        name="paged_decode_attention",
    )(
        layer.astype(jnp.int32).reshape(1), positions.astype(jnp.int32),
        block_table.astype(jnp.int32), q, k_pool, v_pool,
    )
