"""Attention that reads the paged pool where it lies (Pallas, Mosaic): one
kernel for a decode step, one for a prefill chunk or a verify round.

Both take the stacked pool of ops/kvcache.py,

    k/v  [layers, pages, page_size, kv_heads, head_dim]

as an HBM operand as it is: no BlockSpec copies it and nothing views it in
another shape, so the compiler has no reason to lay it out anew. Mosaic
tiles a row of 128 lanes: a pool of 64-wide heads is stored two KV heads to
such a row and comes here as half as many heads of 128, with q widened to
the row and the scale given (ops/kvcache.py::_over_stored_rows). Row b
reads pages block_table[b, 0 .. (its last position) // page_size] of `layer`
by its own DMAs and stops: the work a row costs follows its own length,
shapes stay static, and nothing of size rows x max_seq_len is written
anywhere.

**One query token a row** (`paged_decode_attention`). One invocation walks
every row. Pages arrive a block at a time into one of
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
the scale is head_dim ** -0.5 unless given, masking is k_pos <=
positions[b]. The
probabilities meet a bfloat16 V as two bfloat16 parts (value and rounding
remainder) stacked into one left operand, so the float32 softmax loses no
more than 2**-16 of a weight on the way and V passes the MXU once.

**S query tokens a row** (`paged_chunk_attention`). The decode kernel's
trick does not carry over: a chunk is bound by the MXU, and seven eighths
of it thrown away is seven times the work. Here the `group` query heads of
one KV head x the S tokens are the columns of one operand, transposed (Q^T
[hd, S x group]: a query is a lane), and meet that head's keys only: scores
[keys, columns] = K_g [keys, hd] x Q^T, so the softmax's maximum and sum run
down sublanes (elementwise over vregs; a cross-lane reduction a row and fold
would cost the XLU more than the dots cost the MXU), its state is a row
vector a head, and the output accumulates as V_g^T [hd, keys] x P^T. A page
is [page_size, kv_heads, hd], so one head's rows lie kv_heads apart: each
arrived block of 512 keys is laid out head-major once, by a sublane-strided
read of 32-bit words (`_head_pairs`), and every column then shares it (on
the chip the re-layout is not measurable beside the dots, and a plain
indexed read of the head was 7-11 % slower: PERF.md section 6, PR 30).
Blocks that end before the row's first query position skip the mask. The
arithmetic is the same as above, but the probabilities meet V as one
bfloat16 operand: the path this replaces rounds both operands of both of
its float32 dots to bfloat16 on the MXU (XLA's default precision on a TPU),
and a second pass would cost a quarter more.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# Tokens one fold of the running softmax takes, in whole pages of the pool
# (`fold_pages`): the smallest of these that holds what a block has of its
# row (a fold costs 0.35 us however small, then 0.08 us for 16 tokens: at
# the page of 16 an idle row's one page is folded as 2, a row's full blocks
# as 32; at a page of 64 as 1 and as 8). The largest is also the tokens of a
# DMA block, of which two are in VMEM: the blocks, the folds and VMEM are
# the same at every page that divides them, and a longer page is fewer
# copies. Measured on the chip: PERF.md section 6, PR 28 and PR 48.
FOLD_TOKENS = (32, 128, 512)
LANES = 128


def fold_pages(bs: int, tokens=FOLD_TOKENS) -> tuple:
    """`tokens` in pages of `bs` tokens, ascending, each at least one page
    and none twice: (2, 8, 32) at 16, (1, 2, 8) at 64, (1, 4) at 128."""
    return tuple(sorted({max(t // bs, 1) for t in tokens}))


def _div(x, n: int):
    return x >> (n.bit_length() - 1) if n & (n - 1) == 0 else x // n


def _mod(x, n: int):
    return x & (n - 1) if n & (n - 1) == 0 else x % n


def _block_copies(layer, bt_ref, pools, bufs, sem, pages_of):
    """each_copy(row, j, slot, do): block j of `row` <-> buffer `slot`, one
    K and one V copy a page the row holds there (the last block of a row
    may hold fewer than the buffer's pages); `do` starts or waits."""
    ppb = bufs[0].shape[1]

    def each_copy(row, j, slot, do):
        def page(i, _):
            at = bt_ref[row, j * ppb + i]
            for s, (pool, buf) in enumerate(zip(pools, bufs)):
                do(pltpu.make_async_copy(
                    pool.at[layer, at], buf.at[slot, i], sem.at[s, slot]))

        lax.fori_loop(
            0, jnp.minimum(pages_of(row) - j * ppb, ppb), page, None)

    return each_copy


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

    each_copy = _block_copies(
        layer, bt_ref, (k_hbm, v_hbm), (kbuf, vbuf), sem, pages_of)

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
            for pages in fold_pages(bs):
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


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_decode_attention(
    q: jnp.ndarray,  # [B, H, hd]: one query token a row
    k_pool: jnp.ndarray,  # [L, P, bs, KH, hd]
    v_pool: jnp.ndarray,
    layer: jnp.ndarray,  # scalar int32: the layer of the stack to read
    block_table: jnp.ndarray,  # [B, M] int32 page ids
    positions: jnp.ndarray,  # [B] the query's position = the last to see
    scale: Optional[float] = None,  # of the scores; hd ** -0.5 unless given
    interpret: bool = False,
) -> jnp.ndarray:
    """softmax(q . K * scale) V over positions 0..positions[b] of row b,
    read through its block table out of `layer` of the pool; [B, H, hd] in
    q.dtype. Row b reads positions[b] // bs + 1 pages, whatever the table
    or the other rows hold."""
    n_rows, n_heads, hd = q.shape
    bs, kh = k_pool.shape[2:4]
    assert n_heads % kh == 0, (n_heads, kh)
    block = (2, fold_pages(bs)[-1], bs, kh, hd)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        functools.partial(
            _kernel, scale=hd ** -0.5 if scale is None else scale),
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


# --- more than one query token a row: a prefill chunk, a verify round --------

# Keys of one DMA block of the chunk kernel, in whole pages of the pool (two
# such blocks of K and of V are in VMEM, the next on its way while one is
# folded): 32 pages of 16 tokens, 8 of 64.
CHUNK_TOKENS = 512
# Query columns (S x group, padded to lanes) one grid step keeps the running
# softmax of, and those one fold takes of them. Measured on the chip: PERF.md
# section 6, PR 30.
CHUNK_QUERIES = 2048
FOLD_QUERIES = 256


def _head_pairs(block, keys: int, kh: int, hd: int):
    """A block of pages [pages, bs, KH, hd] head by head: float32 [keys, hd]
    of KV heads 2j and 2j + 1, for j = 0 .. KH / 2. Read as a matrix the
    block holds token t of head g in row t * KH + g, and two bfloat16 rows
    share a 32-bit sublane: rows 2r and 2r + 1 are the halves of word-row r.
    A load of every (KH / 2)th word-row is the sublane stride the hardware
    has (a 16-bit stride it has not); a half widened to float32 is exact."""
    words = block.reshape(keys * kh, hd).bitcast(jnp.uint32)
    for j in range(kh // 2):
        w = words[pl.ds(j, keys, stride=kh // 2), :]
        yield (pltpu.bitcast(w << 16, jnp.float32),
               pltpu.bitcast(w & jnp.uint32(0xFFFF0000), jnp.float32))


def _chunk_kernel(layer_ref, last_ref, first_ref, bt_ref, qpos_ref, q_ref,
                  k_hbm, v_hbm, o_ref, kbuf, vbuf, sem, slot_ref, kh_ref,
                  vt_ref, m_ref, l_ref, acc_ref, *, scale: float):
    """One grid step: row b's queries [KH, hd, columns i] (transposed: a
    query is a lane) against the row's pages 0 .. last[b] // page_size."""
    b, i = pl.program_id(0), pl.program_id(1)
    n_rows, n_tiles = pl.num_programs(0), pl.num_programs(1)
    kh, hd, width = q_ref.shape[1:]
    ppb, bs = kbuf.shape[1:3]
    keys = ppb * bs  # of one block
    fold = math.gcd(width, FOLD_QUERIES)
    layer = layer_ref[0]

    def pages_of(row):
        return _div(last_ref[row], bs) + 1

    each_copy = _block_copies(
        layer, bt_ref, (k_hbm, v_hbm), (kbuf, vbuf), sem, pages_of)

    def fold_block(j, masked: bool):
        """Fold the block held head-major in kh_ref / vt_ref into the running
        softmax of every head and query column of this step."""
        first_key = j * keys

        def head(g, _):
            k, vt = kh_ref[g], vt_ref[g]  # [keys, hd], [hd, keys]
            for c in range(0, width, fold):
                cols = pl.ds(c, fold)
                s = jnp.dot(k, q_ref[0, g, :, cols],
                            preferred_element_type=jnp.float32) * scale
                if masked:
                    k_pos = first_key + lax.broadcasted_iota(
                        jnp.int32, s.shape, 0)
                    s = jnp.where(k_pos <= qpos_ref[0, :, cols], s, NEG_INF)
                # what the scratch holds before a row's first block is
                # nobody's: selected away, never initialised
                m_prev = jnp.where(j == 0, NEG_INF, m_ref[g, :, cols])
                l_prev = jnp.where(j == 0, 0.0, l_ref[g, :, cols])
                acc = jnp.where(j == 0, 0.0, acc_ref[g, :, cols])
                m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
                p = jnp.exp(s - m_new)  # [keys, fold], 0 where masked
                alpha = jnp.exp(m_prev - m_new)
                m_ref[g, :, cols] = m_new
                l_ref[g, :, cols] = alpha * l_prev + jnp.sum(
                    p, axis=0, keepdims=True)
                acc_ref[g, :, cols] = acc * alpha + jnp.dot(
                    vt, p.astype(vt.dtype), preferred_element_type=jnp.float32)

        lax.fori_loop(0, kh, head, None)

    @pl.when((b == 0) & (i == 0))
    def _():
        slot_ref[0] = 0
        each_copy(0, 0, 0, lambda c: c.start())

    last, n_pages = last_ref[b], pages_of(b)
    n_blocks = pl.cdiv(n_pages, ppb)

    def block(j, slot):
        more = j + 1 < n_blocks

        @pl.when(more)
        def _():
            each_copy(b, j + 1, 1 - slot, lambda c: c.start())

        # the next grid step's first block: this row again, or the next
        @pl.when(jnp.logical_not(more) & (i + 1 < n_tiles))
        def _():
            each_copy(b, 0, 1 - slot, lambda c: c.start())

        @pl.when(jnp.logical_not(more) & (i + 1 == n_tiles)
                 & (b + 1 < n_rows))
        def _():
            each_copy(b + 1, 0, 1 - slot, lambda c: c.start())

        each_copy(b, j, slot, lambda c: c.wait())
        first_key = j * keys

        @pl.when(first_key + keys > last + 1)
        def _():
            # Past the row's last position (the rest of its last page,
            # pages of the buffer no copy filled) nothing is the row's:
            # zero it, since a probability of 0 times a NaN is a NaN.
            shape = vbuf.shape[1:]
            tok = (first_key + lax.broadcasted_iota(jnp.int32, shape, 0) * bs
                   + lax.broadcasted_iota(jnp.int32, shape, 1))
            vbuf[slot] = jnp.where(tok <= last, vbuf[slot], 0).astype(
                vbuf.dtype)

        for j2, ((k0, k1), (v0, v1)) in enumerate(zip(
                _head_pairs(kbuf.at[slot], keys, kh, hd),
                _head_pairs(vbuf.at[slot], keys, kh, hd))):
            kh_ref[2 * j2] = k0.astype(kh_ref.dtype)
            kh_ref[2 * j2 + 1] = k1.astype(kh_ref.dtype)
            vt_ref[2 * j2] = v0.T.astype(vt_ref.dtype)
            vt_ref[2 * j2 + 1] = v1.T.astype(vt_ref.dtype)

        # a block that ends at or before the row's first query position is
        # seen whole by every query: no mask
        hidden = first_key + keys - 1 > first_ref[b]

        @pl.when(hidden)
        def _():
            fold_block(j, True)

        @pl.when(jnp.logical_not(hidden))
        def _():
            fold_block(j, False)

        return 1 - slot

    slot_ref[0] = lax.fori_loop(0, n_blocks, block, slot_ref[0])
    for g in range(kh):
        o_ref[0, g] = (acc_ref[g] / l_ref[g]).astype(o_ref.dtype)


def _round_up(x: int, n: int) -> int:
    return -(-x // n) * n


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_chunk_attention(
    q: jnp.ndarray,  # [B, S, H, hd]: S query tokens a row
    k_pool: jnp.ndarray,  # [L, P, bs, KH, hd], bfloat16
    v_pool: jnp.ndarray,
    layer: jnp.ndarray,  # scalar int32: the layer of the stack to read
    block_table: jnp.ndarray,  # [B, M] int32 page ids
    positions: jnp.ndarray,  # [B, S]: query i sees positions 0..positions[b, i]
    scale: Optional[float] = None,  # of the scores; hd ** -0.5 unless given
    interpret: bool = False,
) -> jnp.ndarray:
    """softmax(q . K * scale) V for every query of every row, each over
    positions 0..its own of its row, read through the block table out of
    `layer` of the pool; [B, S, H, hd] in q.dtype. Row b reads
    max(positions[b]) // bs + 1 pages, whatever the table holds, and no
    score leaves VMEM. The positions are read, not assumed: a chunk's padded
    tail clamped onto one position and a verify round's consecutive ones
    are the same call."""
    b, s, h, hd = q.shape
    bs, kh = k_pool.shape[2:4]
    assert h % kh == 0 and kh % 2 == 0, (h, kh)
    assert k_pool.dtype == v_pool.dtype == jnp.bfloat16, k_pool.dtype
    group = h // kh
    # Transposed, a query a lane: column j * S + i of KV head g is query
    # token i of query head g * group + j. So K meets Q^T as it lies
    # ([keys, hd] x [hd, columns]), the softmax's maximum and sum run down
    # sublanes (elementwise over vregs, no cross-lane reduction), and its
    # state is a row vector a head.
    cols = s * group
    width = min(_round_up(cols, LANES), CHUNK_QUERIES)
    padded = _round_up(cols, width)
    qt = q.reshape(b, s, kh, group, hd).transpose(0, 2, 4, 3, 1)
    qt = jnp.pad(qt.reshape(b, kh, hd, cols),
                 ((0, 0), (0, 0), (0, 0), (0, padded - cols)))
    # a query past the table's reach sees the whole table, as the gather's
    reach = block_table.shape[1] * bs - 1
    positions = jnp.minimum(positions.astype(jnp.int32), reach)
    qpos = jnp.pad(jnp.tile(positions, (1, group)),
                   ((0, 0), (0, padded - cols)))[:, None]  # [B, 1, padded]
    ppb = max(CHUNK_TOKENS // bs, 1)
    keys = ppb * bs
    tile = lambda *shape: pl.BlockSpec(  # noqa: E731
        (1,) + shape + (width,), lambda b, i, *_: (b,) + (0,) * len(shape) + (i,))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    block = (2, ppb, bs, kh, hd)
    vmem = (
        2 * 2 * kh * hd * width * q.dtype.itemsize  # q and out, two buffers
        + kh * hd * width * 4  # acc
        + 2 * 2 * keys * kh * hd * 2  # the DMA blocks
        + 2 * keys * kh * hd * 2  # head-major K and V^T
        + 2 * 3 * keys * FOLD_QUERIES * 4  # a fold's scores, as values
    )
    out = pl.pallas_call(
        functools.partial(
            _chunk_kernel, scale=hd ** -0.5 if scale is None else scale),
        out_shape=jax.ShapeDtypeStruct((b, kh, hd, padded), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(b, padded // width),
            in_specs=[tile(1), tile(kh, hd), hbm, hbm],
            out_specs=tile(kh, hd),
            scratch_shapes=[
                pltpu.VMEM(block, k_pool.dtype),
                pltpu.VMEM(block, v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((1,), jnp.int32),
                pltpu.VMEM((kh, keys, hd), k_pool.dtype),
                pltpu.VMEM((kh, hd, keys), v_pool.dtype),
                pltpu.VMEM((kh, 1, width), jnp.float32),
                pltpu.VMEM((kh, 1, width), jnp.float32),
                pltpu.VMEM((kh, hd, width), jnp.float32),
            ],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem + (8 << 20),
        ),
        interpret=interpret,
        name="paged_chunk_attention",
    )(
        layer.astype(jnp.int32).reshape(1), positions.max(axis=1),
        positions.min(axis=1), block_table.astype(jnp.int32), qpos, qt,
        k_pool, v_pool,
    )
    out = out[..., :cols].reshape(b, kh, hd, group, s)
    return out.transpose(0, 4, 1, 3, 2).reshape(b, s, h, hd)
