"""Attention over a pool of latent rows (Pallas, Mosaic): one kernel for a
decode step, one for a prefill chunk.

Multi-head latent attention (models/deepseek_v3.py) keeps one row a token
and layer for all its heads: `[ckv (rkv); kr (dr)]`, the normed latent and
the rotated shared key, stored `w` wide with zeros behind them (w a
multiple of the 128 lanes: ops/kvcache.py::init_latent_cache decides). The
pool

    lat  [layers, pages, page_size, 1, w]

comes here as it lies, read as [layers, pages, page_size, w] (the same
bytes: a page is a matrix of page_size rows, whole 16-row tiles of
bfloat16), and row b reads pages block_table[b, 0 .. (its last position)
// page_size] of `layer` by its own DMAs, as ops/paged_attention.py's
kernels do (their `_block_copies`): the work follows the live context. A
copy costs its issue and not its bytes, so the family states a page of 128
tokens (models/deepseek_v3.py::PAGE_TOKENS) and both kernels size their
blocks in tokens: the pages of a block follow from the pool's.

**One query token a row, absorbed** (`latent_decode_attention`). The
caller has carried each head's q_nope through W_UK^T into the latent's
space: a query is `[qa (rkv); q_rope (dr); 0]`, w wide, and keys and values
are the same rows. Per block of t tokens `S = QA [H, w] x Lat^T [w, t]` with
the heads as the rows, a running softmax in float32, `OL += P [H, t] x
Lat[:, :rkv]`; out [H, rkv] a row, which the caller carries through W_UV.
One pass over the live rows serves all heads: 2 H (w + rkv) FLOPs over
2 w bytes a token, at the published sizes near the v5e's ridge. The
probabilities meet the latents as one bfloat16 operand (the MXU is not
idle here, as it is under ops/paged_attention.py's decode kernel).

**S query tokens a row, expanded** (`latent_chunk_attention`). A chunk is
bound by the MXU and the absorbed form would spend (w + rkv) multiply-adds
a head and pair where the expanded one spends dn + dr + dv: so each block
of arrived latents is expanded through W_UKV in VMEM, head by head, and
never leaves it: `K_i = Lat[:, :rkv] W_UK_i` [t, dn], `V_i^T = W_UV_i^T
Lat[:, :rkv]^T` [dv, t] (both contract the minor dimension of W_UKV as it
is stored, [H, dn + dv, rkv]: nothing is transposed), scores [keys,
queries] = `K_i Q_nope_i^T + Lat[:, rkv:] Q_rope_i^T` with a query a lane
(the softmax's maximum and sum run down sublanes, as in
ops/paged_attention.py's chunk kernel), `O_i^T += V_i^T P`. A grid step
holds `HEADS_A_STEP` heads' weights and queries and walks the row's live
pages once for them; no context is ever held expanded in HBM. Where every
query attends a set of its own (a learned index, ops/sparse_index.py) the
caller hands the sets over as a `bias` [keys, queries] in HBM, a block of
which arrives beside each block of pages and is added to the scores: every
pair is still computed.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from substratus_tpu.ops.paged_attention import (
    FOLD_QUERIES, LANES, NEG_INF, _block_copies, _div, _round_up, fold_pages,
)

# Tokens a decode step folds at once (static sizes: the smallest that holds
# what a block brought, and never less than one page). The last is the DMA
# block: twice ops/paged_attention.py's 512, because a longer block has
# fewer ends (0.658 -> 0.590 ms a layer over 129k rows; one wait a full
# block, `_wait_block`, 0.607 -> 0.557; my chip runs, PR 40).
DECODE_FOLD_TOKENS = (32, 128, 512, 1024)
# Keys of one DMA block of the chunk kernel, as ops/paged_attention.py's.
CHUNK_KEYS = 512

# Heads whose weights, queries and running softmax one grid step of the
# chunk kernel holds (the row's live latents cross HBM once a step), and
# the query columns a step keeps.
HEADS_A_STEP = 8
CHUNK_COLUMNS = 512


def _as_pages(pool):
    """[L, P, bs, 1, w] -> [L, P, bs, w]: the same bytes."""
    return pool.reshape(pool.shape[:3] + pool.shape[4:])


def _wait_block(each_copy, buf, sem, b, j, slot, full):
    """Wait for block j of row b in `slot`. A DMA semaphore counts bytes
    and a wait takes a descriptor for its size alone, so a full block's
    copies, which signal one semaphore, are awaited as one copy of the
    buffer's size; a row's last block, with fewer pages, a page at a time."""

    @pl.when(full)
    def _():
        pltpu.make_async_copy(
            buf.at[slot], buf.at[slot], sem.at[0, slot]).wait()

    @pl.when(jnp.logical_not(full))
    def _():
        each_copy(b, j, slot, lambda c: c.wait())


def _decode_kernel(layer_ref, pos_ref, bt_ref, q_ref, lat_hbm, o_ref, buf,
                   sem, m_ref, l_ref, acc_ref, *, scale: float, folds):
    n_rows, n_heads, _ = q_ref.shape
    bs, w = lat_hbm.shape[2:]
    rkv = o_ref.shape[2]
    ppb = buf.shape[1]
    max_pages = bt_ref.shape[1]
    layer = layer_ref[0]

    def pages_of(b):
        return jnp.minimum(_div(pos_ref[b], bs) + 1, max_pages)

    each_copy = _block_copies(layer, bt_ref, (lat_hbm,), (buf,), sem, pages_of)

    def fold(b, j, slot, pages: int, pos):
        """Fold the first `pages` pages of block j of row b, arrived in
        `slot`, into m, l, acc."""
        first = j * (ppb * bs)  # position of the block's first token
        cols = pages * bs

        @pl.when(first + cols > pos + 1)
        def _():
            # What lies past the row's own position is not the row's: zero
            # it, since a probability of 0 times a NaN is a NaN.
            shape = (pages, bs, w)
            tok = (first + lax.broadcasted_iota(jnp.int32, shape, 0) * bs
                   + lax.broadcasted_iota(jnp.int32, shape, 1))
            buf[slot, :pages] = jnp.where(
                tok <= pos, buf[slot, :pages], 0).astype(buf.dtype)

        lat = buf.at[slot, :pages].reshape(cols, w)[...]
        s = lax.dot_general(
            q_ref[b], lat, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [H, cols]
        col = lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(first + col <= pos, s, NEG_INF)
        m_prev = m_ref[...]  # [H, LANES], every lane the same
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new[:, :1])  # 0 where masked
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        m_ref[...] = m_new
        acc_ref[...] = acc_ref[...] * alpha[:, :1] + jnp.dot(
            p.astype(lat.dtype), lat[:, :rkv],
            preferred_element_type=jnp.float32)

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

            held = pages_of(b) - j * ppb  # may pass ppb
            _wait_block(each_copy, buf, sem, b, j, slot, held >= ppb)
            fewer = 0
            for pages in folds:
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


@functools.partial(jax.jit, static_argnames=("rkv", "scale", "interpret"))
def latent_decode_attention(
    qa: jnp.ndarray,  # [B, H, w]: a row's query in the latent's space
    lat_pool: jnp.ndarray,  # [L, P, bs, 1, w]
    layer: jnp.ndarray,  # scalar int32: the layer of the stack to read
    block_table: jnp.ndarray,  # [B, M] int32 page ids
    positions: jnp.ndarray,  # [B] the query's position = the last to see
    *,
    rkv: int,  # the latent's width: the row's leading values are V
    scale: float,  # of the scores
    interpret: bool = False,
) -> jnp.ndarray:
    """softmax(qa . Lat * scale) Lat[:, :rkv] over positions 0..positions[b]
    of row b, read through its block table out of `layer` of the pool;
    [B, H, rkv] in qa.dtype. Row b reads positions[b] // bs + 1 pages,
    whatever the table or the other rows hold."""
    n_rows, n_heads, w = qa.shape
    lat = _as_pages(lat_pool)
    bs = lat.shape[2]
    assert lat.shape[3] == w and w % LANES == 0 and rkv % LANES == 0, (
        lat.shape, w, rkv)
    folds = fold_pages(bs, DECODE_FOLD_TOKENS)  # the last: a DMA block
    block = (2, folds[-1], bs, w)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    keys = folds[-1] * bs
    need = (
        n_rows * n_heads * (w + rkv) * qa.dtype.itemsize  # q and out
        + 2 * keys * w * lat.dtype.itemsize  # the DMA blocks
        + n_heads * (rkv + 2 * LANES) * 4  # acc, m, l
        + 4 * n_heads * keys * 4  # a fold's scores, as values
    )
    return pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, folds=folds),
        out_shape=jax.ShapeDtypeStruct((n_rows, n_heads, rkv), qa.dtype),
        in_specs=[smem, smem, smem, vmem, hbm],
        out_specs=vmem,
        scratch_shapes=[
            pltpu.VMEM(block, lat.dtype),
            pltpu.SemaphoreType.DMA((1, 2)),
            pltpu.VMEM((n_heads, LANES), jnp.float32),
            pltpu.VMEM((n_heads, LANES), jnp.float32),
            pltpu.VMEM((n_heads, rkv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=need + (8 << 20)),
        interpret=interpret,
        name="latent_decode_attention",
    )(
        layer.astype(jnp.int32).reshape(1), positions.astype(jnp.int32),
        block_table.astype(jnp.int32), qa, lat,
    )


# --- more than one query token a row: a prefill chunk ------------------------


def _chunk_kernel(layer_ref, last_ref, first_ref, bt_ref, qpos_ref, q_ref,
                  w_ref, lat_hbm, *rest, scale: float, dn: int,
                  biased: bool = False):
    """One grid step: `heads` heads of row b, query columns i, against the
    row's pages 0 .. last[b] // page_size, each arrived block expanded
    through the heads' W_UKV where it lies. `biased`: a [keys, queries]
    array in HBM is added to the scores, a block of it brought beside each
    block of pages (0 where the query attends the key, NEG_INF where not:
    each query's own set, ops/sparse_index.py::select)."""
    if biased:
        (bias_hbm, o_ref, buf, sem, slot_ref, m_ref, l_ref, acc_ref,
         bias_buf, bias_sem) = rest
    else:
        o_ref, buf, sem, slot_ref, m_ref, l_ref, acc_ref = rest
    b, g, i = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    n_rows, n_groups, n_tiles = (pl.num_programs(0), pl.num_programs(1),
                                 pl.num_programs(2))
    heads, _, width = q_ref.shape[1:]
    rkv = w_ref.shape[2]
    ppb, bs, w = buf.shape[1:]
    keys = ppb * bs  # of one block
    fold = math.gcd(width, FOLD_QUERIES)
    layer = layer_ref[0]

    def pages_of(row):
        return _div(last_ref[row], bs) + 1

    each_copy = _block_copies(layer, bt_ref, (lat_hbm,), (buf,), sem, pages_of)

    def bias_copy(j, slot):
        return pltpu.make_async_copy(
            bias_hbm.at[b, pl.ds(j * keys, keys), pl.ds(i * width, width)],
            bias_buf.at[slot], bias_sem.at[slot])

    def fold_block(j, slot, masked: bool):
        first_key = j * keys
        lat = buf.at[slot].reshape(keys, w)
        ckv, kr = lat[:, :rkv], lat[:, rkv:]  # [keys, rkv], [keys, w - rkv]

        def head(h, _):
            # the block's keys and values of this head, from its latents
            kn = lax.dot_general(
                ckv, w_ref[h, :dn, :], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32).astype(ckv.dtype)
            vt = lax.dot_general(
                w_ref[h, dn:, :], ckv, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32).astype(ckv.dtype)
            for c in range(0, width, fold):
                cols = pl.ds(c, fold)
                s = (jnp.dot(kn, q_ref[0, h, :dn, cols],
                             preferred_element_type=jnp.float32)
                     + jnp.dot(kr, q_ref[0, h, dn:, cols],
                               preferred_element_type=jnp.float32)) * scale
                if masked:
                    k_pos = first_key + lax.broadcasted_iota(
                        jnp.int32, s.shape, 0)
                    s = jnp.where(k_pos <= qpos_ref[0, :, cols], s, NEG_INF)
                if biased:
                    s = s + bias_buf[slot, :, cols].astype(jnp.float32)
                # what the scratch holds before a row's first block is
                # nobody's: selected away, never initialised
                m_prev = jnp.where(j == 0, NEG_INF, m_ref[h, :, cols])
                l_prev = jnp.where(j == 0, 0.0, l_ref[h, :, cols])
                acc = jnp.where(j == 0, 0.0, acc_ref[h, :, cols])
                m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
                p = jnp.exp(s - m_new)  # [keys, fold], 0 where masked
                if biased:
                    # a block in which a query attends nothing leaves its
                    # maximum at NEG_INF: its keys weigh 0, not exp(0)
                    p = jnp.where(s > 0.5 * NEG_INF, p, 0.0)
                alpha = jnp.exp(m_prev - m_new)
                m_ref[h, :, cols] = m_new
                l_ref[h, :, cols] = alpha * l_prev + jnp.sum(
                    p, axis=0, keepdims=True)
                acc_ref[h, :, cols] = acc * alpha + jnp.dot(
                    vt, p.astype(vt.dtype), preferred_element_type=jnp.float32)

        lax.fori_loop(0, heads, head, None)

    @pl.when((b == 0) & (g == 0) & (i == 0))
    def _():
        slot_ref[0] = 0
        each_copy(0, 0, 0, lambda c: c.start())

    last, n_pages = last_ref[b], pages_of(b)
    n_blocks = pl.cdiv(n_pages, ppb)
    row_ends = (g + 1 == n_groups) & (i + 1 == n_tiles)
    if biased:  # this step's first block of the bias: no step before knew it
        bias_copy(0, slot_ref[0]).start()

    def block(j, slot):
        more = j + 1 < n_blocks

        @pl.when(more)
        def _():
            each_copy(b, j + 1, 1 - slot, lambda c: c.start())
            if biased:
                bias_copy(j + 1, 1 - slot).start()

        # the next grid step's first block: this row again, or the next
        @pl.when(jnp.logical_not(more) & jnp.logical_not(row_ends))
        def _():
            each_copy(b, 0, 1 - slot, lambda c: c.start())

        @pl.when(jnp.logical_not(more) & row_ends & (b + 1 < n_rows))
        def _():
            each_copy(b + 1, 0, 1 - slot, lambda c: c.start())

        each_copy(b, j, slot, lambda c: c.wait())
        if biased:
            bias_copy(j, slot).wait()
        first_key = j * keys

        @pl.when(first_key + keys > last + 1)
        def _():
            # Past the row's last position nothing is the row's: zero it,
            # since a probability of 0 times a NaN is a NaN.
            shape = buf.shape[1:]
            tok = (first_key + lax.broadcasted_iota(jnp.int32, shape, 0) * bs
                   + lax.broadcasted_iota(jnp.int32, shape, 1))
            buf[slot] = jnp.where(tok <= last, buf[slot], 0).astype(buf.dtype)

        # a block that ends at or before the row's first query position is
        # seen whole by every query: no mask
        hidden = first_key + keys - 1 > first_ref[b]

        @pl.when(hidden)
        def _():
            fold_block(j, slot, True)

        @pl.when(jnp.logical_not(hidden))
        def _():
            fold_block(j, slot, False)

        return 1 - slot

    slot_ref[0] = lax.fori_loop(0, n_blocks, block, slot_ref[0])
    for h in range(heads):
        o_ref[0, h] = (acc_ref[h] / l_ref[h]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("dn", "scale", "interpret"))
def latent_chunk_attention(
    q: jnp.ndarray,  # [B, S, H, dn + dr]: S query tokens a row
    w_ukv: jnp.ndarray,  # [H, dn + dv, rkv]: W_UK_i over W_UV_i^T, a head
    lat_pool: jnp.ndarray,  # [L, P, bs, 1, w], bfloat16
    layer: jnp.ndarray,  # scalar int32: the layer of the stack to read
    block_table: jnp.ndarray,  # [B, M] int32 page ids
    positions: jnp.ndarray,  # [B, S]: query i sees positions 0..positions[b, i]
    bias: jnp.ndarray = None,  # [B, T, S], T = the table's reach: added to
    # the scores keys-major (0: the query attends the key, NEG_INF: not)
    *,
    dn: int,  # of a query's values, those that meet k_nope
    scale: float,  # of the scores
    interpret: bool = False,
) -> jnp.ndarray:
    """The expanded form over a pool of latents: for every query of every
    row, softmax((q_nope . k_nope_j + q_rope . kr_j) * scale) v_j over
    positions j = 0..its own of its row, with k_nope_j, v_j = the row's
    latent j through `w_ukv`, made a block of pages at a time inside the
    kernel; [B, S, H, dv] in q.dtype. Row b reads max(positions[b]) // bs
    + 1 pages, whatever the table holds. The positions are read, not
    assumed, as by ops/paged_attention.py::paged_chunk_attention. With
    `bias` a query attends only the keys its column of the bias leaves
    (every query at least one), beside what its position lets it see."""
    b, s, h, dq = q.shape
    lat = _as_pages(lat_pool)
    bs, w = lat.shape[2:]
    rkv = w_ukv.shape[2]
    dv, dr = w_ukv.shape[1] - dn, dq - dn
    assert lat.dtype == jnp.bfloat16 and w % LANES == 0 and rkv % LANES == 0
    assert dr <= w - rkv and dn % 16 == 0 and dv % 16 == 0, (dn, dr, dv, w)
    heads = next(n for n in range(min(HEADS_A_STEP, h), 0, -1) if h % n == 0)
    # Transposed, a query a lane; its rotary part padded to the width of
    # the row's tail (zeros meet the zeros the pool keeps behind kr).
    width = min(_round_up(s, LANES), CHUNK_COLUMNS)
    padded = _round_up(s, width)
    qt = jnp.pad(q, ((0, 0), (0, padded - s), (0, 0), (0, w - rkv - dr)))
    qt = qt.transpose(0, 2, 3, 1)  # [B, H, dn + (w - rkv), padded]
    # a query past the table's reach sees the whole table, as the gather's
    reach = block_table.shape[1] * bs - 1
    positions = jnp.minimum(positions.astype(jnp.int32), reach)
    # padded columns repeat the last query: their output is dropped
    qpos = jnp.pad(positions, ((0, 0), (0, padded - s)), mode="edge")
    qpos = qpos[:, None]  # [B, 1, padded]
    ppb = max(CHUNK_KEYS // bs, 1)
    keys = ppb * bs
    rows = dn + w - rkv
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    operands, scratch = (), ()
    if bias is not None:
        # whole blocks of keys, the queries' padded columns the last one's
        t = block_table.shape[1] * bs
        assert bias.shape == (b, t, s), (bias.shape, (b, t, s))
        bias = jnp.pad(bias.astype(q.dtype),
                       ((0, 0), (0, _round_up(t, keys) - t), (0, 0)),
                       constant_values=NEG_INF)
        bias = jnp.pad(bias, ((0, 0), (0, 0), (0, padded - s)), mode="edge")
        operands = (bias,)
        scratch = (pltpu.VMEM((2, keys, width), bias.dtype),
                   pltpu.SemaphoreType.DMA((2,)))
    vmem = (
        2 * heads * (rows + dv) * width * q.dtype.itemsize  # q and out, x 2
        + 2 * heads * (dn + dv) * rkv * w_ukv.dtype.itemsize  # the weights
        + heads * (dv + 16) * width * 4  # acc, m, l
        + 2 * keys * w * 2  # the DMA blocks
        + 2 * keys * (dn + dv) * 4  # a head's keys and values
        + 3 * keys * FOLD_QUERIES * 4  # a fold's scores, as values
        + (2 * keys * width * 2 if operands else 0)  # the bias's blocks
    )
    out = pl.pallas_call(
        functools.partial(_chunk_kernel, scale=scale, dn=dn,
                          biased=bool(operands)),
        out_shape=jax.ShapeDtypeStruct((b, h, dv, padded), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(b, h // heads, padded // width),
            in_specs=[
                pl.BlockSpec((1, 1, width), lambda b, g, i, *_: (b, 0, i)),
                pl.BlockSpec((1, heads, rows, width),
                             lambda b, g, i, *_: (b, g, 0, i)),
                pl.BlockSpec((heads, dn + dv, rkv),
                             lambda b, g, i, *_: (g, 0, 0)),
                hbm,
            ] + [hbm] * len(operands),
            out_specs=pl.BlockSpec((1, heads, dv, width),
                                   lambda b, g, i, *_: (b, g, 0, i)),
            scratch_shapes=[
                pltpu.VMEM((2, ppb, bs, w), lat.dtype),
                pltpu.SemaphoreType.DMA((1, 2)),
                pltpu.SMEM((1,), jnp.int32),
                pltpu.VMEM((heads, 1, width), jnp.float32),
                pltpu.VMEM((heads, 1, width), jnp.float32),
                pltpu.VMEM((heads, dv, width), jnp.float32),
                *scratch,
            ],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem + (8 << 20),
        ),
        interpret=interpret,
        name="latent_chunk_attention",
    )(
        layer.astype(jnp.int32).reshape(1), positions.max(axis=1),
        positions.min(axis=1), block_table.astype(jnp.int32), qpos, qt,
        w_ukv.astype(q.dtype), lat, *operands,
    )
    return out[..., :s].transpose(0, 3, 1, 2)  # [B, S, H, dv]
