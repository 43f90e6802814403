"""A learned index over what a latent layer has kept: which rows a query
attends (DeepSeek Sparse Attention; models/deepseek_v3.py gives the
equations).

Beside its latent row `[ckv; kr]` a token keeps one index key `ki` [di] a
layer, in the latent pool's second array (ops/kvcache.py::
init_latent_cache). A query t brings `qi` [Hi, di] and a weight a head
`wi` [Hi] (float32), and

    I_t,s = sum_j wi_t,j ReLU(qi_t,j . ki_s)        float32, s <= t
    S_t   = the min(k, t + 1) positions s <= t of largest I_t,s,
            ties toward the lower position

is the set its attention runs over. Four pieces, each with a plain XLA
form that is also what the kernels are tested against:

* `scores` (XLA) / `index_decode_scores` (Pallas): `I` for one query a row
  over the row's live keys. The kernel reads pages block_table[b, 0 ..
  positions[b] // page_size] of `layer` of the key pool in place, by its
  own DMAs (ops/paged_attention.py::_block_copies): 2 di bytes a live
  token, nothing of the table's width.
* `scores` / `index_chunk_scores` (Pallas): `I` for a chunk's S queries
  against a context handed over gathered (di values a token: a hundredth
  of a chunk's work), written keys-major, [T, S], a query a lane, as
  ops/latent_attention.py's chunk kernel folds its scores.
* `select` : the set as a mask, by bisection on the scores' bit patterns
  (32 counts for the k-th largest value, then as many as the positions
  have bits for the ties that stay, only where a tie straddles the set's
  edge): exact, no sort, any layout.
* `select_rows` (XLA) / `index_select_rows` (Pallas): a decode step's set
  as the pool's rows, which its attention gathers: `select`'s mask, then
  `compact`, the mask's positions in ascending order through the block
  table, by counts, compares and one-hot products (attention over a set
  reads no order, so nothing is sorted). The kernel holds the rows' scores
  in VMEM through every count of the bisection and the compaction.
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
    LANES, _block_copies, _div, _round_up,
)

# Tokens whose keys one block of the decode kernel's DMAs brings (pages x
# page_size), and the keys one grid step of the chunk kernel scores.
DECODE_KEYS = 1024
CHUNK_KEYS = 512


def scores(qi: jnp.ndarray, ki: jnp.ndarray, wi: jnp.ndarray) -> jnp.ndarray:
    """I [B, S, T] float32 for queries qi [B, S, Hi, di] with weights wi
    [B, S, Hi] against keys ki [B, T, di]: every pair, masked by nobody."""
    s = jnp.einsum("bsjd,btd->bsjt", qi, ki,
                   preferred_element_type=jnp.float32)
    return jnp.einsum("bsjt,bsj->bst", jax.nn.relu(s),
                      wi.astype(jnp.float32))


def _ordered(x: jnp.ndarray) -> jnp.ndarray:
    """float32 -> uint32 whose order is the floats' (-inf lowest, above 0)."""
    bits = lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    return jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))


def select(index: jnp.ndarray, seen: jnp.ndarray, k: int, axis: int
           ) -> jnp.ndarray:
    """The set as a mask shaped like `index`: along `axis` (the keys), the
    min(k, count(seen)) entries of `seen` with the largest `index`, of
    equal ones those at the lower position first. Exact: the k-th largest
    value is built bit by bit from counts (no sort), then, only if some
    query has more equals at that value than its set has room for, the
    position of the last one that fits, bit by bit again."""
    n = index.shape[axis]
    seen = jnp.broadcast_to(seen, index.shape)
    count = functools.partial(jnp.sum, axis=axis, keepdims=True,
                              dtype=jnp.int32)
    u = jnp.where(seen, _ordered(index), jnp.uint32(0))  # unseen: below all
    need = jnp.minimum(count(seen), k)

    def value_bit(i, tau):
        cand = tau | (jnp.uint32(1) << jnp.asarray(31 - i, jnp.uint32))
        return jnp.where(count(u >= cand) >= need, cand, tau)

    tau = lax.fori_loop(0, 32, value_bit, jnp.zeros(need.shape, jnp.uint32))

    def with_ties(_):
        above, equal = u > tau, u == tau
        room = need - count(above)  # of the entries equal to tau, those kept
        shape = [1] * index.ndim
        shape[axis] = n
        pos = jnp.arange(n, dtype=jnp.int32).reshape(shape)
        bits = max(n - 1, 1).bit_length()

        def position_bit(i, p):
            cand = p | (jnp.int32(1) << (bits - 1 - i))
            return jnp.where(count(equal & (pos < cand)) < room, cand, p)

        # the largest p with fewer than `room` equals below it: the
        # position of the last equal entry that fits
        p = lax.fori_loop(0, bits, position_bit,
                          jnp.zeros(need.shape, jnp.int32))
        return above | (equal & (pos <= p) & (room > 0))

    tight = count(u >= tau) == need  # every equal entry fits
    return lax.cond(jnp.all(tight), lambda _: (u >= tau) & (need > 0),
                    with_ties, None)


def compact(mask: jnp.ndarray, block_table: jnp.ndarray, keep: int
            ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """A set as the pool's rows: for mask [B, M * bs] over the positions of
    block_table [B, M]'s pages, (row [B, keep] int32, ok [B, keep]): the
    rows `block_table[b, s // bs] * bs + s % bs` of the mask's first `keep`
    positions s in ascending order, and which places hold one (the others'
    rows mean nothing). No sort, no scatter and no lookup through the
    table: a position's place is the count of the mask before it, so place
    j finds its page by comparing j with the pages' counts (one page of M
    holds it: what a product with that one-hot row brings is the page's
    own), and its lane by comparing its rank in the page with the page's
    running count. Every number in a product is a whole one that its type
    holds exactly."""
    b, m = block_table.shape
    bs = mask.shape[1] // m
    f32 = jnp.float32
    # a page's running count is at most bs
    small, exact = ((jnp.bfloat16, None) if bs <= 256
                    else (f32, lax.Precision.HIGHEST))
    at = jnp.arange(bs)
    within = jnp.einsum(  # [B, M, bs]: the mask's count up to each lane
        "bml,lj->bmj", mask.reshape(b, m, bs).astype(small),
        (at[:, None] <= at[None, :]).astype(small),
        preferred_element_type=f32, precision=exact)
    total = within[..., -1]
    page = jnp.arange(m)
    end = jnp.einsum(  # [B, M]: the places before a page's end
        "bm,mn->bn", total, (page[:, None] <= page[None, :]).astype(f32),
        precision=lax.Precision.HIGHEST)
    start = end - total
    j = jnp.arange(keep, dtype=f32)[None, :, None]
    hot = (start[:, None] <= j) & (j < end[:, None])  # [B, keep, M]
    counts = jnp.einsum(  # [B, keep, bs]: the running count of j's page
        "bjm,bml->bjl", hot.astype(small), within.astype(small),
        preferred_element_type=f32, precision=exact)
    first = jnp.sum(jnp.where(hot, start[:, None], 0), axis=-1)
    pid = jnp.sum(jnp.where(hot, block_table[:, None], 0), axis=-1)
    lane = jnp.sum(counts <= (j[..., 0] - first)[..., None], axis=-1,
                   dtype=jnp.int32)
    return pid * bs + lane, j[..., 0] < end[:, -1:]


def select_rows(index: jnp.ndarray, positions: jnp.ndarray,
                block_table: jnp.ndarray, k: int
                ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """A decode step's sets as the pool's rows: for I [B, M * bs] of one
    query a row at positions [B], `compact` of `select` over positions 0
    .. positions[b]: (row, ok) [B, min(k, M * bs)], ok in the first min(k,
    positions[b] + 1) places. A score of -0.0 counts as 0.0, as it does to
    a sort (`select` orders bit patterns, which tell them apart)."""
    t = index.shape[1]
    seen = jnp.arange(t, dtype=jnp.int32)[None] <= positions[:, None]
    index = jnp.where(index == 0, 0.0, index)
    return compact(select(index, seen, k, axis=1), block_table, min(k, t))


# --- a decode step's sets, the scores held in VMEM ---------------------------


def _select_rows_kernel(pos_ref, sc_ref, bt_ref, o_ref, key_ref, *, k):
    """`select_rows` for the grid step's rows: `select`'s bisection (the
    float32 order as int32's, every count one pass over a row's scores
    where they lie, the rows' passes interleaved), then `compact` with the
    places on the lanes."""
    g, m, bs = sc_ref.shape
    keep = o_ref.shape[2]
    row0 = pl.program_id(0) * g
    low = jnp.int32(-(1 << 31))
    at = (lax.broadcasted_iota(jnp.int32, (m, bs), 0) * bs
          + lax.broadcasted_iota(jnp.int32, (m, bs), 1))

    def count(which):
        return jnp.sum(which.astype(jnp.int32))

    need = []
    for b in range(g):
        pos = pos_ref[row0 + b]
        x = sc_ref[b]
        bits = pltpu.bitcast(jnp.where(x == 0.0, 0.0, x), jnp.int32)
        key = jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)
        key_ref[b] = jnp.where(at <= pos, key, low)  # unseen: below all
        need.append(jnp.minimum(pos + 1, k))

    def value_bit(i, taus):  # tau as `select` has it, a uint32's bits
        bit = jnp.int32(1) << (31 - i)
        out = []
        for b in range(g):
            cand = taus[b] | bit
            enough = count(key_ref[b] >= (cand ^ low)) >= need[b]
            out.append(jnp.where(enough, cand, taus[b]))
        return tuple(out)

    taus = lax.fori_loop(0, 32, value_bit, (jnp.int32(0),) * g)
    n_bits = max(m * bs - 1, 1).bit_length()
    upto = (lax.broadcasted_iota(jnp.int32, (bs, bs), 1)
            <= lax.broadcasted_iota(jnp.int32, (bs, bs), 0)
            ).astype(jnp.bfloat16)  # [lane, lane']: lane' <= lane
    before = (lax.broadcasted_iota(jnp.int32, (m, m), 1)
              < lax.broadcasted_iota(jnp.int32, (m, m), 0)
              ).astype(jnp.bfloat16)  # [page, page']: page' < page
    j = lax.broadcasted_iota(jnp.int32, (1, keep), 1).astype(jnp.float32)
    for b in range(g):
        key = key_ref[b]
        tau = taus[b] ^ low
        above, equal = key > tau, key == tau
        room = need[b] - count(above)

        def last_that_fits(equal=equal, room=room):
            def position_bit(i, p):
                cand = p | (jnp.int32(1) << (n_bits - 1 - i))
                return jnp.where(count(equal & (at < cand)) < room, cand, p)

            return lax.fori_loop(0, n_bits, position_bit, jnp.int32(0))

        p = lax.cond(count(equal) > room, last_that_fits,
                     lambda: jnp.int32(m * bs))
        chosen = (above | (equal & (at <= p))).astype(jnp.float32)
        packed = chosen.astype(jnp.bfloat16)
        within = lax.dot_general(  # [bs, m]: a page's count up to a lane
            upto, packed, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        total = jnp.sum(chosen, axis=1, keepdims=True)  # [m, 1]
        start = jnp.dot(
            before, jnp.broadcast_to(total, (m, bs)).astype(jnp.bfloat16),
            preferred_element_type=jnp.float32)[:, :1]
        hot = (start <= j) & (j < start + total)  # [m, keep]
        counts = jnp.dot(within.astype(jnp.bfloat16),
                         hot.astype(jnp.bfloat16),
                         preferred_element_type=jnp.float32)  # [bs, keep]
        first = jnp.sum(jnp.where(hot, start, 0.0), axis=0, keepdims=True)
        pid = jnp.sum(jnp.where(hot, bt_ref[b], 0), axis=0, keepdims=True)
        lane = jnp.sum((counts <= j - first).astype(jnp.int32), axis=0,
                       keepdims=True)
        o_ref[b] = pid * bs + lane


def select_rows_kernel_takes(block_table: jnp.ndarray, bs: int, k: int
                             ) -> bool:
    """Whether `index_select_rows` compiles for these shapes: a page a
    lane tile, the table's pages whole sublane tiles, the places whole
    lane tiles."""
    m = block_table.shape[1]
    return bs == LANES and m % 8 == 0 and min(k, m * bs) % LANES == 0


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def index_select_rows(
    index: jnp.ndarray,  # [B, M * bs] float32: I, -inf past a row's position
    positions: jnp.ndarray,  # [B]
    block_table: jnp.ndarray,  # [B, M] int32 page ids
    k: int,
    *,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """`select_rows` in one kernel: a row's scores stay in VMEM through
    the threshold's 32 (+ up to 15) counts and the compaction."""
    b, m = block_table.shape
    bs = index.shape[1] // m
    keep = min(k, m * bs)
    g = math.gcd(b, 8)  # rows a grid step, their passes interleaved
    row = pl.pallas_call(
        functools.partial(_select_rows_kernel, k=k),
        out_shape=jax.ShapeDtypeStruct((b, 1, keep), jnp.int32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b // g,),
            in_specs=[
                pl.BlockSpec((g, m, bs), lambda i, pos: (i, 0, 0)),
                pl.BlockSpec((g, m, 1), lambda i, pos: (i, 0, 0)),
            ],
            out_specs=pl.BlockSpec((g, 1, keep), lambda i, pos: (i, 0, 0)),
            scratch_shapes=[pltpu.VMEM((g, m, bs), jnp.int32)],
        ),
        interpret=interpret,
        name="index_select_rows",
    )(positions.astype(jnp.int32),
      index.astype(jnp.float32).reshape(b, m, bs),
      block_table.astype(jnp.int32)[:, :, None])
    ok = (jnp.arange(keep, dtype=jnp.int32)[None]
          < jnp.minimum(positions.astype(jnp.int32) + 1, k)[:, None])
    return row[:, 0], ok


# --- one query a row, the keys read in place ---------------------------------


def _decode_scores_kernel(layer_ref, pos_ref, bt_ref, q_ref, w_ref, k_hbm,
                          o_ref, buf, sem):
    n_rows = q_ref.shape[0]
    bs = k_hbm.shape[2]
    ppb = buf.shape[1]
    cols = ppb * bs
    max_pages = bt_ref.shape[1]
    layer = layer_ref[0]

    def pages_of(b):
        return jnp.minimum(_div(pos_ref[b], bs) + 1, max_pages)

    each_copy = _block_copies(layer, bt_ref, (k_hbm,), (buf,), sem, pages_of)
    # what no block of a row reaches is nobody's
    o_ref[...] = jnp.full(o_ref.shape, -jnp.inf, jnp.float32)
    each_copy(0, 0, 0, lambda c: c.start())

    def row(b, slot):
        pos = pos_ref[b]
        n_blocks = pl.cdiv(pages_of(b), ppb)

        def block(j, slot):
            more = j + 1 < n_blocks

            @pl.when(more)
            def _():
                each_copy(b, j + 1, 1 - slot, lambda c: c.start())

            @pl.when(jnp.logical_not(more) & (b + 1 < n_rows))
            def _():
                each_copy(b + 1, 0, 1 - slot, lambda c: c.start())

            each_copy(b, j, slot, lambda c: c.wait())
            keys = buf.at[slot].reshape(cols, buf.shape[3])[...]
            s = lax.dot_general(
                q_ref[b], keys, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)  # [Hi, cols]
            index = jnp.sum(jnp.maximum(s, 0.0) * w_ref[b], axis=0,
                            keepdims=True)  # [1, cols]
            # what the buffer holds past the row's own position (pages the
            # block did not bring, a page's tail) is selected away: a NaN
            # there never reaches the result
            at = j * cols + lax.broadcasted_iota(jnp.int32, index.shape, 1)
            o_ref[b, j] = jnp.where(at <= pos, index, -jnp.inf)
            return 1 - slot

        return lax.fori_loop(0, n_blocks, block, slot)

    lax.fori_loop(0, n_rows, row, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def index_decode_scores(
    qi: jnp.ndarray,  # [B, Hi, di]: a row's index queries, rotated
    wi: jnp.ndarray,  # [B, Hi] float32: a head's weight
    key_pool: jnp.ndarray,  # [L, P, bs, 1, di]
    layer: jnp.ndarray,  # scalar int32: the layer of the stack to read
    block_table: jnp.ndarray,  # [B, M] int32 page ids
    positions: jnp.ndarray,  # [B] the query's position = the last to score
    *,
    interpret: bool = False,
) -> jnp.ndarray:
    """I [B, M * bs] float32 of row b's query over positions 0 ..
    positions[b], read through its block table out of `layer` of the key
    pool; -inf beyond. Row b reads positions[b] // bs + 1 pages, whatever
    the table or the other rows hold."""
    n_rows, n_heads, di = qi.shape
    keys = key_pool.reshape(key_pool.shape[:3] + key_pool.shape[4:])
    bs = keys.shape[2]
    assert keys.shape[3] == di and di % LANES == 0, (keys.shape, di)
    m = block_table.shape[1]
    ppb = max(DECODE_KEYS // bs, 1)
    n_blocks = -(-m // ppb)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    out = pl.pallas_call(
        _decode_scores_kernel,
        out_shape=jax.ShapeDtypeStruct(
            (n_rows, n_blocks, 1, ppb * bs), jnp.float32),
        in_specs=[smem, smem, smem, vmem, vmem, hbm],
        out_specs=vmem,
        scratch_shapes=[
            pltpu.VMEM((2, ppb, bs, di), keys.dtype),
            pltpu.SemaphoreType.DMA((1, 2)),
        ],
        interpret=interpret,
        name="index_decode_scores",
    )(
        layer.astype(jnp.int32).reshape(1), positions.astype(jnp.int32),
        block_table.astype(jnp.int32), qi.astype(keys.dtype),
        wi.astype(jnp.float32)[..., None], keys,
    )
    return out.reshape(n_rows, n_blocks * ppb * bs)[:, :m * bs]


# --- S queries a row against a gathered context --------------------------------


def _chunk_scores_kernel(k_ref, q_ref, w_ref, o_ref):
    keys = k_ref[0]  # [tk, di]
    o_ref[0] = jnp.zeros(o_ref.shape[1:], jnp.float32)

    def head(j, _):
        s = jnp.dot(keys, q_ref[0, j], preferred_element_type=jnp.float32)
        o_ref[0] += jnp.maximum(s, 0.0) * w_ref[0, j]  # [tk, S] x [1, S]

    lax.fori_loop(0, q_ref.shape[1], head, None)


@functools.partial(jax.jit, static_argnames=("interpret",))
def index_chunk_scores(
    qi: jnp.ndarray,  # [B, S, Hi, di]
    wi: jnp.ndarray,  # [B, S, Hi] float32
    ki: jnp.ndarray,  # [B, T, di]: the context's keys, position by position
    *,
    interpret: bool = False,
) -> jnp.ndarray:
    """I keys-major, [B, T, S] float32: every pair of the chunk's S queries
    and the T keys handed over (the caller masks what a query may not
    see). A query a lane; the heads one after another into one block of
    the result."""
    b, s, n_heads, di = qi.shape
    t = ki.shape[1]
    width = _round_up(s, LANES)
    tk = min(CHUNK_KEYS, _round_up(t, 16))
    padded = _round_up(t, tk)
    qt = jnp.pad(qi.astype(ki.dtype), ((0, 0), (0, width - s), (0, 0), (0, 0)))
    qt = qt.transpose(0, 2, 3, 1)  # [B, Hi, di, width]
    wt = jnp.pad(wi.astype(jnp.float32), ((0, 0), (0, width - s), (0, 0)))
    wt = wt.transpose(0, 2, 1)[:, :, None]  # [B, Hi, 1, width]
    keys = jnp.pad(ki, ((0, 0), (0, padded - t), (0, 0)))
    out = pl.pallas_call(
        _chunk_scores_kernel,
        out_shape=jax.ShapeDtypeStruct((b, padded, width), jnp.float32),
        grid=(b, padded // tk),
        in_specs=[
            pl.BlockSpec((1, tk, di), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, n_heads, di, width), lambda i, j: (i, 0, 0, 0)),
            pl.BlockSpec((1, n_heads, 1, width), lambda i, j: (i, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, tk, width), lambda i, j: (i, j, 0)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=(
                2 * n_heads * (di * 2 + 4) * width + 6 * tk * width * 4
                + (8 << 20)),
        ),
        interpret=interpret,
        name="index_chunk_scores",
    )(keys, qt, wt)
    return out[:, :t, :s]
