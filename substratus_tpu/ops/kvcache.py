"""Paged KV-cache device ops (vLLM/JetStream-style block layout, XLA path).

The reference served models through external images with per-request
contiguous caches (SURVEY.md §2.2); the TPU-native engine instead keeps one
global page pool, stacked over layers,

    k/v        [layers, pages, page_size, kv_heads, head_dim]
    (+ scales  [layers, pages, page_size, kv_heads, 1] when int8-quantized)

(a bfloat16 pool of heads narrower than a 128-lane row is stored `pack` =
128 // head_dim neighbouring KV heads to a row, [.., kv_heads // pack, 128]:
the same bytes in the same order; `init_paged_cache` decides, every reader
takes `pack` off its operands) and a per-sequence block table [B, max_pages] of page ids.
page_size is the pool's own: the family's where the engine is given none
(serve/paged_kv.py::page_tokens: 16, 64 or 128 for a pool that stores heads
of 64 two to a row, a latent pool's 128), and every reader
here takes it off the pool's shape. Shapes stay fully
static under jit (TPU requirement): dynamism lives in the *contents* of the
block table. Memory is bounded by actual tokens in flight, not
batch x max_seq_len, and identical prompt prefixes can share pages
(serve/paged_kv.py owns the host-side allocator / prefix registry).

The pool is one buffer from a jit's donated argument to its result: the
model's layer scan carries the whole stack (models/llama.py::forward) and
layer l addresses its rows at offset l * pages * page_size of the flat token
axis [layers * pages * page_size]. New entries are scattered there in place,
one token row each (`kv.write`). No op slices a layer out of the stack or
writes one back, so a step needs no second pool.

`paged_attention` is the one entry point of both model families: it writes
the new rows and attends from q to each row's context, and reads off its
inputs which of two realisations of that attention runs (no flag, option or
model name decides):

* **A bfloat16 pool with rows a multiple of 128 wide (heads of 128, or
  heads of 64 stored two to a row), lowered for a TPU, whatever the query
  length**: ops/paged_attention.py takes the stack as
  its HBM operand and reads row b's live pages block_table[b, 0 ..
  max(positions[b]) // page_size] in place. One query token a row (a decode
  step) takes `paged_decode_attention`: nothing of size max_batch x
  max_seq_len exists; a row costs what it holds, an idle row (which the
  engine keeps at position 0) one page. More than one (a prefill chunk, a
  speculative verify round, the draft's chunks) takes
  `paged_chunk_attention`: the work follows the live context, not the
  table, and no score is written to HBM. Under a mesh that shards the pool
  over kv_heads and nothing else, each device runs the kernel on its own
  heads (`shard_map`, no collective).
* **Everything else** (an int8 pool; a float32 pool; heads that could not
  be packed into rows of 128, which Mosaic does not tile: an odd number of
  64-wide heads, a width of 96; `kv_length` given; a pool sharded any other
  way, or a count of rows a token and device that Mosaic does not tile
  (one, three, six); every platform but the TPU): each sequence's context
  is gathered
  one table entry at a time, as a slice of page_size contiguous rows
  (`kv.gather`; row by row out of HBM the same gather measured 1.8x slower,
  and a [pages, page_size, ...] view of the pool made the compiler re-tile
  a kv_heads shard of it in every layer: PERF.md section 6, PR 25), into a
  slot-local [B, max_pages * page_size] view, so the framework's standard
  masked attention applies unchanged: gathered index j IS the token's
  absolute position in its sequence, hence causal masking (k_pos <= q_pos)
  hides unwritten / foreign pages. This is also the reference the kernels
  are tested against (tests/test_paged_attention.py, ops/kernel_cases.py).

A layer whose attention is latent (models/deepseek_v3.py) keeps one row a
token for all its heads, `[ckv; kr]`: the pool is `k` [layers, pages,
page_size, 1, w] alone, `v` a pool of no layers (the values are the keys'
leading part; `init_latent_cache` decides the stored width). Its entry
point is `latent_attention`: one query token a row reads the rows absorbed,
more expanded, by ops/latent_attention.py's kernels where `paged_attention`
would take ops/paged_attention.py's, by a gather everywhere else. A layer
that picks what a query attends by a learned index (ops/sparse_index.py)
keeps the token's index key in `v`, [layers, pages, page_size, 1, di], under
the same page ids: whatever shares, frees or reuses a page does so for both.

A layer that attends to a window of W positions keeps no pages: each decode
slot owns a ring of W rows a window layer (`ring_read_and_update`), beside
the pool in the same cache dict, so the pool holds the global layers alone.
A gated short convolution of L taps keeps less still: the L - 1 rows of its
input just before the next position, a decode slot and layer
(`conv_read_and_update`, under `CONV_STATE` of the same dict), read and
rewritten every step under the rings' contract: the caller says which slot
a batch row is and which of its tokens are real. A family may keep a slot's
rows end to end, one row of (L - 1) x D values
(`conv_rows_read_and_update`, the same contract): a chunk hands its slots'
rows to `conv_read_and_update`, a decode step over every slot shifts the
layer's slab where it lies. A layer that keeps a
recurrent state keeps a float32 matrix a decode slot and layer under the
same contract: power retention's `S` and `z` (`retention_read_and_update`),
a Mamba-2 layer's `S` beside its convolution's rows (`ssm_read_and_update`,
under `SSM_STATE`; models/granitemoehybrid.py holds pages, rows and state
in one dict).
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from substratus_tpu.ops import (
    retention, retention_kernel, scopes, sparse_index, ssd, ssd_kernel,
)
from substratus_tpu.ops.attention import dot_product_attention
from substratus_tpu.ops.latent_attention import (
    latent_chunk_attention, latent_decode_attention,
)
from substratus_tpu.ops.paged_attention import (
    LANES, NEG_INF, paged_chunk_attention, paged_decode_attention,
)
from substratus_tpu.ops.sparse_index import (
    index_chunk_scores, index_decode_scores, index_select_rows,
    select_rows_kernel_takes,
)
from substratus_tpu.ops.quant import dequantize_kv, quantize_kv
from substratus_tpu.parallel.sharding import SERVE_RULES


def _write(pool, layer, block_table, positions, k_new, v_new=None):
    """The pool with the new entries scattered in place at `positions` of
    `layer` (the caller carries and donates it; never sliced per layer).
    With no `v_new` the rows of `k` alone (a latent pool, whose values are
    its keys' leading part: `latent_attention`).

    Duplicate positions (bucket-padding clamps) write in unspecified order —
    only ever at the one-past-the-prompt garbage slot, which the first
    decode step overwrites before attending (engine contract).
    """
    n_layers, pages, bs = pool["k"].shape[:3]
    m = block_table.shape[1]
    first = layer.astype(block_table.dtype) * pages  # the layer's page 0
    out: Dict[str, jnp.ndarray] = {}
    with jax.named_scope(scopes.KV_WRITE):
        # Writes past the block table's reach (speculative verify near the
        # context window) are redirected to the trash page (physical page
        # 0) instead of silently aliasing the last page via index clamping.
        page_idx = positions // bs
        oob = page_idx >= m
        pid = jnp.take_along_axis(
            block_table, jnp.minimum(page_idx, m - 1), axis=1
        )
        pid = jnp.where(oob, 0, pid)
        idx = (first + pid) * bs + positions % bs  # [B, S] flat token index
        if "k_scale" in pool:
            kq, ks = quantize_kv(k_new)
            vq, vs = quantize_kv(v_new)
            new = {"k": kq, "v": vq, "k_scale": ks, "v_scale": vs}
        elif v_new is None:
            new = {"k": k_new}
        else:
            new = {"k": k_new, "v": v_new}
        for name, vals in new.items():
            a = pool[name]
            # the new rows as the pool stores a row (packed: [KH // pack,
            # 128], the same bytes; a relayout of the new rows alone)
            vals = vals.astype(a.dtype).reshape(vals.shape[:2] + a.shape[3:])
            out[name] = _rows(a).at[idx].set(vals).reshape(a.shape)
    return out


def _rows(a):  # [L, P, bs, ...] -> [L * P * bs, ...], a bitcast
    return a.reshape((-1,) + a.shape[3:])


def paged_read(pool, layer, block_table, dtype, head_dim):
    """`layer`'s slot-local context of every row: k_ctx, v_ctx
    [B, M * bs, KH, head_dim], bs contiguous rows a table entry. Of a
    packed pool the gathered rows are split back into their KV heads, never
    the pool ahead of the gather."""
    pages, bs = pool["k"].shape[1:3]
    b, m = block_table.shape
    first = layer.astype(block_table.dtype) * pages
    with jax.named_scope(scopes.KV_GATHER):
        starts = ((first + block_table) * bs).reshape(b * m)

        def read(a, row=None):
            ctx = jax.vmap(
                lambda start: jax.lax.dynamic_slice_in_dim(_rows(a), start, bs)
            )(starts)
            return ctx.reshape((b, m * bs) + (row or a.shape[3:]))

        if "k_scale" in pool:
            return (
                dequantize_kv(read(pool["k"]), read(pool["k_scale"]), dtype),
                dequantize_kv(read(pool["v"]), read(pool["v_scale"]), dtype),
            )
        row = (-1, head_dim)
        return read(pool["k"], row), read(pool["v"], row)


def paged_attention(
    pool: Dict[str, jnp.ndarray],  # the stacked pool, [L, P, bs, ...]
    layer: jnp.ndarray,  # scalar int32: the layer whose pages are touched
    block_table: jnp.ndarray,  # [B, M] int32 page ids
    positions: jnp.ndarray,  # [B, S] absolute (slot-local) positions
    q: jnp.ndarray,  # [B, S, H, hd]
    k_new: jnp.ndarray,  # [B, S, KH, hd]
    v_new: jnp.ndarray,
    dtype,
    kv_length: Optional[jnp.ndarray] = None,  # [B] valid cache prefix
) -> Tuple[Dict[str, jnp.ndarray], jnp.ndarray]:
    """Write the new entries at `positions` of `layer`, then attend from q
    to each row's context 0..position. Returns (updated pool, attn
    [B, S, H, hd]).

    Which of two realisations of that attention runs is read off the
    inputs. A bfloat16 pool with rows a multiple of 128 wide (heads of 128,
    or narrower heads `init_paged_cache` packed into such rows), lowered
    for a TPU: ops/paged_attention.py reads each row's live pages in place,
    a decode step (S == 1) and a chunk or a speculative round (S > 1)
    alike. Everything else (an int8 or float32 pool, heads left unpacked,
    `kv_length` given, a placement `_kernel_for` names, any other
    platform): the context of every table position is gathered and
    ops/attention.py::dot_product_attention runs over it, which is also
    what the kernels are tested against."""
    out = _write(pool, layer, block_table, positions, k_new, v_new)
    return out, paged_attend(
        out, layer, block_table, positions, q, dtype, kv_length)


def paged_attend(pool, layer, block_table, positions, q, dtype,
                 kv_length=None) -> jnp.ndarray:
    """The attention half of `paged_attention`, over the pool as it is."""

    def gathered():
        k_ctx, v_ctx = paged_read(
            pool, layer, block_table, dtype, q.shape[-1])
        with jax.named_scope(scopes.ATTN_CORE):
            return dot_product_attention(
                q, k_ctx, v_ctx, causal=True, q_positions=positions,
                kv_length=kv_length,
            )

    kernel = _kernel_for(pool["k"], q) if kv_length is None else None
    if kernel is None:
        return gathered()

    def in_place():
        with jax.named_scope(scopes.ATTN_CORE):
            return kernel(
                q, pool["k"], pool["v"], layer, block_table, positions)

    return jax.lax.platform_dependent(tpu=in_place, default=gathered)


def _one_token(q, k_pool, v_pool, layer, block_table, positions, scale,
               interpret=False):
    """paged_decode_attention behind the chunk kernel's signature."""
    return paged_decode_attention(
        q[:, 0], k_pool, v_pool, layer, block_table, positions[:, 0],
        scale=scale, interpret=interpret,
    )[:, None]


def _over_stored_rows(kernel):
    """`kernel` (of ops/paged_attention.py) for queries [B, S, H, hd] over
    a pool whose rows hold `pack` = row width // hd neighbouring KV heads.
    To the kernel a row is one KV head of 128 lanes with `pack` times the
    query heads: q goes in widened to the row, its values in the lanes of
    its own KV head and zeros in its neighbours' (a zero meets a finite
    key: the score is exact), and of each output row the head's own lanes
    are kept. The scale stays hd ** -0.5. At `pack` = 1 nothing is added."""

    def attend(q, k_pool, v_pool, layer, block_table, positions):
        h, hd = q.shape[2:]
        scale = hd ** -0.5
        pack = k_pool.shape[4] // hd
        if pack == 1:
            return kernel(
                q, k_pool, v_pool, layer, block_table, positions, scale=scale)
        group = h // (k_pool.shape[3] * pack)  # query heads a KV head
        own = np.arange(h)[:, None] // group % pack == np.arange(pack)
        own = own[:, :, None]  # [H, pack, 1]: the lanes of the head's own
        wide = jnp.where(own, q[..., None, :], 0)  # [B, S, H, pack, hd]
        out = kernel(
            wide.reshape(q.shape[:3] + (pack * hd,)), k_pool, v_pool, layer,
            block_table, positions, scale=scale)
        out = out.reshape(q.shape[:3] + (pack, hd))
        return jnp.where(own, out, 0).sum(axis=3)

    return attend


def _pool_spec() -> P:
    """The pool's PartitionSpec under the serve rules; [3] is the mesh
    axis of kv_heads."""
    return SERVE_RULES.mesh_axes(paged_cache_logical_axes()["k"])


def kv_head_shards(mesh) -> int:
    """The devices a pool's kv_heads axis is split over under `mesh`."""
    return 1 if mesh is None else mesh.shape.get(_pool_spec()[3], 1)


def _kernel_for(k_pool, q):
    """The kernel of ops/paged_attention.py that reads this pool in place
    for these queries ([B, S, H, hd]: the decode kernel for S == 1, the
    chunk kernel beyond), as the pool's placement lets it run: as it is on
    one device; a shard of KV heads a device where the pool is sharded over
    them and nothing else is sharded (each device's kernel reads its own
    heads' share of every page: no collective). None where no kernel here
    is written for the case: a pool that is not bfloat16, rows Mosaic does
    not tile (not a multiple of 128 wide: heads of 64 that
    `init_paged_cache` could not pack) or that hold no whole number of the
    queries' heads, any other placement, and a count of rows a token and
    device that does not fill the sublane tile Mosaic gives a page (of 2,
    of 4, beyond that of 8 rows: one row, three, six or twelve it refuses
    to slice; the chunk kernel besides reads rows two to a 32-bit word)."""
    row = k_pool.shape[4]
    if k_pool.dtype != jnp.bfloat16 or row % LANES or row % q.shape[-1]:
        return None
    mesh = jax.typeof(k_pool).sharding.mesh
    sharded = {name: n for name, n in mesh.shape.items() if n > 1}
    pool = _pool_spec()
    heads = pool[3]  # the mesh axis of kv_heads
    if sharded and (
        list(sharded) != [heads] or k_pool.shape[3] % sharded[heads]
    ):
        return None
    rows = k_pool.shape[3] // sharded.get(heads, 1)
    if rows % (2 if rows <= 2 else 4 if rows <= 4 else 8):
        return None
    chunk = q.shape[1] > 1
    kernel = _over_stored_rows(paged_chunk_attention if chunk else _one_token)
    if not sharded:
        return kernel
    return jax.shard_map(
        kernel, mesh=mesh,
        in_specs=(P(None, None, heads), pool, pool, P(), P(), P()),
        out_specs=P(None, None, heads), check_vma=False,
    )


def latent_attention(
    pool: Dict[str, jnp.ndarray],  # {"k": [L, P, bs, 1, w], "v": no layers}
    layer: jnp.ndarray,  # scalar int32: the layer whose pages are touched
    block_table: jnp.ndarray,  # [B, M] int32 page ids
    positions: jnp.ndarray,  # [B, S] absolute (slot-local) positions
    q: jnp.ndarray,  # [B, S, H, dn + dr]: [q_nope; q_rope, rotated]
    row_new: jnp.ndarray,  # [B, S, rkv + dr]: [ckv, normed; kr, rotated]
    w_uk,  # [H, dn, rkv]: W_UK_i a head, as stored (k_nope = ckv W_UK_i^T)
    w_uv,  # [H, dv, rkv]: W_UV_i^T a head, as stored (v = ckv W_UV_i)
    scale: float,
    dtype,
    index=None,  # (qi [B, S, Hi, di], ki [B, S, di], wi [B, S, Hi], k)
) -> Tuple[Dict[str, jnp.ndarray], jnp.ndarray]:
    """Multi-head latent attention over the paged pool: writes the new
    rows at `positions` of `layer` (one row a token for all heads; keys and
    values are the same bytes, V the row's first rkv), then attends from q
    to each row's context 0..position. Returns (updated pool, attn [B, S,
    H, dv]).

    Two forms of the same numbers, chosen by the call's own shape. One
    query token a row (a decode step) runs **absorbed**: q_nope goes
    through W_UK^T into the latent's space, the scores and the read-out
    run over the rows as they lie, and the read-out comes back through
    W_UV (ATTN_ABSORB around ATTN_CORE). More than one (a prefill chunk)
    runs **expanded**: the context's latents go through W_UKV to per-head
    keys and values, which a chunk's many queries share (ATTN_EXPAND, then
    ATTN_CORE).

    Which realisation runs is read off the inputs, as in
    `paged_attention`. A bfloat16 pool with rows a multiple of 128 wide on
    one device, lowered for a TPU: ops/latent_attention.py reads each
    row's live pages in place, and the chunk's expansion happens inside
    the kernel a block of pages at a time (what stays outside, under
    ATTN_EXPAND, is W_UKV made bfloat16). Everything else (a float32 pool,
    rows stored as declared, a mesh, any other platform): the rows of
    every table position are gathered (KV_GATHER) and the form runs in
    plain XLA over them, the chunk's context expanded whole; that is also
    what the kernels are tested against.

    With `index` (ops/sparse_index.py: the token's index queries, its
    index key, a weight a head and the set's size k) the new keys are
    written to `v` beside the rows, and every query attends its own set
    S_t alone: the min(k, t + 1) positions of largest index score, ties
    toward the lower. A decode step scores the row's live keys (ATTN_INDEX:
    where the kernels run, in place through the block table), takes the
    set as the chunk does, by a threshold, and its positions' rows of the
    pool by a compaction, in ascending position (ATTN_SELECT,
    `sparse_index.select_rows`; where the kernels run and a page is a lane
    tile, in one kernel over the scores in VMEM), and reads those rows and
    no others, by position (ATTN_CORE: a gather of k rows a slot, then the
    absorbed form over them in XLA). A chunk scores its queries against
    the gathered keys of the table (ATTN_INDEX), takes each query's set as
    a mask (ATTN_SELECT, `sparse_index.select`) and runs the expanded form
    under it: every pair is still computed."""
    from substratus_tpu.ops.quant import QTensor, materialize, qeinsum

    k_pool = pool["k"]
    w = k_pool.shape[4]
    b, s, h, dq = q.shape
    rkv = w_uk.shape[2]
    dr = row_new.shape[-1] - rkv
    dn = dq - dr
    qi, ki, wi, topk = index if index is not None else (None,) * 4
    # the row as the pool stores it: zeros behind [ckv; kr]
    stored = jnp.pad(row_new, ((0, 0), (0, 0), (0, w - rkv - dr)))
    out = {**pool, **_write(
        pool, layer, block_table, positions, stored,
        None if index is None else ki[:, :, None, :])}
    k_pool = out["k"]
    kernel = _latent_kernels_for(
        k_pool, rkv, dn, None if index is None else out["v"].shape[4])
    pages, bs = k_pool.shape[1:3]
    first = layer.astype(block_table.dtype) * pages  # the layer's page 0

    def gathered(a):
        """The rows of every table position out of pool `a`, [B, M * bs,
        the row]."""
        n, m = block_table.shape
        starts = ((first + block_table) * bs).reshape(n * m)
        ctx = jax.vmap(
            lambda at: jax.lax.dynamic_slice_in_dim(_rows(a), at, bs)
        )(starts)
        return ctx.reshape(n, m * bs, a.shape[4])

    def gathered_rows():
        with jax.named_scope(scopes.KV_GATHER):
            return gathered(k_pool).astype(jnp.float32)

    def seen(t):  # [B, S, T]: key j of the gathered context visible to query
        return jnp.arange(t, dtype=jnp.int32)[None, None, :] <= positions[
            :, :, None].astype(jnp.int32)

    if s == 1:
        with jax.named_scope(scopes.ATTN_ABSORB):
            if isinstance(w_uk, QTensor):
                # the scale is a channel's, and W_UK's channels are what
                # this product sums over: it goes onto q first
                qn = q[..., :dn] * w_uk.scale[..., 0].astype(dtype)
                qa = jnp.einsum("bshn,hnc->bshc", qn, w_uk.q.astype(dtype))
            else:
                qa = jnp.einsum("bshn,hnc->bshc", q[..., :dn],
                                materialize(w_uk, dtype))
            qa = jnp.concatenate([
                qa.astype(dtype), q[..., dn:],
                jnp.zeros((b, s, h, w - rkv - dr), dtype)], axis=-1)

        def absorbed_in_xla():
            lat = gathered_rows()
            with jax.named_scope(scopes.ATTN_CORE):
                sc = jnp.einsum("bshw,btw->bsht", qa.astype(jnp.float32),
                                lat) * scale
                sc = jnp.where(seen(lat.shape[1])[:, :, None], sc, -1e30)
                p = jax.nn.softmax(sc, axis=-1)
                return jnp.einsum("bsht,btc->bshc", p,
                                  lat[..., :rkv]).astype(dtype)

        def absorbed_in_place():
            with jax.named_scope(scopes.ATTN_CORE):
                return latent_decode_attention(
                    qa[:, 0], k_pool, layer, block_table, positions[:, 0],
                    rkv=rkv, scale=scale)[:, None]

        def absorbed_over_the_set():
            key_pool = out["v"]

            def scores_in_xla():
                sc = sparse_index.scores(qi, gathered(key_pool), wi)[:, 0]
                return jnp.where(seen(sc.shape[1])[:, 0], sc, -jnp.inf)

            def scores_in_place():
                return index_decode_scores(
                    qi[:, 0], wi[:, 0], key_pool, layer, block_table,
                    positions[:, 0])

            with jax.named_scope(scopes.ATTN_INDEX):
                sc = (scores_in_xla() if kernel is None else
                      jax.lax.platform_dependent(
                          tpu=scores_in_place, default=scores_in_xla))
            with jax.named_scope(scopes.ATTN_SELECT):
                # The chunk's mask, then its positions' rows in the layer
                # by ascending position: attention over a set reads no
                # order, so nothing is sorted (a stable sort of 18,432
                # scores a slot cost 0.196 ms a layer on the chip, and
                # 8,192 lookups through the table 0.09, PR 42). A row
                # shorter than the set leaves places over: those stand
                # for no row and read the trash page's first.
                def rows_in_xla():
                    return sparse_index.select_rows(
                        sc, positions[:, 0], block_table, topk)

                def rows_in_vmem():
                    return index_select_rows(
                        sc, positions[:, 0], block_table, topk)

                row, ok = (
                    jax.lax.platform_dependent(
                        tpu=rows_in_vmem, default=rows_in_xla)
                    if kernel is not None and select_rows_kernel_takes(
                        block_table, bs, topk) else rows_in_xla())
                flat = jnp.where(ok, first * bs + row, 0)
            with jax.named_scope(scopes.ATTN_CORE):
                rows = _rows(k_pool).at[flat].get(
                    mode="promise_in_bounds")[:, :, 0]  # [B, k, w]
                rows = jnp.where(ok[..., None], rows, 0)
                sel = jnp.einsum("bhw,btw->bht", qa[:, 0], rows,
                                 preferred_element_type=jnp.float32) * scale
                p = jax.nn.softmax(
                    jnp.where(ok[:, None], sel, NEG_INF), axis=-1)
                return jnp.einsum(
                    "bht,btc->bhc", p.astype(rows.dtype), rows[..., :rkv],
                    preferred_element_type=jnp.float32
                ).astype(dtype)[:, None]

        if index is not None:
            ol = absorbed_over_the_set()
        elif kernel is None:
            ol = absorbed_in_xla()
        else:
            ol = jax.lax.platform_dependent(
                tpu=absorbed_in_place, default=absorbed_in_xla)
        with jax.named_scope(scopes.ATTN_ABSORB):
            o = qeinsum("bshc,hvc->bshv", ol, w_uv, dtype)
        return out, o.astype(dtype)

    with jax.named_scope(scopes.ATTN_EXPAND):
        # W_UK_i over W_UV_i^T a head, [H, dn + dv, rkv], as the matmuls
        # take it
        dense = jnp.concatenate(
            [materialize(w_uk, dtype), materialize(w_uv, dtype)], axis=1)

    attended = None  # [B, T, S]: key j in query i's set, keys-major
    if index is not None:
        def index_in_xla():
            return sparse_index.scores(
                qi, gathered(out["v"]), wi).transpose(0, 2, 1)

        def index_in_kernel():
            return index_chunk_scores(qi, wi, gathered(out["v"]))

        with jax.named_scope(scopes.ATTN_INDEX):
            scored = (index_in_xla() if kernel is None else
                      jax.lax.platform_dependent(
                          tpu=index_in_kernel, default=index_in_xla))
        with jax.named_scope(scopes.ATTN_SELECT):
            attended = sparse_index.select(
                scored, seen(scored.shape[1]).transpose(0, 2, 1), topk,
                axis=1)

    def visible(t):  # [B, S, T]
        return seen(t) if attended is None else attended.transpose(0, 2, 1)

    def expanded_in_xla():
        lat = gathered_rows()
        with jax.named_scope(scopes.ATTN_EXPAND):
            kv = jnp.einsum("btc,hmc->bthm", lat[..., :rkv],
                            dense.astype(jnp.float32))
        with jax.named_scope(scopes.ATTN_CORE):
            qf = q.astype(jnp.float32)
            sc = (jnp.einsum("bshn,bthn->bsht", qf[..., :dn], kv[..., :dn])
                  + jnp.einsum("bshr,btr->bsht", qf[..., dn:],
                               lat[..., rkv:rkv + dr])) * scale
            sc = jnp.where(visible(lat.shape[1])[:, :, None], sc, -1e30)
            p = jax.nn.softmax(sc, axis=-1)
            return jnp.einsum("bsht,bthv->bshv", p, kv[..., dn:]).astype(dtype)

    def expanded_in_place():
        bias = None
        if attended is not None:
            with jax.named_scope(scopes.ATTN_SELECT):
                bias = jnp.where(attended, 0.0, NEG_INF).astype(dtype)
        with jax.named_scope(scopes.ATTN_CORE):
            return latent_chunk_attention(
                q, dense, k_pool, layer, block_table, positions, bias,
                dn=dn, scale=scale)

    if kernel is None:
        return out, expanded_in_xla()
    return out, jax.lax.platform_dependent(
        tpu=expanded_in_place, default=expanded_in_xla)


def _latent_kernels_for(k_pool, rkv: int, dn: int,
                        di: Optional[int] = None) -> Optional[bool]:
    """Whether ops/latent_attention.py's kernels read this latent pool in
    place: a bfloat16 pool whose stored row is a multiple of the 128 lanes
    (`init_latent_cache` makes it so) and whose latent ends on a lane tile
    (the rotary key then has the row's last tile to itself), on one
    device (and ops/sparse_index.py's an index key of `di` values, where
    the layer keeps one: whole lane tiles). None where they are not
    written for the case (a float32 pool,
    a row stored as declared, a latent or a head that Mosaic does not tile,
    any mesh that shards something: one row serves every head, so no axis
    of the pool splits over heads)."""
    if (k_pool.dtype != jnp.bfloat16 or k_pool.shape[4] % LANES
            or rkv % LANES or dn % 16 or (di or 0) % LANES):
        return None
    mesh = jax.typeof(k_pool).sharding.mesh
    if any(n > 1 for n in mesh.shape.values()):
        return None
    return True


def init_latent_cache(n_layers: int, pages: int, page_size: int, row: int,
                      dtype, index_row: int = 0) -> Dict[str, jnp.ndarray]:
    """The page pool of layers whose attention is latent: `k` [L, P, bs, 1,
    w], one row a token for every head, and `v` a pool of no layers (the
    values are the keys' leading part: the dict says that nothing else is
    kept). The one place that decides the stored row: a bfloat16 pool
    keeps `row` values in w = the next multiple of 128 lanes, zeros behind
    them (Mosaic tiles no other width, and the device keeps such a page
    row-major as a matrix of `page_size` rows); any other pool as
    declared. Readers take the logical row off their own operands. With
    `index_row` (a layer that picks its rows by a learned index,
    ops/sparse_index.py) `v` is the tokens' index keys, [L, P, bs, 1,
    index_row], stored as declared: the same pages under the same ids."""
    w = row
    if jnp.dtype(dtype) == jnp.bfloat16:
        w = -(-row // LANES) * LANES
    keys = ((n_layers, pages, page_size, 1, index_row) if index_row
            else (0, pages, page_size, 1, w))
    return {"k": jnp.zeros((n_layers, pages, page_size, 1, w), dtype),
            "v": jnp.zeros(keys, dtype)}


def latent_cache_logical_axes() -> Dict[str, tuple]:
    """Nothing of a latent pool shards: a row belongs to every head."""
    ax = ("layers", None, None, None, None)
    return {"k": ax, "v": ax}


def ring_read_and_update(
    ring: Dict[str, jnp.ndarray],  # {"wk", "wv"}: [Lw, slots, W, KH, hd]
    layer: jnp.ndarray,  # scalar int32: index among the window layers
    slots: jnp.ndarray,  # [B] int32: the ring row of each batch row
    positions: jnp.ndarray,  # [B, S] absolute positions, ascending in a row
    valid: jnp.ndarray,  # [B, S] bool: real tokens (not bucket padding,
    # not an idle slot's filler)
    k_new: jnp.ndarray,  # [B, S, KH, hd]
    v_new: jnp.ndarray,
) -> Tuple[Dict[str, jnp.ndarray], jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """The history of a layer that keeps a window of W rows a sequence.

    Each slot owns W rows of every window layer; position p lives in row
    p % W, so a row is overwritten W positions later, when no query can see
    it any more. Reads the W rows each slot held BEFORE this call, appends
    the new ones, and scatters the newest W real rows back in place (the
    caller carries and donates the ring, as the paged pool; rows keep the
    pool's [KH, hd] shape, one tile: a head-major ring made the compiler
    lay the whole stack out anew in every step). Returns (updated ring,
    k_ctx, v_ctx [B, W + S, KH, hd], k_pos [B, W + S]): the absolute
    position of every context row, -1 where the row holds nothing of this
    sequence (a ring not yet full, another request's leftovers, padding),
    which the caller's mask must hide.

    The ring is assumed to hold the positions just below positions[:, 0]:
    true for a sequence written in order from position 0 (prefill chunks,
    then decode steps; a preempted sequence is prefilled again from 0).
    """
    n_layers, n_slots, w = ring["wk"].shape[:3]
    base = (layer.astype(jnp.int32) * n_slots + slots.astype(jnp.int32)) * w

    def rows(a):  # [Lw, slots, W, ...] -> [Lw * slots * W, ...], a bitcast
        return a.reshape((n_layers * n_slots * w,) + a.shape[3:])

    with jax.named_scope(scopes.KV_RING):
        def read(a):
            return jax.vmap(
                lambda start: jax.lax.dynamic_slice_in_dim(rows(a), start, w)
            )(base)

        k_old, v_old = read(ring["wk"]), read(ring["wv"])
        # row r of a ring whose newest position is `prev` holds the largest
        # position <= prev that is r modulo W
        prev = positions[:, :1].astype(jnp.int32) - 1  # [B, 1]
        r = jnp.arange(w, dtype=jnp.int32)[None, :]
        old_pos = prev - (prev - r) % w
        old_pos = jnp.where(old_pos >= 0, old_pos, -1)
        new_pos = jnp.where(valid, positions.astype(jnp.int32), -1)
        k_ctx = jnp.concatenate([k_old, k_new.astype(k_old.dtype)], axis=1)
        v_ctx = jnp.concatenate([v_old, v_new.astype(v_old.dtype)], axis=1)
        k_pos = jnp.concatenate([old_pos, new_pos], axis=1)
        # Of the new rows only the newest W real ones are kept: they are
        # distinct modulo W, so the scatter has no duplicate index; the
        # others go out of bounds and are dropped.
        last = jnp.max(new_pos, axis=1, keepdims=True)
        keep = valid & (new_pos > last - w)
        idx = jnp.where(keep, base[:, None] + new_pos % w,
                        n_layers * n_slots * w)
        out = {}
        for name, vals in (("wk", k_new), ("wv", v_new)):
            a = ring[name]
            out[name] = (
                rows(a).at[idx].set(vals.astype(a.dtype), mode="drop")
                .reshape(a.shape)
            )
    return out, k_ctx, v_ctx, k_pos


def init_ring_cache(
    n_layers: int, slots: int, window: int, kv_heads: int, head_dim: int, dtype,
) -> Dict[str, jnp.ndarray]:
    """Per-slot rings of the window layers: wk/wv [Lw, slots, W, KH, hd]."""
    shape = (n_layers, slots, window, kv_heads, head_dim)
    return {"wk": jnp.zeros(shape, dtype), "wv": jnp.zeros(shape, dtype)}


def ring_cache_logical_axes() -> Dict[str, tuple]:
    ax = ("layers", None, None, "kv_heads", "head_dim")
    return {"wk": ax, "wv": ax}


CONV_STATE = "conv"  # the cache dict's key of the convolution layers' rows


def conv_read_and_update(
    state: jnp.ndarray,  # [Lc, slots, L - 1, D]
    layer: jnp.ndarray,  # scalar int32: index among the convolution layers
    slots: jnp.ndarray,  # [B] int32: the state row of each batch row
    positions: jnp.ndarray,  # [B, S] absolute positions, ascending in a row
    valid: jnp.ndarray,  # [B, S] bool: real tokens; they lead their row
    u: jnp.ndarray,  # [B, S, D]: the convolution's input at `positions`
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The input history of a causal depthwise convolution of L taps.

    Each slot owns L - 1 rows of every convolution layer: the inputs at
    the L - 1 positions below the next one, oldest first. Returns (updated
    state, context [B, L - 1 + S, D]): the slot's rows as they were BEFORE
    this call, then `u`, so output row t is the taps applied to context
    rows t .. t + L - 1. A state row that would stand for a position below
    0 reads as zero: a sequence that starts at position 0 never sees what
    the slot's last occupant left, and nothing is zeroed at admission. The
    rows scattered back in place (the caller carries and donates the state,
    as the paged pool) are the L - 1 rows of the context that end with the
    row's last REAL token: a chunk's padded tail never enters the state, a
    chunk with fewer real tokens than L - 1 keeps the newest of the old
    rows, and a row with no real token (an idle slot's filler) leaves its
    slot's state as it was.

    The state is assumed to hold the positions just below positions[:, 0]:
    true for a sequence written in order from position 0 (prefill chunks,
    then decode steps; a preempted sequence is prefilled again from 0).
    """
    n_layers, n_slots, keep, d = state.shape
    row = layer.astype(jnp.int32) * n_slots + slots.astype(jnp.int32)  # [B]
    with jax.named_scope(scopes.CONV_STATE):
        flat = state.reshape(n_layers * n_slots, keep, d)
        old = flat[row]  # [B, L - 1, D]
        # state row r stands for position positions[:, 0] - (L - 1) + r
        stands_for = (positions[:, :1].astype(jnp.int32) - keep
                      + jnp.arange(keep, dtype=jnp.int32)[None, :])
        seen = jnp.where((stands_for >= 0)[..., None], old, 0)
        u = u.astype(state.dtype)
        ctx = jnp.concatenate([seen, u], axis=1)
        # the rows kept: those that end with the last real token, i.e.
        # rows n .. n + L - 2 of [old, u] for n real tokens
        n = jnp.sum(valid, axis=1, dtype=jnp.int32)
        kept = jax.vmap(
            lambda rows, start: jax.lax.dynamic_slice_in_dim(
                rows, start, keep, axis=0)
        )(jnp.concatenate([old, u], axis=1), n)
        out = flat.at[row].set(kept).reshape(state.shape)
    return out, ctx


def _take_slots(state, layer, slots, rows: int):
    """[B, ...]: what this call's slots own of `layer` of a per-slot stack
    [L, slots, ...]: the layer's slab where it lies with `slots` None (a
    decode step: row i is slot i), else row by row."""
    if slots is None:
        if rows != state.shape[1]:
            raise ValueError(
                f"{rows} rows for {state.shape[1]} slots: pass `slots`")
        return jax.lax.dynamic_index_in_dim(state, layer, 0, keepdims=False)
    tail = state.shape[2:]
    return jnp.concatenate([jax.lax.dynamic_slice(
        state, (layer, slots[i].astype(jnp.int32)) + (0,) * len(tail),
        (1, 1) + tail)[0] for i in range(rows)])


def _put_slots(state, new, layer, slots):
    """The stack with `_take_slots`' rows written back where they lay."""
    if slots is None:
        return jax.lax.dynamic_update_index_in_dim(state, new, layer, 0)
    for i in range(new.shape[0]):
        state = jax.lax.dynamic_update_slice(
            state, new[i][None, None],
            (layer, slots[i].astype(jnp.int32)) + (0,) * (new.ndim - 1))
    return state


def conv_rows_read_and_update(
    state: jnp.ndarray,  # [Lc, slots, (L - 1) * D]: a slot's rows end to end
    layer: jnp.ndarray,  # scalar int32: index among the convolution layers
    slots: Optional[jnp.ndarray],  # [B] int32, or None: row i is slot i
    positions: jnp.ndarray,  # [B, S]
    valid: jnp.ndarray,  # [B, S] bool
    u: jnp.ndarray,  # [B, S, D]
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """`conv_read_and_update` over a stack that keeps a slot's L - 1 rows
    end to end, one row of (L - 1) * D values. Same contract, same
    returns, two call shapes.

    A decode step over every slot (`slots` None and one token a row: what
    `ssm_read_and_update` reads to choose its kernel) shifts the layer's
    slab where it lies, elementwise: context row r is the slab's r-th
    segment of D values, zero where it would stand for a position below 0,
    and the last one is `u`; the rows kept are the slab less its oldest
    segment with `u` behind it where the row's token is real, and the slab
    as it was where it is not. Every segment starts on a lane tile when D
    is a multiple of 128, so the compiler makes it six small fusions a
    layer with no gather, no scatter and no relaid copy, and the mixer's
    activations around it stay slots down the sublanes (PERF.md section
    6, PR 47).

    A chunk (`slots` given, or more than one token a row) takes its slots'
    rows out of the stack, hands them to `conv_read_and_update` as a state
    of one layer, and writes them back where they lay: that function is
    written for any count of real tokens, and pays for it a gather by row,
    a padded concatenate, a second gather for the rows kept and a scatter.

    Why a family would keep its rows so: the stack's layout is then
    nobody's to choose. Compiled for a v5e, a stack [36, 48, 3, 4352] was
    padded to four rows, held in VMEM and packed and unpacked whole around
    every layer's read of a decode step; and in a 512-token chunk, whose
    convolution input the compiler lays tokens-innermost, that layout
    reached the stack through the one slot's update and padded its rows to
    128 (1.9 GB of temporaries, copied whole eight times a scan iteration:
    PERF.md section 6, PR 46). What is relaid here is a call's own rows."""
    bsz, s, d = u.shape
    layer = layer.astype(jnp.int32)
    if slots is None and s == 1:
        with jax.named_scope(scopes.CONV_STATE):
            old = _take_slots(state, layer, None, bsz)  # [B, (L - 1) * D]
            keep = old.shape[1] // d
            new = u[:, 0].astype(state.dtype)
            # segment r stands for position positions[:, 0] - (L - 1) + r
            first = positions[:, :1].astype(jnp.int32) - keep
            ctx = jnp.stack(
                [jnp.where(first + r >= 0, old[:, r * d:(r + 1) * d], 0)
                 for r in range(keep)] + [new], axis=1)
            # the rows kept, written where they lie as two pieces: all but
            # the oldest segment moved down one, and `u` behind them. (One
            # concatenate of the two cost a layer two more kernels, which
            # wrote its operands out whole first.)
            live, cut = valid[:, :1], (keep - 1) * d
            for at, piece in (
                    (0, jnp.where(live, old[:, d:], old[:, :cut])),
                    (cut, jnp.where(live, new, old[:, cut:]))):
                state = jax.lax.dynamic_update_slice(
                    state, piece[None], (layer, 0, at))
            return state, ctx
    with jax.named_scope(scopes.CONV_STATE):
        rows = _take_slots(state, layer, slots, bsz)  # [B, (L - 1) * D]
    rows, ctx = conv_read_and_update(
        rows.reshape(1, bsz, -1, d), jnp.zeros((), jnp.int32),
        jnp.arange(bsz, dtype=jnp.int32), positions, valid, u)
    with jax.named_scope(scopes.CONV_STATE):
        return _put_slots(state, rows.reshape(bsz, -1), layer, slots), ctx


def init_conv_state(n_layers: int, slots: int, taps: int, dim: int, dtype
                    ) -> Dict[str, jnp.ndarray]:
    """Per-slot rows of the convolution layers: [Lc, slots, taps - 1, D]."""
    return {CONV_STATE: jnp.zeros((n_layers, slots, taps - 1, dim), dtype)}


def conv_state_logical_axes() -> Dict[str, tuple]:
    return {CONV_STATE: ("layers", None, None, "embed")}


RET_S, RET_Z = "ret_s", "ret_z"  # the cache dict's keys of a retention state


def retention_read_and_update(
    state_s: jnp.ndarray,  # [L, slots, KH, F, dv] float32
    state_z: jnp.ndarray,  # [L, slots, KH, F] float32
    layer: jnp.ndarray,  # scalar int32: the layer's index in the stack
    slots: Optional[jnp.ndarray],  # [B] int32, or None: row i is slot i
    positions: jnp.ndarray,  # [B, S] absolute positions, ascending in a row
    valid: jnp.ndarray,  # [B, S] bool: real tokens; they lead their row
    q: jnp.ndarray,  # [B, S, H, d]
    k: jnp.ndarray,  # [B, S, KH, d]
    v: jnp.ndarray,  # [B, S, KH, dv]
    log_g: jnp.ndarray,  # [B, S, KH] float32: log of the gate
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """The state of a power-retention layer (ops/retention.py): each slot
    owns one `S [KH, F, dv]` and one `z [KH, F]` of every layer, the sum of
    everything its sequence has seen, and no history beside it. Returns
    (state_s, state_z, o [B, S, H, dv] float32), the states updated in
    place (the caller carries and donates them, as the paged pool): one
    token a row takes the recurrent step, more the chunked form.

    Under `conv_read_and_update`'s contract. A row whose first token is
    real and at position 0 starts from zero whatever its slot holds:
    nothing is zeroed at admission, and nothing tells the state's age but
    `positions`. A token that is not real (a bucket's padded tail, an idle
    slot's filler) decays nothing and adds nothing, so a row with no real
    token leaves its slot's state bit for bit as it was. The state is
    assumed to hold the positions below positions[:, 0]: true for a
    sequence written in order from position 0 (prefill chunks, then decode
    steps; a preempted sequence is prefilled again from 0).

    With `slots` None the layer's rows are read and written as one slab
    where they lie (a decode step: every row, whichever slots are live);
    with `slots` given, row by row. The decode step (`slots` None, one
    token a row) lowered for a TPU over a state `_retention_kernel_for`
    takes is ops/retention_kernel.py::step: every head's `S` crosses HBM
    once in each direction where the XLA program reads it twice and writes
    it once. Everything else (a chunk, `slots` given, any other platform,
    a state the kernel is not written for) is ops/retention.py, which is
    also what the kernel is tested against."""
    n_slots = state_s.shape[1]
    b, s = positions.shape
    fresh = (positions[:, 0] == 0) & valid[:, 0]
    k = jnp.where(valid[..., None, None], k, 0)
    log_g = jnp.where(valid[..., None], log_g.astype(jnp.float32), 0.0)
    layer = layer.astype(jnp.int32)
    if slots is None and b != n_slots:
        raise ValueError(f"{b} rows for {n_slots} slots: pass `slots`")
    kernel = (_retention_kernel_for(state_s)
              if slots is None and s == 1 else None)

    def in_xla():
        if slots is None:
            old = [jax.lax.dynamic_index_in_dim(a, layer, 0, keepdims=False)
                   for a in (state_s, state_z)]
        else:
            def row(a, i):
                at = (layer, slots[i].astype(jnp.int32)) + (0,) * (a.ndim - 2)
                return jax.lax.dynamic_slice(a, at, (1, 1) + a.shape[2:])[0]

            old = [jnp.concatenate([row(a, i) for i in range(b)])
                   for a in (state_s, state_z)]
        if s == 1:
            new_s, new_z, o = retention.step(
                *old, q[:, 0], k[:, 0], v[:, 0], log_g[:, 0], fresh)
            o = o[:, None]
        else:
            new_s, new_z, o = retention.chunk(old, q, k, v, log_g, fresh)
        out = []
        for a, new in ((state_s, new_s), (state_z, new_z)):
            if slots is None:
                a = jax.lax.dynamic_update_index_in_dim(a, new, layer, 0)
            else:
                for i in range(b):
                    at = ((layer, slots[i].astype(jnp.int32))
                          + (0,) * (a.ndim - 2))
                    a = jax.lax.dynamic_update_slice(a, new[i][None, None], at)
            out.append(a)
        return out[0], out[1], o

    def in_kernel():
        new_s, new_z, o = kernel(
            state_s, state_z, layer, q[:, 0], k[:, 0], v[:, 0], log_g[:, 0],
            fresh)
        return new_s, new_z, o[:, None]

    with jax.named_scope(scopes.RET_STATE):
        if kernel is None:
            return in_xla()
        return jax.lax.platform_dependent(tpu=in_kernel, default=in_xla)


def _retention_state_specs() -> Tuple[P, P]:
    """The PartitionSpecs of `ret_s` and `ret_z` under the serve rules;
    [2] is the mesh axis of kv_heads."""
    axes = retention_state_logical_axes()
    return SERVE_RULES.mesh_axes(axes[RET_S]), SERVE_RULES.mesh_axes(axes[RET_Z])


def _retention_kernel_for(state_s):
    """ops/retention_kernel.py::step as this state's placement lets it run
    a decode step (one token a row, row i slot i): as it is on one device;
    a shard of KV heads a device where the state is sharded over them and
    nothing else is sharded (a head's state is its own: no collective).
    None where the kernel is not written for the case: a state that is not
    float32, values that are no multiple of the 128 lanes wide or not as
    wide as the keys (the kernel makes `phi` from rotations of a 128-lane
    row), one slot's heads more than a grid step holds, and any other
    placement."""
    n_slots, kv_heads, f, dv = state_s.shape[1:]
    if (state_s.dtype != jnp.float32 or dv % LANES
            or f != retention.width(dv)):
        return None
    mesh = jax.typeof(state_s).sharding.mesh
    sharded = {name: n for name, n in mesh.shape.items() if n > 1}
    spec_s, spec_z = _retention_state_specs()
    heads = spec_s[2]  # the mesh axis of kv_heads
    if sharded and (
        list(sharded) != [heads] or kv_heads % sharded[heads]
    ):
        return None
    if not retention_kernel.slots_a_step(
            n_slots, kv_heads // sharded.get(heads, 1), f, dv):
        return None
    if not sharded:
        return retention_kernel.step
    row = P(None, heads)  # q [B, H, d], k, v, log_g [B, KH]: heads second
    return jax.shard_map(
        retention_kernel.step, mesh=mesh,
        in_specs=(spec_s, spec_z, P(), row, row, row, row, P()),
        out_specs=(spec_s, spec_z, row), check_vma=False,
    )


def retention_step_takes_kernel(state_s: jax.Array) -> bool:
    """Whether a decode step over this state runs ops/retention_kernel.py:
    what `retention_read_and_update` chooses for it, on the devices that
    hold it (for the engine's `state_kernel_steps`)."""
    return (all(d.platform == "tpu" for d in state_s.devices())
            and _retention_kernel_for(state_s) is not None)


def init_retention_state(n_layers: int, slots: int, kv_heads: int,
                         head_dim: int, value_dim: int
                         ) -> Dict[str, jnp.ndarray]:
    """Per-slot state of the retention layers, float32 whatever the
    activations' type (a sum over thousands of rank-one terms under a decay
    near 1): `ret_s` [L, slots, KH, F, dv], `ret_z` [L, slots, KH, F], F =
    head_dim (head_dim + 1) / 2."""
    f = head_dim * (head_dim + 1) // 2
    return {
        RET_S: jnp.zeros((n_layers, slots, kv_heads, f, value_dim),
                         jnp.float32),
        RET_Z: jnp.zeros((n_layers, slots, kv_heads, f), jnp.float32),
    }


def retention_state_logical_axes() -> Dict[str, tuple]:
    return {RET_S: ("layers", None, "kv_heads", None, "head_dim"),
            RET_Z: ("layers", None, "kv_heads", None)}


SSM_STATE = "ssm"  # the cache dict's key of a state-space layer's state


def ssm_read_and_update(
    state: jnp.ndarray,  # [Lm, slots, N, H x P] float32
    layer: jnp.ndarray,  # scalar int32: index among the state-space layers
    slots: Optional[jnp.ndarray],  # [B] int32, or None: row i is slot i
    positions: jnp.ndarray,  # [B, S] absolute positions, ascending in a row
    valid: jnp.ndarray,  # [B, S] bool: real tokens; they lead their row
    x: jnp.ndarray,  # [B, S, H, P]
    b: jnp.ndarray,  # [B, S, N]
    c: jnp.ndarray,  # [B, S, N]
    dt: jnp.ndarray,  # [B, S, H] float32: the step size, softplus applied
    a_log: jnp.ndarray,  # [H] float32
    d_skip: jnp.ndarray,  # [H] float32
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The state of a Mamba-2 layer (ops/ssd.py): each slot owns one `S [N,
    H x P]` of every such layer. Returns (state, o [B, S, H, P] float32),
    the state updated in place (the caller carries and donates it, as the
    paged pool): one token a row takes the recurrent step, more the chunked
    form. `retention_read_and_update`'s contract word for word: a row whose
    first token is real and at position 0 starts from zero whatever its
    slot holds and nothing is zeroed at admission; a token that is not real
    (`dt` is made 0 there) decays nothing and adds nothing, so a row with
    no real token leaves its slot's state bit for bit; the state is assumed
    to hold the positions below positions[:, 0].

    With `slots` None the layer's rows are read and written as one slab
    where they lie (a decode step: every row, whichever slots are live);
    with `slots` given, row by row. The decode step (`slots` None, one
    token a row) lowered for a TPU over a state `_ssm_kernel_for` takes is
    ops/ssd_kernel.py::step: a slot's `S` crosses HBM once in each
    direction. Everything else is ops/ssd.py, which is also what the kernel
    is tested against."""
    bsz, s = positions.shape
    fresh = (positions[:, 0] == 0) & valid[:, 0]
    dt = jnp.where(valid[..., None], dt.astype(jnp.float32), 0.0)
    layer = layer.astype(jnp.int32)
    kernel = _ssm_kernel_for(state) if slots is None and s == 1 else None

    def in_xla():
        old = _take_slots(state, layer, slots, bsz)
        if s == 1:
            new, o = ssd.step(old, x[:, 0], b[:, 0], c[:, 0], dt[:, 0],
                              a_log, d_skip, fresh)
            o = o[:, None]
        else:
            new, o = ssd.chunk(old, x, b, c, dt, a_log, d_skip, fresh)
        return _put_slots(state, new, layer, slots), o

    def in_kernel():
        new, o = kernel(state, layer, x[:, 0], b[:, 0], c[:, 0], dt[:, 0],
                        a_log, d_skip, fresh)
        return new, o[:, None]

    with jax.named_scope(scopes.SSM_STATE):
        if kernel is None:
            return in_xla()
        return jax.lax.platform_dependent(tpu=in_kernel, default=in_xla)


def _ssm_kernel_for(state):
    """ops/ssd_kernel.py::step where it is written for this state's
    decode step (one token a row, row i slot i): a float32 state on one
    device, N and H x P both multiples of the 128 lanes (the kernel turns
    `B` and `C` by a [128, N] transpose). None otherwise (a sharded state,
    `tiny-granite-hybrid`'s 8 x 64): ops/ssd.py::step."""
    n, r = state.shape[2:]
    mesh = jax.typeof(state).sharding.mesh
    if (state.dtype != jnp.float32 or n % LANES or r % LANES
            or any(size > 1 for size in mesh.shape.values())):
        return None
    return ssd_kernel.step


def ssm_step_takes_kernel(state: jax.Array) -> bool:
    """Whether a decode step over this state runs ops/ssd_kernel.py: what
    `ssm_read_and_update` chooses for it, on the devices that hold it (for
    the engine's `state_kernel_steps`)."""
    return (all(d.platform == "tpu" for d in state.devices())
            and _ssm_kernel_for(state) is not None)


def init_ssm_state(n_layers: int, slots: int, heads: int, head_dim: int,
                   d_state: int) -> Dict[str, jnp.ndarray]:
    """Per-slot state of the Mamba-2 layers, float32 whatever the
    activations' type (a sum over hundreds of rank-one terms under a decay
    near 1): `ssm` [Lm, slots, N, H x P], ops/ssd.py's layout."""
    return {SSM_STATE: jnp.zeros(
        (n_layers, slots, d_state, heads * head_dim), jnp.float32)}


def ssm_state_logical_axes() -> Dict[str, tuple]:
    return {SSM_STATE: ("layers", None, None, None)}


def init_paged_cache(
    n_layers: int,
    pages: int,
    page_size: int,
    kv_heads: int,
    head_dim: int,
    dtype,
    quantized: bool = False,
    kv_shards: int = 1,
) -> Dict[str, jnp.ndarray]:
    """Layers-stacked page pool: k/v [L, P, bs, KH, hd] (+ f32 scales).

    The one place that decides the stored row. A bfloat16 pool of heads
    that divide a 128-lane row is stored `pack` = 128 // hd neighbouring
    KV heads to a row, [L, P, bs, KH // pack, 128]: the same bytes in the
    same order, in the one shape of them Mosaic tiles and the device keeps
    row-major (a minor dimension of 64 it keeps pages-innermost, and every
    program then lays the pool out anew and back: PERF.md section 6, PR
    35). Every reader takes `pack` off its operands, the row's width over
    q's. Stored as declared: heads of 128 and wider (`pack` = 1, the same
    shape), an int8 or float32 pool, and a KV head count the rows do not
    divide evenly among the `kv_shards` devices that split the axis
    (`kv_head_shards`)."""
    pack = LANES // head_dim if LANES % head_dim == 0 else 1
    if (quantized or jnp.dtype(dtype) != jnp.bfloat16
            or kv_heads % (pack * kv_shards)):
        pack = 1
    shape = (n_layers, pages, page_size, kv_heads // pack, head_dim * pack)
    cache = {
        "k": jnp.zeros(shape, jnp.int8 if quantized else dtype),
        "v": jnp.zeros(shape, jnp.int8 if quantized else dtype),
    }
    if quantized:
        sshape = shape[:-1] + (1,)
        cache["k_scale"] = jnp.ones(sshape, jnp.float32)
        cache["v_scale"] = jnp.ones(sshape, jnp.float32)
    return cache


def paged_cache_logical_axes(quantized: bool = False) -> Dict[str, tuple]:
    """Pool axes: pages/page_size replicated (block tables are global; only
    kv_heads shards, over "tensor" — decode collectives then ride ICI)."""
    ax = ("layers", None, None, "kv_heads", "head_dim")
    axes = {"k": ax, "v": ax}
    if quantized:
        axes["k_scale"] = ax
        axes["v_scale"] = ax
    return axes


def insert_prefill(
    cache: Dict[str, jnp.ndarray],
    kv: Dict[str, jnp.ndarray],
    length: Optional[int] = None,
) -> Dict[str, jnp.ndarray]:
    """Write a fresh prefill kv fragment into a slot cache, in place of
    positions [0, S_frag).

    `kv` is what forward() returns without a cache: {k, v: [L, B, S, KH, D]}
    in activation layout. The slot cache (models/*.init_cache) stores
    [L, B, KH, S, D] (+ [L, B, KH, S] scales when int8) — entries are
    transposed and, for int8 caches, quantized per-vector on the way in
    (ops.decode_attention.pack_fragment).
    """
    from substratus_tpu.ops.decode_attention import pack_fragment

    frag = pack_fragment(cache, kv)
    if length is None:
        length = frag["k"].shape[3]
    out = dict(cache)
    for key, value in frag.items():
        out[key] = (
            cache[key].at[:, :, :, :length].set(value[:, :, :, :length])
        )
    return out
