"""ops/paged_attention.py (interpret mode) against the path it replaces,
which is what ops/kvcache.py::paged_attention runs on the CPU: the context
of every table position gathered (`paged_read`) and ops/attention.py::
dot_product_attention over it. One query token a row (a decode step) takes
the decode kernel, more (a prefill chunk, a verify round) the chunk kernel.
Heads are 64 wide. `declared` pools hold a head a row, [.., KH, 64], handed
to the kernels as they are (interpret mode tiles anything); the `kh*` pools
are stored as ops/kvcache.py::init_paged_cache stores them, two heads to a
row of 128, and reach the kernels the way `paged_attend` takes them there
(q widened to the row, the head's own lanes kept), against the gather over
the same packed pool. A page holds 16 tokens, or the 64 and 128 of the
families whose stored row holds two heads of 64 (models/lfm2_moe.py,
models/granitemoehybrid.py: PAGE_TOKENS): the kernels size their DMA blocks
and folds in tokens, so the same table of 640 positions is two blocks at
each. The whole engine through the
kernels, token for token, is tests/test_paged_attention_engine.py.
"""
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from substratus_tpu.ops import kvcache
from substratus_tpu.ops.paged_attention import (
    CHUNK_TOKENS, FOLD_TOKENS, fold_pages, paged_chunk_attention,
)

BS, KH, HD, LAYERS = 16, 2, 64, 3
FULL = 640  # a table of 640 positions: two DMA blocks of either kernel
TOL = {jnp.bfloat16: 2e-2, jnp.float32: 1e-5}
assert FOLD_TOKENS[-1] < FULL < 2 * FOLD_TOKENS[-1]
assert CHUNK_TOKENS < FULL < 2 * CHUNK_TOKENS
# The page of a case: 16 tokens, and the 64 and 128 of pools of 64-wide heads.
PAGE = pytest.mark.parametrize(
    "bs", [16, 64, 128], ids=["page16", "page64", "page128"])
BUCKETS = [16, 32, 64, 128, 256, 512]  # the engine's prefill buckets
# KV heads of a pool stored as init_paged_cache stores it (None: declared).
# On a TPU the op takes a kernel over 2, 4 or a multiple of 8 rows a token
# (4 heads of 64 up); the arithmetic of one row (`kh2`) is checked for the
# step all the same, a chunk's rows come in pairs.
PACKED = pytest.mark.parametrize(
    "packed", [None, 4, 8], ids=["declared", "kh4", "kh8"])


def _normal(key, shape, dtype):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


def _case(dtype, group, lengths, layer=1, tables=None, seed=0, s=1, real=None,
          kv_heads=KH, packed=None, hd=HD, bs=BS):
    """A seeded pool, a call's q and new K/V rows for rows of `lengths`
    tokens (the `s` new rows are the last of them; of these only the first
    `real` are a prompt's and the padded tail is clamped onto the position
    after them, as the engine's chunk program clamps it), and block tables
    of scattered pages unless given. `packed`: that many KV heads, in the
    shape init_paged_cache stores them. `bs`: the tokens of a page."""
    keys = jax.random.split(jax.random.key(seed), 6)
    b = len(lengths)
    kv_heads = packed or kv_heads
    m = FULL // bs
    pages = 1 + 4 * m
    shape = (LAYERS, pages, bs, kv_heads, hd)
    if packed:
        shape = jax.eval_shape(
            lambda: kvcache.init_paged_cache(*shape, dtype))["k"].shape
        assert shape[3:] == (kv_heads // 2, 128)
    pool = {"k": _normal(keys[0], shape, dtype),
            "v": _normal(keys[1], shape, dtype)}
    q = _normal(keys[2], (b, s, kv_heads * group, hd), dtype)
    k_new = _normal(keys[3], (b, s, kv_heads, hd), dtype)
    v_new = _normal(keys[4], (b, s, kv_heads, hd), dtype)
    if tables is None:
        tables = np.asarray(
            jax.random.permutation(keys[5], np.arange(1, pages))[: b * m]
        ).reshape(b, m)
    first = jnp.asarray(lengths, jnp.int32)[:, None] - s
    positions = first + jnp.arange(s, dtype=jnp.int32)[None, :]
    if real is not None:
        positions = jnp.minimum(positions, first + real)
    return (pool, jnp.int32(layer), jnp.asarray(tables, jnp.int32),
            positions, q, k_new, v_new)


def _reference(pool, layer, table, positions, q, k_new, v_new):
    return kvcache.paged_attention(
        pool, layer, table, positions, q, k_new, v_new, q.dtype)


def _kernel(pool, layer, table, positions, q):
    """The kernel as `paged_attend` calls it on a TPU, interpreted."""
    kernel = paged_chunk_attention if q.shape[1] > 1 else kvcache._one_token
    return kvcache._over_stored_rows(partial(kernel, interpret=True))(
        q, pool["k"], pool["v"], layer, table, positions)


def _close(got, want, dtype):
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=TOL[dtype], rtol=0)


@pytest.mark.parametrize("length", [1, BS - 1, BS, BS + 1, FULL],
                         ids=["one", "page-1", "page", "page+1", "full"])
@pytest.mark.parametrize("group", [4, 8])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_a_row_of_every_length_matches_the_gathered_attention(
    dtype, group, length
):
    pool, layer, table, pos, q, k_new, v_new = _case(
        dtype, group, [length, 3 * BS + 5, FOLD_TOKENS[-1] + 1])
    out, want = _reference(pool, layer, table, pos, q, k_new, v_new)
    _close(_kernel(out, layer, table, pos, q), want, dtype)


@PAGE
@pytest.mark.parametrize("length", ["one", "page-1", "page", "page+1", "full"])
@pytest.mark.parametrize("packed", [2, 4, 8], ids=["kh2", "kh4", "kh8"])
def test_a_row_of_every_length_out_of_a_packed_pool(packed, length, bs):
    """The same over rows that hold two KV heads of 64 each: the query
    heads of a pair share the pair's row and each keeps its own lanes. At
    every page: an idle row's one token, a row that ends a token short of
    its page's end, on it and a token into the next, a full table, beside
    a row that ends mid-page and one a token longer than a DMA block."""
    length = {"one": 1, "page-1": bs - 1, "page": bs, "page+1": bs + 1,
              "full": FULL}[length]
    pool, layer, table, pos, q, k_new, v_new = _case(
        jnp.bfloat16, 4, [length, 3 * bs + 5, FOLD_TOKENS[-1] + 1],
        packed=packed, bs=bs)
    out, want = _reference(pool, layer, table, pos, q, k_new, v_new)
    assert out["k"].shape == pool["k"].shape
    _close(_kernel(out, layer, table, pos, q), want, jnp.bfloat16)


@pytest.mark.parametrize("s", [1, 16], ids=["step", "chunk"])
@pytest.mark.parametrize("layer", [0, 1, LAYERS - 1])
def test_the_layer_is_an_offset_into_the_stack(layer, s):
    pool, _, table, pos, q, k_new, v_new = _case(
        jnp.bfloat16, 4, [37, FULL, 200], layer=layer, seed=layer, s=s)
    out, want = _reference(pool, jnp.int32(layer), table, pos, q, k_new,
                           v_new)
    _close(_kernel(out, jnp.int32(layer), table, pos, q), want, jnp.bfloat16)
    # and no other layer's rows would have given the same
    other = jnp.int32((layer + 1) % LAYERS)
    assert not np.allclose(
        np.asarray(_kernel(out, other, table, pos, q), np.float32),
        np.asarray(want, np.float32), atol=TOL[jnp.bfloat16])


@pytest.mark.parametrize(
    "s,packed",
    [(1, None), (1, 2), (1, 4), (1, 8), (32, None), (32, 4), (32, 8)],
    ids=["step", "step-kh2", "step-kh4", "step-kh8", "chunk", "chunk-kh4",
         "chunk-kh8"])
@PAGE
def test_scattered_pages_and_a_prefix_two_rows_share(s, packed, bs):
    """Rows 0 and 1 hold the same first pages (a prefix hit: five of 16
    tokens, two of 64, one of 128) and their own after them; every page lies somewhere
    else in the pool. (A chunk's rows are written after the shared
    pages.)"""
    m = FULL // bs
    shared, a, b = {16: (5, 9, 12), 64: (2, 4, 6), 128: (1, 2, 3)}[bs]
    tables = np.zeros((3, m), np.int32)
    order = np.random.default_rng(3).permutation(np.arange(1, 1 + 4 * m))
    tables[0, :a] = order[:a]
    tables[1, :shared] = order[:shared]
    tables[1, shared:b] = order[2 * m:2 * m + b - shared]
    tables[2] = order[-m:][::-1]
    lengths = [a * bs - 3, b * bs, FULL - 7]
    pool, layer, table, pos, q, k_new, v_new = _case(
        jnp.bfloat16, 4, lengths, tables=tables, s=s, packed=packed, bs=bs)
    out, want = _reference(pool, layer, table, pos, q, k_new, v_new)
    _close(_kernel(out, layer, table, pos, q), want, jnp.bfloat16)


@pytest.mark.parametrize("packed", [None, 4], ids=["declared", "kh4"])
@pytest.mark.parametrize("s", [1, 16], ids=["step", "chunk"])
@pytest.mark.parametrize("garbage", [float("nan"), 3e37, -3e37],
                         ids=["nan", "huge", "-huge"])
@PAGE
def test_a_row_sees_its_own_pages_up_to_its_own_position(
    garbage, s, packed, bs
):
    """An idle row as the engine leaves it (position 0, a table of the
    trash page: it costs the one page its own write landed on; for a chunk,
    a row whose `s` tokens are all it holds), a row of one page (a chunk:
    of two, the second barely begun) and a row of a full table, in a pool
    where every position no query of the row may see holds garbage: other
    layers, pages nobody owns, the rest of each row's last page, and the
    first row's table past its first entry pointing at pages of garbage
    too. The answer is the clean pool's."""
    lengths = [s, bs - 4 + (s > 1) * s, FULL]
    pool, layer, table, pos, q, k_new, v_new = _case(
        jnp.bfloat16, 8, lengths, s=s, packed=packed, bs=bs)
    table = table.at[0].set(0)
    clean, want = _reference(pool, layer, table, pos, q, k_new, v_new)
    pages, m = pool["k"].shape[1], table.shape[1]
    seen = np.zeros((LAYERS, pages, bs), bool)
    for row, n in enumerate(lengths):
        for p in range(n):
            seen[1, int(table[row, p // bs]), p % bs] = True
    dirty = {
        name: jnp.where(seen[..., None, None], a, garbage).astype(a.dtype)
        for name, a in clean.items()
    }
    # the idle row's stale entries lead to garbage, which it never reads
    unowned = [p for p in range(1, pages) if not seen[1, p].any()]
    stale = table.at[0, 1:].set(jnp.asarray(unowned[: m - 1], jnp.int32))
    got = _kernel(dirty, layer, stale, pos, q)
    assert np.isfinite(np.asarray(got, np.float32)).all()
    _close(got, want, jnp.bfloat16)
    # the other rows' answers are theirs whatever the idle row is
    alone = _kernel(dirty, layer, stale[1:], pos[1:], q[1:])
    np.testing.assert_array_equal(
        np.asarray(got[1:], np.float32), np.asarray(alone, np.float32))


@pytest.mark.parametrize("where", ["start", "mid-page", "table-end"])
@pytest.mark.parametrize("bucket", BUCKETS)
@pytest.mark.parametrize(
    "group,packed,bs", [(4, None, 16), (8, None, 16), (4, 8, 16), (4, 8, 64)],
    ids=["4", "8", "4-kh8", "4-kh8-page64"])
def test_a_chunk_of_every_bucket_matches_the_gathered_attention(
    group, packed, bs, bucket, where
):
    """A prefill chunk of each of the engine's buckets: the prompt's first
    (nothing before it), one that starts in the middle of a page, and one
    that ends on the last position of the table; beside it a row of another
    length, whose pages the first row's walk must not touch."""
    before = {"start": 0, "mid-page": 3 * BS + 5,
              "table-end": FULL - bucket}[where]
    pool, layer, table, pos, q, k_new, v_new = _case(
        jnp.bfloat16, group, [before + bucket, bucket + BS + 3], s=bucket,
        packed=packed, bs=bs)
    out, want = _reference(pool, layer, table, pos, q, k_new, v_new)
    _close(_kernel(out, layer, table, pos, q), want, jnp.bfloat16)


@PAGE
@PACKED
@pytest.mark.parametrize("real", [1, 19, 31])
def test_a_chunks_padded_tail_is_clamped_onto_one_position(real, packed, bs):
    """The engine pads a prompt's last chunk to its bucket and clamps the
    tail onto the one position after the prompt: the kernel reads the
    positions it is given, consecutive or not."""
    s = 32
    pool, layer, table, pos, q, k_new, v_new = _case(
        jnp.bfloat16, 4, [3 * bs + 5 + s, FULL], s=s, real=real,
        packed=packed, bs=bs)
    assert int(pos[0, -1]) == int(pos[0, real]) == 3 * bs + 5 + real
    out, want = _reference(pool, layer, table, pos, q, k_new, v_new)
    _close(_kernel(out, layer, table, pos, q), want, jnp.bfloat16)


@PAGE
@PACKED
@pytest.mark.parametrize("s", [2, 5], ids=["k1", "k4"])
def test_a_verify_round_matches_the_gathered_attention(s, packed, bs):
    """A speculative round: every slot brings k + 1 consecutive positions
    from its own length on, one of them past the table's reach (its writes
    go to the trash page, ops/kvcache.py::_write; its queries see the whole
    table), one idle at position 0."""
    lengths = [s, 7 * bs + s, FULL - 1, FULL + 2]
    pool, layer, table, pos, q, k_new, v_new = _case(
        jnp.bfloat16, 4, lengths, s=s, packed=packed, bs=bs)
    out, want = _reference(pool, layer, table, pos, q, k_new, v_new)
    _close(_kernel(out, layer, table, pos, q), want, jnp.bfloat16)


@pytest.mark.parametrize("s", [1, 16], ids=["step", "chunk"])
@pytest.mark.parametrize(
    "kv_heads,hd,dtype",
    [(3, 64, jnp.bfloat16), (2, 96, jnp.bfloat16), (2, 64, jnp.int8),
     (2, 64, jnp.float32)],
    ids=["kh3", "hd96", "int8", "f32"])
def test_what_is_not_packed_still_gathers(kv_heads, hd, dtype, s):
    """An odd count of 64-wide heads, a head width that does not divide a
    row of 128, an int8 pool and a float32 pool are stored as declared, and
    no kernel takes them: on a TPU too the op gathers, as before."""
    pages = 1 + 4 * FULL // BS
    pool = kvcache.init_paged_cache(
        LAYERS, pages, BS, kv_heads, hd, dtype, quantized=dtype == jnp.int8)
    assert pool["k"].shape == (LAYERS, pages, BS, kv_heads, hd)
    # nor rows that do not fill the sublane tile Mosaic gives a page: two
    # heads of 64 packed into one row, six heads of 128
    for heads, width in ((2, 64), (6, 128)):
        untiled = kvcache.init_paged_cache(
            LAYERS, pages, BS, heads, width, jnp.bfloat16)
        assert untiled["k"].shape[4] == 128
        assert kvcache._kernel_for(
            untiled["k"], jnp.zeros((2, s, 24, width), jnp.bfloat16)) is None
    _, layer, table, pos, q, k_new, v_new = _case(
        jnp.bfloat16, 4, [37, FULL], s=s, kv_heads=kv_heads, hd=hd)
    assert kvcache._kernel_for(pool["k"], q) is None
    out, got = _reference(pool, layer, table, pos, q, k_new, v_new)
    # the context of a pool that held nothing is the new rows alone
    plain = kvcache.init_paged_cache(
        LAYERS, pages, BS, kv_heads, hd, jnp.float32)
    _, want = _reference(plain, layer, table, pos, q, k_new, v_new)
    assert out["k"].dtype == dtype
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=6e-2 if dtype == jnp.int8 else 2e-2, rtol=0)


@PAGE
@pytest.mark.parametrize("kv_heads", [2, 4, 8])
def test_a_chunk_reads_each_kv_head_out_of_the_pages(kv_heads, bs):
    """The chunk kernel takes the heads out of a page two to a 32-bit word,
    every (KH / 2)th word-row: one pair, two and four."""
    pool, layer, table, pos, q, k_new, v_new = _case(
        jnp.bfloat16, 4, [5 * bs + 7, FULL], s=64, kv_heads=kv_heads, bs=bs)
    out, want = _reference(pool, layer, table, pos, q, k_new, v_new)
    _close(_kernel(out, layer, table, pos, q), want, jnp.bfloat16)


def test_blocks_and_folds_are_sized_in_tokens():
    """The kernels state their folds (32 / 128 / 512 tokens) and DMA blocks
    (512) in tokens and take the pages off the pool: at the page of 16 they
    are the constants the kernels were measured with (folds of 2, 8 and 32
    pages, blocks of 32), so a family that keeps 16 runs the programs it
    ran; at a longer page a block holds the same tokens in fewer copies
    and VMEM holds what it held (the decode kernel's two buffers of K, the
    chunk kernel's and its limit), and no fold is less than a page."""
    from chip_compile import kernel_vmem

    assert (FOLD_TOKENS, CHUNK_TOKENS) == ((32, 128, 512), 512)
    assert fold_pages(16) == (2, 8, 32)
    assert fold_pages(32) == (1, 4, 16)
    assert fold_pages(64) == (1, 2, 8)
    assert fold_pages(128) == (1, 4)
    assert fold_pages(1024) == (1,)

    def vmem(bs, s):
        pool = jax.ShapeDtypeStruct((2, 9, bs, 4, 128), jnp.bfloat16)
        q = jax.ShapeDtypeStruct((3, s, 32, 128), jnp.bfloat16)
        table = jax.ShapeDtypeStruct((3, 2048 // bs), jnp.int32)
        pos = jax.ShapeDtypeStruct((3, s), jnp.int32)
        kernel = paged_chunk_attention if s > 1 else kvcache._one_token
        traced = jax.jit(partial(kernel, scale=0.125)).trace(
            q, pool, pool, jax.ShapeDtypeStruct((), jnp.int32), table, pos)
        (found,) = kernel_vmem(traced).values()
        return found

    assert vmem(16, 1) == (None, (2, 32, 16, 4, 128))
    assert vmem(64, 1) == (None, (2, 8, 64, 4, 128))
    limit, block = vmem(16, 512)
    assert block == (2, 32, 16, 4, 128)
    assert vmem(64, 512) == (limit, (2, 8, 64, 4, 128))
    assert vmem(128, 512) == (limit, (2, 4, 128, 4, 128))
    assert vmem(1024, 512)[1] == (2, 1, 1024, 4, 128)
