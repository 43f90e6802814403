"""ops/paged_attention.py (interpret mode) against the path it replaces in a
decode step, which is what ops/kvcache.py::paged_attention runs on the CPU:
the context of every table position gathered (`paged_read`) and
ops/attention.py::dot_product_attention over it. Then the whole engine, the
kernel forced in place of the gather, token for token.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from substratus_tpu.ops import kvcache
from substratus_tpu.ops.paged_attention import (
    FOLD_PAGES, paged_decode_attention,
)

BS, M, KH, HD, LAYERS = 16, 40, 2, 64, 3  # a table of 640 positions: two
PAGES = 1 + 4 * M                          # DMA blocks of FOLD_PAGES[-1]
FULL = M * BS
TOL = {jnp.bfloat16: 2e-2, jnp.float32: 1e-5}
assert FOLD_PAGES[-1] < M < 2 * FOLD_PAGES[-1]


def _normal(key, shape, dtype):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


def _case(dtype, group, lengths, layer=1, tables=None, seed=0):
    """A seeded pool, a step's q and new K/V rows for rows of `lengths`
    tokens (the new row is the last of them), and block tables of
    scattered pages unless given."""
    keys = jax.random.split(jax.random.key(seed), 6)
    b = len(lengths)
    shape = (LAYERS, PAGES, BS, KH, HD)
    pool = {"k": _normal(keys[0], shape, dtype),
            "v": _normal(keys[1], shape, dtype)}
    q = _normal(keys[2], (b, 1, KH * group, HD), dtype)
    k_new = _normal(keys[3], (b, 1, KH, HD), dtype)
    v_new = _normal(keys[4], (b, 1, KH, HD), dtype)
    if tables is None:
        tables = np.asarray(
            jax.random.permutation(keys[5], np.arange(1, PAGES))[: b * M]
        ).reshape(b, M)
    return (pool, jnp.int32(layer), jnp.asarray(tables, jnp.int32),
            jnp.asarray(lengths, jnp.int32)[:, None] - 1, q, k_new, v_new)


def _reference(pool, layer, table, positions, q, k_new, v_new):
    out, attn = kvcache.paged_attention(
        pool, layer, table, positions, q, k_new, v_new, q.dtype)
    return out, attn[:, 0]


def _kernel(pool, layer, table, positions, q):
    return paged_decode_attention(
        q[:, 0], pool["k"], pool["v"], layer, table, positions[:, 0],
        interpret=True)


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
        dtype, group, [length, 3 * BS + 5, FOLD_PAGES[-1] * BS + 1])
    out, want = _reference(pool, layer, table, pos, q, k_new, v_new)
    _close(_kernel(out, layer, table, pos, q), want, dtype)


@pytest.mark.parametrize("layer", [0, 1, LAYERS - 1])
def test_the_layer_is_an_offset_into_the_stack(layer):
    pool, _, table, pos, q, k_new, v_new = _case(
        jnp.bfloat16, 4, [37, FULL, 200], layer=layer, seed=layer)
    out, want = _reference(pool, jnp.int32(layer), table, pos, q, k_new,
                           v_new)
    _close(_kernel(out, jnp.int32(layer), table, pos, q), want, jnp.bfloat16)
    # and no other layer's rows would have given the same
    other = jnp.int32((layer + 1) % LAYERS)
    assert not np.allclose(
        np.asarray(_kernel(out, other, table, pos, q), np.float32),
        np.asarray(want, np.float32), atol=TOL[jnp.bfloat16])


def test_scattered_pages_and_a_prefix_two_rows_share():
    """Rows 0 and 1 hold the same first five pages (a prefix hit) and
    their own after them; every page lies somewhere else in the pool."""
    tables = np.zeros((3, M), np.int32)
    order = np.random.default_rng(3).permutation(np.arange(1, PAGES))
    tables[0, :9] = order[:9]
    tables[1, :5] = order[:5]
    tables[1, 5:12] = order[20:27]
    tables[2] = order[40:40 + M][::-1]
    lengths = [9 * BS - 3, 12 * BS, FULL - 7]
    pool, layer, table, pos, q, k_new, v_new = _case(
        jnp.bfloat16, 4, lengths, tables=tables)
    out, want = _reference(pool, layer, table, pos, q, k_new, v_new)
    _close(_kernel(out, layer, table, pos, q), want, jnp.bfloat16)


@pytest.mark.parametrize("garbage", [float("nan"), 3e37, -3e37],
                         ids=["nan", "huge", "-huge"])
def test_a_row_sees_its_own_pages_up_to_its_own_position(garbage):
    """An idle row as the engine leaves it (position 0, a table of the
    trash page: it costs the one page its own write landed on), a row of
    one page and a row of a full table, in a pool where every position no
    row may see holds garbage: other layers, pages nobody owns, the rest
    of each row's last page, and the idle row's table past its first
    entry pointing at pages of garbage too. The answer is the clean
    pool's."""
    lengths = [1, BS - 4, FULL]
    pool, layer, table, pos, q, k_new, v_new = _case(jnp.bfloat16, 8, lengths)
    table = table.at[0].set(0)
    clean, want = _reference(pool, layer, table, pos, q, k_new, v_new)
    seen = np.zeros((LAYERS, PAGES, BS), bool)
    for row, n in enumerate(lengths):
        for p in range(n):
            seen[1, int(table[row, p // BS]), p % BS] = True
    dirty = {
        name: jnp.where(seen[..., None, None], a, garbage).astype(a.dtype)
        for name, a in clean.items()
    }
    # the idle row's stale entries lead to garbage, which it never reads
    unowned = [p for p in range(1, PAGES) if not seen[1, p].any()]
    stale = table.at[0, 1:].set(jnp.asarray(unowned[: M - 1], jnp.int32))
    got = _kernel(dirty, layer, stale, pos, q)
    assert np.isfinite(np.asarray(got, np.float32)).all()
    _close(got, want, jnp.bfloat16)
    # the other rows' answers are theirs whatever the idle row is
    alone = _kernel(dirty, layer, stale[1:], pos[1:], q[1:])
    np.testing.assert_array_equal(
        np.asarray(got[1:], np.float32), np.asarray(alone, np.float32))


def _force_the_kernel(monkeypatch):
    """On the CPU the platform choice (ops/kvcache.py::paged_attention)
    takes the gather; a test steers it to the kernel, interpreted."""
    monkeypatch.setattr(
        jax.lax, "platform_dependent",
        lambda *args, tpu, default: tpu(*args))


def _greedy(model, cfg, params, prompts, max_tokens, **ec):
    from substratus_tpu.serve.engine import Engine, EngineConfig, Request

    eng = Engine(cfg, params, EngineConfig(**ec), model=model)
    eng.start()
    reqs = [eng.submit(Request(prompt_tokens=[int(t) for t in p],
                               max_tokens=max_tokens, temperature=0.0,
                               eos_token_id=-1)) for p in prompts]
    outs = []
    for r in reqs:
        ids = []
        while (t := r.out.get(timeout=600)) is not None:
            ids.append(t)
        outs.append(ids)
    eng.stop()
    assert eng.error is None
    return outs, eng


@pytest.mark.parametrize("family", ["llama", "exaone_moe"])
def test_the_engine_serves_the_same_tokens_through_the_kernel(
    family, monkeypatch, pallas_interpret
):
    """Greedy tokens of a tiny paged engine, decode steps through the
    kernel, equal those of the gather path: three requests of unlike
    lengths over four slots, so one row idles throughout. (The prompts'
    seed matters: on random weights two logits now and then lie within one
    bfloat16 rounding of an attention output, and of twelve seeded sets two
    flipped one request's token there.)"""
    from substratus_tpu.models import exaone_moe, llama

    if family == "llama":
        model, cfg = llama, llama.CONFIGS["tiny"]
    else:
        model, cfg = exaone_moe, exaone_moe.CONFIGS["tiny-exaone-moe"]
    assert cfg.dtype == jnp.bfloat16
    params = model.init_params(cfg, jax.random.key(0))
    toks = np.asarray(jax.random.randint(
        jax.random.key(3), (64,), 0, cfg.vocab_size))
    prompts = [toks[:37], toks[3:26], toks[40:49]]
    ec = dict(max_batch=4, max_seq_len=96, max_prefill_len=16, page_size=4)
    want, _ = _greedy(model, cfg, params, prompts, 12, **ec)
    _force_the_kernel(monkeypatch)
    got, eng = _greedy(model, cfg, params, prompts, 12, **ec)
    assert got == want
    assert all(len(ids) == 12 for ids in got)
    assert (eng.positions[~eng.active] == 0).all()
