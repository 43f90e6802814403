"""forward() with a paged cache against a plain per-layer reference.

forward carries the stacked pool through its layer scan and
ops/kvcache.py::paged_attention addresses each layer's rows at an
offset into the flat stack. The reference here does it the plain way, one
layer at a time with nothing taken from ops/kvcache.py: slice the layer out
of the pool, write each new row at (page, offset) with the block table read
on the host, gather whole pages, attend, stack the layers back.
Moving rows changes no value, so logits and pool must agree to the bit.
A pool of 64-wide heads is stored two to a row of 128 (`tiny-hd64`): the
reference keeps its rows a head each, and the bytes must still agree.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from substratus_tpu.models import llama
from substratus_tpu.ops import kvcache
from substratus_tpu.ops.attention import dot_product_attention
from substratus_tpu.ops.basics import rms_norm
from substratus_tpu.ops.quant import dequantize_kv, qeinsum, quantize_kv

PAGES, BS, M = 12, 4, 4  # pool pages (+ trash page 0), page size, table width


def _layer_attention(table, positions):
    """The reference's cache op for one layer, closed over the host's copy of
    the block table and positions: takes _block's call in place of
    paged_attention, with that layer's own rows as `layer_cache`."""

    def attention(layer_cache, layer, block_table, pos, q, k_new, v_new, dt,
                  kv_length=None):
        assert layer is None  # the reference hands _block one layer, no index
        new = {"k": k_new, "v": v_new}
        if "k_scale" in layer_cache:
            new["k"], new["k_scale"] = quantize_kv(k_new)
            new["v"], new["v_scale"] = quantize_kv(v_new)
        out = dict(layer_cache)
        for b, row in enumerate(positions):
            for s, p in enumerate(row):
                # Past the table's reach: the trash page, never a live one.
                page = table[b, p // BS] if p // BS < M else 0
                for name in out:
                    out[name] = out[name].at[page, p % BS].set(
                        new[name][b, s].astype(out[name].dtype)
                    )
        ctx = {
            name: a[table].reshape((len(table), M * BS) + a.shape[2:])
            for name, a in out.items()
        }
        k_ctx, v_ctx = ctx["k"], ctx["v"]
        if "k_scale" in out:
            k_ctx = dequantize_kv(k_ctx, ctx["k_scale"], dt)
            v_ctx = dequantize_kv(v_ctx, ctx["v_scale"], dt)
        return out, dot_product_attention(
            q, k_ctx, v_ctx, causal=True, q_positions=pos, kv_length=kv_length
        )

    return attention


def _reference_forward(params, tokens, cfg, positions, pool, table):
    """The pool goes through the layers the plain way: each layer's rows are
    sliced off the stack (the scan's xs) and the written rows are stacked
    back (its ys). The layers stay one scan so that both sides round their
    bfloat16 intermediates alike."""

    def layer(x, xs):
        x, kv, _ = llama._block(
            x, xs["lp"], jnp.asarray(positions), cfg, xs["cache"],
            block_table=jnp.asarray(table),
        )
        return x, kv

    x = params["tok_embed"][tokens]
    x, stacked = lax.scan(layer, x, {"lp": params["layers"], "cache": pool})
    x = rms_norm(x, params["out_norm"], cfg.norm_eps)
    logits = qeinsum("bsd,dv->bsv", x, params["lm_head"], cfg.dtype)
    return logits.astype(jnp.float32), stacked


def _random_pool(cfg, quantized, key):
    """A pool full of noise: a row gathered from the wrong place shows."""
    pool = llama.init_paged_cache(
        cfg, PAGES + 1, BS, dtype=jnp.int8 if quantized else None
    )
    out = {}
    for i, (name, a) in enumerate(sorted(pool.items())):
        k = jax.random.fold_in(key, i)
        if a.dtype == jnp.int8:
            out[name] = jax.random.randint(k, a.shape, -127, 128, jnp.int8)
        elif "scale" in name:
            out[name] = jax.random.uniform(k, a.shape, a.dtype, 0.01, 0.05)
        else:
            out[name] = jax.random.normal(k, a.shape, a.dtype)
    return out


# name -> (block table [B, M], positions [B, S], int8 KV)
CASES = {
    # One token a row at mixed positions; row 1 is an idle slot (a zero
    # table row), whose write lands on the trash page.
    "step": ([[3, 7, 1, 9], [0, 0, 0, 0], [5, 2, 0, 0]], [[13], [0], [6]], False),
    # A multi-token chunk through one block-table row, across a page edge.
    "chunk": ([[4, 11, 6, 0]], [[3, 4, 5, 6, 7, 8]], False),
    "int8": ([[3, 7, 1, 9], [12, 8, 0, 0], [5, 2, 0, 0]], [[13], [4], [6]], True),
    # A speculative verify at the context window's end: position 16 is past
    # the table's reach (M * BS) and must land on the trash page.
    "trash": ([[3, 7, 1, 9], [5, 2, 10, 0]], [[14, 15, 16], [2, 3, 4]], False),
}


HD64 = llama.CONFIGS["tiny"].replace(dim=256)  # 4 heads / 2 KV heads of 64


@pytest.mark.parametrize("case", list(CASES))
@pytest.mark.parametrize("config", ["tiny", "tiny-moe", "tiny-hd64"])
def test_paged_forward_matches_plain_per_layer_reference(
    config, case, monkeypatch
):
    cfg = HD64 if config == "tiny-hd64" else llama.CONFIGS[config]
    table, positions, quantized = CASES[case]
    table, positions = np.array(table, np.int32), np.array(positions, np.int32)
    params = llama.init_params(cfg, jax.random.key(1))
    pool = _random_pool(cfg, quantized, jax.random.key(2))
    packed = config == "tiny-hd64" and not quantized
    assert pool["k"].shape[3:] == (
        (1, 128) if packed else (cfg.n_kv_heads, cfg.head_size))
    tokens = jax.random.randint(
        jax.random.key(3), positions.shape, 0, cfg.vocab_size, jnp.int32
    )

    logits, out = llama.forward(
        params, tokens, cfg, positions=jnp.asarray(positions), cache=pool,
        block_table=jnp.asarray(table),
    )

    monkeypatch.setattr(
        kvcache, "paged_attention", _layer_attention(table, positions),
    )
    # the reference's rows are a KV head each, whatever row the pool stores
    want_logits, want = _reference_forward(
        params, tokens, cfg, positions,
        {name: a.reshape(a.shape[:3] + (cfg.n_kv_heads, -1))
         for name, a in pool.items()},
        table,
    )

    assert jax.tree.structure(out) == jax.tree.structure(pool)
    np.testing.assert_array_equal(np.asarray(logits), np.asarray(want_logits))
    for name in pool:
        assert out[name].dtype == pool[name].dtype, name
        assert out[name].shape == pool[name].shape, name
        np.testing.assert_array_equal(
            np.asarray(out[name]).reshape(want[name].shape),
            np.asarray(want[name]), err_msg=name,
        )
    if case == "trash":
        # Row 0's last write went to the trash page's slot 0 in every
        # layer; the page's other slots keep their noise.
        assert not np.array_equal(
            np.asarray(out["k"][:, 0, 0]), np.asarray(pool["k"][:, 0, 0])
        )
        np.testing.assert_array_equal(
            np.asarray(out["k"][:, 0, 1:]), np.asarray(pool["k"][:, 0, 1:])
        )


@pytest.mark.parametrize("kv_cache_dtype", ["model", "int8"])
def test_the_engine_serves_the_same_tokens_from_a_packed_pool(
    kv_cache_dtype, monkeypatch
):
    """Greedy tokens of a paged engine over heads of 64, its pool stored
    two heads to a row, equal those over the pool stored a head a row (the
    layout until PR 35, which an uneven split over devices still gets):
    the same bytes through the same gather on the CPU, so to the token. An
    int8 pool is stored as declared either way."""
    from substratus_tpu.serve.engine import Engine, EngineConfig

    params = llama.init_params(HD64, jax.random.key(0))
    toks = np.asarray(jax.random.randint(
        jax.random.key(3), (64,), 0, HD64.vocab_size))
    prompts = [[int(t) for t in p]
               for p in (toks[:37], toks[3:26], toks[40:49])]

    def serve():
        eng = Engine(HD64, params, EngineConfig(
            max_batch=4, max_seq_len=96, max_prefill_len=16, page_size=4,
            kv_cache_dtype=kv_cache_dtype))
        eng.start()
        try:
            return eng, [eng.generate(p, max_tokens=12, temperature=0.0)
                         for p in prompts]
        finally:
            eng.stop()

    eng, got = serve()
    packed = kv_cache_dtype == "model"
    assert eng.cache["k"].shape[3:] == ((1, 128) if packed else (2, 64))
    monkeypatch.setattr(kvcache, "kv_head_shards", lambda mesh: 3)
    eng, want = serve()
    assert eng.cache["k"].shape[3:] == (2, 64)
    assert got == want
