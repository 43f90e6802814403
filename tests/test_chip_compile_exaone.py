"""The reason-mixed cell's programs (models/exaone_moe.py), compiled by the
chip's own compiler with no chip (tests/test_chip_compile.py says how): the
pool and the window layers' rings stay in place.
"""
import re

import jax
import jax.numpy as jnp

from chip_compile import (
    CHUNK, KERNEL, PAGE, pool_moving_ops, reads_pages_in_place,
    sorts_only_where_a_row_samples, weights_laid_out_anew,
)


# The reason-mixed cell's engine (benchmarks/traffic/reason-mixed.json):
# K-EXAONE's first 12 layers, 16 of 128 experts, an eighth of the vocabulary.
_X_POOL_PAGES, _X_B, _X_S = 10240, 64, 4096


def test_exaone_programs_leave_pool_and_rings_in_place(v5e):
    """The same rule for the family whose cache holds two kinds of history:
    decode and the 512-token chunk move neither the global layers' pool nor
    the window layers' rings, whole or a layer of them; the pool is 12 KB a
    token (3 global layers of 12), not 48; the chunk groups its tokens by
    expert (a loop over blocks, no product with every held expert)."""
    from jax.sharding import SingleDeviceSharding

    from substratus_tpu.models import exaone_moe
    from substratus_tpu.ops.quant import quantize_params
    from substratus_tpu.serve.engine import Engine, EngineConfig

    cfg = exaone_moe.ExaoneMoeConfig(
        vocab_size=19200, n_layers=12, held_experts=(0, 16))
    eng = Engine(cfg, None, EngineConfig(
        max_batch=_X_B, max_seq_len=_X_S, max_prefill_len=CHUNK,
        page_size=PAGE, kv_pool_tokens=1,
    ))
    assert eng.slot_state and eng.prefix is None
    rep = SingleDeviceSharding(v5e[0])

    def placed(tree):
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=rep),
            tree)

    def arr(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=rep)

    params = placed(jax.eval_shape(
        lambda key: quantize_params(
            exaone_moe.init_params(cfg, key),
            exaone_moe.quant_contracting(cfg)),
        jax.random.key(0)))
    cache = placed(jax.eval_shape(
        lambda: exaone_moe.init_paged_cache(
            cfg, _X_POOL_PAGES + 1, PAGE, slots=_X_B)))
    tokens = (_X_POOL_PAGES + 1) * PAGE
    pool_bytes = sum(cache[n].size * cache[n].dtype.itemsize for n in "kv")
    assert pool_bytes == tokens * 12 * 1024
    assert cache["wk"].shape == (9, _X_B, 128, 8, 128)
    m = _X_S // PAGE
    programs = {
        "decode": eng._decode_fn.lower(
            params, cache, arr((_X_B, m)), arr((_X_B,)), arr((_X_B,)),
            arr((_X_B,), jnp.float32), arr((_X_B,), jnp.float32),
            arr(eng.key.shape, eng.key.dtype), None, None,
            arr((_X_B,), jnp.bool_),
        ),
        "chunk": Engine._chunk_prefill_jit.lower(
            exaone_moe, cfg, params, cache, arr((1, CHUNK)), arr(()), arr(()),
            arr((1, m)), None, None, arr(()),
        ),
    }
    # Refused: any bfloat16 copy or slice the size of the pool, of a layer
    # of it, or of the rings; and a slice the size of one layer's rings. A
    # *copy* of that last size is the step's own read of the rings
    # ([max_batch, W, KH, hd], by construction as large as a layer of them)
    # laid out for the dot, as the pool's gathered context is: not refused.
    # And no int8 weight is laid out anew: stored [D, heads, hd] or [D,
    # heads * hd], the q, k and v stacks of every layer were copied in each
    # program, contracted dim last (2.9 ms of a decode step: PERF.md
    # section 6, PR 27); they are stored that way now.
    whole = {cache[n].size for n in ("k", "wk")} | {
        cache["k"].size // cache["k"].shape[0]}
    ring_layer = {cache["wk"].size // cache["wk"].shape[0]}
    limit = sum(s.dtype.itemsize * s.size for s in cache.values()) / 2
    for name, lowered in programs.items():
        compiled = lowered.compile()
        hlo = compiled.as_text()
        bf16 = "\n".join(l for l in hlo.splitlines() if "= bf16[" in l)
        assert pool_moving_ops(bf16, whole) == [], name
        assert [op for op in pool_moving_ops(bf16, ring_layer)
                if "copy" not in op] == [], name
        assert not re.search(r"= s8\[[\d,]+\]\S* copy\(", hlo), name
        temp = compiled.memory_analysis().temp_size_in_bytes
        assert temp < limit, (name, temp, limit)
        for scope in ("kv.ring", "attn.window", "moe.shared", "moe.router",
                      "moe.experts", "attn.core"):
            assert scope in hlo, (name, scope)
        assert sorts_only_where_a_row_samples(hlo) == (name == "decode")
        # the global layers of the step and of the chunk read live pages
        # in place: no gather, no K or V of rows x max_seq_len, no float32
        # scores of 512 x max_seq_len
        rows = _X_B if name == "decode" else 1
        assert reads_pages_in_place(
            hlo, KERNEL[name], rows, _X_S, cfg.n_kv_heads, cfg.head_size,
            cfg.n_heads * CHUNK * _X_S if name == "chunk" else 0), name
        assert "kv.gather" not in hlo, name
        # the chunk multiplies pairs grouped by expert, one block of one
        # expert's rows at a time; the decode step every held expert
        grouped = "s8[1,1,6144,2048]" in hlo
        assert grouped == (name == "chunk"), name
        # no layer of a projection stack is written anywhere before its
        # dot reads it, in the scan's body or in the head of four layers:
        # `forward` views the stacks [L, heads, hd, D] before it slices
        # them (flat, the body held `constant_dynamic-slice_fusion.58`,
        # three s8[1,8192,6144] a period, and the head the same: 1.9 ms of
        # a 16.5 ms step) and hands the slices an index the compiler cannot
        # fold (folded, layer 0's four slices stayed plain copies in
        # `main`: s8[1,8192,6144] x 2, s8[1,1024,6144] x 2, 113 MB a step)
        layer_of = {w.q.size // cfg.n_layers
                    for w in params["layers"].values() if hasattr(w, "q")}
        assert layer_of == {8192 * 6144, 1024 * 6144}
        assert weights_laid_out_anew(hlo, layer_of) == [], name
