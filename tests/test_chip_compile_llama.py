"""The paged serving programs of models/llama.py, compiled by the chip's own
compiler with no chip (tests/test_chip_compile.py says how): the chat cell's
Mistral-7B engine on one described chip and under a `tensor` mesh, and what
`serve.main --config tinyllama-1.1b` compiles at its defaults. The optimized
HLO of the decode and chunk programs may not copy, slice or re-stack the pool
(models/llama.py::forward carries it in place), and over a bfloat16 pool they
hold the paged-attention kernels, no gathered context and no scores in HBM
(ops/kvcache.py), at a head width of 64 too, where the pool stores two KV
heads to a row of 128.
"""
import math

import jax
import jax.numpy as jnp
import pytest

from chip_compile import (
    CHUNK, KERNEL, PAGE, described, pool_moving_ops, reads_pages_in_place,
    sorts_only_where_a_row_samples, weights_laid_out_anew,
)


# The chat cell's engine (benchmarks/traffic/chat.json): Mistral-7B, int8
# weights, whole depth (the layers are one scan: depth costs no compile time).
_POOL_PAGES, _B, _S = 1792, 32, 2048


@pytest.mark.parametrize(
    "kv_cache_dtype,tensor,door",
    [("model", 1, True), ("int8", 1, True), ("model", 4, True),
     ("model", 1, False)],
    ids=["bf16", "int8kv", "bf16-tensor4", "bf16-published"],
)
def test_serving_programs_leave_the_kv_pool_in_place(
    kv_cache_dtype, tensor, door, v5e
):
    """decode and the 512-token chunk, for one described chip and for the
    four under a `tensor` mesh (pool sharded over kv_heads): no pool- or
    layer-of-pool-sized copy or slice, and temporaries under half a pool.

    The programs are lowered over the tree the engine's door returns
    (models/llama.py::serving_layout: the int8 q, k and v stacks heads
    first, contracted dim last), and on one chip no layer of a projection
    leaf is written anywhere before its dot reads it. `bf16-published`
    lowers the tree as `init_params` lays it out, without the door: there
    the decode step stages the three slices in VMEM
    (`constant_dynamic-slice_fusion`), which shows that the helper sees
    what it guards (1.2 ms of a 12.1 ms step on the chip: PERF.md section
    6, PR 41)."""
    from substratus_tpu.models import llama
    from substratus_tpu.ops.quant import quantize_params
    from substratus_tpu.serve.engine import Engine, EngineConfig

    cfg = llama.LlamaConfig(
        vocab_size=32000, dim=4096, n_layers=32, n_heads=32, n_kv_heads=8,
        hidden_dim=14336, rope_theta=1e6, max_seq_len=32768,
    )
    # The engine itself is built on the CPU with the smallest pool it takes
    # (nothing can be placed on a described device); its jitted programs are
    # then lowered for the described chips at the cell's shapes.
    eng = Engine(cfg, None, EngineConfig(
        max_batch=_B, max_seq_len=_S, max_prefill_len=CHUNK,
        kv_cache_dtype=kv_cache_dtype, page_size=PAGE, kv_pool_tokens=1,
    ))
    quantized = kv_cache_dtype == "int8"
    params = jax.eval_shape(
        lambda key: quantize_params(
            llama.init_params(cfg, key), llama.quant_contracting(cfg)
        ),
        jax.random.key(0),
    )
    pool = jax.eval_shape(
        lambda: llama.init_paged_cache(
            cfg, _POOL_PAGES + 1, PAGE,
            dtype=jnp.int8 if quantized else None,
        )
    )
    if door:
        params = jax.eval_shape(
            lambda tree: llama.serving_layout(tree, cfg), params)
        assert params["layers"]["wq"].q.shape == (32, 32, 128, 4096)
        assert params["layers"]["wk"].scale.shape == (32, 8, 128, 1)
    placed, arr = described(v5e, eng, tensor=tensor)
    params = placed(params, llama.serving_logical_axes(params, cfg))
    pool = placed(pool, llama.paged_cache_logical_axes(cfg, quantized))
    m = _S // PAGE
    programs = {
        "decode": eng._decode_fn.lower(
            params, pool, arr((_B, m)), arr((_B,)), arr((_B,)),
            arr((_B,), jnp.float32), arr((_B,), jnp.float32),
            arr(eng.key.shape, eng.key.dtype),
        ),
        "chunk": Engine._chunk_prefill_jit.lower(
            llama, cfg, params, pool, arr((1, CHUNK)), arr(()), arr(()),
            arr((1, m)),
        ),
    }
    # One layer of each projection leaf, per device (heads, kv_heads and
    # mlp are the sharded dims).
    layer_of = {
        math.prod(w.q.sharding.shard_shape(w.q.shape)) // cfg.n_layers
        for w in params["layers"].values() if hasattr(w, "q")}
    # Elements per device of each pool array and of one layer of it. The
    # int8 pool's f32 scales [L, P, bs, KH, 1] are the exception the test
    # records: the compiler gives that shape a pages-minor layout and lays
    # the whole array out anew on the way in and out (1/32 of the pool's
    # bytes each), so only a per-layer slice of them is refused.
    sizes = set()
    for name, s in pool.items():
        n = math.prod(s.sharding.shard_shape(s.shape))
        sizes |= {n // cfg.n_layers} | (set() if "scale" in name else {n})
    # Temporaries stay under half a pool; an int8 pool is half the bytes and
    # its step also holds K and V of max_batch x max_seq_len dequantized in
    # f32 (ops/quant.py::dequantize_kv), which is no part of the pool.
    limit = sum(s.dtype.itemsize * s.size for s in pool.values()) / 2
    if quantized:
        limit += 2 * 4 * _B * _S * cfg.n_kv_heads * cfg.head_size
    rows = {"decode": _B, "chunk": 1}
    for name, lowered in programs.items():
        compiled = lowered.compile()
        hlo = compiled.as_text()
        assert pool_moving_ops(hlo, sizes) == [], name
        temp = compiled.memory_analysis().temp_size_in_bytes
        assert temp < limit / tensor, (name, temp, limit)
        # A bfloat16 pool is read in place, live pages only, by a decode
        # step and by a chunk (under the `tensor` mesh each chip reads its
        # own KV heads): no gathered K or V of rows x max_seq_len, no
        # float32 scores of S x max_seq_len. An int8 pool gathers every
        # table position (ops/kvcache.py).
        scores = cfg.n_heads * CHUNK * _S if name == "chunk" else 0
        in_place = reads_pages_in_place(
            hlo, KERNEL[name], rows[name], _S, cfg.n_kv_heads // tensor,
            cfg.head_size, scores // tensor)
        assert in_place == (not quantized), name
        assert ("kv.gather" in hlo) == quantized, name
        assert sorts_only_where_a_row_samples(hlo) == (name == "decode")
        anew = weights_laid_out_anew(hlo, layer_of)
        if not door:
            if name == "decode":
                assert sum("constant_dynamic-slice_fusion" in op
                           and "S(1)" in op for op in anew) == 3, anew
        elif (kv_cache_dtype, tensor) == ("model", 1):
            assert anew == [], (name, anew)
        else:  # recorded, not refused
            print(f"{kv_cache_dtype} tensor={tensor} {name}: {anew}")


@pytest.mark.parametrize("program", ["decode", "chunk"])
def test_tinyllama_paged_programs_compile_for_v5e(program, v5e):
    """What `serve.main --config tinyllama-1.1b` compiles on a TPU at its
    defaults (8 slots of 1,024, chunks of 512, the paged layout): 4 KV
    heads of 64 are two stored rows of 128 a token, and both programs read
    them in place (S3c: from PR 28 to PR 30 the decode program held a
    kernel over a 64-wide row, which Mosaic refuses, and chip_smoke.py's
    serve phases answered 500)."""
    from substratus_tpu.models import llama
    from substratus_tpu.serve.engine import Engine, EngineConfig

    cfg = llama.CONFIGS["tinyllama-1.1b"]
    b, s = 8, 1024
    eng = Engine(cfg, None, EngineConfig(
        max_batch=b, max_seq_len=s, max_prefill_len=CHUNK, page_size=PAGE,
        kv_pool_tokens=1,
    ))
    assert eng.paged
    placed, arr = described(v5e, eng)
    params = placed(
        jax.eval_shape(lambda key: llama.init_params(cfg, key),
                       jax.random.key(0)),
        llama.param_logical_axes(cfg),
    )
    pool = placed(
        jax.eval_shape(
            lambda: llama.init_paged_cache(cfg, b * s // PAGE + 1, PAGE)),
        llama.paged_cache_logical_axes(cfg, False),
    )
    m = s // PAGE
    if program == "decode":
        lowered = eng._decode_fn.lower(
            params, pool, arr((b, m)), arr((b,)), arr((b,)),
            arr((b,), jnp.float32), arr((b,), jnp.float32),
            arr(eng.key.shape, eng.key.dtype),
        )
    else:
        lowered = Engine._chunk_prefill_jit.lower(
            llama, cfg, params, pool, arr((1, CHUNK)), arr(()), arr(()),
            arr((1, m)),
        )
    hlo = lowered.compile().as_text()
    assert pool["k"].shape[3:] == (2, 128)
    assert reads_pages_in_place(
        hlo, KERNEL[program], b if program == "decode" else 1, s,
        cfg.n_kv_heads, cfg.head_size,
        cfg.n_heads * CHUNK * s if program == "chunk" else 0)
    assert "kv.gather" not in hlo
    assert pool_moving_ops(
        hlo, {pool["k"].size, pool["k"].size // cfg.n_layers}) == []
