"""The docqa and repoqa cells' programs (models/deepseek_v3.py, plain and under
a learned index), compiled by the chip's own compiler with no chip
(tests/test_chip_compile.py says how): the latent kernels and the index
kernels at the published widths, the pool in place.
"""
import math
import re

import jax
import jax.numpy as jnp
import pytest

from chip_compile import (
    CHUNK, described, kernel_vmem, pool_moving_ops,
    sorts_only_where_a_row_samples,
)


# The docqa cell's engine (benchmarks/traffic/docqa.json): the language
# model of dots.vlm1.inst, its first 16 layers (3 dense + 13 sparse), 8 of
# 256 experts held, an eighth of the vocabulary; given no page, as the
# benchmark's harness gives none: the family's.
_D_B, _D_S, _D_POOL_TOKENS = 12, 14336, 163840


def test_deepseek_v3_programs_compile_with_both_latent_kernels(v5e):
    """The family whose pages hold one latent row a token for all heads:
    decode and the 512-token chunk compile for the chip at the published
    widths; the step holds the absorbed kernel and the chunk the expanded
    one (ops/latent_attention.py), neither gathers a context, no op moves
    the pool or a layer of it, the chunk holds no context expanded in HBM
    (at 14k tokens one layer's keys and values are 0.94 GB: no result is
    that large, and the program's temporaries stay under one layer's W_O
    beside 13 GB of weights and pool), and the chunk groups its tokens by
    expert. At the family's page of 128 tokens the kernels' blocks hold the
    tokens they held at 16 (1,024 a decode block, 512 keys a chunk block)
    and ask for the VMEM they asked for."""
    from substratus_tpu.models import deepseek_v3
    from substratus_tpu.ops.quant import quantize_params
    from substratus_tpu.serve.engine import Engine, EngineConfig

    cfg = deepseek_v3.DeepseekV3Config(
        n_layers=16, vocab_size=16160, held_experts=(0, 8))
    assert deepseek_v3.layer_plan(cfg) == (3, 1, 13)
    eng = Engine(cfg, None, EngineConfig(
        max_batch=_D_B, max_seq_len=_D_S, max_prefill_len=CHUNK,
        kv_pool_tokens=1,
    ))
    assert not eng.slot_state and eng.prefix is not None
    page = eng.page_size
    assert page == deepseek_v3.PAGE_TOKENS == 128
    pool_pages = _D_POOL_TOKENS // page
    placed, arr = described(v5e, eng)
    params = placed(jax.eval_shape(
        lambda key: quantize_params(
            deepseek_v3.init_params(cfg, key),
            deepseek_v3.quant_contracting(cfg)),
        jax.random.key(0)), deepseek_v3.param_logical_axes(cfg))
    cache = placed(jax.eval_shape(
        lambda: deepseek_v3.init_paged_cache(cfg, pool_pages + 1, page)),
        deepseek_v3.paged_cache_logical_axes(cfg))
    # one row of 576 a token and layer, stored 640 wide; no second pool
    assert cache["k"].shape == (16, pool_pages + 1, page, 1, 640)
    assert cache["v"].shape[0] == 0
    m = _D_S // page
    assert eng.block_table.shape == (_D_B, m)
    programs = {
        "decode": eng._decode_fn.trace(
            params, cache, arr((_D_B, m)), arr((_D_B,)), arr((_D_B,)),
            arr((_D_B,), jnp.float32), arr((_D_B,), jnp.float32),
            arr(eng.key.shape, eng.key.dtype), None, None,
            arr((_D_B,), jnp.bool_),
        ),
        "chunk": Engine._chunk_prefill_jit.trace(
            deepseek_v3, cfg, params, cache, arr((1, CHUNK)), arr(()),
            arr(()), arr((1, m)), None, None, arr(()),
        ),
    }
    kernels = {"decode": "latent_decode_attention",
               "chunk": "latent_chunk_attention"}
    # two DMA blocks of 1,024 tokens (2.6 MB) and 2 MB of scores beside q
    # and the output; two of 512 keys, 8 heads' weights and a fold's scores
    vmem = {"decode": (17039360, (2, 1024 // page, page, 640)),
            "chunk": (25165824, (2, 512 // page, page, 640))}
    pool = {cache["k"].size, cache["k"].size // 16}  # whole, or a layer
    expanded_layer = _D_S * cfg.n_heads * 256  # one layer's K and V, whole
    for name, traced in programs.items():
        assert kernel_vmem(traced) == {kernels[name]: vmem[name]}, name
        compiled = traced.lower().compile()
        hlo = compiled.as_text()
        assert re.search(
            r'custom_call_target="tpu_custom_call".*' + kernels[name], hlo
        ), name
        other = kernels["chunk" if name == "decode" else "decode"]
        assert other not in hlo, name
        assert "kv.gather" not in hlo, name
        assert ("attn.absorb" in hlo) == (name == "decode"), name
        assert ("attn.expand" in hlo) == (name == "chunk"), name
        bf16 = "\n".join(l for l in hlo.splitlines() if "= bf16[" in l)
        assert pool_moving_ops(bf16, pool) == [], name
        # no float result as large as one layer's expanded context
        for mm in re.finditer(r"= (?:bf16|f32)\[([\d,]+)\]\S* [\w-]+\(", hlo):
            n = math.prod(map(int, mm.group(1).split(",")))
            assert n < expanded_layer or n in pool, (name, mm.group(0))
        # under one layer's W_O (117 MB): no weight is written out anew
        temp = compiled.memory_analysis().temp_size_in_bytes
        assert temp < 1.1e8, (name, temp)
        if name == "decode":
            # nor a slice of W_UQ (its leaves lie a head apart: as [H dn,
            # rq] the step copied the layer's 25 MB + 12.6 MB out of the
            # stack, every layer, and held 62 MB of temporaries)
            assert temp < 4.5e7, (name, temp)
        if name == "chunk":
            assert "moe.experts/while" in hlo


# The repoqa cell's engine (benchmarks/traffic/repoqa.json): GLM-5's first
# 13 layers (3 dense + 10 sparse), 8 of 256 experts held, an eighth of the
# vocabulary, a learned index in every layer.
_G_B, _G_S, _G_POOL_TOKENS = 4, 18432, 73728


@pytest.mark.slow  # a minute; the two kernel cases above stay in tier-1
def test_glm_dsa_programs_compile_with_the_index_kernels(v5e):
    """The same family under a learned index (GLM-5's widths: 64 heads of
    192 + 64 against 256, 32 index heads over keys of 128, the 2,048 best
    rows a query): the pool's second array holds the index keys under the
    same page ids; the decode program scores them in place
    (`index_decode_scores`), takes each slot's set by a threshold and a
    compaction over the scores held in VMEM (`index_select_rows`: no sort
    but the sampler's) and gathers the picked rows by position, and holds
    no kernel that walks a row's pages of latents; the 512 chunk scores by
    `index_chunk_scores` and runs the expanded kernel under the sets;
    neither moves either array of the pool. The engine is given no page
    and takes the family's 128 tokens: a block of the keys' copies holds
    1,024 tokens in 8 pages."""
    from substratus_tpu.models import deepseek_v3
    from substratus_tpu.ops.quant import quantize_params
    from substratus_tpu.serve.engine import Engine, EngineConfig

    cfg = deepseek_v3.CONFIGS["glm-5"].replace(
        n_layers=13, vocab_size=19360, held_experts=(0, 8))
    assert deepseek_v3.layer_plan(cfg) == (3, 1, 10)
    eng = Engine(cfg, None, EngineConfig(
        max_batch=_G_B, max_seq_len=_G_S, max_prefill_len=CHUNK,
        kv_pool_tokens=1,
    ))
    assert not eng.slot_state and eng.prefix is not None
    assert "dsa_selections" in eng.stats
    page = eng.page_size
    assert page == deepseek_v3.PAGE_TOKENS == 128
    pool_pages = _G_POOL_TOKENS // page
    placed, arr = described(v5e, eng)
    params = placed(jax.eval_shape(
        lambda key: quantize_params(
            deepseek_v3.init_params(cfg, key),
            deepseek_v3.quant_contracting(cfg)),
        jax.random.key(0)), deepseek_v3.param_logical_axes(cfg))
    cache = placed(jax.eval_shape(
        lambda: deepseek_v3.init_paged_cache(cfg, pool_pages + 1, page)),
        deepseek_v3.paged_cache_logical_axes(cfg))
    assert cache["k"].shape == (13, pool_pages + 1, page, 1, 640)
    assert cache["v"].shape == (13, pool_pages + 1, page, 1, 128)
    m = _G_S // page
    programs = {
        "decode": eng._decode_fn.trace(
            params, cache, arr((_G_B, m)), arr((_G_B,)), arr((_G_B,)),
            arr((_G_B,), jnp.float32), arr((_G_B,), jnp.float32),
            arr(eng.key.shape, eng.key.dtype), None, None,
            arr((_G_B,), jnp.bool_),
        ),
        "chunk": Engine._chunk_prefill_jit.trace(
            deepseek_v3, cfg, params, cache, arr((1, CHUNK)), arr(()),
            arr(()), arr((1, m)), None, None, arr(()),
        ),
    }
    kernels = {"decode": ("index_decode_scores", "index_select_rows"),
               "chunk": ("index_chunk_scores", "latent_chunk_attention")}
    pool = set()
    for a in (cache["k"], cache["v"]):
        pool |= {a.size, a.size // 13}  # whole, or a layer
    for name, traced in programs.items():
        vmem = kernel_vmem(traced)
        assert set(vmem) == set(kernels[name]), name
        if name == "decode":
            # two blocks of 1,024 keys (0.5 MB) under the default limit
            assert vmem["index_decode_scores"] == (
                None, (2, 1024 // page, page, 128))
            # the four slots' scores as ordered keys (0.3 MB), one slot's
            # one-hot and running counts (2 MB) under the default limit
            assert vmem["index_select_rows"] == (None, (_G_B, m, page))
        else:
            # what it asked for at 16 tokens a page: two blocks of 512
            # keys and of the bias, 8 heads' weights, a fold's scores
            assert vmem["latent_chunk_attention"] == (
                35389440, (2, 512 // page, page, 640))
        compiled = traced.lower().compile()
        hlo = compiled.as_text()
        for kernel in kernels[name]:
            assert re.search(
                r'custom_call_target="tpu_custom_call".*' + kernel, hlo
            ), (name, kernel)
        assert "latent_decode_attention" not in hlo, name
        assert "attn.index" in hlo and "attn.select" in hlo, name
        assert ("kv.gather" in hlo) == False, name  # noqa: E712
        bf16 = "\n".join(l for l in hlo.splitlines() if "= bf16[" in l)
        assert pool_moving_ops(bf16, pool) == [], name
        temp = compiled.memory_analysis().temp_size_in_bytes
        assert temp < 1.6e8, (name, temp)
        # no selection sorts: the step's only sort is the sampler's, in
        # its sampled branch
        assert sorts_only_where_a_row_samples(hlo) == (name == "decode")
        assert "sort(" not in "\n".join(
            l for l in hlo.splitlines() if "attn.select" in l), name
