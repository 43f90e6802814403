"""The rag cell's programs (models/granitemoehybrid.py), compiled by the
chip's own compiler with no chip (tests/test_chip_compile.py says how):
Granite-4.0-H-Micro whole, the state-space state in place under its kernel,
the convolution rows in place, heads of 64 two to a pool row under the
paged kernels.
"""
import re

import jax
import jax.numpy as jnp
import pytest

from chip_compile import (
    CHUNK, KERNEL, described, pool_moving_ops, reads_pages_in_place,
    region_ops, sorts_only_where_a_row_samples, weights_laid_out_anew,
)

# The rag cell's engine (benchmarks/traffic/rag.json): all 40 layers, 48
# slots of 9,216, every slot's pages whole, in the family's own pages of 128
# tokens (the harness gives no page_size: models/granitemoehybrid.py::
# PAGE_TOKENS).
_G_B, _G_S, PAGE = 48, 9216, 128
_G_PAGES = _G_B * _G_S // PAGE


def _granite_programs(v5e):
    """(lowered decode, lowered 512 chunk, cache shapes) of the rag cell's
    engine for one described chip."""
    from substratus_tpu.models import granitemoehybrid as M
    from substratus_tpu.ops.quant import quantize_params
    from substratus_tpu.serve.engine import Engine, EngineConfig

    cfg = M.GraniteHybridConfig(dt_shift=-5.3)
    assert (cfg.count(M.MAMBA), cfg.count(M.ATTN)) == (36, 4)
    eng = Engine(cfg, None, EngineConfig(
        max_batch=_G_B, max_seq_len=_G_S, max_prefill_len=CHUNK,
        kv_pool_tokens=1,
    ))
    assert eng.slot_state and eng.prefix is None and eng._page_layers == 4
    assert eng.page_size == M.PAGE_TOKENS == PAGE
    assert eng.block_table.shape == (_G_B, 72)
    placed, arr = described(v5e, eng)
    params = placed(jax.eval_shape(
        lambda key: quantize_params(
            M.init_params(cfg, key), M.quant_contracting(cfg)),
        jax.random.key(0)), M.param_logical_axes(cfg))
    cache = placed(jax.eval_shape(
        lambda: M.init_paged_cache(cfg, _G_PAGES + 1, PAGE, slots=_G_B)),
        M.paged_cache_logical_axes(cfg))
    m = _G_S // PAGE
    decode = eng._decode_fn.lower(
        params, cache, arr((_G_B, m)), arr((_G_B,)), arr((_G_B,)),
        arr((_G_B,), jnp.float32), arr((_G_B,), jnp.float32),
        arr(eng.key.shape, eng.key.dtype), None, None,
        arr((_G_B,), jnp.bool_),
    )
    chunk = Engine._chunk_prefill_jit.lower(
        M, cfg, params, cache, arr((1, CHUNK)), arr(()), arr(()),
        arr((1, m)), None, None, arr(()),
    )
    return decode, chunk, cache


@pytest.fixture(scope="module")
def granite(v5e):
    """({"decode" | "chunk": the program compiled for one described chip},
    the cache's shapes): compiled once for the tests of this file."""
    decode, chunk, cache = _granite_programs(v5e)
    return {"decode": decode.compile(), "chunk": chunk.compile()}, cache


def _state_kernel_calls(hlo: str) -> int:
    return len(re.findall(
        r'custom_call_target="tpu_custom_call".*ssm_state_step', hlo))


def test_granite_programs_compile_and_leave_three_histories_in_place(granite):
    """The family whose cache holds pages, convolution rows and a float32
    state a slot: decode (48 slots) and the 512-token chunk compile for the
    chip at the published widths, whole depth. The 3.62 GB of state is the
    layer scan's carry, read where it lies and written where it lies: the
    decode step moves `S` through ops/ssd_kernel.py, one call in the scan's
    body for each of a period's nine Mamba layers, in the region the
    benchmark reads, and nothing else of the program has an operand or a
    result of a layer's slab of it (in XLA a reduction and a loop fusion
    read it twice). The chunk holds no such kernel. Both read the live
    pages in place through the paged kernels (heads of 64 two to a stored
    row), move neither the pool nor the rows nor the state whole or a layer
    at a time (the rows lie a slot's three end to end: as [36, 48, 3, 4352]
    the compiler padded, packed and unpacked the whole stack around every
    layer, ops/kvcache.py::conv_rows_read_and_update), and lay no large
    int8 weight out anew."""
    programs, cache = granite
    assert cache["k"].shape == (4, _G_PAGES + 1, PAGE, 4, 128)
    assert cache["conv"].shape == (36, _G_B, 3 * 4352)
    assert cache["ssm"].shape == (36, _G_B, 128, 4096)
    assert cache["ssm"].dtype == jnp.float32
    assert cache["conv"].dtype == cache["k"].dtype == jnp.bfloat16
    state = cache["ssm"].size * 4
    pool = sum(cache[n].size * 2 for n in "kv")
    assert 3.62e9 < state < 3.63e9 and 3.62e9 < pool < 3.63e9
    s_all, c_all, p_all = (cache[n].size for n in ("ssm", "conv", "k"))
    sizes = {s_all, s_all // 36, c_all, p_all, p_all // 4}
    temp_limit = {"decode": 0.25e9, "chunk": 1.5e9}
    for name, compiled in programs.items():
        hlo = compiled.as_text()
        assert all(r in hlo for r in (
            "ssm.in", "conv.state", "ssm.state", "ssm.out", "attn.qkv",
            "kv.write", "attn.core", "attn.out", "mlp", "lm_head")), name
        assert ("ssm.intra" in hlo) == (name == "chunk"), name
        assert "kv.gather" not in hlo, name
        assert reads_pages_in_place(
            hlo, KERNEL[name], _G_B if name == "decode" else 1, _G_S, 8, 64,
            scores=0 if name == "decode" else 32 * CHUNK * _G_S,
            page=PAGE), name
        assert _state_kernel_calls(hlo) == (9 if name == "decode" else 0)
        assert sorts_only_where_a_row_samples(hlo) == (name == "decode")
        if name == "decode":
            for call in re.findall(
                    r".*tpu_custom_call.*ssm_state_step.*", hlo):
                assert "ssm.state" in call
                assert f"f32[36,{_G_B},128,4096]" in call
            # besides the kernel, which takes the whole stack, no op has
            # an operand or a result of a layer's slab of the state
            assert f"f32[{_G_B},128,4096]" not in hlo
        moved = pool_moving_ops(hlo, sizes)
        assert [op for op in moved if " copy(" in op] == [], (name, moved)
        assert not [op for op in moved if "dynamic-slice(" in op], name
        assert not re.search(r"= s8\[[\d,]+\]\S* copy\(", hlo), name
        # no layer of a large weight stack is written anywhere before its
        # dot reads it (in_proj, out_proj, the MLP's three, q and o). The
        # attention layers' k and v, 0.5 MB each and heads of 64, are staged
        # in VMEM ahead of their dots, once a period: left as it is
        assert weights_laid_out_anew(
            hlo, {2048 * 8512, 4096 * 2048, 2048 * 8192, 2048 * 2048}
        ) == [], name
        mem = compiled.memory_analysis()
        # state, rows and pool are donated and come back as the same buffers
        assert mem.alias_size_in_bytes >= state + pool, name
        assert mem.temp_size_in_bytes < temp_limit[name], (
            name, mem.temp_size_in_bytes)


# `conv.state` kernels in the decode program's scan iteration (a period's
# nine Mamba layers), as compiled for a v5e: six a layer (the slab staged
# in VMEM, two cuts of the tap rows, the taps, the kept rows' two pieces
# written in place) and what the period shares.
_CONV_KERNELS = 56


def test_a_decode_step_shifts_its_conv_rows_as_a_slab(granite):
    """A decode step over every slot shifts a layer's convolution rows
    where they lie (ops/kvcache.py::conv_rows_read_and_update, `slots` None
    and one token a row): nothing in the decode program's `conv.state`
    region, in a fusion or outside one, is a gather, a scatter or a copy
    into a slots-innermost layout (`{2,0,1}`), which is what LFM2's general
    form costs a layer (two gathers, a scatter and two relaid copies:
    PERF.md section 6, PR 47), and the region's kernels all lie in the one
    scan body, at most `_CONV_KERNELS` of them and no fewer than four a
    layer (fewer would mean the form lost the region's name). A chunk
    (`slots` given) keeps the general form: its program is not this
    test's."""
    kernels, inside = region_ops(granite[0]["decode"].as_text(), "conv.state")
    assert len(kernels) == 1, list(kernels)
    (body,) = kernels.values()
    assert 9 * 4 <= len(body) <= _CONV_KERNELS, len(body)
    assert not [op for op in body + inside
                if re.search(r" (gather|scatter)\(", op)
                or re.search(r"\{2,0,1[:}]\S* copy\(", op)]
