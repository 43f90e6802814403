"""The longctx cell's programs (models/brumby.py), compiled by the chip's own
compiler with no chip (tests/test_chip_compile.py says how): the retention
state in place, its kernel in the decode step, alone and under `tensor` = 4.
"""
import re

import jax
import jax.numpy as jnp

from chip_compile import (
    CHUNK, PAGE, described, pool_moving_ops,
    sorts_only_where_a_row_samples, weights_laid_out_anew,
)


# The longctx cell's engine (benchmarks/traffic/longctx.json): Brumby-14B-
# Base's first 10 layers, every one power retention, the whole vocabulary.
_R_B, _R_S = 16, 9216


def _brumby_programs(v5e, **mesh_axes):
    """(lowered decode, lowered 512 chunk, cache shapes) of the longctx
    cell's engine for one described chip or, with mesh axes, the four."""
    from substratus_tpu.models import brumby
    from substratus_tpu.ops.quant import quantize_params
    from substratus_tpu.serve.engine import Engine, EngineConfig

    cfg = brumby.BrumbyConfig(n_layers=10, gate_shift=9.0)
    eng = Engine(cfg, None, EngineConfig(
        max_batch=_R_B, max_seq_len=_R_S, max_prefill_len=CHUNK,
        page_size=PAGE, kv_pool_tokens=1,
    ))
    assert eng.slot_state and eng.prefix is None and eng._page_layers == 0
    placed, arr = described(v5e, eng, **mesh_axes)
    params = placed(jax.eval_shape(
        lambda key: quantize_params(
            brumby.init_params(cfg, key), brumby.quant_contracting(cfg)),
        jax.random.key(0)), brumby.param_logical_axes(cfg))
    cache = placed(jax.eval_shape(
        lambda: brumby.init_paged_cache(
            cfg, _R_B * _R_S // PAGE + 1, PAGE, slots=_R_B)),
        brumby.paged_cache_logical_axes(cfg))
    m = _R_S // PAGE
    decode = eng._decode_fn.lower(
        params, cache, arr((_R_B, m)), arr((_R_B,)), arr((_R_B,)),
        arr((_R_B,), jnp.float32), arr((_R_B,), jnp.float32),
        arr(eng.key.shape, eng.key.dtype), None, None,
        arr((_R_B,), jnp.bool_),
    )
    chunk = Engine._chunk_prefill_jit.lower(
        brumby, cfg, params, cache, arr((1, CHUNK)), arr(()), arr(()),
        arr((1, m)), None, None, arr(()),
    )
    return decode, chunk, cache


def _state_kernel_calls(hlo: str) -> int:
    return len(re.findall(
        r'custom_call_target="tpu_custom_call".*retention_state_step', hlo))


def test_brumby_programs_compile_and_leave_the_state_in_place(v5e):
    """The family whose cache is per-slot state alone: decode and the
    512-token chunk compile for the chip at the published widths beside a
    page pool of no layers; the 5.45 GB of retention state is the layer
    scan's carry, read where it lies and written where it lies. The decode
    step moves `S` through ops/retention_kernel.py, one call in the scan's
    body, and nothing else of the program has an operand the size of a
    layer's slab of it: the state is read once and written once (PR 37;
    until then a convolution and a loop fusion read it twice). The chunk
    holds no kernel. A decode step keeps under a third of one layer's slab
    (541 MB) in temporaries, so no slab of the state is copied out of the
    carry, and no `copy` in either program has the size of the state, a
    layer of it or a slot of it; both open the family's two regions and no
    attention or page one."""
    decode, chunk, cache = _brumby_programs(v5e)
    assert cache["k"].shape == (0, _R_B * _R_S // PAGE + 1, PAGE, 8, 128)
    assert cache["ret_s"].shape == (10, _R_B, 8, 8256, 128)
    assert cache["ret_s"].dtype == cache["ret_z"].dtype == jnp.float32
    state = sum(cache[n].size * 4 for n in ("ret_s", "ret_z"))
    assert 5.45e9 < state < 5.46e9
    s_all = cache["ret_s"].size
    sizes = {s_all, s_all // 10, s_all // 10 // _R_B}  # whole, layer, slot
    sizes |= {n // 128 for n in sizes}  # the same of z
    temp_limit = {"decode": 0.18e9, "chunk": 2.0e9}
    for name, lowered in (("decode", decode), ("chunk", chunk)):
        compiled = lowered.compile()
        hlo = compiled.as_text()
        assert all(r in hlo for r in ("ret.state", "attn.qkv", "attn.out"))
        assert ("ret.intra" in hlo) == (name == "chunk"), name
        assert not any(r in hlo for r in ("kv.write", "kv.gather",
                                          "attn.core"))
        assert _state_kernel_calls(hlo) == (name == "decode"), name
        assert ("tpu_custom_call" in hlo) == (name == "decode"), name
        assert sorts_only_where_a_row_samples(hlo) == (name == "decode")
        if name == "decode":
            # the kernel's call lies in the region the benchmark reads
            call = re.search(r".*retention_state_step.*", hlo).group(0)
            assert "ret.state" in call
            # besides the kernel, which takes the whole stack, no op has
            # an operand or a result of a layer's slab of `ret_s`
            assert f"f32[{_R_B},8,8256,128]" not in hlo
            assert f"f32[10,{_R_B},8,8256,128]" in call
        f32 = "\n".join(l for l in hlo.splitlines() if "= f32[" in l)
        assert [op for op in pool_moving_ops(f32, sizes)
                if " copy(" in op] == [], name
        assert not re.search(r"= s8\[[\d,]+\]\S* copy\(", hlo), name
        # no layer of a weight stack is written anywhere before its dot
        # reads it: `forward` views the four projection stacks [L, heads,
        # hd, D] before it slices them (flat, each program staged
        # s8[1,5120,5120] and one or two s8[1,1024,5120] in VMEM, every
        # layer: `constant_dynamic-slice_fusion`, PR 41)
        assert weights_laid_out_anew(
            hlo, {5120 * 5120, 1024 * 5120, 5120 * 17408}) == [], name
        mem = compiled.memory_analysis()
        # the state is donated and comes back as the same buffers
        assert mem.alias_size_in_bytes >= state, name
        # decode: 11 MB at PR 36 and PR 37; the chunk's 1.5 GB are phi(q)
        # of 40 heads x 512 tokens in bfloat16 (338 MB) beside a turned copy
        # of it and the logits of 512 rows. Beside 5.64 GB of weights and
        # the state
        assert mem.temp_size_in_bytes < temp_limit[name], (
            name, mem.temp_size_in_bytes)


def test_brumby_decode_compiles_under_a_tensor_mesh_with_the_kernel(v5e):
    """`tensor` = 4 over the described 2x2: the state is sharded over its 8
    KV heads and nothing else is, so the decode program holds the kernel,
    under `shard_map` over that axis (each chip its own two heads' state:
    1.36 GB a chip, no collective moves it), not the fallback; no op but
    the kernel has an operand of a chip's share of a layer's slab."""
    decode, _, cache = _brumby_programs(v5e, tensor=4)
    assert cache["ret_s"].sharding.shard_shape(cache["ret_s"].shape) == (
        10, _R_B, 2, 8256, 128)
    compiled = decode.compile()
    hlo = compiled.as_text()
    assert _state_kernel_calls(hlo) == 1
    assert f"f32[{_R_B},2,8256,128]" not in hlo
    state = sum(cache[n].size * 4 for n in ("ret_s", "ret_z")) // 4
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= state
    assert mem.temp_size_in_bytes < 0.18e9, mem.temp_size_in_bytes

