"""The assist cell's programs (models/lfm2_moe.py), compiled by the chip's own
compiler with no chip (tests/test_chip_compile.py says how): heads of 64 two
to a pool row under the kernels, the convolution state in place.
"""
import re

import jax
import jax.numpy as jnp

from chip_compile import (
    CHUNK, KERNEL, pool_moving_ops, reads_pages_in_place,
    sorts_only_where_a_row_samples,
)


# The assist cell's engine (benchmarks/traffic/assist.json): LFM2-24B-A2B's
# first 16 layers, all 64 experts, the whole vocabulary, a pool of 98,304
# tokens in the family's own pages of 64 (the harness gives no page_size:
# models/lfm2_moe.py::PAGE_TOKENS).
_F_POOL_PAGES, _F_B, _F_S, PAGE = 1536, 64, 2048, 64


def test_lfm2_programs_compile_and_leave_the_conv_state_in_place(v5e):
    """The family whose cache holds pages beside convolution rows: decode
    and the 512-token chunk compile for the chip at the published widths,
    read the live pages in place (heads of 64 lie two to a stored row of
    128: the kernels, no gather, and no op moves the pool), move the
    convolution layers' state neither whole nor a layer of it, lay no int8
    weight out anew, and the chunk groups its tokens by expert."""
    from jax.sharding import SingleDeviceSharding

    from substratus_tpu.models import lfm2_moe
    from substratus_tpu.ops.quant import quantize_params
    from substratus_tpu.serve.engine import Engine, EngineConfig

    cfg = lfm2_moe.Lfm2MoeConfig(
        n_layers=16, layer_types=lfm2_moe.Lfm2MoeConfig().layer_types[:16])
    assert (cfg.count(lfm2_moe.CONV), cfg.count(lfm2_moe.ATTN)) == (12, 4)
    eng = Engine(cfg, None, EngineConfig(
        max_batch=_F_B, max_seq_len=_F_S, max_prefill_len=CHUNK,
        kv_pool_tokens=1,
    ))
    assert eng.slot_state and eng.prefix is None
    assert eng.page_size == lfm2_moe.PAGE_TOKENS == PAGE
    assert eng.block_table.shape == (_F_B, 32)
    rep = SingleDeviceSharding(v5e[0])

    def placed(tree):
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=rep),
            tree)

    def arr(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=rep)

    params = placed(jax.eval_shape(
        lambda key: quantize_params(
            lfm2_moe.init_params(cfg, key), lfm2_moe.quant_contracting(cfg)),
        jax.random.key(0)))
    cache = placed(jax.eval_shape(
        lambda: lfm2_moe.init_paged_cache(
            cfg, _F_POOL_PAGES + 1, PAGE, slots=_F_B)))
    tokens = (_F_POOL_PAGES + 1) * PAGE
    pool_bytes = sum(cache[n].size * cache[n].dtype.itemsize for n in "kv")
    assert pool_bytes == tokens * 8 * 1024  # 4 attention layers of 16
    assert cache["k"].shape == (4, _F_POOL_PAGES + 1, PAGE, 4, 128)
    assert cache["conv"].shape == (12, _F_B, 2, 2048)
    m = _F_S // PAGE
    programs = {
        "decode": eng._decode_fn.lower(
            params, cache, arr((_F_B, m)), arr((_F_B,)), arr((_F_B,)),
            arr((_F_B,), jnp.float32), arr((_F_B,), jnp.float32),
            arr(eng.key.shape, eng.key.dtype), None, None,
            arr((_F_B,), jnp.bool_),
        ),
        "chunk": Engine._chunk_prefill_jit.lower(
            lfm2_moe, cfg, params, cache, arr((1, CHUNK)), arr(()), arr(()),
            arr((1, m)), None, None, arr(()),
        ),
    }
    # Refused: a copy or slice the size of the whole state, and a slice the
    # size of one layer of it. A *copy* of that last size is the step's own
    # read of its 64 slots' rows ([max_batch, 2, D], by construction as
    # large as a layer of the state), as with the rings above.
    whole, state_layer = {cache["conv"].size}, {cache["conv"].size // 12}
    pool = {cache["k"].size, cache["k"].size // 4}  # whole, or a layer
    for name, lowered in programs.items():
        compiled = lowered.compile()
        hlo = compiled.as_text()
        rows = _F_B if name == "decode" else 1
        assert reads_pages_in_place(
            hlo, KERNEL[name], rows, _F_S, cfg.n_kv_heads, cfg.head_size,
            cfg.n_heads * CHUNK * _F_S if name == "chunk" else 0,
            page=PAGE), name
        assert "kv.gather" not in hlo, name
        assert all(s in hlo for s in ("conv.in", "conv.state", "conv.out"))
        assert sorts_only_where_a_row_samples(hlo) == (name == "decode")
        bf16 = "\n".join(l for l in hlo.splitlines() if "= bf16[" in l)
        assert pool_moving_ops(bf16, pool) == [], name
        # (the chunk's one slot is written by a dynamic-update-slice whose
        # result is the state itself, updated in place: not a move)
        assert [op for op in pool_moving_ops(bf16, whole)
                if "dynamic-update-slice" not in op] == [], name
        assert [op for op in pool_moving_ops(bf16, state_layer)
                if "copy" not in op] == [], name
        assert not re.search(r"= s8\[[\d,]+\]\S* copy\(", hlo), name
        # it fits beside 9.1 GB of weights and the pool, and holds no
        # second pool (until PR 35 the device kept a pool of 64-wide heads
        # pages-innermost and each program laid it out anew: 823 MB)
        temp = compiled.memory_analysis().temp_size_in_bytes
        assert temp < 1.5e9, (name, temp)
    # the chunk's experts are a loop over blocks of rows, the step's a
    # product with every expert
    assert "moe.experts/while" in programs["chunk"].compile().as_text()
