"""The dense slot cache's programs, compiled by the chip's own compiler with no
chip (tests/test_chip_compile.py says how), for every family that serves on
that layout and for a cache split over `sequence`.
"""
import math
import re

import jax
import jax.numpy as jnp
import pytest

from chip_compile import CHUNK, described, pool_moving_ops


# The dense slot cache [L, B, KH, S, hd] at the server's defaults (8 slots of
# 1,024 positions, chunks of 512): the only layout Falcon and OPT have, and
# the only one that splits over `sequence` (there at TinyLlama's whole 2,048).
_DENSE = {
    "llama-bf16": ("llama", "tinyllama-1.1b", "model", 1, 1024),
    "llama-int8kv": ("llama", "tinyllama-1.1b", "int8", 1, 1024),
    "falcon": ("falcon", "falcon-7b", "model", 1, 1024),
    "opt": ("opt", "opt-1.3b", "model", 1, 1024),
    "llama-sequence4": ("llama", "tinyllama-1.1b", "model", 4, 2048),
}


@pytest.mark.parametrize("program", ["decode", "chunk"])
@pytest.mark.parametrize("name", list(_DENSE))
def test_dense_serving_programs_compile_for_v5e(name, program, v5e):
    """The engine's decode step and 512-token chunk over the dense slot
    cache compile for a described v5e and fit it, as plain XLA: the one
    attention of ops/decode_attention.py, no kernel. Only the int8 cache's
    chunk dequantizes a slot (`kv.gather`). With the cache split four ways
    over its positions each chip holds a quarter of it and nothing gathers
    it: the softmax's partial sums are all that cross chips.

    What the compiler does put in is recorded, not refused: each program
    copies every cache array once, whole (the layer scan takes the cache
    as `xs` and returns it as `ys`; the paged pool is carried in place
    since PR 25). A second whole copy of any of them fails here."""
    import importlib

    from substratus_tpu.serve.engine import Engine, EngineConfig

    family, config, kv_cache_dtype, sequence, seq_len = _DENSE[name]
    model = importlib.import_module(f"substratus_tpu.models.{family}")
    cfg = model.CONFIGS[config]
    quantized = kv_cache_dtype == "int8"
    b = 8
    # Built on the CPU with the smallest cache it takes; its jitted programs
    # are lowered for the described chips at the shapes above.
    eng = Engine(cfg, None, EngineConfig(
        max_batch=1, max_seq_len=16, max_prefill_len=CHUNK,
        kv_cache_dtype=kv_cache_dtype, kv_layout="dense",
    ))
    assert not eng.paged
    placed, arr = described(v5e, eng, sequence=sequence)
    params = placed(
        jax.eval_shape(lambda key: model.init_params(cfg, key),
                       jax.random.key(0)),
        model.param_logical_axes(cfg),
    )
    slots = 1 if program == "chunk" else b  # a chunk runs on its slot's cache
    cache = placed(
        jax.eval_shape(lambda: model.init_cache(
            cfg, slots, seq_len, dtype=jnp.int8 if quantized else None)),
        model.cache_logical_axes(cfg, quantized),
    )
    if program == "decode":
        lowered = eng._decode_fn.lower(
            params, cache, None, arr((b,)), arr((b,)),
            arr((b,), jnp.float32), arr((b,), jnp.float32),
            arr(eng.key.shape, eng.key.dtype),
        )
    else:
        lowered = Engine._chunk_prefill_jit.lower(
            model, cfg, params, cache, arr((1, CHUNK)), arr(()), arr(()),
        )
    hlo = lowered.compile().as_text()  # raises where it does not fit 16 GB
    assert "tpu_custom_call" not in hlo
    for scope in ("kv.write", "attn.core"):
        assert scope in hlo, scope
    assert ("kv.gather" in hlo) == (quantized and program == "chunk")
    per_chip = {
        k: math.prod(s.sharding.shard_shape(s.shape)) for k, s in cache.items()
    }
    assert all(n * sequence == cache[k].size for k, n in per_chip.items())
    whole = pool_moving_ops(
        "\n".join(l for l in hlo.splitlines() if " copy(" in l),
        set(per_chip.values()),
    )
    assert len(whole) <= len(cache), whole
    assert not re.search(r"all-gather|all-to-all|collective-permute", hlo)
