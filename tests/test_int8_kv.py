"""int8 KV cache: decode must track the bf16-cache decode closely (it is a
bandwidth optimization, not a semantics change)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from substratus_tpu.models import llama, opt
from substratus_tpu.serve.engine import Engine, EngineConfig


def test_int8_kv_decode_tracks_full_precision():
    cfg = llama.CONFIGS["tiny"].replace(dtype=jnp.float32)
    params = llama.init_params(cfg, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (2, 12), 0, cfg.vocab_size)
    full, _ = llama.forward(params, tokens, cfg)

    cache = llama.init_cache(cfg, 2, 32, dtype=jnp.int8)
    agree = 0
    for i in range(12):
        pos = jnp.full((2,), i, jnp.int32)
        step, cache = llama.decode_step(
            params, cache, tokens[:, i].astype(jnp.int32), pos, cfg
        )
        agree += int((step.argmax(-1) == full[:, i].argmax(-1)).sum())
    assert agree >= 20, agree  # 24 predictions, allow minor quant flips


def test_engine_int8_kv_greedy_matches():
    cfg = llama.CONFIGS["tiny"].replace(vocab_size=258, dtype=jnp.float32)
    params = llama.init_params(cfg, jax.random.key(0))

    def run(kv_dtype):
        eng = Engine(
            cfg, params,
            EngineConfig(
                max_batch=2, max_seq_len=64, eos_token_id=257,
                kv_cache_dtype=kv_dtype,
            ),
        )
        eng.start()
        try:
            return eng.generate([256, 9, 8, 7], max_tokens=8, temperature=0.0)
        finally:
            eng.stop()

    ref = run("model")
    quant = run("int8")
    # Greedy argmax is robust to the small quantization noise at this scale.
    assert quant == ref, (quant, ref)


def test_int8_kv_rejected_for_unsupported_family():
    cfg = opt.CONFIGS["tiny-opt"].replace(dtype=jnp.float32)
    params = opt.init_params(cfg, jax.random.key(0))
    with pytest.raises(ValueError, match="int8"):
        Engine(
            cfg, params,
            EngineConfig(max_batch=2, max_seq_len=64, kv_cache_dtype="int8"),
            model=opt,
        )


@pytest.mark.parametrize("kv_cache_dtype", ["model", "int8"])
def test_dense_cache_of_any_length_matches_paged(kv_cache_dtype):
    """The dense slot cache takes a length that is no multiple of 128 or of
    a page: a prompt prefilled in chunks and decoded on it gives the paged
    engine's greedy tokens."""
    cfg = llama.CONFIGS["tiny"].replace(
        vocab_size=258, dtype=jnp.float32, max_seq_len=1000
    )
    params = llama.init_params(cfg, jax.random.key(0))
    prompt = [256] + [3 + i % 50 for i in range(40)]
    outs = {}
    for kv_layout in ("dense", "paged"):
        eng = Engine(
            cfg, params,
            EngineConfig(
                max_batch=2, max_seq_len=1000, max_prefill_len=16,
                eos_token_id=-1, kv_cache_dtype=kv_cache_dtype,
                kv_layout=kv_layout,
            ),
        )
        assert eng.paged == (kv_layout == "paged")
        eng.start()
        try:
            outs[kv_layout] = eng.generate(
                prompt, max_tokens=8, temperature=0.0
            )
        finally:
            eng.stop()
    assert outs["dense"] == outs["paged"] and len(outs["dense"]) == 8, outs
