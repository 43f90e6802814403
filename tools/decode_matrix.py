"""Decode-step decomposition on chip: step time across cache_len, KV dtype,
and decode attention impl, to locate the remaining 2.5x-over-roofline."""
import sys
import time

import jax
import jax.numpy as jnp

sys.path.insert(0, "/root/repo")
from substratus_tpu.models import llama
from bench import random_quantized_params

B = 16


def measure(cfg, params, cache_len, kv_dtype, impl, steps=24):
    cfg = cfg.replace(decode_attn_impl=impl)
    cache = llama.init_cache(
        cfg, B, cache_len, dtype=jnp.int8 if kv_dtype == "int8" else None
    )
    tokens = jnp.ones((B,), jnp.int32)
    positions = jnp.full((B,), 16, jnp.int32)
    logits, cache = llama.decode_step(params, cache, tokens, positions, cfg)
    jax.block_until_ready(logits)
    t0 = time.perf_counter()
    for i in range(steps):
        positions = jnp.full((B,), 17 + i, jnp.int32)
        logits, cache = llama.decode_step(params, cache, tokens, positions, cfg)
    jax.block_until_ready(logits)
    return (time.perf_counter() - t0) / steps


def main():
    cfg = llama.CONFIGS["llama2-7b"]
    params = jax.jit(lambda k: random_quantized_params(cfg, k))(jax.random.key(0))
    jax.block_until_ready(params)
    for cache_len, kv_dtype, impl in [
        (64, "int8", "xla"),
        (512, "int8", "xla"),
        (512, "int8", "pallas"),
        (512, "model", "xla"),
    ]:
        try:
            dt = measure(cfg, params, cache_len, kv_dtype, impl)
            print(
                f"cache={cache_len:4d} kv={kv_dtype:5s} impl={impl:6s} "
                f"{dt*1e3:7.2f}ms/step  {B/dt:6.0f} tok/s",
                flush=True,
            )
        except Exception as e:  # noqa: BLE001
            print(f"cache={cache_len} kv={kv_dtype} impl={impl}: "
                  f"FAILED {type(e).__name__}: {str(e)[:200]}", flush=True)


if __name__ == "__main__":
    main()
