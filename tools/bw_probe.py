"""On-chip bandwidth probe with in-graph repetition (one dispatch, scan of
N iterations), so per-dispatch overhead does not swamp the
microbenchmarks.

Measures:
  1. raw HBM streaming bandwidth (elementwise over a big array),
  2. bf16 weight-stream GEMV chain (32 distinct weights),
  3. int8+dequant weight-stream GEMV chain (same shapes),
  4. int8 decode_attention chain over 32 distinct KV caches,
  5. full decode_step at cache_len 64 vs 512 (weights vs weights+KV).
"""
import sys
import time
from functools import partial

import jax
import jax.numpy as jnp

sys.path.insert(0, "/root/repo")
from substratus_tpu.models import llama
from bench import random_quantized_params

B, D, F, L = 16, 4096, 11008, 16


def sync(x):
    jnp.ravel(jax.tree.leaves(x)[0])[0].item()


def timeit1(fn, *args, n=3):
    out = fn(*args)
    sync(out)
    best = float("inf")
    for _ in range(n):
        t0 = time.perf_counter()
        out = fn(*args)
        sync(out)
        best = min(best, time.perf_counter() - t0)
    return best


def main():
    key = jax.random.key(0)

    # 1. raw stream: 2GB bf16 array, read+write per iteration, 8 iters
    big = jax.random.normal(key, (1024, 1024 * 1024), jnp.bfloat16)  # 2GB

    @jax.jit
    def stream(x):
        def step(x, _):
            return x * 1.0001, ()
        x, _ = jax.lax.scan(step, x, None, length=8)
        return x

    t = timeit1(stream, big)
    bytes_moved = 8 * 2 * big.size * 2  # read + write per iter
    print(f"raw stream: {t*1e3:8.2f}ms  {bytes_moved/t/1e9:6.0f} GB/s (r+w)")

    # 2/3. GEMV chains over L distinct weights
    wbf = jax.random.normal(key, (L, D, F), jnp.bfloat16)  # 1.4GB
    wq = jax.random.randint(key, (L, D, F), -127, 128, jnp.int8)
    wscale = jnp.full((L, 1, F), 0.01, jnp.float32)
    x = jax.random.normal(key, (B, D), jnp.bfloat16)

    @jax.jit
    def chain_bf16(x, w):
        def step(x, wi):
            y = x @ wi
            return jnp.tanh(y[:, :D]), ()
        x, _ = jax.lax.scan(step, x, w)
        return x

    @jax.jit
    def chain_deq(x, wq, ws):
        def step(x, wsi):
            wi, si = wsi
            y = x @ (wi.astype(jnp.float32) * si).astype(jnp.bfloat16)
            return jnp.tanh(y[:, :D]), ()
        x, _ = jax.lax.scan(step, x, (wq, ws))
        return x

    t_bf = timeit1(chain_bf16, x, wbf)
    t_dq = timeit1(chain_deq, x, wq, wscale)
    print(f"gemv bf16 x{L}: {t_bf*1e3:8.2f}ms  {L*D*F*2/t_bf/1e9:6.0f} GB/s")
    print(f"gemv int8 x{L}: {t_dq*1e3:8.2f}ms  {L*D*F*1/t_dq/1e9:6.0f} GB/s "
          f"(int8 bytes; {t_bf/t_dq:4.2f}x faster than bf16)")

    # 4. decode attention chain over L distinct int8 caches
    from substratus_tpu.ops.decode_attention import decode_attention

    KH, S, HD, H = 32, 512, 128, 32
    k = jax.random.randint(key, (L, B, KH, S, HD), -127, 128, jnp.int8)
    v = jax.random.randint(key, (L, B, KH, S, HD), -127, 128, jnp.int8)
    ks = jnp.full((L, B, KH, S), 0.01, jnp.float32)
    q0 = jax.random.normal(key, (B, 1, H, HD), jnp.bfloat16)
    pos = jnp.full((B,), S - 1, jnp.int32)

    @jax.jit
    def attn_chain(q, k, v, ks):
        def step(q, kvs):
            ki, vi, ksi = kvs
            o = decode_attention(q, ki, vi, pos, ksi, ksi)
            return jnp.tanh(o), ()
        q, _ = jax.lax.scan(step, q, (k, v, ks))
        return q

    t_at = timeit1(attn_chain, q0, k, v, ks)
    cache_bytes = L * 2 * B * KH * S * HD
    print(f"attn int8 x{L}: {t_at*1e3:8.2f}ms  {cache_bytes/t_at/1e9:6.0f} GB/s "
          f"(per layer {t_at/L*1e3:6.3f}ms)")

    # 5. full decode step, small vs big cache
    cfg = llama.CONFIGS["llama2-7b"]
    params = jax.jit(lambda kk: random_quantized_params(cfg, kk))(key)
    jax.block_until_ready(params)
    for cache_len in (64, 512):
        cache = llama.init_cache(cfg, B, cache_len, dtype=jnp.int8)
        tokens = jnp.ones((B,), jnp.int32)
        positions = jnp.full((B,), 16, jnp.int32)
        logits, cache = llama.decode_step(params, cache, tokens, positions, cfg)
        sync(logits)
        steps = 16
        t0 = time.perf_counter()
        for i in range(steps):
            positions = jnp.full((B,), 17 + i, jnp.int32)
            logits, cache = llama.decode_step(params, cache, tokens, positions, cfg)
        sync(logits)
        dt = (time.perf_counter() - t0) / steps
        print(f"decode_step cache={cache_len}: {dt*1e3:8.2f}ms/step")


if __name__ == "__main__":
    main()
