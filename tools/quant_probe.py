"""On-chip microbench: int8 weight-matmul and int8 KV decode-attention
variants, to find where the 2.7x-over-roofline decode step time goes."""
import sys
import time

import jax
import jax.numpy as jnp

sys.path.insert(0, "/root/repo")
from substratus_tpu.ops.quant import QTensor

B = 16
D, F = 4096, 11008


def timeit(fn, *args, n=20):
    out = fn(*args)
    jnp.ravel(jax.tree.leaves(out)[0])[0].item()  # sync
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jnp.ravel(jax.tree.leaves(out)[0])[0].item()
    return (time.perf_counter() - t0) / n


def main():
    key = jax.random.key(0)
    x = jax.random.normal(key, (B, D), jnp.bfloat16)
    wq = jax.random.randint(key, (D, F), -127, 128, jnp.int8)
    scale = jnp.full((1, F), 0.01, jnp.float32)
    wb = jax.random.normal(key, (D, F), jnp.bfloat16)

    @jax.jit
    def mm_bf16(x, w):
        return x @ w

    @jax.jit
    def mm_dequant(x, wq, scale):
        w = (wq.astype(jnp.float32) * scale).astype(jnp.bfloat16)
        return x @ w

    @jax.jit
    def mm_scale_after(x, wq, scale):
        y = jax.lax.dot_general(
            x, wq.astype(jnp.bfloat16), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return (y * scale).astype(jnp.bfloat16)

    t_bf16 = timeit(mm_bf16, x, wb)
    t_deq = timeit(mm_dequant, x, wq, scale)
    t_sa = timeit(mm_scale_after, x, wq, scale)
    bytes_bf16 = D * F * 2
    bytes_int8 = D * F
    print(f"matmul [{B},{D}]x[{D},{F}]:")
    print(f"  bf16         {t_bf16*1e3:7.3f}ms  {bytes_bf16/t_bf16/1e9:6.0f} GB/s")
    print(f"  int8 dequant {t_deq*1e3:7.3f}ms  {bytes_int8/t_deq/1e9:6.0f} GB/s (int8 bytes)")
    print(f"  int8 scale-after-dot {t_sa*1e3:7.3f}ms  {bytes_int8/t_sa/1e9:6.0f} GB/s")

    # KV decode attention: [B, KH, S, D] int8 cache
    from substratus_tpu.ops.decode_attention import decode_attention

    KH, S, HD, H = 32, 512, 128, 32
    k = jax.random.randint(key, (B, KH, S, HD), -127, 128, jnp.int8)
    v = jax.random.randint(key, (B, KH, S, HD), -127, 128, jnp.int8)
    ks = jnp.full((B, KH, S), 0.01, jnp.float32)
    vs = jnp.full((B, KH, S), 0.01, jnp.float32)
    q = jax.random.normal(key, (B, 1, H, HD), jnp.bfloat16)
    pos = jnp.full((B,), S - 1, jnp.int32)

    fn = jax.jit(decode_attention)
    t = timeit(fn, q, k, v, pos, ks, vs)
    cache_bytes = 2 * B * KH * S * HD
    print(
        f"  decode_attn int8 {t*1e3:7.3f}ms "
        f"{cache_bytes/t/1e9:6.0f} GB/s (one layer; x32 = {t*32*1e3:6.1f}ms)"
    )

    kbf = jax.random.normal(key, (B, KH, S, HD), jnp.bfloat16)
    vbf = jax.random.normal(key, (B, KH, S, HD), jnp.bfloat16)
    t = timeit(fn, q, kbf, vbf, pos, None, None)
    cache_bytes = 2 * B * KH * S * HD * 2
    print(
        f"  decode_attn bf16 {t*1e3:7.3f}ms "
        f"{cache_bytes/t/1e9:6.0f} GB/s (one layer; x32 = {t*32*1e3:6.1f}ms)"
    )


if __name__ == "__main__":
    main()
