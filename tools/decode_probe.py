"""On-chip probe: where does the decode step time go?

Compares per-dispatch decode (the current bench loop) against a fused
lax.scan of K steps inside one jit, across batch sizes — to separate
per-dispatch overhead from true HBM-bound step time.
"""
import sys
import time

import jax
import jax.numpy as jnp
from functools import partial

sys.path.insert(0, "/root/repo")
from substratus_tpu.models import llama
from bench import random_quantized_params


def timeit(fn, sync, n=3):
    best = float("inf")
    for _ in range(n):
        t0 = time.perf_counter()
        r = fn()
        sync(r)
        best = min(best, time.perf_counter() - t0)
    return best, r


@partial(jax.jit, static_argnames=("cfg", "nsteps"), donate_argnames=("cache",))
def decode_scan(params, cache, tokens, pos0, cfg, nsteps):
    def step(carry, i):
        cache, tokens = carry
        logits, cache = llama.forward(
            params, tokens[:, None], cfg,
            positions=(pos0 + i)[:, None], cache=cache,
        )
        nxt = jnp.argmax(logits[:, 0, :], axis=-1).astype(jnp.int32)
        return (cache, nxt), nxt

    (cache, _), toks = jax.lax.scan(
        step, (cache, tokens), jnp.arange(nsteps, dtype=jnp.int32)
    )
    return toks, cache


def main():
    cfg = llama.CONFIGS["llama2-7b"]
    params = jax.jit(lambda k: random_quantized_params(cfg, k))(jax.random.key(0))
    jax.block_until_ready(params)
    print("params ready", file=sys.stderr)

    for batch in (8, 16, 32):
        cache = llama.init_cache(cfg, batch, 512, dtype=jnp.int8)
        tokens = jnp.ones((batch,), jnp.int32)

        # per-dispatch chain (matches bench.py)
        positions = jnp.full((batch,), 16, jnp.int32)
        logits, cache = llama.decode_step(params, cache, tokens, positions, cfg)
        jax.block_until_ready(logits)
        steps = 32
        t0 = time.perf_counter()
        for i in range(steps):
            positions = jnp.full((batch,), 17 + i, jnp.int32)
            logits, cache = llama.decode_step(params, cache, tokens, positions, cfg)
        jax.block_until_ready(logits)
        per_dispatch = (time.perf_counter() - t0) / steps

        # fused scan of 32 steps
        cache2 = llama.init_cache(cfg, batch, 512, dtype=jnp.int8)
        pos0 = jnp.full((batch,), 16, jnp.int32)
        toks, cache2 = decode_scan(params, cache2, tokens, pos0, cfg, 32)
        jax.block_until_ready(toks)  # compile
        cache2 = llama.init_cache(cfg, batch, 512, dtype=jnp.int8)
        t0 = time.perf_counter()
        toks, cache2 = decode_scan(params, cache2, tokens, pos0, cfg, 32)
        jax.block_until_ready(toks)
        per_scan = (time.perf_counter() - t0) / 32

        print(
            f"batch={batch:3d} per_dispatch={per_dispatch*1e3:7.2f}ms "
            f"fused_scan={per_scan*1e3:7.2f}ms "
            f"tok/s dispatch={batch/per_dispatch:7.0f} scan={batch/per_scan:7.0f}",
            flush=True,
        )


if __name__ == "__main__":
    main()
