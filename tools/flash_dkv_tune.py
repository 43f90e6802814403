"""On-chip sweep of the flash backward dK/dV grid (round-2 self-report: dkv
0.92x vs XLA at 8k/16h — the one shape where flash loses).

Sweeps (block_q, block_k) for the dkv kernel at the losing shape (and a
winning control shape), times the FULL flash vjp against the XLA
attention vjp, and prints the best config + the
SUBSTRATUS_FLASH_DKV_BLOCKS setting to pin it.
"""
import itertools
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np


def sync(x):
    np.asarray(jnp.ravel(jax.tree.leaves(x)[0])[0])


def bench_vjp(f, *args, n=3):
    g = jax.jit(jax.grad(lambda *a: f(*a).astype(jnp.float32).sum(),
                         argnums=(0, 1, 2)))
    out = g(*args)
    sync(out)
    best = float("inf")
    for _ in range(n):
        t0 = time.perf_counter()
        out = g(*args)
        sync(out)
        best = min(best, time.perf_counter() - t0)
    return best


def main():
    from substratus_tpu.ops.attention import dot_product_attention
    from substratus_tpu.ops.flash_attention import (
        flash_attention, set_dkv_blocks,
    )

    print("devices:", jax.devices(), flush=True)
    shapes = [
        ("8k/16h (the r2 loser)", 1, 8192, 16, 16, 128),
        ("4k/16h (control)", 2, 4096, 16, 16, 128),
    ]
    candidates = [128, 256, 512, 1024]
    for label, b, s, h, kh, d in shapes:
        ks = jax.random.split(jax.random.key(0), 3)
        q = jax.random.normal(ks[0], (b, s, h, d), jnp.bfloat16)
        k = jax.random.normal(ks[1], (b, s, kh, d), jnp.bfloat16)
        v = jax.random.normal(ks[2], (b, s, kh, d), jnp.bfloat16)

        t_xla = bench_vjp(
            lambda q, k, v: dot_product_attention(q, k, v, causal=True),
            q, k, v,
        )
        print(f"\n{label}: XLA bwd {t_xla*1e3:.1f} ms", flush=True)

        results = []
        for bq, bk in itertools.product(candidates, candidates):
            set_dkv_blocks((bq, bk))
            try:
                t = bench_vjp(
                    lambda q, k, v: flash_attention(q, k, v, causal=True),
                    q, k, v,
                )
            except Exception as e:  # noqa: BLE001 — a config may not fit VMEM
                print(f"  dkv=({bq},{bk}): FAILED "
                      f"{str(e).splitlines()[0][:90]}", flush=True)
                continue
            results.append(((bq, bk), t))
            print(f"  dkv=({bq},{bk}): {t*1e3:.1f} ms "
                  f"({t_xla/t:.2f}x vs XLA)", flush=True)
        set_dkv_blocks(None)
        if results:
            (bq, bk), t = min(results, key=lambda r: r[1])
            print(f"BEST {label}: SUBSTRATUS_FLASH_DKV_BLOCKS={bq},{bk} "
                  f"-> {t*1e3:.1f} ms ({t_xla/t:.2f}x vs XLA)", flush=True)


if __name__ == "__main__":
    main()
