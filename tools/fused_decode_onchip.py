"""On-chip validation + microbench of the flash-decode kernel.

1. Compiled-on-chip parity: fused_decode_attention (Mosaic, real DMA +
   input_output_aliases) vs XLA scatter + decode_attention, int8 and
   bf16, MHA and GQA.
2. Serving-shaped chain microbench: per-step latency of the fused path
   vs the unfused production path at the 7B decode configuration
   (B=24, KH=32, S=512, D=128, int8 KV) — chained steps so the
   per-dispatch overhead amortizes.

The kernel does not lower on a v5e (ops/fused_decode.py MOSAIC_REFUSAL,
found by AOT compilation in PR 21): on a TPU backend this script now
stops at the first call with that message. Kept for the PR that rewrites
or deletes the kernel (ROADMAP.md S3/D3).
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np


def sync(x):
    np.asarray(jnp.ravel(jax.tree.leaves(x)[0])[0])


def main():
    from substratus_tpu.ops.decode_attention import (
        decode_attention, update_cache_and_attend,
    )
    from substratus_tpu.ops.fused_decode import fused_decode_attention
    from substratus_tpu.ops.quant import quantize_kv

    print("devices:", jax.devices(), flush=True)

    # --- parity (compiled, not interpret) ---
    for kh, h, quant in [(8, 8, False), (4, 16, False), (8, 8, True)]:
        B, S, D = 4, 512, 128
        ks = jax.random.split(jax.random.key(0), 5)
        q = jax.random.normal(ks[0], (B, 1, h, D), jnp.float32)
        ckf = jax.random.normal(ks[1], (B, kh, S, D), jnp.float32)
        cvf = jax.random.normal(ks[2], (B, kh, S, D), jnp.float32)
        nkf = jax.random.normal(ks[3], (B, kh, 1, D), jnp.float32)
        nvf = jax.random.normal(ks[4], (B, kh, 1, D), jnp.float32)
        positions = jnp.array([0, 100, 311, S - 1], jnp.int32)
        bidx = jnp.arange(B)[:, None, None]
        hidx = jnp.arange(kh)[None, :, None]
        sidx = positions[:, None, None]
        if quant:
            ck, cks = quantize_kv(ckf)
            cv, cvs = quantize_kv(cvf)
            nk, nks = quantize_kv(nkf)
            nv, nvs = quantize_kv(nvf)
            cks, cvs = cks[..., 0], cvs[..., 0]
            nks, nvs = nks[..., 0], nvs[..., 0]
            cks2 = cks.at[bidx, hidx, sidx].set(nks)
            cvs2 = cvs.at[bidx, hidx, sidx].set(nvs)
            ck2 = ck.at[bidx, hidx, sidx].set(nk)
            cv2 = cv.at[bidx, hidx, sidx].set(nv)
            ref = decode_attention(q, ck2, cv2, positions, cks2, cvs2)
            out, cko, cvo = jax.jit(
                lambda *a: fused_decode_attention(*a, interpret=False)
            )(q, nk, nv, ck, cv, positions, nks, nvs, cks2, cvs2)
        else:
            ck, cv = ckf, cvf
            nk, nv = nkf, nvf
            ck2 = ck.at[bidx, hidx, sidx].set(nk)
            cv2 = cv.at[bidx, hidx, sidx].set(nv)
            ref = decode_attention(q, ck2, cv2, positions)
            out, cko, cvo = jax.jit(
                lambda *a: fused_decode_attention(*a, interpret=False)
            )(q, nk, nv, ck, cv, positions)
        err = float(jnp.abs(out - ref).max())
        ok_k = bool(jnp.array_equal(cko, ck2))
        ok_v = bool(jnp.array_equal(cvo, cv2))
        print(f"parity kh={kh} h={h} int8={quant}: maxabs={err:.3e} "
              f"cache_k={ok_k} cache_v={ok_v}", flush=True)

    # --- serving-shape microbench: chained decode steps ---
    B, h, kh, S, D = 24, 32, 32, 512, 128
    steps = 32
    ks = jax.random.split(jax.random.key(7), 4)
    q = jax.random.normal(ks[0], (B, 1, h, D), jnp.bfloat16)
    kk = jax.random.normal(ks[1], (B, 1, kh, D), jnp.bfloat16)
    vv = jax.random.normal(ks[2], (B, 1, kh, D), jnp.bfloat16)
    hist, hs = quantize_kv(
        jax.random.normal(ks[3], (B, kh, S, D), jnp.bfloat16)
    )
    cache0 = {
        "k": hist, "v": hist,
        "k_scale": hs[..., 0], "v_scale": hs[..., 0],
    }

    def chain(impl):
        @jax.jit
        def run(cache, q, kk, vv):
            a = None
            for i in range(steps):
                pos = jnp.full((B, 1), 64 + i, jnp.int32)
                a, cache = update_cache_and_attend(
                    cache, q, kk, vv, pos, impl=impl
                )
            return a, cache

        return run

    for impl in ("xla", "fused"):
        run = chain(impl)
        a, _ = run(dict(cache0), q, kk, vv)
        sync(a)
        best = float("inf")
        for _ in range(5):
            t0 = time.perf_counter()
            a, _ = run(dict(cache0), q, kk, vv)
            sync(a)
            best = min(best, time.perf_counter() - t0)
        per_step_us = best / steps * 1e6
        print(f"decode chain impl={impl}: {per_step_us:.1f} us/step "
              f"(B={B} KH={kh} S={S} D={D} int8)", flush=True)


if __name__ == "__main__":
    main()
