"""Llama-2-7B decode-step throughput per chip (int8 weights).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...} with
step_time_ms / mfu / hbm_bw_util and the device JAX reports alongside the
throughput. One process: it measures, or it fails with a non-zero exit
code. There is no fallback to a smaller shape, another quantization or
the CPU; JAX_PLATFORMS=cpu with --config tiny is the shape-check the
Makefile's bench-smoke runs, and its JSON says platform "cpu".

Baseline derivation (the reference publishes no perf numbers — BASELINE.md):
the north star is >=2000 tok/s aggregate serving Llama-2-70B on a v5e-16
slice, i.e. 125 tok/s/chip at 70B. Decode is HBM-bandwidth-bound, so the
7B-equivalent per-chip parity target is 125 * (70/7) = 1250 tok/s/chip.
vs_baseline = measured / 1250.

Weights are random but shape/dtype-exact (int8 + per-channel scales created
directly on device), so the measured step time equals real-checkpoint
serving decode step time. It times llama.decode_step directly, past the
engine; ROADMAP.md S1 replaces it with a benchmark that drives the engine.
No figure from this script has been recorded on the chip.
"""
from __future__ import annotations

import json
import time

METRIC_UNIT = "tokens/sec/chip"

# Per-config parity targets (decode is bandwidth-bound, so the 70B-derived
# 125 tok/s/chip north star scales ~inversely with model size). Configs
# without an entry report vs_baseline: null rather than a misleading ratio.
BASELINES = {
    "llama2-7b": 1250.0,
    "llama2-13b": 675.0,
    "llama2-70b": 125.0,
    "debug-1b": 8000.0,
}

# Peaks for the MFU / bandwidth-utilization denominators, keyed by the
# device_kind JAX reports: (bf16 FLOP/s, HBM bytes/s), from the Google
# Cloud documentation page of each part ("TPU v5e": 197 TFLOP/s, 819 GB/s).
# Same kinds as train/telemetry.py::PEAK_FLOPS. A device that is not here
# is an error, not a default.
PEAKS = {
    "TPU v4": (275e12, 1228e9),
    "TPU v5 lite": (197e12, 819e9),
    "TPU v5e": (197e12, 819e9),
    "TPU v5p": (459e12, 2765e9),
    "TPU v6 lite": (918e12, 1640e9),
    "TPU v6e": (918e12, 1640e9),
}


def peak_for(platform: str, device_kind: str):
    """(peak FLOP/s, peak bytes/s) of a listed device; (None, None) on the
    CPU, whose runs carry no utilization; KeyError for anything else."""
    if platform == "cpu":
        return None, None
    if device_kind not in PEAKS:
        raise KeyError(
            f"no peak numbers for device_kind {device_kind!r}; add it to "
            "bench.PEAKS with its source"
        )
    return PEAKS[device_kind]


def random_quantized_params(cfg, key, quantize="int8"):
    """Random int8/int4 params created quantized (no bf16 transient: a 7B
    bf16 tree would not coexist with its quantized copy in 16G HBM)."""
    import jax
    import jax.numpy as jnp

    from substratus_tpu.models import llama
    from substratus_tpu.ops.quant import QTensor
    from substratus_tpu.ops.quant4 import Q4Tensor, _pack_block_for

    contracting = llama.quant_contracting(cfg)
    shapes = jax.eval_shape(lambda k: llama.init_params(cfg, k), key)

    def one(shape_struct, contr, key):
        shape = shape_struct.shape
        if not contr:
            return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(
                cfg.dtype
            )
        if quantize == "int4":
            contr_n = tuple(sorted(c % len(shape) for c in contr))
            ax = contr_n[-1]
            block = _pack_block_for(shape[ax])
            pshape = tuple(
                d // 2 if i == ax else d for i, d in enumerate(shape)
            )
            sshape = tuple(
                d // block if i == ax else d for i, d in enumerate(shape)
            )
            packed = jax.random.randint(key, pshape, 0, 256, jnp.int32
                                        ).astype(jnp.uint8)
            scale = jnp.full(sshape, 0.02 / 7.0, jnp.float32)
            return Q4Tensor(packed=packed, scale=scale,
                            pack_axis=ax - len(shape), block=block)
        scale_shape = tuple(
            1 if i in contr else d for i, d in enumerate(shape)
        )
        q = jax.random.randint(key, shape, -127, 128, jnp.int8)
        scale = jnp.full(scale_shape, 0.02 / 127.0, jnp.float32)
        return QTensor(q=q, scale=scale)

    leaves, treedef = jax.tree.flatten(shapes)
    contr_leaves = treedef.flatten_up_to(contracting)
    keys = jax.random.split(key, len(leaves))
    out = [one(s, c, k) for s, c, k in zip(leaves, contr_leaves, keys)]
    return jax.tree.unflatten(treedef, out)


def perf_model(cfg, batch: int, mean_pos: float, kv_itemsize: int,
               quantize: str = "int8"):
    """Decode-step roofline accounting from the real parameter tree.

    Returns (flops_per_token, bytes_per_step):
      flops_per_token — 2*N over matmul (contracting) weights, with routed
        MoE experts scaled by the active fraction, plus 4*L*H*Dh*pos
        attention score/value flops;
      bytes_per_step  — every weight byte read once (batch amortizes) plus
        the per-sequence KV history read.
    """
    import jax
    import numpy as np

    from substratus_tpu.models import llama

    contracting = llama.quant_contracting(cfg)
    shapes = jax.eval_shape(
        lambda k: llama.init_params(cfg, jax.random.key(0)), 0
    )
    leaves, treedef = jax.tree.flatten(shapes)
    contr_leaves = treedef.flatten_up_to(contracting)

    active_frac = 1.0
    if cfg.n_experts > 0:
        active_frac = cfg.n_experts_per_token / cfg.n_experts

    matmul_flops = 0.0
    weight_bytes = 0.0
    for leaf, contr in zip(leaves, contr_leaves):
        n = float(np.prod(leaf.shape))
        if contr:
            # Expert weights are rank-3 (expert, in, out): only the routed
            # fraction does useful flops per token; all bytes are still read
            # each step under expert-parallel decode.
            frac = active_frac if len(leaf.shape) == 3 else 1.0
            matmul_flops += 2.0 * n * frac
            if quantize == "int4":
                weight_bytes += n * 0.5 + n / 128.0 * 4  # nibbles + g128
            else:
                weight_bytes += n * 1 + n / 128.0 * 4  # int8 + per-ch scale
        else:
            weight_bytes += n * 2  # bf16 norms/embedding

    attn_flops = 4.0 * cfg.n_layers * cfg.n_heads * cfg.head_size * mean_pos
    kv_bytes = (
        2.0 * cfg.n_layers * cfg.n_kv_heads * cfg.head_size
        * mean_pos * batch * kv_itemsize
    )
    return matmul_flops + attn_flops, weight_bytes + kv_bytes


def run_measurement(
    batch: int = 16,
    cache_len: int = 512,
    steps: int = 128,
    config: str = "llama2-7b",
    kv_dtype: str = "int8",
    quantize: str = "int8",
) -> None:
    """Measure and print the JSON line; raises on failure."""
    import jax
    import jax.numpy as jnp

    from substratus_tpu.models import llama
    from substratus_tpu.utils.jaxstart import (
        configure_compile_cache, device_summary,
    )

    configure_compile_cache()
    device = device_summary()
    peak_flops, peak_bw = peak_for(device["platform"], device["kind"])

    cfg = llama.CONFIGS[config]
    if quantize == "w8a8":
        cfg = cfg.replace(quant_activations=True)
    params = jax.block_until_ready(
        jax.jit(lambda k: random_quantized_params(cfg, k, quantize))(
            jax.random.key(0)
        )
    )

    cache = llama.init_cache(
        cfg, batch, cache_len,
        dtype=jnp.int8 if kv_dtype == "int8" else None,
    )
    tokens = jnp.ones((batch,), jnp.int32)
    pos0 = 16  # pretend a short prefix was prefilled

    # Warmup / compile.
    positions = jnp.full((batch,), pos0, jnp.int32)
    logits, cache = llama.decode_step(params, cache, tokens, positions, cfg)
    jax.block_until_ready(logits)

    # Timed steady-state decode. Each step consumes the previous step's
    # cache, so the dispatches form one dependency chain, and the timed
    # region ends in block_until_ready on the last logits.
    t0 = time.perf_counter()
    for i in range(steps):
        positions = jnp.full((batch,), pos0 + 1 + i, jnp.int32)
        logits, cache = llama.decode_step(params, cache, tokens, positions, cfg)
    jax.block_until_ready(logits)
    dt = time.perf_counter() - t0

    tok_s = batch * steps / dt
    step_ms = dt / steps * 1e3
    kv_itemsize = 1 if kv_dtype == "int8" else jnp.dtype(cfg.dtype).itemsize
    mean_pos = pos0 + 1 + steps / 2.0
    flops_per_tok, bytes_per_step = perf_model(
        cfg, batch, mean_pos, kv_itemsize, quantize
    )
    baseline = BASELINES.get(config)
    print(
        json.dumps(
            {
                "metric": f"{config.replace('-', '_')}_{quantize}"
                          "_decode_throughput_per_chip",
                "value": round(tok_s, 1),
                "unit": METRIC_UNIT,
                "vs_baseline": round(tok_s / baseline, 3) if baseline else None,
                "step_time_ms": round(step_ms, 3),
                "mfu": (
                    round(flops_per_tok * tok_s / peak_flops, 4)
                    if peak_flops else None
                ),
                "hbm_bw_util": (
                    round(bytes_per_step / (dt / steps) / peak_bw, 3)
                    if peak_bw else None
                ),
                "batch": batch,
                "cache_len": cache_len,
                "device": device,
            }
        )
    )


def main() -> int:
    import argparse

    from substratus_tpu.models import llama

    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=24)
    ap.add_argument("--cache-len", type=int, default=512)
    ap.add_argument("--steps", type=int, default=64)
    ap.add_argument("--config", default="llama2-7b",
                    choices=sorted(llama.CONFIGS))
    ap.add_argument("--kv-dtype", default="int8", choices=["int8", "model"])
    ap.add_argument(
        "--quantize", default="int8", choices=["int4", "int8", "w8a8"],
        help="weight quantization",
    )
    a = ap.parse_args()
    run_measurement(a.batch, a.cache_len, a.steps, a.config, a.kv_dtype,
                    a.quantize)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
