"""Train-side benchmark: 7B LoRA finetune step-time on one host.

The SECOND of BASELINE.md's two primary metrics (bench.py has the
serve-side decode tok/s/chip): optimizer step wall time of the llama2-7b
LoRA finetune shape, with MFU and tokens/sec. Batch, sequence length and
LoRA rank default to examples/llama2-7b/finetuned-model.yaml — the exact
workload the Model CR runs — read at startup so the bench and the example
can never drift apart silently.

Prints ONE JSON line: {"metric", "value" (step ms), "unit", "vs_baseline",
"tokens_per_second", "mfu", "device", ...}. One process: it measures, or
it fails with a non-zero exit code; no fallback to a smaller shape.
ROADMAP.md S1/S7 replace it with a training cell of the benchmark; no
figure from this script has been recorded on the chip.

Baseline derivation (the reference publishes no train numbers either —
BASELINE.md): a well-tuned LoRA step should sustain >=40% MFU, so the
parity target is step_time = 6*N*tokens / (0.40 * peak_flops * n_chips)
and vs_baseline = target / measured (>1 = better than target).

The base model is random int8 (QLoRA: the frozen 7B base quantizes to
~7 GB so base + adapters + optimizer state + remat activations fit one
16 GB v5e chip; params created quantized directly on device — a bf16 7B
tree would not coexist with its quantized copy). `--quantize none`
measures the bf16-base path on bigger-HBM parts.

    python tools/bench_train.py                  # 7B shape
    python tools/bench_train.py --smoke          # CPU-scaled CI smoke
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

METRIC_UNIT = "ms/step"
EXAMPLE_YAML = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "examples", "llama2-7b", "finetuned-model.yaml",
)
# Target MFU for the derived step-time baseline (see module docstring).
TARGET_MFU = 0.40


def example_defaults() -> dict:
    """batch_size / seq_len / lora_rank from the 7B finetune example CR
    (fallbacks match the YAML as of this writing, so a missing file only
    costs the no-drift guarantee, never the capture)."""
    out = {"batch_size": 8, "seq_len": 1024, "lora_rank": 16}
    try:
        import yaml

        with open(EXAMPLE_YAML) as f:
            doc = yaml.safe_load(f)
        params = ((doc or {}).get("spec") or {}).get("params") or {}
        for key in out:
            if key in params:
                out[key] = int(params[key])
    except Exception as e:  # noqa: BLE001 — defaults are the contract
        print(f"example yaml unreadable ({e}); using defaults",
              file=sys.stderr)
    return out


def metric_name(config: str, quantize: str) -> str:
    return f"{config.replace('-', '_')}_lora_{quantize}_finetune_step_time"


def run_measurement(
    config: str, batch: int, seq_len: int, lora_rank: int, steps: int,
    quantize: str, devices: int = 1,
) -> None:
    """Measure and print the JSON line; raises on failure."""
    import jax
    import numpy as np

    from bench import peak_for
    from substratus_tpu.models import llama
    from substratus_tpu.parallel.mesh import build_mesh
    from substratus_tpu.train.trainer import TrainConfig, Trainer
    from substratus_tpu.utils.jaxstart import (
        configure_compile_cache, device_summary,
    )

    configure_compile_cache()
    device = device_summary()
    peak_flops, _ = peak_for(device["platform"], device["kind"])

    cfg = llama.CONFIGS[config]
    seq_len = min(seq_len, cfg.max_seq_len)
    if quantize == "int8":
        from bench import random_quantized_params

        params = jax.jit(
            lambda k: random_quantized_params(cfg, k, "int8")
        )(jax.random.key(0))
    else:
        params = None  # Trainer initializes bf16 params itself

    # The metric is per-chip: default to ONE device even on multi-chip
    # hosts (and under test envs that force 8 virtual CPU devices);
    # --devices N opts into an fsdp mesh for scaling studies.
    n_dev = min(devices, len(jax.devices())) if devices > 0 else len(
        jax.devices()
    )
    mesh = build_mesh(fsdp=n_dev, devices=jax.devices()[:n_dev])
    tc = TrainConfig(
        total_steps=max(steps, 2),
        lora_rank=lora_rank,
        lora_alpha=2.0 * lora_rank,
        remat=True,
    )
    trainer = Trainer(cfg, tc, mesh, params=params)

    # Param count for the 6*N*tokens MFU numerator: from abstract shapes
    # (the live tree may hold packed QTensors whose leaf sizes undercount).
    shapes = jax.eval_shape(
        lambda k: llama.init_params(cfg, k), jax.random.key(0)
    )
    n_params = sum(
        int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(shapes)
    )

    rng = np.random.default_rng(0)
    batch_data = {
        "tokens": rng.integers(
            1, cfg.vocab_size - 1, (batch, seq_len)
        ).astype(np.int32),
        "weights": np.ones((batch, seq_len), np.float32),
    }

    # Warmup / compile. train_step returns float(loss), so every step in
    # the timed region ends with the device's result on the host.
    trainer.train_step(batch_data)

    t0 = time.perf_counter()
    for _ in range(steps):
        trainer.train_step(batch_data)
    dt = time.perf_counter() - t0

    step_s = dt / steps
    tokens = batch * seq_len
    tps = tokens / step_s
    mfu = target_ms = None
    if peak_flops:
        total_peak = peak_flops * n_dev
        mfu = round((6.0 * n_params * tokens) / (step_s * total_peak), 4)
        if config == "llama2-7b":
            # Derived parity target (module docstring): TARGET_MFU of peak.
            target_ms = 6.0 * n_params * tokens / (TARGET_MFU * total_peak) * 1e3
    step_ms = step_s * 1e3
    print(
        json.dumps(
            {
                "metric": metric_name(config, quantize),
                "value": round(step_ms, 3),
                "unit": METRIC_UNIT,
                "vs_baseline": (
                    round(target_ms / step_ms, 3) if target_ms else None
                ),
                "tokens_per_second": round(tps, 1),
                "mfu": mfu,
                "batch": batch,
                "seq_len": seq_len,
                "lora_rank": lora_rank,
                "quantize": quantize,
                "n_devices": n_dev,
                "device": device,
            }
        )
    )


def main() -> int:
    import argparse

    from substratus_tpu.models import llama

    ex = example_defaults()
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="llama2-7b",
                    choices=sorted(llama.CONFIGS))
    ap.add_argument("--batch", type=int, default=ex["batch_size"])
    ap.add_argument("--seq-len", type=int, default=ex["seq_len"])
    ap.add_argument("--lora-rank", type=int, default=ex["lora_rank"])
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument(
        "--quantize", default="int8", choices=["int8", "none"],
        help="base-model weights: int8 (QLoRA, fits one 16G chip) or "
             "none (bf16 base)",
    )
    ap.add_argument(
        "--smoke", action="store_true",
        help="CPU-scaled CI smoke: tiny config, 2x64 batch, bf16 base — "
             "proves the JSON contract end to end",
    )
    ap.add_argument(
        "--devices", type=int, default=1,
        help="devices for the fsdp mesh (default 1: the metric is "
             "per-chip; 0 = all local devices)",
    )
    a = ap.parse_args()
    if a.smoke:
        a.config, a.batch, a.seq_len = "tiny", 2, 64
        a.lora_rank, a.steps, a.quantize = 4, 2, "none"
    run_measurement(a.config, a.batch, a.seq_len, a.lora_rank, a.steps,
                    a.quantize, a.devices)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
