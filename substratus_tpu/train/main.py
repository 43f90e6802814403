"""Training container entrypoint (container contract).

In-repo TPU-native replacement for `substratusai/model-trainer-huggingface`
(SURVEY.md §2.2; examples/llama2-7b/finetuned-model.yaml). Contract
(docs/container-contract.md:5-36): base model RO at /content/model, dataset
RO at /content/data, hyperparameters at /content/params.json, outputs to
/content/artifacts.

    python -m substratus_tpu.train.main [--data DIR] [--model DIR] [--out DIR]

params.json keys (HF-trainer-style names kept where the reference examples
used them): steps, batch_size, seq_len, learning_rate, save_steps,
lora_rank, lora_alpha, quantize (int8 => QLoRA), config (named model config
when training from scratch), dp/fsdp/tensor/sequence (mesh axis sizes,
default: all devices on fsdp).
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import numpy as np


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--data", default="/content/data")
    ap.add_argument("--model", default=None, help="base model dir (optional)")
    ap.add_argument("--out", default="/content/artifacts")
    ap.add_argument("--params", default="/content/params.json")
    args = ap.parse_args(argv)

    from substratus_tpu.utils.jaxstart import jax_startup, print_device_memory

    jax_startup()

    p = {}
    if os.path.exists(args.params):
        with open(args.params) as f:
            p = json.load(f)

    from substratus_tpu.utils.params import warn_unknown_keys

    warn_unknown_keys(
        p,
        (
            "steps", "max_steps", "batch_size", "seq_len", "learning_rate",
            "warmup_steps", "save_steps", "lora_rank", "lora_alpha",
            "quantize", "config", "dp", "fsdp", "sequence", "tensor",
            "remat", "seed", "grad_accum_steps", "profile_steps",
            "attn_impl",
        ),
        "train.main",
    )

    from substratus_tpu.models import llama
    from substratus_tpu.parallel.mesh import build_mesh
    from substratus_tpu.serve.tokenizer import load_tokenizer
    from substratus_tpu.train.checkpoints import (
        CheckpointManager,
        maybe_restore_orbax,
        save_artifact,
    )
    from substratus_tpu.train.data import PackedDataset
    from substratus_tpu.train.lora import merge_lora
    from substratus_tpu.train.telemetry import StepLogger, device_peak_flops
    from substratus_tpu.train.trainer import TrainConfig, Trainer

    steps = int(p.get("steps", p.get("max_steps", 100)))
    batch_size = int(p.get("batch_size", 8))
    seq_len = int(p.get("seq_len", 512))
    lora_rank = int(p.get("lora_rank", 0))

    model_dir = args.model or (
        "/content/model" if os.path.isdir("/content/model") else None
    )
    params = None
    if model_dir:
        from substratus_tpu.load.gguf import resolve_gguf_or_exit

        gguf_path = resolve_gguf_or_exit(model_dir)
        if gguf_path is not None:
            # fine-tune straight off a llama.cpp checkpoint (same importer
            # serving uses; weights dequantize to the training dtype)
            from substratus_tpu.load.gguf import load_gguf

            cfg, params = load_gguf(gguf_path)
        else:
            restored = maybe_restore_orbax(model_dir)
            if restored is not None:
                cfg, params = restored
            else:
                from substratus_tpu.load.hf import load_pretrained

                cfg, params = load_pretrained(model_dir)
        tokenizer = load_tokenizer(model_dir)
    else:
        from substratus_tpu.models import registry

        _, cfg = registry.find_named_config(p.get("config", "tiny"))
        tokenizer = load_tokenizer(None)
        if cfg.vocab_size < tokenizer.vocab_size:
            cfg = cfg.replace(vocab_size=tokenizer.vocab_size)

    if p.get("quantize") == "int8" and params is not None:
        from substratus_tpu.ops.quant import is_quantized, quantize_params

        if not is_quantized(params):  # int8 artifacts arrive pre-quantized
            params = jax.jit(
                lambda x: quantize_params(x, llama.quant_contracting(cfg))
            )(params)

    n_dev = len(jax.devices())
    mesh = build_mesh(
        data=int(p.get("dp", 1)),
        fsdp=int(p.get("fsdp", -1)),
        sequence=int(p.get("sequence", 1)),
        tensor=int(p.get("tensor", 1)),
    )
    dp_total = mesh.shape["data"] * mesh.shape["fsdp"]
    accum = max(1, int(p.get("grad_accum_steps", 1)))
    nproc = jax.process_count()
    # Each of the `accum` microbatches must itself split over data*fsdp,
    # and the global batch must slice evenly across processes (each host
    # loads only its own rows; train/data.py shard args below).
    unit = dp_total * accum
    if unit % nproc:
        import math

        unit = unit * nproc // math.gcd(unit, nproc)
    if batch_size % unit:
        batch_size = ((batch_size // unit) + 1) * unit
        print(
            f"batch_size rounded up to {batch_size} (multiple of "
            f"{unit} = lcm(data*fsdp*grad_accum_steps, processes))",
            flush=True,
        )
    # Context parallelism: {"sequence": N, "attn_impl": "ring"|"ulysses"}
    # shards attention over the sequence axis (llama family).
    attn_impl = p.get("attn_impl")
    if attn_impl is not None:
        if attn_impl not in ("xla", "flash", "ring", "ulysses"):
            raise SystemExit(f"unknown attn_impl {attn_impl!r}")
        if hasattr(cfg, "attn_impl"):
            cfg = cfg.replace(attn_impl=attn_impl)
        else:
            print(f"attn_impl ignored for the {type(cfg).__name__} family")

    tc = TrainConfig(
        learning_rate=float(p.get("learning_rate", 2e-5)),
        warmup_steps=int(p.get("warmup_steps", min(10, steps // 10 + 1))),
        total_steps=steps,
        lora_rank=lora_rank,
        lora_alpha=float(p.get("lora_alpha", 16.0)),
        remat=bool(p.get("remat", True)),
        seed=int(p.get("seed", 0)),
        grad_accum_steps=int(p.get("grad_accum_steps", 1)),
    )
    trainer = Trainer(cfg, tc, mesh, params=params)
    # Per-process dataset sharding is only sound when the global batch dim
    # actually shards across processes: data/fsdp are the LEADING mesh
    # axes (parallel/mesh.py), so each process owns a contiguous block of
    # batch rows exactly when data*fsdp is a multiple of the process
    # count. Otherwise (e.g. a tensor-only multi-host mesh, dp_total=1,
    # nproc=2) the batch dim is replicated-or-uneven across hosts and
    # per-process shards would SILENTLY diverge — every replica must see
    # identical values, so fall back to every host loading the identical
    # full batch instead.
    shard_data = nproc > 1 and dp_total % nproc == 0
    if nproc > 1 and not shard_data:
        print(
            f"per-process dataset sharding disabled: data*fsdp={dp_total} "
            f"does not divide across {nproc} processes; every host loads "
            "identical full batches",
            flush=True,
        )
    data = PackedDataset(
        args.data, tokenizer,
        batch_size // nproc if shard_data else batch_size, seq_len,
        eos_id=getattr(tokenizer, "eos_id", 0),
        seed=tc.seed,
        shard=jax.process_index() if shard_data else 0,
        num_shards=nproc if shard_data else 1,
    )
    batch_is_global = nproc > 1 and not shard_data
    print(
        f"training: {n_dev} devices, mesh={dict(zip(mesh.axis_names, mesh.devices.shape))}, "
        f"steps={steps}, corpus={data.n_tokens} tokens, lora_rank={lora_rank}",
        flush=True,
    )

    ckpt = CheckpointManager(
        os.path.join(args.out, "checkpoints"),
        save_steps=int(p.get("save_steps", max(1, steps // 5))),
    )
    # Preemption-safe resume (SURVEY.md §5): restore latest training state.
    trainable = trainer.lora if trainer.lora is not None else trainer.params
    abstract = {
        "trainable": jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding),
            trainable,
        ),
        "opt_state": jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding),
            trainer.opt_state,
        ),
    }
    resumed = ckpt.restore_latest(abstract)
    start_step = 0
    if resumed is not None:
        start_step, state = resumed
        if trainer.lora is not None:
            trainer.lora = state["trainable"]
        else:
            trainer.params = state["trainable"]
        trainer.opt_state = state["opt_state"]
        print(f"resumed from step {start_step}", flush=True)

    # Profiling window (SURVEY.md §5): params.json {"profile_steps": [a, b]}
    # captures a device trace of steps a..b into {out}/profile. The window
    # is clamped to the steps this run will actually execute (resume can
    # skip past it) and the trace always stops/flushes.
    prof_range = None
    prof = p.get("profile_steps")
    if prof and len(list(prof)) == 2:
        a, b = (int(x) for x in prof)
        a, b = max(a, start_step), min(b, steps - 1)
        if a <= b:
            prof_range = (a, b)
    elif prof:
        print(f"ignoring malformed profile_steps {prof!r} (need [start, end])")

    # Structured per-step telemetry (train/telemetry.py): step-time and
    # tokens/sec histograms + MFU on the shared registry, one JSON line per
    # log interval instead of bare prints. tokens_per_step is the GLOBAL
    # batch; train_step blocks on the loss, so the measured wall time is
    # the device step, not just dispatch.
    step_log = StepLogger(
        n_params=sum(
            getattr(x, "size", 0) for x in jax.tree.leaves(trainer.params)
        ),
        tokens_per_step=batch_size * seq_len,
        peak_flops=device_peak_flops(),
        # A run of a few steps (a smoke) shows every one of them.
        log_every=10 if steps > 10 else 1,
    )
    # Distributed tracing: the controller stamps a TRACEPARENT env var
    # into the training Job's container (controller/workloads.py), so this
    # run's spans — and every StepLogger JSON line, which carries the
    # active trace/span ids — join the trace that spawned it. The spans
    # export as JSONL next to the artifacts (or SUBSTRATUS_TRACE_EXPORT).
    from substratus_tpu.observability.propagation import context_from_env
    from substratus_tpu.observability.tracing import tracer

    tracing = False
    with tracer.span(
        "train.run", parent=context_from_env(),
        steps=steps, start_step=start_step, batch_size=batch_size,
        seq_len=seq_len, lora_rank=lora_rank,
    ):
        for step in range(start_step, steps):
            if prof_range and step == prof_range[0]:
                jax.profiler.start_trace(os.path.join(args.out, "profile"))
                tracing = True
            # Phase splits (train/telemetry.py): data_load / step /
            # checkpoint each timed separately, so a slow run triages to
            # input pipeline vs device step vs checkpoint I/O.
            t0 = time.perf_counter()
            batch = next(data)
            t_step = time.perf_counter()
            loss = trainer.train_step(batch, batch_is_global=batch_is_global)
            t_ckpt = time.perf_counter()
            if tracing and step == prof_range[1]:
                jax.profiler.stop_trace()
                tracing = False
            trainable = (
                trainer.lora if trainer.lora is not None else trainer.params
            )
            ckpt.maybe_save(
                step + 1,
                {"trainable": trainable, "opt_state": trainer.opt_state},
                force=(step == steps - 1),
            )
            t_end = time.perf_counter()
            step_log.log_step(
                step, float(loss), t_ckpt - t_step,
                last=step == steps - 1,
                data_seconds=t_step - t0,
                checkpoint_seconds=t_end - t_ckpt,
            )
    if tracing:
        jax.profiler.stop_trace()
    ckpt.close()
    try:
        tracer.export_jsonl(
            os.environ.get(
                "SUBSTRATUS_TRACE_EXPORT",
                os.path.join(args.out, "trace.jsonl"),
            )
        )
    except OSError as e:
        print(f"trace export failed (continuing): {e}", flush=True)

    final = (
        merge_lora(trainer.params, trainer.lora, trainer.lora_scale)
        if trainer.lora is not None
        else trainer.params
    )
    save_artifact(args.out, final, cfg, extra_meta={"trained_steps": steps})
    if trainer.lora is not None:
        # Alongside the merged model: the raw adapter as a multi-tenant
        # serving artifact (serve/adapters.py; docs/container-contract.md
        # "Adapter artifacts") — a Server sharing this model's base mounts
        # {artifacts}/adapter under /content/adapters/<tenant>.
        from substratus_tpu.serve.adapters import save_adapter_artifact

        save_adapter_artifact(
            os.path.join(args.out, "adapter"),
            trainer.lora,
            alpha=float(p.get("lora_alpha", 16.0)),
            rank=lora_rank,
            extra_meta={"trained_steps": steps},
        )
        print(f"adapter artifact saved to {args.out}/adapter", flush=True)
    print(f"artifact saved to {args.out}", flush=True)
    print_device_memory()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
