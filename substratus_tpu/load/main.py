"""Model-loader container entrypoint (container contract).

In-repo TPU-native replacement for `substratusai/model-loader-huggingface`
(SURVEY.md §2.2; examples/llama2-7b/base-model.yaml:7): imports a HuggingFace
checkpoint and writes a servable substratus artifact (Orbax params + config
sidecar + tokenizer files) to /content/artifacts.

    python -m substratus_tpu.load.main [--out /content/artifacts]

params.json keys: name (HF repo id or local path), config (named config for
weightless smoke imports), quantize (int8 stores quantized weights).
"""
from __future__ import annotations

import argparse
import json
import os
import shutil

import jax


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="/content/artifacts")
    ap.add_argument("--params", default="/content/params.json")
    ap.add_argument("--name", default=None)
    args = ap.parse_args(argv)

    p = {}
    if os.path.exists(args.params):
        with open(args.params) as f:
            p = json.load(f)
    from substratus_tpu.utils.params import warn_unknown_keys

    warn_unknown_keys(
        p, ("name", "config", "quantize", "seed"), "load.main"
    )
    name = args.name or p.get("name")

    from substratus_tpu.models import llama
    from substratus_tpu.observability.propagation import context_from_env
    from substratus_tpu.observability.tracing import tracer
    from substratus_tpu.train.checkpoints import save_artifact

    # Joins the trace of whoever spawned this Job: the controller stamps
    # TRACEPARENT into the loader container (controller/workloads.py);
    # spans export next to the artifact so the import shows up in the
    # same trace as the reconcile that created the Job.
    with tracer.span(
        "load.run", parent=context_from_env(), source=name or "random"
    ):
        gguf_path = None
        if name:
            from substratus_tpu.load.gguf import (
                load_gguf, resolve_gguf_or_exit,
            )

            gguf_path = resolve_gguf_or_exit(name)
            if gguf_path is not None:
                # llama.cpp checkpoint file -> orbax artifact (same
                # importer serving and training use; load/gguf.py). Its
                # ValueErrors (non-llama arch, rope scaling) exit cleanly
                # like the resolver's.
                try:
                    cfg, params = load_gguf(gguf_path)
                except ValueError as e:
                    raise SystemExit(str(e))
            else:
                from substratus_tpu.load.hf import load_pretrained

                cfg, params = load_pretrained(name)
            meta = {"source": name}
        else:
            # Weightless smoke import (reference parallel: opt-125m CPU
            # smoke); config names resolve across every registered family.
            from substratus_tpu.models import registry

            cfg_name = p.get("config", "tiny")
            family, cfg = registry.find_named_config(cfg_name)
            params = family.init_params(
                cfg, jax.random.key(int(p.get("seed", 0)))
            )
            meta = {"source": f"random:{cfg_name}"}

        if p.get("quantize") == "int8":
            if isinstance(cfg, llama.LlamaConfig):
                from substratus_tpu.ops.quant import quantize_params

                params = jax.jit(
                    lambda x: quantize_params(x, llama.quant_contracting(cfg))
                )(params)
                meta["quantize"] = "int8"
            else:
                print(
                    "int8 quantization not supported for this family; "
                    "skipping"
                )

        save_artifact(args.out, params, cfg, extra_meta=meta)

        # Ship tokenizer artifacts alongside the weights so serving needs
        # no network access. A GGUF source carries its vocab in metadata:
        # export it as a metadata-only tokenizer.gguf sidecar
        # (load_tokenizer resolves it) — without this the converted
        # artifact would silently serve with the byte fallback.
        if gguf_path is not None:
            from substratus_tpu.load.gguf import (
                read_gguf, write_tokenizer_gguf,
            )

            src_meta, _ = read_gguf(gguf_path, with_tensors=False)
            if write_tokenizer_gguf(
                os.path.join(args.out, "tokenizer.gguf"), src_meta
            ):
                print("embedded tokenizer exported to tokenizer.gguf")
        if name and os.path.isdir(name):
            for fname in (
                "tokenizer.json", "tokenizer.model",
                "tokenizer_config.json", "special_tokens_map.json",
            ):
                src = os.path.join(name, fname)
                if os.path.exists(src):
                    shutil.copy(src, os.path.join(args.out, fname))
    try:
        tracer.export_jsonl(
            os.environ.get(
                "SUBSTRATUS_TRACE_EXPORT",
                os.path.join(args.out, "trace.jsonl"),
            )
        )
    except OSError as e:
        print(f"trace export failed (continuing): {e}", flush=True)
    print(f"model artifact written to {args.out}", flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
