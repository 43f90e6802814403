"""Serving container entrypoint (container contract).

The reference's Server CR pointed at external images like
`substratusai/model-server-basaran` (examples/llama2-7b/server.yaml) obeying
the contract: model weights RO-mounted at /content/model, params at
/content/params.json, HTTP on :8080 with `GET /` readiness
(docs/container-contract.md:38-56). This module is the in-repo TPU-native
equivalent:

    python -m substratus_tpu.serve.main [--model /content/model] [--port 8080]
        [--params /content/params.json]

Params (from params.json or flags): quantize=int8|w8a8|int4|none
(w8a8 = int8 weights + dynamic per-token int8 activations on the MXU's
native s8xs8 path; int4 = nibble-packed group-quantized weights, the
4-bit parity path for the reference's MODEL_LOAD_IN_4BIT / GGUF examples),
max_batch, max_seq_len, config (named config for weightless smoke runs).
"""
from __future__ import annotations

import argparse
import json
import os
from typing import Any, Dict, Optional

import jax

from substratus_tpu.parallel.distributed import maybe_initialize


def load_params_json(path: str = "/content/params.json") -> Dict[str, Any]:
    if os.path.exists(path):
        with open(path) as f:
            return json.load(f)
    return {}


def _resolve_gguf(path: str):
    """Strict GGUF path resolution for --model: loud on missing files and
    ambiguous multi-shard dirs (substratus_tpu.load.gguf)."""
    from substratus_tpu.load.gguf import resolve_gguf_or_exit

    return resolve_gguf_or_exit(path)


def load_checkpoint(path: str):
    """One resolution rule for target and draft models alike (shared
    with the batch-generation entrypoint, serve/batchgen.py): a .gguf
    file (or a mounted artifact dir holding one) loads through the
    llama.cpp-format importer; otherwise orbax artifact if present,
    else HF layout."""
    gguf_path = _resolve_gguf(path)
    if gguf_path is not None:
        from substratus_tpu.load.gguf import load_gguf

        return load_gguf(gguf_path)
    from substratus_tpu.train.checkpoints import maybe_restore_orbax

    restored = maybe_restore_orbax(path)
    if restored is not None:
        return restored
    from substratus_tpu.load.hf import load_pretrained

    return load_pretrained(path)


def build_adapter_store(family, cfg, params_json: Dict[str, Any],
                        adapters_dir_flag: Optional[str]):
    """Multi-tenant AdapterStore from params/--adapters-dir discovery
    (docs/serving.md "Multi-tenant adapters"), shared by the interactive
    server and the batch-generation driver so a manifest's per-record
    `model` field selects the same LoRA slots a chat request would.
    Returns None when no adapters are configured (or the family can't
    index them — loud, not silent)."""
    adapters_cfg = params_json.get("adapters") or {}
    adapters_dir = adapters_dir_flag or adapters_cfg.get("dir") or (
        "/content/adapters" if os.path.isdir("/content/adapters") else None
    )
    if not adapters_dir and not adapters_cfg.get("paths"):
        return None
    if not getattr(family, "SUPPORTS_INDEXED_LORA", False):
        # Same loud-not-silent policy as _maybe_quantize: tell the
        # operator their tenants won't be served instead of 404ing
        # every adapter request with no explanation in the logs.
        print(
            "multi-tenant adapters unsupported for this family; "
            "serving the base model only",
            flush=True,
        )
        return None
    from substratus_tpu.serve.adapters import (
        AdapterStore, infer_store_shape, is_adapter_artifact,
    )

    explicit = dict(adapters_cfg.get("paths") or {})
    discovered = {}
    if adapters_dir and os.path.isdir(adapters_dir):
        for entry in sorted(os.listdir(adapters_dir)):
            p = os.path.join(adapters_dir, entry)
            if is_adapter_artifact(p):
                discovered[entry] = p
    inferred_rank, inferred_targets = infer_store_shape(
        list(explicit.values()) + list(discovered.values())
    )
    adapters = AdapterStore(
        cfg,
        capacity=int(adapters_cfg.get("capacity", 8)),
        rank=int(adapters_cfg.get("rank", inferred_rank)),
        targets=tuple(adapters_cfg.get("targets", inferred_targets)),
        search_dir=adapters_dir,
    )
    for aid, p in explicit.items():
        adapters.register_path(aid, p)
    # Preload up to capacity so first requests don't pay the
    # artifact read; the rest hot-load on demand (cache miss).
    for aid in list(adapters.available_ids())[: adapters.capacity]:
        try:
            adapters.load(aid)
        except (OSError, ValueError) as e:
            print(f"adapter {aid!r} failed to preload: {e}", flush=True)
    print(
        f"adapter store: {len(adapters.loaded_ids())} loaded / "
        f"{len(adapters.available_ids())} available "
        f"(capacity {adapters.capacity}, rank {adapters.rank})",
        flush=True,
    )
    return adapters


def _maybe_quantize(family, cfg, params, quantize: str, quiet: bool = False):
    """Quantize a (cfg, params) pair per the requested mode. Pre-quantized
    artifacts pass through; unsupported families keep dense weights."""
    from substratus_tpu.models import llama

    if quantize not in ("int8", "w8a8", "int4"):
        return cfg, params
    if family is not llama:
        if not quiet:
            print(f"{quantize} quantization not supported for this family; "
                  "skipping")
        return cfg, params
    from substratus_tpu.ops.quant import is_quantized, quantize_params
    from substratus_tpu.ops.quant4 import quantize4_params

    if not is_quantized(params):  # quantized artifacts come pre-done
        qfn = quantize4_params if quantize == "int4" else quantize_params
        params = jax.jit(
            lambda p: qfn(p, llama.quant_contracting(cfg))
        )(params)
    if quantize == "w8a8":
        cfg = cfg.replace(quant_activations=True)
    return cfg, params


def _parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default=None, help="checkpoint dir (HF or orbax)")
    ap.add_argument("--config", default=None, help="named config for random-weight smoke")
    ap.add_argument("--port", type=int, default=8080)
    ap.add_argument("--params", default="/content/params.json",
                    help="params.json path (container contract default)")
    ap.add_argument("--host", default="0.0.0.0")
    ap.add_argument("--max-batch", type=int, default=None)
    ap.add_argument("--max-seq-len", type=int, default=None)
    ap.add_argument(
        "--quantize", default=None, choices=["int8", "w8a8", "int4", "none"]
    )
    ap.add_argument(
        "--draft-model", default=None,
        help="draft checkpoint dir for speculative decoding",
    )
    ap.add_argument(
        "--spec-k", type=int, default=None,
        help="draft tokens proposed per verify pass (0 = off)",
    )
    ap.add_argument(
        "--role", default=None, choices=["both", "prefill", "decode"],
        help="disaggregated serving role (serve/disagg.py): prefill "
             "workers hand KV pages to decode workers; default 'both' "
             "(monolithic). Env SUBSTRATUS_SERVE_ROLE / params.json "
             "'role' also set it (flag > env > params)",
    )
    ap.add_argument(
        "--transfer-port", type=int, default=None,
        help="KV-transfer listen port for role=decode (default 8500; "
             "env SUBSTRATUS_TRANSFER_PORT / params 'transfer_port')",
    )
    ap.add_argument(
        "--decode-peers", default=None,
        help="comma-separated host:port transfer endpoints of the "
             "decode tier, for role=prefill (env "
             "SUBSTRATUS_DECODE_PEERS / params 'decode_peers')",
    )
    ap.add_argument(
        "--adapters-dir", default=None,
        help="directory of LoRA adapter artifacts served multi-tenant "
             "(one subdir per adapter id; default /content/adapters "
             "when mounted — docs/serving.md 'Multi-tenant adapters')",
    )
    return ap.parse_args(argv)


def _start(args: argparse.Namespace):
    """Everything between the flags and the listener, which is what the
    span serve.start times: the backend, the weights, the engine with its
    scheduler thread. Returns (engine, state, params_json); `state` is
    None on a follower of a multi-host gang, which binds no HTTP."""
    # Multi-host slice: join the jax.distributed world the operator wired
    # (no-op on single hosts).
    maybe_initialize()
    from substratus_tpu.utils.jaxstart import jax_startup, phase

    jax_startup()

    params_json = load_params_json(args.params)
    from substratus_tpu.utils.params import warn_unknown_keys

    warn_unknown_keys(
        params_json,
        (
            "model", "config", "quantize", "max_batch", "max_seq_len",
            "max_prefill_len", "kv_cache_dtype", "kv_layout", "attn_impl",
            "q4_impl", "tensor", "sequence", "replicas", "draft_model",
            "spec_k", "max_queue", "drain_grace", "adapters", "baseModel",
            "disaggregated", "role", "transfer_port", "decode_peers",
            "batchGenerate",
        ),
        "serve.main",
    )
    model_dir = args.model or params_json.get("model") or (
        "/content/model" if os.path.isdir("/content/model") else None
    )
    quantize = args.quantize or params_json.get("quantize", "none")
    max_batch = args.max_batch or int(params_json.get("max_batch", 8))
    max_seq_len = args.max_seq_len or int(params_json.get("max_seq_len", 1024))

    from substratus_tpu.models import llama, registry
    from substratus_tpu.serve.engine import Engine, EngineConfig
    from substratus_tpu.serve.server import ServerState
    from substratus_tpu.serve.tokenizer import load_tokenizer

    # Each phase waits for its tree to be on the device, so that the next
    # one (and the engine's first launch) is not charged for it.
    with phase("startup.load"):
        if model_dir:
            cfg, params = load_checkpoint(model_dir)
            model_name = os.path.basename(os.path.normpath(model_dir))
            tokenizer = load_tokenizer(model_dir)
        else:
            # Weightless smoke mode (reference parallel: the opt-125m CPU
            # smoke in test/system.sh) — random init of a named config from
            # any registered family.
            name = args.config or params_json.get("config", "tiny")
            smoke_family, cfg = registry.find_named_config(name)
            tokenizer = load_tokenizer(None)
            if cfg.vocab_size < tokenizer.vocab_size:
                cfg = cfg.replace(vocab_size=tokenizer.vocab_size)
            params = smoke_family.init_params(cfg, jax.random.key(0))
            model_name = name
        jax.block_until_ready(params)

    family = registry.module_of(cfg)

    with phase("startup.quantize", mode=quantize):
        cfg, params = _maybe_quantize(family, cfg, params, quantize)
        jax.block_until_ready(params)

    kv_layout = params_json.get("kv_layout", "auto")
    if family is llama:
        # Serving picks its own attention impl (never inherited from
        # training). On TPU the Pallas flash kernel is the default of the
        # no-cache prefill: it keeps the [S, S] score matrix out of HBM,
        # compiles for a v5e at every prefill bucket and matches the XLA
        # reference on the chip (PR 21, chip_smoke.py; its speed is not
        # measured). Only the dense layout's single-shot prefill reaches
        # it: the paged layout (the default) prefills through the block
        # table with XLA attention. Other backends get the XLA reference.
        # params.json {"attn_impl": ...} overrides either way.
        default_impl = "flash" if jax.default_backend() == "tpu" else "xla"
        cfg = cfg.replace(
            attn_impl=params_json.get("attn_impl", default_impl)
        )

    # Bounded admission (gateway contract): beyond this many waiters
    # submit() sheds with 429 instead of queueing. params.json
    # {"max_queue": 0} restores the unbounded legacy behavior.
    max_queue_raw = int(params_json.get("max_queue", 4 * max_batch))
    ec = EngineConfig(
        max_batch=max_batch,
        max_seq_len=min(max_seq_len, cfg.max_seq_len),
        max_prefill_len=int(
            params_json.get("max_prefill_len", EngineConfig.max_prefill_len)
        ),
        eos_token_id=tokenizer.eos_id if tokenizer.eos_id is not None else 2,
        kv_cache_dtype=params_json.get("kv_cache_dtype", "model"),
        kv_layout=kv_layout,
        max_queue=max_queue_raw if max_queue_raw > 0 else None,
        # Overlapped decode scheduling escape hatch (params.json
        # {"overlap": false} forces the synchronous scheduler; absent =
        # auto — on for single-host role=both/decode, off under
        # lockstep sync and speculation; docs/performance.md).
        overlap=params_json.get("overlap"),
    )
    # Multi-chip serving: tensor-parallel over as many chips as the kv heads
    # allow (params.json {"tensor": N} overrides), data-parallel the rest.
    n_dev = len(jax.devices())
    mesh = None
    if n_dev > 1:
        from substratus_tpu.parallel.mesh import build_mesh

        # Serving-side context parallelism: {"sequence": N} shards the
        # dense KV cache's sequence dim over N chips (per-chip cache
        # memory drops N×; XLA partitions the attention softmax over the
        # sharded dim — parallel/sharding.serve_rules_for).
        sp = int(params_json.get("sequence", 1)) or 1
        if n_dev % sp:
            raise SystemExit(
                f"sequence={sp} must divide the device count ({n_dev})"
            )
        rest = n_dev // sp
        tp = int(params_json.get("tensor", 0)) or min(rest, cfg.n_kv_heads)
        while rest % tp or cfg.n_kv_heads % tp:
            tp -= 1
        dp = rest // tp
        mesh = build_mesh(data=dp, sequence=sp, tensor=tp)
        if max_batch % dp:
            ec.max_batch = ((max_batch // dp) + 1) * dp
        print(
            f"serving mesh: data={dp} sequence={sp} tensor={tp}",
            flush=True,
        )
        if sp > 1:
            if kv_layout != "dense":
                # The paged pool indexes pages host-side; only the dense
                # layout sequence-shards.
                print("sequence>1 pins kv_layout=dense", flush=True)
                kv_layout = "dense"
                ec.kv_layout = "dense"
        # The Pallas kernels (int4 unpack-dequant matmul, flash prefill
        # attention) carry custom_partitioning rules that run them
        # per-shard on a virtual CPU mesh. The chip's compiler refuses the
        # wrapper under a multi-chip mesh (tests/test_chip_compile.py), so
        # on several chips keep the XLA paths: the defaults here, and
        # params.json {"q4_impl": "xla"} with int4 weights.
    q4_impl = params_json.get("q4_impl")
    if q4_impl:
        from substratus_tpu.ops.quant4 import set_q4_impl

        if q4_impl not in ("xla", "pallas"):
            raise SystemExit(f"q4_impl {q4_impl!r} invalid (xla|pallas)")
        set_q4_impl(q4_impl)
        print(f"int4 lowering pinned: {q4_impl}", flush=True)
    # Speculative decoding: a small draft model (same family) proposes,
    # the target verifies — engine-integrated, batched (serve/engine.py).
    draft = None
    draft_dir = args.draft_model or params_json.get("draft_model")
    spec_k = (
        args.spec_k
        if args.spec_k is not None
        else int(params_json.get("spec_k", 0))
    )
    if spec_k and draft_dir and kv_layout == "dense":
        # Draft-model speculation shares the target's page tables, so it
        # needs the paged pool; warn and serve unsped rather than crash
        # at Engine construction. Prompt-lookup speculation is
        # layout-agnostic.
        print("draft spec_k needs kv_layout=paged; speculation disabled",
              flush=True)
        spec_k = 0
    if draft_dir and spec_k:
        with phase("startup.draft"):
            draft_cfg, draft_params = load_checkpoint(draft_dir)
            if registry.module_of(draft_cfg) is not family:
                raise SystemExit(
                    "draft model must be the same family as the target")
            # The draft must ride the same quantization as the target — it
            # exists to cut HBM traffic, not to add bf16 streams.
            draft_cfg, draft_params = _maybe_quantize(
                registry.module_of(draft_cfg), draft_cfg, draft_params,
                quantize, quiet=True,
            )
            jax.block_until_ready(draft_params)
        draft = (draft_cfg, draft_params)
        ec.spec_k = spec_k
        print(f"speculative decoding: draft={draft_dir} k={spec_k}", flush=True)
    elif spec_k:
        # No draft model: prompt-lookup decoding — the engine proposes the
        # continuation after the latest match of the context's trailing
        # n-gram (host-side, zero model cost; serve/engine.py).
        ec.spec_k = spec_k
        print(f"speculative decoding: prompt-lookup k={spec_k}", flush=True)

    # Multi-host slice: every process builds the same engine over the
    # global mesh and runs the scheduler in lockstep; only process 0
    # binds HTTP (the Service routes to worker 0), followers mirror the
    # computation via the per-iteration event broadcast
    # (serve/multihost.py).
    sync = None
    if jax.process_count() > 1:
        from substratus_tpu.serve.multihost import StepSync

        sync = StepSync()
        print(
            f"multi-host serving: process {sync.process_index}/"
            f"{sync.num_processes} "
            f"({'leader' if sync.leader else 'follower'})",
            flush=True,
        )

    # Multi-tenant adapter serving (docs/serving.md "Multi-tenant
    # adapters"): pack N tenants' LoRA adapters into this one engine.
    # Sources: --adapters-dir / params.json {"adapters": {"dir": ...,
    # "paths": {id: path}, "capacity", "rank", "targets"}}, defaulting
    # to the container-contract /content/adapters mount when present
    # (build_adapter_store — shared with serve/batchgen.py).
    adapters = build_adapter_store(family, cfg, params_json,
                                   args.adapters_dir)

    # Disaggregated prefill/decode serving (serve/disagg.py, ROADMAP
    # item 3). Per-tier values arrive as env vars (the controller stamps
    # SUBSTRATUS_SERVE_ROLE per Deployment — both tiers share one params
    # ConfigMap) with flag > env > params precedence.
    role = (
        args.role
        or os.environ.get("SUBSTRATUS_SERVE_ROLE")
        or str(params_json.get("role", "both"))
    )
    if role not in ("both", "prefill", "decode"):
        raise SystemExit(f"role {role!r} invalid (both|prefill|decode)")
    handoff = None
    if role != "both" and sync is not None:
        raise SystemExit("disaggregated roles don't combine with a "
                         "multi-host lockstep gang")
    if role == "prefill":
        from substratus_tpu.serve.disagg import HandoffManager, PoolSpec

        raw_peers = (
            args.decode_peers
            or os.environ.get("SUBSTRATUS_DECODE_PEERS")
            or ",".join(params_json.get("decode_peers", []) or [])
        )
        peers = [p.strip() for p in raw_peers.split(",") if p.strip()]
        if not peers:
            raise SystemExit("role=prefill needs --decode-peers")
        ec.role = "prefill"
        handoff = HandoffManager(peers, PoolSpec.from_engine_config(cfg, ec))
        print(f"prefill role: decode peers {peers}", flush=True)
    elif role == "decode":
        ec.role = "decode"

    engine = Engine(
        cfg, params, ec, mesh=mesh, model=family, draft=draft, sync=sync,
        adapters=adapters, handoff=handoff,
        # nothing here reads the trees again: a leaf the engine lays out
        # anew (Engine.serving_tree) is freed as its new form is made, so
        # no stack is held twice
        donate_params=True,
    )
    # Under a mesh the engine holds its own sharded copy; this name was
    # the last reference to the whole tree on the default device.
    del params
    engine.start()

    if role == "decode":
        from substratus_tpu.serve.disagg import (
            DEFAULT_TRANSFER_PORT, HandoffServer,
        )

        transfer_port = int(
            args.transfer_port
            or os.environ.get("SUBSTRATUS_TRANSFER_PORT")
            or params_json.get("transfer_port", DEFAULT_TRANSFER_PORT)
        )
        transfer = HandoffServer(engine, host=args.host, port=transfer_port)
        print(f"decode role: KV transfer on :{transfer.port}", flush=True)
    if sync is not None and not sync.leader:
        return engine, None, params_json

    def checkpoint_loader(ref: str):
        """POST /swapz checkpoint ref -> param tree ready to install:
        the exact load + quantize pipeline boot used, so the swapped
        tree matches the live one structurally whenever the checkpoint
        is the same architecture (anything else is rejected by
        Engine.swap_params' shape check, not installed)."""
        new_cfg, new_params = load_checkpoint(ref)
        _, new_params = _maybe_quantize(
            family, new_cfg, new_params, quantize, quiet=True
        )
        return new_params

    state = ServerState(
        engine, tokenizer, model_name,
        checkpoint_loader=checkpoint_loader,
    )
    return engine, state, params_json


def main(argv=None) -> int:
    args = _parse(argv)

    # Distributed tracing: join the spawner's trace (TRACEPARENT env —
    # the controller stamps it into Server workloads) and, when
    # SUBSTRATUS_TRACE_EXPORT is set, flush buffered spans there as JSONL
    # on shutdown (hack/trace_lint.py validates the format).
    from substratus_tpu.observability.propagation import context_from_env
    from substratus_tpu.observability.tracing import tracer
    from substratus_tpu.utils.jaxstart import phase

    trace_export = os.environ.get("SUBSTRATUS_TRACE_EXPORT")
    if trace_export:
        import atexit

        atexit.register(tracer.export_jsonl, trace_export)

    # serve.start: from here to where the engine runs and only the bind of
    # the listener is left (milliseconds); its children are the start-up
    # phases (docs/observability.md "Start-up").
    with phase("serve.start", parent=context_from_env()):
        engine, state, params_json = _start(args)
    if state is None:
        # Follower: no HTTP. Mirror the leader's scheduler until it
        # broadcasts stop (or the process is torn down with the gang).
        # A crashed follower must exit NON-zero: a Succeeded gang pod
        # would suppress the JobSet failurePolicy restart while the
        # leader hangs at its next collective missing a participant.
        engine._thread.join()
        if engine.error is not None:
            print(f"follower engine died: {engine.error!r}", flush=True)
            return 1
        return 0
    from substratus_tpu.serve.server import serve_forever

    print(f"serving {state.model_name} on {args.host}:{args.port}", flush=True)
    serve_forever(
        state, host=args.host, port=args.port,
        drain_grace_s=float(params_json["drain_grace"])
        if "drain_grace" in params_json else None,
    )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
