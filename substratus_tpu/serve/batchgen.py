"""Offline batch generation: an actor gang that saturates chips with no
HTTP path (ROADMAP item 5; Podracer's Sebulba shape — generation actors
feeding a bulk sink in lockstep, utilization as the only objective).

The interactive stack measures latency under routing, shedding, and
bursts; this driver measures nothing but chips-saturated tokens/sec:

  * **manifest in** — a JSONL prompt manifest (load/manifest.py), RO at
    /content/data per the container contract; each record carries its
    own max_tokens/temperature/top_p and an optional `model` field that
    selects a LoRA adapter slot (serve/adapters.py), so mixed-tenant
    batches pack into the one compiled program;
  * **continuous refill** — the engines take requests through the pull
    source fast-path (Engine.set_source): the scheduler thread pulls the
    next prompt the moment a slot frees — no submit() thread handoff,
    no queue-wait round trip — which is what holds decode occupancy
    near 1.0 for the whole run. Under the overlapped scheduler (the
    default since round 10, docs/performance.md "Overlapped
    scheduling") a completion surfaces at the *drain* of its step, so
    the refill boards one iteration later than the old synchronous
    same-iteration refill — but that drain (and the sink handoff, and
    the prompt tokenization behind pull()) now runs WHILE the next
    device step is in flight, so the refill's host cost vanishes from
    the step cadence (measured: tok/s ratio unchanged, occupancy gauge
    ~0.94 vs 0.96 — the release-to-readmit gap became visible, the
    cadence did not stretch);
  * **double-buffered sink** — finished records land in a swap buffer on
    the scheduler thread (a list append, never I/O); a dedicated sink
    thread swaps it and does the host-side work (detokenize, JSON
    encode, shard write/flush) while the device steps the next batch;
  * **sharded, exactly-once output** — results are JSONL shards whose
    lines carry the record's manifest index. The output IS the resume
    ledger: a restarted driver scans the shards, skips every durable
    index, and regenerates the rest into fresh shards (torn tail lines
    from a kill are unparseable, ignored, and regenerated). No side
    state file, so there is nothing to drift;
  * **actor gangs** — N engines (actors) drain one shared cursor in one
    process, and a multi-host lockstep engine composes too: the leader's
    pulls ride the same per-iteration event broadcast as submitted
    requests (serve/multihost.py), so followers mirror the refill.

Controller shape: `params.batchGenerate` on a Server CR renders a Job
(single host) or JobSet gang (multi-host TPU slice) running this module
(controller/crs.py, docs/batch-generation.md).
"""
from __future__ import annotations

import argparse
import json
import logging
import os
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional

from substratus_tpu.load.manifest import (
    completed_indices,
    iter_manifest,
    next_shard_index,
    record_prompt_tokens,
    shard_name,
)
from substratus_tpu.observability.metrics import METRICS

log = logging.getLogger(__name__)

METRICS.describe(
    "substratus_batchgen_records_total",
    "Batch-generation records written to output shards, labeled by "
    "outcome: ok (generated to stop/length), error (engine-side "
    "failure: unknown adapter, engine death), invalid (malformed "
    "manifest record — written once, never retried).",
    type="counter",
)
METRICS.describe(
    "substratus_batchgen_slot_occupancy",
    "Active decode slots / total slots across the run's actor engines, "
    "sampled by the sink thread each flush interval. The number the "
    "continuous-refill scheduler exists to keep at 1.0.",
    type="gauge",
)
METRICS.describe(
    "substratus_batchgen_manifest_progress_ratio",
    "Durably written manifest records (this run + resumed prior runs) "
    "/ total manifest records.",
    type="gauge",
)


class ShardWriter:
    """Sharded JSONL results writer. Owned by the sink thread (not
    thread-safe); rotation is internal, open_shard/close are the
    driver-visible lifecycle pair (analysis/lifecycle.py gates the
    balance). Resume NEVER appends to an existing shard: a tail line
    torn by a kill must stay inert, not have fresh JSON glued onto it."""

    def __init__(self, out_dir: str, records_per_shard: int = 10000):
        os.makedirs(out_dir, exist_ok=True)
        self.out_dir = out_dir
        self.records_per_shard = max(1, int(records_per_shard))
        self._f = None
        self._in_shard = 0

    def open_shard(self) -> str:
        """Open the next free shard file; returns its path."""
        if self._f is not None:
            self._f.close()
        path = os.path.join(
            self.out_dir, shard_name(next_shard_index(self.out_dir))
        )
        self._f = open(path, "w")
        self._in_shard = 0
        return path

    def write(self, record: Dict[str, Any]) -> None:
        if self._f is None or self._in_shard >= self.records_per_shard:
            path = self.open_shard()
            log.info("batchgen: rotating to %s", path)
        self._f.write(json.dumps(record, sort_keys=True) + "\n")
        self._in_shard += 1

    def flush(self) -> None:
        """Push buffered lines to the OS so a killed PROCESS loses at
        most the in-flight swap buffer (whose records resume regenerates
        — they were never durable, so exactly-once holds)."""
        if self._f is not None:
            self._f.flush()

    def close(self) -> None:
        if self._f is not None:
            self._f.flush()
            self._f.close()
            self._f = None


class _RecordSink:
    """Per-request stand-in for Request.out (queue interface subset).
    put() runs on the engine scheduler thread: tokens append to a plain
    list (single producer), and the terminal None hands the finished
    record to the driver's swap buffer — never I/O, never blocking."""

    __slots__ = ("driver", "index", "rec", "req", "tokens", "n_prompt",
                 "error")

    def __init__(self, driver: "BatchGenDriver", index: int,
                 rec: Dict[str, Any]):
        self.driver = driver
        self.index = index
        self.rec = rec
        self.req = None
        self.tokens: List[int] = []
        self.n_prompt = 0
        self.error: Optional[str] = None  # manifest-invalid records

    def put(self, item) -> None:
        if item is None:
            self.driver._complete(self)
        else:
            self.tokens.append(item)


class _EngineSource:
    """The engine-facing pull source (Engine.set_source): one per actor,
    all draining the driver's shared manifest cursor."""

    def __init__(self, driver: "BatchGenDriver"):
        self._driver = driver

    def pull(self):
        return self._driver._pull()

    def pending(self) -> bool:
        return self._driver._pending_refill()

    def progress(self) -> Dict[str, Any]:
        return self._driver.progress()


class BatchGenDriver:
    """Drives one or more actor engines through a prompt manifest.

    Threading: engine scheduler threads call _pull/_complete (tiny
    lock-guarded critical sections — a list pop/append); the sink thread
    (_sink_loop) owns all output I/O, the shard writer, and every
    counter; run() blocks the caller until the manifest drains. The
    pending-record list is materialized eagerly so malformed manifest
    LINES fail before any device work (malformed RECORDS — bad fields —
    become outcome=invalid output lines instead, written exactly once).
    """

    def __init__(
        self,
        engines: List[Any],
        manifest_path: str,
        out_dir: str,
        *,
        tokenizer=None,
        max_tokens: int = 64,
        temperature: float = 0.0,
        top_p: float = 1.0,
        records_per_shard: int = 10000,
        resume: bool = True,
        flush_interval_s: float = 0.05,
        sample_interval_s: float = 0.01,
        prefetch: Optional[int] = None,
        record_hook=None,
    ):
        if not engines:
            raise ValueError("batch generation needs at least one engine")
        for e in engines:
            if e.ec.role != "both":
                raise ValueError(
                    "batch generation drives monolithic engines "
                    f"(role={e.ec.role!r} given); split pools belong to "
                    "the interactive path"
                )
        self.engines = list(engines)
        self.tokenizer = tokenizer
        self.default_max_tokens = int(max_tokens)
        self.default_temperature = float(temperature)
        self.default_top_p = float(top_p)
        self.flush_interval_s = float(flush_interval_s)
        self.sample_interval_s = float(sample_interval_s)
        self.manifest_path = manifest_path
        # Called with each completed output record AFTER it is written
        # (sink thread — implementations must be thread-safe). The RL
        # actor loop (rl/loop.py) collects episodes through it without
        # re-reading the shards it just wrote.
        self.record_hook = record_hook
        self._writer = ShardWriter(out_dir, records_per_shard)
        self._slots_total = sum(e.ec.max_batch for e in self.engines)
        self._prefetch = (
            int(prefetch) if prefetch else max(2, 2 * self._slots_total)
        )

        all_records = list(iter_manifest(manifest_path))
        self.total = len(all_records)
        done = completed_indices(out_dir) if resume else set()
        self._records = deque(
            (i, rec) for i, rec in all_records if i not in done
        )
        self.resumed = self.total - len(self._records)

        self._lock = threading.Lock()
        self._ready: List[Any] = []  # prefetched Requests awaiting pull
        self._buf: List[_RecordSink] = []  # finished, awaiting write-out
        self._wake = threading.Event()
        self._in_flight = 0
        self._pulled = 0
        self._written = 0
        self._ok = 0
        self._errors = 0
        self._gen_tokens = 0
        self._occ_samples: List[float] = []
        self._abort: Optional[str] = None
        self._finished = threading.Event()

    # -- scheduler-thread side (via _EngineSource / _RecordSink) ----------

    def _build_request(self, index: int, rec: Dict[str, Any]):
        from substratus_tpu.serve.engine import Request

        sink = _RecordSink(self, index, rec)
        toks = record_prompt_tokens(rec, self.tokenizer)
        req = Request(
            prompt_tokens=toks,
            max_tokens=int(rec.get("max_tokens", self.default_max_tokens)),
            temperature=float(
                rec.get("temperature", self.default_temperature)
            ),
            top_p=float(rec.get("top_p", self.default_top_p)),
            adapter=rec.get("model"),
            out=sink,
            id=str(rec.get("id", index)),
        )
        sink.req = req
        sink.n_prompt = len(toks)
        return req

    def _fill_ready_locked(self) -> None:
        """Top the prefetch buffer up from the record cursor. Caller
        holds self._lock. Records whose fields don't validate become
        outcome=invalid completions (buffered like finished requests, so
        every counter write stays on the sink thread)."""
        while (
            self._records
            and self._abort is None
            and len(self._ready) < self._prefetch
        ):
            index, rec = self._records.popleft()
            try:
                self._ready.append(self._build_request(index, rec))
            except ValueError as e:
                bad = _RecordSink(self, index, rec)
                bad.error = f"invalid: {e}"
                self._buf.append(bad)
                self._wake.set()

    def _pull(self):
        """Next request for a freed slot — the engine scheduler thread's
        same-iteration refill. Pops a prefetched request; falls back to
        building one inline when the prefetcher is behind."""
        with self._lock:
            if self._abort is not None:
                return None
            if not self._ready:
                self._fill_ready_locked()
            if not self._ready:
                return None
            req = self._ready.pop(0)
            self._in_flight += 1
            self._pulled += 1
            return req

    def _pending_refill(self) -> bool:
        with self._lock:
            return bool(self._ready) or bool(self._records)

    def _complete(self, sink: _RecordSink) -> None:
        with self._lock:
            self._buf.append(sink)
            self._in_flight -= 1
        self._wake.set()

    # -- sink thread -------------------------------------------------------

    def _write_one(self, sink: _RecordSink) -> None:
        req = sink.req
        if sink.error is not None:
            outcome, finish = "invalid", sink.error
        elif req is not None and req.finish_reason == "error":
            outcome, finish = "error", "error"
        else:
            outcome, finish = "ok", req.finish_reason
        out: Dict[str, Any] = {
            "index": sink.index,
            "id": str(sink.rec.get("id", sink.index)),
            "tokens": list(sink.tokens),
            "finish_reason": finish,
            "prompt_tokens": sink.n_prompt,
            "gen_tokens": len(sink.tokens),
        }
        model = sink.rec.get("model")
        if model is not None:
            out["model"] = model
        if self.tokenizer is not None and sink.tokens:
            out["text"] = self.tokenizer.decode(list(sink.tokens))
        self._writer.write(out)
        self._written += 1
        self._gen_tokens += len(sink.tokens)
        if outcome == "ok":
            self._ok += 1
            if self.record_hook is not None:
                # Hook AFTER the durable write and only for ok records:
                # a consumer (the RL episode buffer) never sees a record
                # the resume ledger could replay differently. Prompt ids
                # ride along — the output record only stores their count.
                self.record_hook(
                    dict(out),
                    list(req.prompt_tokens) if req is not None else [],
                )
        else:
            self._errors += 1
        METRICS.inc(
            "substratus_batchgen_records_total", {"outcome": outcome}
        )

    def _sampler_loop(self) -> None:
        """Steady-cadence occupancy sampling on its own thread. The sink
        loop wakes on COMPLETIONS, so sampling there would land every
        sample right inside the refill window and bias the mean low;
        this thread's clock is independent of the scheduler's phase."""
        while not self._finished.wait(timeout=self.sample_interval_s):
            # Racy read of each engine's host-side active mask: a torn
            # snapshot skews one sample by one slot; the mean absorbs it.
            active = sum(int(e.active.sum()) for e in self.engines)
            occ = active / self._slots_total
            METRICS.set("substratus_batchgen_slot_occupancy", occ)
            with self._lock:
                refill_possible = bool(self._ready) or bool(self._records)
                warm = self._pulled >= self._slots_total
            done_frac = (self.resumed + self._written) / max(1, self.total)
            METRICS.set(
                "substratus_batchgen_manifest_progress_ratio", done_frac
            )
            if refill_possible and warm:
                # Steady state: the batch has filled once and refill is
                # still possible — ramp-up and the final drain (where
                # decay is inevitable, not a scheduling failure) don't
                # count.
                self._occ_samples.append(occ)

    def _sink_loop(self) -> None:
        while True:
            self._wake.wait(timeout=self.flush_interval_s)
            self._wake.clear()
            with self._lock:
                batch, self._buf = self._buf, []
                # Prefetch here too, so tokenize/Request construction
                # stays off the scheduler threads' fast path.
                self._fill_ready_locked()
                if self._abort is None:
                    dead = next(
                        (e for e in self.engines if e.error is not None),
                        None,
                    )
                    if dead is not None:
                        self._abort = f"engine died: {dead.error!r}"
                aborted = self._abort is not None
            for sink in batch:
                self._write_one(sink)
            if batch:
                self._writer.flush()
            if aborted:
                return
            with self._lock:
                if (
                    not self._records
                    and not self._ready
                    and self._in_flight == 0
                    and not self._buf
                ):
                    return

    # -- driver API --------------------------------------------------------

    def progress(self) -> Dict[str, Any]:
        """Manifest progress for load_snapshot()/loadz (read-only; torn
        reads across counters are fine for a progress report)."""
        with self._lock:
            return {
                "manifest_records": self.total,
                "resumed": self.resumed,
                "written": self._written,
                "errors": self._errors,
                "in_flight": self._in_flight,
                "pending": len(self._records) + len(self._ready),
            }

    def cancel(self, reason: str = "cancelled") -> None:
        with self._lock:
            self._abort = reason
        self._wake.set()

    def run(self) -> Dict[str, Any]:
        """Drive the manifest to completion; returns the run summary.
        Raises RuntimeError when an engine dies mid-run (already-written
        shards stay durable — a rerun resumes from them)."""
        t0 = time.perf_counter()
        if not self._records:
            self._writer.close()
            return self._summary(time.perf_counter() - t0)
        first = self._writer.open_shard()
        log.info(
            "batchgen: %d records (%d resumed) -> %s",
            len(self._records), self.resumed, first,
        )
        sink_thread = threading.Thread(target=self._sink_loop, daemon=True)
        sampler = threading.Thread(target=self._sampler_loop, daemon=True)
        for e in self.engines:
            e.set_source(_EngineSource(self))
        sink_thread.start()
        sampler.start()
        try:
            sink_thread.join()
        finally:
            self._finished.set()
            sampler.join(timeout=5)
            for e in self.engines:
                e.set_source(None)
            self._writer.close()
        if self._abort is not None:
            raise RuntimeError(f"batch generation aborted: {self._abort}")
        return self._summary(time.perf_counter() - t0)

    def _summary(self, wall: float) -> Dict[str, Any]:
        occ = (
            round(sum(self._occ_samples) / len(self._occ_samples), 4)
            if self._occ_samples else None
        )
        return {
            "manifest_records": self.total,
            "resumed": self.resumed,
            "written": self._written,
            "ok": self._ok,
            "errors": self._errors,
            "gen_tokens": self._gen_tokens,
            "wall_s": round(wall, 3),
            "gen_tok_s": (
                round(self._gen_tokens / wall, 1) if wall > 0 else 0.0
            ),
            "slot_occupancy": occ,
            "occupancy_samples": len(self._occ_samples),
            "actors": len(self.engines),
        }


class ProgressServer:
    """Optional observation endpoint for an offline run: /loadz (the
    engine load snapshot, which carries the driver's manifest progress
    once the source is attached) and /metrics (the shared registry).
    http.server on a daemon thread — no aiohttp, no serving stack; batch
    Jobs have no HTTP path by design and this one exists purely so
    `kubectl port-forward` can watch progress."""

    def __init__(self, engine, host: str = "0.0.0.0", port: int = 8080):
        from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

        class Handler(BaseHTTPRequestHandler):
            def do_GET(self) -> None:  # noqa: N802 (http.server API)
                if self.path == "/loadz":
                    body = json.dumps(engine.load_snapshot()).encode()
                    ctype = "application/json"
                elif self.path == "/metrics":
                    body = METRICS.render().encode()
                    ctype = "text/plain; version=0.0.4"
                elif self.path == "/":
                    body, ctype = b"ok\n", "text/plain"
                else:
                    self.send_response(404)
                    self.end_headers()
                    return
                self.send_response(200)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def log_message(self, *a) -> None:
                pass  # progress polls must not spam the job log

        self._srv = ThreadingHTTPServer((host, port), Handler)
        self.port = self._srv.server_address[1]
        self._thread = threading.Thread(
            target=self._srv.serve_forever, daemon=True
        )
        self._thread.start()

    def close(self) -> None:
        self._srv.shutdown()
        self._srv.server_close()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="offline batch generation from a JSONL prompt manifest"
    )
    ap.add_argument("--manifest", default=None,
                    help="JSONL prompt manifest (default: params "
                         "batchGenerate.manifest, then "
                         "/content/data/prompts.jsonl)")
    ap.add_argument("--output", default=None,
                    help="output shard directory (default: params "
                         "batchGenerate.output, then "
                         "/content/artifacts/generations)")
    ap.add_argument("--model", default=None, help="checkpoint dir")
    ap.add_argument("--config", default=None,
                    help="named config for random-weight smoke runs")
    ap.add_argument("--max-batch", type=int, default=None)
    ap.add_argument("--max-seq-len", type=int, default=None)
    ap.add_argument("--quantize", default=None,
                    choices=["int8", "w8a8", "int4", "none"])
    ap.add_argument("--max-tokens", type=int, default=None,
                    help="default generation budget for records without "
                         "their own max_tokens")
    ap.add_argument("--temperature", type=float, default=None)
    ap.add_argument("--records-per-shard", type=int, default=None)
    ap.add_argument("--no-resume", action="store_true",
                    help="ignore existing output shards (default: skip "
                         "every record already durably written)")
    ap.add_argument("--progress-port", type=int, default=None,
                    help="serve /loadz + /metrics on this port (0 = "
                         "ephemeral; default off — batch runs need no "
                         "HTTP path)")
    ap.add_argument("--params", default="/content/params.json")
    args = ap.parse_args(argv)

    import jax

    from substratus_tpu.observability.propagation import context_from_env
    from substratus_tpu.observability.tracing import tracer
    from substratus_tpu.parallel.distributed import maybe_initialize
    from substratus_tpu.serve.engine import Engine, EngineConfig
    from substratus_tpu.serve.main import (
        build_adapter_store,
        load_checkpoint,
        load_params_json,
        _maybe_quantize,
    )
    from substratus_tpu.serve.tokenizer import load_tokenizer

    maybe_initialize()
    from substratus_tpu.utils.jaxstart import jax_startup

    jax_startup()

    params_json = load_params_json(args.params)
    from substratus_tpu.utils.params import warn_unknown_keys

    bg = params_json.get("batchGenerate") or {}
    if not isinstance(bg, dict):
        bg = {}
    warn_unknown_keys(
        bg,
        ("manifest", "output", "maxTokens", "temperature",
         "recordsPerShard", "progressPort"),
        "batchgen.params.batchGenerate",
    )
    manifest = args.manifest or bg.get("manifest") or (
        "/content/data/prompts.jsonl"
    )
    output = args.output or bg.get("output") or (
        "/content/artifacts/generations"
    )
    if not os.path.exists(manifest):
        raise SystemExit(f"prompt manifest not found: {manifest}")

    from substratus_tpu.models import registry

    model_dir = args.model or params_json.get("model") or (
        "/content/model" if os.path.isdir("/content/model") else None
    )
    quantize = args.quantize or params_json.get("quantize", "none")
    if model_dir:
        cfg, params = load_checkpoint(model_dir)
        tokenizer = load_tokenizer(model_dir)
    else:
        name = args.config or params_json.get("config", "tiny")
        family, cfg = registry.find_named_config(name)
        tokenizer = load_tokenizer(None)
        if cfg.vocab_size < tokenizer.vocab_size:
            cfg = cfg.replace(vocab_size=tokenizer.vocab_size)
        params = family.init_params(cfg, jax.random.key(0))
    family = registry.module_of(cfg)
    cfg, params = _maybe_quantize(family, cfg, params, quantize)

    max_batch = args.max_batch or int(params_json.get("max_batch", 8))
    max_seq_len = args.max_seq_len or int(
        params_json.get("max_seq_len", 1024)
    )
    ec = EngineConfig(
        max_batch=max_batch,
        max_seq_len=min(max_seq_len, cfg.max_seq_len),
        max_prefill_len=int(
            params_json.get("max_prefill_len", EngineConfig.max_prefill_len)
        ),
        eos_token_id=(
            tokenizer.eos_id if tokenizer.eos_id is not None else 2
        ),
        kv_cache_dtype=params_json.get("kv_cache_dtype", "model"),
        kv_layout=params_json.get("kv_layout", "auto"),
    )

    mesh = None
    n_dev = len(jax.devices())
    if n_dev > 1:
        from substratus_tpu.parallel.mesh import build_mesh

        # Offline generation wants throughput: tensor-parallel over the
        # kv heads, data-parallel the rest (same derivation as
        # serve.main, without the sequence-parallel serving knobs).
        tp = int(params_json.get("tensor", 0)) or min(n_dev, cfg.n_kv_heads)
        while n_dev % tp or cfg.n_kv_heads % tp:
            tp -= 1
        dp = n_dev // tp
        mesh = build_mesh(data=dp, tensor=tp)
        if max_batch % dp:
            ec.max_batch = ((max_batch // dp) + 1) * dp
        print(f"batchgen mesh: data={dp} tensor={tp}", flush=True)

    sync = None
    if jax.process_count() > 1:
        from substratus_tpu.serve.multihost import StepSync

        sync = StepSync()
        print(
            f"batchgen gang: process {sync.process_index}/"
            f"{sync.num_processes} "
            f"({'leader' if sync.leader else 'follower'})",
            flush=True,
        )

    adapters = build_adapter_store(family, cfg, params_json, None)

    engine = Engine(
        cfg, params, ec, mesh=mesh, model=family, sync=sync,
        adapters=adapters,
    )
    engine.start()

    if sync is not None and not sync.leader:
        # Follower: mirror the leader's scheduler (refill pulls arrive
        # via the broadcast) until the stop event. Exit nonzero on an
        # engine error so the JobSet failurePolicy restarts the gang.
        engine._thread.join()
        if engine.error is not None:
            print(f"follower engine died: {engine.error!r}", flush=True)
            return 1
        return 0

    progress_srv = None
    if args.progress_port is not None or bg.get("progressPort") is not None:
        port = (
            args.progress_port
            if args.progress_port is not None
            else int(bg["progressPort"])
        )
        progress_srv = ProgressServer(engine, port=port)
        print(f"batchgen progress on :{progress_srv.port}", flush=True)

    driver = BatchGenDriver(
        [engine],
        manifest,
        output,
        tokenizer=tokenizer,
        max_tokens=(
            args.max_tokens
            if args.max_tokens is not None
            else int(bg.get("maxTokens", 64))
        ),
        temperature=(
            args.temperature
            if args.temperature is not None
            else float(bg.get("temperature", 0.0))
        ),
        records_per_shard=(
            args.records_per_shard or int(bg.get("recordsPerShard", 10000))
        ),
        resume=not args.no_resume,
    )
    rc = 0
    try:
        with tracer.span(
            "batchgen.run", parent=context_from_env(),
            manifest=manifest, records=driver.total,
        ):
            summary = driver.run()
        print(json.dumps(summary), flush=True)
    except RuntimeError as e:
        print(json.dumps({"error": str(e)}), flush=True)
        rc = 1
    finally:
        if progress_srv is not None:
            progress_srv.close()
        # On a gang leader this also releases the followers: the stop
        # flag rides the next event broadcast (serve/multihost.py).
        engine.stop()
    return rc


if __name__ == "__main__":
    raise SystemExit(main())
