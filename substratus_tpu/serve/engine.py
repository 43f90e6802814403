"""Continuous-batching inference engine (prefill -> insert -> decode).

The reference served models through external images (basaran / llama.cpp,
SURVEY.md §2.2) with static batching; this engine is the in-repo TPU-native
replacement, following the orchestrator pattern that works well on TPUs
(fixed shapes, no dynamic batch):

  * the decode batch is a fixed-size slot array; every jitted function sees
    static shapes, so there is exactly one decode executable;
  * prefill runs per-request at bucketed (power-of-two) lengths — a handful
    of prefill executables — then the resulting KV fragment is INSERTed into
    the decode cache at a free slot;
  * decode advances every active slot one token per step, sampling on device
    (ops/sampling.py); finished slots are freed and refilled between steps;
  * weights may be int8 QTensors (ops/quant.py) for ~2x decode throughput.

Threading model: callers enqueue Requests (thread-safe); one background
scheduler thread owns all device state — no locks around jax values.
"""
from __future__ import annotations

import itertools
import logging
import math
import queue
import threading
import time
from dataclasses import dataclass, field
from functools import partial
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from substratus_tpu.models import llama, registry
from substratus_tpu.models.llama import LlamaConfig, Params
from substratus_tpu.observability.journey import (
    JourneyLog,
    RequestJourney,
    SlowRing,
)
from substratus_tpu.observability.metrics import METRICS, RATIO_BUCKETS
from substratus_tpu.observability.sketch import SLOTracker
from substratus_tpu.observability.timeline import StepTimeline
from substratus_tpu.observability.tracing import (
    SpanContext,
    current_trace_id,
    tracer,
)
from substratus_tpu.ops import kvcache, scopes
from substratus_tpu.ops.sampling import sample
from substratus_tpu.utils.jaxstart import phase as startup_phase

# Serving latency/utilization histograms (docs/observability.md). Declared
# once at import so /metrics carries the HELP/TYPE headers even before the
# first request arrives.
METRICS.histogram(
    "substratus_serve_ttft_seconds",
    "Time from request submission to its first generated token (seconds).",
)
METRICS.histogram(
    "substratus_serve_inter_token_seconds",
    "Gap between consecutive generated tokens of one request (seconds).",
)
METRICS.histogram(
    "substratus_serve_queue_wait_seconds",
    "Time from request submission to the start of its prefill (seconds).",
)
METRICS.histogram(
    "substratus_serve_batch_occupancy_ratio",
    "Active decode slots / max_batch, sampled once per scheduler iteration.",
    buckets=RATIO_BUCKETS,
)
METRICS.histogram(
    "substratus_serve_kv_page_utilization_ratio",
    "KV pages / pool size by state, sampled once per scheduler iteration "
    "that decodes (paged layout only): live (referenced by an active "
    "slot) or cached (held by the prefix registry alone, reclaimable).",
    buckets=RATIO_BUCKETS,
)
METRICS.histogram(
    "substratus_serve_phase_seconds",
    "Wall time of one scheduler phase (seconds), labeled by phase: "
    "broadcast (the lockstep collective, serve/multihost.py), admission "
    "(queue -> slots, prefill included; iterations that boarded someone), "
    "prefill (one prefill or chunk dispatch inside admission), sample "
    "(first-token sampling + host read), decode (the host side of the "
    "batched decode/verify launch alone: no drain, no device wait). Each "
    "is one engine.<phase> span of observability/timeline.py.",
)
METRICS.describe(
    "substratus_serve_first_compile_seconds",
    "Wall time of the first decode iteration (executable compile "
    "dominates; steady-state decode is substratus_serve_phase_seconds"
    '{phase="decode"}).',
    type="gauge",
)
METRICS.histogram(
    "substratus_serve_host_overlap_seconds",
    "Host-side work (the deferred token read, emits, stop handling) "
    "hidden under the in-flight decode step by the overlapped scheduler "
    "(seconds; docs/performance.md \"Overlapped scheduling\").",
)
METRICS.describe(
    "substratus_serve_pipeline_flushes_total",
    "Overlapped-scheduler pipeline flushes by reason (gang|handoff|"
    "drain|preempt|swap): points where the engine must observe a "
    "settled batch before proceeding. The historical reason=\"spec\" is "
    "retired — speculative rounds chain on-device and hold it at zero.",
    type="counter",
)
# True counters (monotonic, rate()-able) for prefix-cache effectiveness —
# the scrape-time substratus_serve_<stat> gauges mirror the same numbers
# but only when a server is attached; these increment at admission.
METRICS.describe(
    "substratus_serve_kv_heads_per_pool_row",
    "KV heads one stored row of the paged pool holds, set where the pool "
    "is made (ops/kvcache.py::init_paged_cache): 2 for a bfloat16 pool of "
    "64-wide heads, which the paged-attention kernels read in place; 1 = "
    "stored as declared (heads of 128; or an int8 pool, an odd head count "
    "or an uneven split of 64-wide heads, whose attention gathers). A "
    "family whose pool is not rows of head_size says its own "
    "(`kv_heads_per_pool_row`): every head, for a latent row.",
    type="gauge",
)
METRICS.describe(
    "substratus_serve_kv_bytes_per_token",
    "Bytes one token keeps in the page pool over all layers, as stored "
    "(k, v and their scales): 131,072 for Mistral-7B's 32 layers of 8 "
    "bfloat16 K and V heads of 128, 20,480 for 16 layers of one latent "
    "row stored 640 wide, 19,968 for 13 layers of such a row and an index "
    "key of 128 beside it. Set once where the pool is made; 0 for a "
    "family that keeps no page.",
    type="gauge",
)
METRICS.describe(
    "substratus_serve_kv_page_tokens",
    "Tokens one page of the pool holds, set where the pool is made: "
    "EngineConfig.page_size where given, else the family's "
    "(serve/paged_kv.py::page_tokens): 16, 64 and 128 where a stored row "
    "holds two heads of 64 (models/lfm2_moe.py, models/granitemoehybrid.py), "
    "128 for a latent pool (models/deepseek_v3.py). A page is what a "
    "block-table entry names, an attention kernel copies at once and the "
    "prefix registry shares.",
    type="gauge",
)
METRICS.describe(
    "substratus_serve_prefill_tokens_total",
    "Prompt tokens actually prefilled through the model (prefix-cache "
    "misses; the cold-work half of the reuse ratio).",
    type="counter",
)
METRICS.describe(
    "substratus_serve_prefix_hit_tokens_total",
    "Prompt tokens satisfied from shared prefix pages instead of "
    "recompute (paged layout, serve/paged_kv.py).",
    type="counter",
)
# A family whose paged cache holds per-slot state (PAGED_SLOT_STATE:
# models/exaone_moe.py's rings, models/lfm2_moe.py's convolution rows,
# models/brumby.py's retention state, models/granitemoehybrid.py's
# convolution rows and state-space state). Where it has an expert layer
# that may hold a share of the experts (it then has `step_counters`): what
# its forward counts, read with the step's tokens.
METRICS.describe(
    "substratus_serve_moe_pairs_total",
    "Token-expert pairs the router made, by whether the chosen expert is "
    "held by this program (held=true: multiplied here) or by another rank "
    "(held=false: left out of the partial sum). held / all is the share "
    "of the routed work that lands here.",
    type="counter",
)
METRICS.histogram(
    "substratus_serve_moe_expert_pairs_max",
    "The most token-expert pairs one held expert of one sparse layer "
    "received in a decode step (active slots only).",
    buckets=(1, 2, 4, 8, 16, 32, 64, 128, 256),
)
METRICS.describe(
    "substratus_serve_slot_state_bytes",
    "Bytes of per-slot state in the paged cache dict: every leaf beside "
    "the page pool (window layers' rings, convolution rows, a retention "
    "or state-space layer's state). Set once at start-up; 0 for a family "
    "that keeps pages alone.",
    type="gauge",
)
METRICS.histogram(
    "substratus_serve_window_rows_live_ratio",
    "Rows of a window layer's per-slot rings that hold a decoding "
    "sequence's history (min(context, window) a slot) / all ring rows, "
    "once per scheduler iteration that decodes.",
    buckets=RATIO_BUCKETS,
)
# Speculative-decoding effectiveness as true counters (rate()-able): the
# acceptance ratio accepted/proposed is the lever the adaptive per-stream
# draft length steers on (docs/performance.md "Speculative decoding").
METRICS.describe(
    "substratus_serve_spec_proposed_tokens_total",
    "Draft tokens proposed to speculative verify rounds (greedy streams "
    "only; placeholder rows and degraded streams do not count).",
    type="counter",
)
METRICS.describe(
    "substratus_serve_spec_accepted_tokens_total",
    "Proposed draft tokens the target model accepted (longest matching "
    "prefix of each verify round).",
    type="counter",
)
# Hot weight-swap (docs/serving.md "Zero-downtime rollout"): in-place
# param replacement on a live engine. Same shapes/dtypes/treedef means
# the compiled prefill/decode/verify executables are all kept.
METRICS.describe(
    "substratus_serve_weight_swaps_total",
    "Hot weight-swaps by outcome: applied (params replaced in place, "
    "compiled programs kept) or rejected (treedef/shape/dtype mismatch "
    "— the engine keeps serving the old weights).",
    type="counter",
)
METRICS.describe(
    "substratus_serve_weights_version",
    "Version of the parameter tree the engine is currently serving "
    "(bumped by Engine.swap_params; also on load_snapshot()/ /loadz).",
    type="gauge",
)


METRICS.describe(
    "substratus_serve_weights_relaid_bytes",
    "Bytes of weight leaves the engine laid out anew for its programs when "
    "it last took a parameter tree (Engine.serving_tree: the family's "
    "serving_layout, e.g. Llama's int8 q, k, v stacks turned heads-first; "
    "0 for a tree that came in the served form or a family with none).",
    type="gauge",
)


class EngineOverloaded(RuntimeError):
    """submit() rejected: the waiting queue is at its configured bound.

    Raised instead of queueing so callers can shed (HTTP 429 +
    Retry-After) — an unbounded queue converts overload into unbounded
    tail latency, which every client experiences as an outage anyway.
    `retry_after` estimates when a slot's worth of work will drain."""

    def __init__(self, queue_depth: int, retry_after: float = 1.0):
        super().__init__(
            f"engine overloaded: {queue_depth} requests already waiting"
        )
        self.queue_depth = queue_depth
        self.retry_after = retry_after


class _StagedSwap:
    """One pending hot weight-swap, staged by swap_params() from any
    thread and applied by the scheduler thread at its next
    _sync_iterate. The caller parks on `done`; `applied`/`error` carry
    the outcome back across the thread boundary (write-then-set
    ordering, same contract as Request.out)."""

    __slots__ = ("params", "version", "source", "done", "applied", "error")

    def __init__(self, params, version: Optional[int], source: str):
        self.params = params
        self.version = version
        self.source = source
        self.done = threading.Event()
        self.applied: Optional[int] = None
        self.error: Optional[BaseException] = None


@dataclass
class EngineConfig:
    max_batch: int = 8  # decode slots
    max_seq_len: int = 1024  # cache length per slot
    max_prefill_len: int = 512
    # Waiting-queue bound: submit() raises EngineOverloaded instead of
    # queueing beyond this many waiters. None = unbounded (legacy
    # behavior; serve.main defaults it to 4x max_batch).
    max_queue: Optional[int] = None
    # Disaggregated serving role (serve/disagg.py, ROADMAP item 3):
    # "both" = the monolithic engine (default); "prefill" = run chunked
    # prefill + first-token sampling, then export the request's KV pages
    # to a decode engine (requires a HandoffManager and the paged
    # layout); "decode" = accept migrated KV pages via submit_migration
    # and continue decoding (external submit() is rejected).
    role: str = "both"
    top_k: int = 0  # static top-k (0 = disabled)
    eos_token_id: int = 2
    # "model" keeps the cache in the model dtype; "int8" stores entries
    # quantized per-vector (llama family) — decode cache reads halve.
    kv_cache_dtype: str = "model"
    # KV memory layout: "paged" (block pool + per-slot block tables,
    # ops/kvcache.py — memory bounded by actual tokens, prefix sharing,
    # preempt-and-resume under pressure), "dense" (one max_seq_len region
    # per slot), or "auto" (paged when the model family supports it).
    kv_layout: str = "auto"
    # Tokens per KV page (paged layout). None = the family's: 16, or what
    # its module states (`PAGE_TOKENS`: 64 or 128 where a stored row holds
    # two heads of 64, 128 for a latent pool, whose rows are a twentieth of
    # a per-head page's); serve/paged_kv.py::page_tokens.
    page_size: Optional[int] = None
    # Total pool size in tokens (paged). None = max_batch * max_seq_len
    # (the dense footprint); set lower to oversubscribe slots against real
    # usage — the scheduler preempts (and later resumes) the youngest slot
    # if the pool runs dry mid-decode.
    kv_pool_tokens: Optional[int] = None
    prefix_cache: bool = True  # share full prompt-prefix pages across requests
    # Speculative decoding: a proposer guesses spec_k greedy tokens per
    # iteration and ONE target forward verifies all of
    # them — decode is HBM-bound, so accepted tokens amortize the weight
    # stream. With draft=(cfg, params) at Engine construction the proposer
    # is the draft model (paged layout only — the draft shares the
    # target's page tables); WITHOUT one it is prompt-lookup decoding
    # (layout-agnostic; the
    # continuation after the most recent match of the context's trailing
    # n-gram — zero extra model cost, wins on repetitive outputs:
    # summarization, RAG, code edits). Greedy slots stay token-exact
    # (longest matching prefix + correction); sampling slots take the
    # verify pass's position-0 sample (one token, plain-decode semantics).
    # 0 = off.
    spec_k: int = 0
    # Adaptive per-stream speculation (spec_k > 0): every greedy stream
    # carries an EWMA of its acceptance rate (accepted/k per verify
    # round, decay spec_ewma_decay); the stream's next draft length is
    # k = ceil(ewma * spec_k) in {1..spec_k} while the estimate holds
    # >= spec_threshold, and the stream degrades to a plain decode row
    # inside the same batch (k = 0: no proposals, it rides the verify's
    # position-0 greedy choice) when the estimate falls below — low-
    # acceptance traffic stops paying the (k+1)-wide verify tax.
    # Degraded streams re-probe with k = 1 every spec_probe_every
    # rounds so a stream whose output turns predictable again recovers.
    # spec_threshold 0 disables degradation (always propose spec_k).
    spec_threshold: float = 0.35
    spec_probe_every: int = 8
    spec_ewma_decay: float = 0.8
    # Overlapped decode scheduling (docs/performance.md "Overlapped
    # scheduling"): dispatch decode step N+1 — with step N's sampled
    # tokens fed back on-device — BEFORE reading step N's tokens to the
    # host, so the per-token host work (the read, emits, detokenize
    # downstream, EOS/window release, admission bookkeeping) runs while
    # the device computes. Steady-state inter-token latency becomes
    # max(device_step, host_work) instead of their sum. Speculative
    # rounds pipeline the same way: round N+1's proposal + verify
    # dispatch from round N's device-resident output (the accept-mask
    # advance), and the acceptance walk rides the deferred drain. None
    # = auto: on for single-host role=both/decode engines; off under
    # lockstep sync (the leader must emit host tokens before encoding
    # the gang's event broadcast — gangs run flush-per-step).
    # False forces the synchronous scheduler — the escape hatch.
    overlap: Optional[bool] = None
    # SLO thresholds (observability/sketch.py): emits over budget
    # increment substratus_slo_burn_total{slo=...}, and the mergeable
    # percentile sketches ride load_snapshot() so the gateway's fleet
    # aggregator (gateway/fleet.py) rolls them up fleet-wide.
    slo_ttft_s: float = 2.0
    slo_inter_token_s: float = 0.25
    # Request-journey forensics (observability/journey.py): per-request
    # lifecycle event ring size and the /debug/slowz exemplar ring of
    # SLO-breaching journeys. Recording is pure host work on the
    # scheduler thread (dispatch events stamp at drain), so it stays on
    # in production.
    journey_events: int = 256
    slow_journeys: int = 32


@dataclass
class Request:
    prompt_tokens: List[int]
    max_tokens: int = 64
    temperature: float = 0.0
    top_p: float = 1.0
    eos_token_id: Optional[int] = None
    # Multi-tenant serving (serve/adapters.py): the LoRA adapter id this
    # request decodes under; None = the base model (identity slot 0).
    # `adapter_slot` is engine bookkeeping — the store slot pinned for
    # this request between admission and release.
    adapter: Optional[str] = None
    adapter_slot: int = 0
    # Each generated token id is put on this queue; None marks completion.
    out: "queue.Queue[Optional[int]]" = field(default_factory=queue.Queue)
    id: str = ""
    # Set by the engine before the terminal None: "stop" (eos) or "length"
    # (max_tokens / context-window cap).
    finish_reason: str = "stop"
    # Cooperative cancellation: a consumer (e.g. the HTTP layer on a stop-
    # sequence match) sets this; the scheduler frees the slot at the next
    # emit instead of decoding to max_tokens.
    cancelled: bool = False
    # Multi-host lockstep bookkeeping (serve/multihost.py): the leader
    # latches `cancelled` into `cancel_latched` at an iteration boundary
    # and broadcasts the latch, so every process observes the
    # cancellation at the same step; `sync_id` names the request across
    # processes.
    cancel_latched: bool = False
    sync_id: Optional[int] = None
    # Telemetry (set by submit()/the scheduler): submission timestamp for
    # queue-wait/TTFT, previous-emit timestamp for inter-token latency, and
    # the submitter's span context so engine-side spans join the request's
    # trace. Followers in lockstep mode leave submit_ts at 0 (the wall
    # clocks aren't comparable across hosts) — their observations skip.
    submit_ts: float = 0.0
    last_emit_ts: float = 0.0
    trace_ctx: Optional[SpanContext] = None
    # Lifecycle event timeline (observability/journey.py): created at
    # submit (or KV-install on a decode-role engine) under the request's
    # trace id; the engine copies it into its JourneyLog at terminal.
    journey: Optional[RequestJourney] = None


@dataclass
class _InFlightStep:
    """Bookkeeping for one dispatched decode step whose host read is
    deferred (the overlapped scheduler's one-deep pipeline). `slots`
    pins the (slot, Request) pairs active at dispatch: a slot released
    before the drain (EOS/budget/cancel at the previous drain, or
    preemption) fails the identity check and its in-flight token — the
    pipeline's one wasted token per finished stream — is masked out
    before emit. `pos_next` snapshots host_positions as of THIS step so
    the context-window release check stays token-exact even after a
    further dispatch has advanced the live array."""

    tokens: Any  # device [B] int32 — this step's sampled tokens
    slots: List[tuple]  # [(slot, Request)] active at dispatch
    pos_next: np.ndarray  # host_positions after this step's increment
    t_dispatch: float = 0.0  # host perf_counter at launch (journey drain latency)
    stats: Any = None  # the model's per-step counters (device), or None


@dataclass
class _InFlightSpecStep:
    """Bookkeeping for one dispatched speculative round whose host read
    is deferred (the pipelined spec scheduler). The verify output stays
    device-resident: round N+1's dispatch chains its inputs off
    `choices`/`sampled` through the jitted accept-mask advance
    (_build_spec_advance) — a device-side data dependency, never a host
    round trip — and `_spec_drain` performs the round's ONE deferred
    read for the host acceptance walk + emits. Same one-step
    slot-release lag and identity-mask semantics as _InFlightStep.
    host_positions is advanced only by the drain, so at drain time it
    IS this round's base position (the emit snapshot)."""

    choices: Any  # device [B, width] int32 — per-position greedy argmax
    sampled: Any  # device [B] int32 — position-0 samples (sampling rows)
    props: Any  # [B, width-1] int32 proposals (device in draft mode,
    #   host numpy in lookup mode; width-1 may be 0 for a plain round)
    positions: Any  # this round's input positions (device when chained)
    k_eff: np.ndarray  # host [B] — per-stream draft length this round
    tried: np.ndarray  # host [B] bool — planned a proposal (EWMA decays
    #   on a lookup no-match even though k_eff was zeroed)
    greedy: np.ndarray  # host [B] bool — acceptance-walk rows
    slots: List[tuple]  # [(slot, Request)] active at dispatch
    t_dispatch: float = 0.0  # host perf_counter at launch (journey drain latency)


def _bucket(n: int, lo: int = 16) -> int:
    b = lo
    while b < n:
        b *= 2
    return b


def _pad_to_bucket(tokens, cap: int):
    """Right-pad a token list to its power-of-two bucket (capped): the one
    padding rule both the single-shot and chunked prefill paths share.
    Returns host numpy — jit converts, and under a multi-host mesh a
    numpy input is the one form every process can feed identically."""
    true_len = len(tokens)
    bucket = min(_bucket(true_len), cap)
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :true_len] = tokens
    return padded, true_len


class Engine:
    @startup_phase("startup.engine")
    def __init__(
        self,
        cfg: LlamaConfig,
        params: Params,
        ec: Optional[EngineConfig] = None,
        mesh=None,
        model=None,
        draft: Optional[tuple] = None,  # (draft_cfg, draft_params)
        sync=None,  # serve.multihost.StepSync for multi-host lockstep
        adapters=None,  # serve.adapters.AdapterStore for multi-tenant LoRA
        handoff=None,  # serve.disagg.HandoffManager for role="prefill"
        donate_params: bool = False,  # the caller keeps no use for `params`
        # (nor the draft's): a leaf the engine lays out anew is deleted as
        # soon as its new form is made (serving_tree)
    ):
        """model: the model-family module (models.llama, models.opt, ...)
        implementing forward/init_cache/param_logical_axes/cache_logical_axes;
        by default the family of `cfg`'s class (models/registry.py).

        adapters: an AdapterStore packing N tenants' LoRA adapters into
        one engine — every jitted function gains (lora_tree, adapter_ids)
        inputs and each batch row gathers its own adapter by slot index,
        so a mixed-tenant batch runs in the single compiled program.

        mesh: optional jax Mesh for sharded serving. Params are laid out
        by parallel.sharding.serve_rules_for(mesh) (tensor-parallel
        heads/mlp/vocab, data-parallel batch, and — when the mesh has a
        "sequence" axis — the dense KV cache's length dim for serving-
        side context parallelism); the KV cache shards the same way, so
        decode collectives ride ICI. Constraint: the tensor axis must
        divide n_kv_heads (llama2-70b: KH=8 => tensor<=8 per replica).

        sync: serve.multihost.StepSync for multi-host lockstep serving —
        process 0 owns HTTP + the queue and broadcasts per-iteration
        events; followers mirror the scheduler (see serve/multihost.py)."""
        import dataclasses as _dc

        # Copy the config before clamping: mutating a caller's (or the
        # default) EngineConfig instance would leak between engines.
        ec = _dc.replace(ec) if ec is not None else EngineConfig()
        self.cfg, self.params, self.ec = cfg, params, ec
        if model is None:
            model = registry.module_of(cfg)
        self.model = model
        # The cache may never outrun the model's position space (learned
        # position embeddings silently clamp on OOB lookups), and a prefill
        # fragment must fit in the cache.
        ec.max_seq_len = min(ec.max_seq_len, cfg.max_seq_len)
        ec.max_prefill_len = min(ec.max_prefill_len, ec.max_seq_len)
        B, S = ec.max_batch, ec.max_seq_len

        if ec.kv_cache_dtype not in ("model", "int8"):
            raise ValueError(
                f"kv_cache_dtype {ec.kv_cache_dtype!r} invalid "
                "(expected 'model' or 'int8')"
            )
        if ec.role not in ("both", "prefill", "decode"):
            raise ValueError(
                f"role {ec.role!r} invalid (both|prefill|decode)"
            )
        if ec.role != "both" and sync is not None:
            raise ValueError(
                "disaggregated roles are incompatible with lockstep sync "
                "(a gang engine is one replica; split pools across gangs)"
            )
        if ec.max_prefill_len < 1 or ec.max_batch < 1 or ec.max_seq_len < 2:
            raise ValueError(
                f"invalid engine config: max_prefill_len={ec.max_prefill_len} "
                f"max_batch={ec.max_batch} max_seq_len={ec.max_seq_len}"
            )
        self.adapters = adapters
        if adapters is not None and not getattr(
            model, "SUPPORTS_INDEXED_LORA", False
        ):
            raise ValueError(
                f"multi-tenant adapters unsupported for {model.__name__}"
            )

        kv_int8 = ec.kv_cache_dtype == "int8"
        if kv_int8 and not getattr(model, "SUPPORTS_INT8_KV", False):
            raise ValueError(
                f"kv_cache_dtype=int8 unsupported for {model.__name__}"
            )
        cache_dtype = jnp.int8 if kv_int8 else None

        layout = ec.kv_layout
        if layout == "auto":
            layout = (
                "paged" if getattr(model, "SUPPORTS_PAGED", False) else "dense"
            )
        if layout not in ("paged", "dense"):
            raise ValueError(f"kv_layout {layout!r} invalid")
        if layout == "paged" and not getattr(model, "SUPPORTS_PAGED", False):
            raise ValueError(
                f"kv_layout=paged unsupported for {model.__name__}"
            )
        self.paged = layout == "paged"
        if not self.paged and not hasattr(model, "init_cache"):
            raise ValueError(
                f"kv_layout=dense unsupported for {model.__name__}"
            )
        # A family whose paged cache holds per-slot state: state addressed
        # by decode slot beside the pages or in their place (window layers'
        # rings, convolution layers' input rows, a retention or
        # state-space layer's matrix). The engine tells its forward which
        # slot a row is and which tokens are real, and takes its per-step
        # counters where it has any; pages alone do not carry such a
        # sequence, so what moves or shares pages is refused or off.
        self.slot_state = self.paged and getattr(
            model, "PAGED_SLOT_STATE", False
        )
        if self.slot_state and (ec.role != "both" or ec.spec_k):
            raise ValueError(
                f"{model.__name__} keeps per-slot state that no page "
                "carries: disaggregated roles and speculative decoding are "
                "unsupported"
            )
        # What a pages-only family has not written (or tested) for its pages
        # is refused by its name, never found by a user: a verify round of
        # speculation, the page handoff of the disaggregated roles.
        if ec.spec_k and not getattr(model, "SUPPORTS_SPECULATION", True):
            raise ValueError(
                f"speculative decoding is unsupported for {model.__name__}"
            )
        if ec.role != "both" and not getattr(model, "SUPPORTS_ROLES", True):
            raise ValueError(
                f"role={ec.role!r} is unsupported for {model.__name__}: "
                "its pages have no handoff"
            )
        if ec.role != "both" and not self.paged:
            # The handoff ships pool pages; the dense slot cache has no
            # page-granular export.
            raise ValueError(
                f"role={ec.role!r} requires the paged kv layout"
            )
        self.handoff = handoff
        if ec.role == "prefill":
            if handoff is None:
                raise ValueError(
                    "role='prefill' needs a serve.disagg.HandoffManager"
                )
            handoff.bind_engine(self)

        # The door: the tree in the form the programs read it, before it is
        # sharded, compared with a swap's or met by a compile.
        with startup_phase("engine.build.layout") as span:
            self.params = params = self.serving_tree(
                params, donate=donate_params, span=span
            )
            jax.block_until_ready(params)  # the phase times the transposes

        self.mesh = mesh
        if mesh is not None:
            from substratus_tpu.parallel.sharding import (
                serve_rules_for, shard_tree,
            )

            self._serve_rules = serve_rules_for(mesh)
            self.params = shard_tree(
                params, mesh, self._param_axes(params, cfg),
                self._serve_rules,
            )

        # The pool, the rings and the per-slot state: what the engine
        # allocates on the device (docs/observability.md "Start-up").
        with startup_phase("engine.build.cache"):
            if self.paged:
                from substratus_tpu.serve.paged_kv import (
                    PageAllocator,
                    PrefixRegistry,
                    SlotPages,
                    page_tokens,
                )

                bs = page_tokens(model, ec.page_size)
                if bs < 1:
                    raise ValueError(f"page_size {bs} invalid")
                if ec.kv_pool_tokens is not None and ec.kv_pool_tokens < 1:
                    raise ValueError(
                        f"kv_pool_tokens {ec.kv_pool_tokens} invalid"
                    )
                # A single full-length sequence (+ its pad slot) must always
                # fit.
                pool_tokens = (
                    B * S if ec.kv_pool_tokens is None else ec.kv_pool_tokens
                )
                pool_tokens = max(pool_tokens, S + bs)
                self.page_size = bs
                self.n_pages = -(-pool_tokens // bs)
                self.max_pages = -(-S // bs)  # block-table width per slot
                # Physical page 0 is the trash page: idle slots' decode writes
                # land there (their block-table rows are zero), never in a live
                # page. The allocator hands out ids 1..n_pages.
                pool = model.init_paged_cache(
                    cfg, self.n_pages + 1, bs, dtype=cache_dtype,
                    kv_shards=kvcache.kv_head_shards(mesh),
                    **({"slots": B} if self.slot_state else {}),
                )
                heads_per_row = getattr(model, "kv_heads_per_pool_row", None)
                METRICS.set(
                    "substratus_serve_kv_heads_per_pool_row",
                    heads_per_row(cfg, pool) if heads_per_row
                    else pool["k"].shape[4] // cfg.head_size,
                )
                METRICS.set(
                    "substratus_serve_kv_bytes_per_token",
                    sum(a.nbytes for name, a in pool.items()
                        if name in ("k", "v", "k_scale", "v_scale"))
                    // ((self.n_pages + 1) * bs),
                )
                METRICS.set("substratus_serve_kv_page_tokens", bs)
                METRICS.set(
                    "substratus_serve_slot_state_bytes",
                    sum(a.nbytes for name, a in pool.items()
                        if name not in ("k", "v", "k_scale", "v_scale")),
                )
                # Layers that keep pages: none for a family whose every layer
                # keeps per-slot state, and no attention then reads a page.
                self._page_layers = pool["k"].shape[0]
                if mesh is not None:
                    pool = shard_tree(
                        pool,
                        mesh,
                        model.paged_cache_logical_axes(cfg, quantized=kv_int8),
                        self._serve_rules,
                    )
                self.cache = pool
                self.block_table = np.zeros((B, self.max_pages), np.int32)
                self.alloc = PageAllocator(self.n_pages, first_page=1)
                # Shared pages cannot hand a layer with per-slot state its rows
                # at the prefix boundary: for such a family the registry is
                # off, and stats["prefix_reuse_refused"] counts the admissions
                # it would have looked up.
                self.prefix = (
                    PrefixRegistry(self.alloc)
                    if ec.prefix_cache and not self.slot_state else None
                )
                self.slot_pages = SlotPages(B)
            elif mesh is not None:
                self.cache = shard_tree(
                    model.init_cache(cfg, B, S, dtype=cache_dtype),
                    mesh,
                    model.cache_logical_axes(cfg, quantized=kv_int8),
                    self._serve_rules,
                )
            else:
                self.cache = model.init_cache(cfg, B, S, dtype=cache_dtype)
        # Small per-step state lives as HOST numpy and is fed into the
        # jitted functions each call (jit treats numpy inputs as
        # replicated — in multi-host lockstep serving every process feeds
        # the identical value, which is exactly the contract). The RNG key
        # is carried as raw key data for the same reason; the jitted fns
        # wrap/unwrap it at the boundary.
        self.tokens = np.zeros((B,), np.int32)
        self.positions = np.zeros((B,), np.int32)
        self.temps = np.zeros((B,), np.float32)
        self.top_ps = np.ones((B,), np.float32)
        # Per-row adapter slot fed into every jitted call (0 = identity);
        # slot_adapter mirrors the pins so release can unpin.
        self.adapter_ids = np.zeros((B,), np.int32)
        self.slot_adapter: List[int] = [0] * B
        self.key = np.asarray(jax.random.key_data(jax.random.key(0)))

        # Host-side slot bookkeeping (scheduler thread only). host_positions
        # mirrors the device positions array so per-token checks never force
        # a device->host scalar read.
        self.slot_req: List[Optional[Request]] = [None] * B
        self.slot_generated: List[int] = [0] * B
        self.active = np.zeros(B, dtype=bool)
        self.host_positions = np.zeros(B, dtype=np.int64)
        # Emitted tokens per slot (paged preempt-and-resume rebuilds the
        # prompt from these) and admission order (preemption picks the
        # youngest victim, vLLM-style LIFO).
        self.slot_tokens: List[List[int]] = [[] for _ in range(B)]
        self.slot_admit_seq: List[int] = [0] * B
        self._admit_counter = 0
        # Requests to re-admit before the queue: preempted slots (front)
        # and admission backpressure (pool dry at prefill time).
        self._resume: List[Request] = []
        self._chunk_stats: List[Any] = []
        self.stats: Dict[str, int] = {
            "prefill_tokens": 0,
            "prefix_hit_tokens": 0,
            "preemptions": 0,
            "truncated_by_pool": 0,
            "max_active": 0,
            "verify_passes": 0,
            "spec_proposed": 0,
            "spec_accepted": 0,
            "adapter_requests": 0,
            "handoffs": 0,
            "migrations_in": 0,
            # Added to once per scheduler iteration that decodes (paged
            # layout): a window's mean live share of the pool is the
            # ratio of the two deltas.
            "kv_live_pages_sum": 0,
            "kv_pool_pages_sum": 0,
            # Same iterations: pages a decode step's attention needs
            # (positions // page_size + 1 a decoding slot, 1 an idle row;
            # none where the pool has no layer) and max_batch x max_pages,
            # the table it would gather whole.
            "decode_kv_pages_read_sum": 0,
            "decode_kv_pages_table_sum": 0,
            # Added to once per chunk dispatch through a block-table row
            # (_run_chunks): pages the chunk's attention needs (its last
            # query position // page_size + 1) and max_pages, the row of
            # the table a gather reads whole.
            "prefill_kv_pages_read_sum": 0,
            "prefill_kv_pages_table_sum": 0,
            # The context attention works through, in tokens, from what is
            # handed to the program in the same call: a decode step's sum
            # over decoding slots of position + 1 (_count_step); a chunk's
            # last query position + 1, and the chunks dispatched
            # (_run_chunks).
            "decode_ctx_tokens_sum": 0,
            "chunk_ctx_tokens_sum": 0,
            "chunk_count": 0,
            # Decode steps and verify rounds dispatched, and those of
            # them whose `temps` held a row above 0: what
            # ops/sampling.py::sample branches on, so the steps that paid
            # the sort over the vocabulary and not the argmax alone.
            "decode_steps": 0,
            "decode_steps_sampled": 0,
        }
        # What of the per-slot state this engine observes, read off the
        # family and its cache: rows of history a ring keeps a slot (0: the
        # family has no ring), and whether chunks carry convolution rows.
        self._ring_rows = (
            model.slot_rows(cfg)
            if self.slot_state and hasattr(model, "slot_rows") else 0
        )
        self._conv_state = self.slot_state and kvcache.CONV_STATE in self.cache
        if self.slot_state:
            self.stats.update({
                "prefix_reuse_refused": 0,
                # per decoding iteration (_iterate): slots decoding, and
                # max_batch, the rows of state a step over every slot moves
                "state_rows_live_sum": 0,
                "state_rows_sum": 0,
            })
        # A family whose layers pick the rows a query attends by a learned
        # index (ops/sparse_index.py) says how many a set holds; 0: every
        # live row is attended and the counters below do not exist.
        self._index_topk = (
            getattr(model, "index_topk", lambda cfg: 0)(cfg)
            if self.paged else 0
        )
        if self._index_topk:
            self.stats.update({
                # per decode step (_count_step), over the decoding slots
                # and once, not per layer: the rows a slot's sequence has
                # kept (position + 1) and those its query attends (at most
                # index_topk of them); and the selections the step ran, one
                # a decoding slot and layer
                "dsa_rows_live_sum": 0,
                "dsa_rows_attended_sum": 0,
                "dsa_selections": 0,
            })
        self._state_kernel = False
        for leaf, takes_kernel in (
            (kvcache.RET_S, kvcache.retention_step_takes_kernel),
            (kvcache.SSM_STATE, kvcache.ssm_step_takes_kernel),
        ):
            if self.slot_state and leaf in self.cache:
                # Decoding iterations whose program moved the recurrent
                # state through its Pallas kernel (ops/retention_kernel.py,
                # ops/ssd_kernel.py: every head's S once in, once out) and
                # not through XLA's two reads and a write: decided once, by
                # what ops/kvcache.py reads off the state for the decode
                # program, so all of them or none.
                self._state_kernel = takes_kernel(self.cache[leaf])
                self.stats["state_kernel_steps"] = 0
        # A family whose forward counts over its real tokens is told which
        # they are (`valid`), with or without per-slot state.
        self._step_counts = self.paged and hasattr(model, "step_counters")
        self._tells_valid = self.slot_state or self._step_counts
        if self._step_counts:
            # What such a family's forward counts where it has an expert
            # layer (models/hybrid.py::COUNTERS), summed over decode steps
            # and prefill chunks as they are drained.
            self.stats.update({
                "moe_pairs_held": 0,
                "moe_pairs_all": 0,
                "moe_decode_steps": 0,
                "moe_decode_pairs_held": 0,
                "moe_decode_expert_pairs_max_sum": 0,
            })
        if self._ring_rows:
            # window rows are counted on the host, per decoding iteration
            # like the pages above
            self.stats.update({
                "window_rows_live_sum": 0,
                "window_rows_cap_sum": 0,
            })
        if self._conv_state:
            # per chunk dispatch (_run_chunks): chunks in all, and those
            # that began at offset > 0, so from the rows the chunk before
            # left
            self.stats.update({
                "conv_chunks_sum": 0,
                "conv_chunks_resumed_sum": 0,
            })

        # Speculative decoding state. The draft pool shares the target's
        # block tables and page allocation: identical page ids index both
        # pools, and prefix-shared pages hold identical draft KV because
        # shared prefixes are identical prompts (draft prefill always runs
        # over the full prompt, so reused target pages regain their draft
        # entries too).
        if ec.spec_k < 0:
            raise ValueError(f"spec_k {ec.spec_k} invalid")
        self.spec = bool(ec.spec_k)
        # Adaptive per-stream draft length (EngineConfig.spec_threshold):
        # per-slot acceptance-rate EWMA (optimistic 1.0 at admission so
        # new streams start at full spec_k) and the degraded-round
        # counter that paces re-probes. Scheduler-thread state; the
        # load_snapshot read races benignly (torn floats, never torn
        # structure).
        self._spec_ewma = np.ones((B,), np.float64)
        self._spec_degraded = np.zeros((B,), np.int64)
        # draft model proposer, or prompt-lookup when no draft is given
        self.spec_draft = self.spec and draft is not None
        if self.spec_draft and not self.paged:
            # The draft shares the target's page tables; a dense draft
            # cache has no insert path. Prompt-lookup speculation is
            # layout-agnostic (host-side proposals + a multi-token
            # verify).
            raise ValueError("draft-model spec_k requires the paged kv layout")
        if self.spec_draft:
            self.draft_cfg, draft_params = draft
            self.draft_params = draft_params = self.serving_tree(
                draft_params, self.draft_cfg, donate=donate_params
            )
            if mesh is not None:
                from substratus_tpu.parallel.sharding import shard_tree

                self.draft_params = shard_tree(
                    draft_params, mesh,
                    self._param_axes(draft_params, self.draft_cfg),
                    self._serve_rules,
                )
            # Same KV dtype as the target pool: an int8 configuration means
            # int8 for the draft's (larger-per-token-count) traffic too.
            with startup_phase("engine.build.draft_cache"):
                draft_pool = model.init_paged_cache(
                    self.draft_cfg, self.n_pages + 1, self.page_size,
                    dtype=cache_dtype, kv_shards=kvcache.kv_head_shards(mesh),
                )
                if mesh is not None:
                    draft_pool = shard_tree(
                        draft_pool, mesh,
                        model.paged_cache_logical_axes(
                            self.draft_cfg, quantized=kv_int8
                        ),
                        self._serve_rules,
                    )
                self.draft_cache = draft_pool

        self.queue: "queue.Queue[Request]" = queue.Queue()
        # Pull-based admission fast-path (serve/batchgen.py): when set,
        # the scheduler thread pulls the next request DIRECTLY from the
        # source the moment a slot frees — no submit() thread handoff,
        # no queue-wait round trip — which is what keeps an offline
        # batch-generation run's decode batch permanently full. The
        # queue path stays live alongside it (sources only top up).
        self.source = None
        # Migrated-request admission (serve/disagg.py): the HandoffServer
        # enqueues from its connection threads; only the scheduler thread
        # consumes. Held-back migrations (pool dry / adapter pinned) wait
        # in _resume_migrations, in front of fresh ones.
        self._migrations: "queue.Queue" = queue.Queue()
        self._resume_migrations: List = []
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self.error: Optional[BaseException] = None
        self._admitting: Optional[Request] = None
        self._first_decode_done = False
        # Hot weight-swap (docs/serving.md "Zero-downtime rollout"):
        # swap_params() stages _StagedSwap objects here from any thread;
        # only the scheduler thread installs them (at _sync_iterate, on
        # a settled pipeline), so self.params keeps its single-writer
        # contract. weights_version is scheduler-written, snapshot-read.
        self._swap_q: "queue.Queue[_StagedSwap]" = queue.Queue()
        self.weights_version = 0

        # Multi-host lockstep (serve/multihost.py). The sync'd request
        # list replaces the thread-safe queue as the scheduler's source:
        # requests enter it only through _sync_iterate, identically on
        # every process.
        self.sync = sync if (sync is not None and sync.num_processes > 1) else None
        self._sync_seq = 0
        self._sync_reqs: Dict[int, Request] = {}
        self._synced: List[Request] = []

        # Overlapped decode scheduling (one-step-ahead dispatch; see
        # EngineConfig.overlap). Resolution order matters: lockstep
        # gangs run flush-per-step regardless of the config — the event
        # broadcast must observe a settled batch — and a prefill-role
        # engine never decodes at all. Speculative engines DO overlap:
        # the verify round chains on-device through the accept-mask
        # advance, so the two levers multiply instead of cancelling.
        overlap = ec.overlap if ec.overlap is not None else True
        self.overlap = bool(
            overlap
            and ec.role != "prefill"
            and self.sync is None
        )
        # One in-flight step (plain _InFlightStep or _InFlightSpecStep),
        # the pipeline's one-deep queue.
        self._pending = None
        # Device-resident copy of the last dispatched step's sampled
        # tokens (the on-device feedback path) and the per-slot "the
        # host value is newer" mask: admission writes a first token the
        # device hasn't seen, so the next dispatch merges host values
        # for fresh slots over device values for continuing ones.
        self._dev_tokens = None
        self._token_fresh = np.ones((B,), bool)
        self._merge_tokens = jax.jit(
            lambda dev, host, fresh: jnp.where(fresh, host, dev)
        )
        # Idle wake-up: submit()/resubmit()/submit_migration()/
        # set_source()/stop() set this so an idle scheduler admits
        # immediately instead of on the next poll tick; _idle_wait_s is
        # the safety-net re-check period (tests stretch it to prove the
        # event path carries first-token latency).
        self._wake = threading.Event()
        self._idle_wait_s = 0.05

        # Step timeline + SLO telemetry (observability/timeline.py,
        # observability/sketch.py): one bounded flight recorder per
        # engine (written only by the scheduler thread; /debug/stepz
        # and the bench read it), one SLO tracker fed from _emit whose
        # sketches ride load_snapshot() to the gateway's fleet
        # aggregator. Every scheduler phase is timed once, by
        # self.timeline.phase(): the recorder, the phase histogram and a
        # profiler capture's engine.* spans share that measurement.
        self.timeline = StepTimeline()
        self.slo = SLOTracker({
            "ttft": ec.slo_ttft_s,
            "inter_token": ec.slo_inter_token_s,
        })
        # Request-journey retention (observability/journey.py): completed
        # journeys for /debug/requestz?id= and the SLO-breach exemplar
        # ring for /debug/slowz. Both lock-guarded: the scheduler (and,
        # for prefill engines, the handoff manager's reader thread) add
        # while HTTP handler threads search.
        self.journey_log = JourneyLog()
        self.slow = SlowRing(ec.slow_journeys)
        # Per-replica monotonic load-report sequence (gateway dedupe of
        # hedged/retried report deliveries): itertools.count is
        # atomic under the GIL, and load_snapshot() is called from
        # HTTP handler threads concurrently.
        self._load_seq = itertools.count(1)

        # The jitted closures only: each is traced, lowered and compiled
        # (or read from the cache) at its first launch, where its jax.*
        # spans hang under the request or iteration that launched it.
        with startup_phase("engine.build.programs"):
            self._decode_fn = self._build_decode()
            self._sample1_fn = self._build_first_sample()
            self._chunk_fn = partial(
                self._chunk_prefill_jit, self.model, self.cfg)
            if self.spec_draft:
                self._draft_chunk_fn = partial(
                    self._chunk_prefill_jit, self.model, self.draft_cfg
                )
                self._propose_fn = self._build_propose(ec.spec_k)
                # Width-1 rounds (every stream degraded/sampling) still run
                # one draft step so the draft cache stays hole-free — the
                # next wide round's proposal history needs every position
                # below its start written (the proposals are discarded).
                self._propose1_fn = self._build_propose(1)
            if self.spec:
                self._verify_fn = self._build_verify()
                self._spec_advance = self._build_spec_advance()
            if not self.paged:
                self._prefill_fn = partial(
                    self._prefill_jit, self.model, self.cfg)
                self._insert_fn = self._build_insert()
                self._extract_slot, self._restore_slot = self._build_slot_io()
            else:
                self._export_fn, self._import_fn = self._build_page_io()

    # --- jitted device functions -----------------------------------------

    @staticmethod
    def _lora_kw(lora, adapter_ids) -> dict:
        """forward() kwargs for the multi-tenant adapter gather — empty
        when adapters are off, so families without the lora/adapter_ids
        kwargs (and engines without a store) trace exactly as before."""
        if lora is None:
            return {}
        return {"lora": lora, "adapter_ids": adapter_ids}

    @staticmethod
    @partial(jax.jit, static_argnums=(0, 1))
    def _prefill_jit(model, cfg, params, tokens, true_len, lora=None,
                     adapter_ids=None):
        """tokens [1, Sbucket] (right-padded); returns kv fragment + last
        real token's logits."""
        s = tokens.shape[1]
        positions = jnp.arange(s, dtype=jnp.int32)[None, :]
        logits, kv = model.forward(
            params, tokens, cfg, positions=positions,
            **Engine._lora_kw(lora, adapter_ids),
        )
        last = logits[0, true_len - 1]
        return last, kv

    @staticmethod
    @partial(jax.jit, static_argnums=(0, 1), donate_argnums=(3,))
    def _chunk_prefill_jit(model, cfg, params, slot_cache, tokens, offset,
                           true_len, block_table=None, lora=None,
                           adapter_ids=None, slot=None):
        """One chunk of a long prefill: tokens [1, C] (right-padded) written
        at absolute positions offset..offset+C-1 — into a single-slot dense
        cache, or through a block-table row [1, M] into the paged pool
        (`slot`: the decode slot, for a family with per-slot state).
        Returns (logits of the last real token, updated cache, the model's
        counters for the chunk or None)."""
        c = tokens.shape[1]
        positions = offset + jnp.arange(c, dtype=jnp.int32)[None, :]
        # Padded tail positions all clamp onto the single slot one past the
        # prompt: real queries never attend it (causal mask), and the first
        # decode step writes that exact slot before reading it. The caller
        # keeps prompts <= max_seq_len - 1 so the slot exists (paged: and
        # allocates pages through that slot).
        positions = jnp.minimum(positions, offset + true_len)
        kw = {} if block_table is None else {"block_table": block_table}
        kw.update(Engine._lora_kw(lora, adapter_ids))
        if slot is not None:
            if getattr(model, "PAGED_SLOT_STATE", False):
                kw["slots"] = jnp.reshape(slot, (1,)).astype(jnp.int32)
            kw["valid"] = jnp.arange(c)[None, :] < true_len
        logits, slot_cache = model.forward(
            params, tokens, cfg, positions=positions, cache=slot_cache, **kw
        )
        stats = Engine._pop_step_stats(model, slot_cache)
        return logits[0, true_len - 1], slot_cache, stats

    @staticmethod
    def _pop_step_stats(model, cache):
        """A slot-state family's forward leaves its counters in the cache
        dict: its `step_counters` takes them out (inside the jit) before
        the cache is carried on. None for a family that counts nothing."""
        take = getattr(model, "step_counters", None)
        return take(cache) if take else None

    def _build_propose(self, k: int):
        model, cfg = self.model, self.draft_cfg

        @partial(jax.jit, donate_argnums=(1,))
        def propose(params, cache, block_table, tokens, positions):
            """Draft k greedy tokens for the whole batch: k cheap decode
            steps through the draft's paged pool. Returns (proposals
            [B, k] replicated for the host read, cache)."""

            def step(carry, _):
                cache, tok, pos = carry
                logits, cache = model.forward(
                    params, tok[:, None], cfg, positions=pos[:, None],
                    cache=cache, block_table=block_table,
                )
                nxt = logits[:, 0].argmax(-1).astype(jnp.int32)
                return (cache, nxt, pos + 1), nxt

            (cache, _, _), props = jax.lax.scan(
                step, (cache, tokens, positions), None, length=k
            )
            return self._replicated(jnp.swapaxes(props, 0, 1)), cache

        return propose

    def _replicated(self, *xs):
        """Pin small outputs that the scheduler reads back to host to a
        fully-replicated layout. Under a (multi-host) mesh the compiler is
        otherwise free to leave them sharded, which would make
        np.asarray() on them non-addressable on some process; without a
        mesh this is a no-op constraint."""
        if self.mesh is None:
            return xs if len(xs) > 1 else xs[0]
        from jax.sharding import NamedSharding, PartitionSpec as P

        rep = NamedSharding(self.mesh, P())
        out = tuple(jax.lax.with_sharding_constraint(x, rep) for x in xs)
        return out if len(out) > 1 else out[0]

    def _build_verify(self):
        cfg, ec, model, paged = self.cfg, self.ec, self.model, self.paged

        @partial(jax.jit, donate_argnums=(1,))
        def verify(params, cache, block_table, tokens, props, positions0,
                   temps, top_ps, key_data, lora=None, adapter_ids=None):
            """ONE target forward over [last, d1..dk] per slot
            ([B, k+1]); `tokens` and `props` arrive separately (tokens
            may be the previous round's device-resident output — the
            concat is a device op, never a host round trip). A width-1
            call (props [B, 0]) IS a plain decode step: one position,
            choices[:, 0] the greedy token — which is what lets
            degraded/sampling rounds share this code path with no
            pipeline flush. Returns (greedy choices [B, k+1],
            position-0 samples [B] for sampling slots, cache, key
            data)."""
            block_tokens = jnp.concatenate(
                [tokens[:, None], props.astype(jnp.int32)], axis=1
            )
            s = block_tokens.shape[1]
            positions = (
                positions0[:, None]
                + jnp.arange(s, dtype=jnp.int32)[None, :]
            )
            logits, cache = model.forward(
                params, block_tokens, cfg, positions=positions, cache=cache,
                **({"block_table": block_table} if paged else {}),
                **Engine._lora_kw(lora, adapter_ids),
            )
            with jax.named_scope(scopes.SAMPLE):
                choices = logits.argmax(-1).astype(jnp.int32)
                key, subkey = jax.random.split(
                    jax.random.wrap_key_data(key_data)
                )
                sampled = sample(
                    logits[:, 0], subkey, temps, top_k=ec.top_k, top_p=top_ps
                )
                choices, sampled, kd = self._replicated(
                    choices, sampled, jax.random.key_data(key)
                )
            return choices, sampled, cache, kd

        return verify

    def _build_spec_advance(self):
        """The pipelined spec scheduler's on-device token feedback: from
        an UNDRAINED verify round's device outputs, compute the next
        round's (tokens, positions) without reading anything back — the
        accept-mask analogue of _merge_tokens. Replays the host
        acceptance walk as vectorized device ops: per greedy row the
        longest matching proposal prefix, full acceptance advancing
        k_eff with the last proposal as the seed (no bonus token — the
        draft never wrote its kv), a mismatch advancing accepted+1 with
        the verify's correction; sampling and degraded rows advance one
        position. Freshly admitted rows take the host values admission
        wrote (same `jnp.where(fresh, host, dev)` idiom as plain
        overlap). Shapes are static per verify width, so each width
        traces once."""
        max_pos = self.ec.max_seq_len - 1

        @jax.jit
        def advance(choices, sampled, props, k_eff, greedy, pos0,
                    host_tokens, host_positions, fresh):
            kmax = props.shape[1]
            if kmax > 0:
                m = props == choices[:, :-1]
                valid = (
                    jnp.arange(kmax, dtype=jnp.int32)[None, :]
                    < k_eff[:, None]
                )
                run = jnp.cumprod(
                    (m & valid).astype(jnp.int32), axis=1
                )
                accepted = run.sum(axis=1).astype(jnp.int32)
                full = (accepted == k_eff) & (k_eff > 0)
                last_prop = jnp.take_along_axis(
                    props, jnp.maximum(k_eff - 1, 0)[:, None], axis=1
                )[:, 0]
                corr = jnp.take_along_axis(
                    choices, accepted[:, None], axis=1
                )[:, 0]
                adv_greedy = jnp.where(full, k_eff, accepted + 1)
                tok_greedy = jnp.where(full, last_prop, corr)
            else:
                # Width-1 round: nothing proposed anywhere — every row
                # is a plain decode row this round.
                adv_greedy = jnp.ones_like(k_eff)
                tok_greedy = choices[:, 0]
            adv = jnp.where(greedy, adv_greedy, 1)
            tok = jnp.where(greedy, tok_greedy, sampled).astype(jnp.int32)
            nxt = jnp.minimum(pos0 + adv, max_pos).astype(jnp.int32)
            tok = jnp.where(fresh, host_tokens, tok)
            nxt = jnp.where(fresh, host_positions, nxt)
            return tok, nxt

        return advance

    def _build_slot_io(self):
        @jax.jit
        def extract(cache, slot):
            return jax.tree.map(
                lambda x: jax.lax.dynamic_slice_in_dim(x, slot, 1, axis=1),
                cache,
            )

        @partial(jax.jit, donate_argnums=(0,))
        def restore(cache, slot_cache, slot):
            return jax.tree.map(
                lambda full, one: jax.lax.dynamic_update_slice_in_dim(
                    full, one, slot, axis=1
                ),
                cache,
                slot_cache,
            )

        return extract, restore

    def _build_page_io(self):
        """Page-granular pool I/O for the disaggregated handoff
        (serve/disagg.py): export gathers a request's pages out of the
        pool, import scatters transferred pages into freshly allocated
        ones. `ids` is bucket-padded by the caller (padding ids point at
        the trash page, physical page 0) so each power-of-two page count
        compiles once."""
        from substratus_tpu.ops.quant import dequantize_kv, quantize_kv

        hd = self.cfg.head_size

        # Pages cross the wire in their logical shape [L, n, bs, KH, hd],
        # whatever row the pool stores (ops/kvcache.py::init_paged_cache):
        # the same bytes, and an int8 pool's scale is per hd vector.
        def stored(pages, like):
            return pages.reshape(pages.shape[:2] + like.shape[2:])

        @jax.jit
        def export(cache, ids):
            out = {key: jnp.take(cache[key], ids, axis=1) for key in cache}
            for name in ("k", "v"):
                out[name] = out[name].reshape(out[name].shape[:3] + (-1, hd))
            return {key: self._replicated(a) for key, a in out.items()}

        @partial(jax.jit, static_argnums=(0,), donate_argnums=(1,))
        def import_(convert, cache, ids, frag):
            out = dict(cache)
            if convert == "quantize":
                # Model-dtype pages arriving at an int8 pool: the same
                # per-vector quantization the pool's own writes use.
                for name in ("k", "v"):
                    q, s = quantize_kv(frag[name])
                    out[name] = cache[name].at[:, ids].set(q)
                    out[f"{name}_scale"] = (
                        cache[f"{name}_scale"].at[:, ids].set(s)
                    )
            elif convert == "dequantize":
                for name in ("k", "v"):
                    vals = dequantize_kv(
                        frag[name], frag[f"{name}_scale"],
                        cache[name].dtype,
                    )
                    out[name] = cache[name].at[:, ids].set(
                        stored(vals, cache[name]))
            else:
                for name in cache:
                    out[name] = cache[name].at[:, ids].set(
                        stored(frag[name].astype(cache[name].dtype),
                               cache[name])
                    )
            return out

        return export, import_

    def _build_insert(self):
        @partial(jax.jit, donate_argnums=(0,))
        def insert(cache, kv, slot):
            # kv: {k, v} fragment [L, 1, Sb, KH, hd] (activation layout,
            # bf16 from prefill) -> cache layout (quantized when int8),
            # written into cache[:, slot, :, :Sb].
            from substratus_tpu.ops.decode_attention import pack_fragment

            frag = pack_fragment(cache, kv)
            return {
                key: jax.lax.dynamic_update_slice(
                    cache[key], frag[key],
                    (0, slot) + (0,) * (cache[key].ndim - 2),
                )
                for key in cache
            }

        return insert

    def _build_decode(self):
        cfg, ec, model, paged = self.cfg, self.ec, self.model, self.paged

        @partial(jax.jit, donate_argnums=(1,))
        def decode(params, cache, block_table, tokens, positions, temps,
                   top_ps, key_data, lora=None, adapter_ids=None,
                   active=None):
            logits, cache = model.forward(
                params,
                tokens[:, None],
                cfg,
                positions=positions[:, None],
                cache=cache,
                **({"block_table": block_table} if paged else {}),
                **Engine._lora_kw(lora, adapter_ids),
                # row i is decode slot i; an idle slot's token is filler
                **({} if active is None else {"valid": active[:, None]}),
            )
            stats = Engine._pop_step_stats(model, cache)
            with jax.named_scope(scopes.SAMPLE):
                key, subkey = jax.random.split(
                    jax.random.wrap_key_data(key_data)
                )
                next_tokens = sample(
                    logits[:, 0], subkey, temps, top_k=ec.top_k, top_p=top_ps
                )
                next_tokens, kd = self._replicated(
                    next_tokens, jax.random.key_data(key)
                )
            if stats is None:
                return next_tokens, cache, kd
            return next_tokens, cache, kd, self._replicated(stats)

        return decode

    def _build_first_sample(self):
        ec = self.ec

        @jax.jit
        def first_sample(last_logits, key_data, temp, top_p):
            """Sample the first generated token from prefill logits;
            returns (token [1], new key data), both replicated for the
            scheduler's host read."""
            with jax.named_scope(scopes.SAMPLE):
                key, subkey = jax.random.split(
                    jax.random.wrap_key_data(key_data)
                )
                first = sample(
                    last_logits[None, :], subkey, temp, top_k=ec.top_k,
                    top_p=top_p,
                )
                return self._replicated(first, jax.random.key_data(key))

        return first_sample

    # --- scheduler --------------------------------------------------------

    def _lora_inputs(self):
        """(lora_tree, adapter_ids) for the jitted batch calls — (None,
        None) when multi-tenant serving is off, so legacy engines trace
        the exact pre-adapter signature."""
        if self.adapters is None:
            return None, None
        return self.adapters.device_tree(self.mesh), self.adapter_ids

    def submit(self, req: Request) -> Request:
        if self.sync is not None and not self.sync.leader:
            raise RuntimeError(
                "follower engine: requests arrive via the leader broadcast"
            )
        if self.ec.role == "decode":
            raise RuntimeError(
                "decode-role engine: requests arrive as KV migrations "
                "from the prefill tier (serve/disagg.py)"
            )
        if req.adapter is not None:
            from substratus_tpu.serve.adapters import UnknownAdapter

            # Reject unservable adapters in the CALLER's thread so the
            # HTTP layer can 404 before anything queues; actual loading
            # and pinning happen at admission on the scheduler thread.
            if self.adapters is None or not self.adapters.known(req.adapter):
                raise UnknownAdapter(req.adapter)
        if self.error is not None:
            req.finish_reason = "error"
            req.out.put(None)  # engine is dead; never strand the caller
            return req
        if self.ec.max_queue is not None:
            # Approximate (another submitter may race the read) but the
            # bound only needs to hold the queue near its limit, not
            # exactly at it — overload control, not a semaphore.
            depth = self.queue.qsize()
            if depth >= self.ec.max_queue:
                raise EngineOverloaded(depth)
        req.submit_ts = time.perf_counter()
        if req.trace_ctx is None:
            req.trace_ctx = tracer.current_context()
        if req.journey is None:
            req.journey = RequestJourney(
                trace_id=(
                    req.trace_ctx.trace_id if req.trace_ctx else None
                ),
                rid=req.id or None, origin=self.ec.role,
                cap=self.ec.journey_events,
            )
        req.journey.record(
            "submit", queue=self.queue.qsize(),
            prompt_tokens=len(req.prompt_tokens),
        )
        self.queue.put(req)
        self._wake.set()
        if self.error is not None:
            # The scheduler may have died between the check above and the
            # put — its one-time queue drain could have run before the put,
            # stranding the request. error is always set BEFORE the drain,
            # so re-checking here guarantees a terminal marker either way
            # (a duplicate None in a dead request's queue is harmless).
            req.finish_reason = "error"
            req.out.put(None)
        return req

    def resubmit(self, req: Request) -> None:
        """Re-board a request that already passed admission control once
        (handoff requeue after a decode-worker loss, serve/disagg.py):
        bypasses the max_queue bound — shedding an accepted request
        halfway through its stream would convert a worker failure into
        a client-visible 429."""
        if self.error is not None:
            req.finish_reason = "error"
            req.out.put(None)
            return
        if req.journey is not None:
            req.journey.record("requeue", queue=self.queue.qsize())
        self.queue.put(req)
        self._wake.set()
        if self.error is not None:  # same submit() race: never strand it
            req.finish_reason = "error"
            req.out.put(None)

    def submit_migration(self, mig) -> None:
        """Board a migrated request (serve.disagg.Migration): KV pages
        already computed by a prefill engine — admission installs them
        without recompute. Called from HandoffServer connection threads;
        the scheduler thread is the only consumer."""
        if self.ec.role != "decode":
            raise RuntimeError(
                f"role={self.ec.role!r} engine cannot accept migrations"
            )
        if self.error is not None:
            mig.req.finish_reason = "error"
            mig.req.out.put(None)
            return
        self._migrations.put(mig)
        self._wake.set()
        if self.error is not None:
            mig.req.finish_reason = "error"
            mig.req.out.put(None)

    def set_source(self, source) -> None:
        """Attach (or detach, with None) a pull-based request source —
        the batch-generation admission fast-path. The source's pull()
        runs on the SCHEDULER thread (on the lockstep leader: inside
        _sync_iterate, so pulled requests broadcast like submitted
        ones); it must return a fully-formed Request (with an out sink)
        or None, and pending() must say whether pull() could yield.
        Sources are consulted after the resume list and the submit()
        queue, so interactive traffic always boards first."""
        if source is not None and self.ec.role == "decode":
            raise RuntimeError(
                "decode-role engine: requests arrive as KV migrations, "
                "not from a pull source"
            )
        if source is not None and self.sync is not None and not self.sync.leader:
            raise RuntimeError(
                "follower engine: the leader owns the source; followers "
                "receive pulled requests via the broadcast"
            )
        self.source = source
        self._wake.set()

    def serving_tree(self, params, cfg=None, *, donate: bool = False,
                     span=None):
        """The door every parameter tree passes on its way in (__init__,
        swap_params): the family's `serving_layout` (models/llama.py), which
        keeps every leaf's name and kind and gives the leaves a serving
        program would otherwise lay out anew in every layer and step the
        form its dot reads from the stack; the identity for a family
        without one, on `None` and on a tree that has the form already.
        With `donate` each old leaf is deleted once its new form is made.
        For the served model's tree (no `cfg`: a draft's passes its own)
        sets substratus_serve_weights_relaid_bytes, and on `span` the
        leaves and bytes re-laid."""
        door = getattr(self.model, "serving_layout", None)
        new = params
        if door is not None and params is not None:
            new = door(params, self.cfg if cfg is None else cfg, donate)
        moved = [
            now for old, now in
            zip(jax.tree.leaves(params), jax.tree.leaves(new))
            if now is not old
        ]
        nbytes = sum(now.nbytes for now in moved)
        if cfg is None:
            METRICS.set("substratus_serve_weights_relaid_bytes", nbytes)
        if span is not None:
            span.set_attribute("leaves", len(moved))
            span.set_attribute("bytes", nbytes)
        return new

    def _param_axes(self, params, cfg):
        """Logical axes of a tree `serving_tree` returned."""
        served = getattr(self.model, "serving_logical_axes", None)
        if served is not None and params is not None:
            return served(params, cfg)
        return self.model.param_logical_axes(cfg)

    def swap_params(
        self,
        new_params,
        version: Optional[int] = None,
        *,
        source: str = "swap",
        wait: bool = True,
        timeout_s: float = 120.0,
    ) -> Optional[int]:
        """Hot weight-swap: replace the served parameter tree in place on
        a live engine (docs/serving.md "Zero-downtime rollout").

        Callable from any thread. The new tree passes the same door the
        first one did (`serving_tree`, on the caller's thread: a
        checkpoint in the published form is laid out as the programs read
        it, the caller's arrays left as they are) and must then match the
        served one in treedef, shapes, and dtypes — that is what keeps every
        compiled prefill/decode/verify executable (identical avals, no
        recompile); a mismatch is rejected here and the engine keeps
        serving the old weights. Accepted swaps are staged for the
        scheduler thread, which installs them at its next iteration top
        on a settled pipeline (``_flush("swap")``), bumps
        ``weights_version`` (``version``, or current+1 when None), and
        records a journey event of type ``source`` ("swap" |
        "rollout") on every in-flight request. In-flight streams keep
        their KV caches, positions, and RNG state: a swap to
        value-identical weights is token-exact across the boundary.

        On a lockstep gang the LEADER's staged swap sets the barrier:
        its version rides the per-iteration event broadcast and every
        process installs its own locally staged params on that same
        iteration (stage with ``wait=False`` on followers first; a
        follower with nothing staged within 60s errors the gang). The
        broadcast version wins over a follower's ``version`` argument.

        With ``wait`` (default) blocks until the scheduler applied the
        swap and returns the new version; ``wait=False`` returns None
        immediately (gang followers, fire-and-forget rollouts).
        """
        if source not in ("swap", "rollout"):
            raise ValueError(f"swap source {source!r} invalid (swap|rollout)")
        if self.error is not None:
            raise RuntimeError("engine is dead") from self.error
        if self._thread is None or self._stop.is_set():
            raise RuntimeError("swap_params needs a running engine")
        new_params = self.serving_tree(new_params)
        cur_leaves, cur_def = jax.tree_util.tree_flatten(self.params)
        new_leaves, new_def = jax.tree_util.tree_flatten(new_params)
        mismatch = None
        if new_def != cur_def:
            mismatch = f"treedef differs ({new_def} vs served {cur_def})"
        else:
            for i, (cur, new) in enumerate(zip(cur_leaves, new_leaves)):
                if cur.shape != new.shape or cur.dtype != new.dtype:
                    mismatch = (
                        f"leaf {i}: {new.shape}/{new.dtype} vs served "
                        f"{cur.shape}/{cur.dtype}"
                    )
                    break
        if mismatch is not None:
            METRICS.inc(
                "substratus_serve_weight_swaps_total",
                {"outcome": "rejected"},
            )
            raise ValueError(
                f"swap_params rejected: {mismatch} — matching structure "
                "is the no-recompile contract; load a checkpoint of the "
                "served architecture (or drain and restart for a "
                "different one)"
            )
        sw = _StagedSwap(new_params, version, source)
        self._swap_q.put(sw)
        self._wake.set()
        if not wait:
            return None
        if not sw.done.wait(timeout=timeout_s):
            raise TimeoutError(
                f"swap_params: scheduler did not apply the swap within "
                f"{timeout_s}s (engine error: {self.error!r})"
            )
        if sw.error is not None:
            raise sw.error
        return sw.applied

    def _apply_swap(self, sw: _StagedSwap, version: int) -> None:
        """Install one staged swap (scheduler thread only). The flush
        settles the one-step-ahead pipeline first so no in-flight step
        mixes two weight versions; structure was validated at staging,
        so every executable keyed on these avals is reused."""
        self._flush("swap")
        new = sw.params
        if self.mesh is not None:
            from substratus_tpu.parallel.sharding import shard_tree

            new = shard_tree(
                new, self.mesh, self._param_axes(new, self.cfg),
                self._serve_rules,
            )
        else:
            # Host-resident trees (snapshot_params, checkpoint loads)
            # transfer once here, not on every decode dispatch; device
            # trees pass through unchanged on the same default device.
            new = jax.device_put(new)
        self.params = new
        self.weights_version = version
        METRICS.inc(
            "substratus_serve_weight_swaps_total", {"outcome": "applied"}
        )
        METRICS.set("substratus_serve_weights_version", version)
        for req in self.slot_req:
            if req is not None and req.journey is not None:
                req.journey.record(sw.source, version=version)
        sw.applied = version
        sw.done.set()

    def _apply_staged_swaps(self) -> None:
        """Drain and install every staged swap (single-process path;
        gangs go through the _sync_iterate barrier instead)."""
        while True:
            try:
                sw = self._swap_q.get_nowait()
            except queue.Empty:
                return
            self._apply_swap(
                sw,
                sw.version if sw.version is not None
                else self.weights_version + 1,
            )

    def _fail_staged_swaps(self, exc: BaseException) -> None:
        """Unblock swap_params() waiters when the scheduler exits with
        their swap still staged (stop or crash)."""
        while True:
            try:
                sw = self._swap_q.get_nowait()
            except queue.Empty:
                return
            sw.error = exc
            sw.done.set()

    def start(self):
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()

    def stop(self):
        self._stop.set()
        self._wake.set()
        if self._thread:
            self._thread.join(timeout=30)

    def _next_request(self) -> Optional[Request]:
        """Resumed/held-back requests board before the public queue."""
        if self._resume:
            return self._resume.pop(0)
        if self.sync is not None:
            # Lockstep mode: the queue is drained only at _sync_iterate;
            # admission pulls from the broadcast-ordered list so every
            # process admits the same requests at the same iteration.
            return self._synced.pop(0) if self._synced else None
        try:
            return self.queue.get_nowait()
        except queue.Empty:
            pass
        if self.source is not None:
            # Continuous refill: the freed slot's replacement boards in
            # this same scheduler iteration, straight off the source.
            return self.source.pull()
        return None

    def _has_pending(self) -> bool:
        if self.sync is not None:
            return bool(self._resume) or bool(self._synced)
        return (
            bool(self._resume)
            or not self.queue.empty()
            or (self.source is not None and self.source.pending())
        )

    def _is_cancelled(self, req: Request) -> bool:
        """Lockstep mode reads the broadcast latch (identical on every
        process at a given iteration); single-process reads the live flag."""
        return req.cancel_latched if self.sync is not None else req.cancelled

    def _sync_iterate(self) -> bool:
        """Top-of-iteration synchronization point. Returns False when the
        engine should stop. In lockstep mode the leader drains its queue
        and broadcasts this iteration's events; every process then applies
        them identically."""
        if self.sync is None:
            self._apply_staged_swaps()
            return not self._stop.is_set()
        # Gangs run flush-per-step: the event broadcast encodes
        # decisions (admissions, cancel latches, stop) every process
        # applies to a settled batch, and the leader's emits feed the
        # consumers whose cancellations the broadcast latches — a
        # pipelined step would tear both. Engine.overlap resolves off
        # under sync; this drains any stray pipeline state and keeps
        # today's lockstep semantics bit-for-bit.
        self._flush("gang")
        from substratus_tpu.serve.multihost import (
            NullSink, decode_events, encode_events,
        )

        if self.sync.leader:
            new: List[Request] = []
            while True:
                try:
                    new.append(self.queue.get_nowait())
                except queue.Empty:
                    break
            if self.source is not None:
                # Pull-source refill rides the same broadcast as
                # submitted requests: the leader tops the gang up to its
                # free slot budget and every process admits identically.
                budget = (
                    self.ec.max_batch
                    - int(self.active.sum())  # sublint: allow[hostsync]: host numpy mirror of the active mask, no device read
                    - len(self._synced)
                    - len(self._resume)
                    - len(new)
                )
                while budget > 0:
                    r = self.source.pull()
                    if r is None:
                        break
                    new.append(r)
                    budget -= 1
            for r in new:
                self._sync_seq += 1
                r.sync_id = self._sync_seq
            cancels = [
                i for i, r in self._sync_reqs.items()
                if r.cancelled and not r.cancel_latched
            ]
            stop = self._stop.is_set()
            # Swap barrier: one staged swap per iteration rides the
            # broadcast as its target version; every process installs
            # its OWN locally staged params at this same iteration
            # (below), so the gang changes weights in lockstep. Not
            # popped when stopping — the loop's exit path fails the
            # waiter instead of stranding it.
            leader_sw = None
            if not stop:
                try:
                    leader_sw = self._swap_q.get_nowait()
                except queue.Empty:
                    pass
            swap_version = None
            if leader_sw is not None:
                swap_version = (
                    leader_sw.version if leader_sw.version is not None
                    else self.weights_version + 1
                )
            self.sync.broadcast(
                encode_events(new, cancels, stop, swap=swap_version)
            )
            msg = {"cancels": cancels, "stop": stop, "swap": swap_version}
        else:
            leader_sw = None
            msg = decode_events(self.sync.broadcast(None))
            new = []
            for d in msg["reqs"]:
                self._sync_seq += 1  # mirrors the leader's numbering
                new.append(
                    Request(
                        prompt_tokens=d["p"],
                        max_tokens=d["m"],
                        temperature=d["t"],
                        top_p=d["tp"],
                        eos_token_id=d["e"],
                        id=d["id"],
                        adapter=d.get("ad"),
                        out=NullSink(),
                        sync_id=d["sid"],
                    )
                )
        for r in new:
            self._sync_reqs[r.sync_id] = r
            self._synced.append(r)
        for cid in msg["cancels"]:
            r = self._sync_reqs.get(cid)
            if r is not None:
                r.cancel_latched = True
        if msg["stop"]:
            self._stop.set()
            return False
        swap_version = msg.get("swap")
        if swap_version is not None:
            if self.sync.leader:
                sw = leader_sw
            else:
                # The leader committed the gang to swap on THIS
                # iteration; this process's params arrive through its own
                # control plane's swap_params(wait=False) call. A bounded
                # wait keeps a misconfigured rollout from wedging the
                # gang silently — timing out errors the engine (the
                # JobSet failurePolicy restarts the gang, docs/rl.md
                # "Failure semantics").
                try:
                    sw = self._swap_q.get(timeout=60.0)
                except queue.Empty:
                    raise RuntimeError(
                        "gang swap barrier: leader swapped to "
                        f"weights_version {swap_version} but no params "
                        "were staged on this process within 60s — call "
                        "swap_params(..., wait=False) on every process"
                    )
            # The broadcast version wins over a follower's own argument:
            # the whole gang must agree on what it now serves.
            self._apply_swap(sw, int(swap_version))
        return True

    def _admit(self) -> int:
        """Fill free slots from the request queue (prefill + insert);
        returns how many requests boarded this iteration.

        Admission is capped per scheduler iteration so a burst of arrivals
        can't starve in-flight decodes: each loop admits a few prefills,
        then every active slot advances a token."""
        admitted = self._admit_migrations()
        # No in-flight decodes -> nothing to starve: fill freely (decode
        # steps cost the same at any occupancy, so boarding everyone first
        # is strictly better for TTFT). A pull source (batch generation,
        # serve/batchgen.py) also fills freely: the cap exists to protect
        # in-flight streams' inter-token latency, and an offline run's
        # only objective is keeping every slot busy — throttling refill
        # to one slot per iteration just leaves slots idle for a step
        # after a synchronized completion wave.
        cap = (
            max(1, self.ec.max_batch // 4)
            if self.active.any() and self.source is None
            else self.ec.max_batch
        )
        while (
            admitted < cap
            and self._has_pending()
            and not self.active.all()
        ):
            req = self._next_request()
            if req is None:
                break
            self._admitting = req
            verdict = self._acquire_adapter(req)
            if verdict == "dead":
                self._admitting = None
                continue
            if verdict == "wait":
                # Transient: every adapter slot is pinned by an active
                # request. Hold at the front; decoding slots will unpin.
                if req.journey is not None:
                    req.journey.record_once("adapter_wait")
                self._admitting = None
                self._resume.insert(0, req)
                break
            slot = int(np.flatnonzero(~self.active)[0])
            # Queue wait is submission -> first prefill; a preempted
            # request re-boarding (last_emit_ts set) already paid it.
            if req.submit_ts and not req.last_emit_ts:
                METRICS.observe(
                    "substratus_serve_queue_wait_seconds",
                    time.perf_counter() - req.submit_ts,
                )
            if req.journey is not None:
                wait_us = (
                    int((time.perf_counter() - req.submit_ts) * 1e6)
                    if req.submit_ts and not req.last_emit_ts else 0
                )
                req.journey.record("admit", slot=slot, wait_us=wait_us)
            with tracer.span(
                "engine.prefill", parent=req.trace_ctx,
                request_id=req.id, slot=slot,
                prompt_tokens=len(req.prompt_tokens),
            ):
                if self.paged:
                    ok = self._admit_paged(req, slot)
                else:
                    ok = self._admit_dense(req, slot)
            self._admitting = None
            if not ok:
                # Pool dry even after eviction: hold the request at the
                # front of the line; decoding slots will free pages. The
                # adapter pin drops too — re-admission re-acquires.
                if req.journey is not None:
                    req.journey.record_once("pool_wait")
                self._release_adapter_pin(req)
                self._resume.insert(0, req)
                self.timeline.pool_dry()
                break
            admitted += 1
        self.stats["max_active"] = max(
            self.stats["max_active"], int(self.active.sum())  # sublint: allow[hostsync]: self.active is a host numpy mirror, no device read
        )
        return admitted

    def _admit_migrations(self) -> int:
        """Board migrated requests (decode role, serve/disagg.py): pages
        arrive precomputed, so admission is an allocation + one scatter —
        no model forward, no starvation concern, hence no per-iteration
        cap beyond free slots. Pool-dry migrations hold at the front
        (decoding slots will free pages); they are never preempted FOR —
        a migration is cheaper to delay than a decode is to evict."""
        admitted = 0
        while (
            (self._resume_migrations or not self._migrations.empty())
            and not self.active.all()
        ):
            if self._resume_migrations:
                mig = self._resume_migrations.pop(0)
            else:
                try:
                    mig = self._migrations.get_nowait()
                except queue.Empty:
                    break
            verdict = self._acquire_adapter(mig.req)
            if verdict == "dead":
                continue
            if verdict == "wait":
                self._resume_migrations.insert(0, mig)
                break
            if not self._install_migration(mig):
                self._release_adapter_pin(mig.req)
                self._resume_migrations.insert(0, mig)
                self.timeline.pool_dry()
                break
            admitted += 1
        return admitted

    def _install_migration(self, mig) -> bool:
        """Allocate pages for one migration and scatter its transferred
        KV in; False = pool dry (hold the migration, nothing leaked)."""
        req = mig.req
        n = mig.pages["k"].shape[1]
        owned = self._try_alloc(n)
        if owned is None:
            return False
        slot = int(np.flatnonzero(~self.active)[0])
        self.slot_pages.assign(slot, [], owned)
        row = np.zeros((self.max_pages,), np.int32)
        row[:n] = owned
        self.block_table[slot] = row
        cap = _bucket(n, 1)
        ids = np.zeros((cap,), np.int32)  # padding scatters to trash page 0
        ids[:n] = owned
        frag = {}
        for name, a in mig.pages.items():
            if cap != n:
                pad = np.zeros((a.shape[0], cap - n) + a.shape[2:], a.dtype)
                a = np.concatenate([a, pad], axis=1)
            frag[name] = a
        self.cache = self._import_fn(mig.convert, self.cache, ids, frag)
        self.stats["migrations_in"] += 1

        true_len = mig.true_len
        self.slot_req[slot] = req
        self.slot_generated[slot] = 0
        self.slot_adapter[slot] = req.adapter_slot
        self.adapter_ids[slot] = req.adapter_slot
        self.active[slot] = True
        self.host_positions[slot] = true_len
        self.slot_tokens[slot] = []
        self._admit_counter += 1
        self.slot_admit_seq[slot] = self._admit_counter
        self.tokens[slot] = mig.first_token
        self._token_fresh[slot] = True  # next dispatch feeds the host value
        self.positions[slot] = true_len
        self.temps[slot] = req.temperature
        self.top_ps[slot] = req.top_p
        if req.journey is not None:
            req.journey.record(
                "install", slot=slot, pages=n, tokens=true_len
            )
        # The first token was sampled on the prefill engine but never
        # delivered — this emit is its delivery (the whole stream flows
        # from the decode tier).
        self._emit(slot, mig.first_token)
        return True

    def _handoff_request(self, req: Request, slot: int, first_id: int,
                         true_len: int) -> None:
        """Prefill role: export the admitted slot's pages, free the slot,
        and hand (pages + first token + sampling state) to the transfer
        layer. The slot never activates — the decode tier owns the rest
        of the request's lifecycle. The page export gathers from the
        live pool, so it must observe a settled batch — a prefill-role
        engine never decodes (overlap resolves off), making this flush a
        no-op guard that pins the invariant."""
        self._flush("handoff")
        pages = list(self.slot_pages.pages[slot])
        n = len(pages)
        cap = _bucket(n, 1)
        ids = np.zeros((cap,), np.int32)
        ids[:n] = pages
        frag = self._export_fn(self.cache, ids)
        with self.timeline.phase("wait.handoff"):
            host = {
                key: np.asarray(v)[:, :n]  # sublint: allow[hostsync]: the handoff IS a device->host transfer — one gather read per migrated request
                for key, v in frag.items()
            }
        self.slot_pages.release(slot, self.alloc)
        self.block_table[slot] = 0
        self._release_adapter_pin(req)
        self.stats["handoffs"] += 1
        if req.journey is not None:
            req.journey.record("ship", tokens=true_len, pages=n)
        self.handoff.ship(req, host, true_len, first_id)

    def _acquire_adapter(self, req: Request) -> str:
        """Resolve + pin the request's adapter before prefill. Returns
        'ok' (adapter_slot set; 0 = base), 'wait' (every store slot is
        pinned — transient, hold the request), or 'dead' (adapter
        unknown/unloadable — request finished with an error marker)."""
        req.adapter_slot = 0
        if req.adapter is None:
            return "ok"
        from substratus_tpu.serve.adapters import (
            AdapterCapacityError,
            UnknownAdapter,
        )

        try:
            if self.adapters is None:
                raise UnknownAdapter(req.adapter)
            req.adapter_slot = self.adapters.acquire(req.adapter)
            self.stats["adapter_requests"] += 1
            return "ok"
        except AdapterCapacityError:
            return "wait"
        except (UnknownAdapter, OSError, ValueError) as e:
            # The artifact vanished (or corrupted) between submit()'s
            # known() check and admission: fail THIS request, not the
            # engine.
            logging.getLogger(__name__).warning(
                "adapter %r failed to load for request %s: %s",
                req.adapter, req.id, e,
            )
            req.finish_reason = "error"
            self._journey_end(req, "error", cause="adapter")
            req.out.put(None)
            if req.sync_id is not None:
                self._sync_reqs.pop(req.sync_id, None)
            return "dead"

    def _release_adapter_pin(self, req: Request) -> None:
        if self.adapters is not None and req.adapter_slot:
            self.adapters.release(req.adapter_slot)
        req.adapter_slot = 0

    def _prefill_lora(self, req: Request):
        """(lora_tree, [1]-shaped adapter id) for one request's prefill
        dispatch; (None, None) when multi-tenant serving is off."""
        if self.adapters is None:
            return None, None
        return (
            self.adapters.device_tree(self.mesh),
            np.array([req.adapter_slot], np.int32),
        )

    def _admit_dense(self, req: Request, slot: int) -> bool:
        # Keep the newest tokens that fit the cache (minus one slot for
        # generation); prompts longer than one prefill bucket run as a
        # sequence of chunked prefills against the slot's cache.
        keep = self.ec.max_seq_len - 1
        prompt = req.prompt_tokens[-keep:]
        true_len = len(prompt)
        lora, ids1 = self._prefill_lora(req)
        if true_len <= self.ec.max_prefill_len:
            padded, true_len = _pad_to_bucket(
                prompt, self.ec.max_prefill_len
            )
            with self.timeline.phase(
                "prefill", request_id=req.id, bucket=padded.shape[1],
                chunk=0, tokens=true_len,
            ):
                last_logits, kv = self._prefill_fn(
                    self.params, padded, true_len, lora, ids1
                )
                self.cache = self._insert_fn(self.cache, kv, slot)
        else:
            last_logits = self._chunked_prefill(
                req.id, prompt, slot, lora, ids1
            )
        self.stats["prefill_tokens"] += true_len
        METRICS.inc("substratus_serve_prefill_tokens_total", by=true_len)
        if req.journey is not None:
            req.journey.record(
                "prefill", tokens=true_len,
                chunks=max(1, -(-true_len // self.ec.max_prefill_len)),
            )
        self._finalize_admit(req, slot, last_logits, true_len)
        return True

    def _admit_paged(self, req: Request, slot: int) -> bool:
        """Paged admission: match shared prefix pages, allocate the rest,
        chunk-prefill only the unshared remainder through the slot's
        block-table row, then publish this prompt's full pages."""
        from substratus_tpu.serve.paged_kv import chain_entries

        bs = self.page_size
        keep = self.ec.max_seq_len - 1
        # Degenerate empty prompt: run one pad token through the model so
        # first-token logits exist (same tolerance as the dense path).
        prompt = req.prompt_tokens[-keep:] or [0]
        true_len = len(prompt)

        # Prefix chains are salted with the adapter id: K/V written
        # under one tenant's wk/wv deltas must never seed another
        # tenant's (or the base model's) prompt.
        entries = (
            chain_entries(prompt, bs, salt=req.adapter)
            if self.prefix is not None
            else []
        )
        # Reuse at most the pages strictly before the last prompt token:
        # the last token must run through the model for its logits.
        max_shared = (true_len - 1) // bs
        if self.slot_state and self.ec.prefix_cache and max_shared:
            self.stats["prefix_reuse_refused"] += 1
        shared = (
            self.prefix.match(entries[:max_shared])
            if self.prefix is not None
            else []
        )
        reuse = len(shared) * bs
        # Claim the shared pages BEFORE allocating owned ones: _try_alloc
        # may evict registry entries under pressure, and an unclaimed
        # matched page could be evicted-then-reallocated into `owned`,
        # aliasing one physical page as both prefix and tail.
        if shared:
            self.prefix.claim(shared)
        # Own pages covering slot-local tokens reuse..true_len (inclusive:
        # bucket-padding clamps one write onto the one-past-prompt slot).
        need = -(-(true_len + 1) // bs) - len(shared)
        owned = self._try_alloc(need)
        if owned is None:
            for pid in shared:
                self.alloc.decref(pid)
            return False
        self.slot_pages.assign(slot, shared, owned)
        pages = self.slot_pages.pages[slot]
        row = np.zeros((self.max_pages,), np.int32)
        row[: len(pages)] = pages
        self.block_table[slot] = row
        bt_row = self.block_table[slot : slot + 1].copy()

        lora, ids1 = self._prefill_lora(req)
        last_logits, self.cache = self._run_chunks(
            req.id, self._chunk_fn, self.params, self.cache, prompt, reuse,
            bt_row, lora=lora, adapter_ids=ids1,
            slot=slot if self._tells_valid else None,
        )
        self.stats["prefill_tokens"] += true_len - reuse
        self.stats["prefix_hit_tokens"] += reuse
        METRICS.inc(
            "substratus_serve_prefill_tokens_total", by=true_len - reuse
        )
        if reuse:
            METRICS.inc(
                "substratus_serve_prefix_hit_tokens_total", by=reuse
            )
        if req.journey is not None:
            if reuse:
                req.journey.record("prefix_hit", tokens=reuse)
            req.journey.record(
                "prefill", tokens=true_len - reuse,
                chunks=max(
                    1, -(-(true_len - reuse) // self.ec.max_prefill_len)
                ),
            )

        if self.spec_draft:
            # Draft prefill also starts at `reuse`: the draft pool indexes
            # through the same block table, and shared pages already hold
            # valid draft KV — registered pages are only ever written during
            # the admission that created them (decode/propose writes land at
            # positions >= true_len, past every registered full page), so
            # the invariant holds inductively from the first admission.
            _, self.draft_cache = self._run_chunks(
                req.id, self._draft_chunk_fn, self.draft_params,
                self.draft_cache, prompt, reuse, bt_row,
            )

        n_full = true_len // bs
        if self.prefix is not None and n_full:
            self.prefix.register(entries[:n_full], pages[:n_full])
        self._finalize_admit(req, slot, last_logits, true_len)
        return True

    def _run_chunks(self, rid, fn, params, cache, prompt, start: int,
                    bt_row, lora=None, adapter_ids=None, slot=None):
        """Chunked prefill of request `rid`'s prompt[start:] through a
        block-table row; returns (last real token's logits, updated
        cache). One engine.prefill phase per chunk dispatch. The chunks'
        counters (slot-state families) wait in _chunk_stats for the
        admission's one host read (_finalize_admit)."""
        chunk = self.ec.max_prefill_len
        offset, last_logits = start, None
        slot_kw = {} if slot is None else {"slot": np.int32(slot)}
        while offset < len(prompt):
            padded, clen = _pad_to_bucket(
                prompt[offset : offset + chunk], chunk
            )
            if bt_row is not None:
                # The padded tail sits on position offset + clen
                # (_chunk_prefill_jit); a full bucket ends one before it.
                last = offset + min(clen, padded.shape[1] - 1)
                if self._page_layers:
                    self.stats["prefill_kv_pages_read_sum"] += (
                        last // self.page_size + 1)
                self.stats["prefill_kv_pages_table_sum"] += self.max_pages
                self.stats["chunk_ctx_tokens_sum"] += last + 1
                self.stats["chunk_count"] += 1
            if self._conv_state and slot is not None:
                self.stats["conv_chunks_sum"] += 1
                self.stats["conv_chunks_resumed_sum"] += offset > 0
            with self.timeline.phase(
                "prefill", request_id=rid, bucket=padded.shape[1],
                chunk=(offset - start) // chunk, tokens=clen,
            ):
                last_logits, cache, stats = fn(
                    params, cache, padded, offset, clen, block_table=bt_row,
                    lora=lora, adapter_ids=adapter_ids, **slot_kw,
                )
            if stats is not None:
                self._chunk_stats.append(stats)
            offset += clen
        return last_logits, cache

    def _finalize_admit(self, req: Request, slot: int, last_logits,
                        true_len: int) -> None:
        # Sample the first generated token from the prefill logits.
        with self.timeline.phase("sample"):
            first, key_out = self._sample1_fn(
                last_logits,
                self.key,
                np.array([req.temperature], np.float32),
                np.array([req.top_p], np.float32),
            )
            # The read waits for the prefill programs dispatched above.
            with self.timeline.phase("wait.first_token"):
                self.key = np.asarray(key_out)  # sublint: allow[hostsync]: first-token sample + key readback, once per admission (the "sample" phase)
                first_id = int(first[0])
            # the chunks have run: their counters are ready, no new wait
            for stats in self._chunk_stats:
                self._fold_step_stats(stats, decode=False)
            self._chunk_stats.clear()

        if self.ec.role == "prefill":
            self._handoff_request(req, slot, first_id, true_len)
            return

        self.slot_req[slot] = req
        self.slot_generated[slot] = 0
        self.slot_adapter[slot] = req.adapter_slot
        self.adapter_ids[slot] = req.adapter_slot
        self.active[slot] = True
        self.host_positions[slot] = true_len
        self.slot_tokens[slot] = []
        self._admit_counter += 1
        self.slot_admit_seq[slot] = self._admit_counter
        self.tokens[slot] = first_id
        # The device token array predates this admission: the next
        # dispatch must take this slot's first token from the host.
        self._token_fresh[slot] = True
        # Adaptive speculation starts optimistic for every new stream:
        # the previous tenant's acceptance history must not leak.
        self._spec_ewma[slot] = 1.0
        self._spec_degraded[slot] = 0
        self.positions[slot] = true_len
        self.temps[slot] = req.temperature
        self.top_ps[slot] = req.top_p
        self._emit(slot, first_id)

    def _fold_step_stats(self, stats, decode: bool) -> None:
        """Add one program's counters (those of models/hybrid.py::COUNTERS
        the family's forward carries) to stats and the registry. Called
        where the program's output is read anyway, so it never waits for
        the device."""
        host = {k: int(v) for k, v in jax.device_get(stats).items()}  # sublint: allow[hostsync]: read with the step's tokens (drain) or after the first-token read (admission); the program has finished
        held, every = host["moe_pairs_held"], host["moe_pairs_all"]
        self.stats["moe_pairs_held"] += held
        self.stats["moe_pairs_all"] += every
        METRICS.inc("substratus_serve_moe_pairs_total", {"held": "true"},
                    by=held)
        METRICS.inc("substratus_serve_moe_pairs_total", {"held": "false"},
                    by=every - held)
        if decode:
            self.stats["moe_decode_steps"] += 1
            self.stats["moe_decode_pairs_held"] += held
            self.stats["moe_decode_expert_pairs_max_sum"] += host[
                "moe_expert_pairs_max"]
            METRICS.observe("substratus_serve_moe_expert_pairs_max",
                            host["moe_expert_pairs_max"])
            if "moe_experts_touched" in host:
                # only a family whose forward carries the counter has the
                # key: held experts that a real token of the step chose,
                # summed over sparse layers
                self.stats["moe_decode_experts_touched"] = (
                    self.stats.get("moe_decode_experts_touched", 0)
                    + host["moe_experts_touched"])

    # --- paged pool management -------------------------------------------

    def _try_alloc(self, n: int) -> Optional[List[int]]:
        """n fresh pages, evicting LRU prefix-registry entries under
        pressure; None (nothing leaked) when the pool is truly dry."""
        got: List[int] = []
        while len(got) < n:
            pid = self.alloc.alloc()
            if pid is not None:
                got.append(pid)
                continue
            if self.prefix is not None and self.prefix.evict_lru():
                continue
            for p in got:
                self.alloc.decref(p)
            return None
        return got

    def _pick_victim(self, exclude: int) -> Optional[int]:
        """Youngest active slot (LIFO preemption preserves the oldest
        requests' progress)."""
        best, best_seq = None, -1
        for slot in np.flatnonzero(self.active):
            slot = int(slot)
            if slot == exclude:
                continue
            if self.slot_admit_seq[slot] > best_seq:
                best, best_seq = slot, self.slot_admit_seq[slot]
        return best

    def _preempt(self, victim: int) -> None:
        """Evict a slot mid-decode: its pages free now; the request (same
        object — cancellation flags stay live) re-boards at the front with
        prompt := prompt + generated-so-far, so re-prefill reconstructs the
        exact state and generation continues seamlessly."""
        req = self.slot_req[victim]
        gen = self.slot_tokens[victim]
        req.prompt_tokens = list(req.prompt_tokens) + gen
        req.max_tokens -= len(gen)
        if req.journey is not None:
            req.journey.record("preempt", generated=len(gen))
        self._release_slot(victim)
        self._resume.insert(0, req)
        self.stats["preemptions"] += 1

    def _ensure_capacity(self, slot: int, upto_pos: Optional[int] = None) -> None:
        """Before this iteration writes at positions up to `upto_pos`
        (default: the next decode write, host_positions[slot]), make sure
        the pages backing them exist — allocating, evicting prefix entries,
        then preempting the youngest other slot, in that order. Last resort
        (single survivor, pool exhausted): finish the request as truncated.
        Writes beyond max_seq_len never need pages (the paged kernel
        redirects past-the-table writes to the trash page)."""
        if not self.active[slot]:
            return  # preempted earlier in this same pass
        if upto_pos is None:
            upto_pos = int(self.host_positions[slot])
        upto_pos = min(upto_pos, self.ec.max_seq_len - 1)
        while upto_pos // self.page_size >= len(self.slot_pages.pages[slot]):
            pn = len(self.slot_pages.pages[slot])
            got = self._try_alloc(1)
            while got is None:
                if self._pending is not None:
                    # Preemption (and the truncation fallback below)
                    # must observe a settled batch: the in-flight step's
                    # drain may release slots and free pages on its own,
                    # and a victim's resume prompt needs every token it
                    # generated. Flush, then retry allocation before
                    # evicting anyone.
                    self._flush("preempt")
                    if not self.active[slot]:
                        return  # the flush released this very slot
                    got = self._try_alloc(1)
                    continue
                victim = self._pick_victim(exclude=slot)
                if victim is None:
                    req = self.slot_req[slot]
                    req.finish_reason = "length"
                    self._journey_end(req, "length", cause="pool")
                    req.out.put(None)
                    if req.sync_id is not None:
                        self._sync_reqs.pop(req.sync_id, None)
                    self._release_slot(slot)
                    self.stats["truncated_by_pool"] += 1
                    return
                self._preempt(victim)
                got = self._try_alloc(1)
            self.slot_pages.append(slot, got[0])
            self.block_table[slot, pn] = got[0]

    def _count_step(self) -> None:
        """One decode step or verify round is about to be handed
        `self.temps`: count it, and whether its sampler will take the
        sampled branch (the same test on the same values)."""
        self.stats["decode_steps"] += 1
        # self.temps is a host numpy mirror: no device read
        self.stats["decode_steps_sampled"] += bool((self.temps > 0).any())
        if self.paged:
            # positions and active are host numpy mirrors too
            live = self.positions[self.active] + 1
            ctx = live.sum()
            self.stats["decode_ctx_tokens_sum"] += int(ctx)
            if self._index_topk:
                attended = np.minimum(live, self._index_topk).sum()
                self.stats["dsa_rows_live_sum"] += int(ctx)
                self.stats["dsa_rows_attended_sum"] += int(attended)
                self.stats["dsa_selections"] += live.size * self._page_layers

    def _dispatch(self) -> Optional[_InFlightStep]:
        """Device-only half of one decode step: grow paged capacity from
        the host_positions mirror, feed the previous step's sampled
        tokens back ON-DEVICE (merged with host-side first tokens for
        slots admitted since the last dispatch), launch the jitted step,
        and return the in-flight bookkeeping WITHOUT reading anything
        back. Everything host-blocking belongs in _drain() — under the
        overlapped scheduler it runs one full step later, while this
        step occupies the device. Returns None when capacity handling
        emptied the batch."""
        if self.paged:
            # Grow every slot that will cross a page boundary this step
            # (may flush + preempt or, at the limit, truncate).
            for slot in np.flatnonzero(self.active):
                self._ensure_capacity(int(slot))
            if not self.active.any():
                return None
        lora, adapter_ids = self._lora_inputs()
        if self._dev_tokens is None:
            tok_in = self.tokens
        else:
            # Continuing slots chain the in-flight step's sampled token
            # straight from its device output (JAX async dispatch makes
            # this a device-side data dependency, never a host round
            # trip); freshly (re)admitted slots take their first token
            # from the host array admission wrote.
            tok_in = self._merge_tokens(
                self._dev_tokens, self.tokens, self._token_fresh
            )
        self._count_step()
        next_tokens, self.cache, key_out, *stats = self._decode_fn(
            self.params,
            self.cache,
            self.block_table if self.paged else None,
            tok_in,
            self.positions,
            self.temps,
            self.top_ps,
            self.key,
            lora,
            adapter_ids,
            *((self.active.copy(),) if self._tells_valid else ()),
        )
        if self.overlap:
            # The RNG key stays device-resident between steps: reading
            # it back here would block on the step just launched and
            # re-serialize the pipeline. Single-host only — lockstep
            # gangs (overlap off) need the host copy below.
            self.key = key_out
        else:
            with self.timeline.phase("wait.key"):
                self.key = np.asarray(key_out)  # sublint: allow[hostsync]: overlap-off (lockstep) fallback only — the key rides host-side so every gang process feeds identical replicated inputs; the overlapped path above keeps it on device
        self._dev_tokens = next_tokens
        self._token_fresh[:] = False
        # Only decoding slots advance. An idle slot sits at position 0
        # (_release_slot) until an admission sets its position: its row
        # still runs (static shapes) and writes the trash page, and
        # attention that follows a row's own length (ops/
        # paged_attention.py) then reads one page for it, not max_pages.
        # The clamp at the last cache row is a guard only: active slots
        # are released at the window before reaching it (_emit's
        # hit_window).
        last = self.ec.max_seq_len - 1
        self.positions = np.minimum(self.positions + self.active, last)
        self.host_positions = np.minimum(
            self.host_positions + self.active, last
        )
        return _InFlightStep(
            tokens=next_tokens,
            slots=[
                (int(s), self.slot_req[int(s)])
                for s in np.flatnonzero(self.active)
            ],
            pos_next=self.host_positions.copy(),
            t_dispatch=time.perf_counter(),
            stats=stats[0] if stats else None,
        )

    def _drain(self, step: _InFlightStep, wait: str = "drain") -> None:
        """Host half of one decode step: THE deferred host read (the
        engine.wait.<wait> phase: "drain", or "flush" from _flush), then
        per-slot emits, EOS/budget/window release, and cancellation
        handling for the slots that were active at dispatch. A slot
        whose request was released after that dispatch (EOS at the
        previous drain, preemption, kill) fails the identity check and
        its in-flight token — the pipeline's one wasted token per
        finished stream — never reaches a consumer."""
        with self.timeline.phase("wait." + wait):
            host_tokens = np.asarray(step.tokens)  # sublint: allow[hostsync]: THE one host read per decode step — deferred to drain() so under overlap it lands after the NEXT dispatch, hiding every emit under device compute
        t_drained = time.perf_counter()
        if step.stats is not None:
            self._fold_step_stats(step.stats, decode=True)
        with self.timeline.phase("emit"):
            for slot, req in step.slots:
                if self.slot_req[slot] is not req:
                    continue  # EOS-lag mask: released or re-admitted slot
                if req.journey is not None:
                    # Journey events for a dispatch are stamped at drain
                    # — the overlap pipeline never stalls for forensics.
                    req.journey.record(
                        "drain",
                        lat_us=int((t_drained - step.t_dispatch) * 1e6),
                    )
                self.tokens[slot] = host_tokens[slot]
                self._emit(
                    slot, int(host_tokens[slot]),
                    pos_next=int(step.pos_next[slot]),
                )
        if not self.overlap:
            # Synchronous path (gangs, forced-sync): the next dispatch
            # must feed pure host-side numpy — in lockstep every process
            # replicates the identical input arrays, which is the whole
            # broadcast contract. Device token feedback is overlap-only.
            self._dev_tokens = None
            self._token_fresh[:] = True

    def _flush(self, reason: str) -> None:
        """Drain the in-flight step NOW. Required before anything that
        must observe a settled batch: the lockstep event broadcast
        (reason "gang"), a disaggregated KV handoff ("handoff"), engine
        stop/drain ("drain"), preemption or pool-pressure truncation
        ("preempt"), and a hot weight-swap ("swap" — no in-flight step
        may mix two weight versions). Speculative rounds no longer flush:
        they chain on-device through the accept-mask advance, so the
        historical "spec" reason is retired (steady-state spec traffic
        holds pipeline_flushes_total{reason="spec"} at zero by
        construction)."""
        pending, self._pending = self._pending, None
        if pending is None:
            return
        METRICS.inc(
            "substratus_serve_pipeline_flushes_total", {"reason": reason}
        )
        for slot, req in pending.slots:
            if self.slot_req[slot] is req and req.journey is not None:
                req.journey.record("flush", reason=reason)
        # A flush's drain is host work the pipeline could NOT hide (the
        # device sits settled through it): the timeline's "flush" bubble.
        with self.timeline.phase("flush", reason=reason):
            self._drain_any(pending, wait="flush")
        # The batch is settled; the next dispatch feeds host tokens for
        # every slot (on-device feedback resumes with the step after).
        self._dev_tokens = None
        self._token_fresh[:] = True

    def _dispatch_any(self):
        """The resolved dispatch half: a speculative round (propose +
        multi-token verify) for spec engines, the plain decode step
        otherwise. _decode_step/_step_overlapped/_flush route through
        these two so both step kinds share one pipeline skeleton."""
        return self._spec_dispatch() if self.spec else self._dispatch()

    def _drain_any(self, step, wait: str = "drain") -> None:
        """The matching drain half, type-dispatched on the in-flight
        bookkeeping (a flush may drain either kind)."""
        if isinstance(step, _InFlightSpecStep):
            self._spec_drain(step, wait)
        else:
            self._drain(step, wait)

    def _decode_step(self) -> None:
        """One synchronous iteration: dispatch, then drain immediately
        (the overlap-off path — lockstep gangs and the forced-sync
        escape hatch)."""
        # The compiling first launch stays out of phase="decode"
        # (substratus_serve_first_compile_seconds has it).
        with self.timeline.phase(
            "dispatch", observe=self._first_decode_done
        ):
            pending = self._dispatch_any()
        if pending is None:
            return
        with self.timeline.phase("drain"):
            self._drain_any(pending)

    def _step_overlapped(self) -> None:
        """One pipelined iteration: launch step N, then run step N-1's
        host work while N occupies the device: the deferred np.asarray
        overlaps the transfer with compute via JAX async dispatch, so
        steady-state inter-token latency settles at
        max(device_step, host_work) instead of their sum."""
        # Dispatch FIRST, then pick up whatever is still pending: the
        # dispatch's capacity handling may _flush("preempt") the
        # previous step itself, and draining it again here would emit
        # duplicate tokens.
        with self.timeline.phase("dispatch", observe=self._first_decode_done):
            launched = self._dispatch_any()
        prev, self._pending = self._pending, launched
        if prev is not None:
            with self.timeline.phase("drain") as ph:
                self._drain_any(prev)
            if self._pending is not None:
                # Host work actually hidden under an in-flight step —
                # the overlapped scheduler's win, exported so operators
                # can see how much host time the pipeline absorbs.
                METRICS.observe(
                    "substratus_serve_host_overlap_seconds", ph.seconds
                )

    @staticmethod
    def _prompt_lookup(ctx, k: int, max_n: int = 3):
        """Prompt-lookup proposal: the continuation after the most recent
        earlier occurrence of the context's trailing n-gram (largest n
        first). Returns k tokens, or None when nothing matches — pure
        host work, no model involved; the scan is vectorized numpy so a
        max-context slot costs microseconds, not interpreter loops."""
        a = np.asarray(ctx, np.int32)  # sublint: allow[hostsync]: ctx is a python token list; pure host work by design
        L = a.size
        for n in range(min(max_n, L - 1), 0, -1):
            tgt = a[L - n:]
            # candidate starts j in [0, L-n-1]: the trailing n-gram itself
            # (j = L-n) is excluded by windowing over a[:L-1]
            win = np.lib.stride_tricks.sliding_window_view(a[: L - 1], n)
            hits = np.flatnonzero((win == tgt).all(axis=1))
            if hits.size:
                j = int(hits[-1])  # most recent occurrence
                cont = a[j + n: j + n + k]
                if cont.size:
                    out = np.full((k,), cont[-1], np.int32)
                    out[: cont.size] = cont
                    return out
        return None

    def _plan_spec_round(self):
        """Host-side adaptive-k policy for the next speculative round
        (EngineConfig.spec_threshold): per active slot, pick this
        round's draft length from the stream's acceptance-rate EWMA.
        Sampling slots never speculate (k draft steps + a wide verify
        to emit ONE sampled token is strictly worse than plain decode);
        greedy slots propose k = ceil(ewma * spec_k) while the estimate
        holds, degrade to k = 0 below the threshold, and re-probe with
        k = 1 every spec_probe_every degraded rounds. Returns host
        (k_eff [B], tried [B], greedy [B]); the lookup scan may still
        zero a planned k_eff when no n-gram matches."""
        ec = self.ec
        k_eff = np.zeros((ec.max_batch,), np.int64)
        tried = np.zeros((ec.max_batch,), bool)
        greedy = np.zeros((ec.max_batch,), bool)
        for slot in np.flatnonzero(self.active):
            slot = int(slot)
            if self.slot_req[slot].temperature != 0.0:
                continue
            greedy[slot] = True
            ewma = float(self._spec_ewma[slot])
            if ewma >= ec.spec_threshold:
                k_eff[slot] = min(ec.spec_k, max(1, math.ceil(ewma * ec.spec_k)))
                tried[slot] = True
                self._spec_degraded[slot] = 0
            else:
                self._spec_degraded[slot] += 1
                if self._spec_degraded[slot] >= ec.spec_probe_every:
                    # Probe: one cheap proposal so a stream whose output
                    # turned predictable again can climb back out.
                    self._spec_degraded[slot] = 0
                    k_eff[slot] = 1
                    tried[slot] = True
        return k_eff, tried, greedy

    def _spec_history(self, slot: int):
        """Token history for the lookup scan, extended OPTIMISTICALLY
        through the in-flight round — exact history lags one round
        under the pipeline, and correctness is proposal-independent
        (the verify rejects any mismatch), so the scan assumes the
        in-flight proposals fully accept. In-flight k_eff=0 rows (the
        degraded-probe case) have a genuinely unknown pending token;
        the scan's own 1-token guess stands in for it, and None (no
        guess either) skips speculation for this slot this round."""
        req = self.slot_req[slot]
        keep = self.ec.max_seq_len - 1
        ctx = list(req.prompt_tokens[-keep:] or [0]) + self.slot_tokens[slot]
        p = self._pending
        if p is None or self._token_fresh[slot]:
            # Settled batch, or a slot (re)admitted after the in-flight
            # dispatch: host history is exact.
            return ctx
        ke = int(p.k_eff[slot])
        if ke > 0:
            ctx += [int(x) for x in p.props[slot, :ke]]
        else:
            guess = self._prompt_lookup(ctx, 1)
            if guess is None:
                return None
            ctx.append(int(guess[0]))
        return ctx

    def _spec_dispatch(self) -> Optional[_InFlightSpecStep]:
        """Device-only half of one speculative round: plan per-stream
        draft lengths, run the (pure-numpy) lookup scans — under the
        pipeline this host work executes during the PREVIOUS round's
        device window, which is the point of the split — grow paged
        capacity, chain the previous round's accepted tokens back
        on-device through _build_spec_advance, launch the draft
        proposal and the width-wide verify, and return the in-flight
        bookkeeping WITHOUT reading anything back. The verify width is
        max(k_eff)+1; a round where nothing proposes is a width-1
        verify — exactly a plain decode step, one shared code path and
        NO pipeline flush on the spec<->plain boundary. The host
        acceptance walk belongs in _spec_drain(), one step later."""
        k_eff, tried, greedy = self._plan_spec_round()
        ec = self.ec
        lookup_props = None
        if not self.spec_draft:
            lookup_props = np.zeros((ec.max_batch, ec.spec_k), np.int32)
            for slot in np.flatnonzero(k_eff > 0):
                slot = int(slot)
                ctx = self._spec_history(slot)
                guess = (
                    None if ctx is None
                    else self._prompt_lookup(ctx, int(k_eff[slot]))
                )
                if guess is None:
                    # No n-gram match (or an unknowable in-flight
                    # token): a plain decode row this round. An actual
                    # failed scan decays the EWMA; an unknowable
                    # history does not — it says nothing about the
                    # stream.
                    tried[slot] = ctx is not None
                    k_eff[slot] = 0
                else:
                    lookup_props[slot, : guess.size] = guess
        km = k_eff.max()
        width = int(km) + 1
        if self.paged:
            # Grow every slot for this round's writes. The in-flight
            # round may still advance a slot by up to its own
            # max(1, k_eff) before this one lands, so that slack joins
            # the bound; _pending is re-read per slot because
            # _ensure_capacity may _flush("preempt") mid-loop (after
            # which host_positions is settled and the slack is 0).
            for slot in np.flatnonzero(self.active):
                slot = int(slot)
                p = self._pending
                slack = 0
                if p is not None and not self._token_fresh[slot]:
                    slack = max(1, int(p.k_eff[slot]))
                self._ensure_capacity(
                    slot,
                    int(self.host_positions[slot]) + slack + width - 1,
                )
            if not self.active.any():
                return None
        bt = self.block_table if self.paged else None
        p = self._pending
        if p is None:
            tok_in, pos_in = self.tokens, self.positions
        else:
            # Chain off the undrained round's device-resident verify
            # output (JAX async dispatch makes this a device-side data
            # dependency, never a host round trip); freshly (re)admitted
            # slots merge their host-written first token/position.
            tok_in, pos_in = self._spec_advance(
                p.choices, p.sampled, p.props,
                p.k_eff.astype(np.int32), p.greedy, p.positions,
                self.tokens, self.positions,
                # idle rows too take the host's position: 0, not a drift
                self._token_fresh | ~self.active,
            )
        if self.spec_draft:
            if width > 1:
                proposals, self.draft_cache = self._propose_fn(
                    self.draft_params, self.draft_cache, bt,
                    tok_in, pos_in,
                )
                props = proposals[:, : width - 1]
            else:
                # Width-1 round: one draft step keeps the draft cache
                # hole-free for the next wide round (proposals
                # discarded; see _propose1_fn in __init__).
                warmed, self.draft_cache = self._propose1_fn(
                    self.draft_params, self.draft_cache, bt,
                    tok_in, pos_in,
                )
                props = warmed[:, :0]
        else:
            props = lookup_props[:, : width - 1]
        lora, adapter_ids = self._lora_inputs()
        self._count_step()
        choices, sampled, self.cache, key_out = self._verify_fn(
            self.params, self.cache, bt, tok_in, props,
            pos_in, self.temps, self.top_ps, self.key,
            lora, adapter_ids,
        )
        if self.overlap:
            # Key stays device-resident between rounds (reading it back
            # would block on the verify just launched).
            self.key = key_out
        else:
            with self.timeline.phase("wait.key"):
                self.key = np.asarray(key_out)  # sublint: allow[hostsync]: overlap-off fallback only — the key rides host-side so every lockstep process feeds identical replicated inputs; the overlapped path above keeps it on device
        if width > 1:
            # Width-1 rounds are plain decode steps, not verify passes —
            # tokens_per_verify must keep meaning "emitted per wide
            # verify forward".
            self.stats["verify_passes"] += 1
        self._token_fresh[:] = False
        return _InFlightSpecStep(
            choices=choices,
            sampled=sampled,
            props=props,
            positions=pos_in,
            k_eff=k_eff,
            tried=tried,
            greedy=greedy,
            slots=[
                (int(s), self.slot_req[int(s)])
                for s in np.flatnonzero(self.active)
            ],
            t_dispatch=time.perf_counter(),
        )

    def _spec_drain(self, step: _InFlightSpecStep,
                    wait: str = "drain") -> None:
        """Host half of one speculative round: THE deferred host read,
        the per-slot acceptance walk, emits, EOS/budget/window release,
        and the adaptive-k EWMA update. Greedy rows emit the longest
        matching proposal prefix (+ the target's correction on a
        mismatch; full acceptance emits k with no bonus token — the
        draft never wrote the last proposal's kv, so it seeds the next
        round and both caches stay hole-free) — token-exact vs plain
        decode; sampling rows emit the verify's position-0 sample.
        Cache staleness beyond the accepted point is safe: causal
        masking never reads past the query position, and the next round
        rewrites exactly those slots. host_positions is advanced only
        here, so on entry it IS this round's base position; each emit
        carries its own dispatch-time position snapshot (pos0 + i) so
        the context-window release stays token-exact even though the
        live arrays then jump by the whole accepted run."""
        with self.timeline.phase("wait." + wait):
            chs = np.asarray(step.choices)  # sublint: allow[hostsync]: THE deferred per-spec-round host read — the acceptance walk + emits land here, under the next round's device window
            smp = np.asarray(step.sampled)  # sublint: allow[hostsync]: same deferred read as chs; one transfer per speculative round
            props = np.asarray(step.props)  # sublint: allow[hostsync]: draft proposals reach host with the round's one deferred read (lookup proposals are already host numpy — a no-op there)
        t_drained = time.perf_counter()
        with self.timeline.phase("emit"):
            self._spec_walk(step, chs, smp, props, t_drained)
        if not self.overlap:
            # Synchronous path (gangs, forced-sync): the next dispatch
            # must feed pure host-side numpy — every lockstep process
            # replicates identical input arrays. Device chaining is
            # overlap-only.
            self._dev_tokens = None
            self._token_fresh[:] = True

    def _spec_walk(self, step: _InFlightSpecStep, chs, smp, props,
                   t_drained: float) -> None:
        """The acceptance walk and emits of one drained round."""
        d = self.ec.spec_ewma_decay
        for slot, req in step.slots:
            if self.slot_req[slot] is not req:
                continue  # EOS-lag mask: released or re-admitted slot
            if req.journey is not None:
                # Stamped at drain, same as the plain path: the round's
                # device window is never stalled for forensics.
                req.journey.record(
                    "drain",
                    lat_us=int((t_drained - step.t_dispatch) * 1e6),
                )
            ke = int(step.k_eff[slot])
            pos0 = int(self.host_positions[slot])
            if not step.greedy[slot]:
                emit_list = [int(smp[slot])]
            else:
                accepted = 0
                while (
                    accepted < ke
                    and props[slot, accepted] == chs[slot, accepted]
                ):
                    accepted += 1
                if ke > 0:
                    if req.journey is not None:
                        req.journey.record(
                            "spec_round", k=ke, accepted=accepted
                        )
                    self.stats["spec_proposed"] += ke
                    self.stats["spec_accepted"] += accepted
                    METRICS.inc(
                        "substratus_serve_spec_proposed_tokens_total",
                        by=ke,
                    )
                    METRICS.inc(
                        "substratus_serve_spec_accepted_tokens_total",
                        by=accepted,
                    )
                    self._spec_ewma[slot] = (
                        d * self._spec_ewma[slot]
                        + (1.0 - d) * (accepted / ke)
                    )
                elif step.tried[slot]:
                    # Planned a proposal but the lookup found nothing:
                    # a zero-acceptance observation (placeholder rows
                    # never skew the proposed/accepted counters).
                    self._spec_ewma[slot] = d * self._spec_ewma[slot]
                if ke > 0 and accepted == ke:
                    emit_list = [int(x) for x in props[slot, :ke]]
                else:
                    emit_list = [int(x) for x in props[slot, :accepted]]
                    emit_list.append(int(chs[slot, accepted]))
            self.tokens[slot] = emit_list[-1]
            for i, tok in enumerate(emit_list, start=1):
                self._emit(slot, tok, pos_next=pos0 + i)
                if self.slot_req[slot] is not req:
                    break  # EOS/budget/window/cancel landed mid-run
            if self.slot_req[slot] is req:  # a released slot stays at 0
                npos = min(pos0 + len(emit_list), self.ec.max_seq_len - 1)
                self.host_positions[slot] = npos
                self.positions[slot] = npos

    def _release_slot(self, slot: int) -> None:
        self.active[slot] = False
        self.slot_req[slot] = None
        self.slot_tokens[slot] = []
        if self.adapters is not None and self.slot_adapter[slot]:
            self.adapters.release(self.slot_adapter[slot])
        self.slot_adapter[slot] = 0
        # Idle rows gather the identity adapter: their decode writes
        # keep happening (static shapes) and must stay adapter-free.
        self.adapter_ids[slot] = 0
        # An idle row holds nothing: position 0 (_dispatch advances
        # decoding slots only; an admission sets the position anew).
        self.positions[slot] = 0
        self.host_positions[slot] = 0
        # ... and is greedy, as it was born: a finished sampled request
        # must not hold every later step on the sampler's sorted branch
        # (ops/sampling.py::sample reads all rows' temperatures).
        self.temps[slot] = 0.0
        self.top_ps[slot] = 1.0
        if self.paged:
            self.slot_pages.release(slot, self.alloc)
            # Point the idle slot back at the trash page; its decode writes
            # keep happening (static shapes) and must never land in a page
            # the allocator may hand to someone else.
            self.block_table[slot] = 0

    def _chunked_prefill(self, rid, prompt, slot: int, lora=None,
                         adapter_ids=None):
        """Prefill a prompt longer than one bucket: run bucket-sized chunks
        against the slot's cache (each chunk attends everything before it),
        then restore the slot into the decode cache."""
        slot_cache = self._extract_slot(self.cache, slot)
        last_logits, slot_cache = self._run_chunks(
            rid, self._chunk_fn, self.params, slot_cache, prompt, 0, None,
            lora=lora, adapter_ids=adapter_ids,
        )
        self.cache = self._restore_slot(self.cache, slot_cache, slot)
        return last_logits

    def _journey_end(self, req: Request, reason: str, **data) -> None:
        """Terminal journey bookkeeping: stamp the "end" event exactly
        once, then copy the completed journey into the engine's rings —
        journey_log for /debug/requestz?id= lookups, the slow ring
        (served at /debug/slowz) when any SLO breached mid-flight. Must
        run BEFORE the terminal ``req.out.put(None)``: a disagg
        _RemoteSink ships the journey segment on its done frame."""
        j = req.journey
        if j is None or j.ended:
            return
        j.record("end", reason=reason, **data)
        snap = j.snapshot()
        self.journey_log.add(snap)
        if j.breaches:
            self.slow.add(snap)

    def _slo_exemplar(self, req: Request, slo: str, seconds: float) -> None:
        """One SLO breach observed for this request: mark the journey
        (it lands in the slow ring at terminal) and count the exemplar."""
        j = req.journey
        if j is None:
            return
        j.breach(slo, seconds, self.slo.thresholds.get(slo, 0.0))
        METRICS.inc("substratus_serve_slo_exemplars_total", {"slo": slo})

    def _emit(self, slot: int, token_id: int,
              pos_next: Optional[int] = None):
        """Deliver one token. `pos_next` is the slot's next-write
        position AS OF THE STEP THAT SAMPLED the token: _drain passes
        its dispatch-time snapshot because under overlap the live
        host_positions already advanced for the next in-flight step —
        reading it here would release window-bounded requests one token
        early and break token-exactness vs the synchronous scheduler."""
        req = self.slot_req[slot]
        eos = req.eos_token_id if req.eos_token_id is not None else self.ec.eos_token_id
        self.slot_generated[slot] += 1
        if pos_next is None:
            pos_next = int(self.host_positions[slot])
        hit_eos = token_id == eos
        hit_budget = self.slot_generated[slot] >= req.max_tokens
        hit_window = pos_next + 1 >= self.ec.max_seq_len
        cancelled = self._is_cancelled(req)
        j = req.journey
        if not hit_eos and not cancelled:
            now = time.perf_counter()
            if req.last_emit_ts:
                d = now - req.last_emit_ts
                breach = self.slo.observe("inter_token", d)
                METRICS.observe(
                    "substratus_serve_inter_token_seconds", d,
                    exemplar=(
                        j.trace_id if breach and j is not None else None
                    ),
                )
                if breach:
                    self._slo_exemplar(req, "inter_token", d)
            elif req.submit_ts:
                d = now - req.submit_ts
                breach = self.slo.observe("ttft", d)
                METRICS.observe(
                    "substratus_serve_ttft_seconds", d,
                    exemplar=(
                        j.trace_id if breach and j is not None else None
                    ),
                )
                if breach:
                    self._slo_exemplar(req, "ttft", d)
            req.last_emit_ts = now
            req.out.put(token_id)
            self.slot_tokens[slot].append(token_id)
            if j is not None:
                j.record("emit", t=token_id)
        if hit_eos or hit_budget or hit_window or cancelled:
            # eos/cancel are natural stops; running out of budget or context
            # is a truncation ("length") clients may want to continue from.
            req.finish_reason = (
                "stop" if (hit_eos or cancelled) else "length"
            )
            self._journey_end(
                req, "cancel" if cancelled else req.finish_reason,
                tokens=self.slot_generated[slot],
            )
            req.out.put(None)
            if req.sync_id is not None:
                self._sync_reqs.pop(req.sync_id, None)
            self._release_slot(slot)

    def _step(self) -> None:
        """One scheduler step on the resolved path: pipelined when
        overlap is on, synchronous otherwise — _dispatch_any/_drain_any
        route each iteration to the speculative or plain halves, so
        spec engines pipeline exactly like plain ones."""
        if self.overlap:
            self._step_overlapped()
        else:
            self._decode_step()

    def _iterate(self) -> None:
        """One pass of the scheduler loop (the engine.iter phase): admit,
        then one decode step for every active slot."""
        tl = self.timeline
        with tl.phase("admit") as ph:
            admitted = self._admit()
            # Only iterations that boarded someone observe the admission
            # phase — an idle engine waking on its empty queue would
            # otherwise flood the histogram with ~0 s samples.
            ph.observe = admitted > 0
        if not self.active.any():
            # Nothing decoding implies nothing in flight either
            # (pipelined slots stay active until drained). Block on the
            # wake event instead of poll-spinning: submit()/resubmit()/
            # submit_migration()/set_source()/stop() set it, so
            # first-token admission latency is event-driven, not a
            # poll-tick coin flip. Lockstep gangs keep the 20ms tick —
            # every iteration pays a collective, and a follower's wake
            # event never fires for leader-side submissions.
            with tl.phase("idle"):
                if self.sync is not None:
                    time.sleep(0.02)
                else:
                    self._wake.wait(timeout=self._idle_wait_s)
                    self._wake.clear()
            return
        n_active = self.active.sum()  # host numpy mirror
        METRICS.observe(
            "substratus_serve_batch_occupancy_ratio",
            float(n_active) / self.ec.max_batch,
        )
        if self.paged:
            live = self.slot_pages.live_pages
            self.stats["kv_live_pages_sum"] += live
            self.stats["kv_pool_pages_sum"] += self.n_pages
            # Pages this step's attention has to read (a decoding slot's
            # context, one page for an idle row) against the table
            # positions a gather over every entry reads.
            need = np.where(self.active, self.positions // self.page_size, 0)
            if self._page_layers:
                self.stats["decode_kv_pages_read_sum"] += int(need.sum()) + need.size  # sublint: allow[hostsync]: host numpy mirrors, no device read
            self.stats["decode_kv_pages_table_sum"] += need.size * self.max_pages
            METRICS.observe(
                "substratus_serve_kv_page_utilization_ratio",
                live / self.n_pages, {"state": "live"},
            )
            METRICS.observe(
                "substratus_serve_kv_page_utilization_ratio",
                (self.alloc.used_pages - live) / self.n_pages,
                {"state": "cached"},
            )
        if self.slot_state:
            self.stats["state_rows_live_sum"] += int(n_active)
            self.stats["state_rows_sum"] += self.ec.max_batch
        if self._state_kernel:
            self.stats["state_kernel_steps"] += 1
        if self._ring_rows:
            # rows of a window layer's ring that hold a live sequence's
            # history: min(context, window) a decoding slot
            w = self._ring_rows
            rows = int(np.minimum(self.host_positions[self.active], w).sum())  # sublint: allow[hostsync]: host numpy mirrors, no device read
            self.stats["window_rows_live_sum"] += rows
            self.stats["window_rows_cap_sum"] += self.ec.max_batch * w
            METRICS.observe("substratus_serve_window_rows_live_ratio",
                            rows / (self.ec.max_batch * w))
        if not self._first_decode_done:
            # The first decode iteration is dominated by the executable
            # compile; record it separately so the steady-state decode
            # histogram stays unpolluted.
            t_decode = time.perf_counter()
            with tracer.span("engine.first_compile") as span:
                self._step()
                dt = time.perf_counter() - t_decode
                span.set_attribute("seconds", round(dt, 6))
            self._first_decode_done = True
            METRICS.set("substratus_serve_first_compile_seconds", dt)
            return
        self._step()
        tl.commit(
            admitted=admitted, active_slots=n_active,
            max_slots=self.ec.max_batch,
        )

    def _loop(self):
        try:
            while True:
                with self.timeline.phase("broadcast"):
                    go = self._sync_iterate()
                if not go:
                    break
                with self.timeline.phase("iter"):
                    self._iterate()
            # Clean stop with a step still in flight (stop() during
            # decode, a gang stop event, server drain): deliver its
            # tokens before the thread exits — consumers of in-flight
            # streams must see every sampled token, then their None.
            self._flush("drain")
            self._fail_staged_swaps(
                RuntimeError("engine stopped before the swap was applied")
            )
        except BaseException as e:  # propagate to waiting callers
            self.error = e
            if self.sync is not None and self.sync.leader:
                # Best-effort stop broadcast: without it every follower
                # blocks forever inside the next header collective and
                # the gang wedges with no pod failure for the JobSet
                # failurePolicy to act on.
                from substratus_tpu.serve.multihost import encode_events

                try:
                    self.sync.broadcast(encode_events([], [], True))
                except Exception:  # sublint: allow[broad-except]: the collective itself may be what broke; the original error is re-raised below
                    logging.getLogger(__name__).warning(
                        "stop broadcast failed after engine error "
                        "(trace_id=%s)", current_trace_id(), exc_info=True,
                    )

            def kill(req: Request) -> None:
                # "error", not the "stop" default: consumers must be able
                # to tell an engine crash from a clean EOS.
                req.finish_reason = "error"
                self._journey_end(req, "error", cause="engine")
                req.out.put(None)

            if self._admitting is not None:
                kill(self._admitting)
            for req in self.slot_req:
                if req is not None:
                    kill(req)
            for req in self._resume:
                kill(req)
            for mig in self._resume_migrations:
                kill(mig.req)
            while not self._migrations.empty():
                try:
                    kill(self._migrations.get_nowait().req)
                except queue.Empty:
                    break
            while not self.queue.empty():
                try:
                    kill(self.queue.get_nowait())
                except queue.Empty:
                    break
            self._fail_staged_swaps(e)
            raise

    def load_snapshot(self) -> Dict[str, object]:
        """Cheap load report for the gateway protocol (gateway/
        loadreport.py): host-side counters only, no device read, no
        lock — a slightly torn snapshot routes a request marginally
        suboptimally, which is fine. Served on /loadz and compacted
        into the x-substratus-load response header."""
        active = int(self.active.sum())
        if self.paged:
            kv_free = self.alloc.free_pages / max(1, self.n_pages)
        else:
            kv_free = (self.ec.max_batch - active) / self.ec.max_batch
        if self.ec.role == "prefill" and self.handoff is not None:
            transfer_q = self.handoff.depth()
        elif self.ec.role == "decode":
            transfer_q = self._migrations.qsize() + len(
                self._resume_migrations
            )
        else:
            transfer_q = 0
        snap = {
            "queue_depth": self.queue.qsize() + len(self._resume),
            "active_slots": active,
            "max_slots": self.ec.max_batch,
            "kv_free_frac": round(kv_free, 4),
            "max_queue": self.ec.max_queue,
            # Disaggregated serving (serve/disagg.py): which phase this
            # replica runs, and how deep its transfer/migration backlog
            # is — the gateway's role-aware routing reads both.
            "role": self.ec.role,
            "transfer_queue_depth": transfer_q,
            # Overlapped decode scheduling (resolved value, not the
            # config): whether this engine pipelines host work under the
            # in-flight device step (docs/performance.md).
            "overlap": self.overlap,
            # Hot weight-swap (docs/serving.md "Zero-downtime rollout"):
            # which parameter version this replica is serving — the
            # rollout coordinator polls this to confirm a swap landed.
            "weights_version": self.weights_version,
            # Prefix-cache effectiveness, mirrored for /loadz consumers
            # (also on /metrics as the *_total counters).
            "prefill_tokens": self.stats["prefill_tokens"],
            "prefix_hit_tokens": self.stats["prefix_hit_tokens"],
            # Report ordering (gateway/fleet.py): per-replica monotonic
            # sequence + wall clock, compacted to sq=/ts= on the
            # x-substratus-load header — the fleet aggregator drops
            # stale/out-of-order deliveries from hedged responses.
            "load_seq": next(self._load_seq),
            "load_ts": round(time.time(), 3),
            # SLO sketches + burn counters (observability/sketch.py):
            # mergeable fixed-bucket percentile state the gateway rolls
            # up fleet-wide on every /loadz poll.
            "slo": self.slo.snapshot(),
        }
        if self.spec:
            # Speculation effectiveness for /loadz consumers (mirrors
            # the substratus_serve_spec_*_tokens_total counters):
            # lifetime acceptance plus each active stream's RESOLVED
            # adaptive draft length — what the EWMA policy would plan
            # next round, 0 for degraded/sampling rows. Torn reads are
            # fine (same contract as the rest of this snapshot).
            prop = self.stats["spec_proposed"]
            acc = self.stats["spec_accepted"]
            ks = []
            for slot in np.flatnonzero(self.active):
                slot = int(slot)
                req = self.slot_req[slot]
                ewma = float(self._spec_ewma[slot])
                if (
                    req is None
                    or req.temperature != 0.0
                    or ewma < self.ec.spec_threshold
                ):
                    ks.append(0)
                else:
                    ks.append(
                        min(
                            self.ec.spec_k,
                            max(1, math.ceil(ewma * self.ec.spec_k)),
                        )
                    )
            snap["spec"] = {
                "proposed_tokens": prop,
                "accepted_tokens": acc,
                "acceptance": round(acc / prop, 4) if prop else None,
                "adaptive_k": ks,
            }
        src = self.source
        if src is not None and hasattr(src, "progress"):
            # Batch-generation progress (serve/batchgen.py): manifest
            # totals + done/in-flight counts, so /loadz answers for an
            # offline run when its progress server is enabled.
            snap["batchgen"] = src.progress()
        if self.adapters is not None:
            # Resident adapter ids + hit/miss/evict counters: the
            # gateway's affinity scoring reads `adapters` (loadreport.py
            # piggybacks it as `ad=` on x-substratus-load).
            a = self.adapters.snapshot()
            snap["adapters"] = a["loaded"]
            snap["adapter_capacity"] = a["capacity"]
            snap["adapter_hits"] = a["hits"]
            snap["adapter_misses"] = a["misses"]
            snap["adapter_evictions"] = a["evictions"]
        return snap

    # --- synchronous helper (tests / bench) -------------------------------

    def generate(
        self, prompt_tokens: List[int], max_tokens: int = 32, **kw
    ) -> List[int]:
        """Blocking single-request generation (engine must be started)."""
        req = self.submit(Request(prompt_tokens, max_tokens=max_tokens, **kw))
        out: List[int] = []
        while True:
            tok = req.out.get(timeout=120)
            if tok is None:
                return out
            out.append(tok)
