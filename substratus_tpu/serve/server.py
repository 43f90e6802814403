"""OpenAI-compatible HTTP server on the container contract.

Contract (reference: docs/container-contract.md:50-56, test/system.sh:73-78):
  * listens on port 8080;
  * `GET /` returns 200 once the model is ready (readiness probe target);
  * `POST /v1/completions` accepts {prompt, max_tokens, temperature, top_p,
    stream} and returns an OpenAI-style completion body.

Also exposes `/v1/chat/completions` (template-joined messages) and
`/v1/models`. The HTTP layer is a thin asyncio shim over the Engine's
thread-safe request queue; all device work stays on the engine thread.
"""
from __future__ import annotations

import asyncio
import json
import logging
import math
import os
import re
import time
import uuid
from typing import Optional

from aiohttp import web

from substratus_tpu.gateway.limiter import deadline_remaining, parse_deadline
from substratus_tpu.gateway.loadreport import HEADER as LOAD_HEADER
from substratus_tpu.gateway.loadreport import LoadReport
from substratus_tpu.observability.events import EVENTS
from substratus_tpu.observability.httpstats import count_http_response
from substratus_tpu.observability.metrics import METRICS
from substratus_tpu.observability.propagation import parse_traceparent
from substratus_tpu.observability.tracing import tracer
from substratus_tpu.serve.adapters import UnknownAdapter
from substratus_tpu.serve.engine import Engine, EngineOverloaded, Request
from substratus_tpu.serve.tokenizer import Tokenizer
from substratus_tpu.utils.jaxstart import device_memory, startup_record

# Structured access log: one JSON line per traced request, carrying the
# trace id so log pipelines join lines to span exports
# (docs/observability.md "Joining logs to traces").
access_log = logging.getLogger("substratus.serve.access")

# Scrape-time engine gauges (request-latency histograms live in
# serve/engine.py; the full catalog is docs/observability.md).
for _name, _help in (
    ("substratus_serve_active_slots", "Decode slots currently generating."),
    ("substratus_serve_max_slots", "Configured decode slot count (max_batch)."),
    ("substratus_serve_queue_depth", "Requests waiting for a decode slot."),
    ("substratus_serve_kv_pages_total", "KV pool size in pages (paged layout)."),
    ("substratus_serve_kv_pages_free", "Unallocated KV pages (paged layout)."),
):
    METRICS.describe(_name, _help, type="gauge")
METRICS.describe(
    "substratus_serve_requests_total",
    "Completion requests received.", type="counter",
)


class ServerState:
    def __init__(self, engine: Engine, tokenizer: Tokenizer, model_name: str,
                 authorizer=None, checkpoint_loader=None):
        self.engine = engine
        self.tokenizer = tokenizer
        self.model_name = model_name
        # Checkpoint ref -> param tree ready to install (same family/
        # shape/quantization pipeline the boot path used). POST /swapz
        # needs it; None = the replica cannot hot-swap (endpoint answers
        # 501 so a rollout controller skips it honestly).
        self.checkpoint_loader = checkpoint_loader
        self.ready = True
        # SIGTERM flips this: readiness (`GET /`, `/loadz`) answers 503
        # so the gateway/Service stop routing here, while in-flight
        # streams keep running to the drain deadline (serve_forever).
        self.draining = False
        # The /debug/* plane is gated by the same RBAC check as protected
        # /metrics (observability/authz.py MetricsAuthorizer); None = open
        # (local dev, no kube client to review tokens against).
        self.authorizer = authorizer
        # In-flight request registry for /debug/requestz: request id ->
        # {req, endpoint, trace_id, start}. Mutated only on the event
        # loop (track on submit, untrack when the handler finishes).
        self.inflight: dict = {}

    def track_request(self, req: Request, endpoint: str) -> None:
        ctx = tracer.current_context()
        self.inflight[req.id] = {
            "req": req,
            "endpoint": endpoint,
            "trace_id": ctx.trace_id if ctx is not None else None,
            "start": time.time(),
        }

    def untrack_request(self, req: Request) -> None:
        self.inflight.pop(req.id, None)

    def render_chat(self, messages):
        """Messages -> (prompt, templated) using the MODEL'S chat
        template when the tokenizer carries one (HF apply_chat_template,
        or a GGUF's embedded jinja tokenizer.chat_template) — chat
        checkpoints are trained on their template and degrade badly off
        it. `templated` tells encoding to parse the special tokens the
        template rendered and skip the automatic BOS (the template
        already placed one). Falls back to the generic role-joined
        transcript, loudly when a template EXISTS but fails."""
        tmpl = getattr(self.tokenizer, "apply_chat_template", None)
        if tmpl is not None:
            try:
                rendered = tmpl(messages)
            except Exception:  # sublint: allow[broad-except]: a broken template must not take down the endpoint
                # ...but silence here would serve off-format prompts
                # with no trace, hence the loud log below.
                logging.getLogger(__name__).exception(
                    "chat template failed; using the generic transcript"
                )
                rendered = None
            if rendered is not None:
                return rendered, True
        prompt = "\n".join(
            f"{m.get('role', 'user')}: {m.get('content', '')}"
            for m in messages
        )
        return prompt + "\nassistant:", False

    def encode_prompt(self, prompt: str, templated: bool = False):
        """Prompt -> ids; template-rendered prompts use the tokenizer's
        special-token-aware path (no doubled BOS, control tokens as ids)
        when it has one."""
        if templated:
            enc = getattr(self.tokenizer, "encode_templated", None)
            if enc is not None:
                return enc(prompt)
        return self.tokenizer.encode(prompt)


def _find_stop(text: str, stop) -> Optional[int]:
    """Earliest index of any stop sequence in text, or None. The single
    matching semantic shared by the cancellation trigger and the final
    truncation."""
    cuts = [idx for s in stop or [] if s and (idx := text.find(s)) != -1]
    return min(cuts) if cuts else None


async def _collect(req: Request, tokenizer=None, stop=None) -> list[int]:
    """Await all tokens of a request without blocking the event loop.

    With `stop` sequences, a bounded tail of the accumulating text is
    checked per token (O(n), not O(n^2)); on a match the engine request is
    cancelled so its slot frees immediately instead of decoding to
    max_tokens."""
    loop = asyncio.get_running_loop()
    out: list[int] = []
    # A match must end at the newest token; decoding the last
    # 4*max_stop_len+8 tokens always covers it (>=1 byte per token, <=4
    # bytes per char).
    window = 4 * max((len(s) for s in stop), default=0) + 8 if stop else 0
    while True:
        tok = await loop.run_in_executor(None, req.out.get)
        if tok is None:
            return out
        out.append(tok)
        if stop and tokenizer is not None:
            tail = tokenizer.decode(out[-window:])
            if _find_stop(tail, stop) is not None and _find_stop(
                tokenizer.decode(out), stop
            ) is not None:
                # The tail decode is a cheap filter; BPE boundary effects
                # (leading-space stripping) can make it differ from the
                # suffix of the full decode, so confirm on the full text
                # before cancelling — a false positive would silently
                # truncate output while reporting finish_reason "stop".
                req.cancelled = True
                while (
                    await loop.run_in_executor(None, req.out.get) is not None
                ):
                    pass
                return out


_TRACED_PREFIXES = ("/v1/", "/debug/")


@web.middleware
async def trace_middleware(request: web.Request, handler):
    """Distributed-tracing boundary for the serving plane.

    Parses the W3C `traceparent` request header (CLI and upstream proxies
    inject it) and wraps the handler in a `serve.http` span parented under
    the remote context — so one trace id survives CLI -> server -> engine.
    The trace id is echoed as an `x-trace-id` response header (streamed
    responses stamp it before prepare, see _stream), stamped into every
    error payload, and logged as a structured access line. Probe and
    scrape paths (`/`, `/metrics`) stay untraced — a 5 s Prometheus
    scrape interval would otherwise dominate the span ring — but every
    path, traced or not, bumps substratus_http_requests_total (the
    shed-rate denominator shared with the gateway) and /v1/ responses
    carry the x-substratus-load report header."""
    if not request.path.startswith(_TRACED_PREFIXES):
        try:
            resp = await handler(request)
        except web.HTTPException as e:
            count_http_response(request.path, e.status)
            raise
        count_http_response(request.path, resp.status)
        return resp
    remote = parse_traceparent(request.headers.get("traceparent"))
    span = tracer.span(
        "serve.http", parent=remote,
        method=request.method, path=request.path,
    )
    t0 = time.perf_counter()
    status = 500
    try:
        with span:
            try:
                resp = await handler(request)
            except web.HTTPException as e:
                # aiohttp error responses ARE responses; stamp the trace
                # id so the client can quote it back.
                status = e.status
                span.set_attribute("http_status", e.status)
                e.headers["x-trace-id"] = span.trace_id
                raise
            except Exception as e:  # sublint: allow[broad-except]: last-resort handler — a JSON 500 with the trace id beats an opaque text 500
                logging.getLogger(__name__).exception(
                    "unhandled error serving %s", request.path
                )
                span.set_attribute("http_status", 500)
                return web.json_response(
                    {"error": f"{type(e).__name__}: {e}",
                     "trace_id": span.trace_id},
                    status=500, headers={"x-trace-id": span.trace_id},
                )
            status = resp.status
            span.set_attribute("http_status", status)
            if not resp.prepared:
                resp.headers["x-trace-id"] = span.trace_id
                state = request.app.get("state")
                if state is not None and request.path.startswith("/v1/"):
                    # Passive load reporting: the gateway learns this
                    # replica's load from the responses it already gets
                    # (streamed responses stamp it in _stream).
                    resp.headers[LOAD_HEADER] = LoadReport.from_snapshot(
                        state.engine.load_snapshot()
                    ).to_header()
            return resp
    finally:
        count_http_response(request.path, status)
        access_log.info(
            json.dumps(
                {
                    "event": "http_request",
                    "method": request.method,
                    "path": request.path,
                    "status": status,
                    "duration_ms": round(
                        (time.perf_counter() - t0) * 1e3, 3
                    ),
                    "trace_id": span.trace_id,
                    "span_id": span.span_id,
                },
                separators=(",", ":"),
            )
        )


def _completion_body(state: ServerState, text: str, n_prompt: int,
                     n_gen: int, finish_reason: str = "stop",
                     model: Optional[str] = None):
    return {
        "id": f"cmpl-{uuid.uuid4().hex[:24]}",
        "object": "text_completion",
        "created": int(time.time()),
        # Echo the tenant the request named (OpenAI semantics); the
        # base model's name when none was given.
        "model": model or state.model_name,
        "choices": [
            {
                "index": 0,
                "text": text,
                "finish_reason": finish_reason,
                "logprobs": None,
            }
        ],
        "usage": {
            "prompt_tokens": n_prompt,
            "completion_tokens": n_gen,
            "total_tokens": n_prompt + n_gen,
        },
    }


def build_app(state: ServerState) -> web.Application:
    routes = web.RouteTableDef()

    @routes.get("/")
    async def root(request: web.Request) -> web.Response:
        if state.engine.error is not None:
            return web.Response(status=500, text=str(state.engine.error))
        if state.draining:
            return web.Response(status=503, text="draining")
        return web.Response(status=200 if state.ready else 503, text="ok")

    @routes.get("/loadz")
    async def loadz(request: web.Request) -> web.Response:
        """The load-report endpoint of the gateway protocol (gateway/
        loadreport.py): engine queue/slot/KV counters plus readiness.
        Answers 503 while draining — the gateway's poller treats any
        non-200 as 'stop routing here' without ejecting, which is
        exactly the graceful-shutdown contract."""
        snap = state.engine.load_snapshot()
        snap["model"] = state.model_name
        snap["draining"] = state.draining
        if state.engine.error is not None:
            return web.json_response(
                {**snap, "error": str(state.engine.error)}, status=500
            )
        status = 200 if (state.ready and not state.draining) else 503
        return web.json_response(snap, status=status)

    async def _authorize_debug(request: web.Request) -> None:
        """Gate a /debug/* route with the metrics RBAC check (TokenReview +
        SubjectAccessReview through state.authorizer); open when no
        authorizer is configured (local dev)."""
        if state.authorizer is None:
            return
        loop = asyncio.get_running_loop()
        status, reason = await loop.run_in_executor(
            None, state.authorizer.allow,
            request.headers.get("Authorization"),
        )
        if status == 200:
            return
        if status == 401:
            raise web.HTTPUnauthorized(
                text=reason, headers={"WWW-Authenticate": "Bearer"}
            )
        if status == 403:
            raise web.HTTPForbidden(text=reason)
        raise web.HTTPInternalServerError(text=reason)

    swap_lock = asyncio.Lock()

    @routes.post("/swapz")
    async def swapz(request: web.Request) -> web.Response:
        """Hot weight-swap: load the named checkpoint ref and install it
        on the live engine via Engine.swap_params — no drain, no engine
        teardown, compiled programs kept (docs/serving.md "Zero-downtime
        rollout"). Body: {"checkpoint": ref, "version": optional int,
        "source": "swap"|"rollout"}. Gated by the same RBAC check as the
        /debug plane: swapping weights is strictly more powerful than
        reading debug state."""
        await _authorize_debug(request)
        try:
            body = await request.json()
        except json.JSONDecodeError:
            raise web.HTTPBadRequest(text="invalid JSON body")
        ref = body.get("checkpoint")
        if not ref or not isinstance(ref, str):
            raise web.HTTPBadRequest(text="missing 'checkpoint'")
        source = str(body.get("source", "swap"))
        if source not in ("swap", "rollout"):
            raise web.HTTPBadRequest(
                text="'source' must be 'swap' or 'rollout'"
            )
        version = body.get("version")
        if version is not None:
            try:
                version = int(version)
            except (TypeError, ValueError):
                raise web.HTTPBadRequest(text="'version' must be an integer")
        if state.checkpoint_loader is None:
            raise web.HTTPNotImplemented(
                text=json.dumps({"error": {
                    "message": "this replica has no checkpoint loader "
                               "configured; hot swap is unavailable",
                    "type": "swap_unavailable",
                }}),
                content_type="application/json",
            )
        loop = asyncio.get_running_loop()
        # One swap at a time per replica: concurrent loads would race on
        # version ordering and double the peak host memory for no benefit.
        async with swap_lock:
            try:
                params = await loop.run_in_executor(
                    None, state.checkpoint_loader, ref
                )
                applied = await loop.run_in_executor(
                    None,
                    lambda: state.engine.swap_params(
                        params, version=version, source=source
                    ),
                )
            except ValueError as e:
                # Shape/dtype/tree mismatch — the engine rejected the
                # swap and kept serving the old weights (409: the request
                # conflicts with the live model's structure).
                raise web.HTTPConflict(
                    text=json.dumps({"error": {
                        "message": str(e), "type": "swap_rejected",
                    }}),
                    content_type="application/json",
                )
            except FileNotFoundError as e:
                raise web.HTTPBadRequest(
                    text=json.dumps({"error": {
                        "message": str(e), "type": "checkpoint_not_found",
                    }}),
                    content_type="application/json",
                )
        return web.json_response(
            {"weights_version": applied, "checkpoint": ref,
             "source": source}
        )

    profile_lock = asyncio.Lock()
    # On-demand capture state: {"dir", "started", "task"} while a
    # start/stop capture is live, else empty.
    profile_state: dict = {}
    PROFILE_CAP_S = 60.0

    def _profiler():
        """The JAX profiler module, or None (no-op fallback: serving
        builds without a working profiler still answer the endpoint)."""
        try:
            import jax

            jax.profiler.start_trace  # attribute probe
            return jax.profiler
        except Exception:  # sublint: allow[broad-except]: any import/attr failure means the profiler is absent; endpoint answers no-op
            return None

    def _profile_dir() -> str:
        base = os.environ.get("PROFILE_DIR", "/tmp/substratus-profile")
        return os.path.join(base, time.strftime("%Y%m%d-%H%M%S"))

    def _stop_capture(prof) -> dict:
        """Stop the live capture; returns its summary (caller holds the
        invariants: profile_state non-empty, prof available)."""
        info = dict(profile_state)
        profile_state.clear()
        task = info.pop("task", None)
        if task is not None:
            task.cancel()
        try:
            prof.stop_trace()
        except Exception as e:  # sublint: allow[broad-except]: a capture that failed to start must still be clearable; error surfaces in the response
            info["stop_error"] = str(e)
        elapsed = round(time.perf_counter() - info.pop("t0"), 3)
        with tracer.span(
            "serve.profile", mode="capture", dir=info.get("dir", ""),
        ) as span:
            span.set_attribute("seconds", elapsed)
        EVENTS.emit(
            "ProfileCaptureStopped", kind="Server", name=state.model_name,
            message=f"device trace in {info.get('dir', '')}",
        )
        info["seconds"] = elapsed
        return info

    @routes.post("/debug/profile")
    async def profile(request: web.Request) -> web.Response:
        """Capture a JAX/XLA device trace while serving traffic (SURVEY.md
        §5: the reference had no profiling story; here it is an endpoint).

        Two modes, both writing TensorBoard-format traces under a fixed
        base dir (PROFILE_DIR env overrides; never caller-controlled):

          * {"seconds": N (0 < N <= 60)} — blocking capture of N seconds;
          * {"action": "start"} / {"action": "stop"} — on-demand capture
            bracketing exactly the traffic you care about, with a 60 s
            watchdog cap so a forgotten "stop" can't profile forever.

        Every capture records a `serve.profile` span and a
        ProfileCapture* event. Without a working profiler the endpoint
        answers {"profiler": "unavailable"} instead of failing."""
        await _authorize_debug(request)
        try:
            body = await request.json()
        except json.JSONDecodeError:
            body = {}
        if not isinstance(body, dict):
            raise web.HTTPBadRequest(text="body must be a JSON object")
        prof = _profiler()
        action = body.get("action")
        if action not in (None, "start", "stop"):
            raise web.HTTPBadRequest(text="'action' must be start or stop")

        if action == "stop":
            if not profile_state:
                raise web.HTTPConflict(text="no profile capture is running")
            if prof is None:  # started state can't exist without a profiler
                profile_state.clear()
                return web.json_response({"profiler": "unavailable"})
            return web.json_response({"stopped": True, **_stop_capture(prof)})

        if action == "start":
            if profile_state or profile_lock.locked():
                raise web.HTTPConflict(
                    text="a profile capture is already running"
                )
            if prof is None:
                return web.json_response(
                    {"profiler": "unavailable", "started": False}
                )
            out_dir = _profile_dir()
            try:
                prof.start_trace(out_dir)
            except Exception as e:  # sublint: allow[broad-except]: profiler backends raise anything; converted to a 500 with the message
                raise web.HTTPInternalServerError(
                    text=f"profiler failed to start: {e}"
                )
            EVENTS.emit(
                "ProfileCaptureStarted", kind="Server",
                name=state.model_name, message=f"device trace to {out_dir}",
            )

            async def watchdog():
                await asyncio.sleep(PROFILE_CAP_S)
                if profile_state:
                    _stop_capture(prof)

            profile_state.update(
                {"dir": out_dir, "t0": time.perf_counter(),
                 "task": asyncio.get_running_loop().create_task(watchdog())}
            )
            return web.json_response(
                {"started": True, "dir": out_dir,
                 "cap_seconds": PROFILE_CAP_S}
            )

        # Blocking mode: {"seconds": N}.
        try:
            seconds = float(body.get("seconds", 3))
        except (TypeError, ValueError):
            raise web.HTTPBadRequest(text="'seconds' must be a number")
        if not (0 < seconds <= 60):
            raise web.HTTPBadRequest(text="'seconds' must be in (0, 60]")

        out_dir = _profile_dir()
        if profile_lock.locked() or profile_state:
            raise web.HTTPConflict(text="a profile capture is already running")
        if prof is None:
            return web.json_response(
                {"profiler": "unavailable", "dir": out_dir, "files": []}
            )
        async with profile_lock:
            loop = asyncio.get_running_loop()

            def capture():
                with tracer.span(
                    "serve.profile", mode="blocking", dir=out_dir,
                    seconds=seconds,
                ):
                    prof.start_trace(out_dir)
                    try:
                        time.sleep(seconds)
                    finally:
                        prof.stop_trace()

            await loop.run_in_executor(None, capture)
        EVENTS.emit(
            "ProfileCaptureStopped", kind="Server", name=state.model_name,
            message=f"device trace in {out_dir}",
        )
        files = []
        for root, _, names in os.walk(out_dir):
            files.extend(os.path.join(root, n) for n in names)
        return web.json_response(
            {"dir": out_dir, "seconds": seconds, "files": sorted(files)[-10:]}
        )

    @routes.get("/debug/tracez")
    async def tracez(request: web.Request) -> web.Response:
        """Flight recorder: recent traces from the span ring, grouped by
        root span and latency-bucketed — the 'what has the server been
        doing' page, no collector required."""
        await _authorize_debug(request)
        spans = tracer.finished()
        by_trace: dict = {}
        for s in spans:
            by_trace.setdefault(s["trace_id"], []).append(s)
        buckets = (0.01, 0.1, 1.0)  # seconds; final bucket is +Inf

        def bucket_label(duration_us: int) -> str:
            sec = duration_us / 1e6
            for b in buckets:
                if sec <= b:
                    return f"le_{b}s"
            return "gt_1s"

        traces = []
        by_root: dict = {}
        for tid, ss in by_trace.items():
            ids = {s["span_id"] for s in ss}
            # Root = no parent, or a parent outside the buffer (remote
            # caller / ring-evicted ancestor).
            root = next(
                (s for s in ss
                 if not s["parent_id"] or s["parent_id"] not in ids),
                ss[0],
            )
            errors = [s["status"] for s in ss if s["status"] != "ok"]
            traces.append(
                {
                    "trace_id": tid,
                    "root": root["name"],
                    "start_us": root["start_us"],
                    "duration_us": root["duration_us"],
                    "spans": len(ss),
                    "status": errors[0] if errors else "ok",
                }
            )
            hist = by_root.setdefault(
                root["name"],
                {f"le_{b}s": 0 for b in buckets} | {"gt_1s": 0},
            )
            hist[bucket_label(root["duration_us"])] += 1
        traces.sort(key=lambda t: t["start_us"], reverse=True)
        return web.json_response(
            {
                "traces": traces[:100],
                "latency_buckets": by_root,
                "buffered_spans": len(spans),
                "dropped_spans": tracer.dropped,
            }
        )

    @routes.get("/debug/requestz")
    async def requestz(request: web.Request) -> web.Response:
        """In-flight completion requests: age, where each one is in the
        engine (decoding slot / queue position), tokens emitted so far.

        With ?id=<trace id or request id> the page upgrades to the full
        request journey (observability/journey.py) — the stitched event
        timeline plus a Chrome-trace rendering (save "chrome_trace" and
        load it in chrome://tracing / Perfetto). Lookup order: live
        in-flight requests first, then the engine's completed-journey
        ring, then the slow ring."""
        await _authorize_debug(request)
        eng = state.engine
        wanted = request.query.get("id")
        if wanted:
            from substratus_tpu.observability.journey import (
                chrome_trace,
                waterfall,
            )

            snap = None
            for info in list(state.inflight.values()):
                j = getattr(info["req"], "journey", None)
                if j is not None and wanted in (j.trace_id, info["req"].id):
                    snap = j.snapshot()
                    break
            if snap is None:
                snap = eng.journey_log.find(wanted)
            if snap is None:
                for entry in eng.slow.snapshot():
                    if wanted in (entry.get("trace_id"), entry.get("rid")):
                        snap = entry.get("journey")
                        break
            if snap is None:
                raise web.HTTPNotFound(
                    text=f"no journey for id {wanted!r}"
                )
            return web.json_response({
                "journey": snap,
                "waterfall": waterfall(snap),
                "chrome_trace": chrome_trace(snap),
            })
        now = time.time()
        # Snapshots; the scheduler thread mutates these concurrently and
        # a debug page may be slightly stale, never wrong-by-crash.
        slot_req = list(eng.slot_req)
        queued = list(getattr(eng.queue, "queue", ()))
        rows = []
        for info in list(state.inflight.values()):
            req = info["req"]
            slot = next(
                (i for i, r in enumerate(slot_req) if r is req), None
            )
            if slot is not None:
                where = "decoding"
                tokens = eng.slot_generated[slot]
                queue_position = None
            else:
                pos = next(
                    (i for i, r in enumerate(queued) if r is req), None
                )
                where = "queued" if pos is not None else "pending"
                tokens = 0
                queue_position = pos
            rows.append(
                {
                    "request_id": req.id,
                    "endpoint": info["endpoint"],
                    "trace_id": info["trace_id"],
                    "age_s": round(now - info["start"], 3),
                    "state": where,
                    "slot": slot,
                    "queue_position": queue_position,
                    "prompt_tokens": len(req.prompt_tokens),
                    "max_tokens": req.max_tokens,
                    "tokens_emitted": tokens,
                }
            )
        rows.sort(key=lambda r: r["age_s"], reverse=True)
        return web.json_response(
            {
                "inflight": rows,
                "queue_depth": eng.queue.qsize(),
                # Completed journeys retrievable via ?id= (newest last).
                "journeys": eng.journey_log.ids(),
            }
        )

    @routes.get("/debug/perfz")
    async def perfz(request: web.Request) -> web.Response:
        """Performance flight recorder: the scheduler's phase-level
        timing breakdown (admission / broadcast / prefill / decode /
        sample), first-compile duration, what the process's start was
        made of (`startup`: phases, and every executable built by stage
        and program), request-latency quantiles, and the engine's live
        counters — the 'where does an iteration's time
        go' page, rendered from the shared registry with no scrape
        pipeline required. Phases NEST (admission contains prefill
        contains sample): they time named sections, not a partition."""
        await _authorize_debug(request)
        from substratus_tpu.observability.metrics import (
            quantile_from_buckets,
        )

        _phase_re = re.compile(r'^phase="(.*)"$')

        def family(name: str, key_label: str = "") -> dict:
            out = {}
            for ls, s in METRICS.histogram_series(name).items():
                m = _phase_re.match(ls) if ls else None
                key = m.group(1) if m else (ls or "all")
                out[key] = {
                    "count": s["count"],
                    "sum_s": round(s["sum"], 6),
                    "mean_s": (
                        round(s["sum"] / s["count"], 6) if s["count"] else None
                    ),
                    **{
                        f"p{int(q * 100)}_s": (
                            None
                            if (v := quantile_from_buckets(s["buckets"], q))
                            is None
                            else round(v, 6)
                        )
                        for q in (0.5, 0.9, 0.99)
                    },
                }
            return out

        eng = state.engine
        return web.json_response(
            {
                "phases": family("substratus_serve_phase_seconds"),
                "first_compile_seconds": METRICS.get(
                    "substratus_serve_first_compile_seconds"
                ),
                "startup": startup_record(),
                "latencies": {
                    short: family(f"substratus_serve_{short}_seconds")
                    for short in ("ttft", "inter_token", "queue_wait")
                },
                "occupancy": family("substratus_serve_batch_occupancy_ratio"),
                "train_phases": family("substratus_train_phase_seconds"),
                "engine": {
                    "active_slots": int(eng.active.sum()),
                    "max_slots": eng.ec.max_batch,
                    "queue_depth": eng.queue.qsize(),
                    "kv_layout": "paged" if eng.paged else "dense",
                    "kv_page_tokens": eng.page_size if eng.paged else None,
                    "stats": dict(eng.stats),
                },
            }
        )

    @routes.get("/debug/stepz")
    async def stepz(request: web.Request) -> web.Response:
        """Engine step timeline as Chrome-trace JSON (observability/
        timeline.py): one span per scheduler iteration with admission/
        drain/flush sub-spans and per-cause pipeline-bubble attribution
        in the args — save the body and load it in chrome://tracing or
        Perfetto. `otherData` carries the lifetime bubble totals
        (substratus_serve_pipeline_bubble_seconds mirrors them as
        counters) and the floor estimate. Same RBAC gate as the rest
        of the /debug plane."""
        await _authorize_debug(request)
        tl = state.engine.timeline
        body = tl.chrome_trace()
        body["otherData"]["bubble"] = tl.bubble_totals()
        floor = tl.floor_estimate()
        body["otherData"]["floor_estimate_s"] = (
            round(floor, 6) if floor is not None else None
        )
        return web.json_response(body)

    @routes.get("/debug/slowz")
    async def slowz(request: web.Request) -> web.Response:
        """Slow-request exemplars: the bounded ring of SLO-breaching
        journeys (observability/journey.py SlowRing) plus the per-bucket
        exemplar trace ids attached to the TTFT / inter-token latency
        histograms — a dashboard can jump from a p99 bucket straight to
        the offending journey via /debug/requestz?id=<trace_id>. Same
        RBAC gate as the rest of the /debug plane."""
        await _authorize_debug(request)
        eng = state.engine
        return web.json_response({
            "slow": eng.slow.snapshot(),
            "total_breaching": eng.slow.total,
            "slo": eng.slo.snapshot(),
            "exemplars": {
                short: METRICS.exemplars(
                    f"substratus_serve_{short}_seconds"
                )
                for short in ("ttft", "inter_token")
            },
        })

    @routes.get("/debug/eventz")
    async def eventz(request: web.Request) -> web.Response:
        """Recent events from the shared recorder (count-deduped, newest
        first) — reconcile transitions when a controller shares the
        process, profile captures, anything emitted through EVENTS."""
        await _authorize_debug(request)
        return web.json_response(
            {"events": EVENTS.recent(100), "dropped": EVENTS.dropped}
        )

    @routes.get("/metrics")
    async def metrics(request: web.Request) -> web.Response:
        """Prometheus-format serving metrics: point-in-time engine gauges
        refreshed at scrape, plus everything already in the shared registry
        (latency histograms from serve/engine.py, reconcile counters when a
        controller shares the process). One registry, one exposition."""
        eng = state.engine
        METRICS.set("substratus_serve_active_slots", int(eng.active.sum()))
        METRICS.set("substratus_serve_max_slots", eng.ec.max_batch)
        METRICS.set("substratus_serve_queue_depth", eng.queue.qsize())
        for k, v in eng.stats.items():
            METRICS.set(f"substratus_serve_{k}", v)
        if getattr(eng, "paged", False):
            METRICS.set("substratus_serve_kv_pages_total", eng.n_pages)
            METRICS.set("substratus_serve_kv_pages_free", eng.alloc.free_pages)
        device_memory()  # substratus_device_* gauges (utils/jaxstart.py)
        # The versioned content type Prometheus negotiates for (the
        # controller endpoint in observability/health.py already sends it;
        # a bare text/plain leaves the scraper guessing the format version).
        return web.Response(
            body=METRICS.render().encode(),
            headers={"Content-Type": "text/plain; version=0.0.4; charset=utf-8"},
        )

    @routes.get("/v1/models")
    async def models(request: web.Request) -> web.Response:
        data = [
            {
                "id": state.model_name,
                "object": "model",
                "owned_by": "substratus-tpu",
            }
        ]
        if state.engine.adapters is not None:
            # Every servable tenant adapter is a model clients can name
            # in the OpenAI `model` field (loaded or hot-loadable).
            loaded = set(state.engine.adapters.loaded_ids())
            data.extend(
                {
                    "id": aid,
                    "object": "model",
                    "owned_by": "substratus-tpu",
                    "parent": state.model_name,
                    "loaded": aid in loaded,
                }
                for aid in state.engine.adapters.available_ids()
            )
        return web.json_response({"object": "list", "data": data})

    def _validate_body(body: dict) -> None:
        """Reject malformed request knobs BEFORE any engine work happens
        (applies to streaming and non-streaming alike)."""
        stop = body.get("stop")
        if stop is not None and not (
            isinstance(stop, str)
            or (isinstance(stop, list) and all(isinstance(s, str) for s in stop))
        ):
            raise web.HTTPBadRequest(
                text="'stop' must be a string or list of strings"
            )
        if "max_tokens" in body:
            try:
                v = int(body["max_tokens"])
            except (TypeError, ValueError):
                raise web.HTTPBadRequest(text="'max_tokens' must be an integer")
            if v < 1:
                raise web.HTTPBadRequest(text="'max_tokens' must be >= 1")
        for key in ("temperature", "top_p"):
            if key in body:
                try:
                    v = float(body[key])
                except (TypeError, ValueError):
                    raise web.HTTPBadRequest(text=f"'{key}' must be a number")
                if not math.isfinite(v):
                    # json.loads accepts NaN/Infinity literals, and NaN
                    # passes any < comparison — reject explicitly.
                    raise web.HTTPBadRequest(text=f"'{key}' must be finite")
                if key == "temperature" and v < 0:
                    raise web.HTTPBadRequest(text="'temperature' must be >= 0")
                if key == "top_p" and not (0 < v <= 1):
                    raise web.HTTPBadRequest(
                        text="'top_p' must be in (0, 1]"
                    )

    def _check_admission(request: web.Request) -> None:
        """Per-request admission before any engine work: a draining
        server stops taking NEW requests (503 so the caller retries on
        a live replica), and an already-expired deadline is shed as
        504 — decoding for a client that gave up wastes a slot."""
        if state.engine.ec.role == "decode":
            # Disaggregated decode tier (serve/disagg.py): requests
            # arrive as KV migrations over the transfer port, never as
            # client completions. A role-aware gateway never routes
            # here; a misdirected client gets an honest shed.
            raise web.HTTPServiceUnavailable(
                text=json.dumps({"error": {
                    "message": "decode-role replica: completions are "
                               "admitted by the prefill tier",
                    "type": "wrong_role",
                }}),
                content_type="application/json",
                headers={"Retry-After": "1"},
            )
        if state.draining:
            raise web.HTTPServiceUnavailable(
                text=json.dumps({"error": {
                    "message": "server is draining", "type": "draining",
                }}),
                content_type="application/json",
                headers={"Retry-After": "1"},
            )
        remaining = deadline_remaining(parse_deadline(request.headers))
        if remaining is not None and remaining <= 0:
            raise web.HTTPGatewayTimeout(
                text=json.dumps({"error": {
                    "message": "request deadline already expired",
                    "type": "deadline",
                }}),
                content_type="application/json",
            )

    def _resolve_adapter(body: dict) -> Optional[str]:
        """The OpenAI `model` field -> an engine adapter id. The base
        model's own name (or an absent/empty field) means no adapter;
        anything else must be a servable adapter or the request is a
        404 before any engine work."""
        name = body.get("model")
        if not name or name == state.model_name:
            return None
        eng = state.engine
        if eng.adapters is not None and eng.adapters.known(str(name)):
            return str(name)
        raise web.HTTPNotFound(
            text=json.dumps({"error": {
                "message": f"model {name!r} not found",
                "type": "invalid_request_error",
                "code": "model_not_found",
            }}),
            content_type="application/json",
        )

    def _submit(prompt: str, body: dict, endpoint: str,
                templated: bool = False) -> Request:
        tok = state.tokenizer
        req = Request(
            prompt_tokens=state.encode_prompt(prompt, templated),
            max_tokens=int(body.get("max_tokens", 16)),
            temperature=float(body.get("temperature", 1.0)),
            top_p=float(body.get("top_p", 1.0)),
            eos_token_id=tok.eos_id,
            adapter=_resolve_adapter(body),
            id=uuid.uuid4().hex,
        )
        state.track_request(req, endpoint)
        try:
            return state.engine.submit(req)
        except UnknownAdapter as e:
            # The artifact vanished between the known() check and
            # submit — same client-visible contract as _resolve_adapter.
            state.untrack_request(req)
            raise web.HTTPNotFound(
                text=json.dumps({"error": {
                    "message": str(e), "type": "invalid_request_error",
                    "code": "model_not_found",
                }}),
                content_type="application/json",
            )
        except EngineOverloaded as e:
            state.untrack_request(req)
            # Bounded queue -> explicit shed: 429 + Retry-After beats
            # admitting into a queue whose wait exceeds any deadline.
            raise web.HTTPTooManyRequests(
                text=json.dumps({"error": {
                    "message": str(e), "type": "overloaded",
                }}),
                content_type="application/json",
                headers={
                    "Retry-After": str(max(1, int(e.retry_after + 0.999)))
                },
            )

    async def _generate(request: web.Request, prompt: str, body: dict,
                        templated: bool = False):
        req = _submit(prompt, body, request.path, templated)
        try:
            stop = body.get("stop")
            if isinstance(stop, str):
                stop = [stop]
            gen_ids = await _collect(req, state.tokenizer, stop)
        finally:
            state.untrack_request(req)
        if state.engine.error is not None:
            raise web.HTTPInternalServerError(text=str(state.engine.error))
        text = state.tokenizer.decode(gen_ids)
        # OpenAI `stop`: truncate at the earliest stop sequence (exclusive),
        # computed over the full text so the result is order-independent.
        # _collect already cancelled the engine slot when the match appeared
        # (non-streaming only; streamed responses don't hold tokens back).
        if stop is not None:
            cut = _find_stop(text, stop)
            if cut is not None:
                return text[:cut], len(req.prompt_tokens), len(gen_ids), "stop"
        # The engine recorded why generation ended (eos vs budget/window).
        return text, len(req.prompt_tokens), len(gen_ids), req.finish_reason

    async def _stream(
        request: web.Request, prompt: str, body: dict, chat: bool,
        templated: bool = False,
    ) -> web.StreamResponse:
        """OpenAI-style SSE streaming: one data: chunk per decoded token,
        then [DONE]. The engine already streams per-token through the
        request queue; this just relays it."""
        req = _submit(prompt, body, request.path, templated)
        if state.engine.error is not None:
            state.untrack_request(req)
            raise web.HTTPInternalServerError(text=str(state.engine.error))
        stop = body.get("stop")
        if isinstance(stop, str):
            stop = [stop]
        headers = {
            "Content-Type": "text/event-stream",
            "Cache-Control": "no-cache",
            # Load report at stream START: by the time it ends the
            # snapshot would be stale anyway; the gateway treats it as
            # one more passive sample.
            LOAD_HEADER: LoadReport.from_snapshot(
                state.engine.load_snapshot()
            ).to_header(),
        }
        # SSE headers go out at prepare(), before the middleware sees the
        # response — stamp the trace id here (same id the middleware span
        # carries: we're inside it).
        ctx = tracer.current_context()
        if ctx is not None:
            headers["x-trace-id"] = ctx.trace_id
        resp = web.StreamResponse(headers=headers)
        await resp.prepare(request)
        loop = asyncio.get_running_loop()
        created = int(time.time())
        cid = f"cmpl-{uuid.uuid4().hex[:24]}"
        resp_model = str(body.get("model") or state.model_name)

        async def write_piece(piece: str, finish=None):
            if chat:
                delta = {"content": piece} if piece else {}
                choice = {"index": 0, "delta": delta, "finish_reason": finish}
                obj = "chat.completion.chunk"
            else:
                choice = {"index": 0, "text": piece, "finish_reason": finish}
                obj = "text_completion"
            chunk = {
                "id": cid,
                "object": obj,
                "created": created,
                "model": resp_model,
                "choices": [choice],
            }
            await resp.write(f"data: {json.dumps(chunk)}\n\n".encode())

        # Stop handling mirrors OpenAI semantics on the streamed path too:
        # never emit the stop sequence or anything after it. Matching runs
        # on the FULL decode of all generated tokens — concatenating
        # per-token decodes diverges from it under BPE boundary effects
        # (leading-space stripping), which would make streamed truncation
        # disagree with the non-streaming path. The full re-decode per
        # token is O(n^2) in characters, accepted on this host-side path.
        # A match can span chunk boundaries, so when stop sequences exist
        # the stream holds back the last max(len(stop))-1 chars until more
        # text (or the end) proves they're not a prefix of a match.
        max_stop = max((len(s) for s in stop), default=0) if stop else 0
        holdback = max(0, max_stop - 1)
        tokens: list[int] = []
        sent = 0  # chars already streamed
        finish_reason: Optional[str] = None
        async def pump():
            """Relay tokens until the request finishes (split out so
            untracking can't be skipped by any of the loop's exits)."""
            nonlocal sent, finish_reason
            while True:
                tok_id = await loop.run_in_executor(None, req.out.get)
                if tok_id is None:
                    full = state.tokenizer.decode(tokens)
                    if stop and (cut := _find_stop(full, stop)) is not None:
                        full, finish_reason = full[:cut], "stop"
                    else:
                        # The engine reports "error" on the request itself
                        # when its thread died mid-stream — the committed
                        # 200 stream then ends honestly instead of
                        # fabricating "stop".
                        finish_reason = req.finish_reason
                    if len(full) > sent:
                        await write_piece(full[sent:])
                    return
                tokens.append(tok_id)
                full = state.tokenizer.decode(tokens)
                if stop:
                    # A new match must end inside the unsent tail (plus the
                    # holdback window) — search only there.
                    base = max(0, sent - holdback)
                    cut = _find_stop(full[base:], stop)
                    if cut is not None:
                        cut += base
                        if cut > sent:
                            await write_piece(full[sent:cut])
                            sent = cut
                        req.cancelled = True
                        while (
                            await loop.run_in_executor(None, req.out.get)
                            is not None
                        ):
                            pass
                        finish_reason = "stop"
                        return
                # Hold back the stop window plus any trailing partial UTF-8
                # codepoint (<= 3 replacement chars; a longer run is
                # genuinely invalid output and streams as-is).
                emit_to = len(full) - holdback
                trail = 0
                while (
                    trail < 3
                    and emit_to - 1 - trail >= 0
                    and full[emit_to - 1 - trail] == "�"
                ):
                    trail += 1
                emit_to -= trail if trail < 3 else 0
                if emit_to > sent:
                    await write_piece(full[sent:emit_to])
                    sent = emit_to

        try:
            await pump()
        finally:
            state.untrack_request(req)
        await write_piece("", finish_reason)
        await resp.write(b"data: [DONE]\n\n")
        await resp.write_eof()
        return resp

    @routes.post("/v1/completions")
    async def completions(request: web.Request) -> web.Response:
        try:
            body = await request.json()
        except json.JSONDecodeError:
            raise web.HTTPBadRequest(text="invalid JSON body")
        prompt = body.get("prompt")
        if prompt is None:
            raise web.HTTPBadRequest(text="missing 'prompt'")
        _validate_body(body)
        _check_admission(request)
        if isinstance(prompt, list):
            prompt = prompt[0] if prompt else ""
        METRICS.inc("substratus_serve_requests_total")
        with tracer.span(
            "serve.completion", endpoint="/v1/completions",
            stream=bool(body.get("stream")),
        ) as span:
            if body.get("stream"):
                return await _stream(request, str(prompt), body, chat=False)
            text, n_prompt, n_gen, finish = await _generate(
                request, str(prompt), body
            )
            span.set_attribute("prompt_tokens", n_prompt)
            span.set_attribute("completion_tokens", n_gen)
            span.set_attribute("finish_reason", finish)
        return web.json_response(
            _completion_body(state, text, n_prompt, n_gen, finish,
                             model=body.get("model"))
        )

    @routes.post("/v1/chat/completions")
    async def chat(request: web.Request) -> web.Response:
        try:
            body = await request.json()
        except json.JSONDecodeError:
            raise web.HTTPBadRequest(text="invalid JSON body")
        _validate_body(body)
        _check_admission(request)
        messages = body.get("messages") or []
        prompt, templated = state.render_chat(messages)
        METRICS.inc("substratus_serve_requests_total")
        with tracer.span(
            "serve.completion", endpoint="/v1/chat/completions",
            stream=bool(body.get("stream")), messages=len(messages),
        ):
            if body.get("stream"):
                return await _stream(
                    request, prompt, body, chat=True, templated=templated
                )
            text, n_prompt, n_gen, finish = await _generate(
                request, prompt, body, templated
            )
        resp = _completion_body(state, text, n_prompt, n_gen, finish,
                                model=body.get("model"))
        resp["object"] = "chat.completion"
        resp["choices"] = [
            {
                "index": 0,
                "message": {"role": "assistant", "content": text},
                "finish_reason": finish,
            }
        ]
        return web.json_response(resp)

    app = web.Application(middlewares=[trace_middleware])
    app["state"] = state  # middleware reads it for the load header
    app.add_routes(routes)
    return app


async def drain(state: ServerState, grace_s: float = 30.0,
                poll_s: float = 0.1) -> bool:
    """Graceful-shutdown core, shared by serve_forever and tests:
    flip readiness off (new requests 503, `/loadz` fails so the
    gateway stops routing here), then wait for in-flight requests —
    including active SSE streams — to finish, up to `grace_s`.
    Returns True when everything drained inside the deadline."""
    state.draining = True
    loop = asyncio.get_running_loop()
    deadline = loop.time() + grace_s
    while state.inflight and loop.time() < deadline:
        await asyncio.sleep(poll_s)
    return not state.inflight


def serve_forever(
    state: ServerState, host: str = "0.0.0.0", port: int = 8080,
    drain_grace_s: Optional[float] = None,
) -> None:
    """Run the app until SIGTERM/SIGINT, then drain gracefully:
    readiness fails first, in-flight streams finish (up to the grace
    deadline, SUBSTRATUS_DRAIN_GRACE env or 30 s), THEN the listener
    closes and the engine stops — kubelet's SIGTERM no longer kills
    active SSE responses mid-stream (docs/serving.md "Drain")."""
    if drain_grace_s is None:
        drain_grace_s = float(os.environ.get("SUBSTRATUS_DRAIN_GRACE", 30))
    app = build_app(state)

    async def _run() -> None:
        runner = web.AppRunner(app, handle_signals=False)
        await runner.setup()
        site = web.TCPSite(runner, host, port)
        await site.start()
        stop = asyncio.Event()
        loop = asyncio.get_running_loop()
        import signal as _signal

        for sig in (_signal.SIGTERM, _signal.SIGINT):
            try:
                loop.add_signal_handler(sig, stop.set)
            except NotImplementedError:  # non-unix loops
                pass
        await stop.wait()
        clean = await drain(state, grace_s=drain_grace_s)
        logging.getLogger(__name__).info(
            "drained %s (%d requests still in flight)",
            "cleanly" if clean else "at deadline", len(state.inflight),
        )
        await runner.cleanup()

    asyncio.run(_run())
    # Engine last: its scheduler must outlive every stream it feeds.
    state.engine.stop()
