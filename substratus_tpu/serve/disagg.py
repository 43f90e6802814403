"""Disaggregated prefill/decode serving: KV-page handoff between engines.

Prefill is compute-bound, decode is memory-bound — yet a monolithic
engine runs lockstep batches of both, so a burst of long prompts stalls
every in-flight decode for the duration of its chunked prefill
(ROADMAP item 3). This module splits the request lifecycle across two
engines (Podracer's worker-specialization insight, PAPERS.md):

  * a **prefill** engine (`EngineConfig.role="prefill"`) runs chunked
    prefill into its paged pool, samples the first token, exports the
    request's KV pages, and ships pages + first token + sampling state
    here;
  * a **decode** engine (`role="decode"`) imports the pages into its own
    pool (no recompute), and continues decoding; generated tokens stream
    BACK over the same connection, so the prefill-side `Request.out`
    queue behaves exactly like a local engine's — the HTTP server above
    it is unchanged.

Transport: plain TCP with the same length-prefixed framing discipline as
`serve/multihost.py`'s TcpSync (`struct_pack_u32` headers). Each frame is
`u32 header_len | header JSON | u32 payload_len | payload`; the payload
carries raw page bytes in the header-declared array order. One persistent
connection per (prefill, decode) pair, multiplexed by request id.

Negotiation: the connection opens with a `hello` exchange of PoolSpecs.
Structural dims (layers, page size, kv heads, head dim) must match; KV
dtype may differ — the RECEIVER converts on import (model-dtype pages
quantize into an int8 pool, int8 pages dequantize into a model-dtype
pool), so mixed fleets interoperate during a dtype migration.

Overlapped-scheduler interplay (docs/performance.md "Overlapped
scheduling"): the DECODE tier pipelines — migrations install while a
step is in flight (the scatter import chains behind it on the device
stream) and the first-token emit rides admission as before. The
PREFILL tier never decodes, so `Engine.overlap` resolves off there;
the page export in `_handoff_request` still runs behind an explicit
`_flush("handoff")` guard pinning the settled-batch invariant the
gather depends on.

Failure semantics (the contract the unit tests pin):

  * a truncated/garbled frame kills only that connection — partially
    read handoffs are discarded, nothing is submitted;
  * a dead decode worker never hangs the client: every request in
    flight on the lost connection is REQUEUED on the prefill engine
    with `prompt := prompt + tokens-already-streamed` (the preemption
    trick), so generation resumes token-exactly through another worker
    — or finishes with an error marker when no worker is left;
  * the transfer queue is bounded: a prefill engine outrunning its
    decode tier blocks briefly at ship() (backpressure), then fails the
    request loudly instead of queueing unboundedly.
"""
from __future__ import annotations

import json
import logging
import queue
import socket
import struct
import threading
import time
import uuid
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from substratus_tpu.observability.journey import RequestJourney
from substratus_tpu.observability.metrics import METRICS
from substratus_tpu.observability.propagation import (
    format_traceparent,
    parse_traceparent,
)
from substratus_tpu.observability.tracing import SpanContext

log = logging.getLogger("substratus.serve.disagg")

# Handoff observability (docs/observability.md "Serving plane").
METRICS.histogram(
    "substratus_serve_kv_transfer_seconds",
    "Wall time of one KV-page handoff send (serialize + socket write), "
    "prefill side of disaggregated serving (serve/disagg.py).",
)
METRICS.describe(
    "substratus_serve_kv_transfer_queue_depth",
    "Handoffs waiting in the prefill engine's bounded transfer queue.",
    type="gauge",
)
METRICS.describe(
    "substratus_serve_kv_transfers_total",
    "KV-page handoffs completed, by outcome (sent, requeued, failed).",
    type="counter",
)

DEFAULT_TRANSFER_PORT = 8500


class NegotiationError(ValueError):
    """The two pools cannot exchange pages (structural mismatch)."""


@dataclass(frozen=True)
class PoolSpec:
    """The shape contract of one engine's paged KV pool — everything the
    peer needs to validate (and convert) incoming pages."""

    n_layers: int
    page_size: int
    kv_heads: int
    head_dim: int
    dtype: str  # numpy dtype name of the pool's k/v arrays
    quantized: bool  # int8 pool with per-vector f32 scales

    @classmethod
    def from_engine(cls, engine) -> "PoolSpec":
        if not getattr(engine, "paged", False):
            raise ValueError("disaggregated serving requires the paged layout")
        k = engine.cache["k"]
        L, _, bs, rows, width = k.shape
        # the pages' logical shape: a pool may store several KV heads to a
        # row (ops/kvcache.py::init_paged_cache), the wire never does
        hd = int(engine.cfg.head_size)
        return cls(
            n_layers=int(L), page_size=int(bs), kv_heads=rows * width // hd,
            head_dim=hd, dtype=np.dtype(k.dtype).name,
            quantized="k_scale" in engine.cache,
        )

    @classmethod
    def from_engine_config(cls, cfg, ec) -> "PoolSpec":
        """The spec an Engine(cfg, ec=ec) paged pool will have, computed
        BEFORE the engine exists — the HandoffManager is constructed
        first and handed into the Engine constructor."""
        from substratus_tpu.models.registry import module_of
        from substratus_tpu.serve.paged_kv import page_tokens

        quantized = ec.kv_cache_dtype == "int8"
        return cls(
            n_layers=int(cfg.n_layers),
            page_size=page_tokens(module_of(cfg), ec.page_size),
            kv_heads=int(cfg.n_kv_heads), head_dim=int(cfg.head_size),
            dtype="int8" if quantized else np.dtype(cfg.dtype).name,
            quantized=quantized,
        )

    def to_dict(self) -> Dict[str, Any]:
        return {
            "n_layers": self.n_layers, "page_size": self.page_size,
            "kv_heads": self.kv_heads, "head_dim": self.head_dim,
            "dtype": self.dtype, "quantized": self.quantized,
        }

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "PoolSpec":
        return cls(
            n_layers=int(d["n_layers"]), page_size=int(d["page_size"]),
            kv_heads=int(d["kv_heads"]), head_dim=int(d["head_dim"]),
            dtype=str(d["dtype"]), quantized=bool(d["quantized"]),
        )

    def convert_mode(self, src: "PoolSpec") -> str:
        """How this (receiving) pool installs pages exported from `src`:
        'none' (same quantization; a plain cast covers bf16<->f32),
        'quantize' (model-dtype pages into an int8 pool), 'dequantize'
        (int8 pages into a model-dtype pool). Structural mismatches are
        a NegotiationError — pages from a different model shape or page
        size can never be reinterpreted."""
        for f in ("n_layers", "page_size", "kv_heads", "head_dim"):
            if getattr(self, f) != getattr(src, f):
                raise NegotiationError(
                    f"pool {f} mismatch: sender={getattr(src, f)} "
                    f"receiver={getattr(self, f)}"
                )
        if src.quantized == self.quantized:
            return "none"
        return "quantize" if self.quantized else "dequantize"


# --- framing --------------------------------------------------------------


def _pack_u32(n: int) -> bytes:
    return struct.pack("<I", n)


# A frame larger than this is a protocol violation (or an attack), not a
# big handoff: even a 70B-shaped page batch stays far under it.
MAX_FRAME = 1 << 31


def send_frame(sock, header: Dict[str, Any], payload: bytes = b"") -> None:
    hdr = json.dumps(header, separators=(",", ":")).encode()
    # One sendall of the whole frame: interleaving writers would corrupt
    # the stream, so callers hold the channel's send lock.
    sock.sendall(_pack_u32(len(hdr)) + hdr + _pack_u32(len(payload)) + payload)


def recv_exact(sock, n: int) -> bytes:
    chunks = []
    while n:
        chunk = sock.recv(min(n, 1 << 20))
        if not chunk:
            raise ConnectionError("peer closed the transfer stream")
        chunks.append(chunk)
        n -= len(chunk)
    return b"".join(chunks)


def recv_frame(sock) -> Tuple[Dict[str, Any], bytes]:
    """One frame off the wire; raises ConnectionError on EOF/truncation
    and ValueError on garbage (both kill the connection, never the
    process — a truncated handoff is discarded, not half-applied)."""
    hlen = struct.unpack("<I", recv_exact(sock, 4))[0]
    if not 0 < hlen < MAX_FRAME:
        raise ValueError(f"bad header length {hlen}")
    header = json.loads(recv_exact(sock, hlen).decode())
    plen = struct.unpack("<I", recv_exact(sock, 4))[0]
    if plen >= MAX_FRAME:
        raise ValueError(f"bad payload length {plen}")
    payload = recv_exact(sock, plen) if plen else b""
    return header, payload


def encode_pages(pages: Dict[str, np.ndarray]) -> Tuple[List[dict], bytes]:
    """{name: array} -> (array manifest for the header, payload bytes)."""
    manifest, parts = [], []
    for name in sorted(pages):
        a = np.ascontiguousarray(pages[name])
        manifest.append(
            {"n": name, "s": list(a.shape), "d": np.dtype(a.dtype).name}
        )
        parts.append(a.tobytes())
    return manifest, b"".join(parts)


def decode_pages(manifest: List[dict], payload: bytes) -> Dict[str, np.ndarray]:
    """Inverse of encode_pages; raises ValueError when the payload length
    disagrees with the manifest (a truncated or corrupted frame)."""
    out: Dict[str, np.ndarray] = {}
    off = 0
    for m in manifest:
        dt = np.dtype(str(m["d"]))
        shape = tuple(int(x) for x in m["s"])
        nbytes = dt.itemsize * int(np.prod(shape, dtype=np.int64))
        if off + nbytes > len(payload):
            raise ValueError("page payload shorter than its manifest")
        out[str(m["n"])] = np.frombuffer(
            payload, dt, count=nbytes // dt.itemsize, offset=off
        ).reshape(shape)
        off += nbytes
    if off != len(payload):
        raise ValueError("page payload longer than its manifest")
    return out


# --- prefill side ---------------------------------------------------------


@dataclass
class _Flight:
    """One handed-off request the prefill side is relaying."""

    req: Any  # serve.engine.Request
    peer: str
    emitted: List[int] = field(default_factory=list)
    cancel_sent: bool = False
    done: bool = False


class _Channel:
    """One negotiated connection to a decode worker: a send lock for
    frame atomicity and a reader thread for the token back-channel."""

    def __init__(self, peer: str, sock, remote_spec: PoolSpec):
        self.peer = peer
        self.sock = sock
        self.remote_spec = remote_spec
        self.send_lock = threading.Lock()
        self.dead = False

    def send(self, header: Dict[str, Any], payload: bytes = b"") -> None:
        with self.send_lock:
            send_frame(self.sock, header, payload)

    def close(self) -> None:
        self.dead = True
        # shutdown() before close(): a bare close() on a socket another
        # thread is blocked recv()ing neither wakes that thread nor
        # sends FIN on Linux — the peer would never observe the loss.
        try:
            self.sock.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        try:
            self.sock.close()
        except OSError:
            pass


class HandoffManager:
    """Prefill-side coordinator: owns the connections to the decode
    tier, the bounded transfer queue, and the token relay back into each
    request's `out` queue. The engine's scheduler thread calls ship();
    a sender thread serializes and writes; per-channel reader threads
    deliver tokens — `_lock` guards every structure they share."""

    def __init__(
        self,
        peers: List[str],
        spec: PoolSpec,
        max_queue: int = 8,
        connect_timeout: float = 10.0,
        ship_timeout: float = 30.0,
        io_timeout: float = 600.0,
    ):
        if not peers:
            raise ValueError("disaggregated prefill needs >=1 decode peer")
        self.peers = [p.strip() for p in peers if p.strip()]
        self.spec = spec
        self.connect_timeout = connect_timeout
        self.ship_timeout = ship_timeout
        self.io_timeout = io_timeout
        self._queue: "queue.Queue" = queue.Queue(maxsize=max_queue)
        self._lock = threading.Lock()
        self._channels: Dict[str, _Channel] = {}
        self._flights: Dict[str, _Flight] = {}
        self._rr = 0  # round-robin cursor over peers
        # Resolved peer cache: a headless Service DNS name expands to
        # one address per decode pod, re-resolved at most every few
        # seconds so scale-up/down flows in without a restart.
        self._peer_cache: Tuple[float, List[str]] = (0.0, [])
        self._stop = threading.Event()
        self.engine = None  # bound by bind_engine(); requeue target
        self._sender = threading.Thread(target=self._send_loop, daemon=True)
        self._sender.start()

    # -- engine-facing surface --------------------------------------------

    def bind_engine(self, engine) -> None:
        """The engine requeued requests re-enter (Engine.resubmit)."""
        self.engine = engine

    def depth(self) -> int:
        return self._queue.qsize()

    def ship(self, req, pages: Dict[str, np.ndarray], true_len: int,
             first_token: int) -> None:
        """Enqueue one handoff (scheduler thread). Blocks up to
        ship_timeout when the transfer queue is full — backpressure
        toward admission — then fails the request instead of queueing
        unboundedly."""
        if not req.id:
            # The flight registry and the wire protocol key on the
            # request id; engine-level callers (bench, tests) often
            # leave it empty — mint one rather than collide.
            import uuid

            req.id = uuid.uuid4().hex
        item = (req, pages, true_len, first_token)
        try:
            self._queue.put(item, timeout=self.ship_timeout)
        except queue.Full:
            log.warning(
                "transfer queue full for %.0fs; failing request %s",
                self.ship_timeout, req.id,
            )
            METRICS.inc(
                "substratus_serve_kv_transfers_total", {"outcome": "failed"}
            )
            self._fail(req)
            return
        METRICS.set(
            "substratus_serve_kv_transfer_queue_depth", self._queue.qsize()
        )

    def close(self) -> None:
        self._stop.set()
        with self._lock:
            chans = list(self._channels.values())
            self._channels.clear()
        for ch in chans:
            ch.close()

    # -- sending -----------------------------------------------------------

    def _send_loop(self) -> None:
        while not self._stop.is_set():
            try:
                item = self._queue.get(timeout=0.2)
            except queue.Empty:
                continue
            METRICS.set(
                "substratus_serve_kv_transfer_queue_depth",
                self._queue.qsize(),
            )
            req, pages, true_len, first_token = item
            t0 = time.perf_counter()
            if self._send_one(req, pages, true_len, first_token):
                METRICS.observe(
                    "substratus_serve_kv_transfer_seconds",
                    time.perf_counter() - t0,
                )
                METRICS.inc(
                    "substratus_serve_kv_transfers_total",
                    {"outcome": "sent"},
                )

    def _send_one(self, req, pages, true_len, first_token) -> bool:
        """Try every peer once; on total failure the request fails
        loudly (the no-worker-left case must not hang the client)."""
        manifest, payload = encode_pages(pages)
        # W3C trace context rides the handoff so the decode tier parents
        # its spans AND its journey segment under the same trace id —
        # without it every decode-side span is an orphan root. "tpar",
        # not "tp": this header already carries top_p under "tp".
        tpar = None
        if req.trace_ctx is not None:
            tpar = format_traceparent(req.trace_ctx)
        elif getattr(req, "journey", None) is not None:
            tpar = format_traceparent(
                SpanContext(req.journey.trace_id, uuid.uuid4().hex[:16])
            )
        header = {
            "t": "kv",
            "rid": req.id,
            "p": list(req.prompt_tokens),
            "tl": true_len,
            "first": first_token,
            "m": req.max_tokens,
            "temp": req.temperature,
            "tp": req.top_p,
            "eos": req.eos_token_id,
            "ad": req.adapter,
            "tpar": tpar,
            "arrays": manifest,
        }
        peers = self._resolved_peers()
        n = len(peers)
        for i in range(n):
            peer = peers[(self._rr + i) % n]
            ch = self._channel(peer)
            if ch is None:
                continue
            with self._lock:
                self._flights[req.id] = _Flight(req=req, peer=peer)
            try:
                ch.send(header, payload)
            except (OSError, ValueError) as e:
                log.warning("handoff send to %s failed: %r", peer, e)
                with self._lock:
                    self._flights.pop(req.id, None)
                self._drop_channel(peer, requeue=True)
                continue
            self._rr = (self._rr + i + 1) % n
            return True
        log.error("no decode worker reachable; failing request %s", req.id)
        METRICS.inc(
            "substratus_serve_kv_transfers_total", {"outcome": "failed"}
        )
        self._fail(req)
        return False

    def _resolved_peers(self) -> List[str]:
        """The configured peers with DNS names expanded to every
        address (a headless k8s Service answers one A record per decode
        pod). Sender-thread only; cached for a few seconds."""
        ts, cached = self._peer_cache
        now = time.monotonic()
        if cached and now - ts < 5.0:
            return cached
        out: List[str] = []
        for p in self.peers:
            host, _, port = p.rpartition(":")
            try:
                infos = socket.getaddrinfo(
                    host or "127.0.0.1", int(port),
                    type=socket.SOCK_STREAM,
                )
            except OSError:
                continue
            addrs = sorted({i[4][0] for i in infos})
            out.extend(f"{a}:{port}" for a in addrs)
        out = out or list(self.peers)
        self._peer_cache = (now, out)
        return out

    def _channel(self, peer: str) -> Optional[_Channel]:
        with self._lock:
            ch = self._channels.get(peer)
        if ch is not None and not ch.dead:
            return ch
        host, _, port = peer.rpartition(":")
        try:
            sock = socket.create_connection(
                (host or "127.0.0.1", int(port)),
                timeout=self.connect_timeout,
            )
            sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            sock.settimeout(self.io_timeout)
            send_frame(sock, {"t": "hello", "spec": self.spec.to_dict()})
            reply, _ = recv_frame(sock)
            if reply.get("t") == "reject":
                raise NegotiationError(str(reply.get("reason")))
            if reply.get("t") != "hello":
                raise ValueError(f"unexpected reply {reply.get('t')!r}")
            remote = PoolSpec.from_dict(reply["spec"])
            # Both sides validate: a structural mismatch must fail the
            # CONNECTION (loud, at negotiation), never a request.
            remote.convert_mode(self.spec)
        except (OSError, ValueError, KeyError) as e:
            log.warning("decode peer %s unavailable: %r", peer, e)
            return None
        ch = _Channel(peer, sock, remote)
        with self._lock:
            old = self._channels.get(peer)
            self._channels[peer] = ch
        if old is not None:
            old.close()
        threading.Thread(
            target=self._read_loop, args=(ch,), daemon=True
        ).start()
        return ch

    # -- the token back-channel -------------------------------------------

    def _read_loop(self, ch: _Channel) -> None:
        try:
            while not ch.dead:
                header, _ = recv_frame(ch.sock)
                kind = header.get("t")
                if kind == "tok":
                    self._on_token(ch, str(header["rid"]), int(header["k"]))
                elif kind == "done":
                    self._on_done(
                        str(header["rid"]), str(header.get("fr", "stop")),
                        header.get("j"),
                    )
        except (OSError, ValueError) as e:
            if not ch.dead and not self._stop.is_set():
                log.warning("decode peer %s lost: %r", ch.peer, e)
        self._drop_channel(ch.peer, requeue=True)

    def _on_token(self, ch: _Channel, rid: str, tok: int) -> None:
        with self._lock:
            flight = self._flights.get(rid)
        if flight is None:
            return
        req = flight.req
        now = time.perf_counter()
        if req.last_emit_ts:
            METRICS.observe(
                "substratus_serve_inter_token_seconds", now - req.last_emit_ts
            )
        elif req.submit_ts:
            METRICS.observe(
                "substratus_serve_ttft_seconds", now - req.submit_ts
            )
        req.last_emit_ts = now
        flight.emitted.append(tok)
        req.out.put(tok)
        if req.cancelled and not flight.cancel_sent:
            flight.cancel_sent = True
            try:
                ch.send({"t": "cancel", "rid": rid})
            except OSError:
                pass  # the reader will notice the dead channel

    def _on_done(self, rid: str, finish_reason: str,
                 segment: Optional[dict] = None) -> None:
        with self._lock:
            flight = self._flights.pop(rid, None)
        if flight is None:
            return
        flight.done = True
        req = flight.req
        req.finish_reason = finish_reason
        # Stitch the decode tier's journey segment (the done frame's "j"
        # field) into the prefill-side journey BEFORE the terminal marker:
        # the merged journey — one trace id spanning both processes — is
        # what journey_log/slowz snapshot.
        j = getattr(req, "journey", None)
        if j is not None and segment:
            j.stitch(segment)
        eng = self.engine
        if eng is not None:
            eng._journey_end(req, finish_reason)
        elif j is not None and not j.ended:
            j.record("end", reason=finish_reason)
        req.out.put(None)

    # -- failure handling --------------------------------------------------

    def _drop_channel(self, peer: str, requeue: bool) -> None:
        with self._lock:
            ch = self._channels.pop(peer, None)
            orphans = [
                f for f in self._flights.values()
                if f.peer == peer and not f.done
            ]
            for f in orphans:
                self._flights.pop(f.req.id, None)
        if ch is not None:
            ch.close()
        if not requeue:
            return
        for f in orphans:
            self._requeue(f)

    def _requeue(self, flight: _Flight) -> None:
        """A request whose decode worker died resumes via re-prefill:
        prompt grows by the tokens already streamed (the engine's
        preemption trick), so the client's stream continues seamlessly
        through whichever worker takes the retry."""
        req = flight.req
        req.prompt_tokens = list(req.prompt_tokens) + flight.emitted
        req.max_tokens -= len(flight.emitted)
        if req.max_tokens <= 0 or req.cancelled:
            req.finish_reason = "length" if not req.cancelled else "stop"
            eng = self.engine
            j = getattr(req, "journey", None)
            if eng is not None:
                eng._journey_end(req, req.finish_reason, cause="requeue")
            elif j is not None and not j.ended:
                j.record("end", reason=req.finish_reason, cause="requeue")
            req.out.put(None)
            return
        if self.engine is None:
            self._fail(req)
            return
        METRICS.inc(
            "substratus_serve_kv_transfers_total", {"outcome": "requeued"}
        )
        # The SAME Request object re-enters admission: trace_ctx and the
        # journey ride along, so the re-prefill is visibly the same trace
        # in tracez/journeys — never a fresh root (resubmit stamps the
        # "requeue" journey event).
        log.info(
            "requeueing request %s after decode-worker loss (trace_id=%s)",
            req.id,
            getattr(req, "journey", None) and req.journey.trace_id,
        )
        self.engine.resubmit(req)

    def _fail(self, req) -> None:
        """Terminal error marker. Carries the original trace id into the
        log line and the journey ring so a dead-decode-worker failure is
        attributable to the request's trace, not an anonymous root."""
        req.finish_reason = "error"
        j = getattr(req, "journey", None)
        log.error(
            "handoff failed for request %s (trace_id=%s)",
            req.id, j.trace_id if j is not None else None,
        )
        eng = self.engine
        if eng is not None:
            eng._journey_end(req, "error", cause="handoff")
        elif j is not None and not j.ended:
            j.record("end", reason="error", cause="handoff")
        req.out.put(None)


# --- decode side ----------------------------------------------------------


@dataclass
class Migration:
    """One migrated request, ready for the decode engine's admission:
    KV pages already on the host, no recompute needed."""

    req: Any  # serve.engine.Request (out = _RemoteSink)
    pages: Dict[str, np.ndarray]  # each [L, n_pages, bs, KH, hd]-shaped
    true_len: int
    first_token: int
    convert: str  # "none" | "quantize" | "dequantize"


class _RemoteSink:
    """Decode-side stand-in for Request.out: frames every token back to
    the prefill worker. Sends run on the decode engine's scheduler
    thread; a dead peer marks the request cancelled so its slot frees at
    the next emit instead of wedging the scheduler."""

    def __init__(self, channel: _Channel, rid: str):
        self.channel = channel
        self.rid = rid
        self.req = None  # set right after the Request is constructed

    def put(self, item) -> None:
        if self.channel.dead:
            if self.req is not None:
                self.req.cancelled = True
            return
        try:
            if item is None:
                fr = self.req.finish_reason if self.req is not None else "stop"
                # Ship the decode-side journey segment back with the
                # terminal frame — the prefill side stitches it into ONE
                # merged journey spanning both processes. The engine's
                # _journey_end ran before this put(None), so the segment
                # carries its own "end" event.
                j = (
                    getattr(self.req, "journey", None)
                    if self.req is not None else None
                )
                if j is not None:
                    self.channel.send(
                        {"t": "done", "rid": self.rid, "fr": fr,
                         "j": j.to_wire()}
                    )
                else:
                    self.channel.send({"t": "done", "rid": self.rid, "fr": fr})
            else:
                self.channel.send(
                    {"t": "tok", "rid": self.rid, "k": int(item)}
                )
        except OSError:
            self.channel.dead = True
            if self.req is not None:
                self.req.cancelled = True


class HandoffServer:
    """Decode-side listener: accepts prefill-worker connections,
    negotiates the pool layout, turns kv frames into engine migrations,
    and relays cancellation. One accept thread + one reader thread per
    connection, all daemons; per-connection request registries are
    confined to their reader thread (cancel frames arrive on the same
    connection that created the request)."""

    def __init__(self, engine, host: str = "0.0.0.0", port: int = 0):
        from substratus_tpu.serve.engine import Request  # cycle-free import

        self._Request = Request
        self.engine = engine
        self.spec = PoolSpec.from_engine(engine)
        self._srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._srv.bind((host, port))
        self._srv.listen(64)
        self.port = self._srv.getsockname()[1]
        self._stop = threading.Event()
        self._lock = threading.Lock()
        self._conns: List[Any] = []
        self._accept = threading.Thread(target=self._accept_loop, daemon=True)
        self._accept.start()

    def close(self) -> None:
        """Stop accepting AND sever live connections — prefill peers
        must observe EOF (and requeue their flights) the moment this
        worker leaves, exactly as a process death would read."""
        self._stop.set()
        # shutdown() before close() throughout: close() alone neither
        # wakes a thread blocked in accept()/recv() on the same socket
        # nor sends FIN while one is, so peers (and our own reader
        # threads) would never observe this worker leaving.
        try:
            self._srv.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        try:
            self._srv.close()
        except OSError:
            pass
        with self._lock:
            conns = list(self._conns)
            self._conns.clear()
        for c in conns:
            try:
                c.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            try:
                c.close()
            except OSError:
                pass

    def _accept_loop(self) -> None:
        while not self._stop.is_set():
            try:
                conn, addr = self._srv.accept()
            except OSError:
                return  # listener closed
            conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            with self._lock:
                self._conns.append(conn)
            threading.Thread(
                target=self._serve_conn, args=(conn, addr), daemon=True
            ).start()

    def _serve_conn(self, conn, addr) -> None:
        peer = f"{addr[0]}:{addr[1]}"
        reqs: Dict[str, Any] = {}  # rid -> Request (this connection only)
        ch: Optional[_Channel] = None
        try:
            hello, _ = recv_frame(conn)
            if hello.get("t") != "hello":
                raise ValueError(f"expected hello, got {hello.get('t')!r}")
            src = PoolSpec.from_dict(hello["spec"])
            try:
                convert = self.spec.convert_mode(src)
            except NegotiationError as e:
                send_frame(conn, {"t": "reject", "reason": str(e)})
                return
            ch = _Channel(peer, conn, src)
            ch.send({"t": "hello", "spec": self.spec.to_dict()})
            while True:
                header, payload = recv_frame(conn)
                kind = header.get("t")
                if kind == "kv":
                    self._on_kv(ch, header, payload, convert, reqs)
                elif kind == "cancel":
                    req = reqs.get(str(header.get("rid")))
                    if req is not None:
                        req.cancelled = True
        except (OSError, ValueError, KeyError) as e:
            # Truncated stream / protocol garbage: this connection dies,
            # partially read handoffs are discarded un-submitted.
            if not self._stop.is_set():
                log.warning("transfer connection %s closed: %r", peer, e)
        finally:
            if ch is not None:
                ch.dead = True
            # shutdown() before close(), same as everywhere else in this
            # module: the decode engine's scheduler thread may be inside
            # a _RemoteSink sendall() on this socket right now — a bare
            # close() neither unblocks it nor sends FIN to the peer.
            try:
                conn.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            try:
                conn.close()
            except OSError:
                pass
            with self._lock:
                if conn in self._conns:
                    self._conns.remove(conn)
            # Requests this connection fed have nowhere to stream:
            # cancel them so the engine frees their slots. The prefill
            # side requeues its flights when it notices the same loss.
            for req in reqs.values():
                req.cancelled = True

    def _on_kv(self, ch: _Channel, header: Dict[str, Any], payload: bytes,
               convert: str, reqs: Dict[str, Any]) -> None:
        pages = decode_pages(header["arrays"], payload)
        rid = str(header["rid"])
        sink = _RemoteSink(ch, rid)
        # Parent this tier's spans and journey under the prefill side's
        # trace context ("tpar" header): the decode half of the request
        # keeps the SAME trace id, so the prefill side can stitch the
        # returned segment into one merged journey.
        tctx = parse_traceparent(header.get("tpar") or "")
        journey = RequestJourney(
            trace_id=(tctx.trace_id if tctx is not None else None),
            rid=rid,
            origin="decode",
            cap=self.engine.ec.journey_events,
        )
        journey.record(
            "kv_recv",
            bytes=len(payload),
            prompt_tokens=len(header["p"]),
        )
        req = self._Request(
            prompt_tokens=[int(x) for x in header["p"]],
            max_tokens=int(header["m"]),
            temperature=float(header["temp"]),
            top_p=float(header["tp"]),
            eos_token_id=(
                None if header.get("eos") is None else int(header["eos"])
            ),
            adapter=header.get("ad"),
            id=rid,
            out=sink,
            trace_ctx=tctx,
            journey=journey,
        )
        sink.req = req
        reqs[rid] = req
        self.engine.submit_migration(
            Migration(
                req=req,
                pages=pages,
                true_len=int(header["tl"]),
                first_token=int(header["first"]),
                convert=convert,
            )
        )
