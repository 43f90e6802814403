"""Multi-host serving lockstep (leader/follower request broadcast).

A multi-host TPU slice runs one engine process per host, all of them
jointly executing every jitted computation over the global mesh (JAX's
multi-controller SPMD model). That only works if every process issues
IDENTICAL jit calls in IDENTICAL order — but only process 0 has the HTTP
server and therefore knows which requests exist. This module closes the
gap with a *replicated scheduler*:

  * process 0 (the leader) owns HTTP + the request queue. At the top of
    every scheduler iteration it serializes the iteration's events — new
    requests (full admission parameters), cancellation latches, shutdown
    — and broadcasts them to all processes;
  * every process (leader included) then applies those events to an
    identical local scheduler state and runs the exact same iteration
    code. All scheduler decisions (slot choice, paging, preemption,
    speculation accept/reject) are deterministic functions of the event
    stream plus device values that the engine pins to a fully-replicated
    layout (engine._replicated), so the processes cannot diverge;
  * followers attach a null token sink where the leader has the HTTP
    response queue: they compute everything and deliver nothing.

The broadcast rides `multihost_utils.broadcast_one_to_all` — an XLA
collective over ICI/DCN, the same fabric the decode collectives use, so
the control plane needs no extra network plumbing (the reference's
serving images were single-pod and never faced this problem; SURVEY.md
§2.2, reference internal/controller/server_controller.go).

Cost: ONE fixed-size collective per scheduler iteration for the common
case — the message rides a fixed buffer with its length in the first
four bytes — and a second, bucket-padded collective only when a burst of
long prompts overflows it. Fixed buffer sizes mean each shape compiles
once.

Lockstep gangs and the overlapped scheduler: `Engine.overlap` (one-
step-ahead dispatch, docs/performance.md "Overlapped scheduling")
resolves OFF whenever a sync is attached. The event broadcast encodes
decisions every process applies to a settled batch, the leader must
host-read step N's tokens before its consumers can cancel into step
N+1's event frame, and the engine feeds pure host-numpy inputs so all
processes replicate them identically — a pipelined step would tear all
three. Gangs therefore run flush-per-step (`Engine._flush("gang")` at
the top of `_sync_iterate`), preserving the exact pre-overlap
semantics; the ~20 ms idle tick also stays (a follower's wake event
cannot fire for leader-side submissions).
"""
from __future__ import annotations

import json
import socket
import time
from collections import deque
from typing import Any, Dict, List, Optional

import numpy as np

from substratus_tpu.observability.metrics import METRICS


class NullSink:
    """Follower-side stand-in for Request.out: accepts and drops tokens.
    Followers mirror the full scheduler, so _emit runs on them too — the
    tokens just have nowhere to go (the leader answers the HTTP call)."""

    def put(self, item) -> None:  # queue.Queue interface subset
        pass


def _bucket_bytes(n: int, lo: int = 256) -> int:
    b = lo
    while b < n:
        b *= 2
    return b


def struct_pack_u32(n: int) -> bytes:
    import struct

    return struct.pack("<I", n)


class _TimedSync:
    """Shared broadcast timing for every sync transport: wall time lands
    in the shared registry (`substratus_serve_phase_seconds{phase=
    "broadcast"}`) and the last few thousand `(payload_bytes, seconds)`
    samples stay on `timings`, so a caller can read wall-time
    percentiles — including the bucket-padded overflow path a >=8k-token
    admission takes — without scraping /metrics mid-run."""

    timings: "deque[tuple]"

    def broadcast(self, payload: Optional[bytes]) -> bytes:
        if self.num_processes == 1:
            return payload or b""
        t0 = time.perf_counter()
        out = self._broadcast(payload)
        dt = time.perf_counter() - t0
        # Record the DELIVERED length (== payload on the leader), so
        # follower-side samples carry real message sizes too.
        self.timings.append((len(out), dt))
        METRICS.observe(
            "substratus_serve_phase_seconds", dt, {"phase": "broadcast"}
        )
        return out

    def _broadcast(self, payload: Optional[bytes]) -> bytes:
        raise NotImplementedError


class StepSync(_TimedSync):
    """Per-iteration event broadcast for lockstep multi-host serving."""

    def __init__(self) -> None:
        import jax

        self.process_index = jax.process_index()
        self.num_processes = jax.process_count()
        self.leader = self.process_index == 0
        self.timings = deque(maxlen=4096)

    # Inline buffer: 4-byte length prefix + payload. Sized so a typical
    # iteration (a few requests, cancels, or the idle heartbeat) is one
    # collective.
    INLINE = 1024

    def _broadcast(self, payload: Optional[bytes]) -> bytes:
        """Leader sends `payload`; every process returns it. The message
        rides one fixed-size collective (length embedded in the first 4
        bytes); only payloads overflowing the inline buffer pay a second,
        bucket-padded collective — every process derives the same
        collective count from the first buffer, so the gang stays in
        lockstep."""
        from jax.experimental import multihost_utils

        payload = payload or b""
        n = len(payload)
        cap = self.INLINE - 4
        buf = np.zeros((self.INLINE,), np.uint8)
        if self.leader:
            buf[:4] = np.frombuffer(struct_pack_u32(n), np.uint8)
            buf[4 : 4 + min(n, cap)] = np.frombuffer(
                payload[:cap], np.uint8
            )
        out = np.asarray(multihost_utils.broadcast_one_to_all(buf))
        # The header was packed little-endian (struct "<I"); read it back
        # with an EXPLICIT little-endian dtype — a native-order view on a
        # big-endian host would decode a garbage length and desync the gang.
        n = int(out[:4].view(np.dtype("<u4"))[0])
        if n <= cap:
            return bytes(out[4 : 4 + n].tobytes())
        size = _bucket_bytes(n)
        big = np.zeros((size,), np.uint8)
        if self.leader:
            big[:n] = np.frombuffer(payload, np.uint8)
        out2 = np.asarray(multihost_utils.broadcast_one_to_all(big))
        return bytes(out2[:n].tobytes())


class TcpSync(_TimedSync):
    """Lockstep event broadcast over plain TCP (leader fans each
    length-prefixed message out to every follower; followers block on
    recv). The scheduler only ever sees the `broadcast` interface, so
    this is a drop-in StepSync for environments whose backend has no
    multi-process collectives — notably CPU jaxlib, where it still gives
    a real 2-process lockstep gang: identical mirrored schedulers, a real
    inter-process hop per iteration, only the ICI transfer time missing.
    Production multi-host serving stays on StepSync (the XLA collective
    needs no extra network plumbing and rides the proven fabric)."""

    def __init__(self, process_index: int, num_processes: int, port: int,
                 host: str = "127.0.0.1", timeout: float = 120.0) -> None:
        self.process_index = process_index
        self.num_processes = num_processes
        self.leader = process_index == 0
        self.timings = deque(maxlen=4096)
        if self.num_processes == 1:
            self._conns: List[Any] = []
            return
        if self.leader:
            srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
            srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
            srv.bind((host, port))
            srv.listen(num_processes - 1)
            srv.settimeout(timeout)
            self._conns = [
                srv.accept()[0] for _ in range(num_processes - 1)
            ]
            srv.close()  # sublint: allow[lifecycle]: listener past its final accept; no thread blocks on it
        else:
            deadline = time.monotonic() + timeout
            while True:
                try:
                    conn = socket.create_connection((host, port), timeout=5)
                    break
                except OSError:
                    if time.monotonic() > deadline:
                        raise
                    time.sleep(0.1)
            conn.settimeout(timeout)
            self._conns = [conn]
        for c in self._conns:
            c.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)

    def _broadcast(self, payload: Optional[bytes]) -> bytes:
        payload = payload or b""
        if self.leader:
            msg = struct_pack_u32(len(payload)) + payload
            for c in self._conns:
                c.sendall(msg)
            return payload
        conn = self._conns[0]

        def recv_exact(n: int) -> bytes:
            chunks = []
            while n:
                chunk = conn.recv(n)
                if not chunk:
                    raise ConnectionError("leader closed the sync stream")
                chunks.append(chunk)
                n -= len(chunk)
            return b"".join(chunks)

        n = int(np.frombuffer(recv_exact(4), np.dtype("<u4"))[0])
        return recv_exact(n)

    def close(self) -> None:
        for c in self._conns:
            # shutdown() before close(), the serve/disagg.py discipline:
            # a follower blocked in _broadcast's recv on another thread
            # would neither wake nor see FIN from a bare close().
            try:
                c.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            try:
                c.close()
            except OSError:
                pass


def encode_events(
    reqs: List[Any], cancels: List[int], stop: bool,
    swap: Optional[int] = None,
) -> bytes:
    """Iteration events -> wire bytes. `reqs` carry every field admission
    reads, so a follower's mirror Request behaves identically. `swap` is
    the hot weight-swap barrier: the leader's target weights_version for
    THIS iteration (None = no swap) — every process installs its locally
    staged params when it sees one (Engine._sync_iterate)."""
    return json.dumps(
        {
            "stop": stop,
            "cancels": cancels,
            "swap": swap,
            "reqs": [
                {
                    "sid": r.sync_id,
                    "p": list(r.prompt_tokens),
                    "m": r.max_tokens,
                    "t": r.temperature,
                    "tp": r.top_p,
                    "e": r.eos_token_id,
                    "id": r.id,
                    "ad": r.adapter,
                }
                for r in reqs
            ],
        }
    ).encode()


def decode_events(payload: bytes) -> Dict[str, Any]:
    return json.loads(payload.decode())
