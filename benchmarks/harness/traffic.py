"""One general traffic generator, driven by a mix's data file.

A mix (`benchmarks/traffic/<mix>.json`) gives the loop kind, the rate or
the number of clients, the two length distributions and the engine sizes
it needs. Nothing here knows a mix by name.

What makes a run repeat (the fault that refused PR 22's benchmark):

* The same work in every seed. Lengths are the midpoint quantiles of the
  mix's distribution, rounded to a grid, dealt in blocks of `block`
  requests: every block holds the same pairs of prompt and output length
  (long and short prompts paired with outputs by two fixed strides, so the
  two lengths are paired independently). The seed chooses the order inside
  each block (a permutation of its own for every block), the arrival
  jitter and the token ids, and nothing else: the same set of sizes in
  every seed, in another order. Any whole block of any seed is the same
  work; a window differs from seed to seed only by the order and by the
  blocks its edges cut.
* Paced arrivals (open loop): request i is due at (i + v_i) / rate with
  v_i uniform in [0, 1): no two arrivals share a grid cell, no bursts.
* Exact output lengths: the system adapter builds every request greedy
  with an EOS that cannot match, so each decodes exactly its own length.
* Clocks on the scheduler thread: the request's output queue is a `Sink`
  whose put() stamps time.perf_counter(). No client thread per request;
  one generator thread submits pre-built requests and records how late it
  ran.
* Closed loop: the sink's terminal None hands the client's next pre-built
  request to the generator thread through a queue. A client's next request
  is due at the moment its previous reply ended.
"""
from __future__ import annotations

import contextlib
import queue
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

import numpy as np


# --- the plan: a pure function of (mix, seed, n) ---------------------------


@dataclass(frozen=True)
class Planned:
    index: int
    client: int  # -1 in an open loop
    due_s: Optional[float]  # open loop: seconds after the generator starts
    prompt_len: int
    output_len: int


def quantile_lengths(dist: Dict[str, Any], n: int) -> np.ndarray:
    """The n midpoint quantiles of a length distribution, on its grid."""
    q = (np.arange(n) + 0.5) / n
    lo, hi, grid = float(dist["lo"]), float(dist["hi"]), int(dist["grid"])
    kind = dist["dist"]
    if kind == "loguniform":
        x = lo * (hi / lo) ** q
    elif kind == "uniform":
        x = lo + (hi - lo) * q
    elif kind == "fixed":
        x = np.full(n, lo)
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    x = np.round(x / grid) * grid
    return np.clip(x, np.ceil(lo / grid) * grid, np.floor(hi / grid) * grid
                   ).astype(np.int64)


def planned_count(mix: Dict[str, Any], horizon_s: float) -> int:
    """Requests to pre-build for `horizon_s` seconds of generator time: a
    whole number of blocks."""
    block = int(mix["block"])
    if mix["loop"] == "open":
        n = int(np.ceil(float(mix["rate_rps"]) * horizon_s)) + 1
    else:
        n = int(np.ceil(float(mix["plan_rate_rps"]) * horizon_s)) \
            + int(mix["clients"])
    return -(-n // block) * block


def _stride(n: int, share: float) -> int:
    """The smallest stride >= share * n that visits all n places."""
    k = max(1, int(round(share * n)))
    while np.gcd(k, n) != 1:
        k += 1
    return k


def block_pairs(mix: Dict[str, Any]) -> List[tuple]:
    """The block's (prompt, output) pairs: the same in every seed."""
    n = int(mix["block"])
    prompts = np.sort(quantile_lengths(mix["prompt_len"], n))
    outputs = np.sort(quantile_lengths(mix["output_len"], n))
    sp, so = _stride(n, 0.382), _stride(n, 0.618)
    return [(int(prompts[(j * sp) % n]), int(outputs[(j * so + n // 2) % n]))
            for j in range(n)]


def plan_block(mix: Dict[str, Any], seed: int, b: int) -> List[Planned]:
    """Block b of the plan: a function of (mix, seed, b) alone, so a plan
    can grow by whole blocks while a run is under way (a faster system
    asks for more requests than were pre-built)."""
    block = int(mix["block"])
    pairs = block_pairs(mix)
    order = np.random.default_rng([int(seed), 1, int(b)]).permutation(block)
    jitter = np.random.default_rng([int(seed), 5, int(b)]).random(block)
    open_loop = mix["loop"] == "open"
    clients = 0 if open_loop else int(mix["clients"])
    out = []
    for j in range(block):
        i = b * block + j
        prompt_len, output_len = pairs[int(order[j])]
        out.append(Planned(
            index=i,
            client=-1 if open_loop else i % clients,
            due_s=(i + float(jitter[j])) / float(mix["rate_rps"])
            if open_loop else None,
            prompt_len=prompt_len, output_len=output_len,
        ))
    return out


def plan(mix: Dict[str, Any], seed: int, n: int) -> List[Planned]:
    block = int(mix["block"])
    if n % block:
        raise ValueError(f"{n} requests are not whole blocks of {block}")
    out: List[Planned] = []
    for b in range(n // block):
        out.extend(plan_block(mix, seed, b))
    return out


def prompt_tokens(seed: int, index: int, length: int, vocab: int,
                  stream: int = 2) -> List[int]:
    """Seeded random ids below the vocabulary size; no tokenizer."""
    rng = np.random.default_rng([int(seed), stream, int(index)])
    return rng.integers(0, vocab, size=length, dtype=np.int64).tolist()


def prefill_buckets(lengths, max_prefill_len: int, lo: int = 16) -> List[int]:
    """The prefill shapes these prompt lengths can produce: whole chunks of
    max_prefill_len, and the last chunk padded to a power of two."""
    out = set()
    for n in lengths:
        n = int(n)
        if n >= max_prefill_len:
            out.add(max_prefill_len)
        r = n % max_prefill_len
        if r:
            b = lo
            while b < r:
                b *= 2
            out.add(min(b, max_prefill_len))
    return sorted(out)


def length_histogram(planned: List[Planned]) -> Dict[str, Dict[str, int]]:
    def hist(values):
        h: Dict[str, int] = {}
        for v in values:
            h[str(v)] = h.get(str(v), 0) + 1
        return dict(sorted(h.items(), key=lambda kv: int(kv[0])))

    return {"prompt_len": hist(p.prompt_len for p in planned),
            "output_len": hist(p.output_len for p in planned)}


# --- the sink and the record of one request --------------------------------


class Sink:
    """Request.out stand-in: put() runs on the scheduler thread inside the
    engine's emit and stamps the token's time there."""

    __slots__ = ("ts", "ids", "done_ts", "on_done", "annotate")

    def __init__(self, on_done: Optional[Callable[["Sink"], None]] = None,
                 annotate=None):
        self.ts: List[float] = []
        self.ids: List[int] = []
        self.done_ts: Optional[float] = None
        self.on_done = on_done
        self.annotate = annotate

    def put(self, item, block=True, timeout=None):
        now = time.perf_counter()
        if item is None:
            if self.done_ts is None:
                self.done_ts = now
                if self.on_done is not None:
                    self.on_done(self)
            return
        if self.annotate is not None:
            with self.annotate("bench.sink_put"):
                self.ts.append(now)
                self.ids.append(int(item))
        else:
            self.ts.append(now)
            self.ids.append(int(item))

    def get(self, block=True, timeout=None):  # the queue contract; unused
        raise queue.Empty


@dataclass
class Record:
    planned: Planned
    prompt: List[int]
    sink: Sink
    request: Any = None  # the system's own request object
    due: Optional[float] = None  # absolute perf_counter time
    submit: Optional[float] = None
    refused: bool = False

    @property
    def first(self) -> Optional[float]:
        return self.sink.ts[0] if self.sink.ts else None

    @property
    def done(self) -> Optional[float]:
        return self.sink.done_ts


# --- the generator thread ----------------------------------------------------


@dataclass
class Generator:
    """Submits pre-built requests on one thread: on a schedule (open loop)
    or as each client's previous reply ends (closed loop)."""

    mix: Dict[str, Any]
    records: List[Record]
    submit_fn: Callable[[Any], Any]
    build_block: Callable[[int], List[Record]]  # block index -> its records
    annotate: Any = None
    t0: float = 0.0
    _stop: threading.Event = field(default_factory=threading.Event)
    _thread: Optional[threading.Thread] = None
    _done_q: "queue.SimpleQueue" = field(default_factory=queue.SimpleQueue)
    blocks_added: int = 0  # built inside the run, on the generator thread
    error: Optional[BaseException] = None

    def __post_init__(self):
        self._hook(self.records)

    def _hook(self, records: List[Record]) -> None:
        if self.mix["loop"] == "closed":
            for r in records:
                r.sink.on_done = self._done_q.put

    def _extend(self) -> List[Record]:
        more = self.build_block(len(self.records) // int(self.mix["block"]))
        self._hook(more)
        self.records.extend(more)
        self.blocks_added += 1
        return more

    def start(self) -> None:
        target = self._open if self.mix["loop"] == "open" else self._closed
        self._thread = threading.Thread(
            target=self._guard, args=(target,), name="bench-generator",
            daemon=True,
        )
        self.t0 = time.perf_counter()
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        self._done_q.put(None)
        if self._thread is not None:
            self._thread.join(timeout=30)

    def _guard(self, target) -> None:
        try:
            target()
        except BaseException as e:  # surfaced by the main thread
            self.error = e

    def _submit(self, r: Record, due: float) -> None:
        r.due = due
        ctx = self.annotate("bench.submit") if self.annotate \
            else contextlib.nullcontext()
        with ctx:
            t = time.perf_counter()
            try:
                self.submit_fn(r.request)
            except Exception:  # refused (overload) counts as failed
                r.refused = True
            r.submit = t

    def _open(self) -> None:
        i = 0
        while True:
            if i == len(self.records):
                self._extend()
            r = self.records[i]
            i += 1
            due = self.t0 + r.planned.due_s
            delay = due - time.perf_counter()
            if delay > 0 and self._stop.wait(delay):
                return
            if self._stop.is_set():
                return
            self._submit(r, due)

    def _closed(self) -> None:
        clients = int(self.mix["clients"])
        stagger = float(self.mix.get("stagger_s", 0.0))
        per_client: List[List[Record]] = [[] for _ in range(clients)]
        for r in self.records:
            per_client[r.planned.client].append(r)
        nxt = [0] * clients
        by_sink = {id(r.sink): r for r in self.records}
        starts = [(self.t0 + c * stagger, c) for c in range(clients)]
        while not self._stop.is_set():
            now = time.perf_counter()
            while starts and starts[0][0] <= now:
                due, c = starts.pop(0)
                self._submit(per_client[c][0], due)
                nxt[c] = 1
            timeout = max(0.0, starts[0][0] - time.perf_counter()) \
                if starts else None
            try:
                sink = self._done_q.get(timeout=timeout)
            except queue.Empty:
                continue
            if sink is None:
                return
            c = by_sink[id(sink)].planned.client
            while nxt[c] >= len(per_client[c]):
                for r in self._extend():
                    per_client[r.planned.client].append(r)
                    by_sink[id(r.sink)] = r
            r = per_client[c][nxt[c]]
            nxt[c] += 1
            self._submit(r, sink.done_ts)
