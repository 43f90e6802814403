"""Overlapped decode scheduler (serve/engine.py, ISSUE 10): one-step-
ahead dispatch with on-device token feedback.

The tier-1 gates here:

  * PARITY — greedy output must be token-exact, overlap-on vs the
    synchronous scheduler, across the dense and paged layouts, chunked
    prefill, multi-tenant adapters, and the batch-generation driver;
  * PIPELINE EDGES — cancellation and stream death landing between
    dispatch and drain never emit the in-flight (wasted) token; an
    EOS-lagged slot never leaks its post-stop token; paged capacity
    growth computed one step ahead from host_positions stays correct
    across page boundaries; preemption forces a flush;
  * RESOLUTION — overlap is on by default for single-host role=both
    engines (speculative ones included, ISSUE 14) and resolves OFF
    under lockstep sync and the prefill role (flush-per-step semantics
    preserved);
  * OVERLAP — a step's host work (its drain, with the emits) runs while
    the next step is in flight, counted and not timed; and idle-queue
    admission is event-driven (threading.Event), not a poll-tick coin
    flip.
"""
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from substratus_tpu.models import llama
from substratus_tpu.observability.metrics import METRICS
from substratus_tpu.serve.engine import Engine, EngineConfig, Request
from test_fleet_telemetry import _SlowSink


def tiny_cfg():
    return llama.CONFIGS["tiny"].replace(vocab_size=258, dtype=jnp.float32)


@pytest.fixture(scope="module")
def cfg():
    return tiny_cfg()


@pytest.fixture(scope="module")
def params(cfg):
    return llama.init_params(cfg, jax.random.key(0))


def ec(**kw):
    kw.setdefault("max_batch", 4)
    kw.setdefault("max_seq_len", 64)
    kw.setdefault("eos_token_id", 257)
    return EngineConfig(**kw)


def run_engine(cfg, params, econf, prompts, max_tokens=12, **eng_kw):
    """Start an engine, run the prompts concurrently, return outputs."""
    eng = Engine(cfg, params, econf, **eng_kw)
    eng.start()
    outs = [None] * len(prompts)

    def one(i, p):
        outs[i] = eng.generate(list(p), max_tokens=max_tokens,
                               temperature=0.0)

    threads = [
        threading.Thread(target=one, args=(i, p))
        for i, p in enumerate(prompts)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    eng.stop()
    return outs


def counter_value(name, label_frag=""):
    """Read a counter family's rendered value(s) from the shared
    registry (the same text /metrics serves)."""
    total = 0.0
    for line in METRICS.render().splitlines():
        if line.startswith(name) and label_frag in line:
            total += float(line.rsplit(" ", 1)[-1])
    return total


# --- resolution ----------------------------------------------------------


def test_overlap_resolution(cfg, params):
    """Default on for single-host role=both — INCLUDING speculative
    engines (the pipelined spec scheduler chains verify rounds
    on-device); off under lockstep sync, prefill role, and the explicit
    escape hatch."""
    assert Engine(cfg, params, ec()).overlap is True
    assert Engine(cfg, params, ec(overlap=False)).overlap is False
    assert Engine(cfg, params, ec(spec_k=2)).overlap is True
    assert Engine(cfg, params, ec(spec_k=2, overlap=False)).overlap is False

    class FakeSync:
        num_processes = 2
        leader = True

    assert Engine(cfg, params, ec(), sync=FakeSync()).overlap is False


# --- greedy parity gates (tier-1) ----------------------------------------


def _parity_prompts():
    rng = np.random.default_rng(42)
    return [
        rng.integers(10, 250, n).tolist() for n in (4, 9, 17, 6)
    ]


@pytest.mark.parametrize("layout", ["paged", "dense"])
def test_greedy_parity_layouts(cfg, params, layout):
    """Token-exact overlap-on vs overlap-off, both KV layouts, a full
    concurrent batch (slot release lags one step under overlap — the
    wasted token must never surface)."""
    prompts = _parity_prompts()
    kw = dict(kv_layout=layout, eos_token_id=-1)  # an eos no row can sample
    on = run_engine(cfg, params, ec(overlap=True, **kw), prompts)
    off = run_engine(cfg, params, ec(overlap=False, **kw), prompts)
    assert on == off, (on, off)
    assert all(len(o) == 12 for o in on)


def test_greedy_parity_chunked_prefill(cfg, params):
    """Prompts spanning several prefill chunks (the chunked path runs
    while a step may be in flight under overlap)."""
    rng = np.random.default_rng(7)
    prompts = [rng.integers(10, 250, 40).tolist() for _ in range(3)]
    kw = dict(max_prefill_len=16, max_seq_len=64, eos_token_id=-1)
    on = run_engine(cfg, params, ec(overlap=True, **kw), prompts,
                    max_tokens=8)
    off = run_engine(cfg, params, ec(overlap=False, **kw), prompts,
                     max_tokens=8)
    assert on == off and all(o for o in on)


def test_greedy_parity_adapters(cfg, params):
    """Mixed-tenant batch: per-row adapter gather + overlap must stay
    token-exact vs the synchronous scheduler."""
    from substratus_tpu.serve.adapters import AdapterStore
    from substratus_tpu.train.lora import init_lora

    def store():
        st = AdapterStore(cfg, capacity=2, rank=4, dtype=jnp.float32)
        for i, name in enumerate(("t-a", "t-b")):
            tree = init_lora(cfg, jax.random.key(5 + i), rank=4,
                             alpha=8.0, dtype=jnp.float32)
            for j, k in enumerate(sorted(tree)):
                tree[k]["b"] = np.asarray(
                    jax.random.normal(
                        jax.random.key(100 + 7 * i + j),
                        tree[k]["b"].shape, jnp.float32,
                    ) * 0.05
                )
            st.install(name, jax.tree.map(np.asarray, tree), scale=2.0)
        return st

    prompts = _parity_prompts()
    adapters = [None, "t-a", "t-b", "t-a"]

    def run(overlap):
        eng = Engine(cfg, params, ec(overlap=overlap), adapters=store())
        eng.start()
        outs = [None] * len(prompts)

        def one(i):
            outs[i] = eng.generate(
                list(prompts[i]), max_tokens=10, temperature=0.0,
                adapter=adapters[i],
            )

        ts = [threading.Thread(target=one, args=(i,))
              for i in range(len(prompts))]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        eng.stop()
        return outs

    assert run(True) == run(False)


def test_greedy_parity_batchgen(cfg, params, tmp_path):
    """The batch-generation driver (pull-source refill rides the drain)
    produces identical per-record tokens with overlap on vs off."""
    import json

    from substratus_tpu.load.manifest import write_manifest
    from substratus_tpu.serve.batchgen import BatchGenDriver

    rng = np.random.default_rng(3)
    records = [
        {"id": f"r{i}", "tokens": rng.integers(10, 250, 6).tolist(),
         "max_tokens": 5 + (i % 4)}
        for i in range(12)
    ]
    manifest = tmp_path / "prompts.jsonl"
    write_manifest(str(manifest), records)

    def run(overlap, sub):
        eng = Engine(cfg, params, ec(overlap=overlap))
        eng.start()
        driver = BatchGenDriver(
            [eng], str(manifest), str(tmp_path / sub), max_tokens=8
        )
        summary = driver.run()
        eng.stop()
        assert summary["written"] == len(records), summary
        got = {}
        out_dir = tmp_path / sub
        for shard in sorted(out_dir.glob("shard-*.jsonl")):
            for line in shard.read_text().splitlines():
                rec = json.loads(line)
                got[rec["index"]] = rec.get("tokens") or rec.get("text")
        return got

    assert run(True, "on") == run(False, "off")


# --- pipeline edge cases -------------------------------------------------


def manual_engine(cfg, params, **kw):
    """Engine whose scheduler loop is driven BY THE TEST (start() never
    called): deterministic dispatch/drain interleaving."""
    return Engine(cfg, params, ec(**kw))


def admit_one(eng, prompt, **req_kw):
    req = Request(list(prompt), temperature=0.0, **req_kw)
    eng.queue.put(req)
    assert eng._admit() == 1
    return req


def drain_sink(req):
    out = []
    while True:
        try:
            tok = req.out.get_nowait()
        except Exception:
            break
        out.append(tok)
    return out


def test_cancel_between_dispatch_and_drain(cfg, params):
    """A cancellation landing while the step is in flight releases the
    slot at the drain and the in-flight token never reaches the sink."""
    eng = manual_engine(cfg, params)
    req = admit_one(eng, [256, 10, 20], max_tokens=16)
    slot = eng.slot_req.index(req)
    pending = eng._dispatch()
    req.cancelled = True  # lands mid-flight
    eng._drain(pending)
    assert not eng.active[slot]
    toks = drain_sink(req)
    # first token (admission emit) then the terminal None — the
    # in-flight step's token was sampled but never emitted.
    assert len(toks) == 2 and toks[-1] is None
    assert req.finish_reason == "stop"


def test_dead_stream_kill_between_dispatch_and_drain(cfg, params):
    """A stream killed after dispatch (engine-error style: released +
    error marker) is masked at the drain by the request-identity check —
    no token lands after the None."""
    eng = manual_engine(cfg, params)
    req = admit_one(eng, [256, 30, 40], max_tokens=16)
    slot = eng.slot_req.index(req)
    pending = eng._dispatch()
    # Kill the stream the way the error path does: terminal marker +
    # slot release while the step is still in flight.
    req.finish_reason = "error"
    req.out.put(None)
    eng._release_slot(slot)
    eng._drain(pending)
    toks = drain_sink(req)
    assert toks[-1] is None and toks.count(None) == 1
    assert len(toks) == 2  # admission token + None, nothing after


def test_eos_lag_never_emits_post_stop_token(cfg, params):
    """A slot that hits a stop condition at step N still occupies step
    N+1 (release lags one step): the N+1 token is computed, wasted, and
    masked — the sink sees exactly the pre-stop tokens then None."""
    eng = manual_engine(cfg, params)
    # Learn what the model decodes greedily, then stop on the first token
    # that none before it equals (a request whose first token is its eos
    # stops at admission and never holds a slot).
    probe = admit_one(eng, [256, 50, 60], max_tokens=8)
    for _ in range(5):
        eng._drain(eng._dispatch())
    seen = [t for t in drain_sink(probe) if t is not None]
    assert len(seen) == 6
    probe.cancelled = True
    eng._drain(eng._dispatch())
    assert not eng.active.any()
    stop_at = next(i for i in range(1, 6) if seen[i] not in seen[:i])

    req = admit_one(eng, [256, 50, 60], max_tokens=8,
                    eos_token_id=seen[stop_at])
    slot = eng.slot_req.index(req)
    pending = eng._dispatch()       # computes seen[1]
    for _ in range(stop_at):        # one step always in flight behind it
        ahead = eng._dispatch()
        eng._drain(pending)
        pending = ahead
    # the last step drained computed the eos -> release (lagged), with
    # `pending` in flight past the stop
    assert not eng.active[slot]
    eng._drain(pending)             # wasted token: identity check masks
    toks = drain_sink(req)
    assert toks == seen[:stop_at] + [None]  # no post-stop token surfaced


def test_ensure_capacity_one_step_ahead(cfg, params):
    """Paged growth is computed from host_positions BEFORE the write it
    backs: across every dispatch the slot's pages must already cover the
    position the in-flight step writes (boundary-crossing included)."""
    eng = manual_engine(cfg, params, kv_layout="paged", page_size=4,
                        max_seq_len=48)
    req = admit_one(eng, [256, 10, 20, 30, 40, 50], max_tokens=24)
    slot = eng.slot_req.index(req)
    pendings = []
    for _ in range(10):
        p = eng._dispatch()
        assert p is not None
        # The position this dispatch writes is host_positions - 1 (the
        # increment happened inside); its page must exist NOW.
        written = int(eng.host_positions[slot]) - 1
        n_pages = len(eng.slot_pages.pages[slot])
        assert written // 4 < n_pages, (written, n_pages)
        assert np.count_nonzero(eng.block_table[slot]) == n_pages
        pendings.append(p)
        if len(pendings) > 1:
            eng._drain(pendings.pop(0))
    while pendings:
        eng._drain(pendings.pop(0))
    toks = [t for t in drain_sink(req) if t is not None]
    assert len(toks) == 11  # admission + 10 steps, nothing lost


def test_preemption_forces_flush_and_stays_token_exact(cfg, params):
    """Pool pressure mid-decode: the overlapped engine must flush before
    preempting (resume prompts need every drained token) and the final
    outputs stay token-exact vs the synchronous scheduler."""
    before = counter_value(
        "substratus_serve_pipeline_flushes_total", 'reason="preempt"'
    )
    kw = dict(kv_layout="paged", page_size=4, kv_pool_tokens=48,
              max_seq_len=48, prefix_cache=False)
    prompts = [[256] + [11 * (i + 1), 13 * (i + 1)] for i in range(3)]
    on = run_engine(cfg, params, ec(overlap=True, **kw), prompts,
                    max_tokens=16)
    stats_on = None  # run_engine stops the engine; re-run to inspect
    eng = Engine(cfg, params, ec(overlap=True, **kw))
    eng.start()
    outs = [None] * len(prompts)

    def one(i):
        outs[i] = eng.generate(list(prompts[i]), max_tokens=16,
                               temperature=0.0)

    ts = [threading.Thread(target=one, args=(i,)) for i in range(3)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    stats_on = dict(eng.stats)
    eng.stop()
    off = run_engine(cfg, params, ec(overlap=False, **kw), prompts,
                     max_tokens=16)
    assert on == off == outs, (on, off, outs)
    assert stats_on["preemptions"] >= 1, stats_on
    after = counter_value(
        "substratus_serve_pipeline_flushes_total", 'reason="preempt"'
    )
    assert after > before, (before, after)


def test_stop_flushes_inflight_step(cfg, params):
    """stop() with a step in flight drains it (reason='drain') so the
    sampled token reaches its consumer before the thread exits."""
    eng = manual_engine(cfg, params)
    req = admit_one(eng, [256, 70, 80], max_tokens=32)
    pending = eng._step_overlapped() or eng._pending
    assert eng._pending is not None
    before = counter_value(
        "substratus_serve_pipeline_flushes_total", 'reason="drain"'
    )
    eng._flush("drain")
    after = counter_value(
        "substratus_serve_pipeline_flushes_total", 'reason="drain"'
    )
    assert after == before + 1
    toks = [t for t in drain_sink(req) if t is not None]
    assert len(toks) == 2  # admission emit + the flushed in-flight token
    assert eng._pending is None and eng._dev_tokens is None


# --- idle wake-up (satellite) --------------------------------------------


def test_idle_admission_is_event_driven(cfg, params):
    """With the safety-net poll stretched to 5s, a submit against an
    idle engine must still board immediately: the wake event — not the
    poll tick — carries first-token admission latency."""
    eng = Engine(cfg, params, ec())
    eng._idle_wait_s = 5.0
    eng.start()
    try:
        eng.generate([256, 10], max_tokens=2)  # warm executables
        time.sleep(0.3)  # the loop is now parked in _wake.wait(5.0)
        t0 = time.perf_counter()
        req = eng.submit(Request([256, 20, 30], max_tokens=2,
                                 temperature=0.0))
        first = req.out.get(timeout=10)
        ttft = time.perf_counter() - t0
        assert first is not None
        assert ttft < 1.0, f"TTFT {ttft:.3f}s — poll tick, not the event"
    finally:
        eng.stop()
    assert eng._thread is not None and not eng._thread.is_alive()


# --- host work under an in-flight step -----------------------------------


EMIT_S = 0.005  # host time a slow sink burns in every emit


def _drive_slow_emit(cfg, params, overlap, max_tokens):
    eng = Engine(cfg, params, ec(max_batch=2, overlap=overlap))
    eng.start()
    try:
        eng.generate([256, 10], max_tokens=3, temperature=0.0)  # warm
        before = METRICS.histogram_series(
            "substratus_serve_host_overlap_seconds"
        ).get("", {"count": 0, "sum": 0.0})
        req = eng.submit(Request([256, 20, 30, 40], max_tokens=max_tokens,
                                 temperature=0.0,
                                 out=_SlowSink(sleep_s=EMIT_S)))
        toks = []
        while (t := req.out.get(timeout=120)) is not None:
            toks.append(t)
    finally:
        eng.stop()
    after = METRICS.histogram_series(
        "substratus_serve_host_overlap_seconds"
    ).get("", {"count": 0, "sum": 0.0})
    return toks, after["count"] - before["count"], after["sum"] - before["sum"]


def test_host_work_runs_under_an_inflight_step(cfg, params):
    """What the overlapped scheduler is for, counted: every steady step's
    drain (the slow emit included) is observed as host work hidden under
    the step already in flight, the synchronous engine hides none, and
    the tokens are the synchronous engine's."""
    n = 16
    sync_toks, sync_hidden, _ = _drive_slow_emit(cfg, params, False, n)
    toks, hidden, hidden_s = _drive_slow_emit(cfg, params, True, n)
    assert toks == sync_toks and len(toks) == n
    assert sync_hidden == 0
    # The first token comes from the prefill and the last step's drain
    # has nothing behind it: every step between them hides its drain.
    assert hidden >= n - 3, hidden
    assert hidden_s >= (n - 3) * EMIT_S, (hidden, hidden_s)


# --- load report ---------------------------------------------------------


def test_load_snapshot_carries_overlap_flag(cfg, params):
    assert Engine(cfg, params, ec()).load_snapshot()["overlap"] is True
    assert (
        Engine(cfg, params, ec(overlap=False))
        .load_snapshot()["overlap"] is False
    )
