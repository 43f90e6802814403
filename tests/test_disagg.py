"""Disaggregated prefill/decode serving (serve/disagg.py, ISSUE 7).

Tier-1 gates:

  * PARITY — greedy decode through the KV handoff (prefill engine ->
    real TCP -> decode engine) is token-exact vs the monolithic engine,
    in both the model-dtype and int8 pool layouts (including a chunked
    long-prompt admission and prefix-cache reuse on the prefill side);
  * NEGOTIATION — mixed dtypes interoperate (model->int8 quantizes on
    import, int8->model dequantizes) while structural mismatches reject
    the connection loudly, failing the request, never hanging it;
  * FAILURE — a truncated transfer stream is discarded (nothing
    half-applied, the decode engine survives), and a dead decode worker
    REQUEUES in-flight requests: with another worker available the
    stream resumes token-exactly; with none, the client promptly gets
    an error marker;
  * SURFACE — load reports carry role + transfer-queue depth, the
    balancer keeps client admissions on the prefill pool, decode-role
    servers 503 completions, and per-adapter gateway quotas 429.
"""
import queue
import socket
import struct
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from substratus_tpu.models import llama
from substratus_tpu.serve.disagg import (
    HandoffManager,
    HandoffServer,
    NegotiationError,
    PoolSpec,
    recv_frame,
    send_frame,
)
from substratus_tpu.serve.engine import Engine, EngineConfig, Request


@pytest.fixture(scope="module")
def cfg():
    return llama.CONFIGS["tiny"].replace(vocab_size=258, dtype=jnp.float32)


@pytest.fixture(scope="module")
def base_params(cfg):
    return llama.init_params(cfg, jax.random.key(0))


def ec(**kw):
    kw.setdefault("max_batch", 4)
    kw.setdefault("max_seq_len", 64)
    kw.setdefault("eos_token_id", 257)
    kw.setdefault("kv_layout", "paged")
    return EngineConfig(**kw)


PROMPTS = [
    [256, 5, 6, 7],
    [256, 70, 71],
    list(range(1, 40)),  # > one 16-token page, multiple chunks
]


def reference(cfg, params, prompts, max_tokens=6, **ec_kw):
    eng = Engine(cfg, params, ec(**ec_kw))
    eng.start()
    try:
        return [
            eng.generate(p, max_tokens=max_tokens, temperature=0.0)
            for p in prompts
        ]
    finally:
        eng.stop()


class DisaggPair:
    """1 prefill + 1 decode engine joined over real TCP on loopback."""

    def __init__(self, cfg, params, pre_kw=None, dec_kw=None,
                 manager_kw=None, extra_peers=()):
        self.dec = Engine(cfg, params, ec(role="decode", **(dec_kw or {})))
        self.dec.start()
        self.srv = HandoffServer(self.dec, host="127.0.0.1")
        pre_ec = ec(role="prefill", **(pre_kw or {}))
        self.mgr = HandoffManager(
            list(extra_peers) + [f"127.0.0.1:{self.srv.port}"],
            PoolSpec.from_engine_config(cfg, pre_ec),
            **(manager_kw or {}),
        )
        self.pre = Engine(cfg, params, pre_ec, handoff=self.mgr)
        self.pre.start()

    def close(self):
        self.pre.stop()
        self.dec.stop()
        self.srv.close()
        self.mgr.close()


# --- parity (tier-1 gates) ------------------------------------------------


def test_handoff_greedy_token_exact(cfg, base_params):
    expected = reference(cfg, base_params, PROMPTS)
    pair = DisaggPair(cfg, base_params)
    try:
        got = [
            pair.pre.generate(p, max_tokens=6, temperature=0.0)
            for p in PROMPTS
        ]
        # Repeat the first prompt: its prefix pages are now registered
        # on the prefill engine, so this admission reuses pages and the
        # handoff must STILL be token-exact (shared pages export fine).
        again = pair.pre.generate(PROMPTS[0], max_tokens=6, temperature=0.0)
        assert pair.pre.stats["handoffs"] == 4
        assert pair.dec.stats["migrations_in"] == 4
    finally:
        pair.close()
    assert got == expected, (got, expected)
    assert again == expected[0], (again, expected[0])


def test_handoff_int8_token_exact(cfg, base_params):
    kw = {"kv_cache_dtype": "int8"}
    expected = reference(cfg, base_params, PROMPTS, **kw)
    pair = DisaggPair(cfg, base_params, pre_kw=kw, dec_kw=kw)
    try:
        got = [
            pair.pre.generate(p, max_tokens=6, temperature=0.0)
            for p in PROMPTS
        ]
    finally:
        pair.close()
    assert got == expected, (got, expected)


def test_mixed_dtype_negotiation_runs_both_directions(cfg, base_params):
    """model->int8 (quantize on import) and int8->model (dequantize):
    not bit-exact vs either monolith by construction, but the handoff
    must negotiate, decode to the full budget, and finish cleanly."""
    for pre_kw, dec_kw in (
        ({}, {"kv_cache_dtype": "int8"}),
        ({"kv_cache_dtype": "int8"}, {}),
    ):
        pair = DisaggPair(cfg, base_params, pre_kw=pre_kw, dec_kw=dec_kw)
        try:
            req = pair.pre.submit(
                Request(list(PROMPTS[0]), max_tokens=6, temperature=0.0)
            )
            out = []
            while True:
                tok = req.out.get(timeout=120)
                if tok is None:
                    break
                out.append(tok)
            assert len(out) == 6, (pre_kw, dec_kw, out)
            assert req.finish_reason == "length"
        finally:
            pair.close()


def test_structural_mismatch_fails_request_not_hangs(cfg, base_params):
    """A prefill tier whose page size disagrees with the decode tier
    must reject at NEGOTIATION and fail the request promptly — a config
    error reads as an error, never as a hung client."""
    dec = Engine(cfg, base_params, ec(role="decode"))
    dec.start()
    srv = HandoffServer(dec, host="127.0.0.1")
    pre_ec = ec(role="prefill", page_size=8)  # decode side uses 16
    mgr = HandoffManager(
        [f"127.0.0.1:{srv.port}"],
        PoolSpec.from_engine_config(cfg, pre_ec),
        ship_timeout=5.0,
    )
    pre = Engine(cfg, base_params, pre_ec, handoff=mgr)
    pre.start()
    try:
        req = pre.submit(Request([256, 1, 2], max_tokens=4, temperature=0.0))
        assert req.out.get(timeout=60) is None
        assert req.finish_reason == "error"
    finally:
        pre.stop()
        dec.stop()
        srv.close()
        mgr.close()


def test_pool_spec_convert_modes():
    base = dict(n_layers=2, page_size=16, kv_heads=2, head_dim=8)
    f32 = PoolSpec(dtype="float32", quantized=False, **base)
    i8 = PoolSpec(dtype="int8", quantized=True, **base)
    assert f32.convert_mode(f32) == "none"
    assert i8.convert_mode(i8) == "none"
    assert i8.convert_mode(f32) == "quantize"
    assert f32.convert_mode(i8) == "dequantize"
    other = PoolSpec(dtype="float32", quantized=False,
                     **{**base, "page_size": 8})
    with pytest.raises(NegotiationError):
        f32.convert_mode(other)


def test_pages_cross_the_wire_a_head_a_row_whatever_the_pool_stores():
    """A bfloat16 pool of 64-wide heads stores two to a row of 128
    (ops/kvcache.py::init_paged_cache); its spec and its exported pages are
    the logical [L, n, bs, KH, hd] all the same. Pages exported from it
    come back to the bit through `import_`, and through an int8 pool
    (quantized per hd vector on the way in, dequantized on the way back)
    within int8's rounding."""
    cfg = llama.CONFIGS["tiny"].replace(dim=256, vocab_size=258)
    assert (cfg.n_kv_heads, cfg.head_size, cfg.dtype) == (2, 64, jnp.bfloat16)
    make = lambda **kw: Engine(cfg, None, ec(page_size=4, **kw))  # noqa: E731
    packed, int8 = make(), make(kv_cache_dtype="int8")
    assert packed.cache["k"].shape[3:] == (1, 128)
    assert int8.cache["k"].shape[3:] == (2, 64)
    for eng in (packed, int8):
        assert PoolSpec.from_engine(eng) == PoolSpec.from_engine_config(
            cfg, eng.ec)
    keys = jax.random.split(jax.random.key(0), 2)
    packed.cache = {
        name: jax.random.normal(k, a.shape, jnp.float32).astype(a.dtype)
        for k, (name, a) in zip(keys, packed.cache.items())
    }
    ids = np.array([3, 9, 1, 0], np.int32)  # bucket-padded with the trash page
    sent = packed._export_fn(packed.cache, ids)
    assert sent["k"].shape == (cfg.n_layers, 4, 4, 2, 64)
    rows = np.asarray(sent["k"].astype(jnp.float32))
    assert (rows[:, :3].reshape(cfg.n_layers, 3, 4, 1, 128)
            == np.asarray(packed.cache["k"][:, ids[:3]], np.float32)).all()

    there = np.array([5, 2, 7, 0], np.int32)
    other = make()
    other.cache = other._import_fn("none", other.cache, there, dict(sent))
    back = other._export_fn(other.cache, there)
    np.testing.assert_array_equal(
        np.asarray(back["k"].astype(jnp.float32))[:, :3], rows[:, :3])

    int8.cache = int8._import_fn("quantize", int8.cache, there, dict(sent))
    quantized = int8._export_fn(int8.cache, there)
    assert quantized["k"].dtype == jnp.int8
    assert quantized["k_scale"].shape == (cfg.n_layers, 4, 4, 2, 1)
    other = make()
    other.cache = other._import_fn(
        "dequantize", other.cache, ids, dict(quantized))
    assert other.cache["k"].shape == packed.cache["k"].shape
    for name in ("k", "v"):
        np.testing.assert_allclose(
            np.asarray(other.cache[name][:, ids[:3]], np.float32),
            np.asarray(packed.cache[name][:, ids[:3]], np.float32),
            atol=0.05, rtol=0)


# --- failure paths --------------------------------------------------------


def test_truncated_stream_discarded(cfg, base_params):
    """A connection that dies mid-frame must be discarded whole: no
    partial migration reaches the engine, and the server keeps serving
    well-formed connections afterwards."""
    dec = Engine(cfg, base_params, ec(role="decode"))
    dec.start()
    srv = HandoffServer(dec, host="127.0.0.1")
    try:
        spec = PoolSpec.from_engine(dec)
        s = socket.create_connection(("127.0.0.1", srv.port), timeout=10)
        send_frame(s, {"t": "hello", "spec": spec.to_dict()})
        reply, _ = recv_frame(s)
        assert reply["t"] == "hello"
        # A kv frame whose declared payload never fully arrives.
        import json as _json

        hdr = _json.dumps({
            "t": "kv", "rid": "x", "p": [1, 2], "tl": 2, "first": 3,
            "m": 4, "temp": 0.0, "tp": 1.0, "eos": None, "ad": None,
            "arrays": [{"n": "k", "s": [2, 1, 16, 2, 8], "d": "float32"}],
        }).encode()
        s.sendall(struct.pack("<I", len(hdr)) + hdr)
        s.sendall(struct.pack("<I", 9999) + b"short")
        s.close()
        time.sleep(0.5)
        assert dec.stats["migrations_in"] == 0
        assert dec.error is None

        # And a garbled header on a fresh connection: same containment.
        s2 = socket.create_connection(("127.0.0.1", srv.port), timeout=10)
        s2.sendall(struct.pack("<I", 12) + b"not-json-at!")
        s2.close()
        time.sleep(0.3)
        assert dec.error is None
    finally:
        dec.stop()
        srv.close()


def test_dead_decode_worker_fails_over_token_exact(cfg, base_params):
    """Kill the decode worker mid-stream with a SECOND worker standing
    by: the manager requeues the flight (prompt += streamed tokens),
    re-prefill hands off to the survivor, and the client's total stream
    is token-exact vs the monolithic engine."""
    prompt = [256, 5, 6, 7]
    expected = reference(cfg, base_params, [prompt], max_tokens=12)[0]

    dec1 = Engine(cfg, base_params, ec(role="decode"))
    dec1.start()
    srv1 = HandoffServer(dec1, host="127.0.0.1")
    dec2 = Engine(cfg, base_params, ec(role="decode"))
    dec2.start()
    srv2 = HandoffServer(dec2, host="127.0.0.1")
    pre_ec = ec(role="prefill")
    mgr = HandoffManager(
        # Worker 1 first in round-robin: the first handoff lands there.
        [f"127.0.0.1:{srv1.port}", f"127.0.0.1:{srv2.port}"],
        PoolSpec.from_engine_config(cfg, pre_ec),
    )
    pre = Engine(cfg, base_params, pre_ec, handoff=mgr)
    pre.start()
    try:
        req = pre.submit(Request(list(prompt), max_tokens=12,
                                 temperature=0.0))
        out = []
        # Kill worker 1 after a few tokens streamed.
        while True:
            tok = req.out.get(timeout=120)
            if tok is None:
                break
            out.append(tok)
            if len(out) == 3:
                srv1.close()
                dec1.stop()
        assert out == expected, (out, expected)
        assert req.finish_reason == "length"
        assert dec2.stats["migrations_in"] >= 1, "survivor never used"
    finally:
        pre.stop()
        dec2.stop()
        srv2.close()
        mgr.close()
        dec1.stop()


def test_dead_last_decode_worker_errors_promptly(cfg, base_params):
    """No worker left: the requeued flight must terminate the client
    with an error marker (bounded time), never hang."""
    dec = Engine(cfg, base_params, ec(role="decode"))
    dec.start()
    srv = HandoffServer(dec, host="127.0.0.1")
    pre_ec = ec(role="prefill")
    mgr = HandoffManager(
        [f"127.0.0.1:{srv.port}"],
        PoolSpec.from_engine_config(cfg, pre_ec),
        connect_timeout=2.0, ship_timeout=5.0,
    )
    pre = Engine(cfg, base_params, pre_ec, handoff=mgr)
    pre.start()
    try:
        req = pre.submit(Request([256, 5, 6, 7], max_tokens=24,
                                 temperature=0.0))
        got_one = req.out.get(timeout=120)
        assert got_one is not None
        srv.close()
        dec.stop()
        t0 = time.time()
        while True:
            tok = req.out.get(timeout=60)
            if tok is None:
                break
        assert req.finish_reason in ("error", "length")
        assert time.time() - t0 < 60
    finally:
        pre.stop()
        dec.stop()
        srv.close()
        mgr.close()


# --- engine role contract -------------------------------------------------


def test_role_validation(cfg, base_params):
    with pytest.raises(ValueError):
        Engine(cfg, base_params, ec(role="prefill", kv_layout="dense"))
    with pytest.raises(ValueError):
        Engine(cfg, base_params, ec(role="prefill"))  # no handoff
    with pytest.raises(ValueError):
        Engine(cfg, base_params, ec(role="wat"))
    dec = Engine(cfg, base_params, ec(role="decode"))
    with pytest.raises(RuntimeError):
        dec.submit(Request([1, 2], max_tokens=2))


def test_load_snapshot_carries_role(cfg, base_params):
    dec = Engine(cfg, base_params, ec(role="decode"))
    snap = dec.load_snapshot()
    assert snap["role"] == "decode"
    assert snap["transfer_queue_depth"] == 0
    assert "prefix_hit_tokens" in snap and "prefill_tokens" in snap


# --- gateway surface ------------------------------------------------------


def test_loadreport_role_and_transfer_queue_roundtrip():
    from substratus_tpu.gateway.loadreport import LoadReport

    rep = LoadReport(queue_depth=1, active_slots=2, max_slots=8,
                     kv_free_frac=0.5, role="prefill", transfer_queue=3)
    hdr = rep.to_header()
    assert " r=p" in hdr and " tq=3" in hdr
    back = LoadReport.from_header(hdr)
    assert back.role == "prefill" and back.transfer_queue == 3
    # Transfer backlog adds routing pressure.
    assert back.score() > LoadReport(
        queue_depth=1, active_slots=2, max_slots=8, kv_free_frac=0.5
    ).score()
    # Monolithic replicas stay byte-identical on the wire.
    mono = LoadReport(queue_depth=1, active_slots=2, max_slots=8)
    assert " r=" not in mono.to_header()
    assert LoadReport.from_header(mono.to_header()).role == "both"
    # from_snapshot reads the engine keys.
    snap = LoadReport.from_snapshot(
        {"role": "decode", "transfer_queue_depth": 2}
    )
    assert snap.role == "decode" and snap.transfer_queue == 2


def test_balancer_routes_admissions_to_prefill_pool():
    from substratus_tpu.gateway.balancer import Balancer
    from substratus_tpu.gateway.loadreport import LoadReport

    b = Balancer(["http://p", "http://d", "http://m"], seed=7)
    b.replicas["http://p"].report = LoadReport(role="prefill")
    b.replicas["http://d"].report = LoadReport(role="decode")
    b.replicas["http://m"].report = LoadReport(role="both")
    for _ in range(32):
        rep = b.pick(role="prefill")
        assert rep.url != "http://d", "decode replica took an admission"
    # Role-less picks (e.g. /v1/models relay) remain unrestricted.
    assert b.pick() is not None
    # A decode-only table sheds rather than misroutes.
    b2 = Balancer(["http://d"], seed=1)
    b2.replicas["http://d"].report = LoadReport(role="decode")
    assert b2.pick(role="prefill") is None


def test_decode_role_server_sheds_completions(cfg, base_params):
    from aiohttp.test_utils import TestClient, TestServer

    from substratus_tpu.serve.server import ServerState, build_app
    from substratus_tpu.serve.tokenizer import ByteTokenizer

    eng = Engine(cfg, base_params, ec(role="decode"))  # not started
    state = ServerState(eng, ByteTokenizer(), "tiny")

    async def go():
        async with TestClient(TestServer(build_app(state))) as client:
            r = await client.post(
                "/v1/completions", json={"prompt": "hi", "max_tokens": 2}
            )
            assert r.status == 503
            body = await r.json()
            assert body["error"]["type"] == "wrong_role"
            # /loadz still answers (the gateway's poller reads role).
            r = await client.get("/loadz")
            snap = await r.json()
            assert snap["role"] == "decode"

    import asyncio

    asyncio.run(go())


def test_gateway_adapter_quota_sheds_429():
    """Per-adapter token buckets at the gateway (PR 6 follow-up): one
    tenant over its quota 429s with Retry-After and the adapter_quota
    shed label; other tenants are unaffected."""
    import asyncio

    import aiohttp

    from substratus_tpu.gateway.router import GatewayConfig
    from substratus_tpu.gateway.testing import GatewayHarness
    from substratus_tpu.observability.metrics import METRICS

    async def go():
        h = await GatewayHarness(
            n_replicas=1,
            cfg=GatewayConfig(
                adapter_rate=0.01, adapter_burst=1.0,
                poll_interval=0.2, connect_timeout=1.0,
            ),
        ).start()
        try:
            async with aiohttp.ClientSession() as s:
                # Tenant t1's first request passes the quota (the
                # replica 404s the unknown model — that's fine, the
                # quota fires before routing semantics).
                async with s.post(
                    h.url + "/v1/completions",
                    json={"prompt": "x", "max_tokens": 1, "model": "t1"},
                ) as r:
                    assert r.status == 404
                async with s.post(
                    h.url + "/v1/completions",
                    json={"prompt": "x", "max_tokens": 1, "model": "t1"},
                ) as r:
                    assert r.status == 429
                    assert int(r.headers["Retry-After"]) >= 1
                    body = await r.json()
                    assert body["error"]["type"] == "adapter_quota"
                # Tenant t2 has its own bucket.
                async with s.post(
                    h.url + "/v1/completions",
                    json={"prompt": "x", "max_tokens": 1, "model": "t2"},
                ) as r:
                    assert r.status == 404
                # Base-model traffic (no model field) is never charged.
                async with s.post(
                    h.url + "/v1/completions",
                    json={"prompt": "x", "max_tokens": 1},
                ) as r:
                    assert r.status == 200
        finally:
            await h.stop()

    asyncio.run(go())
    assert METRICS.get(
        "substratus_gateway_sheds_total", 'reason="adapter_quota"'
    ) >= 1
