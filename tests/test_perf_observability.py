"""Performance-observability subsystem (PR 3).

A device kind with no peak is an error and not a default, phase-level
timings land in the shared registry and surface on /debug/perfz, and the
lockstep transports carry what they are given.
"""
import asyncio
import json
import re
import threading

import jax.numpy as jnp
import pytest

from substratus_tpu.observability.metrics import (
    METRICS,
    quantile_from_buckets,
)


def test_unknown_device_kind_is_an_error_not_a_default_peak(monkeypatch):
    """train/telemetry.py divides by the peak of the device JAX reports.
    A TPU that is not in the table raises; only the CPU (shape checks,
    test meshes) runs without a utilization."""
    import jax

    from substratus_tpu.train import telemetry

    assert telemetry.device_peak_flops() is None  # the 8-device CPU mesh

    class FakeTpu:
        platform = "tpu"
        device_kind = "TPU v9 imaginary"

    monkeypatch.setattr(jax, "devices", lambda: [FakeTpu()] * 4)
    with pytest.raises(KeyError, match="TPU v9"):
        telemetry.device_peak_flops()
    FakeTpu.device_kind = "TPU v5 lite"
    assert telemetry.device_peak_flops() == 4 * 197e12


# --- quantile helper --------------------------------------------------------

def test_quantile_from_buckets_interpolates():
    # 10 obs <= 0.1, 10 more <= 1.0 (cumulative), none beyond.
    buckets = [(0.1, 10), (1.0, 20), (float("inf"), 20)]
    assert quantile_from_buckets(buckets, 0.5) == pytest.approx(0.1)
    assert quantile_from_buckets(buckets, 0.75) == pytest.approx(0.55)
    assert quantile_from_buckets(buckets, 1.0) == pytest.approx(1.0)
    # +Inf bucket clamps to the widest finite bound.
    assert quantile_from_buckets(
        [(0.1, 0), (float("inf"), 5)], 0.9
    ) == pytest.approx(0.1)
    assert quantile_from_buckets([], 0.5) is None
    assert quantile_from_buckets([(0.1, 0), (float("inf"), 0)], 0.5) is None


# --- TcpSync lockstep transport ---------------------------------------------

def test_tcp_sync_broadcast_roundtrip():
    """Leader/follower TcpSync: short and >1KB payloads arrive intact,
    both sides record (bytes, seconds) timing samples, and the follower
    sees the delivered length."""
    import socket

    from substratus_tpu.serve.multihost import TcpSync

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]

    payloads = [b"tick", b"x" * 40_000, b""]
    got = []

    def follower():
        sync = TcpSync(1, 2, port)
        for _ in payloads:
            got.append(sync.broadcast(None))
        sync.close()

    t = threading.Thread(target=follower)
    t.start()
    leader = TcpSync(0, 2, port)
    for p in payloads:
        assert leader.broadcast(p) == p
    t.join(timeout=30)
    assert not t.is_alive()
    leader.close()
    assert got == payloads
    # Both sides' timing samples carry the real delivered sizes.
    assert [b for b, _ in leader.timings] == [len(p) for p in payloads]


def test_step_sync_header_is_little_endian():
    """The broadcast length header is packed '<I' and must be read back
    with an explicit little-endian dtype — a native-order view would
    desync the gang on big-endian hosts (satellite fix)."""
    import numpy as np

    from substratus_tpu.serve.multihost import struct_pack_u32

    n = 0x01020304
    buf = np.frombuffer(struct_pack_u32(n), np.uint8)
    assert int(buf.view(np.dtype("<u4"))[0]) == n
    # The buggy read: native order happens to agree on LE hosts but the
    # explicit dtype is what the code must use (see StepSync._broadcast).
    assert int(np.frombuffer(struct_pack_u32(1024), np.dtype("<u4"))[0]) == 1024


# --- engine phase timing + /debug/perfz -------------------------------------

@pytest.fixture(scope="module")
def engine():
    import jax

    from substratus_tpu.models import llama
    from substratus_tpu.serve.engine import Engine, EngineConfig

    cfg = llama.CONFIGS["tiny"].replace(vocab_size=258, dtype=jnp.float32)
    params = llama.init_params(cfg, jax.random.key(0))
    eng = Engine(
        cfg, params,
        EngineConfig(max_batch=4, max_seq_len=64, eos_token_id=257),
    )
    eng.start()
    yield eng
    eng.stop()


def test_engine_phase_metrics_and_first_compile(engine):
    engine.generate([256, 5, 6, 7], max_tokens=8, temperature=0.0)
    text = METRICS.render()
    assert "# TYPE substratus_serve_phase_seconds histogram" in text
    for phase in ("admission", "prefill", "sample", "decode"):
        assert re.search(
            rf'substratus_serve_phase_seconds_count\{{phase="{phase}"\}} '
            r"[1-9]", text
        ), f"phase {phase} not observed\n"
    first = METRICS.get("substratus_serve_first_compile_seconds")
    assert first is not None and first > 0
    # The compile iteration is excluded from the steady-state decode
    # histogram (first_compile >> any single decode step on tiny).
    series = METRICS.histogram_series("substratus_serve_phase_seconds")
    decode = series['phase="decode"']
    assert decode["count"] >= 1
    # first-compile recorded a span too
    from substratus_tpu.observability.tracing import tracer

    names = [s["name"] for s in tracer.finished()]
    assert "engine.first_compile" in names


def test_perfz_endpoint_shape(engine):
    from aiohttp.test_utils import TestClient, TestServer

    from substratus_tpu.serve.server import ServerState, build_app
    from substratus_tpu.serve.tokenizer import ByteTokenizer

    state = ServerState(engine, ByteTokenizer(), "tiny")

    async def go():
        app = build_app(state)
        async with TestClient(TestServer(app)) as client:
            r = await client.post(
                "/v1/completions",
                json={"prompt": "hello", "max_tokens": 6,
                      "temperature": 0.0},
            )
            assert r.status == 200
            r = await client.get("/debug/perfz")
            assert r.status == 200
            return await r.json()

    doc = asyncio.run(go())
    for phase in ("prefill", "sample", "decode"):
        stats = doc["phases"][phase]
        assert stats["count"] >= 1
        assert stats["p50_s"] is not None and stats["p50_s"] >= 0
        assert stats["mean_s"] >= 0
    assert doc["first_compile_seconds"] > 0
    assert doc["latencies"]["ttft"]["all"]["count"] >= 1
    assert doc["engine"]["max_slots"] == 4
    assert doc["engine"]["kv_layout"] in ("paged", "dense")
    # a page of the family's own: llama states none
    assert doc["engine"]["kv_page_tokens"] == engine.page_size == 16
    assert "stats" in doc["engine"]


def test_train_phase_splits_in_record_and_registry():
    from substratus_tpu.train.telemetry import StepLogger

    before = METRICS.histogram_series("substratus_train_phase_seconds")
    n_before = sum(s["count"] for s in before.values()) if before else 0
    lines = []
    sl = StepLogger(n_params=1000, tokens_per_step=128, emit=lines.append)
    rec = sl.log_step(
        0, loss=1.0, step_seconds=0.2, last=True,
        data_seconds=0.05, checkpoint_seconds=0.01,
    )
    assert rec["data_seconds"] == 0.05
    assert rec["checkpoint_seconds"] == 0.01
    assert json.loads(lines[-1])["data_seconds"] == 0.05
    after = METRICS.histogram_series("substratus_train_phase_seconds")
    assert sum(s["count"] for s in after.values()) == n_before + 3
    assert 'phase="data_load"' in after and 'phase="checkpoint"' in after


# --- satellite: q4 tuple-spec axis overlap ----------------------------------

def test_q4_axes_tuple_spec_overlap(mesh8):
    """A contracting dim sharded with a TUPLE spec (("data","fsdp")) must
    knock a plain "data" batch spec off the m axis — membership is per
    mesh-axis name, not whole-value equality (satellite fix)."""
    import jax

    from jax.sharding import NamedSharding, PartitionSpec as P
    from substratus_tpu.ops.quant4 import _q4_axes

    mesh = mesh8
    # C/block must divide the 4-way ("data","fsdp") contracting shards so
    # the row-parallel path stays live and the overlap check is what's
    # under test.
    C, N, block = 512, 128, 128

    def struct(shape, spec):
        return jax.ShapeDtypeStruct(
            shape, jnp.float32, sharding=NamedSharding(mesh, spec)
        )

    xs = struct((8, C), P("data", None))
    ps = struct((C, N), P(("data", "fsdp"), None))
    ss = struct((C // block, N), P())
    m, c, n = _q4_axes(mesh, (xs, ps, ss), block)
    assert m is None  # "data" already claimed by the contracting axis
    # Disjoint batch axis survives.
    xs2 = struct((8, C), P("tensor", None))
    m2, _, _ = _q4_axes(mesh, (xs2, ps, ss), block)
    assert m2 == "tensor"
