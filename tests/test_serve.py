"""Serving engine + HTTP contract tests (reference analogue: test/system.sh's
curl of /v1/completions and the `GET /` readiness contract,
docs/container-contract.md:50-56)."""
import json
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from substratus_tpu.models import llama
from substratus_tpu.serve.engine import Engine, EngineConfig, Request
from substratus_tpu.serve.tokenizer import ByteTokenizer
from substratus_tpu.ops.kvcache import insert_prefill


@pytest.fixture(scope="module")
def engine():
    cfg = llama.CONFIGS["tiny"].replace(vocab_size=258, dtype=jnp.float32)
    params = llama.init_params(cfg, jax.random.key(0))
    eng = Engine(cfg, params, EngineConfig(max_batch=4, max_seq_len=64, eos_token_id=257))
    eng.start()
    yield eng
    eng.stop()


def test_generate_deterministic_greedy(engine):
    out1 = engine.generate([256, 10, 20, 30], max_tokens=8, temperature=0.0)
    out2 = engine.generate([256, 10, 20, 30], max_tokens=8, temperature=0.0)
    assert out1 == out2
    assert 0 < len(out1) <= 8


def test_greedy_matches_model_decode(engine):
    """Engine output == straight-line prefill+decode with the same params."""
    cfg, params = engine.cfg, engine.params
    prompt = [256, 65, 66, 67]
    want = []
    logits, kv = llama.forward(
        params, jnp.asarray([prompt], jnp.int32), cfg
    )
    cache = llama.init_cache(cfg, 1, 64)
    cache = insert_prefill(cache, kv, len(prompt))
    tok = int(logits[0, -1].argmax())
    pos = len(prompt)
    for _ in range(6):
        want.append(tok)
        lg, cache = llama.decode_step(
            params, cache, jnp.array([tok], jnp.int32), jnp.array([pos], jnp.int32), cfg
        )
        tok = int(lg[0].argmax())
        pos += 1
    got = engine.generate(prompt, max_tokens=6, temperature=0.0)
    assert got == want, (got, want)


def test_concurrent_requests(engine):
    """Multiple in-flight requests (continuous batching) don't cross-talk."""
    prompts = [[256, i, i + 1] for i in range(0, 12, 2)]
    solo = [engine.generate(p, max_tokens=5, temperature=0.0) for p in prompts]

    results = [None] * len(prompts)

    def run(i):
        results[i] = engine.generate(prompts[i], max_tokens=5, temperature=0.0)

    threads = [threading.Thread(target=run, args=(i,)) for i in range(len(prompts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert results == solo, (results, solo)


def test_burst_while_decoding(engine):
    """A burst of arrivals while a request is mid-decode exercises the
    capped-admission branch; every request must still complete correctly."""
    prompts = [[256, 40 + i] for i in range(6)]
    solo = [engine.generate(p, max_tokens=6, temperature=0.0) for p in prompts]

    # Start one long request so the engine is actively decoding, then burst.
    first = Request(prompt_tokens=[256, 30], max_tokens=24, temperature=0.0)
    engine.submit(first)
    assert first.out.get(timeout=120) is not None  # it's mid-decode now
    reqs = [
        engine.submit(Request(prompt_tokens=p, max_tokens=6, temperature=0.0))
        for p in prompts
    ]
    results = []
    for r in reqs:
        toks = []
        while True:
            t = r.out.get(timeout=120)
            if t is None:
                break
            toks.append(t)
        results.append(toks)
    while first.out.get(timeout=120) is not None:
        pass
    assert results == solo, (results, solo)


def _idle(eng, timeout=30.0):
    """Wait until no slot is decoding and nothing is in flight."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if not eng.active.any() and eng.queue.empty():
            steps = eng.stats["decode_steps"]
            time.sleep(0.05)
            if steps == eng.stats["decode_steps"] and not eng.active.any():
                return
        time.sleep(0.01)
    raise AssertionError("the engine did not go idle")


@pytest.mark.parametrize("overlap", [True, False], ids=["overlap", "sync"])
def test_a_finished_sampled_request_leaves_its_row_greedy(overlap):
    """A request with a temperature holds its decode steps on the sampler's
    sorted branch (ops/sampling.py::sample reads every row's temperature),
    and only while it lives: its released slot is greedy again (`temps` 0,
    `top_ps` 1, as the rows are born), `decode_steps_sampled` stops growing
    at the release, and the greedy requests that follow get the tokens of
    an engine that never saw it."""
    cfg = llama.CONFIGS["tiny"].replace(vocab_size=258, dtype=jnp.float32)
    params = llama.init_params(cfg, jax.random.key(0))
    ec = EngineConfig(max_batch=2, max_seq_len=64, eos_token_id=-1,
                      overlap=overlap)
    prompts = [[256, 10 + i, 20 + i] for i in range(3)]
    clean, seen = Engine(cfg, params, ec), Engine(cfg, params, ec)
    clean.start()
    seen.start()
    try:
        assert seen.stats["decode_steps"] == seen.stats["decode_steps_sampled"] == 0
        out = seen.generate([256, 1, 2], max_tokens=6, temperature=0.8, top_p=0.9)
        assert len(out) == 6
        _idle(seen)
        st = dict(seen.stats)
        assert 5 <= st["decode_steps_sampled"] == st["decode_steps"]
        np.testing.assert_array_equal(seen.temps, np.zeros(2, np.float32))
        np.testing.assert_array_equal(seen.top_ps, np.ones(2, np.float32))

        want = [clean.generate(p, max_tokens=5, temperature=0.0) for p in prompts]
        got = [seen.generate(p, max_tokens=5, temperature=0.0) for p in prompts]
        assert got == want
        _idle(seen)
        assert seen.stats["decode_steps_sampled"] == st["decode_steps_sampled"]
        assert seen.stats["decode_steps"] >= st["decode_steps"] + 3 * 4
        assert clean.stats["decode_steps_sampled"] == 0 < clean.stats["decode_steps"]

        # a greedy and a sampled request side by side: the step is a sampled
        # one for both, and the greedy one's tokens do not move
        side = seen.submit(Request(prompt_tokens=[256, 7], max_tokens=12,
                                   temperature=1.0, top_p=0.5))
        assert side.out.get(timeout=120) is not None
        assert seen.generate(prompts[0], max_tokens=5, temperature=0.0) == want[0]
        while side.out.get(timeout=120) is not None:
            pass
        _idle(seen)
        assert seen.stats["decode_steps_sampled"] > st["decode_steps_sampled"]
        assert not seen.temps.any() and (seen.top_ps == 1).all()
    finally:
        clean.stop()
        seen.stop()


def test_http_completions(engine):
    """Drive the aiohttp app via its test client."""
    import asyncio

    from aiohttp.test_utils import TestClient, TestServer

    from substratus_tpu.serve.server import ServerState, build_app

    state = ServerState(engine, ByteTokenizer(), "tiny")

    async def go():
        app = build_app(state)
        async with TestClient(TestServer(app)) as client:
            r = await client.get("/")
            assert r.status == 200
            r = await client.get("/v1/models")
            body = await r.json()
            assert body["data"][0]["id"] == "tiny"
            r = await client.post(
                "/v1/completions",
                json={"prompt": "hi", "max_tokens": 4, "temperature": 0.0},
            )
            assert r.status == 200
            body = await r.json()
            assert body["object"] == "text_completion"
            assert body["usage"]["completion_tokens"] >= 1
            # error paths
            r = await client.post("/v1/completions", json={})
            assert r.status == 400
            r = await client.post(
                "/v1/chat/completions",
                json={
                    "messages": [{"role": "user", "content": "hello"}],
                    "max_tokens": 4,
                },
            )
            assert (await r.json())["object"] == "chat.completion"
            # stop sequences: the completion truncates at the first match
            r = await client.post(
                "/v1/completions",
                json={"prompt": "hi", "max_tokens": 8, "temperature": 0.0},
            )
            full_text = (await r.json())["choices"][0]["text"]
            assert len(full_text) >= 2, full_text  # precondition, not a guard
            r = await client.post(
                "/v1/completions",
                json={
                    "prompt": "hi", "max_tokens": 8, "temperature": 0.0,
                    "stop": full_text[1],
                },
            )
            stopped_body = await r.json()
            stopped = stopped_body["choices"][0]["text"]
            assert full_text[1] not in stopped
            assert full_text.startswith(stopped)
            assert stopped_body["choices"][0]["finish_reason"] == "stop"
            # budget exhaustion reports "length"
            r = await client.post(
                "/v1/completions",
                json={"prompt": "hi", "max_tokens": 2, "temperature": 0.0},
            )
            assert (await r.json())["choices"][0]["finish_reason"] == "length"
            # malformed knobs are rejected before any engine work
            r = await client.post(
                "/v1/completions",
                json={"prompt": "hi", "stop": 42},
            )
            assert r.status == 400
            r = await client.post(
                "/v1/completions",
                json={"prompt": "hi", "max_tokens": "many"},
            )
            assert r.status == 400
            # engine-level early stop: the slot must not decode to
            # max_tokens once the stop sequence appeared
            r = await client.post(
                "/v1/completions",
                json={
                    "prompt": "hi", "max_tokens": 40, "temperature": 0.0,
                    "stop": full_text[1],
                },
            )
            early = await r.json()
            assert early["usage"]["completion_tokens"] < 40, early["usage"]
            assert early["choices"][0]["finish_reason"] == "stop"
            # observability surface
            r = await client.get("/metrics")
            text = await r.text()
            assert "substratus_serve_max_slots 4" in text
            # profile path is fixed server-side (never caller-controlled)
            r = await client.post("/debug/profile", json={"seconds": 0.2})
            body = await r.json()
            assert body["dir"].startswith("/tmp/substratus-profile/")
            r = await client.post("/debug/profile", json={"seconds": -1})
            assert r.status == 400
            r = await client.post("/debug/profile", json=[1])
            assert r.status == 400

    asyncio.run(go())


def test_http_streaming_stop_and_knob_validation(engine):
    """The SSE path must honor `stop` exactly like the non-streaming path:
    truncate before the match, cancel the engine slot, finish_reason
    "stop" — and never emit the stop sequence even when it spans chunks."""
    import asyncio

    from aiohttp.test_utils import TestClient, TestServer

    from substratus_tpu.serve.server import ServerState, build_app

    state = ServerState(engine, ByteTokenizer(), "tiny")

    async def read_stream(client, payload):
        r = await client.post("/v1/completions", json=payload)
        assert r.status == 200
        text, finish = "", None
        async for line in r.content:
            line = line.decode().strip()
            if not line.startswith("data: ") or line == "data: [DONE]":
                continue
            chunk = json.loads(line[len("data: "):])
            choice = chunk["choices"][0]
            text += choice.get("text", "")
            if choice["finish_reason"] is not None:
                finish = choice["finish_reason"]
        return text, finish

    async def go():
        app = build_app(state)
        async with TestClient(TestServer(app)) as client:
            # Oracle: the non-streaming full text.
            r = await client.post(
                "/v1/completions",
                json={"prompt": "hi", "max_tokens": 10, "temperature": 0.0},
            )
            full_text = (await r.json())["choices"][0]["text"]
            assert len(full_text) >= 3

            # No stop: the stream reassembles the exact full text.
            text, finish = await read_stream(
                client,
                {"prompt": "hi", "max_tokens": 10, "temperature": 0.0,
                 "stream": True},
            )
            assert text == full_text
            assert finish == "length"

            # Stop on a mid-text char: truncated before it, engine slot
            # cancelled early, finish_reason "stop".
            stop = full_text[2]
            text, finish = await read_stream(
                client,
                {"prompt": "hi", "max_tokens": 40, "temperature": 0.0,
                 "stream": True, "stop": stop},
            )
            assert stop not in text
            assert full_text.startswith(text)
            assert finish == "stop"

            # Multi-char stop spanning chunk boundaries is held back whole.
            stop2 = full_text[1:4]
            text, finish = await read_stream(
                client,
                {"prompt": "hi", "max_tokens": 40, "temperature": 0.0,
                 "stream": True, "stop": [stop2]},
            )
            assert stop2 not in text
            assert text == full_text[:1]
            assert finish == "stop"

            # Knob ranges reject up front, streaming or not.
            for bad in (
                {"max_tokens": 0},
                {"temperature": -0.5},
                {"temperature": float("nan")},
                {"top_p": 0},
                {"top_p": 1.5},
                {"top_p": float("nan")},
            ):
                r = await client.post(
                    "/v1/completions", json={"prompt": "hi", **bad}
                )
                assert r.status == 400, bad

    asyncio.run(go())


def test_checkpoint_roundtrip(tmp_path):
    from substratus_tpu.train.checkpoints import maybe_restore_orbax, save_artifact

    cfg = llama.CONFIGS["tiny"].replace(dtype=jnp.float32)
    params = llama.init_params(cfg, jax.random.key(1))
    save_artifact(str(tmp_path / "art"), params, cfg)
    restored = maybe_restore_orbax(str(tmp_path / "art"))
    assert restored is not None
    cfg2, params2 = restored
    assert cfg2 == cfg
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(params2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # a non-artifact dir returns None
    assert maybe_restore_orbax(str(tmp_path)) is None


def _serve_main_engine(monkeypatch, tmp_path, params_json):
    """serve.main.main with a params.json, up to the point where it would
    bind HTTP: returns the engine it built and started."""
    from substratus_tpu.serve import main as serve_main, server

    seen = []
    monkeypatch.setattr(
        server, "serve_forever", lambda state, **kw: seen.append(state)
    )
    path = tmp_path / "params.json"
    path.write_text(json.dumps(params_json))
    assert serve_main.main(["--config", "tiny", "--params", str(path)]) == 0
    return seen[0].engine


@pytest.mark.parametrize(
    "removed",
    [
        {"decode_attn_impl": "pallas", "chunk_attn_impl": "flash"},
        # what once turned kv_layout=auto into dense
        {"decode_attn_impl": "fused"},
    ],
    ids=["both-keys", "fused-with-auto"],
)
def test_removed_attention_keys_warn_and_change_nothing(
    removed, monkeypatch, tmp_path, capsys
):
    """decode_attn_impl / chunk_attn_impl are no params.json keys: the
    server names them in its unknown-key warning, a Llama-family model
    under kv_layout=auto still serves the paged layout, and its greedy
    tokens are those of the same file without them."""
    kept = {"kv_layout": "auto", "max_batch": 2, "max_seq_len": 64}
    outs = {}
    for name, params_json in (("kept", kept), ("all", {**kept, **removed})):
        capsys.readouterr()
        eng = _serve_main_engine(monkeypatch, tmp_path, params_json)
        try:
            # the line names the unknown keys, then every known one
            unknown = [line.split("(typo?")[0]
                       for line in capsys.readouterr().err.splitlines()
                       if "ignores unrecognized params.json keys" in line]
            assert eng.paged
            assert not any(hasattr(eng.cfg, key) for key in removed)
            outs[name] = (
                unknown,
                eng.generate([256, 10, 20, 30], max_tokens=6, temperature=0.0),
            )
        finally:
            eng.stop()
    assert outs["kept"][0] == []
    (warned,) = outs["all"][0]
    assert all(repr(key) in warned for key in removed), warned
    assert outs["all"][1] == outs["kept"][1] and outs["kept"][1]
