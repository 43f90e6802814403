"""Start-up from the inside (utils/jaxstart.py, docs/observability.md
"Start-up"): every executable's trace, lowering and compile as spans on
the span log and seconds by stage and program, the process's way to ready
as phases, and startup_record() as the one record of both. CPU only: the
listener is bookkeeping around what JAX reports, whatever the backend."""
import asyncio
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from substratus_tpu.models import llama
from substratus_tpu.observability.metrics import METRICS
from substratus_tpu.observability.tracing import Tracer, tracer
from substratus_tpu.serve.engine import Engine, EngineConfig
from substratus_tpu.utils import jaxstart

STAGES = ("jax.trace", "jax.lower", "jax.compile")
BUILD_S = "substratus_jax_build_seconds_total"


@pytest.fixture(autouse=True)
def listening():
    """The listener on, and the span ring empty: a test's spans are its
    own, and the ring (4,096) cannot wrap under its indices."""
    jaxstart.count_compilations()
    tracer.clear()


def _spans_of(program: str, since: int = 0):
    return [s for s in tracer.finished()[since:]
            if s["name"] in STAGES and s["attributes"]["program"] == program]


def _stage_seconds(program: str) -> dict:
    return {labels["stage"]: v for labels, v in METRICS.series(BUILD_S)
            if labels["program"] == program}


def _fresh(name: str):
    """A jitted function nobody has built, under a name of its own."""
    def fn(x):
        return jnp.tanh(x) * 3 + 1
    fn.__name__ = fn.__qualname__ = name
    return jax.jit(fn)


def _tiny_engine(**ec) -> Engine:
    cfg = llama.CONFIGS["tiny"].replace(dtype=jnp.float32)
    params = llama.init_params(cfg, jax.random.key(0))
    return Engine(cfg, params, EngineConfig(
        max_batch=2, max_seq_len=64, **{"max_prefill_len": 16, **ec}))


def test_record_span_takes_its_start_and_end_from_the_caller():
    t = Tracer()
    with t.span("outer") as outer:
        ctx = t.record_span("given", 100.0, 100.25, program="p")
    root = t.record_span("root", 5.0, 4.0, parent=None)  # a clock that stepped
    given, _, stepped = t.finished()
    assert given["start_us"] == 100_000_000
    assert given["duration_us"] == 250_000
    assert given["parent_id"] == outer.span_id
    assert given["trace_id"] == outer.trace_id == ctx.trace_id
    assert given["attributes"] == {"program": "p"} and given["status"] == "ok"
    assert stepped["parent_id"] is None and stepped["duration_us"] == 0
    assert stepped["trace_id"] == root.trace_id != outer.trace_id


def test_a_build_is_three_spans_under_the_span_that_was_open():
    step = _fresh("startup_trace_three_spans")
    with tracer.span("caller") as caller:
        step(jnp.ones((3, 5)))
    spans = _spans_of("startup_trace_three_spans")
    assert [s["name"] for s in spans] == list(STAGES)
    for s in spans:
        assert s["parent_id"] == caller.span_id
        assert s["trace_id"] == caller.trace_id
        assert s["duration_us"] >= 0
    # one after the other, as JAX runs them
    ends = [s["start_us"] + s["duration_us"] for s in spans]
    assert spans[1]["start_us"] >= ends[0] - 1
    assert spans[2]["start_us"] >= ends[1] - 1


def test_a_second_call_builds_nothing_and_records_nothing():
    step = _fresh("startup_trace_second_call")
    x = jnp.ones((3, 5))
    step(x)
    n_spans = len(tracer.finished())
    seconds = _stage_seconds("startup_trace_second_call")
    built = METRICS.get("substratus_jax_compilations_total")
    step(x)
    assert len(tracer.finished()) == n_spans
    assert _stage_seconds("startup_trace_second_call") == seconds
    assert METRICS.get("substratus_jax_compilations_total") == built


def test_helpers_traced_inside_a_program_are_part_of_its_trace():
    """jnp's own jitted helpers (where, einsum) report a trace of their own
    while the program that calls them is traced: no span, no series, their
    seconds are inside the program's."""
    def fn(x):
        return jnp.where(x > 0, jnp.einsum("ij,kj->ik", x, x), 0.0)
    fn.__name__ = fn.__qualname__ = "startup_trace_nested"
    x = jnp.ones((4, 4))  # an eager op: a build of its own, before
    before = len(tracer.finished())
    programs = {labels["program"] for labels, _ in METRICS.series(BUILD_S)}
    jax.jit(fn)(x)
    new = [s for s in tracer.finished()[before:] if s["name"] in STAGES]
    assert [(s["name"], s["attributes"]["program"]) for s in new] == [
        (stage, "startup_trace_nested") for stage in STAGES]
    assert {labels["program"] for labels, _ in METRICS.series(BUILD_S)} \
        == programs | {"startup_trace_nested"}


def test_the_counter_sums_by_stage_to_the_spans():
    step = _fresh("startup_trace_sums")
    step(jnp.ones((2, 2)))
    step(jnp.ones((4, 2)))  # a second shape: a second build of the program
    spans = _spans_of("startup_trace_sums")
    assert len(spans) == 6
    seconds = _stage_seconds("startup_trace_sums")
    assert set(seconds) == {"trace", "lower", "compile"}  # no cache: no read
    for stage in seconds:
        of_spans = sum(s["duration_us"] for s in spans
                       if s["name"] == "jax." + stage) / 1e6
        assert seconds[stage] == pytest.approx(of_spans, abs=1e-5)
    assert METRICS.get("substratus_jax_builds_total", {
        "program": "startup_trace_sums", "cache": "off"}) == 2


def test_registering_twice_registers_once_and_the_old_counters_read_as_before():
    from jax._src import monitoring

    n = [len(lst()) for lst in (monitoring.get_event_listeners,
                                monitoring.get_event_duration_listeners,
                                monitoring.get_event_time_span_listeners,
                                monitoring.get_scalar_listeners)]
    jaxstart.count_compilations()
    jaxstart.count_compilations()
    assert n == [len(lst()) for lst in (
        monitoring.get_event_listeners,
        monitoring.get_event_duration_listeners,
        monitoring.get_event_time_span_listeners,
        monitoring.get_scalar_listeners)]
    x = jnp.ones((2, 3))  # an eager op: an executable of its own
    built = METRICS.get("substratus_jax_compilations_total") or 0
    spent = METRICS.get("substratus_jax_compile_seconds_total") or 0.0
    hits = METRICS.get("substratus_jax_compile_cache_hits_total") or 0
    _fresh("startup_trace_old_counters")(x)
    assert METRICS.get("substratus_jax_compilations_total") == built + 1
    # the old figure is the compile stage alone, cache read included
    compile_s = _stage_seconds("startup_trace_old_counters")["compile"]
    assert METRICS.get("substratus_jax_compile_seconds_total") - spent \
        == pytest.approx(compile_s, abs=1e-3)
    assert (METRICS.get("substratus_jax_compile_cache_hits_total") or 0) \
        == hits


@pytest.fixture
def persistent_cache(tmp_path):
    """JAX's persistent compilation cache in a directory of this test, and
    its settings put back after."""
    from jax.experimental.compilation_cache import compilation_cache

    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_entry_size_bytes",
             "jax_persistent_cache_min_compile_time_secs")
    saved = {n: getattr(jax.config, n) for n in names}
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    compilation_cache.reset_cache()
    yield
    for name, value in saved.items():
        jax.config.update(name, value)
    compilation_cache.reset_cache()


def test_a_rebuild_reads_the_cache_and_says_so(persistent_cache):
    step = _fresh("startup_trace_cached")
    x = jnp.ones((3, 7))
    step(x)
    first = _spans_of("startup_trace_cached")[-1]
    assert first["name"] == "jax.compile"
    assert first["attributes"]["cache"] == "miss"
    assert first["attributes"]["cache_read_s"] == 0.0
    jax.clear_caches()  # what a new process starts with, the directory kept
    hits = METRICS.get("substratus_jax_compile_cache_hits_total") or 0
    step(x)
    spans = _spans_of("startup_trace_cached")
    # traced and lowered again: the cache saves the compiler, nothing else
    assert [s["name"] for s in spans] == list(STAGES) * 2
    again = spans[-1]["attributes"]
    assert again["cache"] == "hit" and again["cache_read_s"] > 0
    assert METRICS.get("substratus_jax_compile_cache_hits_total") == hits + 1
    seconds = _stage_seconds("startup_trace_cached")
    assert seconds["cache_read"] == pytest.approx(again["cache_read_s"])
    compile_us = sum(s["duration_us"] for s in spans
                     if s["name"] == "jax.compile")
    assert seconds["cache_read"] + seconds["compile"] == pytest.approx(
        compile_us / 1e6, abs=1e-5)
    row = next(b for b in jaxstart.startup_record()["builds"]
               if b["program"] == "startup_trace_cached")
    assert (row["count"], row["cache_hits"], row["cache"]) == (2, 1, "mixed")


def test_without_a_cache_directory_the_span_says_off():
    assert jax.config.jax_compilation_cache_dir is None
    _fresh("startup_trace_no_cache")(jnp.ones((3, 7)))
    attrs = _spans_of("startup_trace_no_cache")[-1]["attributes"]
    assert attrs["cache"] == "off" and attrs["cache_read_s"] == 0.0


def test_a_phase_is_a_span_a_gauge_and_survives_what_it_wraps():
    with tracer.span("root") as root:
        with pytest.raises(KeyError):
            with jaxstart.phase("startup.test_phase", mode="x") as span:
                span.set_attribute("k", 1)
                raise KeyError("boom")
    rec = next(s for s in reversed(tracer.finished())
               if s["name"] == "startup.test_phase")
    assert rec["parent_id"] == root.span_id
    assert rec["attributes"] == {"mode": "x", "k": 1}
    assert rec["status"] == "error:KeyError"
    seconds = METRICS.get("substratus_startup_seconds",
                          {"phase": "startup.test_phase"})
    assert seconds == pytest.approx(rec["duration_us"] / 1e6, abs=1e-3)
    METRICS.remove("substratus_startup_seconds",
                   {"phase": "startup.test_phase"})


def test_the_backend_phase_counts_the_seconds_before_it(capsys):
    age = jaxstart.process_age_s()
    assert age is not None and 0 < age < 24 * 3600
    jaxstart.jax_startup()
    capsys.readouterr()
    span = next(s for s in reversed(tracer.finished())
                if s["name"] == "startup.backend")
    assert span["attributes"]["before_s"] >= round(age, 3)
    rec = jaxstart.startup_record()
    assert rec["before_backend_s"] == pytest.approx(
        span["attributes"]["before_s"], abs=1e-3)
    assert "before_backend" not in rec["phases"]
    assert rec["phases"]["startup.backend"] == pytest.approx(
        span["duration_us"] / 1e6, abs=1e-3)


def test_an_engine_is_one_phase_with_its_cache_and_its_programs_inside():
    before = len(tracer.finished())
    _tiny_engine()
    spans = {s["name"]: s for s in tracer.finished()[before:]
             if s["name"].startswith(("startup.", "engine.build."))}
    inner = ("engine.build.layout", "engine.build.cache",
             "engine.build.programs")
    assert set(spans) == {"startup.engine", *inner}
    outer = spans["startup.engine"]
    for name in inner:
        assert spans[name]["parent_id"] == outer["span_id"]
        assert outer["duration_us"] > spans[name]["duration_us"]
    assert outer["duration_us"] >= sum(
        spans[name]["duration_us"] for name in inner)
    # a dense tree has no leaf a serving program lays out anew
    assert spans["engine.build.layout"]["attributes"] == {
        "leaves": 0, "bytes": 0}
    phases = jaxstart.startup_record()["phases"]
    for name, span in spans.items():
        assert phases[name] == pytest.approx(span["duration_us"] / 1e6,
                                             abs=1e-3)


def test_a_cold_bucket_is_built_under_the_request_that_met_it():
    """The chunk program of a bucket no request has used is traced, lowered
    and compiled inside that request's engine.prefill, the first decode
    step inside engine.first_compile; warm shapes record nothing."""
    eng = _tiny_engine(max_prefill_len=32)
    eng.start()
    try:
        eng.generate(list(range(1, 6)), max_tokens=3, temperature=0.0)
        before = len(tracer.finished())
        with tracer.span("client") as client:
            eng.generate(list(range(1, 21)), max_tokens=3, temperature=0.0,
                         id="cold-bucket")
        after = len(tracer.finished())
        eng.generate(list(range(2, 22)), max_tokens=3, temperature=0.0)
    finally:
        eng.stop()
    spans = tracer.finished()
    new = spans[before:after]
    prefill = next(s for s in new if s["name"] == "engine.prefill")
    assert prefill["attributes"]["request_id"] == "cold-bucket"
    assert prefill["parent_id"] == client.span_id
    # (a jax.trace of microseconds with no jax.lower after it is JAX
    # meeting a new argument signature and finding the jaxpr it has)
    built = [s for s in new if s["name"] in STAGES[1:]
             or s["attributes"].get("program") == "_chunk_prefill_jit"]
    assert [s["name"] for s in built
            if s["attributes"]["program"] == "_chunk_prefill_jit"] \
        == list(STAGES)
    for s in built:
        assert s["parent_id"] == prefill["span_id"]
        assert s["trace_id"] == client.trace_id
    # the same bucket again: the program is there
    assert not [s for s in spans[after:] if s["name"] in STAGES[1:]]
    first = next(s for s in spans if s["name"] == "engine.first_compile")
    decode = [s for s in spans if s["name"] in STAGES
              and s["attributes"]["program"] == "decode"
              and s["parent_id"] == first["span_id"]]
    assert [s["name"] for s in decode][:3] == list(STAGES)


def test_the_record_is_plain_data_and_its_totals_are_the_counters():
    _fresh("startup_trace_record")(jnp.ones((5, 5)))
    rec = jaxstart.startup_record()
    assert json.loads(json.dumps(rec)) == rec
    assert set(rec) == {"phases", "before_backend_s", "builds", "totals",
                        "executables", "cache_hits"}
    assert set(rec["totals"]) == set(jaxstart.BUILD_STAGES)
    by_stage = dict.fromkeys(jaxstart.BUILD_STAGES, 0.0)
    for labels, seconds in METRICS.series(BUILD_S):
        by_stage[labels["stage"]] += seconds
    for stage, seconds in rec["totals"].items():
        assert seconds == pytest.approx(by_stage[stage])
        assert seconds == pytest.approx(
            sum(b[stage + "_s"] for b in rec["builds"]))
    row = next(b for b in rec["builds"]
               if b["program"] == "startup_trace_record")
    assert set(row) == {"program", "trace_s", "lower_s", "cache_read_s",
                        "compile_s", "cache", "count", "cache_hits"}
    assert row["count"] == 1 and row["cache"] == "off"
    assert row["trace_s"] > 0 and row["lower_s"] > 0 and row["compile_s"] > 0
    assert rec["executables"] == METRICS.get(
        "substratus_jax_compilations_total")
    # largest first: the order a reader wants
    spent = [sum(b[s + "_s"] for s in jaxstart.BUILD_STAGES)
             for b in rec["builds"]]
    assert spent == sorted(spent, reverse=True)


def _perfz(state) -> dict:
    from aiohttp.test_utils import TestClient, TestServer

    from substratus_tpu.serve.server import build_app

    async def go():
        async with TestClient(TestServer(build_app(state))) as client:
            r = await client.get("/debug/perfz")
            assert r.status == 200
            return await r.json()

    return asyncio.run(go())


def test_perfz_carries_the_record():
    from substratus_tpu.serve.server import ServerState
    from substratus_tpu.serve.tokenizer import ByteTokenizer

    doc = _perfz(ServerState(_tiny_engine(), ByteTokenizer(), "tiny"))
    assert "first_compile_seconds" in doc
    startup = doc["startup"]
    assert set(startup) == set(jaxstart.startup_record())
    assert startup["phases"]["startup.engine"] > 0
    assert startup["executables"] >= len(startup["builds"]) > 0


def test_serve_main_times_its_way_to_ready(monkeypatch, tmp_path):
    """`serve.main --config tiny` up to the listener: serve.start is a real
    span that holds the phases, and the weights are there when each ends."""
    from substratus_tpu.serve import main as serve_main, server

    seen = []
    monkeypatch.setattr(server, "serve_forever",
                        lambda state, **kw: seen.append(state))
    path = tmp_path / "params.json"
    path.write_text(json.dumps({"max_batch": 2, "max_seq_len": 64}))
    before = len(tracer.finished())
    assert serve_main.main(["--config", "tiny", "--params", str(path)]) == 0
    seen[0].engine.stop()
    spans = tracer.finished()[before:]
    start = next(s for s in spans if s["name"] == "serve.start")
    children = {s["name"]: s for s in spans
                if s["parent_id"] == start["span_id"]
                and s["name"].startswith("startup.")}
    assert list(children) == ["startup.backend", "startup.load",
                              "startup.quantize", "startup.engine"]
    assert children["startup.quantize"]["attributes"] == {"mode": "none"}
    assert start["duration_us"] >= sum(
        s["duration_us"] for s in children.values())
    # weights are built inside startup.load, not charged to what follows
    loads = [s for s in spans if s["name"] == "jax.compile"
             and s["parent_id"] == children["startup.load"]["span_id"]]
    assert loads
    phases = jaxstart.startup_record()["phases"]
    assert phases["serve.start"] == pytest.approx(
        start["duration_us"] / 1e6, abs=1e-3)
    # ...and the server it hands to the listener shows the same record
    doc = _perfz(seen[0])
    assert set(doc["startup"]) == set(jaxstart.startup_record())
    assert {"serve.start", "startup.backend", "startup.load",
            "startup.quantize", "startup.engine", "engine.build.cache",
            "engine.build.programs"} <= set(doc["startup"]["phases"])
    assert doc["startup"]["phases"]["serve.start"] == phases["serve.start"]


def test_the_listener_changes_no_program(monkeypatch):
    """The decode and chunk programs lower to the same text with the
    listener registered and with nobody listening: spans and counters are
    bookkeeping beside the build, as region names are (tests/test_scopes.py)."""
    from jax._src import monitoring

    def lowered():
        e = _tiny_engine()
        decode = e._decode_fn.lower(
            e.params, e.cache, e.block_table, e.tokens, e.positions, e.temps,
            e.top_ps, e.key, None, None)
        chunk = Engine._chunk_prefill_jit.lower(
            e.model, e.cfg, e.params, e.cache, np.zeros((1, 16), np.int32),
            0, 16, block_table=e.block_table[:1])
        return decode.as_text(), chunk.as_text()

    before = len(tracer.finished())
    heard = lowered()
    assert {"decode", "_chunk_prefill_jit"} <= {
        s["attributes"]["program"] for s in tracer.finished()[before:]
        if s["name"] == "jax.lower"}
    for listeners in ("_event_listeners", "_event_duration_secs_listeners",
                      "_event_time_span_listeners", "_scalar_listeners"):
        monkeypatch.setattr(monitoring, listeners, [])
    jax.clear_caches()
    before = len(tracer.finished())
    unheard = lowered()
    assert not [s for s in tracer.finished()[before:] if s["name"] in STAGES]
    assert heard == unheard
