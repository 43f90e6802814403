"""Scheduler phases (observability/timeline.py::StepTimeline.phase): one
timing site feeds the step timeline, the phase histogram and the profiler's
trace; and the live / cached split of the KV page-utilisation metric."""
import threading

import jax
import jax.numpy as jnp
import pytest

from substratus_tpu.models import llama
from substratus_tpu.observability.metrics import METRICS
from substratus_tpu.observability.timeline import HISTOGRAM_PHASE, StepTimeline
from substratus_tpu.serve.engine import Engine, EngineConfig
from substratus_tpu.serve.paged_kv import PageAllocator, SlotPages


def _phase_count(label: str) -> int:
    series = METRICS.histogram_series("substratus_serve_phase_seconds")
    return series.get(f'phase="{label}"', {}).get("count", 0)


def test_phases_accumulate_into_one_iteration_record():
    tl = StepTimeline()
    with tl.phase("iter"):
        with tl.phase("admit") as ph:
            ph.observe = False
        with tl.phase("dispatch"):
            with tl.phase("flush", reason="preempt"):
                with tl.phase("wait.flush"):
                    pass
        with tl.phase("drain"):
            with tl.phase("wait.drain"):
                pass
            with tl.phase("emit"):
                pass
        tl.pool_dry()
        rec = tl.commit(admitted=0, active_slots=3, max_slots=4)
    assert rec["flush_reasons"] == ["preempt"] and rec["pool_dry"] is True
    assert rec["dispatch_s"] >= rec["flush_s"] > 0  # a flush nests in it
    assert rec["drain_s"] > 0 and 0 < rec["drain_off_s"] <= rec["wall_s"]
    assert rec["occupancy"] == 0.75
    assert rec["wall_s"] >= rec["admit_s"] + rec["dispatch_s"] + rec["drain_s"]
    # a new iteration starts clean
    with tl.phase("iter"):
        rec2 = tl.commit(admitted=0, active_slots=1, max_slots=4)
    assert rec2["flush_reasons"] == [] and rec2["pool_dry"] is False
    assert rec2["dispatch_s"] == rec2["drain_s"] == rec2["flush_s"] == 0.0


@pytest.mark.parametrize("name,label", sorted(HISTOGRAM_PHASE.items()))
def test_a_phase_observes_the_histogram_where_it_has_that_phase(name, label):
    tl = StepTimeline()
    before = _phase_count(label)
    with tl.phase(name):
        pass
    assert _phase_count(label) == before + 1
    with tl.phase(name) as ph:
        ph.observe = False  # e.g. an admission pass that boarded nobody
    assert _phase_count(label) == before + 1
    assert ph.seconds >= 0.0


def test_phases_outside_the_histogram_add_no_series():
    tl = StepTimeline()
    before = set(METRICS.histogram_series("substratus_serve_phase_seconds"))
    for name in ("iter", "drain", "emit", "idle", "wait.drain", "flush"):
        with tl.phase(name):
            pass
    after = set(METRICS.histogram_series("substratus_serve_phase_seconds"))
    assert after == before


@pytest.fixture(scope="module")
def traced_engine(tmp_path_factory):
    """A tiny paged engine serving two requests under jax.profiler."""
    from benchmarks.harness import trace_scopes as TS

    cfg = llama.CONFIGS["tiny"].replace(dtype=jnp.float32)
    params = llama.init_params(cfg, jax.random.key(0))
    eng = Engine(cfg, params, EngineConfig(
        max_batch=2, max_seq_len=64, max_prefill_len=16))
    eng.start()
    try:
        eng.generate([1, 2, 3], max_tokens=3, temperature=0.0)  # compiles
        d = tmp_path_factory.mktemp("phases")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        stats0 = dict(eng.stats)
        jax.profiler.start_trace(str(d), profiler_options=opts)
        ts = [threading.Thread(target=eng.generate, args=(list(range(1, n)),),
                               kwargs={"max_tokens": 8, "temperature": 0.0})
              for n in (30, 12)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=120)
        jax.profiler.stop_trace()
        stats1 = dict(eng.stats)
    finally:
        eng.stop()
    planes = TS.load_file(TS.find_xplane(str(d)))
    return eng, planes, TS.reduce_planes(planes), stats0, stats1


def _scheduler_events(planes):
    lines = [[(p["meta"][m]["name"], s, d) for m, s, d in l["events"]]
             for p in planes if p["name"].startswith("/host:")
             for l in p["lines"]]
    with_iters = [l for l in lines if any(e[0] == "engine.iter" for e in l)]
    assert len(with_iters) == 1  # every phase is on the scheduler thread
    others = [e for l in lines if l is not with_iters[0] for e in l]
    assert not any(e[0].startswith("engine.") for e in others)
    evs = sorted((e for e in with_iters[0] if e[0].startswith("engine.")),
                 key=lambda e: (e[1], -e[2]))
    # the iteration open when the capture stops is not recorded, its
    # finished children are: keep what the recorded iterations span
    end = max(e[1] + e[2] for e in evs if e[0] == "engine.iter")
    return [e for e in evs if e[1] + e[2] <= end]


def _parent(events, child):
    """The innermost event that covers `child`."""
    cover = [e for e in events if e is not child
             and e[1] <= child[1] and child[1] + child[2] <= e[1] + e[2]]
    return max(cover, key=lambda e: e[1])[0] if cover else None


def test_phases_nest_on_the_scheduler_thread(traced_engine):
    _, planes, _, _, _ = traced_engine
    evs = _scheduler_events(planes)
    parents = {}
    for e in evs:
        parents.setdefault(e[0], set()).add(_parent(evs, e))
    assert parents["engine.iter"] == {None}
    assert parents["engine.broadcast"] == {None}
    for name in ("engine.admit", "engine.dispatch", "engine.drain"):
        assert parents[name] == {"engine.iter"}, name
    assert parents["engine.wait.drain"] == {"engine.drain"}
    assert parents["engine.emit"] == {"engine.drain"}
    assert parents["engine.prefill"] == {"engine.admit"}
    assert parents["engine.sample"] == {"engine.admit"}
    assert parents["engine.wait.first_token"] == {"engine.sample"}
    # 29 prompt tokens through chunks of 16: two dispatches, one request
    assert sum(1 for e in evs if e[0] == "engine.prefill") == 3


def test_iterations_split_into_work_waits_and_idle(traced_engine):
    from benchmarks.harness import trace_scopes as TS

    _, _, red, _, _ = traced_engine
    host = red["host"]
    decoded = [i for i in host["iters"] if i["decoded"]]
    assert len(decoded) >= 8
    for i in decoded:
        assert i["work_ms"] > 0 and i["wait_ms"] >= 0
        assert i["wall_ms"] == pytest.approx(
            i["work_ms"] + i["wait_ms"] + i["idle_ms"])
    # nothing the thread does is outside a phase
    assert host["covered_share"] > 0.98
    assert TS.host_work_ms(red) > 0


def test_live_pages_are_counted_once_and_cached_pages_apart(traced_engine):
    eng, _, _, stats0, stats1 = traced_engine
    live = stats1["kv_live_pages_sum"] - stats0["kv_live_pages_sum"]
    pool = stats1["kv_pool_pages_sum"] - stats0["kv_pool_pages_sum"]
    assert pool > 0 and pool % eng.n_pages == 0
    assert 0 < live < pool
    series = METRICS.histogram_series(
        "substratus_serve_kv_page_utilization_ratio")
    assert series['state="live"']["count"] > 0
    assert series['state="live"']["count"] == series['state="cached"']["count"]
    # after the run no slot holds a page; the prefix registry still does
    assert eng.slot_pages.live_pages == 0 and eng.alloc.used_pages > 0


@pytest.mark.parametrize("overlap", [False, True], ids=["lockstep", "overlap"])
def test_a_step_counts_the_pages_it_needs_against_the_table(overlap):
    """One long request beside an idle slot: a decoding iteration needs
    position // page_size + 1 pages of the request's row and one of the
    idle row's, against a table of max_batch x max_pages; the idle slot
    sits at position 0 before, during and after."""
    from benchmarks.layer_metrics import decode_kv_read_share

    cfg = llama.CONFIGS["tiny"].replace(dtype=jnp.float32)
    params = llama.init_params(cfg, jax.random.key(0))
    page, prompt, new = 4, 29, 11
    eng = Engine(cfg, params, EngineConfig(
        max_batch=2, max_seq_len=64, max_prefill_len=16, page_size=page,
        overlap=overlap))
    table = 2 * (64 // page)
    eng.start()
    try:
        eng.generate(list(range(1, prompt + 1)), max_tokens=new,
                     temperature=0.0)
    finally:
        eng.stop()
    st = eng.stats
    steps, rest = divmod(st["decode_kv_pages_table_sum"], table)
    # the first token comes from the prefill, the others one a step; under
    # overlap one more step is in flight when the last token is read
    assert rest == 0 and steps in (new - 1, new - 1 + overlap)
    want = sum((prompt + i) // page + 1 + 1 for i in range(steps))
    assert st["decode_kv_pages_read_sum"] == want
    run = {"counters": {"stats": st}, "rehearse": False}
    share = decode_kv_read_share.read(run)
    assert share == pytest.approx(100.0 * want / (steps * table))
    assert 25 < share < 35  # 8-10 pages + 1 of 32
    # a CPU rehearsal writes nothing under a `decode_` name
    assert decode_kv_read_share.read(dict(run, rehearse=True)) is None
    assert not eng.active.any()
    assert (eng.positions == 0).all() and (eng.host_positions == 0).all()


def test_the_read_share_of_a_program_without_the_counters_is_nothing():
    from benchmarks.layer_metrics import decode_kv_read_share

    for stats in ({}, {"kv_live_pages_sum": 5, "kv_pool_pages_sum": 10}):
        run = {"counters": {"stats": stats}, "rehearse": False}
        assert decode_kv_read_share.read(run) is None


def test_slot_pages_count_a_shared_page_once():
    alloc = PageAllocator(8, first_page=1)
    sp = SlotPages(3)
    a, b, c = alloc.alloc(), alloc.alloc(), alloc.alloc()
    sp.assign(0, [], [a, b])
    alloc.incref(a)  # slot 1 claims the shared prefix page
    sp.assign(1, [a], [c])
    assert sp.live_pages == 3
    d = alloc.alloc()
    sp.append(1, d)
    assert sp.live_pages == 4
    sp.release(0, alloc)
    assert sp.live_pages == 3  # `a` still held by slot 1
    sp.release(1, alloc)
    assert sp.live_pages == 0 and alloc.used_pages == 0


@pytest.mark.parametrize("prompt,chunks", [
    (29, [(0, 16), (16, 13)]),  # a full bucket, then a padded one
    (48, [(0, 16), (16, 16), (32, 16)]),  # the last ends on a page's end
    (7, [(0, 7)]),
], ids=["two-chunks", "three-full", "one-padded"])
def test_a_chunk_counts_the_pages_it_needs_against_its_table_row(
    prompt, chunks
):
    """Every chunk dispatch adds the pages its attention needs (its last
    query position // page_size + 1: a full bucket's last token, a padded
    bucket's tail clamped one past the prompt) and max_pages, the row a
    gather reads whole; a second request that hits the prefix cache starts
    its chunks after the pages it reuses."""
    from benchmarks.harness import manifest as M

    read = M.layer_reader("prefill_kv_read_share.tok")
    cfg = llama.CONFIGS["tiny"].replace(dtype=jnp.float32)
    params = llama.init_params(cfg, jax.random.key(0))
    page, bucket = 4, 16
    eng = Engine(cfg, params, EngineConfig(
        max_batch=2, max_seq_len=64, max_prefill_len=bucket, page_size=page))
    max_pages = 64 // page
    tokens = list(range(1, prompt + 1))
    eng.start()
    try:
        eng.generate(tokens, max_tokens=2, temperature=0.0)
        first = dict(eng.stats)
        eng.generate(tokens, max_tokens=2, temperature=0.0)
    finally:
        eng.stop()

    def pages(offset, n):
        return (offset + min(n, bucket - 1)) // page + 1

    assert first["prefill_kv_pages_table_sum"] == len(chunks) * max_pages
    want = sum(pages(*c) for c in chunks)
    assert first["prefill_kv_pages_read_sum"] == want
    run = {"counters": {"stats": first}, "rehearse": False}
    assert read(run) == pytest.approx(100.0 * want / (len(chunks) * max_pages))
    # a CPU rehearsal writes nothing under a `prefill_` name
    assert read(dict(run, rehearse=True)) is None
    # a program without the counters (the parent commit) reports nothing
    assert read({"counters": {"stats": {}}, "rehearse": False}) is None
    # the same prompt again: its whole pages before the last token are
    # reused, and the one chunk left starts after them
    reused = (prompt - 1) // page * page
    assert prompt - reused <= bucket
    st = eng.stats
    assert st["prefix_hit_tokens"] == reused
    assert (st["prefill_kv_pages_table_sum"]
            - first["prefill_kv_pages_table_sum"]) == max_pages
    assert (st["prefill_kv_pages_read_sum"]
            - first["prefill_kv_pages_read_sum"]
            ) == pages(reused, prompt - reused)
