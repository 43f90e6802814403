"""Fleet telemetry plane (ISSUE 11): step timeline + bubble
attribution, SLO sketches, and the gateway fleet aggregator.

Tier-1 coverage promised by the issue:

  * timeline recorder bounds + Chrome-trace JSON shape;
  * bubble-cause accounting under a forced flush (pool-pressure
    preemption) and a forced host overrun (slow emit sink);
  * fleet aggregator EWMA smoothing, stale/out-of-order drops, and
    eviction of dead replicas;
  * sketch merge correctness vs exact percentiles;
  * `/debug/stepz` + `/debug/fleetz` RBAC + payload;
  * LoadReport `sq=`/`ts=` wire keys (legacy headers keep parsing).
"""
import asyncio
import threading
import time

import numpy as np
import pytest

from substratus_tpu.gateway.fleet import FleetAggregator
from substratus_tpu.gateway.loadreport import LoadReport
from substratus_tpu.observability.metrics import METRICS
from substratus_tpu.observability.sketch import SLOTracker, Sketch
from substratus_tpu.observability.timeline import (
    BUBBLE_CAUSES,
    StepTimeline,
)


# -- sketches ---------------------------------------------------------------


def test_sketch_quantiles_vs_exact():
    """Sketch quantiles must land inside the bucket holding the exact
    percentile — the bounded-error contract a fixed-bucket sketch
    makes (anything tighter would be an accident of interpolation)."""
    rng = np.random.default_rng(3)
    samples = rng.gamma(2.0, 0.05, 4000)  # latency-shaped
    sk = Sketch()
    for v in samples:
        sk.observe(float(v))
    bounds = (0.0,) + sk.bounds
    for q in (0.5, 0.9, 0.99):
        exact = float(np.quantile(samples, q))
        got = sk.quantile(q)
        # Bucket bracketing the exact percentile.
        hi = next(b for b in sk.bounds if exact <= b)
        lo = max(b for b in bounds if b < hi)
        assert lo <= got <= hi, (q, exact, got, lo, hi)


def test_sketch_merge_is_exact():
    """merge(A, B) must equal the sketch of the union sample set —
    counts, sum, and every quantile."""
    rng = np.random.default_rng(7)
    a, b = rng.exponential(0.02, 500), rng.exponential(0.3, 700)
    s1, s2, union = Sketch(), Sketch(), Sketch()
    for v in a:
        s1.observe(float(v))
        union.observe(float(v))
    for v in b:
        s2.observe(float(v))
        union.observe(float(v))
    s1.merge(s2)
    assert s1.to_dict() == union.to_dict()
    for q in (0.1, 0.5, 0.9, 0.99):
        assert s1.quantile(q) == union.quantile(q)


def test_sketch_dict_roundtrip_and_garbage():
    sk = Sketch(bounds=(0.1, 1.0))
    sk.observe(0.05)
    sk.observe(5.0)  # +Inf bucket
    rt = Sketch.from_dict(sk.to_dict())
    assert rt.to_dict() == sk.to_dict()
    assert rt.quantile(0.5) == sk.quantile(0.5)
    for bad in (
        {},  # no bounds
        {"bounds": [0.1], "counts": [1]},  # counts too short
        {"bounds": [0.1], "counts": [1, -2]},  # negative count
        {"bounds": [0.1], "counts": [1, True]},  # bool masquerading
    ):
        with pytest.raises(ValueError):
            Sketch.from_dict(bad)


def test_sketch_merge_bounds_mismatch_raises():
    with pytest.raises(ValueError):
        Sketch(bounds=(0.1, 1.0)).merge(Sketch(bounds=(0.2, 1.0)))


def test_slo_tracker_burns_only_over_threshold():
    before = METRICS.get("substratus_slo_burn_total", {"slo": "ttft"}) or 0
    slo = SLOTracker({"ttft": 1.0, "inter_token": 0.1})
    slo.observe("ttft", 0.5)  # under: no burn
    slo.observe("ttft", 1.5)  # over: burns
    slo.observe("ttft", 3.0)  # over: burns
    slo.observe("inter_token", 0.05)
    slo.observe("unknown_slo", 99.0)  # typo must not crash or count
    assert slo.burn("ttft") == 2
    assert slo.burn("inter_token") == 0
    snap = slo.snapshot()
    assert snap["ttft"]["burn"] == 2
    assert snap["ttft"]["threshold_s"] == 1.0
    assert snap["ttft"]["sketch"]["count"] == 3
    after = METRICS.get("substratus_slo_burn_total", {"slo": "ttft"})
    assert after == before + 2


# -- timeline ---------------------------------------------------------------


def _iter(tl, seq_t, wall, **kw):
    return tl.record_iteration(t_start=seq_t, wall_s=wall, **kw)


def _timeline(floor_s=0.01, **kw):
    """A timeline whose first iteration (seq 1, no gap) is the floor the
    later ones are measured from."""
    tl = StepTimeline(**kw)
    _iter(tl, -floor_s, floor_s)
    return tl


def test_timeline_ring_bounded_but_totals_lifetime():
    tl = _timeline(capacity=8)
    for i in range(20):
        _iter(tl, 0.02 * i, 0.02, dispatch_s=0.001, drain_s=0.005)
    recs = tl.records()
    assert len(recs) == 8  # ring bound
    assert recs[-1]["seq"] == 21  # numbering never resets
    tot = tl.bubble_totals()
    assert tot["iterations"] == 21  # lifetime, not ring-bounded
    assert tot["gap_s"] == pytest.approx(20 * 0.01, rel=1e-6)


def test_timeline_attribution_order_and_unattributed():
    tl = _timeline()
    # flush first, then pool_dry admission, remainder to host_overrun.
    r = _iter(
        tl, 0.0, 0.05, admit_s=0.01, admitted=0, pool_dry=True,
        dispatch_s=0.002, drain_s=0.02, flush_s=0.008,
        flush_reasons=["preempt"],
    )
    assert r["gap_s"] == pytest.approx(0.04)
    assert r["bubble"]["flush"] == pytest.approx(0.008)
    assert r["bubble"]["pool_dry"] == pytest.approx(0.01)
    assert r["bubble"]["host_overrun"] == pytest.approx(0.022)
    assert r["unattributed_s"] == 0.0
    # Admission checks on an empty queue (admitted=0, not pool-dry)
    # never bill admission_stall; with no host work either, the gap
    # stays visibly unattributed instead of being misfiled.
    r2 = _iter(tl, 0.1, 0.03, admit_s=0.02, admitted=0)
    assert r2["bubble"] == {}
    assert r2["unattributed_s"] == pytest.approx(0.02)
    tot = tl.bubble_totals()
    assert tot["unattributed_s"] == pytest.approx(0.02)
    assert set(tot["by_cause"]) == set(BUBBLE_CAUSES)


def test_timeline_floor_self_calibrates():
    tl = StepTimeline()
    _iter(tl, 0.0, 0.010, drain_s=0.001)
    _iter(tl, 0.1, 0.012, drain_s=0.001)
    r = _iter(tl, 0.2, 0.030, drain_s=0.02)
    # Floor = min recent wall (0.010): production bubbles measure
    # against the best the hardware recently did.
    assert r["floor_s"] == pytest.approx(0.010)
    assert r["gap_s"] == pytest.approx(0.020)
    assert tl.floor_estimate() == pytest.approx(0.010)


def test_timeline_chrome_trace_shape():
    tl = _timeline()
    _iter(tl, 0.0, 0.02, admit_s=0.003, admitted=1, dispatch_s=0.001,
          drain_s=0.004, drain_off_s=0.002, flush_s=0.002,
          flush_reasons=["spec"], active_slots=3, max_slots=4)
    doc = tl.chrome_trace()
    events = doc["traceEvents"]
    assert isinstance(events, list) and events
    names = [e["name"] for e in events]
    assert "iteration" in names and "admit" in names
    assert "drain" in names and "flush:spec" in names
    for e in events:
        assert "ph" in e and "pid" in e
        if e["ph"] == "X":
            assert e["dur"] >= 0 and "ts" in e and "tid" in e
    it = next(e for e in events
              if e["name"] == "iteration" and e["args"]["seq"] == 2)
    assert it["args"]["occupancy"] == 0.75
    assert it["args"]["bubble"]
    assert doc["otherData"]["iterations_recorded"] == 2


# -- fleet aggregator -------------------------------------------------------


def _report(seq=-1, q=0, active=0, slots=4, kvf=1.0, tq=0, wall_ts=0.0,
            role="both"):
    return LoadReport(
        queue_depth=q, active_slots=active, max_slots=slots,
        kv_free_frac=kvf, transfer_queue=tq, seq=seq, wall_ts=wall_ts,
        role=role,
    )


def test_fleet_ewma_smooths_toward_new_value():
    fa = FleetAggregator(halflife_s=10.0)
    assert fa.record("http://r0", _report(seq=1, q=0), now=0.0)
    assert fa.record("http://r0", _report(seq=2, q=10), now=10.0)
    sig = fa.signals(now=10.0)
    (rep,) = sig.replicas
    # One halflife elapsed: EWMA is halfway between old and new.
    assert rep.queue_depth == pytest.approx(5.0, rel=0.01)
    assert rep.samples == 2 and rep.seq == 2
    snap = fa.snapshot(now=10.0)
    assert len(snap["replicas"]["http://r0"]["series"]) == 2


def test_fleet_drops_out_of_order_and_stale_keeps_legacy():
    fa = FleetAggregator(stale_s=30.0)
    drops = (
        METRICS.get("substratus_fleet_reports_dropped_total",
                    {"reason": "out_of_order"}) or 0,
        METRICS.get("substratus_fleet_reports_dropped_total",
                    {"reason": "stale"}) or 0,
    )
    assert fa.record("http://r0", _report(seq=5, q=7), now=0.0)
    # A hedged retry delivering an OLDER report after the newer one.
    assert not fa.record("http://r0", _report(seq=4, q=0), now=1.0)
    assert not fa.record("http://r0", _report(seq=5, q=0), now=1.0)
    # Grossly stale wall clock (a delayed retransmit).
    assert not fa.record(
        "http://r0", _report(seq=6, wall_ts=time.time() - 3600), now=2.0
    )
    # Fresh wall clock + newer seq: accepted.
    assert fa.record(
        "http://r0", _report(seq=6, q=3, wall_ts=time.time()), now=3.0
    )
    # Legacy replicas (no sq=) are always accepted.
    assert fa.record("http://r0", _report(), now=4.0)
    sig = fa.signals(now=4.0)
    assert sig.replicas[0].samples == 3
    assert (
        METRICS.get("substratus_fleet_reports_dropped_total",
                    {"reason": "out_of_order"}) == drops[0] + 2
    )
    assert (
        METRICS.get("substratus_fleet_reports_dropped_total",
                    {"reason": "stale"}) == drops[1] + 1
    )


def test_fleet_accepts_restarted_replica_with_reset_seq():
    """A pod restart resets the replica's report counter; its wall
    clock keeps moving. The seq regression must read as a new counter
    epoch (accepted), NOT as a stale delivery — otherwise a restarted
    replica's reports are dropped forever and the balancer routes on
    its pre-crash snapshot (the chaos test's recovery phase)."""
    fa = FleetAggregator()
    t0 = time.time()
    assert fa.record("http://r0", _report(seq=50, q=9, wall_ts=t0),
                     now=0.0)
    # Stale echo of an old report (older seq AND older clock): dropped.
    assert not fa.record(
        "http://r0", _report(seq=49, q=0, wall_ts=t0 - 5.0), now=1.0
    )
    # Restarted process: seq resets to 1 but the clock moved forward.
    assert fa.record(
        "http://r0", _report(seq=1, q=0, wall_ts=t0 + 2.0), now=2.0
    )
    sig = fa.signals(now=2.0)
    assert sig.replicas[0].seq == 1  # new epoch latched
    # And the new epoch orders normally from here.
    assert not fa.record(
        "http://r0", _report(seq=1, q=0, wall_ts=t0 + 2.0), now=3.0
    )
    assert fa.record(
        "http://r0", _report(seq=2, q=0, wall_ts=t0 + 3.0), now=4.0
    )


def test_fleet_evicts_dead_replicas_and_their_gauges():
    fa = FleetAggregator(evict_s=60.0)
    fa.record("http://dead", _report(seq=1, q=2), now=0.0)
    fa.record("http://live", _report(seq=1, q=1), now=50.0)
    assert METRICS.get(
        "substratus_fleet_queue_depth", {"replica": "http://dead"}
    ) is not None
    sig = fa.signals(now=100.0)  # dead last seen 100s ago > evict_s
    assert [r.url for r in sig.replicas] == ["http://live"]
    # The gauge series must go with it: a scrape must not keep
    # reporting a scaled-down replica's last load as current.
    assert METRICS.get(
        "substratus_fleet_queue_depth", {"replica": "http://dead"}
    ) is None
    assert METRICS.get(
        "substratus_fleet_queue_depth", {"replica": "http://live"}
    ) is not None


def test_fleet_signals_rollup_semantics():
    fa = FleetAggregator()
    fa.record("http://p0", _report(seq=1, q=4, active=4, slots=4,
                                   kvf=0.2, tq=3, role="prefill"), now=0.0)
    fa.record("http://d0", _report(seq=1, q=2, active=2, slots=4,
                                   kvf=0.8, role="decode"), now=0.0)
    fa.record_shed("http://p0", now=0.0)
    sig = fa.signals(now=0.0)
    assert sig.queue_depth == pytest.approx(6.0)  # SUM
    assert sig.occupancy == pytest.approx(0.75)  # MEAN of 1.0 and 0.5
    assert sig.kv_free_frac == pytest.approx(0.2)  # MIN
    assert sig.transfer_queue == pytest.approx(3.0)  # SUM
    assert sig.shed_rate > 0.0
    assert sig.roles == {"prefill": 1, "decode": 1}


def test_fleet_merges_slo_sketches_across_replicas():
    fa = FleetAggregator()
    slo_a = SLOTracker({"ttft": 1.0})
    slo_b = SLOTracker({"ttft": 1.0})
    for v in (0.2, 0.4, 2.0):
        slo_a.observe("ttft", v)
    for v in (0.3, 3.0):
        slo_b.observe("ttft", v)
    fa.record("http://a", _report(seq=1), now=0.0,
              snapshot={"slo": slo_a.snapshot()})
    fa.record("http://b", _report(seq=1), now=0.0,
              snapshot={"slo": slo_b.snapshot()})
    merged = fa.merged_slo()
    assert merged["ttft"]["count"] == 5
    assert merged["ttft"]["burn"] == 2  # 2.0 and 3.0 burned
    assert merged["ttft"]["p50_s"] is not None
    # A garbled sketch payload is skipped, never poisons the merge.
    fa.record("http://c", _report(seq=1), now=0.0,
              snapshot={"slo": {"ttft": {"sketch": {"bounds": "x"}}}})
    assert fa.merged_slo()["ttft"]["count"] == 5


# -- load-report wire keys --------------------------------------------------


def test_loadreport_seq_ts_header_roundtrip():
    rep = LoadReport(queue_depth=1, seq=42, wall_ts=1234.5678)
    h = rep.to_header()
    assert " sq=42" in h and " ts=1234.568" in h
    rt = LoadReport.from_header(h)
    assert rt.seq == 42
    assert rt.wall_ts == pytest.approx(1234.568)
    # Legacy header (pre-telemetry replica): absent keys = sentinel
    # values, report accepted everywhere.
    legacy = LoadReport.from_header("q=3 a=2 m=8 kvf=0.75")
    assert legacy.seq == -1 and legacy.wall_ts == 0.0
    # Default-constructed reports never emit the keys (byte-identical
    # wire format for everything that existed before ISSUE 11).
    assert "sq=" not in LoadReport(queue_depth=3).to_header()


def test_loadreport_from_snapshot_carries_seq_and_slo_ignored():
    snap = {"queue_depth": 2, "active_slots": 1, "max_slots": 4,
            "kv_free_frac": 0.5, "load_seq": 7, "load_ts": 99.5,
            "slo": {"ttft": {}}}
    rep = LoadReport.from_snapshot(snap)
    assert rep.seq == 7 and rep.wall_ts == 99.5


# -- engine-level bubble accounting (jax) -----------------------------------


def _tiny_engine(**kw):
    import jax
    import jax.numpy as jnp

    from substratus_tpu.models import llama
    from substratus_tpu.serve.engine import Engine, EngineConfig

    cfg = llama.CONFIGS["tiny"].replace(vocab_size=258, dtype=jnp.float32)
    params = llama.init_params(cfg, jax.random.key(0))
    kw.setdefault("max_batch", 4)
    kw.setdefault("max_seq_len", 64)
    kw.setdefault("eos_token_id", 257)
    eng = Engine(cfg, params, EngineConfig(**kw))
    eng.start()
    return eng


class _SlowSink:
    """Request sink whose put() burns host time on the scheduler
    thread — the forced host overrun."""

    def __init__(self, sleep_s):
        import queue as _q

        self.sleep_s = sleep_s
        self.q = _q.Queue()

    def put(self, item, block=True, timeout=None):
        if item is not None:
            time.sleep(self.sleep_s)
        self.q.put(item)

    def get(self, block=True, timeout=None):
        return self.q.get(block, timeout)


def test_engine_bubble_host_overrun_under_forced_slow_emit():
    """Per-token host work far over the device window: the timeline
    must attribute the (inter-token − floor) gap to host_overrun, and
    the attribution must cover >90% of the measured gap (the ISSUE 11
    acceptance shape, compressed)."""
    from substratus_tpu.serve.engine import Request

    eng = _tiny_engine()
    try:
        # Warm, and give the timeline its floor: the fastest of these
        # plain decode iterations is what the slow ones are measured from.
        eng.generate([1, 2, 3], max_tokens=8, temperature=0.0)
        sink = _SlowSink(sleep_s=0.03)  # far over a tiny step, every emit
        req = eng.submit(Request([5, 6, 7], max_tokens=10,
                                 temperature=0.0, out=sink))
        while req.out.get(timeout=120) is not None:
            pass
        steady = [r for r in eng.timeline.records()
                  if not r["admitted"] and r["active_slots"]]
        assert steady, "no steady-state iterations recorded"
        over = sum(r["bubble"].get("host_overrun", 0.0) for r in steady)
        gap = sum(r["gap_s"] for r in steady)
        assert gap > 0.0
        assert over / gap > 0.9, (over, gap)
        # ~30ms of forced host work per decode iteration must be seen.
        slow_iters = [r for r in steady
                      if r["bubble"].get("host_overrun", 0.0) > 0.015]
        assert slow_iters, steady
        # The counter mirror (whole-process, so >= this engine's share).
        assert (METRICS.get("substratus_serve_pipeline_bubble_seconds",
                            {"cause": "host_overrun"}) or 0) > 0
    finally:
        eng.stop()


def test_engine_bubble_flush_under_forced_preemption():
    """Pool pressure mid-decode (the test_overlap preemption recipe):
    the overlapped engine flushes before preempting, and the timeline
    must bill that flush's drain as a 'flush' bubble with the preempt
    reason on the record."""
    eng = _tiny_engine(
        kv_layout="paged", page_size=4, kv_pool_tokens=48,
        max_seq_len=48, prefix_cache=False, overlap=True,
    )
    try:
        prompts = [[256] + [11 * (i + 1), 13 * (i + 1)] for i in range(3)]
        outs = [None] * len(prompts)

        def one(i):
            outs[i] = eng.generate(list(prompts[i]), max_tokens=16,
                                   temperature=0.0)

        ts = [threading.Thread(target=one, args=(i,)) for i in range(3)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert eng.stats["preemptions"] >= 1, eng.stats
        recs = eng.timeline.records()
        flushed = [r for r in recs if "preempt" in r["flush_reasons"]]
        assert flushed, "no iteration recorded the preempt flush"
        assert any(r["bubble"].get("flush", 0.0) > 0.0 for r in flushed)
        # pool_dry admissions (held for pages) mark their iterations.
        assert eng.timeline.bubble_totals()["by_cause"]["flush"] > 0.0
    finally:
        eng.stop()


# -- debug endpoints: RBAC + payload ----------------------------------------


class _DenyAll:
    def allow(self, authorization):
        if authorization == "Bearer good":
            return 200, "ok"
        return 403, "nope"


def test_stepz_payload_and_rbac():
    """/debug/stepz serves Chrome-trace JSON behind the same RBAC gate
    as the rest of the debug plane."""
    from aiohttp import web

    from substratus_tpu.gateway.testing import build_tiny_engine
    from substratus_tpu.serve.server import ServerState, build_app
    from substratus_tpu.serve.tokenizer import ByteTokenizer

    engine = build_tiny_engine()
    engine.generate([1, 2, 3], max_tokens=4, temperature=0.0)

    async def go():
        import aiohttp

        state = ServerState(engine, ByteTokenizer(), "tiny",
                            authorizer=_DenyAll())
        runner = web.AppRunner(build_app(state))
        await runner.setup()
        site = web.TCPSite(runner, "127.0.0.1", 0)
        await site.start()
        port = site._server.sockets[0].getsockname()[1]
        url = f"http://127.0.0.1:{port}/debug/stepz"
        try:
            async with aiohttp.ClientSession() as s:
                async with s.get(url) as r:
                    assert r.status == 403  # gated
                async with s.get(
                    url, headers={"Authorization": "Bearer good"}
                ) as r:
                    assert r.status == 200
                    doc = await r.json()
        finally:
            await runner.cleanup()
        events = doc["traceEvents"]
        assert any(e["name"] == "iteration" for e in events)
        other = doc["otherData"]
        assert other["bubble"]["iterations"] > 0
        assert "floor_estimate_s" in other

    try:
        asyncio.run(asyncio.wait_for(go(), timeout=120))
    finally:
        engine.stop()


def test_fleetz_payload_and_rbac_via_routed_replicas():
    """The acceptance shape: a routed 2-replica run must surface BOTH
    replicas on /debug/fleetz with non-empty EWMA series and a fleet
    rollup; with an authorizer configured the endpoint is gated."""
    import aiohttp
    from aiohttp import web

    from substratus_tpu.gateway.router import Gateway, build_gateway_app
    from substratus_tpu.gateway.testing import GatewayHarness

    async def go():
        h = await GatewayHarness(n_replicas=2).start()
        try:
            async with aiohttp.ClientSession() as s:
                for i in range(4):
                    async with s.post(
                        h.url + "/v1/completions",
                        json={"prompt": f"p{i}", "max_tokens": 3,
                              "temperature": 0.0},
                    ) as r:
                        assert r.status == 200
                await asyncio.sleep(0.6)  # a poll cycle for the sketches
                async with s.get(h.url + "/debug/fleetz") as r:
                    assert r.status == 200  # no authorizer = open
                    fz = await r.json()
            urls = {rep.url for rep in h.replicas}
            assert set(fz["replicas"]) == urls
            for row in fz["replicas"].values():
                assert row["series"]
                assert row["seq"] >= 1
                assert set(row["ewma"]) >= {
                    "queue_depth", "occupancy", "kv_free_frac",
                    "transfer_queue", "shed_rate",
                }
            assert fz["fleet"]["replicas"] == 2
            assert fz["fleet"]["slo"]["ttft"]["count"] > 0
        finally:
            await h.stop()

        # RBAC: a gateway with an authorizer gates the endpoint.
        gw = Gateway(["http://127.0.0.1:1"], authorizer=_DenyAll())
        runner = web.AppRunner(build_gateway_app(gw))
        await runner.setup()
        site = web.TCPSite(runner, "127.0.0.1", 0)
        await site.start()
        port = site._server.sockets[0].getsockname()[1]
        try:
            async with aiohttp.ClientSession() as s:
                async with s.get(
                    f"http://127.0.0.1:{port}/debug/fleetz"
                ) as r:
                    assert r.status == 403
                async with s.get(
                    f"http://127.0.0.1:{port}/debug/fleetz",
                    headers={"Authorization": "Bearer good"},
                ) as r:
                    assert r.status == 200
        finally:
            await runner.cleanup()

    asyncio.run(asyncio.wait_for(go(), timeout=300))
