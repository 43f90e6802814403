"""The traffic generator's properties: the same work in every seed, paced
arrivals, exact output lengths, lateness recorded, closed-loop hand-off."""
import threading
import time
from collections import Counter

import numpy as np
import pytest

from benchmarks.harness import manifest as M
from benchmarks.harness import traffic as T

MIXES = ["longdoc", "chat", "batch-tp4"]
SEEDS = [0, 1, 2, 3, 5, 8, 13, 21, 2**31 + 7, 3_000_000_000, 99, 12345]


@pytest.fixture(params=MIXES)
def mix(request):
    return M.traffic_of(request.param)


@pytest.mark.parametrize("seed", SEEDS)
def test_same_length_multiset_in_every_seed(mix, seed):
    n = 4 * int(mix["block"])
    base, other = T.plan(mix, 0, n), T.plan(mix, seed, n)
    for key in ("prompt_len", "output_len"):
        assert Counter(getattr(p, key) for p in base) == Counter(
            getattr(p, key) for p in other)
    # every block holds the same multiset, so any window sees the same work
    b = int(mix["block"])
    for k in range(4):
        blk = other[k * b:(k + 1) * b]
        assert Counter(p.prompt_len for p in blk) == Counter(
            p.prompt_len for p in base[:b])
        assert Counter(p.output_len for p in blk) == Counter(
            p.output_len for p in base[:b])


def test_seed_chooses_the_order_within_each_block(mix):
    n = int(mix["block"])
    pairs = sorted(T.block_pairs(mix))
    orders = set()
    for seed in SEEDS:
        plan = T.plan(mix, seed, 3 * n)
        got = [(p.prompt_len, p.output_len) for p in plan]
        blocks = [got[k * n:(k + 1) * n] for k in range(3)]
        for blk in blocks:  # the same pairs in every block of every seed
            assert sorted(blk) == pairs
        if n > 3:  # each block has an order of its own
            assert blocks[0] != blocks[1] or blocks[1] != blocks[2]
        orders.add(tuple(blocks[0]))
        assert T.plan(mix, seed, 3 * n) == plan  # same seed, same plan
    assert len(orders) > len(SEEDS) // 2  # seeds do order the work differently


def test_block_pairs_the_two_lengths_independently(mix):
    pairs = T.block_pairs(mix)
    assert len(pairs) == int(mix["block"])
    assert sorted(p for p, _ in pairs) == sorted(
        T.quantile_lengths(mix["prompt_len"], len(pairs)).tolist())
    assert sorted(o for _, o in pairs) == sorted(
        T.quantile_lengths(mix["output_len"], len(pairs)).tolist())
    # no monotone relation between a prompt's length and its reply's
    outs = [o for _, o in sorted(pairs)]
    assert outs != sorted(outs) and outs != sorted(outs, reverse=True)


def test_plan_grows_by_blocks_without_changing(mix):
    b = int(mix["block"])
    assert T.plan(mix, 7, 3 * b)[: 2 * b] == T.plan(mix, 7, 2 * b)


def test_lengths_within_the_mix_and_on_its_grid(mix):
    for key in ("prompt_len", "output_len"):
        d = mix[key]
        xs = T.quantile_lengths(d, int(mix["block"]))
        assert xs.min() >= d["lo"] and xs.max() <= d["hi"]
        assert all(x % d["grid"] == 0 for x in xs)
    med = np.median(T.quantile_lengths(mix["prompt_len"], 1000))
    lo, hi = mix["prompt_len"]["lo"], mix["prompt_len"]["hi"]
    assert abs(med - (lo * hi) ** 0.5) <= 0.05 * med  # log-uniform median


@pytest.mark.parametrize("seed", SEEDS[:4])
def test_open_loop_due_times_on_the_jittered_grid(seed):
    mix = M.traffic_of("chat")
    rate = mix["rate_rps"]
    planned = T.plan(mix, seed, 4 * mix["block"])
    for p in planned:
        assert p.index / rate <= p.due_s < (p.index + 1) / rate
        assert p.client == -1
    due = [p.due_s for p in planned]
    assert due == sorted(due)


def test_closed_loop_clients_round_robin():
    mix = M.traffic_of("longdoc")
    c = mix["clients"]
    planned = T.plan(mix, 3, c * mix["block"])
    assert all(p.due_s is None for p in planned)
    assert [p.client for p in planned[:c]] == list(range(c))
    per_client = Counter(p.client for p in planned)
    assert set(per_client.values()) == {mix["block"]}


@pytest.mark.parametrize("lengths,chunk,want", [
    ([64, 80, 1024], 512, [64, 128, 512]),
    ([2048, 2304], 512, [256, 512]),
    ([17], 512, [32]),
    ([600], 512, [128, 512]),
    ([512], 512, [512]),
])
def test_prefill_buckets(lengths, chunk, want):
    assert T.prefill_buckets(lengths, chunk) == want


def test_prompt_tokens_seeded_and_below_vocab():
    a = T.prompt_tokens(2**31 + 5, 3, 500, 32000)
    assert a == T.prompt_tokens(2**31 + 5, 3, 500, 32000)
    assert a != T.prompt_tokens(2**31 + 5, 4, 500, 32000)
    assert 0 <= min(a) and max(a) < 32000 and len(a) == 500


def test_requests_decode_exactly_their_length():
    pytest.importorskip("substratus_tpu")
    from benchmarks.harness import system

    sink = T.Sink()
    req = system.new_request([1, 2, 3], 17, sink, "r0")
    assert req.max_tokens == 17 and req.eos_token_id == -1
    assert req.temperature == 0.0 and req.out is sink


def _build(planned):
    recs = []
    for p in planned:
        r = T.Record(planned=p, prompt=[], sink=T.Sink())
        r.request = r
        recs.append(r)
    return recs


def _records(mix, seed, n):
    return _build(T.plan(mix, seed, n))


def test_open_loop_generator_records_lateness():
    mix = dict(M.traffic_of("chat"), rate_rps=200.0, block=10)
    recs = _records(mix, 4, 20)
    seen = []
    gen = T.Generator(mix=mix, records=recs, submit_fn=seen.append,
                      build_block=lambda b: _build(T.plan_block(mix, 4, b)))
    gen.start()
    deadline = time.perf_counter() + 5
    while len(seen) < 20 and time.perf_counter() < deadline:
        time.sleep(0.005)
    gen.stop()
    assert not gen._thread.is_alive() and gen.error is None
    assert len(seen) >= 20
    for r in recs[:20]:
        assert r.submit is not None and r.due is not None
        assert r.due == pytest.approx(gen.t0 + r.planned.due_s)
        assert 0.0 <= r.submit - r.due < 0.5  # never early; lateness recorded


def test_closed_loop_hands_the_next_request_on_completion():
    mix = dict(M.traffic_of("longdoc"), clients=3, block=6, stagger_s=0.0)
    recs = _records(mix, 4, 6)
    built = []

    def build_block(b):
        built.append(b)
        return _build(T.plan_block(mix, 4, b))

    lock = threading.Lock()
    order = []

    def submit(r):
        with lock:
            order.append(r)
        # the system answers at once: one token, then the terminal None
        r.sink.put(5)
        r.sink.put(None)

    gen = T.Generator(mix=mix, records=recs, submit_fn=submit,
                      build_block=build_block)
    gen.start()
    deadline = time.perf_counter() + 5
    while len(order) < 12 and time.perf_counter() < deadline:
        time.sleep(0.005)
    gen.stop()
    assert not gen._thread.is_alive() and gen.error is None
    assert len(order) >= 12 and built  # the plan grew by blocks on demand
    by_client = {}
    for r in order:
        by_client.setdefault(r.planned.client, []).append(r)
    for c, rs in by_client.items():
        idx = [r.planned.index for r in rs]
        assert idx == sorted(idx)  # a client's order is fixed by the seed
        for prev, nxt in zip(rs, rs[1:]):
            assert nxt.due == prev.sink.done_ts  # due when the reply ended
            assert nxt.submit >= nxt.due


def test_sink_stamps_tokens_and_end_once():
    s = T.Sink()
    s.put(3)
    s.put(4)
    s.put(None)
    first_end = s.done_ts
    s.put(None)
    assert s.ids == [3, 4] and len(s.ts) == 2 and s.ts[0] <= s.ts[1]
    assert s.done_ts == first_end
