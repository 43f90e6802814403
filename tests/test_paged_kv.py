"""Paged KV cache: allocator/prefix-registry units + engine behavior.

The reference's serving images used per-request contiguous caches; the paged
engine bounds KV memory by actual tokens in flight (VERDICT r1 item 3).
These tests pin the three behaviors that matter: capacity beyond the dense
equivalent at fixed HBM, prefix-page sharing, and preempt-and-resume
correctness under pool pressure (greedy output must be identical with and
without pressure).
"""
import jax
import jax.numpy as jnp
import pytest

from substratus_tpu.models import llama
from substratus_tpu.serve.engine import Engine, EngineConfig, Request
from substratus_tpu.serve.paged_kv import (
    PageAllocator,
    PrefixRegistry,
    chain_entries,
)


def test_allocator_alloc_free_refcount():
    a = PageAllocator(4, first_page=1)
    pids = [a.alloc() for _ in range(4)]
    assert sorted(pids) == [1, 2, 3, 4]
    assert a.alloc() is None  # exhausted
    a.incref(pids[0])
    a.decref(pids[0])
    assert a.alloc() is None  # still held by the original ref
    a.decref(pids[0])
    assert a.alloc() == pids[0]  # freed and reused
    assert a.free_pages == 0
    assert a.used_pages == 4


def test_prefix_registry_match_and_lru_eviction():
    a = PageAllocator(8)
    reg = PrefixRegistry(a)
    e = chain_entries(list(range(48)), 16)  # 3 full pages
    pids = [a.alloc() for _ in range(3)]
    reg.register(e, pids)
    assert reg.match(e) == pids
    # A different prefix shares nothing even when later pages coincide.
    e2 = chain_entries([99] + list(range(1, 48)), 16)
    assert reg.match(e2) == []
    # LRU eviction drops the registry's ref; page frees once callers do.
    owner_free = a.free_pages
    assert reg.evict_lru()
    a.decref(pids[0])  # the original owner's ref
    assert a.free_pages == owner_free + 1


def test_chain_entries_commit_to_whole_prefix_and_verify_content():
    e1 = chain_entries([1, 2, 3, 4], 2)
    e2 = chain_entries([9, 9, 3, 4], 2)
    assert e1[1][0] != e2[1][0]  # same page-2 tokens, different prefix
    # match() verifies (parent, tokens), so even a forged equal hash with
    # different content is rejected.
    a = PageAllocator(4)
    reg = PrefixRegistry(a)
    pid = a.alloc()
    reg.register(e1[:1], [pid])
    forged = [(e1[0][0], e1[0][1], (7, 7))]
    assert reg.match(forged) == []


@pytest.fixture(scope="module")
def setup():
    cfg = llama.CONFIGS["tiny"].replace(vocab_size=258, dtype=jnp.float32)
    params = llama.init_params(cfg, jax.random.key(0))
    return cfg, params


def _run(engine, prompts, max_tokens=8):
    reqs = [
        engine.submit(Request(list(p), max_tokens=max_tokens))
        for p in prompts
    ]
    outs = []
    for r in reqs:
        toks = []
        while True:
            t = r.out.get(timeout=120)
            if t is None:
                break
            toks.append(t)
        outs.append(toks)
    return outs


def test_paged_fits_more_than_dense_at_fixed_hbm(setup):
    """Pool = 2 dense slots' worth of tokens, but 4 short requests board
    concurrently: batch is bounded by actual tokens, not slot reservation."""
    cfg, params = setup
    eng = Engine(
        cfg, params,
        EngineConfig(
            max_batch=4, max_seq_len=64, eos_token_id=257,
            kv_pool_tokens=128, page_size=16,
        ),
    )
    assert eng.paged and eng.n_pages == 8
    eng.start()
    try:
        outs = _run(eng, [[256, 10 + i, 20, 30] for i in range(4)])
        assert all(len(o) == 8 for o in outs)
        # All four boarded together even though dense layout would cap at 2.
        assert eng.stats["max_active"] >= 3
        assert eng.stats["preemptions"] == 0
    finally:
        eng.stop()
    assert eng.alloc.free_pages + len(eng.prefix) == eng.n_pages


def test_prefix_cache_shares_pages_and_skips_prefill(setup):
    cfg, params = setup
    eng = Engine(
        cfg, params,
        EngineConfig(
            max_batch=2, max_seq_len=64, eos_token_id=257, page_size=8,
            max_prefill_len=32,
        ),
    )
    eng.start()
    try:
        prompt = [256] + list(range(1, 40))  # 5 full pages of 8
        (out1,) = _run(eng, [prompt], max_tokens=6)
        prefill_after_first = eng.stats["prefill_tokens"]
        assert eng.stats["prefix_hit_tokens"] == 0
        (out2,) = _run(eng, [prompt], max_tokens=6)
        assert out2 == out1  # greedy determinism through shared pages
        assert eng.stats["prefix_hit_tokens"] == 32  # 4 shared pages
        # Second admission prefilled only the unshared remainder.
        assert (
            eng.stats["prefill_tokens"] - prefill_after_first
            == len(prompt) - 32
        )
    finally:
        eng.stop()


def test_preempt_and_resume_preserves_greedy_output(setup):
    """Two long generations against a pool that cannot hold both: the
    youngest gets preempted (pages freed, request re-boards, prefill
    reconstructs) and BOTH still produce exactly the unpressured output."""
    cfg, params = setup
    prompts = [[256, 5, 6, 7], [256, 8, 9, 10]]
    max_tokens = 40

    roomy = Engine(
        cfg, params,
        EngineConfig(max_batch=2, max_seq_len=64, eos_token_id=257,
                     page_size=8, prefix_cache=False),
    )
    roomy.start()
    try:
        want = _run(roomy, prompts, max_tokens=max_tokens)
    finally:
        roomy.stop()

    tight = Engine(
        cfg, params,
        EngineConfig(
            max_batch=2, max_seq_len=64, eos_token_id=257, page_size=8,
            kv_pool_tokens=72, prefix_cache=False,  # 9 pages < 2 full seqs
        ),
    )
    tight.start()
    try:
        got = _run(tight, prompts, max_tokens=max_tokens)
        assert tight.stats["preemptions"] >= 1
        assert got == want
    finally:
        tight.stop()


def test_pool_pages_all_recovered_after_load(setup):
    cfg, params = setup
    eng = Engine(
        cfg, params,
        EngineConfig(max_batch=4, max_seq_len=64, eos_token_id=257,
                     page_size=8, kv_pool_tokens=96),
    )
    eng.start()
    try:
        _run(eng, [[256, i, i + 1] for i in range(1, 9)], max_tokens=12)
    finally:
        eng.stop()
    # Every page is either free or held (once) by the prefix registry.
    held = sum(eng.alloc.refs(eng.prefix._map[h]) for h in eng.prefix._map)
    assert eng.alloc.free_pages + len(eng.prefix) == eng.n_pages
    assert held == len(eng.prefix)


@pytest.mark.parametrize("name,given,page", [
    ("tiny-deepseek-v3", None, 128), ("tiny-glm-dsa", None, 128),
    ("tiny-lfm2-moe", None, 64), ("tiny-granite-hybrid", None, 128),
    ("tiny", None, 16), ("tiny-exaone-moe", None, 16),
    ("tiny-brumby", None, 16),
    ("tiny-deepseek-v3", 16, 16), ("tiny-glm-dsa", 64, 64), ("tiny", 32, 32),
    ("tiny-lfm2-moe", 16, 16), ("tiny-granite-hybrid", 32, 32),
])
def test_a_page_is_the_familys_unless_one_is_given(name, given, page):
    """An engine given no `page_size` reads the page off the family's
    module: 128 tokens where one latent row a token serves every head (with
    and without an index), 64 and 128 where a stored row holds two heads of
    64 (LFM2; Granite-4.0-H, whose cell's contexts are six times as long),
    16 where the module states nothing; a size given wins. The pool, the block tables and the gauge all have it."""
    from substratus_tpu.models import registry
    from substratus_tpu.observability.metrics import METRICS
    from substratus_tpu.serve.paged_kv import page_tokens

    model, cfg = registry.find_named_config(name)
    eng = Engine(cfg, None, EngineConfig(
        max_batch=2, max_seq_len=128, max_prefill_len=16, page_size=given,
        kv_pool_tokens=1), model=model)
    assert eng.paged and eng.page_size == page
    assert page_tokens(model, given) == page
    assert eng.cache["k"].shape[2] == page
    assert eng.block_table.shape == (2, 128 // page)
    assert METRICS.get("substratus_serve_kv_page_tokens") == page
