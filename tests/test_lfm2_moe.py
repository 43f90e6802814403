"""models/lfm2_moe.py against the plain reference
(benchmarks/reference/lfm2_moe.py, which imports nothing of the program),
on seeded random weights at a small size: 16 layers `conv conv attention
conv` x 4 with the first two FFNs dense, 64 experts top-4 and no shared
one, 3 taps, chunks of 16, pages of 4, so prompts cross chunk boundaries
and the convolution's state is carried, refilled and reused.

Everything here runs in float32 with int8 weights (the precision the
benchmark's cell states, less bfloat16 rounding), so the tolerances are
those of float32 summation order, and a lower precision fails them."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import lfm2_moe as R
from family_harness import Family, plain, seeded_params, submit_all, table
from substratus_tpu.models import hybrid, registry
from substratus_tpu.models import lfm2_moe as M
from substratus_tpu.ops import kvcache
from substratus_tpu.serve.engine import Engine, EngineConfig

CFG = M.CONFIGS["tiny-lfm2-moe"].replace(dtype=jnp.float32)
CHUNK, PAGE = 16, 4
# float32 activations, exact int8 weights: the program and the reference
# differ by summation order alone (measured 5e-6 on logits of magnitude 3;
# the limit leaves a factor of five). w8a8 reads 2e-2, bfloat16 1e-2.
TOL = 3e-5
F = Family(M, CFG, chunk=CHUNK, page=PAGE)
prefill, decode, serve = F.prefill, F.decode, F.serve


def cfg_dict(cfg: M.Lfm2MoeConfig, **over):
    """The configuration as the benchmark's files spell it."""
    d = dict(
        hidden_size=cfg.dim, num_hidden_layers=cfg.n_layers,
        num_attention_heads=cfg.n_heads, num_key_value_heads=cfg.n_kv_heads,
        head_dim=cfg.head_dim, intermediate_size=cfg.hidden_dim,
        moe_intermediate_size=cfg.moe_hidden_dim, vocab_size=cfg.vocab_size,
        num_dense_layers=cfg.n_dense_layers, num_experts=cfg.held_experts[1],
        published={"num_experts": cfg.n_experts},
        layout={"experts_held": list(cfg.held_experts)},
        num_experts_per_tok=cfg.n_experts_per_token,
        conv_L_cache=cfg.conv_taps, layer_types=list(cfg.layer_types),
        rope_parameters={"rope_theta": cfg.rope_theta},
        norm_eps=cfg.norm_eps,
        routed_scaling_factor=cfg.routed_scaling_factor,
        norm_topk_prob=cfg.norm_topk_prob,
    )
    d.update(over)
    return d


@pytest.fixture(scope="module")
def params():
    return seeded_params(M, CFG)


@pytest.fixture(scope="module")
def tokens():
    return np.asarray(jax.random.randint(jax.random.key(1), (64,), 0,
                                         CFG.vocab_size))


def new_cache(cfg, slots=3, pages=64):
    return M.init_paged_cache(cfg, pages, PAGE, slots=slots)


def reference_logits(params, cfg, toks):
    return np.asarray(R.logits_at(plain(params), cfg_dict(cfg), list(toks),
                                  list(range(len(toks))), pad_to=8, block=16))


# -- (a) the forward pass, and chunks, pages and state against it ----------------

def test_forward_matches_the_reference(params, tokens):
    """The whole sequence at once, no cache: logits of every row."""
    ref = reference_logits(params, CFG, tokens[:40])
    got, _ = F.forward(params, jnp.asarray(tokens[:40])[None], CFG)
    assert np.abs(np.asarray(got[0]) - ref).max() < TOL
    assert np.std(ref) > 0.3  # the logits are not degenerate


@pytest.mark.parametrize("prompt_len", [1, 2, CHUNK - 1, CHUNK, CHUNK + 1,
                                        2 * CHUNK + 1])
def test_chunked_prefill_then_decode_matches_the_reference(
        params, tokens, prompt_len):
    """Prefill in chunks and then decoding through the pages and the
    convolution state, against the reference's one full forward pass:
    prompts of one and two tokens (a chunk that leaves one old row in the
    state), one short of a chunk, a chunk, one over, and over two."""
    n = prompt_len + 6
    ref = reference_logits(params, CFG, tokens[:n])
    bt, slot = table(3), 1
    got, cache = prefill(params, CFG, new_cache(CFG), tokens[:prompt_len],
                         slot, bt)
    assert np.abs(got - ref[:prompt_len]).max() < TOL
    for pos in range(prompt_len, n):
        row, cache, stats = decode(params, CFG, cache, tokens[pos], pos,
                                   slot, bt)
        assert np.abs(row - ref[pos]).max() < TOL, pos
    # one live slot, every expert held: all of its pairs land here, on four
    # experts a sparse layer
    k, sparse = CFG.n_experts_per_token, CFG.count(M.SPARSE)
    assert int(stats["moe_pairs_held"]) == int(stats["moe_pairs_all"]) \
        == int(stats["moe_experts_touched"]) == k * sparse
    assert int(stats["moe_expert_pairs_max"]) == 1


def test_decode_step_is_forward_for_one_token_a_slot(params, tokens):
    """The family's jitted decode_step (row i = slot i, cache donated)
    gives the logits of the same step through forward."""
    bt = table(3)
    _, cache = prefill(params, CFG, new_cache(CFG), tokens[:21], 0, bt)
    want, cache, _ = decode(params, CFG, cache, tokens[21], 21, 0, bt)
    _, cache = prefill(params, CFG, cache, tokens[:21], 0, bt)
    got, cache = M.decode_step(
        params, cache, jnp.asarray([tokens[21], 0, 0], jnp.int32),
        jnp.asarray([21, 0, 0], jnp.int32), CFG,
        jnp.asarray(np.where(np.arange(3)[:, None] == 0, bt, 0)))
    assert set(cache) == set(new_cache(CFG))
    assert np.abs(np.asarray(got[0]) - want).max() < TOL


@pytest.mark.parametrize("lower", ["w8a8", "bfloat16"])
def test_a_lower_precision_fails_the_tolerance(params, tokens, lower):
    """The control of (a): int8 activations, or bfloat16 ones, through the
    same path read over a hundred times the limit."""
    cfg = (CFG.replace(quant_activations=True) if lower == "w8a8"
           else CFG.replace(dtype=jnp.bfloat16))
    ref = reference_logits(params, CFG, tokens[:37])
    got, _ = prefill(params, cfg, new_cache(cfg), tokens[:37], 0, table(3))
    assert np.abs(got - ref).max() > 100 * TOL


# -- (b) the convolution state alone ---------------------------------------------

def _conv_call(state, slots, positions, valid, u, layer=0):
    out, ctx = kvcache.conv_read_and_update(
        jnp.asarray(state), jnp.int32(layer), jnp.asarray(slots, jnp.int32),
        jnp.asarray(positions, jnp.int32), jnp.asarray(valid),
        jnp.asarray(u))
    return np.asarray(out), np.asarray(ctx)


def test_a_position_below_zero_reads_zero_and_nothing_else_is_hidden():
    """A token at position p reads state only for taps p - 1, p - 2 >= 0:
    a slot's new occupant never sees the last one's rows, and nothing has
    to be zeroed at admission."""
    state = np.arange(2 * 3 * 2 * 4, dtype=np.float32).reshape(2, 3, 2, 4) + 1
    u = np.full((3, 1, 4), 100.0, np.float32)
    _, ctx = _conv_call(state, [0, 1, 2], [[0], [1], [5]], np.ones((3, 1), bool),
                        u, layer=1)
    assert (ctx[0, :2] == 0).all()  # position 0: both taps below zero
    assert (ctx[1, 0] == 0).all() and (ctx[1, 1] == state[1, 1, 1]).all()
    assert (ctx[2, :2] == state[1, 2]).all()  # position 5: both rows seen
    assert (ctx[:, 2] == 100).all()


def test_the_rows_kept_are_those_of_the_last_real_tokens():
    """A chunk's padded tail never enters the state; a chunk with one real
    token keeps one old row; an idle row (no real token) leaves its slot's
    state as it was, stale rows and all."""
    state = np.arange(1 * 4 * 2 * 2, dtype=np.float32).reshape(1, 4, 2, 2) + 1
    u = 100.0 + np.arange(4 * 5 * 2, dtype=np.float32).reshape(4, 5, 2)
    n_real = [5, 3, 1, 0]
    valid = np.arange(5)[None] < np.asarray(n_real)[:, None]
    positions = 7 + np.minimum(np.arange(5)[None], np.asarray(n_real)[:, None])
    out, _ = _conv_call(state, [3, 2, 1, 0], positions, valid, u)
    assert (out[0, 3] == u[0, 3:5]).all()  # a full chunk: its last two
    assert (out[0, 2] == u[1, 1:3]).all()  # three real tokens: rows 1, 2
    assert (out[0, 1, 0] == state[0, 1, 1]).all()  # one: the newer old row
    assert (out[0, 1, 1] == u[2, 0]).all()
    assert (out[0, 0] == state[0, 0]).all()  # idle: untouched
    # and at position 0 the kept old row is the last occupant's, unmasked:
    # the next token masks it again by its own position
    out, ctx = _conv_call(state, [1], [[0, 1, 1]], [[True, False, False]],
                          u[:1, :3])
    assert (ctx[0, :2] == 0).all() and (out[0, 1, 0] == state[0, 1, 1]).all()


def test_an_idle_row_leaves_state_and_pages_untouched(params, tokens):
    """A decode step in which slot 1 is live: slots 0 and 2 keep their
    convolution rows bit for bit, whatever filler their rows carried."""
    bt = table(3)
    _, cache = prefill(params, CFG, new_cache(CFG), tokens[:20], 0, bt)
    _, cache = prefill(params, CFG, cache, tokens[5:30], 2, bt)
    _, cache = prefill(params, CFG, cache, tokens[9:22], 1, bt)
    before = np.asarray(cache[kvcache.CONV_STATE])
    _, cache, _ = decode(params, CFG, cache, tokens[22], 13, 1, bt)
    after = np.asarray(cache[kvcache.CONV_STATE])
    assert np.array_equal(after[:, 0], before[:, 0])
    assert np.array_equal(after[:, 2], before[:, 2])
    assert not np.array_equal(after[:, 1], before[:, 1])


def test_a_chunk_at_an_offset_continues_from_the_rows_left(params, tokens):
    """The second chunk's logits depend on the first chunk's last two
    convolution inputs through the state alone: with the state wiped
    between the chunks they differ, carried they match the reference."""
    bt = table(2)
    ref = reference_logits(params, CFG, tokens[:24])
    _, cache = prefill(params, CFG, new_cache(CFG, 2), tokens[:16], 0, bt)
    wiped = {**cache, kvcache.CONV_STATE:
             jnp.zeros_like(cache[kvcache.CONV_STATE])}
    got, _ = prefill(params, CFG, cache, tokens[:24], 0, bt, start=16)
    assert np.abs(got - ref[16:]).max() < TOL
    lost, _ = prefill(params, CFG, wiped, tokens[:24], 0, bt, start=16)
    assert np.abs(lost - ref[16:]).max() > 1e-2


# -- (c) through the engine ------------------------------------------------------

def test_the_engine_serves_the_family_through_submit(params, tokens):
    """Engine.submit/start, chunked prefill, jit_decode, overlap: every
    served token is the reference's best at its position (float32: a gap
    above 1e-4 is a wrong token, not rounding), three requests in flight,
    one over two chunks, one of a single token."""
    prompts = [tokens[:37], tokens[3:26], tokens[40:41]]
    outs, eng = serve(params, prompts, 20)
    for p, ids in zip(prompts, outs):
        assert len(ids) == 20
        gaps = R.served_gaps(plain(params), cfg_dict(CFG), list(p), ids)
        assert gaps.max() < 1e-4
    st = eng.stats
    assert st["preemptions"] == 0 and st["prefix_hit_tokens"] == 0
    assert st["prefix_reuse_refused"] == 2  # off and counted: 37 and 23 tokens
    assert st["moe_pairs_held"] == st["moe_pairs_all"] > 0
    assert st["moe_decode_steps"] > 0
    sparse, held = CFG.count(M.SPARSE), CFG.held_experts[1]
    assert 0 < st["moe_decode_experts_touched"] <= (
        st["moe_decode_steps"] * sparse * held)
    # 3 + 2 + 1 chunks; those at an offset began from carried rows
    assert (st["conv_chunks_sum"], st["conv_chunks_resumed_sum"]) == (6, 3)
    # a family with no ring observes no window rows
    assert "window_rows_live_sum" not in st
    # the pool holds the attention layers alone, the state the others
    assert eng.cache["k"].shape[0] == CFG.count(M.ATTN) == 4
    assert eng.cache[kvcache.CONV_STATE].shape == (
        CFG.count(M.CONV), 3, CFG.conv_taps - 1, CFG.dim)


def test_the_engine_serves_the_same_tokens_from_a_packed_pool(
    tokens, monkeypatch
):
    """The published head width, 64, in bfloat16: the attention layers'
    pool stores two KV heads to a row of 128, and the engine serves from
    it, through chunks and decode steps beside the convolution rows, the
    tokens it serves from the pool stored a head a row (the layout until
    PR 35): the same bytes through the same gather on the CPU."""
    cfg = M.CONFIGS["tiny-lfm2-moe"].replace(head_dim=64)
    assert cfg.dtype == jnp.bfloat16
    p = seeded_params(M, cfg)
    prompts = [tokens[:37], tokens[3:26], tokens[40:41]]

    def run():
        eng = Engine(cfg, p, EngineConfig(
            max_batch=3, max_seq_len=96, max_prefill_len=CHUNK,
            page_size=PAGE), model=M)
        eng.start()
        outs = submit_all(eng, prompts, 12)
        eng.stop()
        assert eng.error is None
        return eng, outs

    eng, got = run()
    assert eng.cache["k"].shape[3:] == (1, 128)
    monkeypatch.setattr(kvcache, "kv_head_shards", lambda mesh: 3)
    eng, want = run()
    assert eng.cache["k"].shape[3:] == (2, 64)
    assert got == want and all(len(ids) == 12 for ids in got)


def test_a_slots_second_occupant_equals_a_fresh_engine(params, tokens):
    """One slot, two requests one after the other: the second is served
    what a fresh engine serves it, though the first left its rows in the
    slot's state and nothing was zeroed."""
    first, second = tokens[:30], tokens[33:52]
    eng = Engine(CFG, params, EngineConfig(
        max_batch=1, max_seq_len=96, max_prefill_len=CHUNK, page_size=PAGE),
        model=M)
    eng.start()
    submit_all(eng, [first], 12)
    stale = np.asarray(eng.cache[kvcache.CONV_STATE])
    assert np.abs(stale).max() > 0
    reused = submit_all(eng, [second], 12)
    eng.stop()
    assert eng.error is None
    fresh, _ = serve(params, [second], 12, max_batch=1)
    assert reused == fresh


def test_the_engine_preempts_and_resumes_token_exact(params, tokens):
    """A pool too small for three sequences: the engine preempts, prefills
    the victim again from position 0 over whatever its slot's state held,
    and serves the tokens of a roomy pool."""
    prompts = [tokens[:30], tokens[10:38], tokens[20:45]]
    roomy, _ = serve(params, prompts, 24)
    tight, eng = serve(params, prompts, 24, kv_pool_tokens=120)
    assert eng.stats["preemptions"] >= 1
    assert tight == roomy


def test_an_int8_cache_is_refused(params):
    with pytest.raises(ValueError, match="int8"):
        M.init_paged_cache(CFG, 8, PAGE, dtype=jnp.int8)
    with pytest.raises(ValueError, match="int8"):
        Engine(CFG, params, EngineConfig(kv_cache_dtype="int8"), model=M)


@pytest.mark.parametrize("what", ["role", "spec", "dense"])
def test_what_pages_alone_cannot_carry_is_refused(params, what):
    ec = {"role": EngineConfig(role="decode"),
          "spec": EngineConfig(spec_k=2),
          "dense": EngineConfig(kv_layout="dense")}[what]
    with pytest.raises(ValueError, match="per-slot state|dense"):
        Engine(CFG, params, ec, model=M)


def test_the_registry_knows_the_family():
    assert registry.module_for("lfm2_moe") is M
    assert registry.HF_MODEL_TYPES["lfm2_moe"] == "lfm2_moe"
    assert registry.config_class("lfm2_moe") is M.Lfm2MoeConfig
    assert registry.family_of(CFG) == "lfm2_moe"
    assert registry.find_named_config("tiny-lfm2-moe")[0] is M


# -- the stack's shape -----------------------------------------------------------

@pytest.mark.parametrize("layers,plan", [(16, (4, 4, 3)), (40, (4, 4, 9)),
                                         (8, (0, 8, 1))])
def test_layer_plan_scans_the_periods(layers, plan):
    cfg = M.Lfm2MoeConfig(n_layers=layers)
    assert cfg.layer_types[:4] == (M.CONV, M.CONV, M.ATTN, M.CONV)
    assert cfg.mlp_layer_types[:3] == (M.DENSE, M.DENSE, M.SPARSE)
    assert M.layer_plan(cfg) == plan


def test_layer_kinds_come_from_the_config(params, tokens):
    """Another pattern than the published one: attention first, one dense
    layer, a period of two, two taps more. Same program, same reference."""
    cfg = CFG.replace(
        n_layers=6, n_dense_layers=1, conv_taps=5,
        layer_types=(M.ATTN, M.CONV, M.ATTN, M.CONV, M.ATTN, M.CONV))
    assert M.layer_plan(cfg) == (2, 2, 2)
    p = seeded_params(M, cfg, 2)
    ref = reference_logits(p, cfg, tokens[:30])
    got, cache = prefill(p, cfg, new_cache(cfg), tokens[:27], 2, table(3))
    assert np.abs(got - ref[:27]).max() < TOL
    for pos in range(27, 30):
        row, cache, _ = decode(p, cfg, cache, tokens[pos], pos, 2, table(3))
        assert np.abs(row - ref[pos]).max() < TOL


# -- (d) the expert layer: whole, and as shares ----------------------------------

@pytest.mark.parametrize("seq", [12, 40], ids=["every", "grouped"])
def test_the_whole_layer_is_the_reference_and_four_shares_add_up_to_it(
        params, seq):
    """`held_experts` (0, 64) is the uncut reference's sparse layer, and
    the parts that four ranks of 16 experts each compute (each routing over
    all 64) add up to it; no shared expert is counted. Both ways of
    multiplying, each picked by the call's token count."""
    t = 2 * seq
    assert (t > hybrid.EVERY_AT_MOST) == (seq == 40)
    mp = params["moe"]
    mw = jax.tree.map(lambda a: a[0], plain(mp))
    h = jax.random.normal(jax.random.key(3), (2, seq, CFG.dim), jnp.float32)
    flat = h.reshape(t, CFG.dim)
    dims = R.model_dims(cfg_dict(CFG))
    with jax.default_matmul_precision("highest"):
        whole = np.asarray(R.routed_part(
            flat, mw, dims, CFG.routed_scaling_factor, True))
    valid = jnp.ones((2, seq), bool)
    y, stats = hybrid.moe(h, mp, jnp.int32(0), CFG, valid, M.qeinsum)
    assert np.abs(np.asarray(y).reshape(t, -1) - whole).max() < TOL
    assert int(stats["moe_pairs_held"]) == t * CFG.n_experts_per_token
    total, held = np.zeros_like(whole), 0
    for rank in range(4):
        cfg = CFG.replace(held_experts=(16 * rank, 16))
        share = dict(mp)
        for name in hybrid.EXPERT_LEAVES:
            share[name] = jax.tree.map(
                lambda a: a[:, 16 * rank:16 * rank + 16], mp[name])
        y, stats = hybrid.moe(h, share, jnp.int32(0), cfg, valid, M.qeinsum)
        total += np.asarray(y).reshape(t, -1)
        held += int(stats["moe_pairs_held"])
        assert int(stats["moe_pairs_all"]) == t * CFG.n_experts_per_token
    assert np.abs(total - whole).max() < TOL
    assert held == t * CFG.n_experts_per_token  # every pair landed once


def test_the_normaliser_carries_the_configurations_epsilon():
    """Scores so small that 1e-6 shows: the weights are s / (sum + 1e-6),
    in the program and in the reference; K-EXAONE's 1e-20 would give 1."""
    cfg = CFG.replace(n_experts=8, n_experts_per_token=2, held_experts=(0, 8))
    router = jnp.eye(CFG.dim, 8, dtype=jnp.float32)
    h = jnp.full((1, CFG.dim), -14.0).at[0, 0].set(-13.0)
    s = 1 / (1 + np.exp(np.array([13.0, 14.0])))
    idx, w = hybrid.route(h, router, jnp.zeros((8,)), cfg)
    assert sorted(np.asarray(idx[0])) == [0, 1]
    want = s / (s.sum() + 1e-6)
    np.testing.assert_allclose(np.sort(np.asarray(w[0]))[::-1], want,
                               rtol=1e-4)
    assert float(w.sum()) < 0.8  # and not 1: the epsilon is a third of the sum
    ref = np.asarray(R.route(h, router, jnp.zeros((8,)), 2, 1.0))
    np.testing.assert_allclose(ref[0, :2], want, rtol=1e-4)


def test_a_published_config_json_gives_the_named_preset():
    """load/hf.py reads `model_type: lfm2_moe`: the published keys of
    LFM2-24B-A2B are the named preset."""
    from types import SimpleNamespace

    from substratus_tpu.load import hf

    published = SimpleNamespace(
        model_type="lfm2_moe", vocab_size=65536, hidden_size=2048,
        num_hidden_layers=40, num_attention_heads=32, num_key_value_heads=8,
        intermediate_size=11776, moe_intermediate_size=1536,
        num_dense_layers=2, num_experts=64, num_experts_per_tok=4,
        routed_scaling_factor=1, norm_topk_prob=True, use_expert_bias=True,
        conv_L_cache=3, conv_bias=False,
        layer_types=["conv", "conv", "full_attention", "conv"] * 10,
        rope_parameters={"rope_theta": 1000000, "rope_type": "default"},
        norm_eps=1e-05, max_position_embeddings=128000)
    to_config, convert = hf._dispatch_hf("lfm2_moe")
    cfg = to_config(published)
    assert cfg == M.CONFIGS["lfm2-24b-a2b"]
    assert (cfg.count(M.CONV), cfg.count(M.ATTN), cfg.count(M.DENSE),
            cfg.count(M.SPARSE)) == (30, 10, 2, 38)
    with pytest.raises(NotImplementedError, match="converter"):
        convert({}, cfg)
