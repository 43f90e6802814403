"""models/brumby.py against the plain reference
(benchmarks/reference/brumby.py: the attention form, which builds no state
and imports nothing of the program), on seeded random weights at a small
size: 4 layers, 4 query heads over 2 KV heads of 16 (phi is 136 wide),
chunks of 16, the gate's bias in [3, 7] so that the carried state matters
to every later token.

Everything here runs in float32 with int8 weights (the precision the
benchmark's cell states, less bfloat16 rounding), so the tolerances are
those of float32 summation order, and a lower precision fails them."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import brumby as R
from family_harness import Family, plain, seeded_params, submit_all
from substratus_tpu.models import brumby as M
from substratus_tpu.models import registry
from substratus_tpu.observability.metrics import METRICS
from substratus_tpu.ops import kvcache, retention
from substratus_tpu.serve.engine import Engine, EngineConfig

CFG = M.CONFIGS["tiny-brumby"].replace(dtype=jnp.float32)
CHUNK, PAGE, SLOTS = 16, 4, 3
# float32 activations, exact int8 weights: the program's recurrent and
# chunked forms and the reference's attention form differ by summation
# order alone (measured 9e-6 on logits of magnitude 3; the limit leaves a
# factor of five). w8a8 reads 2e-2, bfloat16 3e-2.
TOL = 5e-5
# The family reads no page: prefill and decode are handed no block table.
F = Family(M, CFG, chunk=CHUNK, page=PAGE, slots=SLOTS)
prefill, decode, serve = F.prefill, F.decode, F.serve


def cfg_dict(cfg: M.BrumbyConfig, **over):
    """The configuration as the benchmark's files spell it."""
    d = dict(
        hidden_size=cfg.dim, num_hidden_layers=cfg.n_layers,
        num_attention_heads=cfg.n_heads, num_key_value_heads=cfg.n_kv_heads,
        head_dim=cfg.head_dim, intermediate_size=cfg.hidden_dim,
        vocab_size=cfg.vocab_size, rope_theta=cfg.rope_theta,
        rms_norm_eps=cfg.norm_eps,
        max_position_embeddings=cfg.max_seq_len,
        assumed={"gate_shift": cfg.gate_shift},
    )
    d.update(over)
    return d


@pytest.fixture(scope="module")
def params():
    p = seeded_params(M, CFG)
    b = np.asarray(p["layers"]["b_gamma"])
    assert b.dtype == np.float32 and 3 <= b.min() and b.max() <= 7
    return p


@pytest.fixture(scope="module")
def tokens():
    return np.asarray(jax.random.randint(jax.random.key(1), (64,), 0,
                                         CFG.vocab_size))


def new_cache(cfg=CFG, slots=SLOTS):
    return M.init_paged_cache(cfg, 8, PAGE, slots=slots)


def reference_logits(params, cfg, toks):
    return np.asarray(R.logits_at(
        plain(params), cfg_dict(cfg), list(toks), list(range(len(toks))),
        pad_to=8, q_block=16, k_block=24))


# -- (a) the forward pass, and chunks and the state against it -------------------

def test_forward_matches_the_reference(params, tokens):
    """The whole sequence at once, no cache (the attention form in both):
    logits of every row."""
    ref = reference_logits(params, CFG, tokens[:40])
    got, kv = F.forward(params, jnp.asarray(tokens[:40])[None], CFG)
    assert kv == {}
    assert np.abs(np.asarray(got[0]) - ref).max() < TOL
    assert np.std(ref) > 0.3  # the logits are not degenerate


@pytest.mark.parametrize("prompt_len", [1, CHUNK - 1, CHUNK, CHUNK + 1,
                                        2 * CHUNK + 1])
def test_chunked_prefill_then_decode_matches_the_reference(
        params, tokens, prompt_len):
    """Prefill in chunks and then decoding through the state, against the
    reference's one full forward pass, which never builds a state: a
    prompt of one token, one short of a chunk, a chunk, one over, and over
    two. The slot's state was another occupant's."""
    n = prompt_len + 6
    ref = reference_logits(params, CFG, tokens[:n])
    slot = 1
    cache = jax.tree.map(lambda a: a + 3.0, new_cache())
    got, cache = prefill(params, CFG, cache, tokens[:prompt_len], slot)
    assert np.abs(got - ref[:prompt_len]).max() < TOL
    for pos in range(prompt_len, n):
        row, cache, _ = decode(params, CFG, cache, tokens[pos], pos, slot)
        assert np.abs(row - ref[pos]).max() < TOL, pos


def test_the_carry_is_what_a_later_token_reads(params, tokens):
    """With the gate near 1 the second chunk depends on the first through
    the state alone: wiped between the chunks its logits differ, carried
    they match the reference."""
    ref = reference_logits(params, CFG, tokens[:24])
    _, cache = prefill(params, CFG, new_cache(), tokens[:16], 0)
    wiped = jax.tree.map(jnp.zeros_like, cache)
    got, _ = prefill(params, CFG, cache, tokens[:24], 0, start=16)
    assert np.abs(got - ref[16:]).max() < TOL
    lost, _ = prefill(params, CFG, wiped, tokens[:24], 0, start=16)
    assert np.abs(lost - ref[16:]).max() > 1e-2


def test_decode_step_is_forward_for_one_token_a_slot(params, tokens):
    """The family's jitted decode_step (row i = slot i, cache donated)
    gives the logits of the same step through forward."""
    _, cache = prefill(params, CFG, new_cache(), tokens[:21], 0)
    want, cache, _ = decode(params, CFG, cache, tokens[21], 21, 0)
    _, cache = prefill(params, CFG, cache, tokens[:21], 0)
    got, cache = M.decode_step(
        params, cache, jnp.asarray([tokens[21], 0, 0], jnp.int32),
        jnp.asarray([21, 0, 0], jnp.int32), CFG)
    assert set(cache) == set(new_cache())
    assert np.abs(np.asarray(got[0]) - want).max() < TOL


@pytest.mark.parametrize("lower", ["w8a8", "bfloat16"])
def test_a_lower_precision_fails_the_tolerance(params, tokens, lower):
    """The control of (a): int8 activations, or bfloat16 ones, through the
    same path read over a hundred times the limit."""
    cfg = (CFG.replace(quant_activations=True) if lower == "w8a8"
           else CFG.replace(dtype=jnp.bfloat16))
    ref = reference_logits(params, CFG, tokens[:37])
    got, _ = prefill(params, cfg, new_cache(cfg), tokens[:37], 0)
    assert np.abs(got - ref).max() > 100 * TOL


def test_an_idle_row_and_a_padded_tail_leave_the_state(params, tokens):
    """A decode step in which slot 1 is live leaves slots 0 and 2 bit for
    bit as they were; a chunk's padded tail, whatever ids it carries,
    leaves the state its real tokens leave."""
    _, cache = prefill(params, CFG, new_cache(), tokens[:20], 0)
    _, cache = prefill(params, CFG, cache, tokens[5:30], 2)
    _, cache = prefill(params, CFG, cache, tokens[9:22], 1)
    before = {n: np.asarray(a) for n, a in cache.items()}
    _, cache, _ = decode(params, CFG, cache, tokens[22], 13, 1)
    for name in (kvcache.RET_S, kvcache.RET_Z):
        after = np.asarray(cache[name])
        assert np.array_equal(after[:, 0], before[name][:, 0])
        assert np.array_equal(after[:, 2], before[name][:, 2])
        assert not np.array_equal(after[:, 1], before[name][:, 1])

    def chunk_of_five(filler):
        padded = np.full((1, CHUNK), filler, np.int32)
        padded[0, :5] = tokens[:5]
        _, out = M.forward(
            params, jnp.asarray(padded), CFG,
            positions=jnp.minimum(jnp.arange(CHUNK), 5)[None],
            cache=new_cache(), slots=jnp.asarray([1]),
            valid=jnp.arange(CHUNK)[None] < 5)
        return out

    a, b = chunk_of_five(0), chunk_of_five(77)
    for name in (kvcache.RET_S, kvcache.RET_Z):
        assert np.array_equal(np.asarray(a[name]), np.asarray(b[name]))


# -- (b) through the engine ------------------------------------------------------

def test_the_engine_serves_the_family_through_submit(params, tokens):
    """Engine.submit/start, chunked prefill, jit_decode, overlap: every
    served token is the reference's best at its position (float32: a gap
    above 1e-4 is a wrong token, not rounding), three requests in flight,
    one over two chunks, one of a single token. The engine starts with a
    pool of no layers, counts no page read, and says what state it holds."""
    prompts = [tokens[:37], tokens[3:26], tokens[40:41]]
    outs, eng = serve(params, prompts, 20)
    for p, ids in zip(prompts, outs):
        assert len(ids) == 20
        gaps = R.served_gaps(plain(params), cfg_dict(CFG), list(p), ids)
        assert gaps.max() < 1e-4
    st = eng.stats
    assert st["preemptions"] == 0 and st["prefix_hit_tokens"] == 0
    assert st["prefix_reuse_refused"] == 2  # off and counted: 37 and 23 tokens
    # no expert layer: nothing of the moe counters is seeded or observed
    assert not [k for k in st if k.startswith("moe_")]
    # no convolution rows and no ring: neither's counters are seeded
    assert "conv_chunks_sum" not in st and "window_rows_live_sum" not in st
    # pages: a pool of no layers, the table counted, nothing read
    assert eng.cache["k"].shape == (0, eng.n_pages + 1, PAGE, 2, 16)
    assert eng.cache["k"].nbytes == eng.cache["v"].nbytes == 0
    assert st["decode_kv_pages_table_sum"] > 0
    assert st["prefill_kv_pages_table_sum"] > 0
    assert st["decode_kv_pages_read_sum"] == 0
    assert st["prefill_kv_pages_read_sum"] == 0
    # the state: float32 whatever the activations, a slot and layer
    f = retention.width(CFG.head_dim)
    assert eng.cache[kvcache.RET_S].shape == (4, SLOTS, 2, f, 16)
    assert eng.cache[kvcache.RET_Z].shape == (4, SLOTS, 2, f)
    assert eng.cache[kvcache.RET_S].dtype == jnp.float32
    assert METRICS.get("substratus_serve_slot_state_bytes") == (
        4 * SLOTS * 2 * f * 17 * 4)
    # slots decoding of the rows a step moves
    assert 0 < st["state_rows_live_sum"] <= st["state_rows_sum"]
    assert st["state_rows_sum"] % SLOTS == 0
    # values of 16 lanes: the step is ops/retention.py's, not the kernel's
    assert st["state_kernel_steps"] == 0


def test_the_kernel_path_serves_the_tokens_of_the_step_path(
        monkeypatch, pallas_interpret):
    """Heads of 128 (the width ops/retention_kernel.py is written for; two
    layers, one KV head under two query heads, two slots): the same seed
    served twice, through XLA's step as the CPU takes it and through the
    kernel, interpreted, as a TPU would. The tokens are equal, the decode
    program called the kernel, and `state_kernel_steps` counts the
    decoding iterations of the second engine and none of the first."""
    cfg = M.BrumbyConfig(
        vocab_size=256, dim=64, n_layers=2, n_heads=2, n_kv_heads=1,
        head_dim=128, hidden_dim=128, max_seq_len=64, dtype=jnp.float32)
    wide = seeded_params(M, cfg, 2)
    toks = np.asarray(jax.random.randint(jax.random.key(4), (40,), 0, 256))
    prompts = [toks[:21], toks[25:34]]

    def served():
        eng = Engine(cfg, wide, EngineConfig(
            max_batch=2, max_seq_len=64, max_prefill_len=CHUNK,
            page_size=PAGE), model=M)
        assert eng.cache[kvcache.RET_S].shape == (2, 2, 1, 8256, 128)
        eng.start()
        outs = submit_all(eng, prompts, 10)
        eng.stop()
        assert eng.error is None
        return outs, eng.stats

    in_xla, st = served()
    assert st["state_kernel_steps"] == 0 < st["state_rows_sum"]
    calls = []
    monkeypatch.setattr(
        kvcache.retention_kernel, "step",
        lambda *a, _k=kvcache.retention_kernel.step, **kw:
        calls.append(1) or _k(*a, **kw))
    monkeypatch.setattr(jax.lax, "platform_dependent",
                        lambda *args, tpu, default: tpu(*args))
    # what the engine asks of a state its TPU holds
    monkeypatch.setattr(kvcache, "retention_step_takes_kernel",
                        lambda s: kvcache._retention_kernel_for(s) is not None)
    jax.clear_caches()
    in_kernel, st = served()
    jax.clear_caches()  # no later test meets a program traced here
    assert calls and in_kernel == in_xla
    assert all(len(ids) == 10 for ids in in_kernel)
    assert st["state_kernel_steps"] * 2 == st["state_rows_sum"] > 0


def test_the_state_stays_float32_under_bfloat16_activations(params):
    cache = M.init_paged_cache(CFG.replace(dtype=jnp.bfloat16), 8, PAGE,
                               slots=2)
    assert cache["k"].dtype == jnp.bfloat16 and cache["k"].shape[0] == 0
    assert cache[kvcache.RET_S].dtype == cache[kvcache.RET_Z].dtype \
        == jnp.float32


def test_a_slots_second_occupant_equals_a_fresh_engine(params, tokens):
    """One slot, two requests one after the other: the second is served
    what a fresh engine serves it, though the first left its state in the
    slot and nothing was zeroed."""
    first, second = tokens[:30], tokens[33:52]
    eng = Engine(CFG, params, EngineConfig(
        max_batch=1, max_seq_len=96, max_prefill_len=CHUNK, page_size=PAGE),
        model=M)
    eng.start()
    submit_all(eng, [first], 12)
    stale = np.asarray(eng.cache[kvcache.RET_S])
    assert np.abs(stale).max() > 0
    reused = submit_all(eng, [second], 12)
    eng.stop()
    assert eng.error is None
    fresh, _ = serve(params, [second], 12, max_batch=1)
    assert reused == fresh


def test_the_engine_preempts_and_resumes_token_exact(params, tokens):
    """A page allocator too small for three sequences (its ids are handed
    out though no layer reads a page): the engine preempts, prefills the
    victim again from position 0 over whatever its slot's state held, and
    serves the tokens of a roomy one. No snapshot is taken."""
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


@pytest.mark.parametrize("what", ["role", "spec", "dense", "lora"])
def test_what_a_state_cannot_carry_is_refused(params, what):
    ec = {"role": EngineConfig(role="decode"),
          "spec": EngineConfig(spec_k=2),
          "dense": EngineConfig(kv_layout="dense"),
          "lora": EngineConfig()}[what]
    with pytest.raises(ValueError, match="per-slot state|dense|adapters"):
        Engine(CFG, params, ec, model=M,
               adapters=object() if what == "lora" else None)


def test_the_registry_knows_the_family():
    assert registry.module_for("brumby") is M
    assert registry.HF_MODEL_TYPES["brumby"] == "brumby"
    assert registry.config_class("brumby") is M.BrumbyConfig
    assert registry.family_of(CFG) == "brumby"
    assert registry.find_named_config("tiny-brumby")[0] is M
    with pytest.raises(ValueError, match="head of its own"):
        M.BrumbyConfig(tie_embeddings=True)


def test_the_gate_shift_moves_the_gate_and_nothing_else(params, tokens):
    """`gate_shift` is added to the gate's pre-activation: a tree whose
    bias was drawn around zero, served with the shift, is the tree with
    the shift in its bias."""
    p = jax.tree.map(lambda a: a, params)
    layers = dict(p["layers"])
    layers["b_gamma"] = layers["b_gamma"] - 5.0
    moved = {**p, "layers": layers}
    toks = jnp.asarray(tokens[:24])[None]
    want, _ = F.forward(params, toks, CFG)
    got, _ = F.forward(moved, toks, CFG.replace(gate_shift=5.0))
    assert np.abs(np.asarray(got - want)).max() < TOL
    ref = np.asarray(R.logits_at(
        plain(moved), cfg_dict(CFG.replace(gate_shift=5.0)),
        list(tokens[:24]), list(range(24)), pad_to=8, q_block=16, k_block=24))
    assert np.abs(np.asarray(got[0]) - ref).max() < TOL


def test_a_published_config_json_gives_the_named_preset():
    """load/hf.py reads `model_type: brumby`: the published keys of
    Brumby-14B-Base are the named preset."""
    from types import SimpleNamespace

    from substratus_tpu.load import hf

    published = SimpleNamespace(
        model_type="brumby", attention_bias=False, head_dim=128,
        hidden_act="silu", hidden_size=5120, intermediate_size=17408,
        max_position_embeddings=32768, num_attention_heads=40,
        num_hidden_layers=40, num_key_value_heads=8, rms_norm_eps=1e-06,
        rope_scaling=None, rope_theta=1000000, sliding_window=None,
        tie_word_embeddings=False, use_sliding_window=False,
        vocab_size=151936)
    to_config, convert = hf._dispatch_hf("brumby")
    cfg = to_config(published)
    assert cfg == M.CONFIGS["brumby-14b-base"]
    assert retention.width(cfg.head_dim) == 8256
    with pytest.raises(NotImplementedError, match="converter"):
        convert({}, cfg)
    published.attention_bias = True
    with pytest.raises(NotImplementedError, match="attention_bias"):
        to_config(published)
