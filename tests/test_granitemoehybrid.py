"""models/granitemoehybrid.py against the plain reference
(benchmarks/reference/granitemoehybrid.py: the recurrence as a scan over
tokens, which shares nothing with the chunked form or the kernel and
imports nothing of the program), on seeded random weights at a small size:
two periods `m m a m`, 4 mixer heads of 16 over a state of 8, 4 / 2
attention heads of 16, chunks of 16, `dt` and `A` drawn as Mamba-2
initialises them so that the carried state matters to every later token.

Everything here runs in float32 with int8 weights (the precision the
benchmark's cell states, less bfloat16 rounding), so the tolerances are
those of float32 summation order, and a lower precision fails them."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import granitemoehybrid as R
from family_harness import Family, plain, seeded_params, submit_all
from substratus_tpu.models import granitemoehybrid as M
from substratus_tpu.models import registry
from substratus_tpu.observability.metrics import METRICS
from substratus_tpu.ops import kvcache, ssd, ssd_kernel
from substratus_tpu.serve.engine import Engine, EngineConfig

CFG = M.CONFIGS["tiny-granite-hybrid"].replace(dtype=jnp.float32)
CHUNK, PAGE, SLOTS = 16, 4, 3
# float32 activations, exact int8 weights: the program's recurrent and
# chunked forms and the reference's scan differ by summation order alone
# (measured 3e-7 on logits of magnitude 0.4; the limit is ISSUE 46's). w8a8
# reads 3e-3, bfloat16 5e-3.
TOL = 1e-5
F = Family(M, CFG, chunk=CHUNK, page=PAGE, slots=SLOTS)
prefill, decode, serve = F.prefill, F.decode, F.serve


def cfg_dict(cfg: M.GraniteHybridConfig, **over):
    """The configuration as the benchmark's files spell it."""
    d = dict(
        hidden_size=cfg.dim, num_hidden_layers=cfg.n_layers,
        num_attention_heads=cfg.n_heads, num_key_value_heads=cfg.n_kv_heads,
        shared_intermediate_size=cfg.hidden_dim, vocab_size=cfg.vocab_size,
        layer_types=list(cfg.layer_types), mamba_n_heads=cfg.mamba_n_heads,
        mamba_d_head=cfg.mamba_d_head, mamba_d_state=cfg.mamba_d_state,
        mamba_d_conv=cfg.mamba_d_conv, mamba_n_groups=cfg.mamba_n_groups,
        mamba_expand=cfg.inner // cfg.dim, mamba_chunk_size=256,
        num_local_experts=0,
        embedding_multiplier=cfg.embedding_multiplier,
        residual_multiplier=cfg.residual_multiplier,
        attention_multiplier=cfg.attention_multiplier,
        logits_scaling=cfg.logits_scaling, rms_norm_eps=cfg.norm_eps,
        max_position_embeddings=cfg.max_seq_len, tie_word_embeddings=True,
        assumed={"dt_shift": cfg.dt_shift},
    )
    d.update(over)
    return d


@pytest.fixture(scope="module")
def params():
    p = seeded_params(M, CFG)
    decay = np.exp(-np.exp(np.asarray(p["ssm"]["a_log"])) * 0.01)
    assert p["ssm"]["a_log"].dtype == jnp.float32 and decay.min() > 0.8
    return p


@pytest.fixture(scope="module")
def tokens():
    return np.asarray(jax.random.randint(jax.random.key(1), (64,), 0,
                                         CFG.vocab_size))


@pytest.fixture(scope="module")
def bt():
    from family_harness import table
    return table(SLOTS)


def new_cache(cfg=CFG, slots=SLOTS):
    return M.init_paged_cache(cfg, 1 + SLOTS * 16, PAGE, slots=slots)


def reference_logits(params, cfg, toks):
    return np.asarray(R.logits_at(
        plain(params), cfg_dict(cfg), list(toks), list(range(len(toks))),
        pad_to=8, block=16))


# -- (a) the forward pass, and chunks and the state against it -------------------

def test_forward_matches_the_reference(params, tokens):
    """The whole sequence at once, no cache (the chunked form from a zero
    state against the reference's scan over tokens): logits of every row,
    over a length that is no multiple of the scan's block."""
    ref = reference_logits(params, CFG, tokens[:40])
    got, kv = F.forward(params, jnp.asarray(tokens[:40])[None], CFG)
    assert kv == {}
    assert np.abs(np.asarray(got[0]) - ref).max() < TOL
    assert np.std(ref) > 0.1  # the logits are not degenerate


def test_forward_in_bfloat16_is_the_reference_to_its_rounding(params, tokens):
    """bfloat16 activations through the same path: 8 layers of bfloat16
    matmul inputs and residual adds on logits that spread by 0.12 read
    about 5e-3 (eight bits of mantissa: 2 ** -8 of values near 1, summed
    over a few layers); 2e-2 holds that and fails a wrong scale or a lost
    branch, which read above 5e-2."""
    ref = reference_logits(params, CFG, tokens[:40])
    cfg = CFG.replace(dtype=jnp.bfloat16)
    got, _ = F.forward(params, jnp.asarray(tokens[:40])[None], cfg)
    err = np.abs(np.asarray(got[0]) - ref).max()
    assert 100 * TOL < err < 2e-2, err


@pytest.mark.parametrize("prompt_len", [1, CHUNK - 1, CHUNK + 1,
                                        2 * CHUNK + 5])
def test_chunked_prefill_then_decode_matches_the_reference(
        params, tokens, bt, prompt_len):
    """Prefill in chunks that do not divide the prompt and then decoding
    through pages, rows and state, against the reference's one full forward
    pass, which builds its state a token at a time and keeps no cache. The
    slot's rows and state were another occupant's."""
    n = prompt_len + 5
    ref = reference_logits(params, CFG, tokens[:n])
    slot = 1
    cache = jax.tree.map(lambda a: a + 3.0, new_cache())
    got, cache = prefill(params, CFG, cache, tokens[:prompt_len], slot, bt)
    assert np.abs(got - ref[:prompt_len]).max() < TOL
    for pos in range(prompt_len, n):
        row, cache, _ = decode(params, CFG, cache, tokens[pos], pos, slot, bt)
        assert np.abs(row - ref[pos]).max() < TOL, pos


def test_the_carry_is_what_a_later_token_reads(params, tokens, bt):
    """The second chunk depends on the first through the state and the
    rows: with either wiped between the chunks its logits differ, carried
    they match the reference."""
    ref = reference_logits(params, CFG, tokens[:24])
    _, cache = prefill(params, CFG, new_cache(), tokens[:16], 0, bt)
    got, _ = prefill(params, CFG, cache, tokens[:24], 0, bt, start=16)
    assert np.abs(got - ref[16:]).max() < TOL
    for name in (kvcache.SSM_STATE, kvcache.CONV_STATE):
        wiped = {**cache, name: jnp.zeros_like(cache[name])}
        lost, _ = prefill(params, CFG, wiped, tokens[:24], 0, bt, start=16)
        assert np.abs(lost - ref[16:]).max() > 1e-3, name


def test_the_chunked_form_is_the_recurrent_step_from_a_carried_state():
    """ops/ssd.py alone: 37 tokens through `chunk` in blocks of 16 from a
    carried state equal 37 calls of `step` from the same state, outputs and
    final state; a fresh row starts from zero in both; a token with dt = 0
    moves nothing."""
    k = iter(jax.random.split(jax.random.key(3), 8))
    b_, t, h, p, n = 2, 37, 4, 16, 8
    x = jax.random.normal(next(k), (b_, t, h, p))
    b = jax.random.normal(next(k), (b_, t, n))
    c = jax.random.normal(next(k), (b_, t, n))
    dt = jax.nn.softplus(jax.random.normal(next(k), (b_, t, h)) - 3.0)
    dt = dt.at[:, 20].set(0.0)
    a_log = jnp.log(jax.random.uniform(next(k), (h,), minval=1.0, maxval=8.0))
    d_skip = jax.random.normal(next(k), (h,))
    s0 = jax.random.normal(next(k), (b_, n, h * p))
    fresh = jnp.asarray([False, True])
    s_c, o_c = ssd.chunk(s0, x, b, c, dt, a_log, d_skip, fresh, block=16)
    s, outs = s0, []
    for i in range(t):
        s, o = ssd.step(s, x[:, i], b[:, i], c[:, i], dt[:, i], a_log,
                        d_skip, fresh & (i == 0))
        if i == 20:
            assert np.array_equal(np.asarray(s), before)
        before = np.asarray(s)
        outs.append(o)
    assert np.abs(np.asarray(o_c) - np.stack(outs, 1)).max() < 1e-4
    assert np.abs(np.asarray(s_c) - np.asarray(s)).max() < 1e-4


def test_the_kernel_is_the_step(pallas_interpret):
    """ops/ssd_kernel.py, interpreted, against ops/ssd.py::step over layer
    1 of a stack of three: the layer's state and the read-out equal, the
    other layers untouched, a fresh row from zero whatever the slot held
    (an infinity among it), an idle row bit for bit."""
    k = iter(jax.random.split(jax.random.key(5), 8))
    slots, h, p, n = 4, 8, 32, 128
    stack = jax.random.normal(next(k), (3, slots, n, h * p))
    stack = stack.at[1, 2, 0, 0].set(jnp.inf)
    x = jax.random.normal(next(k), (slots, h, p)).astype(jnp.bfloat16)
    b = jax.random.normal(next(k), (slots, n)).astype(jnp.bfloat16)
    c = jax.random.normal(next(k), (slots, n)).astype(jnp.bfloat16)
    dt = jax.nn.softplus(jax.random.normal(next(k), (slots, h)) - 3.0)
    dt = dt.at[3].set(0.0)  # an idle row
    a_log = jnp.log(jax.random.uniform(next(k), (h,), minval=1.0, maxval=8.0))
    d_skip = jax.random.normal(next(k), (h,))
    fresh = jnp.asarray([False, False, True, False])
    want_s, want_o = ssd.step(stack[1], x, b, c, dt, a_log, d_skip, fresh)
    got, o = ssd_kernel.step(stack, jnp.int32(1), x, b, c, dt, a_log, d_skip,
                             fresh, interpret=True)
    assert np.array_equal(np.asarray(got[0]), np.asarray(stack[0]))
    assert np.array_equal(np.asarray(got[2]), np.asarray(stack[2]))
    assert np.array_equal(np.asarray(got[1, 3]), np.asarray(stack[1, 3]))
    assert np.isfinite(np.asarray(got[1])).all()
    assert np.abs(np.asarray(got[1]) - np.asarray(want_s)).max() < 1e-5
    assert np.abs(np.asarray(o) - np.asarray(want_o)).max() < 1e-3
    assert kvcache._ssm_kernel_for(stack) is ssd_kernel.step
    assert kvcache._ssm_kernel_for(stack[:, :, :, :64]) is None


def test_decode_step_is_forward_for_one_token_a_slot(params, tokens, bt):
    """The family's jitted decode_step (row i = slot i, cache donated)
    gives the logits of the same step through forward."""
    _, cache = prefill(params, CFG, new_cache(), tokens[:21], 0, bt)
    want, cache, _ = decode(params, CFG, cache, tokens[21], 21, 0, bt)
    _, cache = prefill(params, CFG, cache, tokens[:21], 0, bt)
    table = np.where(np.arange(SLOTS)[:, None] == 0, bt, 0)
    got, cache = M.decode_step(
        params, cache, jnp.asarray([tokens[21], 0, 0], jnp.int32),
        jnp.asarray([21, 0, 0], jnp.int32), CFG, jnp.asarray(table))
    assert set(cache) == set(new_cache())
    assert np.abs(np.asarray(got[0]) - want).max() < TOL


@pytest.mark.parametrize("lower", ["w8a8", "bfloat16"])
def test_a_lower_precision_fails_the_tolerance(params, tokens, bt, lower):
    """The control of (a): int8 activations, or bfloat16 ones, through the
    same path read over a hundred times the limit."""
    cfg = (CFG.replace(quant_activations=True) if lower == "w8a8"
           else CFG.replace(dtype=jnp.bfloat16))
    ref = reference_logits(params, CFG, tokens[:37])
    got, _ = prefill(params, cfg, new_cache(cfg), tokens[:37], 0, bt)
    assert np.abs(got - ref).max() > 100 * TOL


def test_an_idle_row_and_a_padded_tail_leave_state_and_rows(
        params, tokens, bt):
    """A decode step in which slot 1 is live leaves the state and the rows
    of slots 0 and 2 bit for bit as they were; a chunk's padded tail,
    whatever ids it carries, leaves what its real tokens leave."""
    _, cache = prefill(params, CFG, new_cache(), tokens[:20], 0, bt)
    _, cache = prefill(params, CFG, cache, tokens[5:30], 2, bt)
    _, cache = prefill(params, CFG, cache, tokens[9:22], 1, bt)
    before = {n: np.asarray(a) for n, a in cache.items()}
    _, cache, _ = decode(params, CFG, cache, tokens[22], 13, 1, bt)
    for name in (kvcache.SSM_STATE, kvcache.CONV_STATE):
        after = np.asarray(cache[name])
        assert np.array_equal(after[:, 0], before[name][:, 0])
        assert np.array_equal(after[:, 2], before[name][:, 2])
        assert not np.array_equal(after[:, 1], before[name][:, 1])

    def chunk_of_five(filler):
        padded = np.full((1, CHUNK), filler, np.int32)
        padded[0, :5] = tokens[:5]
        _, out = F.forward(
            params, jnp.asarray(padded), CFG,
            positions=jnp.minimum(jnp.arange(CHUNK), 5)[None],
            cache=new_cache(), block_table=jnp.asarray(bt[1:2]),
            slots=jnp.asarray([1]), valid=jnp.arange(CHUNK)[None] < 5)
        return out

    a, b = chunk_of_five(0), chunk_of_five(77)
    for name in (kvcache.SSM_STATE, kvcache.CONV_STATE):
        assert np.array_equal(np.asarray(a[name]), np.asarray(b[name]))


# what a decode step's rows stand for, a row of four slots: position, real
_ROW_CASES = {
    "position-0-over-stale-rows": ([0, 0, 0, 0], [1, 1, 1, 1]),
    "position-1": ([1, 1, 1, 1], [1, 1, 1, 1]),
    "position-2": ([2, 2, 2, 2], [1, 1, 1, 1]),
    "positions-3-and-up": ([3, 4, 17, 400], [1, 1, 1, 1]),
    "idle-rows-beside-live-ones": ([0, 9, 2, 0], [1, 0, 1, 0]),
}


@pytest.mark.parametrize("keep", [2, 3])
@pytest.mark.parametrize("case", sorted(_ROW_CASES))
def test_a_decode_steps_slab_form_is_the_general_form_bit_for_bit(case, keep):
    """ops/kvcache.py::conv_rows_read_and_update over every slot with one
    token a row (`slots` None: the layer's slab shifted where it lies)
    returns, bit for bit, the stack and the context of the general form
    (`slots` = arange: LFM2's conv_read_and_update a call's rows at a time,
    which is what a chunk takes and what a decode step took until PR 47),
    whatever the rows stand for and for two rows a slot as for Granite's
    three. The stack holds non-zero rows everywhere, as a slot's last
    occupant leaves them: a context row that would stand for a position
    below 0 reads zero (the mask is on the read), the rows kept are the
    general form's, stale ones among them, and a row whose token is not
    real keeps its rows as they were."""
    d, layers = 256, 3
    positions, real = (np.asarray(a) for a in _ROW_CASES[case])
    rows = len(positions)
    k_state, k_u = jax.random.split(jax.random.key(keep))
    state = jax.random.normal(
        k_state, (layers, rows, keep * d), jnp.float32).astype(jnp.bfloat16)
    assert bool(jnp.all(state != 0))
    u = jax.random.normal(k_u, (rows, 1, d), jnp.float32)
    args = (jnp.asarray(positions, jnp.int32)[:, None],
            jnp.asarray(real, bool)[:, None], u)
    layer = jnp.asarray(1, jnp.int32)
    slab, slab_ctx = kvcache.conv_rows_read_and_update(
        state, layer, None, *args)
    general, general_ctx = kvcache.conv_rows_read_and_update(
        state, layer, jnp.arange(rows, dtype=jnp.int32), *args)
    assert slab.dtype == general.dtype == state.dtype
    assert slab_ctx.shape == general_ctx.shape == (rows, keep + 1, d)
    assert slab_ctx.dtype == general_ctx.dtype == state.dtype
    assert np.array_equal(np.asarray(slab), np.asarray(general))
    assert np.array_equal(np.asarray(slab_ctx), np.asarray(general_ctx))
    # and what the contract says of them, on the slab form's own returns
    before = np.asarray(state.astype(jnp.float32))
    after = np.asarray(slab.astype(jnp.float32))
    ctx = np.asarray(slab_ctx.astype(jnp.float32))
    new = np.asarray(u[:, 0].astype(jnp.bfloat16).astype(jnp.float32))
    assert np.array_equal(after[[0, 2]], before[[0, 2]])  # other layers
    for i, (pos, live) in enumerate(zip(positions, real)):
        for r in range(keep):
            seg = before[1, i, r * d:(r + 1) * d]
            assert np.array_equal(
                ctx[i, r], seg if pos - keep + r >= 0 else np.zeros(d))
        assert np.array_equal(ctx[i, keep], new[i])
        kept = (np.concatenate([before[1, i, d:], new[i]]) if live
                else before[1, i])
        assert np.array_equal(after[1, i], kept)


# -- (b) what makes the block Granite's ------------------------------------------

@pytest.mark.parametrize("name, plain_value", [
    ("attention_multiplier", CFG.head_dim ** -0.5),
    ("embedding_multiplier", 1.0), ("residual_multiplier", 1.0),
    ("logits_scaling", 1.0)])
def test_a_multiplier_left_out_fails_the_reference(params, tokens, name,
                                                   plain_value):
    """The attention's scale is `attention_multiplier` (1 / 16 here, where
    head_dim ** -0.5 is 1 / 4) and the embedding, every branch and the
    logits carry theirs: the program with one of them at the value a plain
    decoder has reads far from the reference, which has them all."""
    assert getattr(CFG, name) != plain_value
    ref = reference_logits(params, CFG, tokens[:24])
    got, _ = F.forward(params, jnp.asarray(tokens[:24])[None],
                       CFG.replace(**{name: plain_value}))
    assert np.abs(np.asarray(got[0]) - ref).max() > 1000 * TOL


def test_no_position_enters_the_block(params, tokens):
    """`position_embedding_type` nope: the pass without a cache gives the
    same logits whatever positions it is told (stretched and shifted here,
    which a rotation would turn into other angles between tokens); order
    alone is what the model sees."""
    toks = jnp.asarray(tokens[:24])[None]
    want, _ = F.forward(params, toks, CFG)
    got, _ = F.forward(params, toks, CFG,
                       positions=(7 + 3 * jnp.arange(24))[None])
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_the_shift_moves_dt_and_nothing_else(params, tokens):
    """`dt_shift` is added to `dt`'s pre-activation: a tree whose `dt_bias`
    was drawn around zero, served with the shift, is the tree with the
    shift in its vector; the reference reads it from `assumed`."""
    ssm = dict(params["ssm"])
    ssm["dt_bias"] = ssm["dt_bias"] + 2.0
    moved = {**params, "ssm": ssm}
    toks = jnp.asarray(tokens[:24])[None]
    want, _ = F.forward(params, toks, CFG)
    shifted = CFG.replace(dt_shift=-2.0)
    got, _ = F.forward(moved, toks, shifted)
    assert np.abs(np.asarray(got - want)).max() < TOL
    ref = reference_logits(moved, shifted, tokens[:24])
    assert np.abs(np.asarray(got[0]) - ref).max() < TOL


# -- (c) through the engine ------------------------------------------------------

def test_the_engine_serves_the_family_through_submit(params, tokens):
    """Engine.submit/start, chunked prefill, jit_decode, overlap: every
    served token is the reference's best at its position (float32: a gap
    above 1e-4 is a wrong token, not rounding), three requests in flight,
    one over two chunks, one of a single token. The engine says what it
    holds beside the pages."""
    prompts = [tokens[:37], tokens[3:26], tokens[40:41]]
    outs, eng = serve(params, prompts, 16)
    for p, ids in zip(prompts, outs):
        assert len(ids) == 16
        gaps = R.served_gaps(plain(params), cfg_dict(CFG), list(p), ids)
        assert gaps.max() < 1e-4
    st = eng.stats
    assert st["preemptions"] == 0 and st["prefix_hit_tokens"] == 0
    assert st["prefix_reuse_refused"] == 2  # off and counted: 37 and 23 tokens
    assert eng.prefix is None
    assert not [k for k in st if k.startswith("moe_")]
    assert "window_rows_live_sum" not in st
    # pages of the two attention layers, read
    assert eng.cache["k"].shape == (2, eng.n_pages + 1, PAGE, 2, 16)
    assert st["decode_kv_pages_read_sum"] > 0
    # rows and state of the six Mamba layers, a slot each
    # three rows of 80 a slot, end to end
    assert eng.cache[kvcache.CONV_STATE].shape == (6, SLOTS, 3 * 80)
    assert eng.cache[kvcache.SSM_STATE].shape == (6, SLOTS, 8, 64)
    assert eng.cache[kvcache.SSM_STATE].dtype == jnp.float32
    assert METRICS.get("substratus_serve_slot_state_bytes") == (
        6 * SLOTS * (3 * 80 * 4 + 8 * 64 * 4))
    # chunks that began from a carried state: 37 tokens are three chunks,
    # 23 two, 1 one: 3 of 6 resumed, rows and state alike
    assert (st["conv_chunks_sum"], st["conv_chunks_resumed_sum"]) == (6, 3)
    assert 0 < st["state_rows_live_sum"] <= st["state_rows_sum"]
    assert st["state_rows_sum"] % SLOTS == 0
    # 64 lanes, on a CPU: the step is ops/ssd.py's, not the kernel's
    assert st["state_kernel_steps"] == 0


def test_the_kernel_path_serves_the_tokens_of_the_step_path(
        monkeypatch, pallas_interpret):
    """A state of 128 rows by 128 lanes (the shape ops/ssd_kernel.py is
    written for; `m a`, two slots): the same seed served twice, through
    XLA's step as the CPU takes it and through the kernel, interpreted, as
    a TPU would. The tokens are equal, the decode program called the
    kernel, and `state_kernel_steps` counts the decoding iterations of the
    second engine and none of the first."""
    cfg = M.GraniteHybridConfig(
        vocab_size=256, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
        head_dim=16, hidden_dim=128, layer_types=(M.MAMBA, M.ATTN),
        mamba_n_heads=8, mamba_d_head=16, mamba_d_state=128,
        attention_multiplier=1.0 / 16, max_seq_len=64, dtype=jnp.float32)
    wide = seeded_params(M, cfg, 2)
    toks = np.asarray(jax.random.randint(jax.random.key(4), (40,), 0, 256))
    prompts = [toks[:21], toks[25:34]]

    def served():
        eng = Engine(cfg, wide, EngineConfig(
            max_batch=2, max_seq_len=64, max_prefill_len=CHUNK,
            page_size=PAGE), model=M)
        assert eng.cache[kvcache.SSM_STATE].shape == (1, 2, 128, 128)
        eng.start()
        outs = submit_all(eng, prompts, 10)
        eng.stop()
        assert eng.error is None
        return outs, eng.stats

    in_xla, st = served()
    assert st["state_kernel_steps"] == 0 < st["state_rows_sum"]
    calls = []
    monkeypatch.setattr(
        kvcache.ssd_kernel, "step",
        lambda *a, _k=kvcache.ssd_kernel.step, **kw:
        calls.append(1) or _k(*a, **kw))
    monkeypatch.setattr(jax.lax, "platform_dependent",
                        lambda *args, tpu, default: tpu(*args))
    # what the engine asks of a state its TPU holds
    monkeypatch.setattr(kvcache, "ssm_step_takes_kernel",
                        lambda s: kvcache._ssm_kernel_for(s) is not None)
    jax.clear_caches()
    in_kernel, st = served()
    jax.clear_caches()  # no later test meets a program traced here
    assert calls and in_kernel == in_xla
    assert all(len(ids) == 10 for ids in in_kernel)
    assert st["state_kernel_steps"] * 2 == st["state_rows_sum"] > 0


def test_a_slots_second_occupant_equals_a_fresh_engine(params, tokens):
    """One slot, two requests one after the other, the first the longer:
    the second is served what a fresh engine serves it, though the first
    left its rows and its state in the slot and nothing was zeroed."""
    first, second = tokens[:30], tokens[33:52]
    eng = Engine(CFG, params, EngineConfig(
        max_batch=1, max_seq_len=96, max_prefill_len=CHUNK, page_size=PAGE),
        model=M)
    eng.start()
    submit_all(eng, [first], 12)
    for name in (kvcache.SSM_STATE, kvcache.CONV_STATE):
        assert np.abs(np.asarray(eng.cache[name])).max() > 0
    reused = submit_all(eng, [second], 12)
    eng.stop()
    assert eng.error is None
    fresh, _ = serve(params, [second], 12, max_batch=1)
    assert reused == fresh


def test_the_engine_preempts_and_resumes_token_exact(params, tokens):
    """A pool too small for three sequences: the engine preempts, frees the
    victim's pages, prefills it again from position 0 over whatever its
    slot's rows and state held, and serves the tokens of a roomy one. No
    snapshot is taken."""
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


def test_the_state_stays_float32_under_bfloat16_activations():
    cache = M.init_paged_cache(CFG.replace(dtype=jnp.bfloat16), 8, PAGE,
                               slots=2)
    assert cache["k"].dtype == cache[kvcache.CONV_STATE].dtype == jnp.bfloat16
    assert cache[kvcache.SSM_STATE].dtype == jnp.float32


def test_the_registry_knows_the_family():
    assert registry.module_for("granitemoehybrid") is M
    assert registry.HF_MODEL_TYPES["granitemoehybrid"] == "granitemoehybrid"
    assert registry.config_class("granitemoehybrid") is M.GraniteHybridConfig
    assert registry.family_of(CFG) == "granitemoehybrid"
    assert registry.find_named_config("tiny-granite-hybrid")[0] is M
    assert M.layer_plan(M.CONFIGS["granite-4.0-h-micro"]) == (0, 10, 4)
    with pytest.raises(ValueError, match="ties its output head"):
        M.GraniteHybridConfig(tie_embeddings=False)
    with pytest.raises(ValueError, match="one group"):
        M.GraniteHybridConfig(mamba_n_groups=8)


def test_a_published_config_json_gives_the_named_preset():
    """load/hf.py reads `model_type: granitemoehybrid`: the catalog's
    config of Granite-4.0-H-Micro is the named preset; the same model with
    routed experts (Granite-4.0-H-Small's keys) is refused by name."""
    import json
    from types import SimpleNamespace

    from substratus_tpu.load import hf

    with open("benchmarks/configs/granite-4.0-h-micro.json") as f:
        published = SimpleNamespace(**json.load(f))
    to_config, _ = hf._dispatch_hf("granitemoehybrid")
    cfg = to_config(published)
    assert cfg == M.CONFIGS["granite-4.0-h-micro"]
    assert (cfg.inner, cfg.conv_dim, cfg.count(M.MAMBA)) == (4096, 4352, 36)
    published.num_local_experts = 72
    with pytest.raises(NotImplementedError, match="routed experts"):
        to_config(published)


def test_the_published_tensor_names_give_the_tree(params):
    """The tree under the published names (torch.nn.Linear's [out, in],
    `shared_mlp.input_linear` the gate's rows above the up's, `conv1d`
    [W, 1, K]) and back through load/hf.py's converter: leaf for leaf."""
    from substratus_tpu.load import hf

    tree = jax.jit(lambda key: M.init_params(CFG, key))(jax.random.key(9))
    sd = {"model.embed_tokens.weight": tree["tok_embed"],
          "model.norm.weight": tree["out_norm"]}
    seen = {M.MAMBA: 0, M.ATTN: 0}
    for i, kind in enumerate(CFG.layer_types):
        pre, lp = f"model.layers.{i}.", jax.tree.map(
            lambda a: a[i], tree["layers"])
        sd[pre + "input_layernorm.weight"] = lp["input_norm"]
        sd[pre + "post_attention_layernorm.weight"] = lp["post_norm"]
        sd[pre + "shared_mlp.input_linear.weight"] = jnp.concatenate(
            [lp["w_gate"].T, lp["w_up"].T])
        sd[pre + "shared_mlp.output_linear.weight"] = lp["w_down"].T
        j = seen[kind]
        seen[kind] += 1
        if kind == M.MAMBA:
            sp = jax.tree.map(lambda a: a[j], tree["ssm"])
            sd.update({
                pre + "mamba.in_proj.weight": sp["w_in"].T,
                pre + "mamba.conv1d.weight": sp["taps"].T[:, None, :],
                pre + "mamba.conv1d.bias": sp["conv_bias"],
                pre + "mamba.A_log": sp["a_log"], pre + "mamba.D": sp["d_skip"],
                pre + "mamba.dt_bias": sp["dt_bias"],
                pre + "mamba.norm.weight": sp["norm"],
                pre + "mamba.out_proj.weight": sp["w_out"].T})
        else:
            ap = jax.tree.map(lambda a: a[j], tree["attn"])
            sd.update({pre + f"self_attn.{n}_proj.weight": ap["w" + n]
                       for n in "qkv"})
            sd[pre + "self_attn.o_proj.weight"] = ap["wo"].T
    back = hf.convert_granitemoehybrid_state_dict(sd, CFG, jnp.float32)
    assert jax.tree.structure(back) == jax.tree.structure(tree)
    for a, b in zip(jax.tree.leaves(back), jax.tree.leaves(tree)):
        assert a.dtype == b.dtype and np.array_equal(a, b)
