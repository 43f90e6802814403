"""models/exaone_moe.py against the plain reference
(benchmarks/reference/exaone_moe.py, which imports nothing of the program),
on seeded random weights at a small size: 12 layers `LLLG` x 3 with layer 0
dense, 16 experts top-4 beside a shared one, window 8, chunks of 16, so
contexts cross the window and a chunk boundary.

Everything here runs in float32 with int8 weights (the precision the
benchmark's cell states, less bfloat16 rounding), so the tolerances are
those of float32 summation order, and a lower precision fails them."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import exaone_moe as R
from family_harness import Family, plain, seeded_params, table
from substratus_tpu.models import exaone_moe as M
from substratus_tpu.models import hybrid
from substratus_tpu.models import registry
from substratus_tpu.serve.engine import Engine, EngineConfig

CFG = M.CONFIGS["tiny-exaone-moe"].replace(dtype=jnp.float32)
W, CHUNK, PAGE = CFG.sliding_window, 16, 4
# float32 activations, exact int8 weights: the program and the reference
# differ by summation order alone (measured 6e-6 on logits of magnitude 4;
# the limit leaves a factor of five). w8a8 reads 2e-2, bfloat16 1e-2.
TOL = 3e-5
F = Family(M, CFG, chunk=CHUNK, page=PAGE)
prefill, decode, serve = F.prefill, F.decode, F.serve


def cfg_dict(cfg: M.ExaoneMoeConfig, **over):
    """The configuration as the benchmark's files spell it."""
    d = dict(
        hidden_size=cfg.dim, num_hidden_layers=cfg.n_layers,
        num_attention_heads=cfg.n_heads, num_key_value_heads=cfg.n_kv_heads,
        head_dim=cfg.head_dim, intermediate_size=cfg.hidden_dim,
        moe_intermediate_size=cfg.moe_hidden_dim,
        num_shared_experts=cfg.n_shared_experts, vocab_size=cfg.vocab_size,
        num_experts=cfg.held_experts[1],
        published={"num_experts": cfg.n_experts},
        layout={"experts_held": [cfg.held_experts[0], cfg.held_experts[1]]},
        num_experts_per_tok=cfg.n_experts_per_token,
        sliding_window=cfg.sliding_window, layer_types=list(cfg.layer_types),
        mlp_layer_types=list(cfg.mlp_layer_types),
        rope_parameters={"rope_theta": cfg.rope_theta},
        rms_norm_eps=cfg.norm_eps,
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


# -- (a) chunks, pool and rings against the reference's full forward -------------

@pytest.mark.parametrize("prompt_len", [5, 16, 37])
def test_chunked_prefill_then_decode_matches_the_reference(
        params, tokens, prompt_len):
    """Prompts under the window, of exactly one chunk, and over two chunks
    and the window; then decode steps until the ring has wrapped twice."""
    n = prompt_len + 2 * W + 3
    ref = reference_logits(params, CFG, tokens[:n])
    bt, slot = table(3), 1
    got, cache = prefill(params, CFG, new_cache(CFG), tokens[:prompt_len],
                         slot, bt)
    assert np.abs(got - ref[:prompt_len]).max() < TOL
    for pos in range(prompt_len, n):
        row, cache, stats = decode(params, CFG, cache, tokens[pos], pos,
                                   slot, bt)
        assert np.abs(row - ref[pos]).max() < TOL, pos
    # one live slot, every expert held: all of its pairs land here
    k, sparse = CFG.n_experts_per_token, CFG.count(M.SPARSE)
    assert int(stats["moe_pairs_held"]) == int(stats["moe_pairs_all"]) \
        == k * sparse


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


def test_an_int8_cache_is_refused(params):
    with pytest.raises(ValueError, match="int8"):
        M.init_paged_cache(CFG, 8, PAGE, dtype=jnp.int8)
    with pytest.raises(ValueError, match="int8"):
        Engine(CFG, params, EngineConfig(kv_cache_dtype="int8"), model=M)


def test_the_engine_serves_the_family_through_submit(params, tokens):
    """Engine.submit/start, chunked prefill, jit_decode, overlap: every
    served token is the reference's best at its position (float32: a gap
    above 1e-4 is a wrong token, not rounding), three requests in flight
    whose contexts cross the window and a chunk."""
    prompts = [tokens[:37], tokens[3:26], tokens[40:49]]
    outs, eng = serve(params, prompts, 20)
    for p, ids in zip(prompts, outs):
        assert len(ids) == 20
        gaps = R.served_gaps(plain(params), cfg_dict(CFG), list(p), ids)
        assert gaps.max() < 1e-4
    st = eng.stats
    assert st["preemptions"] == 0 and st["prefix_hit_tokens"] == 0
    assert st["prefix_reuse_refused"] == 3  # the registry is off, counted
    assert st["moe_pairs_held"] == st["moe_pairs_all"] > 0
    assert st["moe_decode_steps"] > 0
    assert 0 < st["window_rows_live_sum"] <= st["window_rows_cap_sum"]
    # the pool holds the global layers alone, the rings the window layers
    assert eng.cache["k"].shape[0] == CFG.count(M.GLOBAL) == 3
    assert eng.cache["wk"].shape[:3] == (CFG.count(M.WINDOW), 3, W)


def test_the_registry_knows_the_family():
    assert registry.module_for("exaone_moe") is M
    assert registry.HF_MODEL_TYPES["exaone_moe"] == "exaone_moe"
    assert registry.config_class("exaone_moe") is M.ExaoneMoeConfig
    assert registry.family_of(CFG) == "exaone_moe"
    assert registry.find_named_config("tiny-exaone-moe")[0] is M


@pytest.mark.parametrize("what", ["role", "spec", "dense"])
def test_what_pages_alone_cannot_carry_is_refused(params, what):
    ec = {"role": EngineConfig(role="decode"),
          "spec": EngineConfig(spec_k=2),
          "dense": EngineConfig(kv_layout="dense")}[what]
    with pytest.raises(ValueError):
        Engine(CFG, params, ec, model=M)


# -- the stack's shape -----------------------------------------------------------

@pytest.mark.parametrize("layers,plan", [(12, (4, 4, 2)), (48, (4, 4, 11)),
                                         (4, (0, 4, 1))])
def test_layer_plan_scans_the_periods(layers, plan):
    cfg = M.ExaoneMoeConfig(n_layers=layers)
    assert cfg.layer_types[:4] == (M.WINDOW,) * 3 + (M.GLOBAL,)
    assert cfg.mlp_layer_types[:2] == (M.DENSE, M.SPARSE)
    assert M.layer_plan(cfg) == plan


def test_layer_kinds_come_from_the_config(params, tokens):
    """Another pattern than the published one: global first, two dense
    layers, a period of two. Same program, same reference."""
    cfg = CFG.replace(
        n_layers=6,
        layer_types=(M.GLOBAL, M.WINDOW, M.GLOBAL, M.WINDOW, M.GLOBAL,
                     M.WINDOW),
        mlp_layer_types=(M.DENSE, M.DENSE) + (M.SPARSE,) * 4)
    assert M.layer_plan(cfg) == (2, 2, 2)
    p = seeded_params(M, cfg, 2)
    ref = reference_logits(p, cfg, tokens[:30])
    got, cache = prefill(p, cfg, new_cache(cfg), tokens[:27], 2, table(3))
    assert np.abs(got - ref[:27]).max() < TOL
    for pos in range(27, 30):
        row, cache, _ = decode(p, cfg, cache, tokens[pos], pos, 2, table(3))
        assert np.abs(row - ref[pos]).max() < TOL


# -- (b) the shares add up to the uncut layer ------------------------------------

def sparse_layer(params, i=0):
    """(the program's stack of sparse layers, the reference's layer i)."""
    return params["moe"], jax.tree.map(lambda a: a[i], plain(params["moe"]))


@pytest.mark.parametrize("seq,block_rows", [(12, None), (40, None), (40, 4)],
                         ids=["every", "grouped", "grouped-many-blocks"])
def test_eight_shares_add_up_to_the_uncut_layer(params, monkeypatch, seq,
                                                block_rows):
    """Each of 8 ranks holds 2 of the 16 experts, routes over all 16 and
    computes its own part; the parts, with the shared expert counted once,
    are the uncut reference's sparse layer. Both ways of multiplying, each
    picked by the call's token count as the served program picks it: every
    token by every held expert (24 tokens), and pairs grouped by expert
    (80 tokens), there also with blocks so short that an expert takes
    several."""
    if block_rows:
        monkeypatch.setattr(hybrid, "BLOCK_ROWS", block_rows)
    t = 2 * seq
    assert (t > hybrid.EVERY_AT_MOST) == (seq == 40)
    mp, mw = sparse_layer(params)
    h = jax.random.normal(jax.random.key(3), (2, seq, CFG.dim), jnp.float32)
    flat = h.reshape(t, CFG.dim)
    dims = R.model_dims(cfg_dict(CFG))
    with jax.default_matmul_precision("highest"):
        whole = (R.routed_part(flat, mw, dims, CFG.routed_scaling_factor, True)
                 + R.shared_part(flat, mw))
        shared = np.asarray(R.shared_part(flat, mw))
    valid = jnp.ones((2, seq), bool)
    total, held = np.zeros_like(shared), 0
    for rank in range(8):
        cfg = CFG.replace(held_experts=(2 * rank, 2))
        share = dict(mp)
        for name in ("w_gate", "w_up", "w_down"):
            share[name] = jax.tree.map(
                lambda a: a[:, 2 * rank:2 * rank + 2], mp[name])
        y, stats = hybrid.moe(h, share, jnp.int32(0), cfg, valid, M.qeinsum)
        total += np.asarray(y).reshape(t, -1) - shared
        held += int(stats["moe_pairs_held"])
        assert int(stats["moe_pairs_all"]) == t * CFG.n_experts_per_token
        # the reference, given the same share, gives the same partial sum
        part = R.routed_part(flat, jax.tree.map(
            lambda a: a, {**mw, **{n: jax.tree.map(
                lambda a: a[2 * rank:2 * rank + 2], mw[n])
                for n in ("w_gate", "w_up", "w_down")}}),
            {**dims, "Eh": 2, "first": 2 * rank}, CFG.routed_scaling_factor,
            True)
        assert np.abs(np.asarray(y).reshape(t, -1) - shared
                      - np.asarray(part)).max() < TOL
    assert np.abs(total + shared - np.asarray(whole)).max() < TOL
    assert held == t * CFG.n_experts_per_token  # every pair landed once


def test_a_share_changes_nothing_outside_the_expert_sum(params, tokens):
    """Residual and attention are computed alike on every rank: two shares'
    layer outputs differ by their experts' parts alone."""
    toks = jnp.asarray(tokens[:20])[None]
    outs = []
    for first in (0, 8):
        cfg = CFG.replace(held_experts=(first, 8))
        share = jax.tree.map(lambda a: a, params)
        share["moe"] = dict(params["moe"])
        for name in ("w_gate", "w_up", "w_down"):
            share["moe"][name] = jax.tree.map(
                lambda a: a[:, first:first + 8], params["moe"][name])
        outs.append(np.asarray(F.forward(share, toks, cfg)[0]))
    whole = np.asarray(F.forward(params, toks, CFG)[0])
    assert np.abs(outs[0] - whole).max() > 1e-2  # a share is not the model


# -- (c) the router alone --------------------------------------------------------

def test_router_sigmoid_bias_normalisation_and_factor():
    cfg = CFG.replace(n_experts=8, n_experts_per_token=3, held_experts=(0, 8))
    # router = identity on the first 8 dims: the score of expert e is
    # sigmoid(h_e)
    router = jnp.eye(CFG.dim, 8, dtype=jnp.float32)
    logits = np.array([[3.0, 2.0, 1.0, 0.5, 0.0, -1.0, -2.0, -3.0]], np.float32)
    h = jnp.zeros((1, CFG.dim)).at[:, :8].set(logits)
    s = 1 / (1 + np.exp(-logits[0]))
    idx, w = hybrid.route(h, router, jnp.zeros((8,)), cfg)
    assert sorted(np.asarray(idx[0])) == [0, 1, 2]
    np.testing.assert_allclose(np.asarray(w[0]), 2.5 * s[:3] / s[:3].sum(),
                               rtol=1e-6)
    assert abs(float(w.sum()) - 2.5) < 1e-6  # the factor, after normalising
    # a bias moves the choice (expert 7 displaces expert 2) and never the
    # weight: expert 7 weighs by its own small score
    bias = jnp.zeros((8,)).at[7].set(1.0)
    idx, w = hybrid.route(h, router, bias, cfg)
    chosen = dict(zip(np.asarray(idx[0]).tolist(), np.asarray(w[0]).tolist()))
    assert sorted(chosen) == [0, 1, 7]
    denom = s[0] + s[1] + s[7]
    np.testing.assert_allclose(chosen[7], 2.5 * s[7] / denom, rtol=1e-5)
    np.testing.assert_allclose(chosen[0], 2.5 * s[0] / denom, rtol=1e-5)
    # without normalisation the weights are the scores times the factor
    idx, w = hybrid.route(h, router, jnp.zeros((8,)),
                     cfg.replace(norm_topk_prob=False))
    np.testing.assert_allclose(np.sort(np.asarray(w[0])), np.sort(2.5 * s[:3]),
                               rtol=1e-6)
    # the reference's router agrees, expert by expert
    ref = np.asarray(R.route(h, router, bias, 3, 2.5))
    assert sorted(np.flatnonzero(ref[0])) == [0, 1, 7]
    np.testing.assert_allclose(ref[0, 7], chosen[7], rtol=1e-6)


def test_absent_experts_stay_in_the_normalisation(params):
    """A rank that holds experts 0-1 weighs them by the sum over all the
    chosen, held or not: its weights are the uncut router's, not rescaled
    to what it holds."""
    mp = hybrid.take(params["moe"], 0)
    h = jax.random.normal(jax.random.key(4), (24, CFG.dim), jnp.float32)
    idx, w = hybrid.route(h, mp["router"], mp["router_bias"], CFG)
    here = np.asarray(idx) < 2
    assert here.any() and not here.all()
    np.testing.assert_allclose(np.asarray(w).sum(-1), 2.5, rtol=1e-5)
    assert (np.asarray(w)[here] < 2.5).all()


# -- (d) the window's edge --------------------------------------------------------

WINDOW_ONLY = CFG.replace(n_layers=1, layer_types=(M.WINDOW,),
                          mlp_layer_types=(M.DENSE,))


@pytest.mark.parametrize("where", ["decode", "in_chunk", "across_chunks"])
def test_window_mask_is_exact_at_its_edge(tokens, where):
    """One window layer, so logits at i depend on the token at j iff
    0 <= i - j < W: changing token j moves the logits at j + W - 1 and
    leaves those at j + W bit for bit, with i in a decode step, in j's own
    chunk, and in the chunk after j's."""
    p = M.init_params(WINDOW_ONLY, jax.random.key(5))
    j = {"decode": 17, "in_chunk": 2, "across_chunks": 11}[where]
    n_prompt = {"decode": 20, "in_chunk": 16, "across_chunks": 32}[where]
    runs = []
    for swap in (False, True):
        toks = tokens[:40].copy()
        if swap:
            toks[j] = (toks[j] + 1) % CFG.vocab_size
        bt = table(2)
        rows, cache = prefill(p, WINDOW_ONLY, new_cache(WINDOW_ONLY, 2),
                              toks[:n_prompt], 0, bt)
        rows = list(rows)
        for pos in range(n_prompt, j + W + 1):
            row, cache, _ = decode(p, WINDOW_ONLY, cache, toks[pos], pos, 0, bt)
            rows.append(row)
        runs.append(np.stack(rows))
    a, b = runs
    assert np.abs(a[j + W - 1] - b[j + W - 1]).max() > 1e-4  # i - j = W - 1
    assert np.array_equal(a[j + W], b[j + W])  # i - j = W
    assert np.array_equal(a[:j], b[:j])  # and nothing before j


# -- (e) resumed sequences and wrapped rings -------------------------------------

def test_a_resumed_sequence_gives_the_same_logits(params, tokens):
    """Preempt-and-resume prefills prompt + emitted tokens again from
    position 0 into whatever the slot's rings held: the next logits are
    those of the sequence that was never interrupted, whose ring had
    wrapped (37 + 12 tokens over a window of 8)."""
    bt = table(3)
    _, cache = prefill(params, CFG, new_cache(CFG), tokens[:37], 0, bt)
    for pos in range(37, 49):
        through, cache, _ = decode(params, CFG, cache, tokens[pos], pos, 0, bt)
    # another sequence leaves its rows in slot 0's rings and pages
    _, cache = prefill(params, CFG, cache, tokens[5:64], 0, bt)
    again, cache = prefill(params, CFG, cache, tokens[:49], 0, bt)
    assert np.abs(again[-1] - through).max() < TOL
    nxt, _, _ = decode(params, CFG, cache, tokens[49], 49, 0, bt)
    ref = reference_logits(params, CFG, tokens[:50])
    assert np.abs(nxt - ref[49]).max() < TOL


def test_the_engine_preempts_and_resumes_token_exact(params, tokens):
    """A pool too small for three sequences: the engine preempts, resumes
    through the rings, and serves the tokens of a roomy pool."""
    prompts = [tokens[:30], tokens[10:38], tokens[20:45]]
    roomy, _ = serve(params, prompts, 24)
    tight, eng = serve(params, prompts, 24, kv_pool_tokens=120)
    assert eng.stats["preemptions"] >= 1
    assert tight == roomy


def test_a_published_config_json_gives_the_named_preset():
    """load/hf.py reads `model_type: exaone_moe`: the published keys of
    K-EXAONE-236B-A23B (its lists a layer each) are the named preset."""
    from types import SimpleNamespace

    from substratus_tpu.load import hf

    published = SimpleNamespace(
        model_type="exaone_moe", vocab_size=153600, hidden_size=6144,
        num_hidden_layers=48, num_attention_heads=64, num_key_value_heads=8,
        head_dim=128, intermediate_size=18432, moe_intermediate_size=2048,
        num_experts=128, num_experts_per_tok=8, num_shared_experts=1,
        routed_scaling_factor=2.5, norm_topk_prob=True, sliding_window=128,
        layer_types=(["sliding_attention"] * 3 + ["full_attention"]) * 12,
        mlp_layer_types=["dense"] + ["sparse"] * 47,
        rope_parameters={"rope_theta": 1000000, "rope_type": "default"},
        rms_norm_eps=1e-05, max_position_embeddings=262144,
        tie_word_embeddings=False)
    to_config, convert = hf._dispatch_hf("exaone_moe")
    cfg = to_config(published)
    assert cfg == M.CONFIGS["k-exaone-236b-a23b"]
    assert (cfg.count(M.WINDOW), cfg.count(M.GLOBAL), cfg.count(M.DENSE),
            cfg.count(M.SPARSE)) == (36, 12, 1, 47)
    with pytest.raises(NotImplementedError, match="converter"):
        convert({}, cfg)
