"""models/deepseek_v3.py under a learned index (`glm_moe_dsa`, GLM-5's
mechanism; ops/sparse_index.py) against the plain reference
(benchmarks/reference/glm_moe_dsa.py, which imports nothing of the
program), on seeded random weights at a small size: `tiny-glm-dsa`, 1 dense
+ 3 sparse layers, heads of 24 + 8 against 32, 2 index heads over keys of
16, the 8 best rows of a context kept; contexts run to 60 and more, several
times that, so the selection bites in every test that does not say
otherwise. The helpers, the chunking and the tolerance are
tests/test_deepseek_v3.py's, which also runs prefix reuse and preemption
over both families."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import glm_moe_dsa as R
from substratus_tpu.models import deepseek_v3 as M
from substratus_tpu.models import hybrid, registry
from substratus_tpu.ops import sparse_index
from test_deepseek_v3 import (
    DSA, PAGE, PAGES, TOL, _forward, cfg_dict, decode, new_cache, params_of,
    plain, prefill, reference_logits, serve, table,
)


@pytest.fixture(scope="module")
def params():
    return params_of(DSA)


@pytest.fixture(scope="module")
def tokens():
    return np.asarray(jax.random.randint(jax.random.key(1), (80,), 0,
                                         DSA.vocab_size))


def program_sets(params, cfg, toks):
    """Every layer's sets as the program's no-cache forward takes them,
    [layers][T, T]: `sparse_index.select` watched, the stack walked
    without jit so that what it returns is an array."""
    seen = []
    real = sparse_index.select

    def watched(*a, **k):
        out = real(*a, **k)
        seen.append(np.asarray(out[0]))
        return out

    sparse_index.select = watched
    try:
        with jax.disable_jit():
            logits, _ = M.forward(params, jnp.asarray(toks)[None], cfg)
    finally:
        sparse_index.select = real
    return seen, np.asarray(logits[0])


def test_chunks_then_steps_through_the_selection_match_the_reference(
        params, tokens):
    """Chunks of 16 (each query under its own set, a mask over the
    gathered context), then decode steps (scores over the live keys, the 8
    best positions, their rows read by position), in slot 1 of 3: every
    position's logits are the reference's whole forward pass."""
    bt = table(3)
    prompt, total = 37, 52
    ref = reference_logits(params, DSA, tokens[:total])
    got, cache = prefill(params, DSA, new_cache(DSA), tokens[:prompt], 1, bt)
    assert np.abs(got - ref[:prompt]).max() < TOL
    for pos in range(prompt, total):
        step, cache, _ = decode(params, DSA, cache, tokens[pos], pos, 1, bt)
        assert np.abs(step - ref[pos]).max() < TOL, pos
    # the index bites: without it the same weights give other logits
    plain_mla, _ = _forward(params, jnp.asarray(tokens[:total])[None],
                            DSA.replace(index_n_heads=0))
    assert np.abs(np.asarray(plain_mla[0]) - ref).max() > 1e-2


def test_the_programs_sets_are_the_references(params, tokens):
    """With `I` in float32 every query's set is the reference's, position
    for position, in every layer; each holds min(k, t + 1) positions, none
    ahead of its query."""
    toks = tokens[:40]
    want = []
    reference_logits(params, DSA, toks, sets_out=want)
    got, _ = program_sets(params, DSA, toks)
    assert len(got) == len(want) == DSA.n_layers
    size = np.minimum(DSA.index_topk, np.arange(40) + 1)
    for mine, theirs in zip(got, want):
        assert (mine == theirs).all()
        assert (mine.sum(1) == size).all()
        assert not np.triu(mine, 1).any()


@pytest.mark.slow  # a second eager walk of the stack: 12 s
def test_in_bfloat16_most_of_a_sets_positions_stay(params, tokens):
    """A bfloat16 rounding moves a position at a set's edge now and then
    (a set here is 8 of up to 40 rows): the share of the reference's
    positions the program keeps, a layer."""
    toks = tokens[:40]
    want = []
    reference_logits(params, DSA, toks, sets_out=want)
    rounded, _ = program_sets(params, M.CONFIGS["tiny-glm-dsa"], toks)
    kept = [(a & b).sum() / b.sum() for a, b in zip(rounded, want)]
    assert 0.6 < min(kept) and np.mean(kept) < 1.0, kept


def test_a_set_as_wide_as_the_context_is_plain_latent_attention(params,
                                                                tokens):
    """At contexts of at most index_topk tokens every query attends all it
    sees: the family gives deepseek_v3's logits at the same weights, the
    whole sequence at once and through the pool."""
    wide = DSA.replace(index_topk=64)
    none = DSA.replace(index_n_heads=0)
    toks = tokens[:50]
    a, _ = _forward(params, jnp.asarray(toks)[None], wide)
    b, _ = _forward(params, jnp.asarray(toks)[None], none)
    assert np.abs(np.asarray(a) - np.asarray(b)).max() < 1e-6
    got, _ = prefill(params, wide, new_cache(wide), toks[:37], 0, table(2))
    assert np.abs(got - np.asarray(b[0, :37])).max() < TOL


def test_a_step_reads_no_row_it_did_not_select(params, tokens):
    """A decode step over a pool whose unselected latent rows are NaN
    gives the logits of the pool as it was: the rows are read by position,
    the set's and no others. (The index keys stay: every live key is
    scored.)"""
    bt = table(2)
    prompt = 48
    _, cache = prefill(params, DSA, new_cache(DSA), tokens[:prompt], 0, bt)
    want, after, _ = decode(params, DSA, dict(cache), tokens[prompt], prompt,
                            0, bt)
    sets = []
    reference_logits(params, DSA, tokens[:prompt + 1], sets_out=sets)
    rows = np.asarray(cache["k"]).copy()
    for layer, chosen in enumerate(sets):
        for pos in np.flatnonzero(~chosen[prompt]):
            if pos < prompt:  # the step writes position `prompt` itself
                rows[layer, bt[0, pos // PAGE], pos % PAGE] = np.nan
    assert np.isnan(rows).sum() > 0
    poisoned = {**cache, "k": jnp.asarray(rows)}
    got, _, _ = decode(params, DSA, poisoned, tokens[prompt], prompt, 0, bt)
    assert np.isfinite(got).all()
    assert np.abs(got - want).max() < 1e-6


@pytest.mark.parametrize("s,seq,page,topk", [
    (1, 128, 16, 24), (20, 128, 16, 24), (1, 1024, 128, 128)],
    ids=["step", "chunk", "step-page-128"])
def test_the_index_kernels_are_the_xla_forms(pallas_interpret, s, seq, page,
                                             topk):
    """ops/sparse_index.py's kernels and the chunk kernel under a bias,
    interpreted, through `latent_attention` against its gathered forms
    (ops/kernel_cases.py's small cases: rows of every length, 24 of up to
    128 kept; at a page of 128 tokens, where a step's selection runs in
    `index_select_rows`, 128 of up to 1,024)."""
    from substratus_tpu.ops import kernel_cases as KC

    case = KC.latent_attend("small", 3 if s == 1 else 2, s, seq, h=8, dn=48,
                            dr=16, dv=64, rkv=128, pages=25, page=page,
                            index=(4, 128, topk))
    args = case.make_args(jax.random.key(0))
    got = np.asarray(case.kernel(*args, interpret=True), np.float32)
    want = np.asarray(case.reference(*args), np.float32)
    assert np.abs(got - want).max() < case.tol * max(1.0, np.abs(want).max())


def test_select_is_top_k_with_ties_toward_the_lower_position():
    """The bisection's mask is `lax.top_k`'s set on scores full of ties,
    along either axis, for queries that see less than k and more."""
    x = jnp.round(jax.random.normal(jax.random.key(3), (3, 5, 33)) * 2) / 2
    seen = jnp.arange(33)[None, None, :] <= (jnp.arange(5) * 7 + 4)[None, :,
                                                                  None]
    seen = jnp.broadcast_to(seen, x.shape)
    _, best = jax.lax.top_k(jnp.where(seen, x, -jnp.inf), 6)
    want = np.zeros(x.shape, bool)
    for b in range(3):
        for q in range(5):
            want[b, q, np.asarray(best[b, q, :min(6, 7 * q + 5)])] = True
    assert (np.asarray(sparse_index.select(x, seen, 6, axis=2)) == want).all()
    turned = sparse_index.select(x.transpose(0, 2, 1),
                                 seen.transpose(0, 2, 1), 6, axis=1)
    assert (np.asarray(turned).transpose(0, 2, 1) == want).all()


def _rows_by_the_sort(sc, block_table, k):
    """The decode step's selection as it stood until PR 44, the plain
    reference: one stable sort of the scores, best first, with the pool's
    rows carried along; (row, ok) [B, min(k, T)]."""
    b, t = sc.shape
    bs = t // block_table.shape[1]
    rows_of = (block_table[:, :, None] * bs + jnp.arange(
        bs, dtype=block_table.dtype)).reshape(b, -1)
    worst, row = jax.lax.sort(
        (-sc, rows_of), dimension=1, is_stable=True, num_keys=1)
    keep = min(k, t)
    return row[:, :keep], worst[:, :keep] < jnp.inf


def _selection_case(name, page):
    """(scores [B, M * page], positions [B], block table [B, M], k): what
    a decode step hands its selection, -inf past each row's position; at
    tier-1's page of 4 tokens or the chip's of 128, a lane tile."""
    m, k, rows = (12, 8, 4) if page == 4 else (8, 128, 4)
    key = jax.random.key(7)
    t = page * m
    sc = jax.random.normal(key, (rows, t), jnp.float32)
    # the table's last token, inside a page, a page's last and its first
    pos = jnp.array([t - 1, t - page - 2, 5 * page - 1, 5 * page], jnp.int32)
    bt = jax.random.permutation(key, jnp.arange(1, 70000, dtype=jnp.int32))[
        :rows * m].reshape(rows, m)
    if name == "ties at the edge":
        # a few values in all: a set's edge falls inside a run of equals
        sc = jnp.round(sc)
    elif name == "rows shorter than the set":
        pos = jnp.array([0, 3, k - 2, k - 1], jnp.int32)
    elif name == "-inf past the position":
        # rows just longer than the set: most of the table is nobody's
        pos = jnp.array([k, k + 1, k + page, t // 2], jnp.int32)
    elif name == "negative and zero scores":
        # 0.0 and -0.0 are one value to the sort, and straddle the edge
        sc = -jnp.abs(jnp.round(sc))
        sc = sc.at[:, ::3].multiply(-1.0)
    elif name == "page ids that descend":
        bt = jnp.sort(bt, axis=1)[:, ::-1]
    else:
        assert name == "random scores", name
    live = jnp.arange(t)[None] <= pos[:, None]
    return jnp.where(live, sc, -jnp.inf), pos, bt, k


@pytest.mark.parametrize("form", [
    "xla, pages of 4", "xla, pages of 128", "kernel, pages of 128"])
@pytest.mark.parametrize("name", [
    "random scores", "ties at the edge", "rows shorter than the set",
    "-inf past the position", "negative and zero scores",
    "page ids that descend"])
def test_a_steps_selection_is_the_stable_sorts_set(name, form):
    """The decode step's threshold and compaction (`select_rows`; on the
    chip one kernel, `index_select_rows`, here interpreted) return, as a
    set, the pool rows one stable sort of the scores returns, row for row:
    min(k, t + 1) of them, in the first places, by ascending position."""
    sc, pos, bt, k = _selection_case(name, 4 if form.endswith(" 4") else 128)
    if form.startswith("kernel"):
        assert sparse_index.select_rows_kernel_takes(
            bt, sc.shape[1] // bt.shape[1], k)
        row, ok = sparse_index.index_select_rows(sc, pos, bt, k,
                                                 interpret=True)
    else:
        row, ok = jax.jit(sparse_index.select_rows, static_argnums=3)(
            sc, pos, bt, k)
    want_row, want_ok = _rows_by_the_sort(sc, bt, k)
    row, ok, want_row, want_ok = map(np.asarray, (row, ok, want_row, want_ok))
    assert row.shape == want_row.shape == (len(pos), min(k, sc.shape[1]))
    size = np.minimum(k, np.asarray(pos) + 1)
    assert (ok.sum(1) == size).all() and (size < k).any() == (
        name == "rows shorter than the set")
    page = sc.shape[1] // bt.shape[1]
    for b in range(len(pos)):
        assert (ok[b] == want_ok[b]).all()
        assert set(row[b, ok[b]]) == set(want_row[b, want_ok[b]]), (name, b)
        # ascending position: undo the table
        at = {int(p) * page + i: j * page + i
              for j, p in enumerate(np.asarray(bt[b])) for i in range(page)}
        where = [at[r] for r in row[b, ok[b]]]
        assert where == sorted(where) and where[-1] <= pos[b], (name, b)
    if name in ("ties at the edge", "negative and zero scores"):
        # the case bites: more equals at some row's edge than fit
        edge = np.sort(np.asarray(sc), axis=1)[:, -k]
        assert ((np.asarray(sc) >= edge[:, None]).sum(1) > k).any()


def test_every_share_of_256_experts_adds_up_to_the_uncut_layer():
    """GLM-5's sparse layer at a small width: 256 experts, 8 chosen, no
    group limit; each of 32 ranks holds 8, routes over all 256 and
    computes its own part; the parts, with the shared expert counted once,
    are the uncut reference's layer."""
    cfg = DSA.replace(n_layers=1, first_k_dense=0, n_experts=256,
                      held_experts=(0, 256), n_experts_per_token=8, dim=32,
                      moe_hidden_dim=16)
    mp = params_of(cfg)["moe"]
    mw = jax.tree.map(lambda a: a[0], plain(mp))
    h = jax.random.normal(jax.random.key(3), (2, 6, cfg.dim), jnp.float32)
    flat = h.reshape(12, cfg.dim)
    dims = R.model_dims(cfg_dict(cfg))
    with jax.default_matmul_precision("highest"):
        shared = np.asarray(R.shared_part(flat, mw))
        whole = np.asarray(R.routed_part(
            flat, mw, dims, cfg.routed_scaling_factor, True)) + shared
    valid = jnp.ones((2, 6), bool)

    @jax.jit
    def every_rank(h, mp):  # one program: 32 ranks, each its own share
        parts, held = [], 0
        for rank in range(32):
            share = dict(mp)
            for name in hybrid.EXPERT_LEAVES:
                share[name] = jax.tree.map(
                    lambda a: a[:, 8 * rank:8 * rank + 8], mp[name])
            y, stats = hybrid.moe(
                h, share, jnp.int32(0),
                cfg.replace(held_experts=(8 * rank, 8)), valid, M.qeinsum)
            parts.append(y.reshape(12, -1))
            held += stats["moe_pairs_held"]
        return jnp.stack(parts), held

    parts, held = every_rank(h, mp)
    total = np.asarray(parts).sum(0) - 32 * shared
    held = int(held)
    assert np.abs(total + shared - whole).max() < TOL
    assert held == 12 * 8  # every pair landed once


@PAGES
def test_the_engine_serves_the_index_and_counts_what_it_selected(
        params, tokens, page):
    """Engine.submit/start over the second pool: every served token is the
    reference's best (float32), the pool keeps both arrays a token, and
    the counters say what the steps' queries attended of what was live."""
    from substratus_tpu.observability.metrics import METRICS

    prompts = [tokens[:37], tokens[3:26]]
    outs, eng = serve(params, prompts, 12, DSA, page_size=page)
    for p, ids in zip(prompts, outs):
        gaps = R.served_gaps(plain(params), cfg_dict(DSA), list(p), ids)
        assert gaps.max() < 1e-4
    assert eng.cache["k"].shape[0] == eng.cache["v"].shape[0] == DSA.n_layers
    assert eng.cache["v"].shape[3:] == (1, DSA.index_head_dim)
    assert METRICS.get("substratus_serve_kv_bytes_per_token") == (
        DSA.n_layers * (DSA.latent_row + DSA.index_head_dim) * 4)
    st = eng.stats
    assert st["dsa_rows_live_sum"] == st["decode_ctx_tokens_sum"] > 0
    # every decoding context here is past the 8 rows a set holds
    assert st["dsa_rows_attended_sum"] * DSA.n_layers == (
        DSA.index_topk * st["dsa_selections"])
    assert "dsa_selections" not in serve(
        params_of(DSA.replace(index_n_heads=0)), [tokens[:9]], 2,
        DSA.replace(index_n_heads=0))[1].stats


def test_the_registry_and_the_published_config_give_the_preset():
    """`glm_moe_dsa` is models/deepseek_v3.py's; the catalog row's config
    (copied here as published) builds the named preset and what the
    benchmark's family file builds from the same keys; what is not written
    is refused by name."""
    from types import SimpleNamespace

    from benchmarks.families import glm_moe_dsa as F
    from substratus_tpu.load import hf

    assert registry.HF_MODEL_TYPES["glm_moe_dsa"] == "deepseek_v3"
    assert registry.find_named_config("tiny-glm-dsa")[0] is M
    published = {
        "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 3,
        "hidden_act": "silu", "head_dim": 64, "hidden_size": 6144,
        "index_head_dim": 128, "index_n_heads": 32, "index_topk": 2048,
        "indexer_rope_interleave": True, "intermediate_size": 12288,
        "kv_lora_rank": 512, "max_position_embeddings": 202752,
        "moe_intermediate_size": 2048, "moe_layer_freq": 1,
        "model_type": "glm_moe_dsa", "n_group": 1, "n_routed_experts": 256,
        "n_shared_experts": 1, "norm_topk_prob": True,
        "num_attention_heads": 64, "num_experts_per_tok": 8,
        "num_hidden_layers": 78, "num_key_value_heads": 64,
        "num_nextn_predict_layers": 1, "q_lora_rank": 2048,
        "qk_head_dim": 256, "qk_nope_head_dim": 192, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-05, "rope_interleave": True,
        "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
        "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
        "tie_word_embeddings": False, "topk_group": 1,
        "topk_method": "noaux_tc", "v_head_dim": 256, "vocab_size": 154880}
    config_fn, convert_fn = hf._dispatch_hf("glm_moe_dsa")
    cfg = config_fn(SimpleNamespace(**published))
    assert cfg == M.CONFIGS["glm-5"]
    name, kwargs = F.program(published)
    assert registry.config_class(name)(**kwargs) == cfg
    with pytest.raises(NotImplementedError):
        convert_fn({}, cfg)
    for key, value, needle in [
            ("rope_parameters", {"rope_type": "yarn"}, "rope_type"),
            ("n_group", 8, "topk_group"), ("index_topk", 0, "index_topk")]:
        other = {**published, key: value}
        if key == "n_group":
            del other["topk_group"]
        with pytest.raises(NotImplementedError, match=needle):
            config_fn(SimpleNamespace(**other))
