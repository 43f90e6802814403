"""models/deepseek_v3.py against the plain reference
(benchmarks/reference/deepseek_v3.py, which imports nothing of the program),
on seeded random weights at a small size: 1 dense + 3 sparse layers, latent
attention of rank 16 + a rotary key of 8 under 4 heads, 8 experts in 4
groups of which 2 are kept, top 2, YaRN stretching 32 positions by 4;
chunks of 16, so contexts cross a chunk boundary, a page and the 32
positions beyond which YaRN's interpolation shows.

Everything here runs in float32 with int8 weights (the precision the
benchmark's cell states, less bfloat16 rounding), so the tolerances are
those of float32 summation order, and a lower precision fails them."""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import deepseek_v3 as R
from benchmarks.reference import glm_moe_dsa as R_DSA
from family_harness import Family, plain, seeded_params
from family_harness import table as _table
from substratus_tpu.models import deepseek_v3 as M
from substratus_tpu.models import hybrid
from substratus_tpu.models import registry
from substratus_tpu.ops import kvcache
from substratus_tpu.ops import latent_attention as LA
from substratus_tpu.ops.basics import rope_freqs, yarn_mscale
from substratus_tpu.serve.engine import Engine, EngineConfig, Request

CFG = M.CONFIGS["tiny-deepseek-v3"].replace(dtype=jnp.float32)
# The same block under a learned index (GLM-5's mechanism: the 8 best rows
# of a context; contexts here are several times that): what carries pages
# from one sequence to another has to carry the index keys with them.
DSA = M.CONFIGS["tiny-glm-dsa"].replace(dtype=jnp.float32)
BOTH = pytest.mark.parametrize("cfg", [CFG, DSA],
                               ids=["deepseek_v3", "glm_moe_dsa"])
CHUNK, PAGE = 16, 4
# The engine's tests run at the page they pinned before the family stated
# one, and at the family's own (None: what the engine reads off the module).
PAGES = pytest.mark.parametrize("page", [PAGE, None], ids=["page4", "family"])
# float32 activations, exact int8 weights: the program and the reference
# differ by summation order alone, and by the absorbed form's other order
# of the same products (measured 5e-6 on logits of magnitude 4; the limit
# leaves a factor of six). w8a8 reads 2e-2, bfloat16 1e-2.
TOL = 3e-5
F = Family(M, CFG, chunk=CHUNK, page=PAGE)
# `_forward`: the model's own forward, compiled once a shape.
_forward, prefill, decode, serve = F.forward, F.prefill, F.decode, F.serve
table = functools.partial(_table, max_pages=24)
params_of = functools.partial(seeded_params, M)


def cfg_dict(cfg: M.DeepseekV3Config, **over):
    """The configuration as the benchmark's files spell it."""
    d = dict(
        hidden_size=cfg.dim, num_hidden_layers=cfg.n_layers,
        num_attention_heads=cfg.n_heads, q_lora_rank=cfg.q_lora_rank,
        kv_lora_rank=cfg.kv_lora_rank,
        qk_nope_head_dim=cfg.qk_nope_head_dim,
        qk_rope_head_dim=cfg.qk_rope_head_dim, v_head_dim=cfg.v_head_dim,
        intermediate_size=cfg.hidden_dim,
        moe_intermediate_size=cfg.moe_hidden_dim,
        first_k_dense_replace=cfg.first_k_dense,
        n_shared_experts=cfg.n_shared_experts, vocab_size=cfg.vocab_size,
        n_routed_experts=cfg.held_experts[1],
        published={"n_routed_experts": cfg.n_experts},
        layout={"experts_held": list(cfg.held_experts)},
        num_experts_per_tok=cfg.n_experts_per_token,
        n_group=cfg.n_group, topk_group=cfg.topk_group,
        rope_theta=cfg.rope_theta,
        rope_scaling={
            "type": "yarn", "factor": cfg.rope_factor,
            "original_max_position_embeddings": cfg.rope_original_max,
            "beta_fast": cfg.rope_beta_fast, "beta_slow": cfg.rope_beta_slow,
            "mscale": cfg.rope_mscale,
            "mscale_all_dim": cfg.rope_mscale_all_dim},
        rms_norm_eps=cfg.norm_eps,
        routed_scaling_factor=cfg.routed_scaling_factor,
        norm_topk_prob=cfg.norm_topk_prob,
    )
    if cfg.index_n_heads:  # as `glm_moe_dsa` spells it
        del d["rope_scaling"], d["rope_theta"]
        d.update(rope_parameters={"rope_theta": cfg.rope_theta,
                                  "rope_type": "default"},
                 index_n_heads=cfg.index_n_heads,
                 index_head_dim=cfg.index_head_dim,
                 index_topk=cfg.index_topk)
    d.update(over)
    return d


def reference_of(cfg):
    return R_DSA if cfg.index_n_heads else R


@pytest.fixture(scope="module")
def params():
    return params_of(CFG)


@pytest.fixture(scope="module")
def tokens():
    return np.asarray(jax.random.randint(jax.random.key(1), (80,), 0,
                                         CFG.vocab_size))


@pytest.fixture(scope="module")
def long_tokens():
    """Enough of them to fill a page of the family's own and go on."""
    return np.asarray(jax.random.randint(jax.random.key(2), (160,), 0,
                                         CFG.vocab_size))


def new_cache(cfg=CFG, pages=80):
    return M.init_paged_cache(cfg, pages, PAGE)


def reference_logits(params, cfg, toks, **kw):
    return np.asarray(reference_of(cfg).logits_at(
        plain(params), cfg_dict(cfg), list(toks), list(range(len(toks))),
        pad_to=8, block=16, group=2, **kw))


# -- (a) the whole sequence at once against the reference ------------------------

def test_forward_matches_the_reference(params, tokens):
    """No cache: the expanded form over the whole sequence, 70 tokens (past
    the 32 positions YaRN stretches), logits at every position."""
    toks = tokens[:70]
    got, left = F.forward(params, jnp.asarray(toks)[None], CFG)
    assert left == {}
    ref = reference_logits(params, CFG, toks)
    assert np.abs(np.asarray(got[0]) - ref).max() < TOL


# -- (b) chunks, then decode steps through the latent pool -----------------------

@pytest.mark.parametrize("prompt_len", [5, 16, 37, 50])
def test_chunked_prefill_then_decode_matches_the_reference(
        params, tokens, prompt_len):
    """Chunks at offsets 0 and beyond (the expanded form over what the pool
    holds), then decode steps (the absorbed form), in slot 1 of 3: every
    position's logits are the reference's full forward pass."""
    bt = table(3)
    total = prompt_len + 14
    ref = reference_logits(params, CFG, tokens[:total])
    got, cache = prefill(params, CFG, new_cache(), tokens[:prompt_len], 1, bt)
    assert np.abs(got - ref[:prompt_len]).max() < TOL
    for pos in range(prompt_len, total):
        step, cache, stats = decode(params, CFG, cache, tokens[pos], pos, 1, bt)
        assert np.abs(step - ref[pos]).max() < TOL, pos
    # every expert is held: the live row's pairs all landed here
    assert int(stats["moe_pairs_held"]) == int(stats["moe_pairs_all"]) == (
        CFG.count(M.SPARSE) * CFG.n_experts_per_token)


def test_the_absorbed_and_the_expanded_form_of_a_layer_agree(params, tokens):
    """One layer's attention over one pool, the last token asked both ways:
    as a decode step (one query: absorbed) and as the last of two queries
    (expanded). The same numbers up to the order of the products."""
    lp = hybrid.take(params["layers"], jnp.int32(2))
    key = jax.random.key(5)
    n, h = 23, CFG.n_heads
    dq = CFG.head_size
    q = jax.random.normal(key, (1, n, h, dq), jnp.float32)
    rows = jax.random.normal(jax.random.fold_in(key, 1),
                             (1, n, CFG.latent_row), jnp.float32)
    bt = jnp.asarray(table(1))
    pos = jnp.arange(n, dtype=jnp.int32)[None]
    pool = new_cache()
    args = (lp["w_uk"], lp["w_uv"], CFG.softmax_scale, jnp.float32)
    pool, _ = kvcache.latent_attention(
        pool, jnp.int32(2), bt, pos[:, :-2], q[:, :-2], rows[:, :-2], *args)
    _, two = kvcache.latent_attention(
        pool, jnp.int32(2), bt, pos[:, -2:], q[:, -2:], rows[:, -2:], *args)
    pool, _ = kvcache.latent_attention(
        pool, jnp.int32(2), bt, pos[:, -2:-1], q[:, -2:-1], rows[:, -2:-1],
        *args)
    _, one = kvcache.latent_attention(
        pool, jnp.int32(2), bt, pos[:, -1:], q[:, -1:], rows[:, -1:], *args)
    assert one.shape == (1, 1, h, CFG.v_head_dim)
    assert np.abs(np.asarray(one[0, 0] - two[0, 1])).max() < 1e-5


def test_a_bfloat16_pool_stores_the_row_in_whole_lane_tiles():
    """`init_latent_cache` is the one place that decides: 576 values lie in
    640 (five tiles of 128 lanes), zeros behind them; a float32 pool as
    declared; `v` holds no layer either way."""
    pool = kvcache.init_latent_cache(3, 5, 16, 576, jnp.bfloat16)
    assert pool["k"].shape == (3, 5, 16, 1, 640)
    assert pool["v"].shape == (0, 5, 16, 1, 640)
    assert kvcache.init_latent_cache(3, 5, 16, 576, jnp.float32)[
        "k"].shape == (3, 5, 16, 1, 576)
    full = M.DeepseekV3Config()
    assert full.latent_row == 576
    assert math.isclose(full.softmax_scale, 192 ** -0.5 * 1.3689 ** 2,
                        rel_tol=1e-4)


def test_a_stored_row_wider_than_the_logical_one_changes_nothing(
        params, tokens):
    """bfloat16 end to end at the tiny size: the pool's row of 24 lies in
    128 lanes, zeros behind it, and chunks and steps give what they give
    over a pool stored as declared (the same bfloat16 rows, the same
    float32 sums: to the last bit but for the order of a padded sum)."""
    cfg = CFG.replace(dtype=jnp.bfloat16)
    wide = new_cache(cfg)
    assert wide["k"].shape[-1] == 128 and wide["k"].dtype == jnp.bfloat16
    declared = {name: a[..., :CFG.latent_row] for name, a in wide.items()}
    bt = table(3)
    outs = []
    for cache in (wide, declared):
        rows, cache = prefill(params, cfg, cache, tokens[:37], 1, bt)
        step, cache, _ = decode(params, cfg, cache, tokens[37], 37, 1, bt)
        outs.append(np.concatenate([rows, step[None]]))
        assert not np.asarray(cache["k"][..., CFG.latent_row:]).any()
    assert np.abs(outs[0] - outs[1]).max() < 1e-5


@pytest.mark.parametrize("lower", ["w8a8", "bfloat16"])
def test_a_lower_precision_fails_the_tolerance(params, tokens, lower):
    cfg = (CFG.replace(quant_activations=True) if lower == "w8a8"
           else CFG.replace(dtype=jnp.bfloat16))
    got, _ = F.forward(params, jnp.asarray(tokens[:40])[None], cfg)
    ref = reference_logits(params, CFG, tokens[:40])
    assert np.abs(np.asarray(got[0]) - ref).max() > 10 * TOL


def test_decode_step_is_forward_for_one_token_a_slot(params, tokens):
    bt = table(3)
    _, cache = prefill(params, CFG, new_cache(), tokens[:20], 0, bt)
    toks = jnp.asarray([tokens[20], 0, 0], jnp.int32)
    pos = jnp.asarray([20, 0, 0], jnp.int32)
    table3 = jnp.asarray(np.where(np.arange(3)[:, None] == 0, bt, 0))
    want, _ = M.forward(params, toks[:, None], CFG, positions=pos[:, None],
                        cache=jax.tree.map(jnp.copy, cache),
                        block_table=table3)
    got, after = M.decode_step(params, cache, toks, pos, CFG, table3)
    assert np.abs(np.asarray(got) - np.asarray(want[:, 0])).max() < 1e-5
    assert set(after) == {"k", "v"}


# -- the kernels, interpreted, against the gathered forms ------------------------

def _kernel_case(key, b=3, h=8, dn=32, dr=16, dv=32, rkv=128, pages=80,
                 m=40):
    ks = jax.random.split(key, 5)
    layers, bs = 2, 16
    pool = kvcache.init_latent_cache(layers, pages, bs, rkv + dr, jnp.bfloat16)
    rows = jax.random.normal(ks[0], (layers, pages, bs, 1, rkv + dr))
    pool["k"] = pool["k"].at[..., :rkv + dr].set(rows.astype(jnp.bfloat16))
    bt = jax.random.permutation(ks[1], jnp.arange(1, pages))[:b * m // 2]
    bt = jnp.concatenate([bt.reshape(b, m // 2),
                          jnp.zeros((b, m // 2), jnp.int32)], 1)
    w = jax.random.normal(ks[2], (h, dn + dv, rkv)) * rkv ** -0.5
    w = w.astype(jnp.bfloat16)
    return pool, bt.astype(jnp.int32), w[:, :dn], w[:, dn:], ks[3], ks[4]


def _both_ways(monkeypatch, pool, bt, w_uk, w_uv, q, new, positions):
    """(gathered, in place): `latent_attention`'s two realisations, the
    kernels interpreted."""
    args = (pool, jnp.int32(1), bt, positions, q, new, w_uk, w_uv, 0.1,
            jnp.bfloat16)
    with monkeypatch.context() as mp:
        mp.setattr(kvcache, "_latent_kernels_for", lambda *a: None)
        gathered = kvcache.latent_attention(*args)[1]
    with monkeypatch.context() as mp:
        mp.setattr(jax.lax, "platform_dependent",
                   lambda *a, tpu, default: tpu(*a))
        for name in ("latent_decode_attention", "latent_chunk_attention"):
            mp.setattr(kvcache, name, lambda *a, _f=getattr(LA, name), **k:
                       _f(*a, interpret=True, **k))
        in_place = kvcache.latent_attention(*args)[1]
    return (np.asarray(gathered, np.float32), np.asarray(in_place, np.float32))


@pytest.mark.parametrize("pages,m,last", [(80, 40, [0, 37, 300]),
                                          (500, 320, [1023, 1061, 2300])],
                         ids=["a-block", "blocks"])
def test_the_decode_kernel_is_the_gathered_absorbed_form(monkeypatch, pages,
                                                         m, last):
    """Rows of one page and of a few, one idle at position 0; then rows
    that end with a block of 64 pages, one page into the next and in the
    third (every size the kernel folds at once, a full block before a
    short one): bfloat16's rounding of the read-out apart."""
    assert LA.fold_pages(16, LA.DECODE_FOLD_TOKENS) == (2, 8, 32, 64)
    pool, bt, w_uk, w_uv, kq, kn = _kernel_case(jax.random.key(0),
                                                pages=pages, m=m)
    q = jax.random.normal(kq, (3, 1, 8, 48)).astype(jnp.bfloat16)
    new = jax.random.normal(kn, (3, 1, 144)).astype(jnp.bfloat16)
    pos = jnp.asarray(last, jnp.int32)[:, None]
    want, got = _both_ways(monkeypatch, pool, bt, w_uk, w_uv, q, new, pos)
    assert np.abs(got - want).max() < 2e-2 * max(1.0, np.abs(want).max())


@pytest.mark.parametrize("s,offset", [(40, 0), (40, 200), (130, 150)])
def test_the_chunk_kernel_is_the_gathered_expanded_form(monkeypatch, s,
                                                        offset):
    """Chunks at offset 0 and behind a context that spans blocks, a padded
    tail clamped onto one position, three rows of different contexts."""
    pool, bt, w_uk, w_uv, kq, kn = _kernel_case(jax.random.key(1))
    q = jax.random.normal(kq, (3, s, 8, 48)).astype(jnp.bfloat16)
    new = jax.random.normal(kn, (3, s, 144)).astype(jnp.bfloat16)
    pos = jnp.stack([jnp.arange(s) + o for o in (0, offset, offset // 2)])
    pos = jnp.minimum(pos, pos[:, :1] + s - 5).astype(jnp.int32)
    want, got = _both_ways(monkeypatch, pool, bt, w_uk, w_uv, q, new, pos)
    real = slice(0, s - 5)  # the clamped tail's rows are nobody's
    assert np.abs(got[:, real] - want[:, real]).max() < 2e-2 * max(
        1.0, np.abs(want).max())


def _paged(key, bs, tokens, width, layers=2):
    """A seeded bfloat16 pool [layers, pages, bs, 1, width] and, for rows
    that hold `tokens` tokens each, block tables of scattered pages (a row
    of no token idles: the trash page in every entry)."""
    m = max(-(-t // bs) for t in tokens) + 1
    pages = 1 + sum(-(-t // bs) for t in tokens)
    kp, kt = jax.random.split(key)
    pool = jax.random.normal(kp, (layers, pages, bs, 1, width))
    own = iter(np.asarray(jax.random.permutation(kt, np.arange(1, pages))))
    bt = np.zeros((len(tokens), m), np.int32)
    for b, t in enumerate(tokens):
        for i in range(-(-t // bs)):
            bt[b, i] = next(own)
    return pool.astype(jnp.bfloat16), jnp.asarray(bt)


def _rows_of(pool, layer, bt):
    """[B, M * bs, width] float32: every row's context, gathered."""
    b, m = bt.shape
    got = np.asarray(pool, np.float32)[layer][np.asarray(bt)]
    return got.reshape(b, m * pool.shape[2], pool.shape[4])


@pytest.mark.parametrize("bs", [16, 64, 128])
@pytest.mark.parametrize("kernel", ["decode", "chunk", "chunk-bias", "index"])
def test_a_kernel_reads_pages_of_any_size_as_its_xla_form(kernel, bs):
    """The three kernels that walk a row's pages, interpreted, against
    plain float32 forms over the gathered rows, at the size every other
    family keeps, at this family's 128 and between: contexts that end
    inside a page, on a page's last token and on the next one's first, past
    a DMA block, a row of one token and an idle row (position 0 over the
    trash page)."""
    from substratus_tpu.ops import sparse_index as SI

    h, dn, dr, dv, rkv, w = 8, 32, 16, 32, 128, 256
    key = jax.random.key(bs)
    kq, kp, kw, kb = jax.random.split(key, 4)
    layer = jnp.int32(1)

    def close(got, want):
        got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
        assert np.abs(got - want).max() < 2e-2 * max(1.0, np.abs(want).max())

    if kernel in ("decode", "index"):
        # the last token seen: mid-page, a page's last, the next page's
        # first, one page into a second DMA block of 1,024 tokens, the only
        # token of a row, and an idle row
        last = [3 * bs + 5, 2 * bs - 1, 2 * bs, 1024 + bs + 3, 0, 0]
        tokens = [p + 1 for p in last[:-1]] + [0]
        pos = jnp.asarray(last, jnp.int32)
        pool, bt = _paged(kp, bs, tokens, w if kernel == "decode" else 128)
        seen = np.arange(bt.shape[1] * bs)[None] <= np.asarray(last)[:, None]
    if kernel == "decode":
        pool = pool.at[..., rkv + dr:].set(0)
        qa = jax.random.normal(kq, (len(last), h, w)).astype(jnp.bfloat16)
        got = LA.latent_decode_attention(
            qa, pool, layer, bt, pos, rkv=rkv, scale=0.1, interpret=True)
        lat = _rows_of(pool, 1, bt)
        sc = np.einsum("bhw,btw->bht", np.asarray(qa, np.float32), lat) * 0.1
        p = jax.nn.softmax(jnp.where(seen[:, None], sc, -1e30), axis=-1)
        close(got, np.einsum("bht,btc->bhc", p, lat[..., :rkv]))
    elif kernel == "index":
        hi, di = 4, 128
        qi = jax.random.normal(kq, (len(last), hi, di)).astype(jnp.bfloat16)
        wi = jax.random.normal(kw, (len(last), hi))
        got = SI.index_decode_scores(qi, wi, pool, layer, bt, pos,
                                     interpret=True)
        want = SI.scores(qi[:, None].astype(jnp.float32),
                         jnp.asarray(_rows_of(pool, 1, bt)), wi[:, None])[:, 0]
        assert got.shape == want.shape
        assert (np.asarray(got)[~seen] == -np.inf).all()
        close(np.where(seen, got, 0), np.where(seen, want, 0))
    else:
        # S queries a row: from position 0 inside one page, behind a
        # context that ends on a page's edge, behind one past a block of
        # 512 keys; the tail clamped onto one position as a padded chunk's
        s, first = 24, [0, 3 * bs - 24, 512 + bs + 7]
        pos = np.stack([np.arange(s) + f for f in first])
        pos = np.minimum(pos, pos[:, :1] + s - 5).astype(np.int32)
        pool, bt = _paged(kp, bs, [f + s for f in first], w)
        pool = pool.at[..., rkv + dr:].set(0)
        q = jax.random.normal(kq, (3, s, h, dn + dr)).astype(jnp.bfloat16)
        w_ukv = (jax.random.normal(kw, (h, dn + dv, rkv)) * rkv ** -0.5
                 ).astype(jnp.bfloat16)
        t = bt.shape[1] * bs
        seen = np.arange(t)[None, None] <= pos[:, :, None]  # [B, S, T]
        bias = None
        if kernel == "chunk-bias":
            # every query keeps its own position and a random half
            kept = np.asarray(jax.random.bernoulli(kb, 0.5, (3, s, t)))
            kept = kept | (np.arange(t)[None, None] == pos[:, :, None])
            seen = seen & kept
            bias = jnp.where(kept, 0.0, -1e30).transpose(0, 2, 1)
        got = LA.latent_chunk_attention(
            q, w_ukv, pool, layer, bt, jnp.asarray(pos), bias, dn=dn,
            scale=0.1, interpret=True)
        lat = _rows_of(pool, 1, bt)
        kv = np.einsum("btc,hmc->bthm", lat[..., :rkv],
                       np.asarray(w_ukv, np.float32))
        qf = np.asarray(q, np.float32)
        sc = (np.einsum("bshn,bthn->bsht", qf[..., :dn], kv[..., :dn])
              + np.einsum("bshr,btr->bsht", qf[..., dn:],
                          lat[..., rkv:rkv + dr])) * 0.1
        p = jax.nn.softmax(jnp.where(seen[:, :, None], sc, -1e30), axis=-1)
        want = np.einsum("bsht,bthv->bshv", p, kv[..., dn:])
        real = slice(0, s - 5)  # the clamped tail's rows are nobody's
        close(np.asarray(got)[:, real], want[:, real])


# -- (c) the shares add up -------------------------------------------------------

def sparse_layer(params, i=0):
    """(the program's stack of sparse layers, the reference's layer i)."""
    return params["moe"], jax.tree.map(lambda a: a[i], plain(params["moe"]))


@pytest.mark.parametrize("seq", [12, 40], ids=["every", "grouped"])
def test_every_share_adds_up_to_the_uncut_layer(params, seq):
    """Each of 8 ranks holds 1 of the 8 experts (as each of 32 holds 8 of
    256), routes over all of them under the group limit and computes its
    own part; the parts, with the shared expert counted once, are the
    uncut reference's sparse layer, in both ways of multiplying."""
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
    for rank in range(CFG.n_experts):
        cfg = CFG.replace(held_experts=(rank, 1))
        share = dict(mp)
        for name in hybrid.EXPERT_LEAVES:
            share[name] = jax.tree.map(lambda a: a[:, rank:rank + 1], mp[name])
        y, stats = hybrid.moe(h, share, jnp.int32(0), cfg, valid, M.qeinsum)
        total += np.asarray(y).reshape(t, -1) - shared
        held += int(stats["moe_pairs_held"])
        assert int(stats["moe_pairs_all"]) == t * CFG.n_experts_per_token
    assert np.abs(total + shared - np.asarray(whole)).max() < TOL
    assert held == t * CFG.n_experts_per_token  # every pair landed once


# -- (d) the router's group limit ------------------------------------------------

def _route_by_loop(s, bias, groups, kept, k):
    """The published selection, a token and a group at a time; on a tie the
    lower index wins, as `lax.top_k`."""
    t, e = s.shape
    size = e // groups
    c = s + bias
    out = np.zeros((t, k), np.int64)
    for i in range(t):
        score = [np.sort(c[i, g * size:(g + 1) * size])[-2:].sum()
                 for g in range(groups)]
        best = sorted(range(groups), key=lambda g: (-score[g], g))[:kept]
        masked = np.zeros(e, c.dtype)
        for g in best:
            masked[g * size:(g + 1) * size] = c[i, g * size:(g + 1) * size]
        out[i] = sorted(range(e), key=lambda j: (-masked[j], j))[:k]
    return out


@pytest.mark.parametrize("ties", [False, True])
def test_the_group_limit_is_the_published_selection(ties):
    """16 experts in 4 groups, 2 groups kept, top 3: `route` against a
    plain loop. With ties: scores rounded to quarters, so groups tie and
    experts tie, and the lower index has to win both."""
    class Cfg:
        n_experts_per_token, n_group, topk_group = 3, 4, 2
        norm_topk_prob, route_norm_eps, routed_scaling_factor = True, 1e-20, 2.5

    key = jax.random.key(7)
    h = jax.random.normal(key, (64, 24), jnp.float32)
    router = jax.random.normal(jax.random.fold_in(key, 1), (24, 16))
    bias = 0.1 * jax.random.normal(jax.random.fold_in(key, 2), (16,))
    if ties:
        h = jnp.round(h)
        router, bias = jnp.round(router * 0.5), jnp.round(bias * 4) / 4
    idx, w = hybrid.route(h, router, bias, Cfg)
    s = np.asarray(jax.nn.sigmoid(h @ router))
    want = _route_by_loop(s, np.asarray(bias), 4, 2, 3)
    assert (np.asarray(idx) == want).all()
    chosen = np.take_along_axis(s, want, axis=1)
    assert np.abs(np.asarray(w) - 2.5 * chosen / chosen.sum(
        1, keepdims=True)).max() < 1e-6
    if ties:
        assert len(np.unique(s + np.asarray(bias))) < s.size // 4
    # the reference's router makes the same choice
    ref = np.asarray(R.route(h, router, bias, 3, 2.5, True, 4, 2))
    assert ((ref > 0).sum(1) == 3).all()
    assert (np.sort(np.argsort(-ref, axis=1)[:, :3]) == np.sort(want)).all()


def test_without_groups_route_traces_what_it_traced():
    """`n_group` = 1, or a configuration that has no such attribute (the
    held expert families'): the same jaxpr as the plain top k of score +
    bias, operation for operation."""
    class Old:
        n_experts_per_token = 4
        norm_topk_prob, route_norm_eps, routed_scaling_factor = True, 1e-20, 2.5

    class One(Old):
        n_group, topk_group = 1, 1

    def before(h, router, bias, cfg):
        s = jax.nn.sigmoid(jnp.einsum(
            "td,de->te", h.astype(jnp.float32), router.astype(jnp.float32)))
        _, idx = jax.lax.top_k(s + bias.astype(jnp.float32),
                               cfg.n_experts_per_token)
        w = jnp.take_along_axis(s, idx, axis=-1)
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + cfg.route_norm_eps)
        return idx.astype(jnp.int32), w * cfg.routed_scaling_factor

    args = (jnp.ones((5, 8)), jnp.ones((8, 16)), jnp.ones((16,)))
    want = str(jax.make_jaxpr(lambda *a: before(*a, Old))(*args))
    for cfg in (Old, One):
        assert str(jax.make_jaxpr(
            lambda *a: hybrid.route(*a, cfg))(*args)) == want


# -- (e) YaRN --------------------------------------------------------------------

def test_yarn_frequencies_are_the_closed_form():
    """dr = 64, theta 1e4, factor 40 over 4,096 positions, beta 32 and 1:
    lo = floor(10.47) = 10, hi = ceil(22.52) = 23. Pair 5 (below lo) keeps
    its frequency, pair 16 (inside the ramp) mixes (16 - 10) / 13 of the
    stretched one, pair 28 (above hi) is stretched whole."""
    yarn = (40.0, 4096, 32.0, 1.0)
    got = np.asarray(rope_freqs(64, 1e4, yarn))
    f = lambda i: 1e4 ** (-2 * i / 64)  # noqa: E731
    at = lambda beta: 64 * math.log(4096 / (beta * 2 * math.pi)) / (  # noqa: E731
        2 * math.log(1e4))
    assert (math.floor(at(32)), math.ceil(at(1))) == (10, 23)
    assert math.isclose(got[5], f(5), rel_tol=1e-6)
    ramp = (16 - 10) / 13
    assert math.isclose(got[16], f(16) / 40 * ramp + f(16) * (1 - ramp),
                        rel_tol=1e-6)
    assert math.isclose(got[28], f(28) / 40, rel_tol=1e-6)
    assert math.isclose(yarn_mscale(40.0, 1.0), 1.3689, rel_tol=1e-4)
    assert yarn_mscale(1.0, 1.0) == 1.0
    # the reference's own table, written apart from the program's
    assert np.allclose(got, R.yarn_frequencies(64, 1e4, yarn + (1.0, 1.0)),
                       rtol=1e-6)


def test_without_yarn_the_table_is_todays_bit_for_bit():
    exponent = jnp.arange(0, 64, 2, dtype=jnp.float32) / 64
    want = np.asarray(1.0 / (1e4 ** exponent))
    assert (np.asarray(rope_freqs(64, 1e4)) == want).all()
    assert (np.asarray(rope_freqs(64, 1e4, None)) == want).all()


# -- (f) the engine ---------------------------------------------------------------

@PAGES
def test_the_engine_serves_the_family_through_submit(params, tokens, page):
    """Engine.submit/start, chunked prefill, jit_decode, overlap: every
    served token is the reference's best at its position (float32: a gap
    above 1e-4 is a wrong token, not rounding), three requests in flight
    whose contexts cross a chunk and YaRN's 32 positions (at the family's
    page a chunk is an eighth of a page and no context leaves its first)."""
    prompts = [tokens[:37], tokens[3:26], tokens[40:49]]
    outs, eng = serve(params, prompts, 20, page_size=page)
    assert eng.page_size == (page or M.PAGE_TOKENS)
    for p, ids in zip(prompts, outs):
        assert len(ids) == 20
        gaps = R.served_gaps(plain(params), cfg_dict(CFG), list(p), ids)
        assert gaps.max() < 1e-4
    st = eng.stats
    assert st["preemptions"] == 0 and st["prefix_hit_tokens"] == 0
    assert "prefix_reuse_refused" not in st  # the registry is on
    assert not eng.slot_state and eng.prefix is not None
    assert st["moe_pairs_held"] == st["moe_pairs_all"] > 0
    assert st["moe_decode_steps"] > 0
    # a chunk's context is its last query's position + 1, and a padded
    # tail sits one past the prompt: 37 tokens in chunks of 16 read 16, 32
    # and 38, and so on for the other two
    assert st["chunk_count"] == 3 + 2 + 1
    assert st["chunk_ctx_tokens_sum"] == (16 + 32 + 38) + (16 + 24) + 10
    # a step attends position + 1 tokens a decoding slot
    assert st["decode_ctx_tokens_sum"] >= st["decode_steps"] > 0
    # one pool of latent rows, every layer in it; no second pool
    assert eng.cache["k"].shape == (CFG.n_layers, eng.n_pages + 1,
                                    eng.page_size, 1, CFG.latent_row)
    assert eng.cache["v"].shape[0] == 0


def test_the_pool_says_what_a_token_keeps(params):
    from substratus_tpu.observability.metrics import METRICS

    Engine(CFG, params, EngineConfig(
        max_batch=2, max_seq_len=32, max_prefill_len=CHUNK, page_size=PAGE),
        model=M)
    assert METRICS.get("substratus_serve_kv_bytes_per_token") == (
        CFG.n_layers * CFG.latent_row * 4)  # float32 here
    assert METRICS.get("substratus_serve_kv_heads_per_pool_row") == CFG.n_heads
    assert METRICS.get("substratus_serve_slot_state_bytes") == 0


@PAGES
@BOTH
def test_a_shared_prefix_is_served_from_its_pages(cfg, tokens, long_tokens,
                                                  page):
    """Pages carry everything: a second request that shares 32 tokens with
    the first (a page of 128 at the family's: whole pages are what is
    shared) takes their pages from the registry, prefills the rest at an
    offset (the expanded form over pages it did not write), and serves the
    tokens of an engine that reuses nothing. Under an index the reused
    pages bring their index keys: the second request's queries pick their
    8 rows among tokens it never scored a key for."""
    params = params_of(cfg)
    if page:
        shared, a, b, seq = 32, 40, 50, 96
    else:
        tokens, shared, a, b, seq = long_tokens, M.PAGE_TOKENS, 128, 133, 192
    prompts = [np.concatenate([tokens[:shared], tokens[a:a + 7]]),
               np.concatenate([tokens[:shared], tokens[b:b + 11]])]
    cold = [serve(params, [p], 12, cfg, prefix_cache=False, page_size=page,
                  max_seq_len=seq)[0][0] for p in prompts]
    eng = Engine(cfg, params, EngineConfig(
        max_batch=3, max_seq_len=seq, max_prefill_len=CHUNK, page_size=page),
        model=M)
    eng.start()
    warm = []
    for p in prompts:  # one after the other: the first registers its pages
        r = eng.submit(Request(prompt_tokens=[int(t) for t in p],
                               max_tokens=12, temperature=0.0,
                               eos_token_id=-1))
        ids = []
        while (t := r.out.get(timeout=300)) is not None:
            ids.append(t)
        warm.append(ids)
    eng.stop()
    assert eng.error is None
    assert eng.stats["prefix_hit_tokens"] == shared
    assert warm == cold


def test_a_resumed_sequence_gives_the_same_logits(params, tokens):
    """Preempt-and-resume prefills prompt + emitted tokens again from
    position 0 into pages another sequence has used: the next logits are
    those of the sequence that was never interrupted."""
    bt = table(3)
    _, cache = prefill(params, CFG, new_cache(), tokens[:37], 0, bt)
    for pos in range(37, 49):
        through, cache, _ = decode(params, CFG, cache, tokens[pos], pos, 0, bt)
    _, cache = prefill(params, CFG, cache, tokens[5:64], 0, bt)
    again, cache = prefill(params, CFG, cache, tokens[:49], 0, bt)
    assert np.abs(again[-1] - through).max() < TOL
    nxt, _, _ = decode(params, CFG, cache, tokens[49], 49, 0, bt)
    ref = reference_logits(params, CFG, tokens[:50])
    assert np.abs(nxt - ref[49]).max() < TOL


@PAGES
@BOTH
def test_the_engine_preempts_and_resumes_token_exact(cfg, tokens,
                                                     long_tokens, page):
    """A pool too small for three sequences: the engine preempts, prefills
    the victim again from 0 (rows and index keys alike, into pages another
    sequence has used), and serves the tokens of a roomy pool. At the
    family's page the pool is three pages and every sequence grows into a
    second."""
    params = params_of(cfg)
    if page:
        n, seq = 30, 96
    else:
        tokens, n, seq = long_tokens, 110, 192
    prompts = [tokens[:n], tokens[10:n + 8], tokens[20:n + 15]]
    roomy, _ = serve(params, prompts, 24, cfg, prefix_cache=False,
                     page_size=page, max_seq_len=seq)
    tight, eng = serve(params, prompts, 24, cfg, kv_pool_tokens=120,
                       prefix_cache=False, page_size=page, max_seq_len=seq)
    assert eng.stats["preemptions"] >= 1
    assert tight == roomy


@pytest.mark.parametrize("what,needle", [
    ("spec", "speculative"), ("role", "role="), ("dense", "dense"),
    ("int8", "int8")])
def test_what_is_not_written_for_a_latent_row_is_refused_by_name(
        params, what, needle):
    """Speculation's verify round, the roles' page handoff, a dense slot
    cache and an int8 pool: refused at start-up, with the family's name."""
    ec = {"spec": EngineConfig(spec_k=2),
          "role": EngineConfig(role="decode"),
          "dense": EngineConfig(kv_layout="dense"),
          "int8": EngineConfig(kv_cache_dtype="int8")}[what]
    with pytest.raises(ValueError, match=needle) as e:
        Engine(CFG, params, ec, model=M)
    assert "deepseek_v3" in str(e.value)


def test_the_registry_knows_the_family():
    assert registry.module_for("deepseek_v3") is M
    assert registry.HF_MODEL_TYPES["deepseek_v3"] == "deepseek_v3"
    assert registry.HF_MODEL_TYPES["dots_vlm"] == "deepseek_v3"
    assert registry.config_class("deepseek_v3") is M.DeepseekV3Config
    assert registry.family_of(CFG) == "deepseek_v3"
    assert registry.find_named_config("tiny-deepseek-v3")[0] is M


@pytest.mark.parametrize("layers,dense,plan", [
    (16, 3, (3, 1, 13)), (61, 3, (3, 1, 58)), (4, 1, (1, 1, 3))])
def test_layer_plan_is_a_dense_head_and_one_scanned_body(layers, dense, plan):
    cfg = M.DeepseekV3Config(n_layers=layers, first_k_dense=dense)
    assert M.layer_plan(cfg) == plan
    assert (cfg.count(M.DENSE), cfg.count(M.SPARSE)) == (dense, layers - dense)


def test_a_published_config_json_gives_the_named_preset():
    """load/hf.py reads `model_type: dots_vlm` (and `deepseek_v3`): the
    catalog's keys of dots.vlm1.inst are the named preset."""
    from types import SimpleNamespace

    from substratus_tpu.load import hf

    published = SimpleNamespace(
        model_type="dots_vlm", attention_bias=False, first_k_dense_replace=3,
        hidden_size=7168, intermediate_size=18432, kv_lora_rank=512,
        max_position_embeddings=163840, moe_intermediate_size=2048,
        moe_layer_freq=1, n_group=8, n_routed_experts=256,
        n_shared_experts=1, norm_topk_prob=True, num_attention_heads=128,
        num_experts_per_tok=8, num_hidden_layers=61, q_lora_rank=1536,
        qk_nope_head_dim=128, qk_rope_head_dim=64, rms_norm_eps=1e-06,
        rope_scaling={"beta_fast": 32, "beta_slow": 1, "factor": 40,
                      "mscale": 1, "mscale_all_dim": 1,
                      "original_max_position_embeddings": 4096,
                      "type": "yarn"},
        rope_theta=10000, routed_scaling_factor=2.5, scoring_func="sigmoid",
        tie_word_embeddings=False, topk_group=4, topk_method="noaux_tc",
        v_head_dim=128, vocab_size=129280)
    config_fn, convert_fn = hf._dispatch_hf("dots_vlm")
    assert config_fn(published) == M.CONFIGS["deepseek-v3"]
    with pytest.raises(NotImplementedError):
        convert_fn({}, M.CONFIGS["deepseek-v3"])
