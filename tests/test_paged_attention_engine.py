"""The whole engine with the kernels of ops/paged_attention.py forced in
place of the gather (interpret mode), token for token against the gather
path; the kernels alone are tests/test_paged_attention.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from family_harness import submit_all
from substratus_tpu.ops import kvcache
from substratus_tpu.serve.engine import Engine, EngineConfig


def _force_the_kernel(monkeypatch):
    """On the CPU the platform choice (ops/kvcache.py::paged_attention)
    takes the gather; a test steers it to the kernel, interpreted. The
    chunk program is one jit for every engine of a process
    (Engine._chunk_prefill_jit), so what was traced before is dropped."""
    monkeypatch.setattr(
        jax.lax, "platform_dependent",
        lambda *args, tpu, default: tpu(*args))
    jax.clear_caches()


def _greedy(model, cfg, params, prompts, max_tokens, **ec):
    eng = Engine(cfg, params, EngineConfig(**ec), model=model)
    eng.start()
    outs = submit_all(eng, prompts, max_tokens)
    eng.stop()
    assert eng.error is None
    return outs, eng


@pytest.mark.parametrize(
    "family", ["llama", "exaone_moe", "llama-hd64", "llama-hd64-page64"])
def test_the_engine_serves_the_same_tokens_through_the_kernel(
    family, monkeypatch, pallas_interpret
):
    """Greedy tokens of a tiny paged engine, prefill chunks and decode
    steps through the kernels, equal those of the gather path: three
    requests of unlike lengths over four slots, so one row idles
    throughout; the longest prompt takes three chunks. Heads are 128 wide,
    or 64 wide in a pool that stores them two to a row (4 KV heads: two
    rows a token; once more in pages of 64 tokens, what the families with
    such a pool state, over prompts four times as long: rows of three
    pages, two and one, blocks of 8 pages); any other pool the op leaves on
    the gather path. The sparse family
    routes every token to all its experts here: the kernels' outputs lie
    within one bfloat16 rounding of the gather's, and on random weights
    that flips a top-4-of-16 choice every few tokens, which says nothing
    of attention. (The prompts' seed matters: on random weights two logits
    now and then lie within one bfloat16 rounding of an attention output,
    and of twelve seeded sets two flipped one request's token there; at
    heads of 64, seeds 3 and 4 of 3 .. 10 did, with logits as far from the
    gather's as at heads of 128: 0.03.)"""
    from substratus_tpu.models import exaone_moe, llama

    long = 4 if family.endswith("page64") else 1
    if family == "llama":
        model, cfg = llama, llama.CONFIGS["tiny"].replace(dim=512)
    elif family.startswith("llama-hd64"):
        model, cfg = llama, llama.CONFIGS["tiny"].replace(
            dim=512, n_heads=8, n_kv_heads=4, max_seq_len=96 * long)
    else:
        model = exaone_moe
        cfg = exaone_moe.CONFIGS["tiny-exaone-moe"].replace(
            head_dim=128, n_experts_per_token=16)
    assert cfg.dtype == jnp.bfloat16
    assert cfg.head_size == (64 if family.startswith("llama-hd64") else 128)
    params = model.init_params(cfg, jax.random.key(0))
    toks = np.asarray(jax.random.randint(
        jax.random.key(5 if family.startswith("llama-hd64") else 3),
        (64 * long,), 0, cfg.vocab_size))
    prompts = [toks[:37 * long], toks[3 * long:26 * long], toks[40:49]]
    ec = dict(max_batch=4, max_seq_len=96 * long, max_prefill_len=16,
              page_size=4 if long == 1 else 64)
    want, _ = _greedy(model, cfg, params, prompts, 12, **ec)
    _force_the_kernel(monkeypatch)
    picked = []
    for name in ("paged_chunk_attention", "_one_token"):
        kernel = getattr(kvcache, name)
        monkeypatch.setattr(
            kvcache, name,
            lambda *a, _k=kernel, _n=name, **kw: picked.append(_n)
            or _k(*a, **kw))
    got, eng = _greedy(model, cfg, params, prompts, 12, **ec)
    jax.clear_caches()  # no later test meets a program traced here
    assert set(picked) == {"paged_chunk_attention", "_one_token"}
    assert eng.cache["k"].shape[3:] == (cfg.n_kv_heads * cfg.head_size // 128,
                                        128)
    assert got == want
    assert all(len(ids) == 12 for ids in got)
    assert (eng.positions[~eng.active] == 0).all()
