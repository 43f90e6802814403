"""The engine's door (serve/engine.py::Engine.serving_tree, PR 41): every
parameter tree passes the family's `serving_layout` on its way in, which
keeps each leaf's name and kind and gives the int8 projection stacks a
serving program would otherwise lay out anew in every layer and step the
form their dot reads from the stack (models/llama.py: q, k, v heads first,
contracted dim last; models/exaone_moe.py views its four stacks so at the
top of `forward`). Checkpoints, training and LoRA keep the published form:
an engine given it serves the tokens `forward` gives on it."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from substratus_tpu.models import brumby, deepseek_v3, exaone_moe, llama
from substratus_tpu.observability.metrics import METRICS
from substratus_tpu.ops.quant import QTensor, quantize_params
from substratus_tpu.ops.quant4 import Q4Tensor, quantize4_params
from substratus_tpu.serve.engine import Engine, EngineConfig
from substratus_tpu.train.lora import init_lora
from substratus_tpu.utils import jaxstart

FAMILIES = {
    "llama": (llama, "tiny"),
    "exaone_moe": (exaone_moe, "tiny-exaone-moe"),
    "deepseek_v3": (deepseek_v3, "tiny-deepseek-v3"),
    "brumby": (brumby, "tiny-brumby"),
}
PROMPT = [7, 3, 200, 41, 5, 6, 99, 12, 64, 33, 8, 150, 2, 77, 18, 91, 45]


def _cfg(family):
    model, name = FAMILIES[family]
    return model, model.CONFIGS[name].replace(dtype=jnp.float32)


@functools.cache
def _builder(family, kind):
    """One program a family and kind: leaf by leaf, eagerly, an expert
    family's tree takes 15 s."""
    model, cfg = _cfg(family)

    def build(key):
        params = model.init_params(cfg, key)
        if kind in ("int8", "int4"):
            fn = quantize_params if kind == "int8" else quantize4_params
            return fn(params, model.quant_contracting(cfg))
        return jax.tree.map(
            lambda a: a.astype(jnp.bfloat16)
            if jnp.issubdtype(a.dtype, jnp.floating) else a, params)

    return jax.jit(build)


def _tree(family, kind, seed=0):
    """The published tree: `int8` as ops.quant.quantize_params makes it,
    `int4` as ops.quant4.quantize4_params does (the benchmark's control),
    `dense` with bfloat16 leaves. A new tree every call."""
    return _builder(family, kind)(jax.random.key(seed))


def _engine(family, params, **ec):
    model, cfg = _cfg(family)
    ec = {"max_batch": 2, "max_seq_len": 64, "max_prefill_len": 16,
          "page_size": 4, "eos_token_id": -1, **ec}
    return Engine(cfg, params, EngineConfig(**ec), model=model)


def _greedy_by_forward(family, params, served):
    """True where `served`, the tokens an engine gave after PROMPT, are
    the published tree's own greedy answer, token for token: the argmax of
    the family's `forward` over the whole sequence, no cache, at every
    position (one jitted call: each token is checked behind the tokens
    before it, which is what a greedy loop would have fed)."""
    model, cfg = _cfg(family)
    toks = jnp.asarray([PROMPT + list(served)], jnp.int32)
    logits, _ = jax.jit(lambda p, t: model.forward(p, t, cfg))(params, toks)
    best = jnp.argmax(logits[0, len(PROMPT) - 1:-1], axis=-1)
    return [int(t) for t in best] == list(served)


def _quantized(tree):
    return jax.tree.leaves(
        tree, is_leaf=lambda x: isinstance(x, (QTensor, Q4Tensor)))


@pytest.mark.parametrize("kind", ["int8", "dense", "int4"])
@pytest.mark.parametrize("family", list(FAMILIES))
def test_an_engine_serves_the_published_trees_tokens(family, kind):
    """Token for token what `forward` gives on the tree as published, and
    the caller's tree is left as it was (the engine deletes nothing it was
    not told it may)."""
    params = _tree(family, kind)
    eng = _engine(family, params)
    eng.start()
    try:
        served = eng.generate(PROMPT, max_tokens=10)
    finally:
        eng.stop()
    assert eng.error is None and len(served) == 10
    assert _greedy_by_forward(family, params, served)  # and still readable
    # every path and kind of the published tree, as
    # benchmarks/harness/system.py::precision_found looks them up
    assert jax.tree.structure(eng.params) == jax.tree.structure(params)
    for held, given in zip(_quantized(eng.params), _quantized(params)):
        assert type(held) is type(given)
        if isinstance(given, QTensor):
            assert held.q.dtype == jnp.int8
            assert held.q.size == given.q.size


def test_llamas_door_turns_q_k_v_and_nothing_else():
    model, cfg = _cfg("llama")
    params = _tree("llama", "int8")
    eng = _engine("llama", params)
    L, D, H, KH, hd = (cfg.n_layers, cfg.dim, cfg.n_heads, cfg.n_kv_heads,
                       cfg.head_size)
    lay = eng.params["layers"]
    assert lay["wq"].q.shape == (L, H, hd, D)
    assert lay["wq"].scale.shape == (L, H, hd, 1)
    assert lay["wk"].q.shape == lay["wv"].q.shape == (L, KH, hd, D)
    np.testing.assert_array_equal(
        lay["wq"].q, jnp.transpose(params["layers"]["wq"].q, (0, 2, 3, 1)))
    np.testing.assert_array_equal(
        lay["wv"].scale,
        jnp.transpose(params["layers"]["wv"].scale, (0, 2, 3, 1)))
    for name in ("wo", "w_gate", "w_up", "w_down", "attn_norm"):
        assert lay[name] is params["layers"][name]
    assert eng.params["lm_head"] is params["lm_head"]
    relaid = sum(lay[n].q.nbytes + lay[n].scale.nbytes
                 for n in ("wq", "wk", "wv"))
    assert METRICS.get("substratus_serve_weights_relaid_bytes") == relaid
    axes = model.serving_logical_axes(eng.params, cfg)["layers"]
    assert axes["wq"] == ("layers", "heads", "head_dim", "embed")
    assert axes["wk"] == ("layers", "kv_heads", "head_dim", "embed")
    assert axes["wo"] == model.param_logical_axes(cfg)["layers"]["wo"]


@pytest.mark.parametrize("family", list(FAMILIES))
def test_the_door_is_the_identity_the_second_time_and_on_int4(family):
    model, cfg = _cfg(family)
    eng = _engine(family, _tree(family, "int8"))
    again = eng.serving_tree(eng.params)
    assert all(a is b for a, b in zip(jax.tree.leaves(again),
                                      jax.tree.leaves(eng.params)))
    assert METRICS.get("substratus_serve_weights_relaid_bytes") == 0
    int4 = _tree(family, "int4")
    assert any(isinstance(w, Q4Tensor) for w in _quantized(int4))
    through = eng.serving_tree(int4)
    assert all(a is b for a, b in zip(jax.tree.leaves(through),
                                      jax.tree.leaves(int4)))


def test_the_door_deletes_what_it_turned_only_when_told():
    params = _tree("llama", "int8")
    kept = _tree("llama", "int8")
    model, cfg = _cfg("llama")
    eng = Engine(cfg, params, EngineConfig(max_batch=2, max_seq_len=64),
                 donate_params=True)
    assert params["layers"]["wq"].q.is_deleted()
    assert params["layers"]["wv"].scale.is_deleted()
    assert not params["layers"]["wo"].q.is_deleted()
    np.testing.assert_array_equal(
        eng.params["layers"]["wk"].q,
        jnp.transpose(kept["layers"]["wk"].q, (0, 2, 3, 1)))


def test_a_published_tree_swaps_in_without_a_compile_and_a_wrong_one_not():
    jaxstart.count_compilations()
    params = _tree("llama", "int8")
    # (no prefix reuse: pages another tree's weights wrote stay registered
    # across a swap, and this test swaps in another seed's)
    eng = _engine("llama", params, prefix_cache=False)
    eng.start()
    try:
        want = eng.generate(PROMPT, max_tokens=8)
        # the first swap and the stream after it warm what a swap meets
        # once a process (test_weight_swap.py): the second proves the rule
        assert eng.swap_params(_tree("llama", "int8")) == 1
        assert eng.generate(PROMPT, max_tokens=8) == want
        built = METRICS.get("substratus_jax_compilations_total")
        assert eng.swap_params(_tree("llama", "int8")) == 2
        assert eng.generate(PROMPT, max_tokens=8) == want
        assert METRICS.get("substratus_jax_compilations_total") == built
        # another seed's weights in the published form: other tokens
        other = _tree("llama", "int8", seed=1)
        assert eng.swap_params(other) == 3
        after = eng.generate(PROMPT, max_tokens=8)
        assert after != want and _greedy_by_forward("llama", other, after)
        # a tree already in the served form swaps in as it is
        assert eng.swap_params(eng.params) == 4
        # a wrong shape is still rejected, in either form
        model, cfg = _cfg("llama")
        wide = cfg.replace(n_kv_heads=cfg.n_heads)
        wrong = quantize_params(model.init_params(wide, jax.random.key(0)),
                                model.quant_contracting(wide))
        with pytest.raises(ValueError, match="swap_params rejected"):
            eng.swap_params(wrong)
        with pytest.raises(ValueError, match="swap_params rejected"):
            eng.swap_params(model.serving_layout(wrong, wide))
        with pytest.raises(ValueError, match="swap_params rejected"):
            eng.swap_params(_tree("llama", "dense"))
        assert eng.generate(PROMPT, max_tokens=8) == after
    finally:
        eng.stop()
    assert eng.error is None


@pytest.mark.parametrize("indexed", [False, True], ids=["lora", "adapter_ids"])
def test_a_lora_delta_is_the_same_on_both_forms(indexed):
    """LoRA's `b` keeps its published [r, heads, hd]: the delta is added to
    the same [B, S, heads, hd] output whichever form the base leaf has."""
    model, cfg = _cfg("llama")
    params = _tree("llama", "int8")
    served = model.serving_layout(params, cfg)
    keys = iter(jax.random.split(jax.random.key(4), 32))
    lora = {"scale": 2.0, "layers": {  # init_lora's b is zero: draw one
        name: {"a": ab["a"],
               "b": 0.05 * jax.random.normal(next(keys), ab["b"].shape)}
        for name, ab in init_lora(
            cfg, jax.random.key(3), rank=4, dtype=jnp.float32).items()}}
    toks = jnp.asarray([PROMPT, PROMPT[::-1]], jnp.int32)
    kw = {}
    if indexed:  # slot-stacked: row 0 takes slot 1, row 1 the zero slot 0
        lora["layers"] = jax.tree.map(
            lambda x: jnp.stack([jnp.zeros_like(x), x], axis=1),
            lora["layers"])
        kw["adapter_ids"] = jnp.asarray([1, 0], jnp.int32)
    base, _ = model.forward(params, toks, cfg)
    want, _ = model.forward(params, toks, cfg, lora=lora, **kw)
    got, _ = model.forward(served, toks, cfg, lora=lora, **kw)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    assert float(jnp.max(jnp.abs(want[0] - base[0]))) > 1e-3
    if indexed:
        np.testing.assert_allclose(got[1], base[1], rtol=1e-5, atol=1e-5)
