"""ops/sampling.py::sample: a batch whose rows are all greedy takes the
argmax and nothing else; a batch with a sampled row computes what the
sampler always computed, for every row, under the same key."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from substratus_tpu.ops.sampling import sample

SHAPES = [(1, 33), (4, 64), (3, 1000)]
TOP_KS = [0, 8]
TOP_PS = [None, "array"]


def _reference(logits, key, temperature, top_k=0, top_p=None):
    """The sampler before it branched (PR 38's body, kept here as the plain
    reference): every row pays the sort and the draw, and the greedy rows
    are picked out at the end."""
    b, v = logits.shape
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    safe_t = jnp.maximum(temperature, 1e-6)[:, None]
    scaled = logits / safe_t
    if top_k and top_k < v:
        kth = jax.lax.top_k(scaled, top_k)[0][:, -1:]
        scaled = jnp.where(scaled < kth, -jnp.inf, scaled)
    if top_p is not None:
        sorted_logits = jnp.sort(scaled, axis=-1)[:, ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        keep_sorted = (cum - probs) < top_p[:, None]
        cutoff = jnp.min(
            jnp.where(keep_sorted, sorted_logits, jnp.inf), axis=-1, keepdims=True
        )
        scaled = jnp.where(scaled < cutoff, -jnp.inf, scaled)
    sampled = jax.random.categorical(key, scaled, axis=-1).astype(jnp.int32)
    return jnp.where(temperature <= 0.0, greedy, sampled)


def _inputs(shape, top_p, seed=0):
    b, v = shape
    rng = np.random.default_rng(seed)
    logits = jnp.asarray(rng.normal(size=shape) * 3.0, jnp.float32)
    p = None
    if top_p is not None:
        p = jnp.asarray(rng.uniform(0.3, 1.0, size=(b,)), jnp.float32)
    return logits, p


def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        for x in v if isinstance(v, (tuple, list)) else (v,):
            inner = getattr(x, "jaxpr", x)
            if hasattr(inner, "eqns"):
                yield inner


def _primitives(jaxpr):
    """Names of every primitive of a jaxpr, those of its sub-jaxprs too."""
    names = []
    for eqn in jaxpr.eqns:
        names.append(eqn.primitive.name)
        for sub in _sub_jaxprs(eqn):
            names.extend(_primitives(sub))
    return names


@pytest.mark.parametrize("top_p", TOP_PS, ids=["no_top_p", "top_p"])
@pytest.mark.parametrize("top_k", TOP_KS, ids=["k0", "k8"])
@pytest.mark.parametrize("shape", SHAPES, ids=lambda s: f"{s[0]}x{s[1]}")
def test_an_all_greedy_batch_is_the_argmax(shape, top_k, top_p):
    """Ties go to the first index, and neither the key nor what top_p
    holds reaches the result."""
    logits, p = _inputs(shape, top_p)
    b, v = shape
    # a tie for the best in every row: columns 5 and v - 2 share the top
    logits = logits.at[:, 5].set(50.0).at[:, v - 2].set(50.0)
    temps = jnp.zeros((b,), jnp.float32)
    want = np.full((b,), 5, np.int32)
    for seed in (0, 1):
        got = jax.jit(sample, static_argnames="top_k")(
            logits, jax.random.key(seed), temps, top_k=top_k, top_p=p)
        assert got.dtype == jnp.int32 and got.shape == (b,)
        np.testing.assert_array_equal(got, want)
    if p is not None:
        # a leftover top_p of a finished request changes nothing
        got = sample(logits, jax.random.key(0), temps, top_k=top_k,
                     top_p=jnp.full((b,), 0.05, jnp.float32))
        np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("top_p", TOP_PS, ids=["no_top_p", "top_p"])
@pytest.mark.parametrize("top_k", TOP_KS, ids=["k0", "k8"])
@pytest.mark.parametrize("shape", SHAPES, ids=lambda s: f"{s[0]}x{s[1]}")
def test_a_batch_with_a_sampled_row_is_the_old_sampler_bit_for_bit(
    shape, top_k, top_p
):
    """Greedy rows included, key for key: one row above 0 puts the whole
    batch through the body the sampler always ran."""
    logits, p = _inputs(shape, top_p, seed=1)
    b, _ = shape
    temps = np.zeros((b,), np.float32)
    temps[0] = 0.8  # one sampled row; the others (if any) stay greedy
    if b > 2:
        temps[2] = 1.3
    temps = jnp.asarray(temps)
    new = jax.jit(sample, static_argnames="top_k")
    old = jax.jit(_reference, static_argnames="top_k")
    for seed in range(4):
        key = jax.random.key(seed)
        np.testing.assert_array_equal(
            new(logits, key, temps, top_k=top_k, top_p=p),
            old(logits, key, temps, top_k=top_k, top_p=p),
        )
    greedy = np.asarray(jnp.argmax(logits, -1))
    got = np.asarray(new(logits, jax.random.key(0), temps, top_k=top_k, top_p=p))
    rows = np.asarray(temps) <= 0
    np.testing.assert_array_equal(got[rows], greedy[rows])


@pytest.mark.parametrize("top_p", TOP_PS, ids=["no_top_p", "top_p"])
@pytest.mark.parametrize("top_k", TOP_KS, ids=["k0", "k8"])
def test_one_cond_holds_the_sort_and_the_draw(top_k, top_p):
    """The jaxpr of `sample`: one `cond` on the temperatures; outside it
    nothing sorts, draws or walks the vocabulary but the argmax; its greedy
    branch is empty."""
    logits, p = _inputs((4, 64), top_p)
    temps = jnp.zeros((4,), jnp.float32)
    jaxpr = jax.make_jaxpr(
        lambda l, k, t, pp: sample(l, k, t, top_k=top_k, top_p=pp)
    )(logits, jax.random.key(0), temps, p).jaxpr
    conds = [e for e in jaxpr.eqns if e.primitive.name == "cond"]
    assert len(conds) == 1
    assert _primitives(jaxpr).count("cond") == 1
    heavy = {"sort", "cumsum", "random_bits", "top_k", "exp", "div"}
    outside = [e.primitive.name for e in jaxpr.eqns if e is not conds[0]]
    for e in jaxpr.eqns:
        if e is not conds[0]:
            for sub in _sub_jaxprs(e):
                outside.extend(_primitives(sub))
    assert not heavy & set(outside), outside
    assert "argmax" in outside
    # lax.cond(pred, true_fn, false_fn) keeps (false, true) as its branches
    greedy_branch, sampled_branch = (
        _primitives(b.jaxpr) for b in conds[0].params["branches"])
    assert greedy_branch == []
    assert "random_bits" in sampled_branch
    assert ("sort" in sampled_branch) == (top_p is not None)
    assert ("top_k" in sampled_branch) == bool(top_k)


@pytest.mark.parametrize("temp", [0.0, 0.7], ids=["greedy", "sampled"])
def test_a_step_advances_the_key_whichever_branch_it_took(temp):
    """The callers split the key outside `sample` (serve/engine.py's
    decode, verify and first_sample): the key data a jitted step hands back
    is the same split after a greedy step as after a sampled one, so a later
    sampled request draws what it drew before the sampler branched."""
    logits, p = _inputs((4, 64), "array")

    @jax.jit
    def step(logits, key_data, temps, top_ps):
        key, subkey = jax.random.split(jax.random.wrap_key_data(key_data))
        tokens = sample(logits, subkey, temps, top_k=0, top_p=top_ps)
        return tokens, jax.random.key_data(key)

    kd = jax.random.key_data(jax.random.key(7))
    temps = jnp.full((4,), temp, jnp.float32)
    want = kd
    for _ in range(3):
        tokens, kd = step(logits, kd, temps, p)
        key, subkey = jax.random.split(jax.random.wrap_key_data(want))
        want = jax.random.key_data(key)
        np.testing.assert_array_equal(kd, want)
        np.testing.assert_array_equal(
            tokens, _reference(logits, subkey, temps, top_p=p))
