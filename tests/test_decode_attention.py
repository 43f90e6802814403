"""The dense slot cache's attention (ops/decode_attention.py): the one-token
scale-after-dot step against the float reference across MHA/GQA/MQA and
masking cases, and update_cache_and_attend's chunk path (S > 1, kv_length)
against ops/kernel_cases.py's plain reference.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from substratus_tpu.ops.decode_attention import (
    decode_attention, update_cache_and_attend,
)
from substratus_tpu.ops.kernel_cases import _cache_reference
from substratus_tpu.ops.quant import quantize_kv


def _reference(q, k, v, positions, k_scale=None, v_scale=None):
    """Float-math oracle on the [B, KH, S, D] cache layout."""
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    if k_scale is not None:
        kf = kf * k_scale[..., None]
        vf = vf * v_scale[..., None]
    b, _, h, d = q.shape
    kh, s = k.shape[1], k.shape[2]
    g = h // kh
    qf = (q.astype(jnp.float32) * d ** -0.5).reshape(b, kh, g, d)
    logits = jnp.einsum("bkgd,bksd->bkgs", qf, kf)
    mask = jnp.arange(s)[None, :] <= positions[:, None]
    logits = jnp.where(mask[:, None, None, :], logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgs,bksd->bkgd", p, vf)
    return out.reshape(b, 1, h, d)


def _mk(kh, g, b=4, s=64, d=32, quantized=True, seed=0):
    key = jax.random.key(seed)
    kq, kk, kv_, kp = jax.random.split(key, 4)
    q = jax.random.normal(kq, (b, 1, kh * g, d), jnp.bfloat16)
    k = jax.random.normal(kk, (b, kh, s, d), jnp.float32)
    v = jax.random.normal(kv_, (b, kh, s, d), jnp.float32)
    positions = jax.random.randint(kp, (b,), 0, s, jnp.int32)
    if not quantized:
        return q, k.astype(jnp.bfloat16), v.astype(jnp.bfloat16), positions, None, None
    kq8, ks = quantize_kv(k)
    vq8, vs = quantize_kv(v)
    return q, kq8, vq8, positions, ks[..., 0], vs[..., 0]


HEAD_LAYOUTS = {"mha": (4, 1), "gqa": (2, 2), "mqa": (1, 4)}


@pytest.mark.parametrize("layout", sorted(HEAD_LAYOUTS))
@pytest.mark.parametrize("quantized", [True, False])
def test_xla_matches_reference(layout, quantized):
    kh, g = HEAD_LAYOUTS[layout]
    q, k, v, positions, ks, vs = _mk(kh, g, quantized=quantized)
    out = decode_attention(q, k, v, positions, ks, vs)
    ref = _reference(q, k, v, positions, ks, vs)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=0.03, rtol=0.05,
    )


def test_position_zero_attends_only_first_slot():
    """A row at position 0 must ignore every other slot, whatever it holds."""
    b, kh, s, d = 2, 1, 16, 8
    q = jnp.ones((b, 1, kh, d), jnp.bfloat16)
    k = jax.random.normal(jax.random.key(3), (b, kh, s, d), jnp.bfloat16)
    # Slot 0 holds a distinctive value; the rest garbage.
    v = jnp.full((b, kh, s, d), 7.0, jnp.bfloat16)
    v = v.at[:, :, 0].set(1.5)
    positions = jnp.zeros((b,), jnp.int32)
    out = decode_attention(q, k, v, positions)
    np.testing.assert_allclose(np.asarray(out, np.float32), 1.5, atol=1e-2)


# --- update_cache_and_attend with more than one query a row ------------------


def _chunk(kh, int8, b=2, sq=16, h=4, d=32, sk=128, start=(64, 40), seed=3):
    """A float32 chunk of `sq` fresh rows a sequence, landing at `start`,
    and a slot cache whose every other slot holds junk. Returns the op's
    operands and the cache as it must be afterwards, put together row by
    row on the host."""
    ks = jax.random.split(jax.random.key(seed), 5)
    q = jax.random.normal(ks[0], (b, sq, h, d), jnp.float32)
    kk = jax.random.normal(ks[1], (b, sq, kh, d), jnp.float32)
    vv = jax.random.normal(ks[2], (b, sq, kh, d), jnp.float32)
    cache = {
        "k": jax.random.normal(ks[3], (b, kh, sk, d), jnp.float32),
        "v": jax.random.normal(ks[4], (b, kh, sk, d), jnp.float32),
    }
    fresh = {"k": kk.transpose(0, 2, 1, 3), "v": vv.transpose(0, 2, 1, 3)}
    if int8:
        for name in ("k", "v"):
            cache[name], scale = quantize_kv(cache[name])
            cache[name + "_scale"] = scale[..., 0]
            fresh[name], scale = quantize_kv(fresh[name])
            fresh[name + "_scale"] = scale[..., 0]
    positions = jnp.asarray(start[:b], jnp.int32)[:, None] + jnp.arange(sq)
    want = {name: np.array(val) for name, val in cache.items()}
    for row in range(b):
        at = int(positions[row, 0])
        for name in want:
            want[name][row, :, at:at + sq] = np.asarray(fresh[name][row])
    return cache, q, kk, vv, positions, want


@pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("kh", [4, 2], ids=["mha", "gqa"])
def test_chunk_matches_reference(kh, int8):
    """A prefill chunk / verify round: the rows land where `positions` say,
    quantized on the way into an int8 cache, and every query attends to
    the cache up to its own position."""
    cache, q, kk, vv, positions, want = _chunk(kh, int8)
    attn, out = jax.jit(update_cache_and_attend)(cache, q, kk, vv, positions)
    assert sorted(out) == sorted(want)
    for name in want:  # scales: the jitted quantizer's last bit may differ
        np.testing.assert_allclose(np.asarray(out[name]), want[name], rtol=1e-6)
    ref = _cache_reference(
        q, want["k"], want["v"], positions,
        want.get("k_scale"), want.get("v_scale"),
    )
    np.testing.assert_allclose(np.asarray(attn), np.asarray(ref), atol=2e-5)


def test_chunk_kv_length_masks_the_cache_past_it():
    """kv_length = 20 under queries at 40..47: what the same queries see of
    a cache cut to its first 20 slots, their own fresh rows masked too."""
    cache, q, kk, vv, positions, want = _chunk(
        2, False, b=1, sq=8, sk=64, start=(40,)
    )
    attn, out = update_cache_and_attend(
        cache, q, kk, vv, positions, kv_length=jnp.array([20], jnp.int32)
    )
    np.testing.assert_array_equal(np.asarray(out["k"]), want["k"])
    ref = _cache_reference(
        q, want["k"][:, :, :20], want["v"][:, :, :20], positions
    )
    np.testing.assert_allclose(np.asarray(attn), np.asarray(ref), atol=2e-5)


def test_chunk_zero_length_row_stays_finite_and_alone():
    """A row with nothing to attend to (kv_length 0: a padding slot) masks
    every column. Its output is the plain softmax's even weighting of V,
    finite, and the row beside it is what it is alone."""
    cache, q, kk, vv, positions, want = _chunk(
        2, False, sq=8, sk=64, start=(0, 30)
    )
    attn, _ = update_cache_and_attend(
        cache, q, kk, vv, positions, kv_length=jnp.array([0, 20], jnp.int32)
    )
    mean_v = want["v"][0].mean(axis=1)  # [KH, D], each serving two heads
    np.testing.assert_allclose(
        np.asarray(attn[0]), np.broadcast_to(
            np.repeat(mean_v, 2, axis=0), attn[0].shape), atol=2e-5,
    )
    ref = _cache_reference(
        q[1:], want["k"][1:, :, :20], want["v"][1:, :, :20], positions[1:]
    )
    np.testing.assert_allclose(np.asarray(attn[1:]), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("sq", [8, 1], ids=["chunk", "decode"])
def test_sequence_sharded_cache_matches_unsharded(sq):
    """What `sequence>1` serving runs: the cache's slot axis split over
    four devices. XLA partitions the scatter and the softmax over it; the
    result is the unsharded one and the cache stays split."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    cache, q, kk, vv, positions, want = _chunk(2, True, sq=sq)
    op = jax.jit(update_cache_and_attend)
    attn, _ = op(cache, q, kk, vv, positions)
    mesh = Mesh(np.array(jax.devices()[:4]), ("sequence",))
    split = {
        name: jax.device_put(val, NamedSharding(
            mesh, P(None, None, "sequence", *[None] * (val.ndim - 3))))
        for name, val in cache.items()
    }
    attn_split, out = op(split, q, kk, vv, positions)
    np.testing.assert_allclose(
        np.asarray(attn_split), np.asarray(attn), atol=2e-5
    )
    for name in want:
        np.testing.assert_allclose(np.asarray(out[name]), want[name], rtol=1e-6)
        assert out[name].sharding.spec[2] == "sequence", name
