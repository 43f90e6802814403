"""Flash + ring attention vs the XLA oracle (ops/attention.py).

Flash runs in Pallas interpret mode on CPU (the compiled path needs a real
TPU); ring attention runs under shard_map on the virtual 8-device mesh —
exactly how multi-chip context parallelism executes on a slice.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P
from jax import shard_map

from substratus_tpu.ops.attention import dot_product_attention
from substratus_tpu.ops.flash_attention import flash_attention
from substratus_tpu.ops.ring_attention import ring_attention


def _qkv(b=2, s=256, h=4, kh=2, d=32, dtype=jnp.float32):
    ks = jax.random.split(jax.random.key(0), 3)
    return (
        jax.random.normal(ks[0], (b, s, h, d), dtype),
        jax.random.normal(ks[1], (b, s, kh, d), dtype),
        jax.random.normal(ks[2], (b, s, kh, d), dtype),
    )


def test_flash_matches_reference():
    q, k, v = _qkv()
    ref = dot_product_attention(q, k, v, causal=True)
    out = flash_attention(q, k, v, True, None, 64, 64, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_noncausal():
    q, k, v = _qkv(s=128)
    ref = dot_product_attention(q, k, v, causal=False)
    out = flash_attention(q, k, v, False, None, 64, 64, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize(
    "kh,causal",
    [(4, True), (2, True), (4, False)],
    ids=["mha-causal", "gqa-causal", "mha-noncausal"],
)
def test_flash_backward_matches_reference(kh, causal):
    """The Pallas backward kernels (dQ over k-blocks, dK/dV over q-blocks
    with GQA group reduction) vs differentiating the XLA oracle."""
    q, k, v = _qkv(s=128, kh=kh)

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, causal, None, 64, 64, True) ** 2).sum()

    def loss_ref(q, k, v):
        return (dot_product_attention(q, k, v, causal=causal) ** 2).sum()

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_flash_backward_dkv_block_override_parity():
    """Retuning the dkv grid independently (set_dkv_blocks /
    SUBSTRATUS_FLASH_DKV_BLOCKS) must not change gradients — only the
    schedule."""
    from substratus_tpu.ops.flash_attention import set_dkv_blocks

    q, k, v = _qkv(s=128, kh=2)

    def loss(q, k, v):
        return (flash_attention(q, k, v, True, None, 64, 64, True) ** 2).sum()

    base = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    try:
        set_dkv_blocks((32, 128))  # different q AND k blocking than dq's
        tuned = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    finally:
        set_dkv_blocks(None)
    for a, b in zip(tuned, base):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


@pytest.mark.parametrize("n", [2, 4])
def test_ulysses_attention_matches_reference(mesh8, n):
    from substratus_tpu.ops.ulysses_attention import ulysses_attention
    from substratus_tpu.parallel.mesh import build_mesh

    mesh = build_mesh(sequence=n, data=8 // n)
    b, s = 4, 128
    q, k, v = _qkv(b=b, s=s, h=4, kh=4)  # heads divisible by axis
    ref = dot_product_attention(q, k, v, causal=True)

    spec = P("data", "sequence", None, None)
    fn = shard_map(
        lambda q, k, v: ulysses_attention(q, k, v, axis_name="sequence"),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
    )
    out = jax.jit(fn)(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_ulysses_train_step_matches_xla(mesh8):
    """A full train step with attn_impl=ulysses matches the plain path."""
    import jax.numpy as jnp

    from substratus_tpu.models import llama
    from substratus_tpu.parallel.mesh import build_mesh
    from substratus_tpu.train.trainer import TrainConfig, Trainer

    mesh = build_mesh(data=2, sequence=2, tensor=2)
    base = llama.CONFIGS["tiny"].replace(dtype=jnp.float32)
    batch = {
        "tokens": np.ones((4, 32), np.int32),
        "weights": np.ones((4, 32), np.float32),
    }
    loss_plain = Trainer(base, TrainConfig(), mesh).train_step(batch)
    loss_uly = Trainer(
        base.replace(attn_impl="ulysses"), TrainConfig(), mesh
    ).train_step(batch)
    assert abs(loss_plain - loss_uly) < 1e-5, (loss_plain, loss_uly)


@pytest.mark.parametrize("ring_size", [2, 4, 8])
def test_ring_attention_matches_reference(mesh8, ring_size):
    from substratus_tpu.parallel.mesh import build_mesh

    mesh = build_mesh(sequence=ring_size, data=8 // ring_size)
    b, s = 4, 128
    q, k, v = _qkv(b=b, s=s)
    ref = dot_product_attention(q, k, v, causal=True)

    ring = shard_map(
        lambda q, k, v: ring_attention(q, k, v, axis_name="sequence"),
        mesh=mesh,
        in_specs=(
            P("data", "sequence", None, None),
            P("data", "sequence", None, None),
            P("data", "sequence", None, None),
        ),
        out_specs=P("data", "sequence", None, None),
    )
    out = jax.jit(ring)(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_non_divisible_bucket():
    """A 384-token prefill bucket (not a multiple of the 256 default
    block) must shrink the block instead of asserting."""
    q, k, v = _qkv(s=384, h=2, kh=2, d=16)
    ref = dot_product_attention(q, k, v, causal=True)
    out = flash_attention(q, k, v, True, None, 256, 256, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_sharded_forward_and_grad_match_unsharded():
    """Round-5: flash fwd/bwd carry custom_partitioning rules (kernel_
    partition.bh_partitioned), so GSPMD runs them per (batch, head)
    shard. Sharded inputs over a (data x tensor) mesh must reproduce the
    unsharded forward AND gradients — this is the TPU serving default
    (attn_impl=flash) under the TP mesh, previously an unpartitionable
    pallas_call."""
    from jax.sharding import NamedSharding

    from substratus_tpu.parallel.mesh import build_mesh

    mesh = build_mesh(data=2, tensor=2, fsdp=2)
    q, k, v = _qkv(b=2, s=128, h=4, kh=2)

    def loss(q, k, v):
        return (flash_attention(q, k, v, True, None, 64, 64, True) ** 2).sum()

    out_ref = flash_attention(q, k, v, True, None, 64, 64, True)
    g_ref = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    qs = jax.device_put(q, NamedSharding(mesh, P("data", None, "tensor")))
    ks = jax.device_put(k, NamedSharding(mesh, P("data", None, "tensor")))
    vs = jax.device_put(v, NamedSharding(mesh, P("data", None, "tensor")))
    out_sh = jax.jit(
        lambda q, k, v: flash_attention(q, k, v, True, None, 64, 64, True)
    )(qs, ks, vs)
    np.testing.assert_allclose(
        np.asarray(out_sh), np.asarray(out_ref), atol=2e-5
    )
    g_sh = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(qs, ks, vs)
    for a, b in zip(g_sh, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_flash_sharded_gqa_tensor_wider_than_kv_heads():
    """Code-review r5 (empirically confirmed bug): h=8, kh=2 under a
    tensor=4 axis used to force a 4-way shard onto the 2-row kv-head
    dim — silently wrong output. bh_partitioned now drops (replicates)
    a head axis that does not divide EVERY head dim it touches, so the
    result must match the unsharded kernel exactly."""
    from jax.sharding import NamedSharding

    from substratus_tpu.parallel.mesh import build_mesh

    mesh = build_mesh(data=2, tensor=4)
    q, k, v = _qkv(b=2, s=128, h=8, kh=2)
    ref = flash_attention(q, k, v, True, None, 64, 64, True)

    qs = jax.device_put(q, NamedSharding(mesh, P("data", None, "tensor")))
    out = jax.jit(
        lambda q, k, v: flash_attention(q, k, v, True, None, 64, 64, True)
    )(qs, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
