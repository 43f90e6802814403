"""The Pallas kernels, compiled by the chip's own compiler with no chip.

The TPU compiler is installed here and compiles for a described,
unattached `v5e:2x2` topology, so what Mosaic would refuse on the chip it
refuses in this test, at no chip time (interpret mode shows none of it:
tiling alignment, VMEM limits). One case per kernel and shape from
ops/kernel_cases.py — the list chip_smoke.py's kernel phase runs on the
attached chip. Cases the compiler refuses are strict xfail carrying its
message: the day one compiles, the test fails until the mark goes.
"""
import os
from functools import partial

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import pytest

from substratus_tpu.ops.kernel_cases import (
    SHARDED_REFUSED, chip_cases, shard_batch, sharded_flash_case,
)


@pytest.fixture(scope="module")
def v5e():
    """The four described chips of a v5e:2x2 host, with the persistent
    compilation cache off: an executable compiled for a described device
    is written there but cannot be read back without one, and the next
    compile would warn."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no libtpu, no topology: skip
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo.devices
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _param(case):
    marks = ()
    if case.refused:
        marks = pytest.mark.xfail(strict=True, reason=case.refused)
    return pytest.param(case, id=case.name, marks=marks)


@pytest.mark.parametrize("case", [_param(c) for c in chip_cases()])
def test_kernel_compiles_for_v5e(case, v5e):
    from jax.sharding import SingleDeviceSharding

    one_chip = SingleDeviceSharding(v5e[0])
    shapes = jax.eval_shape(case.make_args, jax.random.key(0))
    args = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        shapes,
    )
    compiled = (
        jax.jit(partial(case.kernel, interpret=False)).lower(*args).compile()
    )
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.xfail(strict=True, reason=SHARDED_REFUSED)
def test_sharded_kernel_compiles_for_v5e_2x2(v5e):
    """The custom_partitioning wrappers (ops/kernel_partition.py) pass on a
    virtual CPU mesh in interpret mode; the chip's compiler has not taken
    one yet. Recorded on four real chips by `chip_smoke.py --chips 4`."""
    import numpy as np
    from jax.sharding import Mesh

    mesh = Mesh(np.array(v5e), ("data",))
    case = sharded_flash_case(len(v5e))
    args = shard_batch(
        jax.eval_shape(case.make_args, jax.random.key(0)), mesh
    )
    jax.jit(partial(case.kernel, interpret=False)).lower(*args).compile()


def test_untileable_cache_lengths_are_refused_before_the_compiler():
    """Llama-2-7B widths with a cache of 1000: no multiple of 128 divides
    it and it does not fit VMEM whole, so the block choosers raise, and
    Engine construction raises with them (not the first jitted step)."""
    from substratus_tpu.models import llama
    from substratus_tpu.ops.decode_attention import pick_block_s
    from substratus_tpu.ops.flash_attention import cached_block_k
    from substratus_tpu.serve.engine import Engine, EngineConfig

    with pytest.raises(ValueError, match="multiple of 128"):
        pick_block_s(1000, kh=32, d=128, itemsize=2, quantized=False)
    with pytest.raises(ValueError, match="multiple of 128"):
        cached_block_k(256, 1000, quantized=True)
    # What the tiling can take stays accepted: whole-axis blocks, bf16.
    assert pick_block_s(1000, kh=4, d=64, itemsize=1, quantized=True) == 1000
    assert pick_block_s(512, kh=32, d=128, itemsize=2, quantized=False) == 256
    assert cached_block_k(256, 1024, quantized=True) == 256
    assert cached_block_k(256, 1000, quantized=False) == 8

    cfg = llama.CONFIGS["llama2-7b"].replace(
        n_layers=1, decode_attn_impl="pallas"
    )
    ec = EngineConfig(max_batch=2, max_seq_len=1000, kv_layout="dense")
    with pytest.raises(ValueError, match="multiple of 128"):
        Engine(cfg, None, ec)


def test_fused_decode_raises_on_a_tpu_backend(monkeypatch):
    """decode_attn_impl=fused stays opt-in and refuses a TPU backend with
    the compiler's message at Engine construction."""
    from substratus_tpu.models import llama
    from substratus_tpu.ops import fused_decode
    from substratus_tpu.serve.engine import Engine, EngineConfig

    fused_decode.check_lowers()  # the CPU backend: nothing to refuse
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = llama.CONFIGS["tiny"].replace(decode_attn_impl="fused")
    ec = EngineConfig(max_batch=2, max_seq_len=64, kv_layout="dense")
    with pytest.raises(NotImplementedError, match="aligned to tiling"):
        Engine(cfg, None, ec)
