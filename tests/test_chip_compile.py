"""The Pallas kernels, compiled by the chip's own compiler with no chip.

The TPU compiler is installed here and compiles for a described,
unattached `v5e:2x2` topology (the `v5e` fixture, tests/conftest.py), so
what Mosaic would refuse on the chip it refuses in this test, at no chip
time (interpret mode shows none of it: tiling alignment, VMEM limits). One
case per kernel and shape from ops/kernel_cases.py — the list
chip_smoke.py's kernel phase runs on the attached chip — the dense slot
cache's attention, which is plain XLA, among them.

The serving programs are compiled the same way, one file a family so that
`--dist loadfile` spreads them over its workers: test_chip_compile_llama.py,
_exaone.py, _lfm2.py, _brumby.py, _deepseek_v3.py and, for the dense slot
cache, _dense.py; what they share reads tests/chip_compile.py.
"""
import math
import re
from functools import partial

import jax
import pytest

from chip_compile import pool_moving_ops
from substratus_tpu.ops.kernel_cases import (
    SHARDED_REFUSED, chip_cases, shard_batch, sharded_flash_case,
)


@pytest.mark.parametrize("case", chip_cases(), ids=lambda case: case.name)
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
    assert ("tpu_custom_call" in compiled.as_text()) == case.mosaic


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


@pytest.mark.parametrize("s", [1, 512], ids=["step", "chunk"])
def test_paged_decode_kernel_compiles_at_head_dim_64(s, v5e):
    """TinyLlama-1.1B's heads are 64 wide and Mosaic tiles no kernel of
    ops/paged_attention.py at that minor dimension ("Slice shape along
    dimension 4 must be aligned to tiling (128), but is 64"): the pool
    stores such heads two to a row of 128 (ops/kvcache.py::
    init_paged_cache) and a decode step and a chunk take the kernels over
    those rows. A pool handed in as `bf16[..., 4, 64]` all the same is
    left on the gather path: the op reads the row's width (from PR 28 to
    PR 30 it picked the decode kernel regardless, and on a TPU `serve.main
    --config tinyllama-1.1b` answered 500: ROADMAP.md S3c)."""
    from jax.sharding import SingleDeviceSharding

    from substratus_tpu.ops import kvcache
    from substratus_tpu.ops.kernel_cases import TINYLLAMA, paged_chunk

    case = paged_chunk("tinyllama", 8, s, 1024, pages=513, **TINYLLAMA)
    one_chip = SingleDeviceSharding(v5e[0])
    q, k, v, layer, table, positions = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(case.make_args, jax.random.key(0)),
    )
    assert k.shape[3:] == (2, 128)
    assert kvcache._kernel_for(k, q) is not None
    hlo = jax.jit(case.kernel).lower(
        q, k, v, layer, table, positions).compile().as_text()
    name = "paged_chunk_attention" if s > 1 else "paged_decode_attention"
    assert re.search(r'custom_call_target="tpu_custom_call".*' + name, hlo)
    assert "kv.gather" not in hlo
    assert pool_moving_ops(hlo, {math.prod(k.shape)}) == []
    # one row a token (two heads of 64; TinyLlama's four split over two
    # chips) Mosaic does not slice: "must be aligned to tiling (2), but is 1"
    one = jax.ShapeDtypeStruct(k.shape[:3] + (1, 128), k.dtype,
                               sharding=one_chip)
    assert kvcache._kernel_for(one, q) is None
    # the same bytes declared a head a row
    k = v = jax.ShapeDtypeStruct(
        k.shape[:3] + (4, 64), k.dtype, sharding=one_chip)
    assert kvcache._kernel_for(k, q) is None
    hlo = jax.jit(case.kernel).lower(
        q, k, v, layer, table, positions).compile().as_text()
    assert "tpu_custom_call" not in hlo and "kv.gather" in hlo
