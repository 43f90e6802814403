"""The Pallas kernels, compiled by the chip's own compiler with no chip.

The TPU compiler is installed here and compiles for a described,
unattached `v5e:2x2` topology, so what Mosaic would refuse on the chip it
refuses in this test, at no chip time (interpret mode shows none of it:
tiling alignment, VMEM limits). One case per kernel and shape from
ops/kernel_cases.py — the list chip_smoke.py's kernel phase runs on the
attached chip — the dense slot cache's attention, which is plain XLA,
among them.

The serving programs that carry the paged KV pool are compiled the same way:
the optimized HLO of the engine's decode and chunk programs may not copy,
slice or re-stack the pool (models/llama.py::forward carries it in place),
nor, for a family with window layers, their rings (models/exaone_moe.py);
and the decode and chunk programs over a bfloat16 pool hold the
paged-attention kernels, no gathered context and no scores in HBM
(ops/kvcache.py), at a head width of 64 too, where the pool stores two KV
heads to a row of 128 (TinyLlama, LFM2). The dense
slot cache's programs are compiled for every family that serves on it, and
for a cache split over `sequence`.
"""
import math
import os
import re
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
    assert _pool_moving_ops(hlo, {math.prod(k.shape)}) == []
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


# The chat cell's engine (benchmarks/traffic/chat.json): Mistral-7B, int8
# weights, whole depth (the layers are one scan: depth costs no compile time).
_POOL_PAGES, _PAGE, _B, _S, _CHUNK = 1792, 16, 32, 2048, 512


def _pool_moving_ops(hlo: str, sizes) -> list:
    """Every copy / dynamic-slice / dynamic-update-slice of the optimized
    HLO (fused computations included) whose result has one of `sizes`
    elements."""
    found = []
    for m in re.finditer(
        r"= \w+\[([\d,]+)\]\S* (copy|dynamic-slice|dynamic-update-slice)\(",
        hlo,
    ):
        if math.prod(map(int, m.group(1).split(","))) in sizes:
            found.append(m.group(0))
    return found


# Ops that move no byte of their own, or whose result is not theirs alone.
_NO_MOVE = {"parameter", "get-tuple-element", "bitcast", "tuple", "while",
            "conditional", "call", "custom-call", "copy-done", "slice-done",
            "optimization-barrier"}


def _weights_laid_out_anew(hlo: str, sizes) -> list:
    """Every op of the optimized HLO that stands outside any fusion (so
    outside every dot's fusion: a fusion's own result is listed, its inside
    is not) and whose result is int8 with one of `sizes` elements, the
    elements of one layer of a projection leaf: a layer's weights written
    somewhere else before their dot reads them (a
    `constant_dynamic-slice_fusion` staged in VMEM, a `copy`, a
    `copy_bitcast_fusion`, a plain `slice`). As "computation: name =
    type op"."""
    found, where, fused = [], "", False
    for line in hlo.splitlines():
        if line and not line.startswith(" "):
            where = line.split("(")[0].replace("ENTRY", "").strip(" %")
            fused = "fused_computation" in where
            continue
        m = re.match(r"\s+(?:ROOT )?%?([\w.\-]+) = (.*?) ([\w\-]+)\(", line)
        if fused or not m or m.group(3) in _NO_MOVE:
            continue
        for t in re.finditer(r"s8\[([\d,]+)\](\{[^}]*\})?", m.group(2)):
            if math.prod(map(int, t.group(1).split(","))) in sizes:
                found.append(f"{where}: {m.group(1)} = {t.group(0)} "
                             f"{m.group(3)}")
    return found


def _reads_pages_in_place(hlo: str, kernel: str, rows: int, seq: int,
                          kv_heads: int, head_dim: int,
                          scores: int = 0) -> bool:
    """The program's attention is the named kernel of
    ops/paged_attention.py, and nothing in the program is a gathered K or V
    (a result [..., kv_heads, head_dim] of rows x seq positions, flat or
    as pages of _PAGE: gone, not moved) nor a float32 result of `scores` = heads x S x seq elements (a
    chunk's scores never reach HBM)."""
    sized = []
    for m in re.finditer(r"= (\w+)\[([\d,]+)\]\S* [\w-]+\(", hlo):
        dims = list(map(int, m.group(2).split(",")))
        n = math.prod(dims)
        lead = set(dims[:-2])
        context = (dims[-2:] == [kv_heads, head_dim]
                   and n == rows * seq * kv_heads * head_dim
                   and (seq in lead or {rows * seq // _PAGE, _PAGE} <= lead))
        if context or (m.group(1) == "f32" and n == scores):
            sized.append(m.group(0))
    found = re.search(
        r'custom_call_target="tpu_custom_call".*' + kernel, hlo)
    return bool(found) and not sized


_KERNEL = {"decode": "paged_decode_attention",
           "chunk": "paged_chunk_attention"}


def _sorts_only_where_a_row_samples(hlo: str) -> bool:
    """The decode program kept the sampler's branch as a `conditional`
    (ops/sampling.py::sample: not flattened into a select that runs both
    sides), and every sort of the sampler lies in its sampled branch."""
    sorts = [name for name in
             re.findall(r" sort\(.*?op_name=\"([^\"]*)\"", hlo)
             if "/sample/" in name]
    return (bool(re.search(r" conditional\(.*op_name=\"[^\"]*sample/cond", hlo))
            and bool(sorts)
            and all("sample/cond/branch_1_fun/" in name for name in sorts))


def _kernel_vmem(traced) -> dict:
    """{kernel's name: (vmem_limit_bytes it asks for, its first scratch
    buffer's shape: the DMA blocks, where it has one)} of every
    `pallas_call` of a traced program, whichever scan or branch holds it:
    what a kernel keeps in VMEM is decided where it is traced, from its
    operands' shapes."""
    found = {}

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                params = eqn.params["compiler_params"].get("mosaic_tpu")
                scratch = eqn.params["grid_mapping"].scratch_avals
                found[eqn.params["name"]] = (
                    params.vmem_limit_bytes if params else None,
                    scratch[0].shape if scratch else None)
            for value in eqn.params.values():
                for inner in (value if isinstance(value, (list, tuple))
                              else [value]):
                    inner = getattr(inner, "jaxpr", inner)
                    if hasattr(inner, "eqns"):
                        walk(inner)

    walk(traced.jaxpr.jaxpr)
    return found


def _described(v5e, eng, **mesh_axes):
    """(placed, arr): abstract arguments for one described chip or, with
    mesh axes given, sharded over the four by the serve rules (`eng.mesh` is
    set to that mesh). placed(tree, logical_axes) places a tree of shapes;
    arr(shape, dtype) is one replicated array."""
    from jax.sharding import (
        NamedSharding, PartitionSpec as P, SingleDeviceSharding,
    )

    from substratus_tpu.parallel.mesh import build_mesh
    from substratus_tpu.parallel.sharding import serve_rules_for, sharding_tree

    if math.prod(mesh_axes.values()) == 1:
        rep = SingleDeviceSharding(v5e[0])

        def shardings(tree, axes):
            return jax.tree.map(lambda _: rep, tree)
    else:
        eng.mesh = mesh = build_mesh(devices=v5e, **mesh_axes)
        rep = NamedSharding(mesh, P())

        def shardings(tree, axes):
            return sharding_tree(tree, mesh, axes, serve_rules_for(mesh))

    def placed(tree, axes):
        return jax.tree.map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            tree, shardings(tree, axes),
        )

    def arr(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=rep)

    return placed, arr


@pytest.mark.parametrize(
    "kv_cache_dtype,tensor,door",
    [("model", 1, True), ("int8", 1, True), ("model", 4, True),
     ("model", 1, False)],
    ids=["bf16", "int8kv", "bf16-tensor4", "bf16-published"],
)
def test_serving_programs_leave_the_kv_pool_in_place(
    kv_cache_dtype, tensor, door, v5e
):
    """decode and the 512-token chunk, for one described chip and for the
    four under a `tensor` mesh (pool sharded over kv_heads): no pool- or
    layer-of-pool-sized copy or slice, and temporaries under half a pool.

    The programs are lowered over the tree the engine's door returns
    (models/llama.py::serving_layout: the int8 q, k and v stacks heads
    first, contracted dim last), and on one chip no layer of a projection
    leaf is written anywhere before its dot reads it. `bf16-published`
    lowers the tree as `init_params` lays it out, without the door: there
    the decode step stages the three slices in VMEM
    (`constant_dynamic-slice_fusion`), which shows that the helper sees
    what it guards (1.2 ms of a 12.1 ms step on the chip: PERF.md section
    6, PR 41)."""
    from substratus_tpu.models import llama
    from substratus_tpu.ops.quant import quantize_params
    from substratus_tpu.serve.engine import Engine, EngineConfig

    cfg = llama.LlamaConfig(
        vocab_size=32000, dim=4096, n_layers=32, n_heads=32, n_kv_heads=8,
        hidden_dim=14336, rope_theta=1e6, max_seq_len=32768,
    )
    # The engine itself is built on the CPU with the smallest pool it takes
    # (nothing can be placed on a described device); its jitted programs are
    # then lowered for the described chips at the cell's shapes.
    eng = Engine(cfg, None, EngineConfig(
        max_batch=_B, max_seq_len=_S, max_prefill_len=_CHUNK,
        kv_cache_dtype=kv_cache_dtype, page_size=_PAGE, kv_pool_tokens=1,
    ))
    quantized = kv_cache_dtype == "int8"
    params = jax.eval_shape(
        lambda key: quantize_params(
            llama.init_params(cfg, key), llama.quant_contracting(cfg)
        ),
        jax.random.key(0),
    )
    pool = jax.eval_shape(
        lambda: llama.init_paged_cache(
            cfg, _POOL_PAGES + 1, _PAGE,
            dtype=jnp.int8 if quantized else None,
        )
    )
    if door:
        params = jax.eval_shape(
            lambda tree: llama.serving_layout(tree, cfg), params)
        assert params["layers"]["wq"].q.shape == (32, 32, 128, 4096)
        assert params["layers"]["wk"].scale.shape == (32, 8, 128, 1)
    placed, arr = _described(v5e, eng, tensor=tensor)
    params = placed(params, llama.serving_logical_axes(params, cfg))
    pool = placed(pool, llama.paged_cache_logical_axes(cfg, quantized))
    m = _S // _PAGE
    programs = {
        "decode": eng._decode_fn.lower(
            params, pool, arr((_B, m)), arr((_B,)), arr((_B,)),
            arr((_B,), jnp.float32), arr((_B,), jnp.float32),
            arr(eng.key.shape, eng.key.dtype),
        ),
        "chunk": Engine._chunk_prefill_jit.lower(
            llama, cfg, params, pool, arr((1, _CHUNK)), arr(()), arr(()),
            arr((1, m)),
        ),
    }
    # One layer of each projection leaf, per device (heads, kv_heads and
    # mlp are the sharded dims).
    layer_of = {
        math.prod(w.q.sharding.shard_shape(w.q.shape)) // cfg.n_layers
        for w in params["layers"].values() if hasattr(w, "q")}
    # Elements per device of each pool array and of one layer of it. The
    # int8 pool's f32 scales [L, P, bs, KH, 1] are the exception the test
    # records: the compiler gives that shape a pages-minor layout and lays
    # the whole array out anew on the way in and out (1/32 of the pool's
    # bytes each), so only a per-layer slice of them is refused.
    sizes = set()
    for name, s in pool.items():
        n = math.prod(s.sharding.shard_shape(s.shape))
        sizes |= {n // cfg.n_layers} | (set() if "scale" in name else {n})
    # Temporaries stay under half a pool; an int8 pool is half the bytes and
    # its step also holds K and V of max_batch x max_seq_len dequantized in
    # f32 (ops/quant.py::dequantize_kv), which is no part of the pool.
    limit = sum(s.dtype.itemsize * s.size for s in pool.values()) / 2
    if quantized:
        limit += 2 * 4 * _B * _S * cfg.n_kv_heads * cfg.head_size
    rows = {"decode": _B, "chunk": 1}
    for name, lowered in programs.items():
        compiled = lowered.compile()
        hlo = compiled.as_text()
        assert _pool_moving_ops(hlo, sizes) == [], name
        temp = compiled.memory_analysis().temp_size_in_bytes
        assert temp < limit / tensor, (name, temp, limit)
        # A bfloat16 pool is read in place, live pages only, by a decode
        # step and by a chunk (under the `tensor` mesh each chip reads its
        # own KV heads): no gathered K or V of rows x max_seq_len, no
        # float32 scores of S x max_seq_len. An int8 pool gathers every
        # table position (ops/kvcache.py).
        scores = cfg.n_heads * _CHUNK * _S if name == "chunk" else 0
        in_place = _reads_pages_in_place(
            hlo, _KERNEL[name], rows[name], _S, cfg.n_kv_heads // tensor,
            cfg.head_size, scores // tensor)
        assert in_place == (not quantized), name
        assert ("kv.gather" in hlo) == quantized, name
        assert _sorts_only_where_a_row_samples(hlo) == (name == "decode")
        anew = _weights_laid_out_anew(hlo, layer_of)
        if not door:
            if name == "decode":
                assert sum("constant_dynamic-slice_fusion" in op
                           and "S(1)" in op for op in anew) == 3, anew
        elif (kv_cache_dtype, tensor) == ("model", 1):
            assert anew == [], (name, anew)
        else:  # recorded, not refused
            print(f"{kv_cache_dtype} tensor={tensor} {name}: {anew}")


# The reason-mixed cell's engine (benchmarks/traffic/reason-mixed.json):
# K-EXAONE's first 12 layers, 16 of 128 experts, an eighth of the vocabulary.
_X_POOL_PAGES, _X_B, _X_S = 10240, 64, 4096


def test_exaone_programs_leave_pool_and_rings_in_place(v5e):
    """The same rule for the family whose cache holds two kinds of history:
    decode and the 512-token chunk move neither the global layers' pool nor
    the window layers' rings, whole or a layer of them; the pool is 12 KB a
    token (3 global layers of 12), not 48; the chunk groups its tokens by
    expert (a loop over blocks, no product with every held expert)."""
    from jax.sharding import SingleDeviceSharding

    from substratus_tpu.models import exaone_moe
    from substratus_tpu.ops.quant import quantize_params
    from substratus_tpu.serve.engine import Engine, EngineConfig

    cfg = exaone_moe.ExaoneMoeConfig(
        vocab_size=19200, n_layers=12, held_experts=(0, 16))
    eng = Engine(cfg, None, EngineConfig(
        max_batch=_X_B, max_seq_len=_X_S, max_prefill_len=_CHUNK,
        page_size=_PAGE, kv_pool_tokens=1,
    ))
    assert eng.slot_state and eng.prefix is None
    rep = SingleDeviceSharding(v5e[0])

    def placed(tree):
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=rep),
            tree)

    def arr(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=rep)

    params = placed(jax.eval_shape(
        lambda key: quantize_params(
            exaone_moe.init_params(cfg, key),
            exaone_moe.quant_contracting(cfg)),
        jax.random.key(0)))
    cache = placed(jax.eval_shape(
        lambda: exaone_moe.init_paged_cache(
            cfg, _X_POOL_PAGES + 1, _PAGE, slots=_X_B)))
    tokens = (_X_POOL_PAGES + 1) * _PAGE
    pool_bytes = sum(cache[n].size * cache[n].dtype.itemsize for n in "kv")
    assert pool_bytes == tokens * 12 * 1024
    assert cache["wk"].shape == (9, _X_B, 128, 8, 128)
    m = _X_S // _PAGE
    programs = {
        "decode": eng._decode_fn.lower(
            params, cache, arr((_X_B, m)), arr((_X_B,)), arr((_X_B,)),
            arr((_X_B,), jnp.float32), arr((_X_B,), jnp.float32),
            arr(eng.key.shape, eng.key.dtype), None, None,
            arr((_X_B,), jnp.bool_),
        ),
        "chunk": Engine._chunk_prefill_jit.lower(
            exaone_moe, cfg, params, cache, arr((1, _CHUNK)), arr(()), arr(()),
            arr((1, m)), None, None, arr(()),
        ),
    }
    # Refused: any bfloat16 copy or slice the size of the pool, of a layer
    # of it, or of the rings; and a slice the size of one layer's rings. A
    # *copy* of that last size is the step's own read of the rings
    # ([max_batch, W, KH, hd], by construction as large as a layer of them)
    # laid out for the dot, as the pool's gathered context is: not refused.
    # And no int8 weight is laid out anew: stored [D, heads, hd] or [D,
    # heads * hd], the q, k and v stacks of every layer were copied in each
    # program, contracted dim last (2.9 ms of a decode step: PERF.md
    # section 6, PR 27); they are stored that way now.
    whole = {cache[n].size for n in ("k", "wk")} | {
        cache["k"].size // cache["k"].shape[0]}
    ring_layer = {cache["wk"].size // cache["wk"].shape[0]}
    limit = sum(s.dtype.itemsize * s.size for s in cache.values()) / 2
    for name, lowered in programs.items():
        compiled = lowered.compile()
        hlo = compiled.as_text()
        bf16 = "\n".join(l for l in hlo.splitlines() if "= bf16[" in l)
        assert _pool_moving_ops(bf16, whole) == [], name
        assert [op for op in _pool_moving_ops(bf16, ring_layer)
                if "copy" not in op] == [], name
        assert not re.search(r"= s8\[[\d,]+\]\S* copy\(", hlo), name
        temp = compiled.memory_analysis().temp_size_in_bytes
        assert temp < limit, (name, temp, limit)
        for scope in ("kv.ring", "attn.window", "moe.shared", "moe.router",
                      "moe.experts", "attn.core"):
            assert scope in hlo, (name, scope)
        assert _sorts_only_where_a_row_samples(hlo) == (name == "decode")
        # the global layers of the step and of the chunk read live pages
        # in place: no gather, no K or V of rows x max_seq_len, no float32
        # scores of 512 x max_seq_len
        rows = _X_B if name == "decode" else 1
        assert _reads_pages_in_place(
            hlo, _KERNEL[name], rows, _X_S, cfg.n_kv_heads, cfg.head_size,
            cfg.n_heads * _CHUNK * _X_S if name == "chunk" else 0), name
        assert "kv.gather" not in hlo, name
        # the chunk multiplies pairs grouped by expert, one block of one
        # expert's rows at a time; the decode step every held expert
        grouped = "s8[1,1,6144,2048]" in hlo
        assert grouped == (name == "chunk"), name
        # no layer of a projection stack is written anywhere before its
        # dot reads it, in the scan's body or in the head of four layers:
        # `forward` views the stacks [L, heads, hd, D] before it slices
        # them (flat, the body held `constant_dynamic-slice_fusion.58`,
        # three s8[1,8192,6144] a period, and the head the same: 1.9 ms of
        # a 16.5 ms step) and hands the slices an index the compiler cannot
        # fold (folded, layer 0's four slices stayed plain copies in
        # `main`: s8[1,8192,6144] x 2, s8[1,1024,6144] x 2, 113 MB a step)
        layer_of = {w.q.size // cfg.n_layers
                    for w in params["layers"].values() if hasattr(w, "q")}
        assert layer_of == {8192 * 6144, 1024 * 6144}
        assert _weights_laid_out_anew(hlo, layer_of) == [], name


# The assist cell's engine (benchmarks/traffic/assist.json): LFM2-24B-A2B's
# first 16 layers, all 64 experts, the whole vocabulary.
_F_POOL_PAGES, _F_B, _F_S = 6144, 64, 2048


def test_lfm2_programs_compile_and_leave_the_conv_state_in_place(v5e):
    """The family whose cache holds pages beside convolution rows: decode
    and the 512-token chunk compile for the chip at the published widths,
    read the live pages in place (heads of 64 lie two to a stored row of
    128: the kernels, no gather, and no op moves the pool), move the
    convolution layers' state neither whole nor a layer of it, lay no int8
    weight out anew, and the chunk groups its tokens by expert."""
    from jax.sharding import SingleDeviceSharding

    from substratus_tpu.models import lfm2_moe
    from substratus_tpu.ops.quant import quantize_params
    from substratus_tpu.serve.engine import Engine, EngineConfig

    cfg = lfm2_moe.Lfm2MoeConfig(
        n_layers=16, layer_types=lfm2_moe.Lfm2MoeConfig().layer_types[:16])
    assert (cfg.count(lfm2_moe.CONV), cfg.count(lfm2_moe.ATTN)) == (12, 4)
    eng = Engine(cfg, None, EngineConfig(
        max_batch=_F_B, max_seq_len=_F_S, max_prefill_len=_CHUNK,
        page_size=_PAGE, kv_pool_tokens=1,
    ))
    assert eng.slot_state and eng.prefix is None
    rep = SingleDeviceSharding(v5e[0])

    def placed(tree):
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=rep),
            tree)

    def arr(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=rep)

    params = placed(jax.eval_shape(
        lambda key: quantize_params(
            lfm2_moe.init_params(cfg, key), lfm2_moe.quant_contracting(cfg)),
        jax.random.key(0)))
    cache = placed(jax.eval_shape(
        lambda: lfm2_moe.init_paged_cache(
            cfg, _F_POOL_PAGES + 1, _PAGE, slots=_F_B)))
    tokens = (_F_POOL_PAGES + 1) * _PAGE
    pool_bytes = sum(cache[n].size * cache[n].dtype.itemsize for n in "kv")
    assert pool_bytes == tokens * 8 * 1024  # 4 attention layers of 16
    assert cache["k"].shape == (4, _F_POOL_PAGES + 1, _PAGE, 4, 128)
    assert cache["conv"].shape == (12, _F_B, 2, 2048)
    m = _F_S // _PAGE
    programs = {
        "decode": eng._decode_fn.lower(
            params, cache, arr((_F_B, m)), arr((_F_B,)), arr((_F_B,)),
            arr((_F_B,), jnp.float32), arr((_F_B,), jnp.float32),
            arr(eng.key.shape, eng.key.dtype), None, None,
            arr((_F_B,), jnp.bool_),
        ),
        "chunk": Engine._chunk_prefill_jit.lower(
            lfm2_moe, cfg, params, cache, arr((1, _CHUNK)), arr(()), arr(()),
            arr((1, m)), None, None, arr(()),
        ),
    }
    # Refused: a copy or slice the size of the whole state, and a slice the
    # size of one layer of it. A *copy* of that last size is the step's own
    # read of its 64 slots' rows ([max_batch, 2, D], by construction as
    # large as a layer of the state), as with the rings above.
    whole, state_layer = {cache["conv"].size}, {cache["conv"].size // 12}
    pool = {cache["k"].size, cache["k"].size // 4}  # whole, or a layer
    for name, lowered in programs.items():
        compiled = lowered.compile()
        hlo = compiled.as_text()
        rows = _F_B if name == "decode" else 1
        assert _reads_pages_in_place(
            hlo, _KERNEL[name], rows, _F_S, cfg.n_kv_heads, cfg.head_size,
            cfg.n_heads * _CHUNK * _F_S if name == "chunk" else 0), name
        assert "kv.gather" not in hlo, name
        assert all(s in hlo for s in ("conv.in", "conv.state", "conv.out"))
        assert _sorts_only_where_a_row_samples(hlo) == (name == "decode")
        bf16 = "\n".join(l for l in hlo.splitlines() if "= bf16[" in l)
        assert _pool_moving_ops(bf16, pool) == [], name
        # (the chunk's one slot is written by a dynamic-update-slice whose
        # result is the state itself, updated in place: not a move)
        assert [op for op in _pool_moving_ops(bf16, whole)
                if "dynamic-update-slice" not in op] == [], name
        assert [op for op in _pool_moving_ops(bf16, state_layer)
                if "copy" not in op] == [], name
        assert not re.search(r"= s8\[[\d,]+\]\S* copy\(", hlo), name
        # it fits beside 9.1 GB of weights and the pool, and holds no
        # second pool (until PR 35 the device kept a pool of 64-wide heads
        # pages-innermost and each program laid it out anew: 823 MB)
        temp = compiled.memory_analysis().temp_size_in_bytes
        assert temp < 1.5e9, (name, temp)
    # the chunk's experts are a loop over blocks of rows, the step's a
    # product with every expert
    assert "moe.experts/while" in programs["chunk"].compile().as_text()


# The longctx cell's engine (benchmarks/traffic/longctx.json): Brumby-14B-
# Base's first 10 layers, every one power retention, the whole vocabulary.
_R_B, _R_S = 16, 9216


def _brumby_programs(v5e, **mesh_axes):
    """(lowered decode, lowered 512 chunk, cache shapes) of the longctx
    cell's engine for one described chip or, with mesh axes, the four."""
    from substratus_tpu.models import brumby
    from substratus_tpu.ops.quant import quantize_params
    from substratus_tpu.serve.engine import Engine, EngineConfig

    cfg = brumby.BrumbyConfig(n_layers=10, gate_shift=9.0)
    eng = Engine(cfg, None, EngineConfig(
        max_batch=_R_B, max_seq_len=_R_S, max_prefill_len=_CHUNK,
        page_size=_PAGE, kv_pool_tokens=1,
    ))
    assert eng.slot_state and eng.prefix is None and eng._page_layers == 0
    placed, arr = _described(v5e, eng, **mesh_axes)
    params = placed(jax.eval_shape(
        lambda key: quantize_params(
            brumby.init_params(cfg, key), brumby.quant_contracting(cfg)),
        jax.random.key(0)), brumby.param_logical_axes(cfg))
    cache = placed(jax.eval_shape(
        lambda: brumby.init_paged_cache(
            cfg, _R_B * _R_S // _PAGE + 1, _PAGE, slots=_R_B)),
        brumby.paged_cache_logical_axes(cfg))
    m = _R_S // _PAGE
    decode = eng._decode_fn.lower(
        params, cache, arr((_R_B, m)), arr((_R_B,)), arr((_R_B,)),
        arr((_R_B,), jnp.float32), arr((_R_B,), jnp.float32),
        arr(eng.key.shape, eng.key.dtype), None, None,
        arr((_R_B,), jnp.bool_),
    )
    chunk = Engine._chunk_prefill_jit.lower(
        brumby, cfg, params, cache, arr((1, _CHUNK)), arr(()), arr(()),
        arr((1, m)), None, None, arr(()),
    )
    return decode, chunk, cache


def _state_kernel_calls(hlo: str) -> int:
    return len(re.findall(
        r'custom_call_target="tpu_custom_call".*retention_state_step', hlo))


def test_brumby_programs_compile_and_leave_the_state_in_place(v5e):
    """The family whose cache is per-slot state alone: decode and the
    512-token chunk compile for the chip at the published widths beside a
    page pool of no layers; the 5.45 GB of retention state is the layer
    scan's carry, read where it lies and written where it lies. The decode
    step moves `S` through ops/retention_kernel.py, one call in the scan's
    body, and nothing else of the program has an operand the size of a
    layer's slab of it: the state is read once and written once (PR 37;
    until then a convolution and a loop fusion read it twice). The chunk
    holds no kernel. A decode step keeps under a third of one layer's slab
    (541 MB) in temporaries, so no slab of the state is copied out of the
    carry, and no `copy` in either program has the size of the state, a
    layer of it or a slot of it; both open the family's two regions and no
    attention or page one."""
    decode, chunk, cache = _brumby_programs(v5e)
    assert cache["k"].shape == (0, _R_B * _R_S // _PAGE + 1, _PAGE, 8, 128)
    assert cache["ret_s"].shape == (10, _R_B, 8, 8256, 128)
    assert cache["ret_s"].dtype == cache["ret_z"].dtype == jnp.float32
    state = sum(cache[n].size * 4 for n in ("ret_s", "ret_z"))
    assert 5.45e9 < state < 5.46e9
    s_all = cache["ret_s"].size
    sizes = {s_all, s_all // 10, s_all // 10 // _R_B}  # whole, layer, slot
    sizes |= {n // 128 for n in sizes}  # the same of z
    temp_limit = {"decode": 0.18e9, "chunk": 2.0e9}
    for name, lowered in (("decode", decode), ("chunk", chunk)):
        compiled = lowered.compile()
        hlo = compiled.as_text()
        assert all(r in hlo for r in ("ret.state", "attn.qkv", "attn.out"))
        assert ("ret.intra" in hlo) == (name == "chunk"), name
        assert not any(r in hlo for r in ("kv.write", "kv.gather",
                                          "attn.core"))
        assert _state_kernel_calls(hlo) == (name == "decode"), name
        assert ("tpu_custom_call" in hlo) == (name == "decode"), name
        assert _sorts_only_where_a_row_samples(hlo) == (name == "decode")
        if name == "decode":
            # the kernel's call lies in the region the benchmark reads
            call = re.search(r".*retention_state_step.*", hlo).group(0)
            assert "ret.state" in call
            # besides the kernel, which takes the whole stack, no op has
            # an operand or a result of a layer's slab of `ret_s`
            assert f"f32[{_R_B},8,8256,128]" not in hlo
            assert f"f32[10,{_R_B},8,8256,128]" in call
        f32 = "\n".join(l for l in hlo.splitlines() if "= f32[" in l)
        assert [op for op in _pool_moving_ops(f32, sizes)
                if " copy(" in op] == [], name
        assert not re.search(r"= s8\[[\d,]+\]\S* copy\(", hlo), name
        # no layer of a weight stack is written anywhere before its dot
        # reads it: `forward` views the four projection stacks [L, heads,
        # hd, D] before it slices them (flat, each program staged
        # s8[1,5120,5120] and one or two s8[1,1024,5120] in VMEM, every
        # layer: `constant_dynamic-slice_fusion`, PR 41)
        assert _weights_laid_out_anew(
            hlo, {5120 * 5120, 1024 * 5120, 5120 * 17408}) == [], name
        mem = compiled.memory_analysis()
        # the state is donated and comes back as the same buffers
        assert mem.alias_size_in_bytes >= state, name
        # decode: 11 MB at PR 36 and PR 37; the chunk's 1.5 GB are phi(q)
        # of 40 heads x 512 tokens in bfloat16 (338 MB) beside a turned copy
        # of it and the logits of 512 rows. Beside 5.64 GB of weights and
        # the state
        assert mem.temp_size_in_bytes < temp_limit[name], (
            name, mem.temp_size_in_bytes)


def test_brumby_decode_compiles_under_a_tensor_mesh_with_the_kernel(v5e):
    """`tensor` = 4 over the described 2x2: the state is sharded over its 8
    KV heads and nothing else is, so the decode program holds the kernel,
    under `shard_map` over that axis (each chip its own two heads' state:
    1.36 GB a chip, no collective moves it), not the fallback; no op but
    the kernel has an operand of a chip's share of a layer's slab."""
    decode, _, cache = _brumby_programs(v5e, tensor=4)
    assert cache["ret_s"].sharding.shard_shape(cache["ret_s"].shape) == (
        10, _R_B, 2, 8256, 128)
    compiled = decode.compile()
    hlo = compiled.as_text()
    assert _state_kernel_calls(hlo) == 1
    assert f"f32[{_R_B},2,8256,128]" not in hlo
    state = sum(cache[n].size * 4 for n in ("ret_s", "ret_z")) // 4
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= state
    assert mem.temp_size_in_bytes < 0.18e9, mem.temp_size_in_bytes


# The docqa cell's engine (benchmarks/traffic/docqa.json): the language
# model of dots.vlm1.inst, its first 16 layers (3 dense + 13 sparse), 8 of
# 256 experts held, an eighth of the vocabulary; given no page, as the
# benchmark's harness gives none: the family's.
_D_B, _D_S, _D_POOL_TOKENS = 12, 14336, 163840


def test_deepseek_v3_programs_compile_with_both_latent_kernels(v5e):
    """The family whose pages hold one latent row a token for all heads:
    decode and the 512-token chunk compile for the chip at the published
    widths; the step holds the absorbed kernel and the chunk the expanded
    one (ops/latent_attention.py), neither gathers a context, no op moves
    the pool or a layer of it, the chunk holds no context expanded in HBM
    (at 14k tokens one layer's keys and values are 0.94 GB: no result is
    that large, and the program's temporaries stay under one layer's W_O
    beside 13 GB of weights and pool), and the chunk groups its tokens by
    expert. At the family's page of 128 tokens the kernels' blocks hold the
    tokens they held at 16 (1,024 a decode block, 512 keys a chunk block)
    and ask for the VMEM they asked for."""
    from substratus_tpu.models import deepseek_v3
    from substratus_tpu.ops.quant import quantize_params
    from substratus_tpu.serve.engine import Engine, EngineConfig

    cfg = deepseek_v3.DeepseekV3Config(
        n_layers=16, vocab_size=16160, held_experts=(0, 8))
    assert deepseek_v3.layer_plan(cfg) == (3, 1, 13)
    eng = Engine(cfg, None, EngineConfig(
        max_batch=_D_B, max_seq_len=_D_S, max_prefill_len=_CHUNK,
        kv_pool_tokens=1,
    ))
    assert not eng.slot_state and eng.prefix is not None
    page = eng.page_size
    assert page == deepseek_v3.PAGE_TOKENS == 128
    pool_pages = _D_POOL_TOKENS // page
    placed, arr = _described(v5e, eng)
    params = placed(jax.eval_shape(
        lambda key: quantize_params(
            deepseek_v3.init_params(cfg, key),
            deepseek_v3.quant_contracting(cfg)),
        jax.random.key(0)), deepseek_v3.param_logical_axes(cfg))
    cache = placed(jax.eval_shape(
        lambda: deepseek_v3.init_paged_cache(cfg, pool_pages + 1, page)),
        deepseek_v3.paged_cache_logical_axes(cfg))
    # one row of 576 a token and layer, stored 640 wide; no second pool
    assert cache["k"].shape == (16, pool_pages + 1, page, 1, 640)
    assert cache["v"].shape[0] == 0
    m = _D_S // page
    assert eng.block_table.shape == (_D_B, m)
    programs = {
        "decode": eng._decode_fn.trace(
            params, cache, arr((_D_B, m)), arr((_D_B,)), arr((_D_B,)),
            arr((_D_B,), jnp.float32), arr((_D_B,), jnp.float32),
            arr(eng.key.shape, eng.key.dtype), None, None,
            arr((_D_B,), jnp.bool_),
        ),
        "chunk": Engine._chunk_prefill_jit.trace(
            deepseek_v3, cfg, params, cache, arr((1, _CHUNK)), arr(()),
            arr(()), arr((1, m)), None, None, arr(()),
        ),
    }
    kernels = {"decode": "latent_decode_attention",
               "chunk": "latent_chunk_attention"}
    # two DMA blocks of 1,024 tokens (2.6 MB) and 2 MB of scores beside q
    # and the output; two of 512 keys, 8 heads' weights and a fold's scores
    vmem = {"decode": (17039360, (2, 1024 // page, page, 640)),
            "chunk": (25165824, (2, 512 // page, page, 640))}
    pool = {cache["k"].size, cache["k"].size // 16}  # whole, or a layer
    expanded_layer = _D_S * cfg.n_heads * 256  # one layer's K and V, whole
    for name, traced in programs.items():
        assert _kernel_vmem(traced) == {kernels[name]: vmem[name]}, name
        compiled = traced.lower().compile()
        hlo = compiled.as_text()
        assert re.search(
            r'custom_call_target="tpu_custom_call".*' + kernels[name], hlo
        ), name
        other = kernels["chunk" if name == "decode" else "decode"]
        assert other not in hlo, name
        assert "kv.gather" not in hlo, name
        assert ("attn.absorb" in hlo) == (name == "decode"), name
        assert ("attn.expand" in hlo) == (name == "chunk"), name
        bf16 = "\n".join(l for l in hlo.splitlines() if "= bf16[" in l)
        assert _pool_moving_ops(bf16, pool) == [], name
        # no float result as large as one layer's expanded context
        for mm in re.finditer(r"= (?:bf16|f32)\[([\d,]+)\]\S* [\w-]+\(", hlo):
            n = math.prod(map(int, mm.group(1).split(",")))
            assert n < expanded_layer or n in pool, (name, mm.group(0))
        # under one layer's W_O (117 MB): no weight is written out anew
        temp = compiled.memory_analysis().temp_size_in_bytes
        assert temp < 1.1e8, (name, temp)
        if name == "decode":
            # nor a slice of W_UQ (its leaves lie a head apart: as [H dn,
            # rq] the step copied the layer's 25 MB + 12.6 MB out of the
            # stack, every layer, and held 62 MB of temporaries)
            assert temp < 4.5e7, (name, temp)
        if name == "chunk":
            assert "moe.experts/while" in hlo


# The repoqa cell's engine (benchmarks/traffic/repoqa.json): GLM-5's first
# 13 layers (3 dense + 10 sparse), 8 of 256 experts held, an eighth of the
# vocabulary, a learned index in every layer.
_G_B, _G_S, _G_POOL_TOKENS = 4, 18432, 73728


@pytest.mark.slow  # a minute; the two kernel cases above stay in tier-1
def test_glm_dsa_programs_compile_with_the_index_kernels(v5e):
    """The same family under a learned index (GLM-5's widths: 64 heads of
    192 + 64 against 256, 32 index heads over keys of 128, the 2,048 best
    rows a query): the pool's second array holds the index keys under the
    same page ids; the decode program scores them in place
    (`index_decode_scores`), takes each slot's set by a threshold and a
    compaction over the scores held in VMEM (`index_select_rows`: no sort
    but the sampler's) and gathers the picked rows by position, and holds
    no kernel that walks a row's pages of latents; the 512 chunk scores by
    `index_chunk_scores` and runs the expanded kernel under the sets;
    neither moves either array of the pool. The engine is given no page
    and takes the family's 128 tokens: a block of the keys' copies holds
    1,024 tokens in 8 pages."""
    from substratus_tpu.models import deepseek_v3
    from substratus_tpu.ops.quant import quantize_params
    from substratus_tpu.serve.engine import Engine, EngineConfig

    cfg = deepseek_v3.CONFIGS["glm-5"].replace(
        n_layers=13, vocab_size=19360, held_experts=(0, 8))
    assert deepseek_v3.layer_plan(cfg) == (3, 1, 10)
    eng = Engine(cfg, None, EngineConfig(
        max_batch=_G_B, max_seq_len=_G_S, max_prefill_len=_CHUNK,
        kv_pool_tokens=1,
    ))
    assert not eng.slot_state and eng.prefix is not None
    assert "dsa_selections" in eng.stats
    page = eng.page_size
    assert page == deepseek_v3.PAGE_TOKENS == 128
    pool_pages = _G_POOL_TOKENS // page
    placed, arr = _described(v5e, eng)
    params = placed(jax.eval_shape(
        lambda key: quantize_params(
            deepseek_v3.init_params(cfg, key),
            deepseek_v3.quant_contracting(cfg)),
        jax.random.key(0)), deepseek_v3.param_logical_axes(cfg))
    cache = placed(jax.eval_shape(
        lambda: deepseek_v3.init_paged_cache(cfg, pool_pages + 1, page)),
        deepseek_v3.paged_cache_logical_axes(cfg))
    assert cache["k"].shape == (13, pool_pages + 1, page, 1, 640)
    assert cache["v"].shape == (13, pool_pages + 1, page, 1, 128)
    m = _G_S // page
    programs = {
        "decode": eng._decode_fn.trace(
            params, cache, arr((_G_B, m)), arr((_G_B,)), arr((_G_B,)),
            arr((_G_B,), jnp.float32), arr((_G_B,), jnp.float32),
            arr(eng.key.shape, eng.key.dtype), None, None,
            arr((_G_B,), jnp.bool_),
        ),
        "chunk": Engine._chunk_prefill_jit.trace(
            deepseek_v3, cfg, params, cache, arr((1, _CHUNK)), arr(()),
            arr(()), arr((1, m)), None, None, arr(()),
        ),
    }
    kernels = {"decode": ("index_decode_scores", "index_select_rows"),
               "chunk": ("index_chunk_scores", "latent_chunk_attention")}
    pool = set()
    for a in (cache["k"], cache["v"]):
        pool |= {a.size, a.size // 13}  # whole, or a layer
    for name, traced in programs.items():
        vmem = _kernel_vmem(traced)
        assert set(vmem) == set(kernels[name]), name
        if name == "decode":
            # two blocks of 1,024 keys (0.5 MB) under the default limit
            assert vmem["index_decode_scores"] == (
                None, (2, 1024 // page, page, 128))
            # the four slots' scores as ordered keys (0.3 MB), one slot's
            # one-hot and running counts (2 MB) under the default limit
            assert vmem["index_select_rows"] == (None, (_G_B, m, page))
        else:
            # what it asked for at 16 tokens a page: two blocks of 512
            # keys and of the bias, 8 heads' weights, a fold's scores
            assert vmem["latent_chunk_attention"] == (
                35389440, (2, 512 // page, page, 640))
        compiled = traced.lower().compile()
        hlo = compiled.as_text()
        for kernel in kernels[name]:
            assert re.search(
                r'custom_call_target="tpu_custom_call".*' + kernel, hlo
            ), (name, kernel)
        assert "latent_decode_attention" not in hlo, name
        assert "attn.index" in hlo and "attn.select" in hlo, name
        assert ("kv.gather" in hlo) == False, name  # noqa: E712
        bf16 = "\n".join(l for l in hlo.splitlines() if "= bf16[" in l)
        assert _pool_moving_ops(bf16, pool) == [], name
        temp = compiled.memory_analysis().temp_size_in_bytes
        assert temp < 1.6e8, (name, temp)
        # no selection sorts: the step's only sort is the sampler's, in
        # its sampled branch
        assert _sorts_only_where_a_row_samples(hlo) == (name == "decode")
        assert "sort(" not in "\n".join(
            l for l in hlo.splitlines() if "attn.select" in l), name


@pytest.mark.parametrize("program", ["decode", "chunk"])
def test_tinyllama_paged_programs_compile_for_v5e(program, v5e):
    """What `serve.main --config tinyllama-1.1b` compiles on a TPU at its
    defaults (8 slots of 1,024, chunks of 512, the paged layout): 4 KV
    heads of 64 are two stored rows of 128 a token, and both programs read
    them in place (S3c: from PR 28 to PR 30 the decode program held a
    kernel over a 64-wide row, which Mosaic refuses, and chip_smoke.py's
    serve phases answered 500)."""
    from substratus_tpu.models import llama
    from substratus_tpu.serve.engine import Engine, EngineConfig

    cfg = llama.CONFIGS["tinyllama-1.1b"]
    b, s = 8, 1024
    eng = Engine(cfg, None, EngineConfig(
        max_batch=b, max_seq_len=s, max_prefill_len=_CHUNK, page_size=_PAGE,
        kv_pool_tokens=1,
    ))
    assert eng.paged
    placed, arr = _described(v5e, eng)
    params = placed(
        jax.eval_shape(lambda key: llama.init_params(cfg, key),
                       jax.random.key(0)),
        llama.param_logical_axes(cfg),
    )
    pool = placed(
        jax.eval_shape(
            lambda: llama.init_paged_cache(cfg, b * s // _PAGE + 1, _PAGE)),
        llama.paged_cache_logical_axes(cfg, False),
    )
    m = s // _PAGE
    if program == "decode":
        lowered = eng._decode_fn.lower(
            params, pool, arr((b, m)), arr((b,)), arr((b,)),
            arr((b,), jnp.float32), arr((b,), jnp.float32),
            arr(eng.key.shape, eng.key.dtype),
        )
    else:
        lowered = Engine._chunk_prefill_jit.lower(
            llama, cfg, params, pool, arr((1, _CHUNK)), arr(()), arr(()),
            arr((1, m)),
        )
    hlo = lowered.compile().as_text()
    assert pool["k"].shape[3:] == (2, 128)
    assert _reads_pages_in_place(
        hlo, _KERNEL[program], b if program == "decode" else 1, s,
        cfg.n_kv_heads, cfg.head_size,
        cfg.n_heads * _CHUNK * s if program == "chunk" else 0)
    assert "kv.gather" not in hlo
    assert _pool_moving_ops(
        hlo, {pool["k"].size, pool["k"].size // cfg.n_layers}) == []


# The dense slot cache [L, B, KH, S, hd] at the server's defaults (8 slots of
# 1,024 positions, chunks of 512): the only layout Falcon and OPT have, and
# the only one that splits over `sequence` (there at TinyLlama's whole 2,048).
_DENSE = {
    "llama-bf16": ("llama", "tinyllama-1.1b", "model", 1, 1024),
    "llama-int8kv": ("llama", "tinyllama-1.1b", "int8", 1, 1024),
    "falcon": ("falcon", "falcon-7b", "model", 1, 1024),
    "opt": ("opt", "opt-1.3b", "model", 1, 1024),
    "llama-sequence4": ("llama", "tinyllama-1.1b", "model", 4, 2048),
}


@pytest.mark.parametrize("program", ["decode", "chunk"])
@pytest.mark.parametrize("name", list(_DENSE))
def test_dense_serving_programs_compile_for_v5e(name, program, v5e):
    """The engine's decode step and 512-token chunk over the dense slot
    cache compile for a described v5e and fit it, as plain XLA: the one
    attention of ops/decode_attention.py, no kernel. Only the int8 cache's
    chunk dequantizes a slot (`kv.gather`). With the cache split four ways
    over its positions each chip holds a quarter of it and nothing gathers
    it: the softmax's partial sums are all that cross chips.

    What the compiler does put in is recorded, not refused: each program
    copies every cache array once, whole (the layer scan takes the cache
    as `xs` and returns it as `ys`; the paged pool is carried in place
    since PR 25). A second whole copy of any of them fails here."""
    import importlib

    from substratus_tpu.serve.engine import Engine, EngineConfig

    family, config, kv_cache_dtype, sequence, seq_len = _DENSE[name]
    model = importlib.import_module(f"substratus_tpu.models.{family}")
    cfg = model.CONFIGS[config]
    quantized = kv_cache_dtype == "int8"
    b = 8
    # Built on the CPU with the smallest cache it takes; its jitted programs
    # are lowered for the described chips at the shapes above.
    eng = Engine(cfg, None, EngineConfig(
        max_batch=1, max_seq_len=16, max_prefill_len=_CHUNK,
        kv_cache_dtype=kv_cache_dtype, kv_layout="dense",
    ))
    assert not eng.paged
    placed, arr = _described(v5e, eng, sequence=sequence)
    params = placed(
        jax.eval_shape(lambda key: model.init_params(cfg, key),
                       jax.random.key(0)),
        model.param_logical_axes(cfg),
    )
    slots = 1 if program == "chunk" else b  # a chunk runs on its slot's cache
    cache = placed(
        jax.eval_shape(lambda: model.init_cache(
            cfg, slots, seq_len, dtype=jnp.int8 if quantized else None)),
        model.cache_logical_axes(cfg, quantized),
    )
    if program == "decode":
        lowered = eng._decode_fn.lower(
            params, cache, None, arr((b,)), arr((b,)),
            arr((b,), jnp.float32), arr((b,), jnp.float32),
            arr(eng.key.shape, eng.key.dtype),
        )
    else:
        lowered = Engine._chunk_prefill_jit.lower(
            model, cfg, params, cache, arr((1, _CHUNK)), arr(()), arr(()),
        )
    hlo = lowered.compile().as_text()  # raises where it does not fit 16 GB
    assert "tpu_custom_call" not in hlo
    for scope in ("kv.write", "attn.core"):
        assert scope in hlo, scope
    assert ("kv.gather" in hlo) == (quantized and program == "chunk")
    per_chip = {
        k: math.prod(s.sharding.shard_shape(s.shape)) for k, s in cache.items()
    }
    assert all(n * sequence == cache[k].size for k, n in per_chip.items())
    whole = _pool_moving_ops(
        "\n".join(l for l in hlo.splitlines() if " copy(" in l),
        set(per_chip.values()),
    )
    assert len(whole) <= len(cache), whole
    assert not re.search(r"all-gather|all-to-all|collective-permute", hlo)
