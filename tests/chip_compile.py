"""What the `test_chip_compile*.py` files share: readers of a compiled
program's optimized HLO and of a traced program's kernels, and the abstract
arguments of an engine's programs for the described chips of the `v5e`
fixture (tests/conftest.py). One file a family, so that `--dist loadfile`
can spread them over its workers."""
import math
import re

import jax
import jax.numpy as jnp

# Every cell's engine: chunks of 512, and pages of 16 tokens where the family
# states no page of its own (the latent family states 128, the two whose
# stored row holds two heads of 64 state 64 and 128: their files take the
# engine's).
PAGE, CHUNK = 16, 512


def pool_moving_ops(hlo: str, sizes) -> list:
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


def _ops(hlo: str):
    """(computation, whether it is a fusion's inside, op's name, result
    type, opcode, the line) of every op of the optimized HLO."""
    where = ""
    for line in hlo.splitlines():
        if line and not line.startswith(" "):
            where = line.split("(")[0].replace("ENTRY", "").strip(" %")
            continue
        m = re.match(r"\s+(?:ROOT )?%?([\w.\-]+) = (.*?) ([\w\-]+)\(", line)
        if m:
            yield (where, "fused_computation" in where, *m.groups(),
                   line.strip())


def weights_laid_out_anew(hlo: str, sizes) -> list:
    """Every op of the optimized HLO that stands outside any fusion (so
    outside every dot's fusion: a fusion's own result is listed, its inside
    is not) and whose result is int8 with one of `sizes` elements, the
    elements of one layer of a projection leaf: a layer's weights written
    somewhere else before their dot reads them (a
    `constant_dynamic-slice_fusion` staged in VMEM, a `copy`, a
    `copy_bitcast_fusion`, a plain `slice`). As "computation: name =
    type op"."""
    found = []
    for where, fused, name, result, opcode, _ in _ops(hlo):
        if fused or opcode in _NO_MOVE:
            continue
        for t in re.finditer(r"s8\[([\d,]+)\](\{[^}]*\})?", result):
            if math.prod(map(int, t.group(1).split(","))) in sizes:
                found.append(f"{where}: {name} = {t.group(0)} {opcode}")
    return found


def region_ops(hlo: str, region: str) -> tuple:
    """(kernels, inside) of a region (ops/scopes.py: its name in an op's
    `op_name`), as text lines with `_NO_MOVE` and constants left out:
    {computation: the region's ops outside any fusion}, what the device
    runs one after the other, a kernel each, and the list of its ops
    inside the fusions, what those kernels are made of."""
    kernels, inside = {}, []
    for where, fused, _, _, opcode, line in _ops(hlo):
        scope = re.search(r'op_name="([^"]*)"', line)
        if (scope and region in scope.group(1)
                and opcode not in _NO_MOVE | {"constant"}):
            (inside if fused else kernels.setdefault(where, [])).append(line)
    return kernels, inside


def reads_pages_in_place(hlo: str, kernel: str, rows: int, seq: int,
                          kv_heads: int, head_dim: int,
                          scores: int = 0, page: int = PAGE) -> bool:
    """The program's attention is the named kernel of
    ops/paged_attention.py, and nothing in the program is a gathered K or V
    (a result [..., kv_heads, head_dim] of rows x seq positions, flat or
    as pages of `page`: gone, not moved) nor a float32 result of `scores` =
    heads x S x seq elements (a chunk's scores never reach HBM)."""
    sized = []
    for m in re.finditer(r"= (\w+)\[([\d,]+)\]\S* [\w-]+\(", hlo):
        dims = list(map(int, m.group(2).split(",")))
        n = math.prod(dims)
        lead = set(dims[:-2])
        context = (dims[-2:] == [kv_heads, head_dim]
                   and n == rows * seq * kv_heads * head_dim
                   and (seq in lead or {rows * seq // page, page} <= lead))
        if context or (m.group(1) == "f32" and n == scores):
            sized.append(m.group(0))
    found = re.search(
        r'custom_call_target="tpu_custom_call".*' + kernel, hlo)
    return bool(found) and not sized


KERNEL = {"decode": "paged_decode_attention",
           "chunk": "paged_chunk_attention"}


def sorts_only_where_a_row_samples(hlo: str) -> bool:
    """The decode program kept the sampler's branch as a `conditional`
    (ops/sampling.py::sample: not flattened into a select that runs both
    sides), and every sort of the sampler lies in its sampled branch."""
    sorts = [name for name in
             re.findall(r" sort\(.*?op_name=\"([^\"]*)\"", hlo)
             if "/sample/" in name]
    return (bool(re.search(r" conditional\(.*op_name=\"[^\"]*sample/cond", hlo))
            and bool(sorts)
            and all("sample/cond/branch_1_fun/" in name for name in sorts))


def kernel_vmem(traced) -> dict:
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


def described(v5e, eng, **mesh_axes):
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
