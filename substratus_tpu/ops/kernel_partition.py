"""SPMD partitioning for (batch, head)-local Pallas kernels.

Attention-family kernels are embarrassingly parallel over batch and
(kv-)head once the sequence and head-dim axes stay whole: every shard
can run the identical kernel on its slice with zero collectives. GSPMD
cannot know that about an opaque `pallas_call`, so without a rule it
either fails to partition or all-gathers the operands. This module
generalizes the rule used by ops/quant4.py: wrap the kernel in
`jax.experimental.custom_partitioning`, read the mesh axes for batch
and head off a reference operand's sharding, and force every
operand/result spec consistent — batch/head sharded, everything else
replicated.

Used by ops/flash_attention.py (forward and backward) so the TPU serving
default of the no-cache prefill (attn_impl="flash") and flash training
survive GSPMD sharding.
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

# Canonical mesh-axis flattening (parallel/mesh.py) — shared with
# ops/quant4.py so tuple-spec overlap semantics can never drift again.
from substratus_tpu.parallel.mesh import axis_names

Dims = Tuple[Optional[int], Optional[int]]  # (batch dim idx, head dim idx)


def bh_partitioned(
    impl,
    arg_dims: Sequence[Dims],
    out_dims: Sequence[Dims],
    sharding_rule: str,
):
    """custom_partitioning wrapper for a kernel that is local per
    (batch, head) shard.

    impl: positional-args function (statics already closed over).
    arg_dims/out_dims: for each operand/result, which dimension index
        carries batch and which carries heads (None = not present).
    sharding_rule: Shardy propagation rule (einsum-like factor string).

    The first operand's sharding (q) names the mesh axes.
    """
    from jax.experimental.custom_partitioning import custom_partitioning

    f = custom_partitioning(impl)
    single = len(out_dims) == 1

    def _axis_size(mesh, axis) -> int:
        size = 1
        for n in axis_names(axis):
            size *= int(mesh.shape[n])
        return size

    def axes(mesh, arg_shapes, result_shape):
        spec = tuple(
            getattr(arg_shapes[0].sharding, "spec", ()) or ()
        )

        def at(i):
            return spec[i] if i is not None and i < len(spec) else None

        bdim, hdim = arg_dims[0]
        b, h = at(bdim), at(hdim)

        # One mesh axis cannot appear twice in a sharding. The overlap
        # check must flatten tuple specs: b="data" vs h=("data", "tensor")
        # collides on "data" just as surely as b == h exactly.
        if (
            b is not None
            and h is not None
            and set(axis_names(b)) & set(axis_names(h))
        ):
            b = None

        # An axis is only usable if it divides EVERY dimension it would
        # shard, across all operands and results — q's heads and the
        # kv heads share one mesh axis, and a GQA model with tensor
        # wider than its kv-head count must fall back to replicated
        # heads, not silently compute garbage on misaligned shards.
        shapes = list(arg_shapes) + (
            list(result_shape) if not single else [result_shape]
        )
        dims = list(arg_dims) + list(out_dims)
        for which, axis in (("b", b), ("h", h)):
            if axis is None:
                continue
            size = _axis_size(mesh, axis)
            for s, (bdim_i, hdim_i) in zip(shapes, dims):
                d = bdim_i if which == "b" else hdim_i
                if d is not None and s.shape[d] % size:
                    if which == "b":
                        b = None
                    else:
                        h = None
                    break
        return b, h

    def spec_of(dims: Dims, rank: int, b, h):
        from jax.sharding import PartitionSpec as P

        parts = [None] * rank
        bdim, hdim = dims
        if bdim is not None and b is not None:
            parts[bdim] = b
        if hdim is not None and h is not None:
            parts[hdim] = h
        return P(*parts)

    def result_shardings(mesh, result_shape, b, h):
        from jax.sharding import NamedSharding

        shapes = result_shape if not single else [result_shape]
        out = tuple(
            NamedSharding(mesh, spec_of(d, len(s.shape), b, h))
            for d, s in zip(out_dims, shapes)
        )
        return out[0] if single else out

    def infer(mesh, arg_shapes, result_shape):
        b, h = axes(mesh, arg_shapes, result_shape)
        return result_shardings(mesh, result_shape, b, h)

    def partition(mesh, arg_shapes, result_shape):
        from jax.sharding import NamedSharding

        b, h = axes(mesh, arg_shapes, result_shape)
        arg_shardings = tuple(
            NamedSharding(mesh, spec_of(d, len(s.shape), b, h))
            for d, s in zip(arg_dims, arg_shapes)
        )
        return (
            mesh, impl, result_shardings(mesh, result_shape, b, h),
            arg_shardings,
        )

    f.def_partition(
        partition,
        infer_sharding_from_operands=infer,
        sharding_rule=sharding_rule,
    )
    return f
