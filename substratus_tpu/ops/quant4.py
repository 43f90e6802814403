"""Weight-only int4 quantization with a Pallas unpack-dequant matmul.

The reference's flagship 70B example serves 4-bit on a single GPU
(reference: examples/llama2-70b/server.yaml:10, `MODEL_LOAD_IN_4BIT` via
bitsandbytes; examples/llama2-13b-chat-gguf serves 4-bit GGUF through
llama.cpp). Here 4-bit is a first-class TPU op: decode is HBM-bandwidth
bound, and int4 halves the dominant weight stream relative to int8.
Whether that wins on the chip is not measured (ROADMAP.md S3).

Storage
-------
Two int4 values nibble-pack into one uint8 along the LAST contracting dim
of the weight (packing is explicit rather than a native jnp.int4 array,
so the kernel controls the layout). Packing is *block-folded*:
within each block of `block` consecutive rows, byte r holds original rows
(r, r + block/2) as (low, high) nibbles. Unpacking a block is then a
concatenate of the two sign-extended nibble planes — no sublane
interleave, which Mosaic would otherwise relayout on every tile.

Scales are symmetric (absmax/7, clipped to [-8, 7]) per group of `block`
rows of the packed dim x every remaining channel — the GPTQ/AWQ-style
group size (128) that keeps 4-bit quality at 7B-70B scale.

Compute
-------
* `q4einsum` — einsum with the packed weight. On a TPU backend (sharded
  or not, via the custom_partitioning rule) it tiles a Pallas kernel:
  packed bytes stream HBM->VMEM, nibble unpack + group-scale dequant
  happen in VMEM right next to the MXU dot, and only the f32 accumulator
  leaves. On other backends it lowers to two fused XLA einsums over the
  nibble planes — elementwise producers + dots the SPMD partitioner
  shards like any dense matmul. The kernel never picks interpret mode:
  a CPU test that forces the kernel path asks for it itself
  (tests/conftest.py::pallas_interpret).
* Equations whose contracted dims are not (trailing in x, leading in w,
  same order) dequantize and fall back (MoE expert einsums).

Sharding: Q4Tensor's children (packed, scale) flatten in lockstep with the
dense tree (parallel/sharding.py::sharding_tree) and lax.scan slices the
leading layer dim off both, exactly like the int8 QTensor.
"""
from __future__ import annotations

import functools
import os
from dataclasses import dataclass
from typing import Any, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Canonical mesh-axis helpers (parallel/mesh.py): the axis-name
# flattening every overlap check shares — this module used to carry its
# own copy, and the PR 3 tuple-spec overlap bug came from that drift.
from substratus_tpu.parallel.mesh import axis_names as _axis_names

BLOCK = 128  # pack-fold / scale-group size along the packed dim


def _pack_block_for(dim: int) -> int:
    """Largest power of two <= BLOCK dividing `dim` (tiny test configs have
    sub-128 dims; every real config dim is a multiple of 128)."""
    b = BLOCK
    while b > 2 and dim % b:
        b //= 2
    if dim % b:
        raise ValueError(f"int4 pack dim {dim} must be even")
    return b


@jax.tree_util.register_pytree_node_class
@dataclass
class Q4Tensor:
    """Nibble-packed int4 weight + per-group float32 scale.

    packed: uint8, original weight rank, pack axis at half size.
    scale:  f32, original rank, pack axis at size dim/block.
    pack_axis: NEGATIVE axis index (stable when lax.scan slices a leading
        layer dim off both children).
    block: fold/group size along the pack axis (counted before packing).
    """

    packed: jnp.ndarray
    scale: jnp.ndarray
    pack_axis: int
    block: int

    def tree_flatten(self):
        return (self.packed, self.scale), (self.pack_axis, self.block)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], children[1], *aux)

    @property
    def shape(self) -> Tuple[int, ...]:
        """Logical (unpacked) shape."""
        s = list(self.packed.shape)
        s[self.pack_axis] *= 2
        return tuple(s)

    @property
    def dtype(self):
        return jnp.uint8

    def dequant(self, dtype=jnp.bfloat16) -> jnp.ndarray:
        """Unpack + dequantize to a dense array (XLA ops only)."""
        ax = self.pack_axis % self.packed.ndim
        dim2 = self.packed.shape[ax]
        half = self.block // 2
        pre = self.packed.shape[:ax]
        post = self.packed.shape[ax + 1:]
        lo, hi = _nibbles(self.packed)
        lo = lo.reshape(*pre, dim2 // half, half, *post)
        hi = hi.reshape(*pre, dim2 // half, half, *post)
        w = jnp.concatenate([lo, hi], axis=ax + 1)  # [.., G, block, ..]
        w = w.astype(jnp.float32) * jnp.expand_dims(self.scale, ax + 1)
        return w.reshape(*pre, dim2 * 2, *post).astype(dtype)


def _nibbles(packed: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Sign-extended int8 planes (low, high) from packed uint8."""
    i8 = lax.bitcast_convert_type(packed, jnp.int8)
    four = jnp.int8(4)
    lo = lax.shift_right_arithmetic(lax.shift_left(i8, four), four)
    hi = lax.shift_right_arithmetic(i8, four)
    return lo, hi


def quantize4(w: jnp.ndarray, contracting: Sequence[int]) -> Q4Tensor:
    """Symmetric int4 group quantization: groups of `block` along the last
    contracting dim, per-channel over every other dim (including other
    contracting dims — the scale dequantizes the weight before the dot, so
    contracted dims need not be scale-constant as int8 scale-after-dot
    requires)."""
    contracting = tuple(sorted(c % w.ndim for c in contracting))
    ax = contracting[-1]
    dim = w.shape[ax]
    block = _pack_block_for(dim)
    g = dim // block
    half = block // 2
    pre, post = w.shape[:ax], w.shape[ax + 1:]
    wf = w.astype(jnp.float32).reshape(*pre, g, block, *post)
    absmax = jnp.max(jnp.abs(wf), axis=ax + 1, keepdims=True)
    scale = jnp.where(absmax == 0, 1.0, absmax / 7.0)  # [.., G, 1, ..]
    q = jnp.clip(jnp.round(wf / scale), -8, 7).astype(jnp.int8)
    # Block-fold: byte r of each block <- rows (r, r + block/2).
    lo = lax.slice_in_dim(q, 0, half, axis=ax + 1)
    hi = lax.slice_in_dim(q, half, block, axis=ax + 1)
    byte = jnp.bitwise_or(
        jnp.bitwise_and(lo, 0x0F).astype(jnp.uint8),
        jnp.left_shift(jnp.bitwise_and(hi, 0x0F).astype(jnp.uint8), 4),
    )
    return Q4Tensor(
        packed=byte.reshape(*pre, dim // 2, *post),
        scale=jnp.squeeze(scale, axis=ax + 1),
        pack_axis=ax - w.ndim,
        block=block,
    )


# ---------------------------------------------------------------------------
# Pallas kernel: x [M, C] @ packed [C/2, N] (scale [C/block, N]) -> [M, N]
# ---------------------------------------------------------------------------


def _matmul_kernel(x_ref, p_ref, s_ref, o_ref, acc_ref, *,
                   block: int, nk: int, groups: int):
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    p = p_ref[...]  # [bk//2, bn] uint8
    bk2, bn = p.shape
    half = block // 2
    m = bk2 // half  # fold blocks in this k tile
    # Sign-extended nibble planes; int32 lanes (i8 shifts are not a Mosaic
    # fast path) — these live entirely in VMEM/registers.
    i32 = p.astype(jnp.int32)
    lo = lax.shift_right_arithmetic(lax.shift_left(i32, 28), 28)
    hi = lax.shift_right_arithmetic(lax.shift_left(i32, 24), 28)
    w = jnp.concatenate(
        [lo.reshape(m, half, bn), hi.reshape(m, half, bn)], axis=1
    )  # [m, block, bn] — natural row order thanks to the block-fold pack
    s = s_ref[...]  # [m, bn] f32
    x = x_ref[...]
    if groups % m:
        # Ragged last k tile (C is not a multiple of bk): what the block
        # reads past C is unspecified, so zero the scale rows and the x
        # columns beyond it. The nibbles themselves are always finite.
        first = ik * m
        live = first + lax.broadcasted_iota(jnp.int32, (m, 1), 0) < groups
        s = jnp.where(live, s, 0.0)
        cols = first * block + lax.broadcasted_iota(
            jnp.int32, (1, 2 * bk2), 1
        )
        x = jnp.where(cols < groups * block, x, jnp.zeros_like(x))
    wf = (w.astype(jnp.float32) * s[:, None, :]).reshape(2 * bk2, bn)
    acc_ref[...] += lax.dot_general(
        x, wf.astype(x.dtype), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(ik == nk - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _pick(total: int, prefs: Sequence[int]) -> int:
    for p in prefs:
        if total % p == 0:
            return p
    return total


def _pick_bk(C: int, block: int) -> int:
    """k tile: the scale block is (bk // block, bn), and Mosaic takes a
    second-minor block dim only when it is a multiple of 8 or the whole
    axis. So 16 or 8 groups where that divides C, the whole of a C under
    8 groups, and otherwise 8 groups with a ragged, masked last tile
    (Llama-2-7B's down projection: 11008 = 86 groups)."""
    for m in (16, 8):
        if C % (block * m) == 0:
            return block * m
    return C if C < 8 * block else 8 * block


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def _matmul(x2: jnp.ndarray, packed: jnp.ndarray, scale: jnp.ndarray,
            block: int, interpret: bool = False):
    """x2 [M, C] @ int4-packed [C/2, N] -> [M, N] in x2.dtype."""
    M, C = x2.shape
    N = packed.shape[1]
    bm = _pick(M, (256, 128, 64, 32, 24, 16, 8))
    bn = _pick(N, (512, 256, 128))
    bk = _pick_bk(C, block)
    nk = pl.cdiv(C, bk)
    kernel = functools.partial(
        _matmul_kernel, block=block, nk=nk, groups=C // block
    )
    return pl.pallas_call(
        kernel,
        grid=(M // bm, N // bn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk // 2, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((bk // block, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), x2.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x2, packed, scale)


def _q4_xla_2d(x2: jnp.ndarray, p2: jnp.ndarray, s2: jnp.ndarray,
               block: int) -> jnp.ndarray:
    """XLA lowering of x2 [M, C] @ packed [C/2, N]: one fused einsum per
    nibble plane (the block-fold pack maps plane rows to strided x
    slices). Elementwise producers + dots only — CPU-correct and
    SPMD-shardable. Shared by the generic fallback AND by shards whose
    local shapes don't fit the kernel's tiling."""
    M, C = x2.shape
    N = p2.shape[1]
    half = block // 2
    g = C // block
    lo, hi = _nibbles(p2)  # [C/2, N] int8
    xg = x2.reshape(M, g, block)
    sa = s2.reshape(g, 1, N)
    dtype = x2.dtype
    lo3 = (lo.reshape(g, half, N).astype(jnp.float32) * sa).astype(dtype)
    hi3 = (hi.reshape(g, half, N).astype(jnp.float32) * sa).astype(dtype)
    y = jnp.einsum(
        "mgh,ghn->mn", xg[:, :, :half], lo3,
        preferred_element_type=jnp.float32,
    ) + jnp.einsum(
        "mgh,ghn->mn", xg[:, :, half:], hi3,
        preferred_element_type=jnp.float32,
    )
    return y


# Count of Pallas-kernel TRACES (compile-time): tests assert the sharded
# path actually lowered the kernel instead of silently falling back.
_KERNEL_TRACES = 0


def kernel_trace_count() -> int:
    return _KERNEL_TRACES


def _local_q4_matmul(x2, p2, s2, block: int) -> jnp.ndarray:
    """Per-shard (or unsharded) lowering: the Pallas kernel when the
    local shapes fit its tiling, else the XLA nibble-plane formula.
    Output dtype = x2.dtype either way."""
    global _KERNEL_TRACES
    M, C = x2.shape
    N = p2.shape[1]
    if M >= 8 and N % 128 == 0 and C % (2 * block) == 0:
        _KERNEL_TRACES += 1
        return _matmul(x2, p2, s2, block)
    return _q4_xla_2d(x2, p2, s2, block).astype(x2.dtype)


def _spec_tuple(shape_struct, rank: int):
    s = getattr(shape_struct, "sharding", None)
    if s is None or not hasattr(s, "spec"):
        return (None,) * rank
    spec = tuple(s.spec) + (None,) * (rank - len(s.spec))
    return spec[:rank]


def _q4_axes(mesh, arg_shapes, block: int):
    """(m_axis, c_axis, n_axis) mesh axes of a sharded q4 matmul. The
    PACKED weight's committed sharding is authoritative: its axis 0
    names the contracting (row-parallel wo/down) axis, its axis 1 the
    output-feature (column-parallel wq/wk/wv/gate/up/lm_head) axis; the
    activation keeps whatever batch-dim sharding GSPMD propagated.

    Row-parallel is only kept when every shard's contracting slice
    covers whole scale groups (local C a multiple of `block`, scale rows
    divisible) — otherwise the weight replicates (degenerate tiny-config
    case; every real config has C/block >> tensor)."""
    xs, ps, ss = arg_shapes
    c_axis, n_axis = _spec_tuple(ps, 2)
    m_axis = _spec_tuple(xs, 2)[0]
    # Overlap is per MESH AXIS NAME, not whole-spec-value equality: a
    # tuple spec like ("data", "fsdp") on the contracting dim still
    # claims "data", so a batch dim sharded plain "data" must drop out
    # (one mesh axis cannot appear twice in a sharding).
    used = set()
    for ax in (c_axis, n_axis):
        if ax is not None:
            used.update(_axis_names(ax))
    if m_axis is not None and set(_axis_names(m_axis)) & used:
        m_axis = None
    if c_axis is not None:
        tp = int(np_prod(mesh.shape[a] for a in _axis_names(c_axis)))
        C = xs.shape[1]
        groups = ss.shape[0]
        if groups % tp or (C // tp) % block:
            c_axis = None
    return m_axis, c_axis, n_axis


def np_prod(it) -> int:
    p = 1
    for v in it:
        p *= int(v)
    return p


def _make_q4_mm_infer(block: int):
    def infer(mesh, arg_shapes, result_shape):
        from jax.sharding import NamedSharding, PartitionSpec as P

        m_axis, _, n_axis = _q4_axes(mesh, arg_shapes, block)
        return NamedSharding(mesh, P(m_axis, n_axis))

    return infer


def _make_q4_mm_sp(block: int):
    """custom_partitioning wrapper giving the Pallas kernel the SPMD
    partitioning rule pallas_call lacks: GSPMD/Shardy keeps the kernel
    per-shard (column-parallel runs it locally; row-parallel adds the
    psum), so sharded serving no longer pins the XLA fallback
    (round-4 gap: serve/main.py used to force xla under any mesh).
    One wrapper per group size — custom_partitioning's partition
    callback has no static-arg channel, so `block` rides the closure."""
    from jax.experimental.custom_partitioning import custom_partitioning

    @custom_partitioning
    def q4_mm(x2, p2, s2):
        return _local_q4_matmul(x2, p2, s2, block)

    def partition(mesh, arg_shapes, result_shape):
        from jax.sharding import NamedSharding, PartitionSpec as P

        m_axis, c_axis, n_axis = _q4_axes(mesh, arg_shapes, block)

        def lower(x2, p2, s2):
            y = _local_q4_matmul(x2, p2, s2, block)
            if c_axis is not None:
                # Row-parallel: every shard holds a partial sum over
                # its contracting slice.
                y = lax.psum(y, c_axis)
            return y

        result_sharding = NamedSharding(mesh, P(m_axis, n_axis))
        arg_shardings = (
            NamedSharding(mesh, P(m_axis, c_axis)),
            NamedSharding(mesh, P(c_axis, n_axis)),
            NamedSharding(mesh, P(c_axis, n_axis)),
        )
        return mesh, lower, result_sharding, arg_shardings

    q4_mm.def_partition(
        partition,
        infer_sharding_from_operands=_make_q4_mm_infer(block),
        # Factor naming for Shardy propagation: n is shared by the packed
        # weight, the scale, and the output (column-parallel flows
        # through); the contracting-family dims (k, j, g — different
        # sizes) stay independent factors, and the partition callback
        # forces their consistency from the packed weight's spec.
        sharding_rule="m k, j n, g n -> m n",
    )
    return q4_mm


_Q4_MM_SP: dict = {}


def _q4_mm_sp(x2, p2, s2, block: int):
    if block not in _Q4_MM_SP:
        _Q4_MM_SP[block] = _make_q4_mm_sp(block)
    return _Q4_MM_SP[block](x2, p2, s2)


_FORCE_IMPL: Optional[str] = os.environ.get("SUBSTRATUS_Q4_IMPL") or None


def set_q4_impl(impl: Optional[str]) -> Optional[str]:
    """Force the q4einsum lowering: "pallas", "xla", or None for auto
    (pallas on a TPU backend — sharded or not, via the
    custom_partitioning rule — xla elsewhere). Returns the previous
    value so callers can save/restore without touching internals."""
    global _FORCE_IMPL
    assert impl in (None, "pallas", "xla"), impl
    prev = _FORCE_IMPL
    _FORCE_IMPL = impl
    return prev


def _use_pallas() -> bool:
    if _FORCE_IMPL is not None:
        return _FORCE_IMPL == "pallas"
    return jax.default_backend() == "tpu"


def q4einsum(eq: str, x: jnp.ndarray, w: Q4Tensor,
             dtype=jnp.bfloat16) -> jnp.ndarray:
    """einsum(eq, x, w) for a nibble-packed int4 weight.

    The fused path requires the contracted letters trailing in x and
    leading in w in the same order, the pack axis as the LAST contracted
    dim, and kept letters order-preserved into the output (x's kept dims
    before w's). That covers every dense-layer projection (wq/wk/wv, wo,
    gate/up/down, lm_head); anything else — the MoE expert einsums —
    dequantizes and falls back.
    """
    ins, out = eq.split("->")
    xsub, wsub = ins.split(",")
    contracted = "".join(c for c in xsub if c not in out)
    nc = len(contracted)
    ok = (
        nc >= 1
        and xsub[-nc:] == contracted
        and wsub[:nc] == contracted
        and w.pack_axis % w.packed.ndim == nc - 1
        and [l for l in out if l in xsub] + [l for l in out if l in wsub]
        == list(out)
        and [l for l in xsub if l in out] == [l for l in out if l in xsub]
        and [l for l in wsub if l in out] == [l for l in out if l in wsub]
    )
    if not ok:
        return jnp.einsum(eq, x, w.dequant(dtype))

    batch_shape = x.shape[:-nc]
    M = 1
    for d in batch_shape:
        M *= d
    C = 1
    for d in x.shape[-nc:]:
        C *= d
    x2 = x.reshape(M, C).astype(dtype)
    p2 = w.packed.reshape(C // 2, -1)
    N = p2.shape[1]
    s2 = w.scale.reshape(-1, N)
    out_shape = batch_shape + w.packed.shape[nc:]

    if _use_pallas():
        # Kernel path, sharded or not: the custom_partitioning rule keeps
        # the Pallas kernel per-shard under GSPMD (shards whose local
        # shapes miss the tiling fall back to the XLA formula inside
        # _local_q4_matmul — loudly countable via kernel_trace_count).
        y = _q4_mm_sp(x2, p2, s2, w.block)
    else:
        y = _q4_xla_2d(x2, p2, s2, w.block)
    return y.reshape(out_shape).astype(dtype)


def quantize4_params(params: Any, contracting_of: Any) -> Any:
    """quantize4 every leaf with a non-empty entry in `contracting_of`
    (same contract as quant.quantize_params; () = keep dense)."""

    def one(w, contracting):
        if not contracting:
            return w
        return quantize4(w, contracting)

    return jax.tree.map(one, params, contracting_of)
