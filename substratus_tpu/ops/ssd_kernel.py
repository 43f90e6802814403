"""The decode step of a Mamba-2 layer over the stacked state, as one Pallas
kernel (Mosaic): a slot's `S [N, H x P]` crosses HBM once in each direction.

ops/ssd.py::step is the function: for one token a row it needs the state as
it was twice, in the read-out `S C` and in the update `S' = a S + B u^T`.
Written in XLA those are two passes over the layer's slab (a reduction and
a loop fusion), as power retention's were (ops/retention_kernel.py). Here a
grid step takes `SLOTS` slots' states into VMEM through the pipeline's own
blocks, and from that one copy, a 128-lane column block at a time,

  * the read-out of the state **as it was** is summed down the sublanes,
    `carried[r] = sum_n C[n] S[n, r]`, and
  * `where(fresh, 0, a[r] S[n, r]) + B[n] u[r]` goes to the output block,

all on the VPU in float32: in ops/ssd.py's layout the state's N rows lie
down the sublanes and (head, value) along the lanes, so `a` and `u` are lane
vectors, `B` and `C` columns (turned once a slot, a [128, 128] transpose),
and the sum over N adds whole registers. The stack is aliased in to out, a
block's index map names (layer, slot), and the other layers are not
touched: the stack stays the layer scan's donated carry. Every slot's state
moves, live or idle: an idle row has `u = 0` and `a = 1` and comes back bit
for bit.

What is left to XLA (`step`): `dt`'s decay, `dt x`, and the read-out's
other two terms (ops/ssd.py::read_out).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from substratus_tpu.ops import ssd
from substratus_tpu.ops.paged_attention import LANES

# Slots of one grid step: their blocks stand twice in VMEM in each direction
# (2 MiB a slot at Granite-4.0-H's widths: 16 MiB at 2).
SLOTS = 2


def slots_a_step(slots: int) -> int:
    return max(i for i in range(1, SLOTS + 1) if slots % i == 0)


def _kernel(layer_ref, fresh_ref, a_ref, u_ref, b_ref, c_ref, s_in, s_out,
            o_ref):
    """One grid step: `group` slots. a, u [group, 1, R]: the decay and `dt
    x` along the lanes; b, c [group, 1, N]; s_in / s_out [group, N, R] the
    slots' states of the layer; o [group, 1, R]."""
    group, n, r = s_in.shape
    first = pl.program_id(0) * group
    for j in range(group):
        fresh = fresh_ref[first + j] != 0
        # [n, lane] = B[n], C[n]: a row laid down the sublanes
        b_col = jnp.broadcast_to(b_ref[j], (LANES, n)).T
        c_col = jnp.broadcast_to(c_ref[j], (LANES, n)).T
        for k in range(r // LANES):
            at = (j, slice(None), pl.ds(k * LANES, LANES))
            s = s_in[at]
            o_ref[at] = jnp.sum(s * c_col, axis=0, keepdims=True)
            # nothing of the slot's last occupant is kept, whatever it left
            # (a product with 0 would keep an infinity)
            s_out[at] = (jnp.where(fresh, 0.0, a_ref[at] * s)
                         + b_col * u_ref[at])


@functools.partial(jax.jit, static_argnames=("interpret",))
def state_step(
    state: jnp.ndarray,  # [Lm, B, N, R] float32; aliased to the result
    layer: jnp.ndarray,  # scalar int32: the layer of the stack
    a: jnp.ndarray,  # [B, R] float32: the decay, 1 for a row that is idle
    u: jnp.ndarray,  # [B, R] float32: dt x, 0 for such a row
    b: jnp.ndarray,  # [B, N] float32
    c: jnp.ndarray,  # [B, N] float32
    fresh: jnp.ndarray,  # [B] bool: the row starts from a zero state
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(the stack with `layer` updated in place: `where(fresh, 0, a S) + B
    u^T`; `S C` [B, R] of the state as it was)."""
    _, slots, n, r = state.shape
    if state.dtype != jnp.float32 or n % LANES or r % LANES:
        raise ValueError(f"no kernel for a state {state.shape} "
                         f"{state.dtype}: ops/kvcache.py takes ssd.step")
    group = slots_a_step(slots)
    per = lambda width: pl.BlockSpec(  # noqa: E731
        (group, 1, width), lambda i, *_: (i, 0, 0))
    slab = pl.BlockSpec((None, group, n, r),
                        lambda i, layer, *_: (layer[0], i, 0, 0))
    state, carried = pl.pallas_call(
        _kernel,
        out_shape=(jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((slots, 1, r), jnp.float32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(slots // group,),
            in_specs=[per(r), per(r), per(n), per(n), slab],
            out_specs=(slab, per(r)),
        ),
        input_output_aliases={6: 0},  # counted with the prefetched scalars
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=4 * group * n * r * 4 + (16 << 20),
        ),
        interpret=interpret,
        name="ssm_state_step",
    )(
        layer.astype(jnp.int32).reshape(1), fresh.astype(jnp.int32),
        a[:, None], u[:, None], b[:, None], c[:, None], state,
    )
    return state, carried[:, 0]


def step(
    state: jnp.ndarray,  # [Lm, B, N, H x P] float32
    layer: jnp.ndarray,  # scalar int32
    x: jnp.ndarray,  # [B, H, P]
    b: jnp.ndarray,  # [B, N]
    c: jnp.ndarray,  # [B, N]
    dt: jnp.ndarray,  # [B, H] float32; zero for a row that is not real
    a_log: jnp.ndarray,  # [H] float32
    d_skip: jnp.ndarray,  # [H] float32
    fresh: jnp.ndarray,  # [B] bool
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """ops/ssd.py::step for `layer` of the stacked state, every slot a row:
    (state, o [B, H, P] float32), the stack updated in place."""
    a, u = ssd.decay_and_input(x, dt, a_log)
    state, carried = state_step(
        state, layer, a, u, b.astype(jnp.float32), c.astype(jnp.float32),
        fresh, interpret=interpret)
    carried = jnp.where(fresh[:, None], 0.0, a * carried)
    return state, ssd.read_out(carried, x, b, c, dt, d_skip)
