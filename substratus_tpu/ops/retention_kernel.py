"""The decode step of a power-retention layer over the stacked state, as
one Pallas kernel (Mosaic): each head's `S [F, dv]` crosses HBM once in
each direction.

ops/retention.py::step is the function: for one token a row it needs the
state as it was twice, in the read-out `phi(s q)^T S` of the head's query
heads and in the update `S' = g S + phi(k) v^T`. Written in XLA the two are
two passes over the layer's slab (a convolution and a loop fusion: 16.4 GB a
step where the equations need 10.9, PERF.md section 6, PR 37). Here one
(slot, KV head)'s whole `S [F, dv]` comes into VMEM by one DMA, and from
that one copy, walked in chunks of rows,

  * the read-out of every query head of the group is accumulated from the
    state **as it was**, `num[g, :] += phi(s q_g)[rows] . S[rows, :]`, on the
    MXU at float32 precision (`highest`: the passes XLA's own read-out
    makes), and
  * `where(fresh, 0, g * S[rows]) + phi(k)[rows] v^T` is written over the
    same rows of the copy, in float32 on the VPU,

and one DMA takes the copy back to the place it came from: the stack is
aliased in to out and never leaves HBM as an operand, so it stays the
layer scan's donated carry, no slab is copied, and the other layers are
not touched. Every slot's state moves, live or idle: an idle row has
`k = 0` and `g = 1` and comes back bit for bit.

`phi` is made where it is used: in the module's layout a block of d
entries is the vector times a rotation of itself, so the d rows of a chunk
that belong to rotation `delta` need `x * roll(x, delta)` and nothing from
HBM (made in XLA, `phi` of the queries and the key is 34 MB a layer that
the kernel read back). The normaliser `z [F]`, 1 / dv of the bytes, lies
along lanes as `phi` does and rides along: `den[g] += phi(s q_g) . z`, `z'
= where(fresh, 0, g z) + phi(k)`, the layer's 4 MB of it through the
pipeline's own blocks. What is left to XLA (`step`) is the gate, `(s q .
k)^2`, the division, and z's slice out of its stack and back.

**Reads and writes take turns.** HBM gives a stream that mixes the two
directions less than either alone (on a v5e a slab copied in place through
the double-buffered pipeline ran at 617 GB/s, read alone at 662, by the
host's clock, and XLA's own in-place pass at 615: PERF.md section 6, PR 37).
So a grid step takes a group of heads: it starts all their reads, computes
the first half as they land, and only when the last has landed starts
writing, the second half computed while the first half's writes are on
their way; the next step's reads start when the last write is done. At 8
heads a step (35 MB of VMEM at heads of 128) the kernel took 7 % less than
the pipelined one, which ran at the pace of a kernel that only copies.

F = d (d + 1) / 2 is no multiple of 128 (8,256 = 64.5 x 128 for heads of
128), so a head is taken whole, and the d / 2 rows of the last, half
rotation are one shorter chunk.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from substratus_tpu.ops import retention
from substratus_tpu.ops.paged_attention import LANES

# Rotations of one pass of the kernel's loop: 8 x d rows of S, 1,024 at
# heads of 128. Measured on the chip (PERF.md section 6, PR 37): passes of
# 128 rows 3.3 ms a layer, 512 1.86, 1,024 and beyond 1.79.
DELTAS = 8
# Heads of one grid step, whose reads and writes take turns, and the VMEM
# their copies may take (a v5e has 128 MiB): 4 heads 1.73 ms a layer, 8
# 1.72, 16 1.68 where the pipelined kernel took 1.84 (the same call).
GROUP_HEADS = 8
GROUP_BYTES = 40 << 20
_SUBLANES = 8
_ROOT2 = 2.0 ** 0.5


def slots_a_step(slots: int, kv_heads: int, f: int, dv: int) -> int:
    """Slots whose heads one grid step takes: whole slots (`z` comes a
    slot's [KH, F] at a time, the tile HBM keeps it in), at most GROUP_HEADS
    heads in GROUP_BYTES of copies; 0 where one slot's heads alone are too
    many."""
    fits = min(GROUP_HEADS, GROUP_BYTES // (f * dv * 4)) // kv_heads
    return max((i for i in range(1, fits + 1) if slots % i == 0), default=0)


def _kernel(layer_ref, fresh_ref, x_ref, v_ref, g_ref, z_in, s_in, s_out,
            z_out, num_ref, den_ref, buf, in_sem, out_sem, *,
            kv_heads: int, n_q: int):
    """One grid step: `group` (slot, KV head) pairs, flat index step *
    group + j. x [group, R, d] holds the head's queries in its first n_q
    rows and its key in the last, zeros between; v [group, 1, dv]; g
    [group, 1, dv], the gate in every lane; z_in / z_out [slots, KH, F]
    the step's slots of the layer's z; s_in / s_out the stack in HBM; buf
    [group, F, dv] the heads' copies."""
    group, rows, d = x_ref.shape
    dv = buf.shape[-1]
    half = d // 2
    first = pl.program_id(0) * group
    layer = layer_ref[0]

    def head(ref, j):
        pair = first + j
        return ref.at[layer, pair // kv_heads, pair % kv_heads]

    def read(j):
        return pltpu.make_async_copy(head(s_in, j), buf.at[j], in_sem.at[j])

    def write(j):
        return pltpu.make_async_copy(buf.at[j], head(s_out, j), out_sem.at[j])

    def compute(j):
        pair = first + j
        fresh = fresh_ref[pair // kv_heads] != 0
        x, g, v = x_ref[j], g_ref[j], v_ref[j]
        is_q = lax.broadcasted_iota(jnp.int32, (rows, 1), 0) < n_q
        slot = j // kv_heads
        # a slot's z is one tile of KH rows: the head's row is picked by a
        # mask (Mosaic loads no single row at an index it does not know)
        mine = (lax.broadcasted_iota(jnp.int32, (kv_heads, 1), 0)
                == j % kv_heads)

        def phi(delta, width: int):
            """[R, width]: phi's entries of rotation `delta`, of s q in the
            queries' rows (phi(s q) = phi(q) / d) and of k in the last."""
            turned = pltpu.roll(x, lax.rem(d - delta, d), 1)  # x[a + delta]
            c = x * turned * jnp.where(delta == 0, 1.0, _ROOT2)
            return jnp.where(is_q, c / d, c)[:, :width]

        def normaliser(start, c, den):
            at = (slot, slice(None), pl.ds(start, c.shape[1]))
            z = jnp.sum(jnp.where(mine, z_in[at], 0.0), axis=0,
                        keepdims=True)  # [1, width]
            new = jnp.where(fresh, 0.0, g[:, :1] * z) + c[rows - 1:]
            z_out[at] = jnp.where(mine, new, z_out[at])
            return den + jnp.sum(c * z, axis=1, keepdims=True)

        def state(start, c, acc):
            at = (j, pl.ds(start, c.shape[1]), slice(None))
            s = buf[at]
            pk = c.T[:, rows - 1:]  # [size, 1]: phi(k) down the sublanes
            # nothing of the slot's last occupant is kept, whatever it left
            # (a product with 0 would keep an infinity)
            buf[at] = jnp.where(fresh, 0.0, g * s) + pk * v
            return acc + jnp.dot(c, s, precision=lax.Precision.HIGHEST,
                                 preferred_element_type=jnp.float32)

        def chunk(i, carry):
            acc, den = carry
            start = pl.multiple_of(i * (DELTAS * d), DELTAS * d)
            blocks = []
            for t in range(DELTAS):
                blocks.append(phi(i * DELTAS + t, d))
                den = normaliser(start + t * d, blocks[-1], den)
            return state(start, jnp.concatenate(blocks, axis=1), acc), den

        acc, den = lax.fori_loop(
            0, half // DELTAS, chunk,
            (jnp.zeros((rows, dv), jnp.float32),
             jnp.zeros((rows, 1), jnp.float32)))
        last = phi(half, half)  # the half rotation: d / 2 entries
        num_ref[j] = state(half * d, last, acc)
        den_ref[j] = jnp.broadcast_to(
            normaliser(half * d, last, den), den_ref.shape[1:])

    def each(lo, hi, do):
        lax.fori_loop(lo, hi, lambda j, _: do(j), None)

    # The heads are walked by loops, not unrolled: every process traces and
    # lowers the kernel before its compile cache answers, and eight copies
    # of `compute` cost the cell 6 s of set-up (PERF.md section 6, PR 37).
    early = group // 2
    each(0, group, lambda j: read(j).start())

    def one(j):
        @pl.when(j < early)
        def _():
            read(j).wait()

        @pl.when(j == early)
        def _():
            each(early, group, lambda i: read(i).wait())
            each(0, early, lambda i: write(i).start())

        compute(j)

        @pl.when(j >= early)
        def _():
            write(j).start()

    each(0, group, one)
    each(0, group, lambda j: write(j).wait())


@functools.partial(jax.jit, static_argnames=("n_q", "interpret"))
def state_step(
    state_s: jnp.ndarray,  # [L, B, KH, F, dv] float32; aliased to the result
    z: jnp.ndarray,  # [B, KH, F] float32: the layer's normalisers
    layer: jnp.ndarray,  # scalar int32: the layer of the stack
    x: jnp.ndarray,  # [B, KH, R, d] float32: n_q queries, zeros, the key
    v: jnp.ndarray,  # [B, KH, dv] float32
    g: jnp.ndarray,  # [B, KH] float32: the gate, 1 for a row that is idle
    fresh: jnp.ndarray,  # [B] bool: the row starts from a zero state
    n_q: int,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """(the stack with `layer` updated in place: `where(fresh, 0, g S) +
    phi(k) v^T`; z' = `where(fresh, 0, g z) + phi(k)`; `phi(s q_g) . S`
    [B, KH, n_q, dv] and `phi(s q_g) . z` [B, KH, n_q] of the state as it
    was). z comes as the layer's own 4 MB and not as its stack, which XLA,
    free to place a kernel's operand, brought whole into VMEM before every
    layer's call and took back after (84 MB a layer)."""
    _, b, kh, f, dv = state_s.shape
    rows, d = x.shape[2:]
    n = b * kh
    slots = slots_a_step(b, kh, f, dv)
    if not slots or (d // 2) % DELTAS or f != retention.width(d):
        raise ValueError(f"no kernel for a state {state_s.shape} of heads "
                         f"of {d}: ops/kvcache.py takes retention.step")
    group = slots * kh
    per = lambda *shape: pl.BlockSpec(  # noqa: E731
        (group,) + shape, lambda i, *_: (i,) + (0,) * len(shape))
    z_slots = pl.BlockSpec((slots, kh, f), lambda i, *_: (i, 0, 0))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    state_s, z, num, den = pl.pallas_call(
        functools.partial(_kernel, kv_heads=kh, n_q=n_q),
        out_shape=(jax.ShapeDtypeStruct(state_s.shape, state_s.dtype),
                   jax.ShapeDtypeStruct(z.shape, z.dtype),
                   jax.ShapeDtypeStruct((n, rows, dv), jnp.float32),
                   jax.ShapeDtypeStruct((n, rows, LANES), jnp.float32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n // group,),
            in_specs=[per(rows, d), per(1, dv), per(1, dv), z_slots, hbm],
            out_specs=(hbm, z_slots, per(rows, dv), per(rows, LANES)),
            scratch_shapes=[
                pltpu.VMEM((group, f, dv), jnp.float32),
                pltpu.SemaphoreType.DMA((group,)),
                pltpu.SemaphoreType.DMA((group,)),
            ],
        ),
        input_output_aliases={6: 0},  # counted with the prefetched scalars
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=group * f * dv * 4 + (24 << 20),
        ),
        interpret=interpret,
        name="retention_state_step",
    )(
        layer.astype(jnp.int32).reshape(1), fresh.astype(jnp.int32),
        x.reshape(n, rows, d), v.reshape(n, 1, dv),
        jnp.broadcast_to(g.reshape(n, 1, 1), (n, 1, dv)), z, state_s,
    )
    return (state_s, z, num.reshape(b, kh, rows, dv)[:, :, :n_q],
            den.reshape(b, kh, rows, LANES)[:, :, :n_q, 0])


def step(
    state_s: jnp.ndarray,  # [L, B, KH, F, dv] float32
    state_z: jnp.ndarray,  # [L, B, KH, F] float32
    layer: jnp.ndarray,  # scalar int32
    q: jnp.ndarray,  # [B, H, d]
    k: jnp.ndarray,  # [B, KH, d]; zero for a row that is not real
    v: jnp.ndarray,  # [B, KH, dv]
    log_g: jnp.ndarray,  # [B, KH] float32; zero for a row that is not real
    fresh: jnp.ndarray,  # [B] bool
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """ops/retention.py::step for `layer` of the stacked state, every slot
    a row: (state_s, state_z, o [B, H, dv] float32), the stacks updated in
    place."""
    b, kh, d = k.shape
    group = q.shape[1] // kh
    rows = -(-(group + 1) // _SUBLANES) * _SUBLANES
    q32, k32, v32 = (q.reshape(b, kh, group, d).astype(jnp.float32),
                     k.astype(jnp.float32), v.astype(jnp.float32))
    g = jnp.exp(log_g)
    keep = jnp.where(fresh[:, None], 0.0, g)  # [B, KH]
    x = jnp.concatenate(
        [q32, jnp.zeros((b, kh, rows - group - 1, d), jnp.float32),
         k32[:, :, None]], axis=2)
    state_s, z, read, norm = state_step(
        state_s, lax.dynamic_index_in_dim(state_z, layer, 0, keepdims=False),
        layer, x, v32, g, fresh, n_q=group, interpret=interpret)
    state_z = lax.dynamic_update_index_in_dim(state_z, z, layer, 0)
    qk2 = jnp.square(jnp.einsum("bkgd,bkd->bkg", q32, k32)) / d
    num = keep[..., None, None] * read + qk2[..., None] * v32[:, :, None, :]
    den = keep[..., None] * norm + qk2
    o = num / (den[..., None] + retention.EPS)
    return state_s, state_z, o.reshape(b, -1, o.shape[-1])
