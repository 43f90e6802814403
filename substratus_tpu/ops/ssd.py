"""Mamba-2's selective state space ("Transformers are SSMs", arXiv:
2405.21060) with one group: a recurrence over a matrix state whose decay and
input are scaled by a step size a head and token, and whose key `B_t` and
query `C_t` (N wide) are shared by all H heads of P values.

For one sequence, head h, `a_t = exp(dt_t A)` (`A < 0`, `dt_t >= 0`, both
float32) and `u_t = dt_t x_t` ([P]), two forms of one function:

  * recurrent (`step`): the head keeps `S [P, N]`, zero before position 0:
    `S_t = a_t S_{t-1} + u_t B_t^T`, `o_t = S_t C_t + D x_t`;
  * chunked (`chunk`, the state-space dual): Q tokens from a carried `S`,
    `b_i = sum_{r <= i} dt_r A` inside the block (every exponent <= 0):
    `o_i = exp(b_i) S C_i + sum_{j <= i} (C_i . B_j) exp(b_i - b_j) u_j +
    D x_i`, `S' = exp(b_Q) S + sum_j exp(b_Q - b_j) u_j B_j^T`. A call's
    tokens are walked `BLOCK` at a time under one `lax.scan` whose carry is
    the state, so the decays of a block ([H, Q, Q] float32) are all that
    is ever square.

This is ops/retention.py's recurrence without `phi` and without the
normaliser, with a decay a head and one key and query for all heads. The
state's layout is this module's own: **[N, H x P]**, the state's N rows down
the sublanes and (head, value) along the lanes, so that what differs by
head and value (`a`, `u`, the read-out) is a lane vector, what is shared
(`B`, `C`) a column, a chunk's read-out `C S` and update `B^T u` are plain
matmuls [Q, N] x [N, H P] and [N, Q] x [Q, H P], and no minor dimension is
64 wide (ops/kvcache.py::init_paged_cache says what that costs).

Everything here is float32: `dt`, the decays, the state, the products with
it and the read-out; the matmuls run at `highest` (a 512-token chunk of 36
layers needs 0.06 TFLOP of them beside 3.3 of weight matmuls). A token that
is not real has `dt = 0`: it decays nothing (`exp(0) = 1`) and adds
nothing, so a row of such tokens leaves its state bit for bit.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from substratus_tpu.ops import scopes

BLOCK = 128  # tokens of one pass of `chunk`'s scan
_HIGHEST = lax.Precision.HIGHEST


def lanes(v: jnp.ndarray, p: int) -> jnp.ndarray:
    """[..., H] a head -> [..., H x P]: each head's number under its P
    values' lanes."""
    return jnp.repeat(v, p, axis=-1)


def read_out(carried, x, b, c, dt, d_skip):
    """o [..., H, P] float32 of one token from `carried` = `a_t S_{t-1} C_t`
    ([..., H x P], the state as it was): `o_t = a_t S_{t-1} C_t + (B_t . C_t)
    dt_t x_t + D x_t`, which is `S_t C_t + D x_t`."""
    x32 = x.astype(jnp.float32)
    bc = jnp.sum(b.astype(jnp.float32) * c.astype(jnp.float32), axis=-1)
    return (carried.reshape(x.shape)
            + (bc[..., None] * dt + d_skip)[..., None] * x32)


def decay_and_input(x, dt, a_log):
    """One token a row, along the state's lanes: (`a = exp(dt A)`, `u = dt
    x`), both [B, H x P] float32."""
    p = x.shape[-1]
    a = lanes(jnp.exp(dt * -jnp.exp(a_log)), p)
    return a, lanes(dt, p) * x.astype(jnp.float32).reshape(x.shape[0], -1)


def step(
    s_old: jnp.ndarray,  # [B, N, H x P] float32
    x: jnp.ndarray,  # [B, H, P]
    b: jnp.ndarray,  # [B, N]
    c: jnp.ndarray,  # [B, N]
    dt: jnp.ndarray,  # [B, H] float32; zero for a row that is not real
    a_log: jnp.ndarray,  # [H] float32: A = -exp(a_log)
    d_skip: jnp.ndarray,  # [H] float32
    fresh: jnp.ndarray,  # [B] bool: the row starts from a zero state
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The recurrent form for one token a row: (S', o [B, H, P] float32).
    The read-out is taken from the state as it was (`read_out`), so the
    state is read where it lies and written where it lies and no updated
    copy stands between the two."""
    b32, c32 = b.astype(jnp.float32), c.astype(jnp.float32)
    a, u = decay_and_input(x, dt, a_log)
    # neither the read-out nor the state written holds anything of the
    # slot's last occupant, whatever that left (a product with 0 would keep
    # an infinity)
    carried = jnp.where(fresh[:, None], 0.0, a * jnp.einsum(
        "bnr,bn->br", s_old, c32, precision=_HIGHEST))
    s_new = (jnp.where(fresh[:, None, None], 0.0, a[:, None, :] * s_old)
             + b32[:, :, None] * u[:, None, :])
    return s_new, read_out(carried, x, b, c, dt, d_skip)


def chunk(
    s_old: Optional[jnp.ndarray],  # [B, N, H x P] float32, or None: zero
    x: jnp.ndarray,  # [B, C, H, P]
    b: jnp.ndarray,  # [B, C, N]
    c: jnp.ndarray,  # [B, C, N]
    dt: jnp.ndarray,  # [B, C, H] float32; zero at a token that is not real
    a_log: jnp.ndarray,  # [H] float32
    d_skip: jnp.ndarray,  # [H] float32
    fresh: Optional[jnp.ndarray] = None,  # [B] bool, with a state
    block: int = BLOCK,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The chunked form: (S', o [B, C, H, P] float32). C is padded to a
    multiple of the block with tokens of `dt = 0`, which add and decay
    nothing."""
    bsz, n_tok, h, p = x.shape
    n = b.shape[-1]
    q = min(block, n_tok)
    pad = -n_tok % q
    blocks = (n_tok + pad) // q

    def split(t):  # [B, C, ...] -> [blocks, B, Q, ...], padded
        t = jnp.pad(t.astype(jnp.float32),
                    ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
        return jnp.moveaxis(t.reshape((bsz, blocks, q) + t.shape[2:]), 1, 0)

    if s_old is None:
        s_old = jnp.zeros((bsz, n, h * p), jnp.float32)
    else:
        s_old = jnp.where(fresh[:, None, None], 0.0, s_old)
    neg_a = -jnp.exp(a_log)
    seen = jnp.tril(jnp.ones((q, q), bool))

    def one(s, part):
        xq, bq, cq, dtq = part  # [B, Q, H, P], [B, Q, N] x 2, [B, Q, H]
        run = jnp.cumsum(dtq * neg_a, axis=1)  # b_i: [B, Q, H]
        u = (dtq[..., None] * xq).reshape(bsz, q, h * p)
        with jax.named_scope(scopes.SSM_INTRA):
            sc = jnp.einsum("bin,bjn->bij", cq, bq, precision=_HIGHEST)
            rh = jnp.moveaxis(run, 1, 2)  # [B, H, Q]
            w = jnp.where(
                seen, sc[:, None] * jnp.exp(jnp.where(
                    seen, rh[..., :, None] - rh[..., None, :], 0.0)), 0.0)
            o = jnp.einsum("bhij,bjhp->bihp", w, u.reshape(xq.shape),
                           precision=_HIGHEST)
        o = o + (lanes(jnp.exp(run), p) * jnp.einsum(
            "bin,bnr->bir", cq, s, precision=_HIGHEST)).reshape(xq.shape)
        o = o + d_skip[:, None] * xq
        last = run[:, -1]  # b_Q: [B, H]
        left = lanes(jnp.exp(last[:, None] - run), p)  # exp(b_Q - b_j)
        s = (lanes(jnp.exp(last), p)[:, None, :] * s
             + jnp.einsum("bjn,bjr->bnr", bq, left * u, precision=_HIGHEST))
        return s, o

    # the caller's region (SSM_STATE) holds the scan, its carry and whatever
    # it moves; the in-block part opens SSM_INTRA inside it, as
    # ops/retention.py::chunk opens RET_INTRA inside RET_STATE
    s_new, o = lax.scan(one, s_old, tuple(split(t) for t in (x, b, c, dt)))
    o = jnp.moveaxis(o, 0, 1).reshape(bsz, blocks * q, h, p)
    return s_new, o[:, :n_tok]
