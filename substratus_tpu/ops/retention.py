"""Power retention of degree 2 (Manifest AI, "Scaling Context Requires
Rethinking Attention", arXiv:2507.04239): linear attention whose feature
map is the symmetric second power of the key, under a learned decay.

For one KV head with queries `q_t`, keys `k_t` (d wide), values `v_t`,
gates `g_t` in (0, 1], `s = 1 / sqrt(d)` and `G_t = sum_{r <= t} log g_r`,
three forms of one function:

  * attention: `a_tj = (s q_t . k_j)^2 exp(G_t - G_j)` for `j <= t`, 0
    above; `o_t = sum_j a_tj v_j / (sum_j a_tj + EPS)`;
  * recurrent (`step`): `phi(x)` is `d (d + 1) / 2` wide with
    `phi(q) . phi(k) = (q . k)^2`; the head keeps `S [F, dv]` and `z [F]`,
    zero before position 0: `S_t = g_t S_{t-1} + phi(k_t) v_t^T`, `z_t =
    g_t z_{t-1} + phi(k_t)`, `o_t = phi(s q_t)^T S_t / (phi(s q_t)^T z_t +
    EPS)`;
  * chunked (`chunk`): C tokens from a carried `(S, z)`, `b_i` the running
    sum of `log g` inside the chunk: `num_i = exp(b_i) phi(s q_i)^T S +
    sum_{j <= i} (s q_i . k_j)^2 exp(b_i - b_j) v_j`, `den_i` the same
    with `z` and without `v`, `o_i = num_i / (den_i + EPS)`; `S' =
    exp(b_C) S + sum_j exp(b_C - b_j) phi(k_j) v_j^T`, `z'` likewise.
    Every exponent is <= 0. Without a state it is the attention form.

`phi`'s layout is this module's own: for `delta` = 0 .. d/2 - 1 a block of
d entries `c x_a x_{(a + delta) mod d}` (c = 1 for the squares, sqrt(2)
beyond), then the d/2 entries `sqrt(2) x_a x_{a + d/2}`: every unordered
pair once, and a block is the vector times a rotation of itself.

What is accumulated is float32: scores, `exp`, `S`, `z`, the division. A
decode step multiplies in float32 throughout (it is bound by the state's
bytes). A chunk hands the MXU operands of the activations' type (`q.dtype`:
bfloat16 when served, float32 in a CPU test): `phi(q)` against the carried
state for the read-out, `phi(k)` against the decayed values for the
update; q . k is exact in either, and the in-chunk weights meet `v` in
float32. A token that is not real has `k = 0` and `log g = 0`: it adds
nothing to the state and decays nothing.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from substratus_tpu.ops import scopes

EPS = 1e-6  # added to the normaliser
_ROOT2 = 2.0 ** 0.5
_HIGHEST = lax.Precision.HIGHEST


def width(d: int) -> int:
    """How wide `phi` of a d-wide vector is."""
    return d * (d + 1) // 2


def phi(x: jnp.ndarray) -> jnp.ndarray:
    """x [..., d] -> float32 [..., d (d + 1) / 2] with `phi(a) . phi(b) =
    (a . b)^2`, in the layout the module's docstring gives. The rotations
    are picked by a 0/1 matrix on the MXU, which is exact (one product a
    sum) and one operation where d / 2 slices would be d / 2."""
    d = x.shape[-1]
    if d % 2:
        raise ValueError(f"phi needs an even width, got {d}")
    half = d // 2
    at = jnp.arange(d)
    turn = jnp.arange(half + 1)
    pick = at[:, None, None] == (at[None, None, :] + turn[None, :, None]) % d
    turned = jnp.einsum(  # [..., delta, a] = x[(a + delta) mod d]
        "...c,cra->...ra", x, pick.astype(x.dtype),
        precision=_HIGHEST if x.dtype == jnp.float32 else None)
    scale = jnp.where(turn == 0, 1.0, _ROOT2)[:, None]
    both = x.astype(jnp.float32)[..., None, :] * turned * scale
    return jnp.concatenate(
        [both[..., :half, :].reshape(x.shape[:-1] + (half * d,)),
         both[..., half, :half]], axis=-1)


def _grouped(q: jnp.ndarray, kv_heads: int) -> jnp.ndarray:
    """[..., H, d] -> [..., KH, H // KH, d]: query head i reads KV head
    i // (H // KH)."""
    h, d = q.shape[-2:]
    return q.reshape(q.shape[:-2] + (kv_heads, h // kv_heads, d))


def step(
    s_old: jnp.ndarray,  # [B, KH, F, dv] float32
    z_old: jnp.ndarray,  # [B, KH, F] float32
    q: jnp.ndarray,  # [B, H, d]
    k: jnp.ndarray,  # [B, KH, d]; zero for a row that is not real
    v: jnp.ndarray,  # [B, KH, dv]
    log_g: jnp.ndarray,  # [B, KH] float32; zero for a row that is not real
    fresh: jnp.ndarray,  # [B] bool: the row starts from a zero state
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """The recurrent form for one token a row: (S', z', o [B, H, dv]
    float32). The read-out is taken from the state as it was: `o = (g
    phi(s q)^T S + (s q . k)^2 v) / (g phi(s q)^T z + (s q . k)^2 + EPS)`,
    the same number, so the state is read where it lies and written where
    it lies and no updated copy stands between the two."""
    kh, d = k.shape[-2:]
    q32, k32, v32 = (_grouped(q, kh).astype(jnp.float32),
                     k.astype(jnp.float32), v.astype(jnp.float32))
    g = jnp.exp(log_g)
    keep = jnp.where(fresh[:, None], 0.0, g)  # [B, KH]
    pq = phi(_grouped(q, kh)) / d  # phi(s q) = s^2 phi(q)
    pk = phi(k)
    qk2 = jnp.square(jnp.einsum("bkgd,bkd->bkg", q32, k32)) / d
    num = (keep[..., None, None] * jnp.einsum(
        "bkgf,bkfd->bkgd", pq, s_old, precision=_HIGHEST)
        + qk2[..., None] * v32[:, :, None, :])
    den = (keep[..., None] * jnp.einsum(
        "bkgf,bkf->bkg", pq, z_old, precision=_HIGHEST) + qk2)
    # the state written holds nothing of the slot's last occupant, whatever
    # that left (a product with 0 would keep an infinity)
    zero = fresh[:, None, None]
    z_new = jnp.where(zero, 0.0, g[..., None] * z_old) + pk
    s_new = (jnp.where(zero[..., None], 0.0, g[..., None, None] * s_old)
             + pk[..., None] * v32[:, :, None, :])
    o = num / (den[..., None] + EPS)
    return s_new, z_new, o.reshape(o.shape[0], -1, o.shape[-1])


def chunk(
    state: Optional[Tuple[jnp.ndarray, jnp.ndarray]],  # (S, z) as `step`
    q: jnp.ndarray,  # [B, C, H, d]
    k: jnp.ndarray,  # [B, C, KH, d]; zero at a token that is not real
    v: jnp.ndarray,  # [B, C, KH, dv]
    log_g: jnp.ndarray,  # [B, C, KH] float32; zero at such a token
    fresh: Optional[jnp.ndarray] = None,  # [B] bool, with a state
):
    """The chunked form: (S', z', o [B, C, H, dv] float32), or o alone
    without a state (the attention form over the whole of C)."""
    b_, c, kh, d = k.shape
    mxu = q.dtype
    qg = _grouped(q, kh)  # [B, C, KH, G, d]
    v32 = v.astype(jnp.float32)
    run = jnp.cumsum(log_g, axis=1)  # b_i: [B, C, KH]
    with jax.named_scope(scopes.RET_INTRA):
        sc = jnp.einsum("bikgd,bjkd->bkgij", qg, k,
                        preferred_element_type=jnp.float32)
        rk = jnp.moveaxis(run, 1, 2)  # [B, KH, C]
        seen = jnp.tril(jnp.ones((c, c), bool))
        decay = jnp.exp(jnp.where(seen, rk[..., :, None] - rk[..., None, :],
                                  0.0))
        a = jnp.where(seen, jnp.square(sc) / d * decay[:, :, None], 0.0)
        num = jnp.einsum("bkgij,bjkd->bikgd", a, v32, precision=_HIGHEST)
        den = jnp.moveaxis(jnp.sum(a, axis=-1), 3, 1)  # [B, C, KH, G]
    if state is None:
        o = num / (den[..., None] + EPS)
        return o.reshape(b_, c, -1, o.shape[-1])
    s_old, z_old = state
    with jax.named_scope(scopes.RET_STATE):
        alive = jnp.where(fresh, 0.0, 1.0)[:, None, None]  # [B, 1, 1]
        carried = jnp.exp(run) * alive  # exp(b_i), 0 from a zero state
        pq = (phi(qg) / d).astype(mxu)
        num = num + carried[..., None, None] * jnp.einsum(
            "bckgf,bkfd->bckgd", pq, s_old.astype(mxu),
            preferred_element_type=jnp.float32)
        den = den + carried[..., None] * jnp.einsum(
            "bckgf,bkf->bckg", pq, z_old.astype(mxu),
            preferred_element_type=jnp.float32)
        last = run[:, -1]  # b_C: [B, KH]
        w = jnp.exp(last[:, None] - run)  # exp(b_C - b_j): [B, C, KH]
        pk = phi(k)
        whole = jnp.exp(last)  # exp(b_C): [B, KH]
        zero = fresh[:, None, None]
        z_new = (jnp.where(zero, 0.0, whole[..., None] * z_old)
                 + jnp.einsum("bckf,bck->bkf", pk, w, precision=_HIGHEST))
        s_new = (jnp.where(zero[..., None], 0.0,
                           whole[..., None, None] * s_old)
                 + jnp.einsum("bckf,bckd->bkfd", pk.astype(mxu),
                              (w[..., None] * v32).astype(mxu),
                              preferred_element_type=jnp.float32))
    o = num / (den[..., None] + EPS)
    return s_new, z_new, o.reshape(b_, c, -1, o.shape[-1])
