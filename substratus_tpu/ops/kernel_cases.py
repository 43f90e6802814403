"""One list of kernel cases at the widths the repo serves and trains: the
Pallas kernels, and the dense slot cache's attention, which is plain XLA.

Shared by tests/test_chip_compile.py (each case AOT-compiled by the
chip's compiler for a described, unattached v5e) and chip_smoke.py's
kernel phase (each case compiled, run on the attached chip and compared
with the XLA reference in ops/attention.py / ops/kvcache.py / ops/quant.py /
Q4Tensor.dequant).

Widths: TinyLlama-1.1B (32 heads / 4 kv heads of 64, dim 2048, hidden
5632), Llama-2-7B (32/32 heads of 128, dim 4096, hidden 11008) and, for
attention over a cache, Mistral-7B (32 heads / 8 kv heads of 128) and
LFM2-24B-A2B (32 / 8 of 64, stored two to a row of 128 in the paged pool);
for a retention state, Brumby-14B (40 / 8 of 128: 8,256 x 128 a head); for a
state-space state, Granite-4.0-H-Micro (64 heads of 64 over 128: 128 x 4,096
a slot);
prefill lengths are the engine's power-of-two buckets up to
EngineConfig.max_prefill_len (16..512) plus the trainer's 1024/2048;
cache lengths are EngineConfig.max_seq_len (1024) and the old bench's
512; batches are the engine's default 8 and the old bench's 24.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, List, Optional

import jax
import jax.numpy as jnp

from substratus_tpu.ops.attention import dot_product_attention
from substratus_tpu.ops.quant import dequantize_kv, quantize_kv

BF16 = jnp.bfloat16


@dataclass(frozen=True)
class KernelCase:
    """make_args(key) -> positional arrays; kernel(*args, interpret=...)
    and reference(*args) return the same pytree. `tol` bounds
    max|kernel - reference| / max(1, max|reference|) over every leaf.
    `mosaic` says whether the compiled program holds a Pallas kernel."""

    name: str
    make_args: Callable[[jax.Array], tuple]
    kernel: Callable[..., Any]
    reference: Callable[..., Any]
    tol: float
    mosaic: bool = True


def max_error(got: Any, want: Any) -> float:
    """Largest difference over all leaves, relative to the reference's
    largest magnitude (floored at 1 so near-zero outputs compare
    absolutely)."""
    worst = 0.0
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        g32, w32 = g.astype(jnp.float32), w.astype(jnp.float32)
        denom = jnp.maximum(1.0, jnp.max(jnp.abs(w32)))
        worst = max(worst, float(jnp.max(jnp.abs(g32 - w32)) / denom))
    return worst


def _normal(key, shape, dtype=BF16):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


# --- flash attention (no-cache prefill / training) -------------------------


def _flash_args(b, s, h, kh, d, key):
    kq, kk, kv, kg = jax.random.split(key, 4)
    return (
        _normal(kq, (b, s, h, d)), _normal(kk, (b, s, kh, d)),
        _normal(kv, (b, s, kh, d)), _normal(kg, (b, s, h, d)),
    )


def flash_fwd(tag, b, s, h, kh, d) -> KernelCase:
    from substratus_tpu.ops.flash_attention import (
        DEFAULT_BLOCK_K, DEFAULT_BLOCK_Q, flash_attention,
    )

    def kernel(q, k, v, g, interpret=False):
        return flash_attention(
            q, k, v, True, None, DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K, interpret
        )

    def reference(q, k, v, g):
        return dot_product_attention(q, k, v, causal=True)

    return KernelCase(
        f"flash_fwd/{tag}/b{b}-s{s}", partial(_flash_args, b, s, h, kh, d),
        kernel, reference, tol=2e-2,
    )


def flash_bwd(tag, b, s, h, kh, d) -> KernelCase:
    from substratus_tpu.ops.flash_attention import (
        DEFAULT_BLOCK_K, DEFAULT_BLOCK_Q, flash_attention,
    )

    def grads(attend, q, k, v, g):
        def loss(q, k, v):
            return jnp.sum(
                attend(q, k, v).astype(jnp.float32) * g.astype(jnp.float32)
            )

        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    def kernel(q, k, v, g, interpret=False):
        return grads(
            lambda q, k, v: flash_attention(
                q, k, v, True, None, DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K,
                interpret,
            ),
            q, k, v, g,
        )

    def reference(q, k, v, g):
        return grads(
            lambda q, k, v: dot_product_attention(q, k, v, causal=True),
            q, k, v, g,
        )

    return KernelCase(
        f"flash_bwd/{tag}/b{b}-s{s}", partial(_flash_args, b, s, h, kh, d),
        kernel, reference, tol=4e-2,
    )


# --- attention against the dense slot cache --------------------------------


def _cache_args(b, sq, h, kh, d, cache_len, int8, key):
    """q [B, Sq, H, D], cache k/v [B, KH, S, D] (+ scales), and per-row
    positions that put the queries at the END of a nearly full cache."""
    kq, kk, kv = jax.random.split(key, 3)
    q = _normal(kq, (b, sq, h, d))
    k = _normal(kk, (b, kh, cache_len, d))
    v = _normal(kv, (b, kh, cache_len, d))
    start = cache_len - sq - jnp.arange(b, dtype=jnp.int32) * 3
    positions = start[:, None] + jnp.arange(sq, dtype=jnp.int32)[None, :]
    if not int8:
        return q, k, v, positions
    kq8, ks = quantize_kv(k)
    vq8, vs = quantize_kv(v)
    return q, kq8, vq8, positions, ks[..., 0], vs[..., 0]


def _cache_reference(q, k, v, positions, k_scale=None, v_scale=None):
    if k_scale is not None:
        k = dequantize_kv(k, k_scale[..., None], q.dtype)
        v = dequantize_kv(v, v_scale[..., None], q.dtype)
    return dot_product_attention(
        q, k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
        causal=True, q_positions=positions,
    )


def _kv_tag(int8: bool) -> str:
    return "int8kv" if int8 else "bf16kv"


def dense_attend(tag, b, sq, h, kh, d, cache_len, int8) -> KernelCase:
    """ops/decode_attention.py::update_cache_and_attend, the one attention
    of the dense slot cache: fresh rows written at `positions`, then a
    one-token step (sq == 1) or a chunk / verify round attends."""
    from substratus_tpu.ops.decode_attention import update_cache_and_attend

    def make_args(key):
        kc, kk, kv = jax.random.split(key, 3)
        fresh = (_normal(kk, (b, sq, kh, d)), _normal(kv, (b, sq, kh, d)))
        return fresh + _cache_args(b, sq, h, kh, d, cache_len, int8, kc)

    def kernel(kk, vv, q, k, v, positions, ks=None, vs=None,
               interpret=False):
        cache = {"k": k, "v": v}
        if ks is not None:
            cache.update(k_scale=ks, v_scale=vs)
        return update_cache_and_attend(cache, q, kk, vv, positions)

    def reference(kk, vv, q, k, v, positions, ks=None, vs=None):
        # Each row's fresh positions are consecutive (_cache_args): one
        # slice update a row, where the op scatters position by position.
        def put(cache, rows):  # [B, KH, S, ...] <- [B, KH, sq, ...]
            return jax.vmap(
                lambda c, r, at: jax.lax.dynamic_update_slice_in_dim(
                    c, r.astype(c.dtype), at, axis=1)
            )(cache, rows, positions[:, 0])

        kT, vT = kk.transpose(0, 2, 1, 3), vv.transpose(0, 2, 1, 3)
        if ks is None:
            cache = {"k": put(k, kT), "v": put(v, vT)}
        else:
            (kq, kqs), (vq, vqs) = quantize_kv(kT), quantize_kv(vT)
            cache = {
                "k": put(k, kq), "v": put(v, vq),
                "k_scale": put(ks, kqs[..., 0]),
                "v_scale": put(vs, vqs[..., 0]),
            }
        return _cache_reference(
            q, cache["k"], cache["v"], positions,
            cache.get("k_scale"), cache.get("v_scale"),
        ), cache

    q_tag = f"q{sq}-" if sq > 1 else ""
    return KernelCase(
        f"dense_attend/{tag}/b{b}-{q_tag}s{cache_len}-{_kv_tag(int8)}",
        make_args, kernel, reference, tol=2e-2, mosaic=False,
    )


# --- attention over the paged pool -------------------------------------------


def _pool_and_tables(key, b, m, pool_shape, idle_last=True):
    """A seeded K and V pool of `pool_shape` = (layers, pages, page size, KV
    heads, head width), in the shape ops/kvcache.py::init_paged_cache
    stores it (heads of 64 two to a row), and block tables [b, m] of
    scattered pages (where the pool is smaller than the tables, rows share
    them); the last row's is the trash page's where that row idles."""
    from substratus_tpu.ops.kvcache import init_paged_cache

    kk, kv, kt = jax.random.split(key, 3)
    pool_shape = jax.eval_shape(
        lambda: init_paged_cache(*pool_shape, BF16))["k"].shape
    pages = pool_shape[1]
    own = jax.random.permutation(kt, jnp.arange(1, pages, dtype=jnp.int32))
    table = own[jnp.arange(b * m) % (pages - 1)].reshape(b, m)
    if idle_last:
        table = table.at[-1].set(0)
    return _normal(kk, pool_shape), _normal(kv, pool_shape), table


def _gathered(q, k, v, layer, table, positions):
    """[B, S, H, hd] x the pool -> what ops/kvcache.py's gather path gives."""
    from substratus_tpu.ops.kvcache import paged_read

    k_ctx, v_ctx = paged_read(
        {"k": k, "v": v}, layer, table, q.dtype, q.shape[-1])
    return dot_product_attention(
        q, k_ctx, v_ctx, causal=True, q_positions=positions)


def _in_place(q, k, v, layer, table, positions, interpret=False):
    """[B, S, H, hd] x the pool -> what ops/kvcache.py::paged_attend picks
    for these operands on a TPU: the decode kernel for S == 1, the chunk
    kernel beyond, over the pool's stored rows; the gather itself where
    `_kernel_for` has no kernel."""
    from substratus_tpu.ops import kvcache
    from substratus_tpu.ops.paged_attention import paged_chunk_attention

    if interpret:  # the CPU rehearsal: the op would take the gather
        kernel = (paged_chunk_attention if q.shape[1] > 1
                  else kvcache._one_token)
        return kvcache._over_stored_rows(partial(kernel, interpret=True))(
            q, k, v, layer, table, positions)
    return kvcache.paged_attend(
        {"k": k, "v": v}, layer, table, positions, q, q.dtype)


def paged_decode(tag, b, max_seq, h, kh, d, pages, page=16,
                 layers=2) -> KernelCase:
    """One query a row against a stacked pool of `layers` x `pages` pages:
    rows of every length from a full table down, the last row idle as the
    engine leaves one (position 0, a table of the trash page); the pages
    of a row lie scattered, and where the pool is smaller than the tables
    rows share them."""

    def make_args(key):
        kq, kp = jax.random.split(key)
        k, v, table = _pool_and_tables(
            kp, b, max_seq // page, (layers, pages, page, kh, d))
        positions = (max_seq - 1) * (b - 1 - jnp.arange(b)) // max(b - 1, 1)
        return (
            _normal(kq, (b, h, d)), k, v, jnp.int32(layers - 1), table,
            positions.astype(jnp.int32),
        )

    def one_token(attend):
        def run(q, k, v, layer, table, positions, **kw):
            return attend(
                q[:, None], k, v, layer, table, positions[:, None], **kw)[:, 0]

        return run

    return KernelCase(
        f"paged_decode/{tag}/b{b}-s{max_seq}-h{h}", make_args,
        one_token(_in_place), one_token(_gathered), tol=2e-2,
    )


def paged_chunk(tag, b, s, max_seq, h, kh, d, pages, page=16,
                layers=2) -> KernelCase:
    """`s` query tokens a row against the same stacked pool: a prefill chunk
    (b = 1: its last token is the last position of the table, so the walk
    takes every page) or a speculative verify round (rows of every length
    from a full table down, the last row idle: positions 0 .. s - 1, a
    table of the trash page)."""

    def make_args(key):
        kq, kp = jax.random.split(key)
        k, v, table = _pool_and_tables(
            kp, b, max_seq // page, (layers, pages, page, kh, d),
            idle_last=b > 1)
        last = max_seq - 1 - (max_seq - s) * jnp.arange(b) // max(b - 1, 1)
        positions = last[:, None] - (s - 1) + jnp.arange(s)[None, :]
        return (
            _normal(kq, (b, s, h, d)), k, v, jnp.int32(layers - 1), table,
            positions.astype(jnp.int32),
        )

    return KernelCase(
        f"paged_chunk/{tag}/b{b}-q{s}-s{max_seq}-h{h}", make_args, _in_place,
        _gathered, tol=2e-2,
    )


# --- latent attention over a pool of shared rows --------------------------------


def latent_attend(tag, b, s, max_seq, h, dn, dr, dv, rkv, pages, page=16,
                  layers=2, index=None) -> KernelCase:
    """ops/kvcache.py::latent_attention over a stacked pool of latent rows
    [ckv (rkv); kr (dr)], one a token for all `h` heads: `s` = 1 a decode
    step (absorbed: rows of every length from a full table down, the last
    idle), `s` > 1 a prefill chunk (expanded: its last token the table's
    last position, so the walk takes every page). The new rows are written
    first, as the op does; both realisations return the attention alone.
    `index` = (heads, key width, k): the layer picks each query's k rows by
    a learned index (ops/sparse_index.py), whose keys fill the pool's
    second array; the step then scores them in place (and, at a page of
    128 tokens, takes its set in VMEM) and the chunk runs under each
    query's own set."""
    from substratus_tpu.ops import kvcache
    from substratus_tpu.ops import latent_attention as LA
    from substratus_tpu.ops import sparse_index as SI

    hi, di, topk = index or (0, 0, 0)

    def make_args(key):
        kq, kn, kw, kp, kt, ki = jax.random.split(key, 6)
        pool = kvcache.init_latent_cache(layers, pages, page, rkv + dr, BF16,
                                         di)
        rows = _normal(kp, (layers, pages, page, 1, rkv + dr))
        keys = _normal(ki, pool["v"].shape) if index else pool["v"]
        pool = pool["k"].at[..., :rkv + dr].set(rows)
        m = max_seq // page
        own = jax.random.permutation(kt, jnp.arange(1, pages, dtype=jnp.int32))
        table = own[jnp.arange(b * m) % (pages - 1)].reshape(b, m)
        if b > 1:
            table = table.at[-1].set(0)
        last = max_seq - 1 - (max_seq - s) * jnp.arange(b) // max(b - 1, 1)
        positions = last[:, None] - (s - 1) + jnp.arange(s)[None, :]
        w = _normal(kw, (h, dn + dv, rkv), jnp.float32) * rkv ** -0.5
        ks = jax.random.split(ki, 3)
        return (
            _normal(kq, (b, s, h, dn + dr)), _normal(kn, (b, s, rkv + dr)),
            w[:, :dn].astype(BF16), w[:, dn:].astype(BF16), pool,
            jnp.int32(layers - 1), table, positions.astype(jnp.int32), keys,
            _normal(ks[0], (b, s, hi, di)), _normal(ks[1], (b, s, di)),
            _normal(ks[2], (b, s, hi), jnp.float32),
        )

    scale = (dn + dr) ** -0.5

    def attend(q, new, w_uk, w_uv, pool, layer, table, positions, keys, qi,
               ki, wi):
        cache = {"k": pool, "v": keys}
        return kvcache.latent_attention(
            cache, layer, table, positions, q, new, w_uk, w_uv, scale,
            q.dtype, (qi, ki, wi, topk) if index else None)[1]

    def kernel(*args, interpret=False):
        if not interpret:
            return attend(*args)
        # the CPU rehearsal: the op would take the gather
        names = ("latent_decode_attention", "latent_chunk_attention",
                 "index_decode_scores", "index_chunk_scores",
                 "index_select_rows")
        real = jax.lax.platform_dependent, [getattr(kvcache, n) for n in names]
        jax.lax.platform_dependent = lambda *a, tpu, default: tpu(*a)
        for n in names:
            setattr(kvcache, n, partial(
                getattr(SI if n.startswith("index") else LA, n),
                interpret=True))
        try:
            return attend(*args)
        finally:
            jax.lax.platform_dependent = real[0]
            for n, f in zip(names, real[1]):
                setattr(kvcache, n, f)

    def reference(*args):
        real = kvcache._latent_kernels_for
        kvcache._latent_kernels_for = lambda *a: None
        try:
            return attend(*args)
        finally:
            kvcache._latent_kernels_for = real

    return KernelCase(
        f"latent_attend/{tag}/b{b}-q{s}-s{max_seq}-h{h}"
        + (f"-top{topk}" if index else ""), make_args, kernel, reference,
        tol=2e-2,
    )


# --- int4 unpack-dequant matmul ---------------------------------------------


def q4_matmul(tag, m, c, n) -> KernelCase:
    from substratus_tpu.ops.quant4 import Q4Tensor, _matmul, quantize4

    def make_args(key):
        kx, kw = jax.random.split(key)
        w = quantize4(_normal(kw, (c, n), jnp.float32) * 0.02, (0,))
        return _normal(kx, (m, c)), w.packed, w.scale

    def kernel(x, packed, scale, interpret=False):
        return _matmul(x, packed, scale, c // scale.shape[0],
                       interpret=interpret)

    def reference(x, packed, scale):
        w = Q4Tensor(packed, scale, pack_axis=-2, block=c // scale.shape[0])
        return jnp.einsum(
            "mc,cn->mn", x.astype(jnp.float32), w.dequant(jnp.float32)
        ).astype(x.dtype)

    return KernelCase(
        f"q4_matmul/{tag}/m{m}-c{c}-n{n}", make_args, kernel, reference,
        tol=2e-2,
    )


# --- a power-retention layer's decode step over its stacked state ---------------


def retention_decode(tag, b, h, kh, d, layers=2, seen=4) -> KernelCase:
    """One token a row, every slot a row, against the last layer of a
    float32 state of `layers`: what ops/kvcache.py::retention_read_and_update
    runs on a TPU (ops/retention_kernel.py) beside ops/retention.py::step.
    The state is the sum of `seen` keys' `phi(k) v^T`, so the normaliser is
    a sum of squares as a served one is; row 0 starts afresh, the last row
    idles (`k = 0`, `log g = 0`)."""
    from substratus_tpu.ops import retention, retention_kernel

    def make_args(key):
        ks = jax.random.split(key, 6)
        pk = retention.phi(_normal(ks[0], (layers, b, kh, seen, d)))
        state_s = jnp.einsum(
            "lbkjf,lbkjd->lbkfd", pk,
            _normal(ks[1], (layers, b, kh, seen, d), jnp.float32))
        idle = jnp.arange(b) == b - 1
        k = jnp.where(idle[:, None, None], 0, _normal(ks[3], (b, kh, d)))
        log_g = jax.nn.log_sigmoid(
            jax.random.uniform(ks[5], (b, kh), minval=8.0, maxval=10.0))
        return (
            state_s, pk.sum(axis=3), jnp.int32(layers - 1),
            _normal(ks[2], (b, h, d)), k, _normal(ks[4], (b, kh, d)),
            jnp.where(idle[:, None], 0.0, log_g), jnp.arange(b) == 0,
        )

    def kernel(state_s, state_z, layer, *row, interpret=False):
        s, z, o = retention_kernel.step(
            state_s, state_z, layer, *row, interpret=interpret)
        return s[-1], z[-1], o

    def reference(state_s, state_z, layer, *row):
        return retention.step(state_s[-1], state_z[-1], *row)

    return KernelCase(
        f"retention_decode/{tag}/b{b}-h{h}-d{d}", make_args, kernel,
        reference, tol=1e-4,
    )


# --- a Mamba-2 layer's decode step over its stacked state -----------------------


def ssm_decode(tag, b, h, p, n, layers=2, seen=4) -> KernelCase:
    """One token a row, every slot a row, against the last layer of a
    float32 state of `layers`: what ops/kvcache.py::ssm_read_and_update
    runs on a TPU (ops/ssd_kernel.py) beside ops/ssd.py::step. The state is
    the sum of `seen` tokens' `B u^T`; row 0 starts afresh, the last row
    idles (`dt = 0`)."""
    from substratus_tpu.ops import ssd, ssd_kernel

    def make_args(key):
        ks = jax.random.split(key, 8)
        state = jnp.einsum(
            "lbjn,lbjr->lbnr", _normal(ks[0], (layers, b, seen, n), jnp.float32),
            0.01 * _normal(ks[1], (layers, b, seen, h * p), jnp.float32))
        dt = jax.nn.softplus(_normal(ks[5], (b, h), jnp.float32) - 5.0)
        return (
            state, jnp.int32(layers - 1), _normal(ks[2], (b, h, p)),
            _normal(ks[3], (b, n)), _normal(ks[4], (b, n)),
            jnp.where((jnp.arange(b) == b - 1)[:, None], 0.0, dt),
            0.01 * _normal(ks[6], (h,), jnp.float32),
            1.0 + 0.1 * _normal(ks[7], (h,), jnp.float32),
            jnp.arange(b) == 0,
        )

    def kernel(state, layer, *row, interpret=False):
        s, o = ssd_kernel.step(state, layer, *row, interpret=interpret)
        return s[-1], o

    def reference(state, layer, *row):
        return ssd.step(state[-1], *row)

    return KernelCase(
        f"ssm_decode/{tag}/b{b}-h{h}-p{p}-n{n}", make_args, kernel,
        reference, tol=1e-4,
    )


# --- the lists ---------------------------------------------------------------

# What the chip's compiler says of a kernel wrapped in custom_partitioning
# (ops/kernel_partition.py) once its operands are sharded over a mesh of
# several chips: the partitioner never runs, and the wrapper reaches the
# TPU emitter as it is. One chip compiles the same kernels.
SHARDED_REFUSED = "Custom emitter for CustomSPMDPartitioning not found"

TINYLLAMA = dict(h=32, kh=4, d=64)
LFM2 = dict(h=32, kh=8, d=64)
SMALL = dict(h=4, kh=2, d=64)  # the CPU rehearsal's widths
LLAMA7B = dict(h=32, kh=32, d=128)
MISTRAL7B = dict(h=32, kh=8, d=128)


def chip_cases() -> List[KernelCase]:
    """Every kernel at TinyLlama-1.1B's and Llama-2-7B's widths; the dense
    slot cache's attention at Mistral-7B's too (grouped queries over heads
    of 128, which neither of the two has)."""
    cases: List[KernelCase] = []
    for tag, w in (("tinyllama", TINYLLAMA), ("llama2-7b", LLAMA7B)):
        for s in (16, 128, 384, 512, 2048):
            cases.append(flash_fwd(tag, 1, s, **w))
        cases.append(flash_bwd(tag, 2, 512, **w))
        cases.append(flash_bwd(tag, 1, 2048, **w))
    for tag, w in (("tinyllama", TINYLLAMA), ("llama2-7b", LLAMA7B),
                   ("mistral-7b", MISTRAL7B)):
        for int8 in (False, True):
            # The decode batch, the old bench's, a bucket-padded prefill
            # chunk, and a spec_k=4 verify round over the decode batch.
            cases.append(dense_attend(tag, 8, 1, cache_len=1024, int8=int8, **w))
            cases.append(dense_attend(tag, 24, 1, cache_len=512, int8=int8, **w))
            cases.append(dense_attend(tag, 1, 512, cache_len=1024, int8=int8, **w))
            cases.append(dense_attend(tag, 8, 5, cache_len=1024, int8=int8, **w))
    # A cache length that is no multiple of 128.
    cases.append(dense_attend("tinyllama", 8, 1, cache_len=1000, int8=True, **TINYLLAMA))
    for tag, dim, hidden, kv_dim in (
        ("tinyllama", 2048, 5632, 256), ("llama2-7b", 4096, 11008, 4096)
    ):
        for m in (8, 24, 512):
            cases.append(q4_matmul(f"{tag}-wq", m, dim, dim))
            cases.append(q4_matmul(f"{tag}-up", m, dim, hidden))
            cases.append(q4_matmul(f"{tag}-down", m, hidden, dim))
        cases.append(q4_matmul(f"{tag}-wk", 8, dim, kv_dim))
        cases.append(q4_matmul(f"{tag}-lm_head", 8, dim, 32000))
    # The decode step of the benchmark's four cells (benchmarks/traffic/):
    # max_batch x max_seq_len, query / KV heads, the cell's pool. LFM2's
    # and Granite's heads of 64 lie two to a stored row of 128, in the
    # families' own pages (64 and 128 tokens: their modules' PAGE_TOKENS).
    cases.append(paged_decode("mistral-chat", 32, 2048, 32, 8, 128, 1793))
    cases.append(paged_decode("mistral-longdoc", 5, 8192, 32, 8, 128, 1921))
    cases.append(paged_decode("k-exaone", 64, 4096, 64, 8, 128, 10241))
    cases.append(paged_decode(
        "lfm2-assist", 64, 2048, pages=1537, page=64, **LFM2))
    cases.append(paged_decode(
        "granite-rag", 48, 9216, pages=3457, page=128, **LFM2))
    # Their largest chunk programs' attention (the chat cell's median prompt
    # is one chunk of 256), a spec_k=4 verify round over the chat cell's
    # batch, and TinyLlama's chunk (4 KV heads of 64: two rows a token).
    cases.append(paged_chunk("mistral-longdoc", 1, 512, 8192, 32, 8, 128, 1921))
    cases.append(paged_chunk("mistral-chat", 1, 256, 2048, 32, 8, 128, 1793))
    cases.append(paged_chunk("k-exaone", 1, 512, 4096, 64, 8, 128, 10241))
    cases.append(paged_chunk("mistral-verify", 32, 5, 2048, 32, 8, 128, 1793))
    cases.append(paged_chunk(
        "lfm2-assist", 1, 512, 2048, pages=1537, page=64, **LFM2))
    cases.append(paged_chunk(
        "granite-rag", 1, 512, 9216, pages=3457, page=128, **LFM2))
    cases.append(paged_chunk("tinyllama", 1, 512, 1024, pages=513, **TINYLLAMA))
    # The longctx cell's decode step of one retention layer: 16 slots, 40
    # query heads over 8 of 128, a state of 8,256 x 128 a head.
    cases.append(retention_decode("brumby-longctx", 16, 40, 8, 128))
    # The rag cell's decode step of one Mamba-2 layer: 48 slots, 64 heads of
    # 64 over a state of 128, 2 MiB a slot.
    cases.append(ssm_decode("granite-rag", 48, 64, 64, 128))
    # The docqa cell's latent attention (DeepSeek-V3's widths: 128 heads over
    # one row of 512 + 64 a token, stored 640 wide, in the family's pages of
    # 128 tokens, models/deepseek_v3.py::PAGE_TOKENS): the decode step's
    # absorbed kernel over 12 slots of 14k, the 512 and 256 chunks' expanded
    # one.
    mla = dict(h=128, dn=128, dr=64, dv=128, rkv=512, pages=1281, page=128)
    cases.append(latent_attend("dots-docqa", 12, 1, 14336, **mla))
    cases.append(latent_attend("dots-docqa", 1, 512, 14336, **mla))
    cases.append(latent_attend("dots-docqa", 1, 256, 14336, **mla))
    # The repoqa cell's (GLM-5's widths: 64 heads of 192 + 64 against 256,
    # 32 index heads over keys of 128, the 2,048 best rows a query): the
    # step scores 4 slots' keys in place and reads the picked rows, the 512
    # chunk scores its queries and runs under each one's own set.
    dsa = dict(h=64, dn=192, dr=64, dv=256, rkv=512, pages=577, page=128,
               index=(32, 128, 2048))
    cases.append(latent_attend("glm5-repoqa", 4, 1, 18432, **dsa))
    cases.append(latent_attend("glm5-repoqa", 1, 512, 18432, **dsa))
    return cases


def rehearsal_cases() -> List[KernelCase]:
    """One small case per kernel for the CPU rehearsal (interpret mode)."""
    w = SMALL
    return [
        flash_fwd("small", 1, 128, **w),
        flash_bwd("small", 1, 128, **w),
        dense_attend("small", 1, 16, cache_len=128, int8=False, **w),
        dense_attend("small", 2, 5, cache_len=128, int8=True, **w),
        dense_attend("small", 2, 1, cache_len=128, int8=False, **w),
        dense_attend("small", 2, 1, cache_len=128, int8=True, **w),
        q4_matmul("small", 8, 256, 128),
        paged_decode("small", 3, 128, h=4, kh=2, d=64, pages=17),
        paged_chunk("small", 2, 16, 128, h=8, kh=4, d=64, pages=17),
        paged_decode("small", 3, 256, h=8, kh=4, d=64, pages=9, page=64),
        paged_chunk("small", 2, 16, 256, h=8, kh=4, d=64, pages=9, page=64),
        retention_decode("small", 3, h=4, kh=2, d=16),
        ssm_decode("small", 3, h=4, p=32, n=128),
        latent_attend("small", 3, 1, 128, h=8, dn=32, dr=16, dv=32, rkv=128,
                      pages=25),
        latent_attend("small", 2, 20, 128, h=8, dn=32, dr=16, dv=32, rkv=128,
                      pages=25),
        latent_attend("small", 3, 1, 128, h=8, dn=48, dr=16, dv=64, rkv=128,
                      pages=25, index=(4, 128, 24)),
        latent_attend("small", 2, 20, 128, h=8, dn=48, dr=16, dv=64, rkv=128,
                      pages=25, index=(4, 128, 24)),
        # a page a lane tile: the step takes its set in VMEM too
        latent_attend("small", 3, 1, 1024, h=8, dn=48, dr=16, dv=64,
                      rkv=128, pages=25, page=128, index=(4, 128, 128)),
    ]


def sharded_flash_case(n_devices: int, s: int = 512,
                       widths: Optional[dict] = None) -> KernelCase:
    """The trainer's flash forward with one sequence per device (what
    fsdp=n_devices hands the kernel); TinyLlama's widths by default."""
    return flash_fwd("sharded", n_devices, s, **(widths or TINYLLAMA))


def shard_batch(args: tuple, mesh) -> tuple:
    """Shard every operand's leading (batch) axis over the mesh's first
    axis; `args` may be arrays or ShapeDtypeStructs."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    sharding = NamedSharding(mesh, P(mesh.axis_names[0]))
    return tuple(
        jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding)
        if isinstance(a, jax.ShapeDtypeStruct) else jax.device_put(a, sharding)
        for a in args
    )
