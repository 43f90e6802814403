"""Elementwise / normalization / positional ops.

These deliberately stay as plain jnp expressions: XLA fuses them into the
surrounding matmuls, which is the right call on TPU (HBM-bandwidth-bound
elementwise work should never round-trip). Pallas is reserved for ops XLA
can't fuse well (attention, see ops/flash_attention.py).
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp


def rms_norm(x: jnp.ndarray, scale: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    """RMSNorm in float32 accumulation regardless of input dtype."""
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    normed = x32 * jnp.reciprocal(jnp.sqrt(var + eps))
    return (normed * scale.astype(jnp.float32)).astype(dtype)


def rope_freqs(head_dim: int, theta: float = 10000.0,
               yarn: Optional[Tuple[float, int, float, float]] = None
               ) -> jnp.ndarray:
    """Inverse frequencies, shape [head_dim // 2] (float32).

    `yarn` = (factor, original positions, beta_fast, beta_slow) stretches
    the table as YaRN does (DeepSeek-V3's `yarn_find_correction_range` and
    `yarn_linear_ramp_mask`): pair i keeps f_i = theta^(-2i/d) where the
    original positions turn it more than beta_fast times, takes f_i /
    factor where they turn it less than beta_slow times, and between the
    two pair indices `lo` and `hi` at which they turn it exactly so often
    a linear ramp of both. Without it: the plain table, as ever."""
    exponent = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    freqs = 1.0 / (theta**exponent)
    if yarn is None:
        return freqs
    factor, original, beta_fast, beta_slow = yarn

    def pair_at(rotations: float) -> float:
        return (head_dim * math.log(original / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    lo = max(math.floor(pair_at(beta_fast)), 0)
    hi = min(math.ceil(pair_at(beta_slow)), head_dim // 2 - 1)
    if lo == hi:
        hi += 0.001  # the published code's guard against a ramp of no width
    ramp = jnp.clip(
        (jnp.arange(head_dim // 2, dtype=jnp.float32) - lo) / (hi - lo), 0, 1)
    return freqs / factor * ramp + freqs * (1 - ramp)


def yarn_mscale(factor: float, mscale: float) -> float:
    """DeepSeek-V3's `yarn_get_mscale`: 0.1 mscale ln(factor) + 1 (1 for a
    factor of 1 or less). The softmax scale of a YaRN model carries its
    square at `mscale_all_dim`."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rope(
    x: jnp.ndarray, positions: jnp.ndarray, theta: float = 10000.0,
    yarn: Optional[Tuple[float, int, float, float]] = None,
) -> jnp.ndarray:
    """Rotary position embedding, HF-Llama "rotate_half" convention.

    x: [..., seq, heads, head_dim]; positions: broadcastable to [..., seq].
    The two rotated halves are x[..., :d/2] and x[..., d/2:] (NOT interleaved
    pairs), matching transformers' LlamaRotaryEmbedding so HF checkpoints load
    without permutation. `yarn`: `rope_freqs`' stretched table.
    """
    head_dim = x.shape[-1]
    freqs = rope_freqs(head_dim, theta, yarn)  # [d/2]
    angles = positions[..., None].astype(jnp.float32) * freqs  # [..., seq, d/2]
    cos = jnp.cos(angles)[..., None, :]  # broadcast over heads
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate((x1 * cos - x2 * sin, x2 * cos + x1 * sin), axis=-1)
    return out.astype(x.dtype)


def swiglu(gate: jnp.ndarray, up: jnp.ndarray) -> jnp.ndarray:
    return jax.nn.silu(gate) * up


def layer_norm(
    x: jnp.ndarray, scale: jnp.ndarray, bias: jnp.ndarray, eps: float = 1e-5
) -> jnp.ndarray:
    """Classic LayerNorm (mean-centered, affine) in float32 accumulation —
    the GPT/OPT-family normalizer (Llama uses rms_norm)."""
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mean), axis=-1, keepdims=True)
    normed = (x32 - mean) * jnp.reciprocal(jnp.sqrt(var + eps))
    return (
        normed * scale.astype(jnp.float32) + bias.astype(jnp.float32)
    ).astype(dtype)


def lora_delta(h, adapter, scale, out_einsum: str):
    """LoRA low-rank update h @ A @ B * scale; shared by every model family
    (adapter trees come from train/lora.py)."""
    down = jnp.einsum("bsd,dr->bsr", h, adapter["a"])
    return jnp.einsum(out_einsum, down, adapter["b"]) * scale


def batched_lora_einsum(out_einsum: str) -> str:
    """The per-row form of a lora_delta output einsum: the second
    operand (the gathered B matrices) grows a leading batch axis, e.g.
    'bsr,rhk->bshk' -> 'bsr,brhk->bshk'."""
    lhs, _, out = out_einsum.partition("->")
    first, _, second = lhs.partition(",")
    return f"{first},b{second}->{out}"


def lora_delta_indexed(h, adapter, scale, out_einsum: str, adapter_ids):
    """Per-batch-row LoRA update for multi-tenant serving
    (serve/adapters.py): the adapter leaves carry a leading adapter-slot
    axis (`a: [A, in, r]`, `b: [A, r, ...out]`) and `adapter_ids` [B]
    gathers each row's pair, so one einsum applies every tenant's delta
    in the same dispatch. Slot 0 is the all-zero identity adapter —
    rows without a tenant gather zeros and stay exactly the base model."""
    a = jnp.take(adapter["a"], adapter_ids, axis=0)  # [B, in, r]
    b = jnp.take(adapter["b"], adapter_ids, axis=0)  # [B, r, ...out]
    down = jnp.einsum("bsd,bdr->bsr", h, a)
    return jnp.einsum(batched_lora_einsum(out_einsum), down, b) * scale
