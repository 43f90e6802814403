"""On-device token sampling (greedy / temperature / top-k / top-p).

Runs inside the jitted decode step so logits never leave HBM; only the
sampled token ids (a few bytes/row) cross to the host. Per-row temperature
and top-p let a continuous-batching engine serve heterogeneous requests in
one decode batch.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def sample(
    logits: jnp.ndarray,  # [B, V] float32
    key: jax.Array,
    temperature: jnp.ndarray,  # [B] float32; 0 => greedy for that row
    top_k: int = 0,  # static; 0 disables
    top_p: Optional[jnp.ndarray] = None,  # [B] float32 in (0, 1]; None disables
) -> jnp.ndarray:
    """Returns sampled token ids [B] int32.

    The temperatures decide what the step costs: a batch with no row above
    0 takes the argmax and nothing else; the sort over the vocabulary, the
    softmax, the cumulative sum and the [B, V] draw run only where some row
    samples (`lax.cond` on a value of the step's own input: no host read).
    The caller splits the key either way, so a greedy step advances it as a
    sampled one does."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def sampled_rows():
        # Scale by temperature (guard 0 to avoid inf; greedy rows are
        # overridden at the end anyway).
        safe_t = jnp.maximum(temperature, 1e-6)[:, None]
        scaled = logits / safe_t

        if top_k and top_k < logits.shape[-1]:
            kth = jax.lax.top_k(scaled, top_k)[0][:, -1:]
            scaled = jnp.where(scaled < kth, -jnp.inf, scaled)

        if top_p is not None:
            sorted_logits = jnp.sort(scaled, axis=-1)[:, ::-1]
            probs = jax.nn.softmax(sorted_logits, axis=-1)
            cum = jnp.cumsum(probs, axis=-1)
            # Keep the smallest prefix with cumulative prob >= top_p (always
            # keep the first token).
            keep_sorted = (cum - probs) < top_p[:, None]
            cutoff = jnp.min(
                jnp.where(keep_sorted, sorted_logits, jnp.inf), axis=-1,
                keepdims=True,
            )
            scaled = jnp.where(scaled < cutoff, -jnp.inf, scaled)

        sampled = jax.random.categorical(key, scaled, axis=-1).astype(jnp.int32)
        return jnp.where(temperature <= 0.0, greedy, sampled)

    return jax.lax.cond(
        jnp.any(temperature > 0.0), sampled_rows, lambda: greedy
    )
