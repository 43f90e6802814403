"""Seeded weights, born on the device in the type they are served in.

One jitted call makes the whole tree from `--seed`: int8 values with one
float32 scale per output channel for every matmul weight, bfloat16 for
embeddings, norms and the router. Nothing is made on the host and no
bfloat16 copy of a matmul weight ever exists, so a 7B tree (7.2 GB) fits
beside its KV pool on one 16 GB chip and a 47 GB Mixtral tree is born
already sharded (`shardings`: a tree of the same structure, given by the
system adapter from the program's own sharding rules).

The tree is plain dicts and arrays: a quantized weight is
``{"q": int8, "scale": float32}``. The reference reads this tree as it
is; `system.py` wraps it into the program's classes. Stacked layers lead
every per-layer leaf, as the program scans them.

Scales differ from channel to channel (0.75..1.25 of the fan-in scale), so
a scale applied along the wrong axis changes the logits and is caught by
the comparison that decides `correct`.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp


def model_dims(cfg: Dict[str, Any]) -> Dict[str, int]:
    """The sizes a builder needs, from the published keys."""
    d = int(cfg["hidden_size"])
    h = int(cfg["num_attention_heads"])
    return {
        "D": d,
        "H": h,
        "KH": int(cfg["num_key_value_heads"]),
        "hd": int(cfg.get("head_dim") or d // h),
        "M": int(cfg["intermediate_size"]),
        "V": int(cfg["vocab_size"]),
        "L": int(cfg["num_hidden_layers"]),
        "E": int(cfg.get("num_local_experts", 0)),
        "K": int(cfg.get("num_experts_per_tok", 0)),
    }


def leaf_table(cfg: Dict[str, Any]) -> Dict[str, Tuple[tuple, tuple, int]]:
    """name -> (shape, contracting dims, fan-in). Empty contracting dims
    mean the leaf stays bfloat16. Per-layer leaves are "layers/<name>"."""
    s = model_dims(cfg)
    D, H, KH, hd, M, V, L, E = (s[k] for k in "D H KH hd M V L E".split())
    t: Dict[str, Tuple[tuple, tuple, int]] = {
        "tok_embed": ((V, D), (), 1),
        "out_norm": ((D,), (), 0),
        "layers/attn_norm": ((L, D), (), 0),
        "layers/mlp_norm": ((L, D), (), 0),
        "layers/wq": ((L, D, H, hd), (1,), D),
        "layers/wk": ((L, D, KH, hd), (1,), D),
        "layers/wv": ((L, D, KH, hd), (1,), D),
        "layers/wo": ((L, H, hd, D), (1, 2), H * hd),
    }
    if E:
        t["layers/router"] = ((L, D, E), (), D)
        t["layers/w_gate"] = ((L, E, D, M), (2,), D)
        t["layers/w_up"] = ((L, E, D, M), (2,), D)
        t["layers/w_down"] = ((L, E, M, D), (2,), M)
    else:
        t["layers/w_gate"] = ((L, D, M), (1,), D)
        t["layers/w_up"] = ((L, D, M), (1,), D)
        t["layers/w_down"] = ((L, M, D), (1,), M)
    if not cfg.get("tie_word_embeddings", False):
        t["lm_head"] = ((D, V), (0,), D)
    return t


def _nest(flat: Dict[str, Any]) -> Dict[str, Any]:
    out: Dict[str, Any] = {"layers": {}}
    for name, v in flat.items():
        if name.startswith("layers/"):
            out["layers"][name.split("/", 1)[1]] = v
        else:
            out[name] = v
    return out


def tree_shapes(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The tree as ShapeDtypeStructs (for shardings and ahead-of-time
    compiles)."""
    flat = {}
    for name, (shape, contr, _) in leaf_table(cfg).items():
        if not contr:
            flat[name] = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
        else:
            sshape = tuple(1 if i in contr else n for i, n in enumerate(shape))
            flat[name] = {
                "q": jax.ShapeDtypeStruct(shape, jnp.int8),
                "scale": jax.ShapeDtypeStruct(sshape, jnp.float32),
            }
    return _nest(flat)


def seed_key(seed: int) -> jax.Array:
    """A key from any whole number (the driver's seeds pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(
        jax.random.key(seed % (2**31 - 1)), seed // (2**31 - 1)
    )


def _int8_values(key, shape):
    """Uniform int8 in [-127, 127], stacked layer by layer so that the
    generator's 32-bit words never exist for more than one layer."""

    def one(k):
        bits = jax.random.bits(k, shape[1:], jnp.uint8)
        return jnp.maximum(
            jax.lax.bitcast_convert_type(bits, jnp.int8), jnp.int8(-127)
        )

    return jax.lax.map(one, jax.random.split(key, shape[0]))


def _make(cfg_items: tuple, key):
    cfg = dict(cfg_items)
    flat = {}
    for i, (name, (shape, contr, fan_in)) in enumerate(
        sorted(leaf_table(cfg).items())
    ):
        k = jax.random.fold_in(key, i)
        if name.endswith("norm"):
            # Near one, not all equal: a norm weight left out would show.
            flat[name] = (
                1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)
            ).astype(jnp.bfloat16)
        elif not contr:
            flat[name] = (
                jax.random.normal(k, shape, jnp.float32) * fan_in ** -0.5
            ).astype(jnp.bfloat16)
        else:
            kq, ks = jax.random.split(k)
            if len(shape) > 2:
                q = _int8_values(kq, shape)
            else:
                q = _int8_values(kq, (1,) + shape)[0]
            sshape = tuple(1 if j in contr else n for j, n in enumerate(shape))
            # uniform int8 has a standard deviation of 127/sqrt(3)
            base = (3.0 ** 0.5 / 127.0) * fan_in ** -0.5
            scale = base * (
                0.75 + 0.5 * jax.random.uniform(ks, sshape, jnp.float32)
            )
            flat[name] = {"q": q, "scale": scale}
    return _nest(flat)


def _hashable(cfg: Dict[str, Any]) -> tuple:
    keys = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "head_dim", "intermediate_size", "vocab_size",
            "num_hidden_layers", "num_local_experts", "num_experts_per_tok",
            "tie_word_embeddings")
    return tuple((k, cfg[k]) for k in keys if k in cfg)


def make_weights(cfg: Dict[str, Any], seed: int,
                 shardings: Optional[Any] = None) -> Dict[str, Any]:
    """The whole tree in one jitted call; `shardings` places each leaf."""
    fn = jax.jit(_make, static_argnums=0, out_shardings=shardings)
    return fn(_hashable(cfg), seed_key(seed))
