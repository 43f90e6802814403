"""Seeded weights, born on the device in the type they are served in.

One jitted call makes the whole tree from `--seed`, leaf by leaf as the
configuration's family lists them (`benchmarks/families/<family>.py::
leaf_table`: path -> `Leaf`): int8 values with one float32 scale per output
channel for every matmul weight, bfloat16 for embeddings, norms and
routers, float32 for a small bias. Nothing is made on the host and no
bfloat16 copy of a matmul weight ever exists, so a 7B tree (7.2 GB) fits
beside its KV pool on one 16 GB chip and a 47 GB Mixtral tree is born
already sharded (`shardings`: a tree of the same structure, given by the
system adapter from the program's own sharding rules).

The tree is plain dicts and arrays, nested by the leaves' paths: a
quantized weight is ``{"q": int8, "scale": float32}``. The reference reads
this tree as it is; `system.py` wraps it into the program's classes. A
stack's layer dim leads its leaves, as the program scans them.

Scales differ from channel to channel (0.75..1.25 of the fan-in scale), so
a scale applied along the wrong axis changes the logits and is caught by
the comparison that decides `correct`.
"""
from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp


class Leaf(NamedTuple):
    """One entry of a family's leaf table. The entry, not the path, says
    what the leaf is."""

    shape: Tuple[int, ...]
    contracting: Tuple[int, ...]  # dims a matmul sums over; () unless int8
    fan_in: int
    # "int8": int8 values, a float32 scale per output channel
    # "norm": bfloat16 near one; "normal": bfloat16, fan-in normal
    # "bias": float32, small
    kind: str
    stacked: bool = False  # the leading dim counts a stack's layers


def scale_shape(leaf: Leaf) -> Tuple[int, ...]:
    return tuple(1 if i in leaf.contracting else n
                 for i, n in enumerate(leaf.shape))


def nest(flat: Dict[str, Any]) -> Dict[str, Any]:
    """{"a/b/c": v} -> {"a": {"b": {"c": v}}}: paths of any depth."""
    out: Dict[str, Any] = {}
    for path, v in flat.items():
        *groups, name = path.split("/")
        node = out
        for g in groups:
            node = node.setdefault(g, {})
        node[name] = v
    return out


def at(tree: Dict[str, Any], path: str):
    """The leaf of a nested tree at a table's path."""
    for part in path.split("/"):
        tree = tree[part]
    return tree


def tree_shapes(table: Dict[str, Leaf]) -> Dict[str, Any]:
    """The tree as ShapeDtypeStructs (for shardings and ahead-of-time
    compiles)."""
    flat: Dict[str, Any] = {}
    for path, leaf in table.items():
        if leaf.kind == "int8":
            flat[path] = {
                "q": jax.ShapeDtypeStruct(leaf.shape, jnp.int8),
                "scale": jax.ShapeDtypeStruct(scale_shape(leaf), jnp.float32),
            }
        else:
            flat[path] = jax.ShapeDtypeStruct(
                leaf.shape, jnp.float32 if leaf.kind == "bias" else jnp.bfloat16)
    return nest(flat)


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


def _make(table_items: tuple, key):
    """Every leaf from its own key: the i-th path in sorted order takes
    fold_in(key, i)."""
    flat = {}
    for i, (path, leaf) in enumerate(table_items):
        k = jax.random.fold_in(key, i)
        shape = leaf.shape
        if leaf.kind == "norm":
            # Near one, not all equal: a norm weight left out would show.
            flat[path] = (
                1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)
            ).astype(jnp.bfloat16)
        elif leaf.kind == "normal":
            flat[path] = (
                jax.random.normal(k, shape, jnp.float32) * leaf.fan_in ** -0.5
            ).astype(jnp.bfloat16)
        elif leaf.kind == "bias":
            flat[path] = 0.01 * jax.random.normal(k, shape, jnp.float32)
        elif leaf.kind == "int8":
            kq, ks = jax.random.split(k)
            if len(shape) > 2:
                q = _int8_values(kq, shape)
            else:
                q = _int8_values(kq, (1,) + shape)[0]
            # uniform int8 has a standard deviation of 127/sqrt(3)
            base = (3.0 ** 0.5 / 127.0) * leaf.fan_in ** -0.5
            scale = base * (0.75 + 0.5 * jax.random.uniform(
                ks, scale_shape(leaf), jnp.float32))
            flat[path] = {"q": q, "scale": scale}
        else:
            raise ValueError(f"leaf {path}: unknown kind {leaf.kind!r}")
    return nest(flat)


def make_weights(table: Dict[str, Leaf], seed: int,
                 shardings: Optional[Any] = None) -> Dict[str, Any]:
    """The whole tree in one jitted call; `shardings` places each leaf. The
    table itself is the call's static key, so nothing a family reads can
    be left out of it."""
    fn = jax.jit(_make, static_argnums=0, out_shardings=shardings)
    return fn(tuple(sorted(table.items())), seed_key(seed))
