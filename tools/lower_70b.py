"""Lower the north-star serving step — Llama-2-70B int8 decode over a
16-device mesh — without materializing a single weight byte.

Run standalone (the driver-style proof at v5e-16 scale):
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=16 \
        python tools/lower_70b.py [tensor=16 | data=2,tensor=8]
Also invoked by tests/test_70b_sharding.py as a subprocess.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(axes_arg: str = "tensor=16") -> None:
    # This is a CPU-only lowering.
    import os

    os.environ.setdefault("JAX_PLATFORMS", "cpu")

    import jax
    import jax.numpy as jnp

    from substratus_tpu.models import llama
    from substratus_tpu.ops.quant import QTensor
    from substratus_tpu.parallel.mesh import build_mesh
    from substratus_tpu.parallel.sharding import SERVE_RULES, sharding_tree

    axes = {
        k: int(v) for k, v in
        (pair.split("=") for pair in axes_arg.split(","))
    }
    cfg = llama.CONFIGS["llama2-70b"]
    mesh = build_mesh(**axes)

    # Abstract int8 param tree (QTensor of ShapeDtypeStructs), then the
    # SAME sharding construction the serving engine uses (sharding_tree:
    # logical rules + shape-aware legalization — e.g. the 8 GQA kv heads
    # replicate over a 16-way tensor axis instead of erroring).
    contracting = llama.quant_contracting(cfg)
    shapes = jax.eval_shape(lambda k: llama.init_params(cfg, k),
                            jax.random.key(0))

    def qstruct(struct, contr):
        if not contr:
            return jax.ShapeDtypeStruct(struct.shape, cfg.dtype)
        scale_shape = tuple(
            1 if i in contr else d for i, d in enumerate(struct.shape)
        )
        return QTensor(
            q=jax.ShapeDtypeStruct(struct.shape, jnp.int8),
            scale=jax.ShapeDtypeStruct(scale_shape, jnp.float32),
        )

    leaves, treedef = jax.tree.flatten(shapes)
    contr = treedef.flatten_up_to(contracting)
    qstructs = jax.tree.unflatten(
        treedef, [qstruct(s, c) for s, c in zip(leaves, contr)]
    )
    shardings = sharding_tree(
        qstructs, mesh, llama.param_logical_axes(cfg), SERVE_RULES
    )
    params = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        qstructs, shardings,
    )

    batch, cache_len = 16, 512
    cache = jax.eval_shape(
        lambda: llama.init_cache(cfg, batch, cache_len, dtype=jnp.int8)
    )
    tokens = jax.ShapeDtypeStruct((batch,), jnp.int32)
    positions = jax.ShapeDtypeStruct((batch,), jnp.int32)

    with jax.set_mesh(mesh):
        lowered = jax.jit(
            llama.decode_step, static_argnames=("cfg",),
            donate_argnames=("cache",),
        ).lower(params, cache, tokens, positions, cfg)
    text = lowered.as_text()
    # .lower() emits pre-partitioning StableHLO: collectives appear only
    # after SPMD partitioning, so assert the sharding annotations instead
    # (the partitioner turns these into all-reduces over "tensor").
    assert "mhlo.sharding" in text or "sdy.sharding" in text, (
        "lowered module carries no sharding annotations"
    )
    n_sharded = text.count("mhlo.sharding") + text.count("sdy.sharding")
    print(f"LOWER_OK mesh={dict(zip(mesh.axis_names, mesh.devices.shape))} "
          f"sharding_annotations={n_sharded}")


if __name__ == "__main__":
    main(sys.argv[1] if len(sys.argv) > 1 else "tensor=16")
