"""Execute the north-star 70B serving shardings for real on a virtual mesh.

Runs the ACTUAL Engine — paged KV, chunked prefill, prefix cache, and
speculative decoding all on — over 16 virtual CPU devices, on a scaled-down
config that keeps Llama-2-70B's exact axis structure (64 q heads, 8 kv
heads, GQA group 8 — the tensor>8 regime where kv projections replicate
while q/mlp shard, engine.py sharding constraint). Greedy tokens must match
the single-device engine bit-for-bit for every mesh in the matrix:

    tensor=16  and  data=2,tensor=8   (the BASELINE.json v5e-16 layouts)

Usage (also invoked by tests/test_sharded_serving.py as a subprocess):
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=16 \
        python tools/serve_70b_cpu.py
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def main() -> None:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    import jax.numpy as jnp

    from substratus_tpu.models import llama
    from substratus_tpu.parallel.mesh import build_mesh
    from substratus_tpu.serve.engine import Engine, EngineConfig

    n = len(jax.devices())
    assert n >= 16, f"need 16 virtual devices, got {n}"

    # ONE definition of the north-star shape (70B axis structure at toy
    # width, engine knobs, prompt set) shared with the multi-host proof
    # so the two token-exactness stories can never de-synchronize.
    from serve_70b_multihost import PROMPTS, engine_config, scaled_70b_cfg

    cfg = scaled_70b_cfg()
    params = llama.init_params(cfg, jax.random.key(0))
    draft_cfg = cfg.replace(n_layers=1)
    draft_params = llama.init_params(draft_cfg, jax.random.key(1))

    prompts = PROMPTS

    def run(mesh=None, run_params=params, draft=True):
        eng = Engine(
            cfg, run_params, engine_config(), mesh=mesh,
            draft=(draft_cfg, draft_params) if draft else None,
        )
        eng.start()
        try:
            return [
                eng.generate(p, max_tokens=8, temperature=0.0)
                for p in prompts
            ]
        finally:
            eng.stop()

    print("single-device reference...", flush=True)
    want = run()
    assert all(len(t) > 0 for t in want), want

    for axes in ({"tensor": 16}, {"data": 2, "tensor": 8}):
        print(f"mesh {axes}...", flush=True)
        mesh = build_mesh(**axes)
        got = run(mesh)
        assert got == want, (axes, got, want)
        # The point of TP: weights are actually sharded over the tensor
        # axis (q/mlp), kv replicates when tensor > KH.
        eng = Engine(
            cfg, params, engine_config(), mesh=mesh,
            draft=(draft_cfg, draft_params),
        )
        wq_spec = str(eng.params["layers"]["wq"].sharding.spec)
        assert "tensor" in wq_spec, wq_spec
        tp = axes["tensor"]
        wk_spec = str(eng.params["layers"]["wk"].sharding.spec)
        if tp > cfg.n_kv_heads:
            assert "tensor" not in wk_spec, wk_spec  # replicated, by fit()
        else:
            assert "tensor" in wk_spec, wk_spec
        print(f"mesh {axes}: tokens match single-device; wq={wq_spec}",
              flush=True)

    # The HEADLINE configuration: int4 weights over tensor=16 — the
    # reference's 4-bit 70B serving (examples/llama2-70b/server.yaml:10)
    # at this framework's target topology. Same exactness bar, this time
    # vs the single-device int4 engine (prompt-lookup proposer: the int4
    # story needs no second model resident).
    from substratus_tpu.ops import quant4
    from substratus_tpu.ops.quant4 import quantize4_params, set_q4_impl

    qparams = quantize4_params(params, llama.quant_contracting(cfg))

    prev_impl = quant4._FORCE_IMPL
    set_q4_impl("xla")  # the SPMD-shardable lowering serve/main pins
    try:
        print("int4 single-device reference...", flush=True)
        want_q4 = run(run_params=qparams, draft=False)
        assert all(len(t) > 0 for t in want_q4), want_q4
        print("int4 mesh tensor=16...", flush=True)
        mesh16 = build_mesh(tensor=16)
        got_q4 = run(mesh16, run_params=qparams, draft=False)
        assert got_q4 == want_q4, (got_q4, want_q4)
        # parity alone holds even if nothing sharded — prove the packed
        # nibbles actually live on the tensor axis
        eng = Engine(cfg, qparams, engine_config(), mesh=mesh16)
        q4_spec = str(eng.params["layers"]["wq"].packed.sharding.spec)
        assert "tensor" in q4_spec, q4_spec
    finally:
        set_q4_impl(prev_impl)
    print(f"int4 @ tensor=16: tokens match single-device; wq.packed="
          f"{q4_spec}", flush=True)

    print("serve_70b_cpu ok: north-star shardings execute with "
          "paged KV + chunked prefill + prefix cache + spec decode, "
          "int8 AND int4", flush=True)


if __name__ == "__main__":
    main()
