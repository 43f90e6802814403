"""Sharded serving: the engine over a (data x tensor) mesh must produce
exactly the greedy tokens of the single-device engine — multi-chip serving
is a layout change, never a semantics change."""
import jax
import jax.numpy as jnp
import pytest

from substratus_tpu.models import llama
from substratus_tpu.parallel.mesh import build_mesh
from substratus_tpu.serve.engine import Engine, EngineConfig


@pytest.fixture(scope="module")
def setup():
    cfg = llama.CONFIGS["tiny"].replace(vocab_size=258, dtype=jnp.float32)
    params = llama.init_params(cfg, jax.random.key(0))
    return cfg, params


def _run(engine, prompts):
    engine.start()
    try:
        return [
            engine.generate(p, max_tokens=6, temperature=0.0) for p in prompts
        ]
    finally:
        engine.stop()


def test_tensor_parallel_engine_matches_single_device(setup):
    cfg, params = setup
    prompts = [[256, 5, 6, 7], [256, 70, 71]]
    ec = lambda: EngineConfig(max_batch=4, max_seq_len=64, eos_token_id=257)

    single = _run(Engine(cfg, params, ec()), prompts)

    mesh = build_mesh(data=2, tensor=2, fsdp=2)  # fsdp unused by SERVE_RULES
    sharded = _run(Engine(cfg, params, ec(), mesh=mesh), prompts)
    assert sharded == single, (sharded, single)

    # Sanity: weights actually ended up tensor-sharded.
    spec = (
        Engine(cfg, params, ec(), mesh=mesh).params["layers"]["wq"].sharding.spec
    )
    assert "tensor" in str(spec), spec


def test_tensor_parallel_int4_engine_matches_single_device(setup):
    """int4 weights through a (data x tensor) mesh — the 70B-serving
    headline configuration — must be token-exact vs the single-device
    int4 engine. Uses the SPMD-shardable XLA lowering, exactly as
    serve/main pins it for sharded serving (ops/quant4.py)."""
    from substratus_tpu.ops import quant4
    from substratus_tpu.ops.quant4 import quantize4_params, set_q4_impl

    cfg, params = setup
    qparams = quantize4_params(params, llama.quant_contracting(cfg))
    prompts = [[256, 5, 6, 7], [256, 70, 71]]
    ec = lambda: EngineConfig(max_batch=4, max_seq_len=64, eos_token_id=257)

    prev_impl = quant4._FORCE_IMPL
    set_q4_impl("xla")
    try:
        single = _run(Engine(cfg, qparams, ec()), prompts)
        mesh = build_mesh(data=2, tensor=2, fsdp=2)
        sharded = _run(Engine(cfg, qparams, ec(), mesh=mesh), prompts)
    finally:
        set_q4_impl(prev_impl)
    assert sharded == single, (sharded, single)

    # Sanity: the packed int4 weights themselves are tensor-sharded.
    eng = Engine(cfg, qparams, ec(), mesh=mesh)
    spec = eng.params["layers"]["wq"].packed.sharding.spec
    assert "tensor" in str(spec), spec


def test_tensor_parallel_int4_pallas_kernel_under_mesh(
    setup, pallas_interpret
):
    """Round-5 closure of the 'kernels are inert under sharding' gap:
    with the custom_partitioning rule, q4einsum keeps the Pallas
    unpack-dequant kernel per-shard under a (data x tensor) mesh
    (interpret mode asked for by the fixture) — token-exact vs the
    single-device XLA engine. kernel_trace_count proves the kernel was
    actually lowered, not silently swapped for the fallback."""
    from substratus_tpu.ops import quant4
    from substratus_tpu.ops.quant4 import (
        kernel_trace_count, quantize4_params, set_q4_impl,
    )

    # Dims sized so the PER-SHARD projections fit the kernel tiling at
    # tensor=2 (local N a multiple of 128, local C covering whole scale
    # groups); the tiny config's shards are too small and would silently
    # exercise only the fallback.
    cfg = llama.CONFIGS["tiny"].replace(
        vocab_size=258, dtype=jnp.float32, dim=256, n_heads=4,
        n_kv_heads=4, head_dim=64, hidden_dim=512,
    )
    params = llama.init_params(cfg, jax.random.key(0))
    qparams = quantize4_params(params, llama.quant_contracting(cfg))
    prompts = [[256, 5, 6, 7], [256, 70, 71]]
    ec = lambda: EngineConfig(max_batch=8, max_seq_len=64, eos_token_id=257)

    prev_impl = quant4._FORCE_IMPL
    set_q4_impl("xla")
    try:
        single = _run(Engine(cfg, qparams, ec()), prompts)
        set_q4_impl("pallas")
        before = kernel_trace_count()
        mesh = build_mesh(data=2, tensor=2, fsdp=2)
        sharded = _run(Engine(cfg, qparams, ec(), mesh=mesh), prompts)
    finally:
        set_q4_impl(prev_impl)
    assert kernel_trace_count() > before  # the kernel really lowered
    assert sharded == single, (sharded, single)


def test_north_star_70b_structure_engine_matrix():
    """Execute the ACTUAL engine — paged KV, chunked prefill, prefix
    cache, speculative decoding — over a 16-device virtual mesh at
    tensor=16 and data=2,tensor=8, on a scaled config keeping 70B's exact
    axis structure (H=64, KH=8, GQA 8). Exact-token parity vs
    single-device is asserted inside tools/serve_70b_cpu.py; a 16-device
    mesh needs its own process (conftest pins this one to 8)."""
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "")
        .replace("--xla_force_host_platform_device_count=8", "")
        + " --xla_force_host_platform_device_count=16"
    )
    proc = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "serve_70b_cpu.py")],
        capture_output=True, text=True, timeout=900, env=env,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "serve_70b_cpu ok" in proc.stdout, proc.stdout


def test_sequence_parallel_serving_long_prompt(setup):
    """Serving-side context parallelism (round-5, VERDICT #6): with a
    "sequence" axis in the serving mesh the dense KV cache shards its
    sequence dim (serve_rules_for), so a prompt LONGER than one chip's
    cache share still serves — token-exact vs the single-device engine.
    Here S=96 over sequence=4 means 24 rows per chip; the 70-token
    prompt could never fit one shard."""
    cfg, params = setup
    long_prompt = [256] + [(3 + i * 7) % 250 for i in range(69)]  # 70 toks
    short_prompt = [256, 5, 6, 7]
    ec = lambda: EngineConfig(
        max_batch=4, max_seq_len=96, max_prefill_len=32,  # force chunking
        eos_token_id=257, kv_layout="dense",
    )

    single = _run(Engine(cfg, params, ec()), [long_prompt, short_prompt])

    mesh = build_mesh(data=1, sequence=4, tensor=2)
    eng = Engine(cfg, params, ec(), mesh=mesh)
    # the cache really is sequence-sharded (axis 3 of [L, B, KH, S, D])
    spec = str(eng.cache["k"].sharding.spec)
    assert "sequence" in spec, spec
    sharded = _run(eng, [long_prompt, short_prompt])
    assert sharded == single, (sharded, single)


@pytest.mark.parametrize("tensor,stored", [(2, (2, 128)), (4, (4, 64))])
def test_a_tensor_mesh_splits_whole_pool_rows(tensor, stored):
    """A bfloat16 pool of 4 KV heads of 64 stores them two to a row of 128
    where the `tensor` axis divides the two rows a token (one row a device),
    and a head a row where it does not (four devices: a head a device, as
    before PR 35): the pool stays split over its KV heads either way, and
    the engine serves from it."""
    cfg = llama.CONFIGS["tiny"].replace(
        vocab_size=258, dim=512, n_heads=8, n_kv_heads=4)
    assert (cfg.head_size, cfg.dtype) == (64, jnp.bfloat16)
    params = llama.init_params(cfg, jax.random.key(0))
    mesh = build_mesh(data=8 // tensor, tensor=tensor)
    eng = Engine(cfg, params, EngineConfig(
        max_batch=4, max_seq_len=64, eos_token_id=257, page_size=4),
        mesh=mesh)
    k = eng.cache["k"]
    assert k.shape[3:] == stored
    assert k.sharding.spec[3] == "tensor"
    assert k.sharding.shard_shape(k.shape)[3] == stored[0] // tensor
    outs = _run(eng, [[256, 5, 6, 7], list(range(1, 40))])
    assert all(len(ids) == 6 for ids in outs)
