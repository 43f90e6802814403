"""Flash-decode kernel: fused KV-cache-write + attention parity.

The fused kernel (ops/fused_decode.py) must produce EXACTLY what the
unfused path (XLA scatter + decode_attention) produces: same attention
output, same updated caches — int8 and full-precision, MHA and GQA,
pos = 0 (no history) through pos = S-1 (full cache). Runs in interpret
mode on CPU; the Mosaic lowering is validated on-chip by
tools/fused_decode_onchip.py.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from substratus_tpu.ops.decode_attention import (
    decode_attention, update_cache_and_attend,
)
from substratus_tpu.ops.fused_decode import fused_decode_attention
from substratus_tpu.ops.quant import quantize_kv


def _rand(key, *shape):
    return jax.random.normal(key, shape, jnp.float32)


def _scatter(cache, fresh, positions):
    b, kh = cache.shape[:2]
    bidx = jnp.arange(b)[:, None, None]
    hidx = jnp.arange(kh)[None, :, None]
    sidx = positions[:, None, None]
    return cache.at[bidx, hidx, sidx].set(fresh)


@pytest.mark.parametrize("kh,h", [(4, 4), (2, 8)])  # MHA, GQA(g=4)
def test_fused_matches_unfused_fp(kh, h):
    S, D, B = 128, 32, 3
    ks = jax.random.split(jax.random.key(0), 5)
    q = _rand(ks[0], B, 1, h, D)
    ck, cv = _rand(ks[1], B, kh, S, D), _rand(ks[2], B, kh, S, D)
    nk, nv = _rand(ks[3], B, kh, 1, D), _rand(ks[4], B, kh, 1, D)
    positions = jnp.array([0, 77, S - 1], jnp.int32)  # edges + middle

    ck2, cv2 = _scatter(ck, nk, positions), _scatter(cv, nv, positions)
    ref = decode_attention(q, ck2, cv2, positions, impl="xla")
    attn, cko, cvo = fused_decode_attention(
        q, nk, nv, ck, cv, positions, block_s=32, interpret=True
    )
    np.testing.assert_allclose(attn, ref, atol=2e-6)
    np.testing.assert_array_equal(cko, ck2)
    np.testing.assert_array_equal(cvo, cv2)


def test_fused_matches_unfused_int8():
    B, h, kh, S, D = 2, 8, 4, 256, 64
    ks = jax.random.split(jax.random.key(1), 5)
    q = _rand(ks[0], B, 1, h, D)
    ck, cks = quantize_kv(_rand(ks[1], B, kh, S, D))
    cv, cvs = quantize_kv(_rand(ks[2], B, kh, S, D))
    nk, nks = quantize_kv(_rand(ks[3], B, kh, 1, D))
    nv, nvs = quantize_kv(_rand(ks[4], B, kh, 1, D))
    cks, cvs, nks, nvs = cks[..., 0], cvs[..., 0], nks[..., 0], nvs[..., 0]
    positions = jnp.array([13, 200], jnp.int32)

    ck2, cv2 = _scatter(ck, nk, positions), _scatter(cv, nv, positions)
    bidx = jnp.arange(B)[:, None, None]
    hidx = jnp.arange(kh)[None, :, None]
    sidx = positions[:, None, None]
    cks2 = cks.at[bidx, hidx, sidx].set(nks)
    cvs2 = cvs.at[bidx, hidx, sidx].set(nvs)
    ref = decode_attention(q, ck2, cv2, positions, cks2, cvs2, impl="xla")
    attn, cko, cvo = fused_decode_attention(
        q, nk, nv, ck, cv, positions, nks, nvs, cks2, cvs2, interpret=True
    )
    np.testing.assert_allclose(attn, ref, atol=2e-6)
    np.testing.assert_array_equal(cko, ck2)
    np.testing.assert_array_equal(cvo, cv2)


def test_update_cache_and_attend_fused_path(pallas_interpret):
    """The impl="fused" branch of the shared cached-attention entry point
    returns the same attn + cache dict as impl="xla", int8 cache."""
    B, h, kh, S, D = 2, 4, 2, 64, 16
    ks = jax.random.split(jax.random.key(2), 4)
    q = _rand(ks[0], B, 1, h, D)
    kk = _rand(ks[1], B, 1, kh, D)
    vv = _rand(ks[2], B, 1, kh, D)
    cache = {
        "k": jnp.zeros((B, kh, S, D), jnp.int8),
        "v": jnp.zeros((B, kh, S, D), jnp.int8),
        "k_scale": jnp.ones((B, kh, S), jnp.float32),
        "v_scale": jnp.ones((B, kh, S), jnp.float32),
    }
    # seed some history so the loop path runs
    hist_k, hks = quantize_kv(_rand(ks[3], B, kh, S, D))
    cache["k"] = hist_k
    cache["k_scale"] = hks[..., 0]
    positions = jnp.array([[5], [37]], jnp.int32)

    a_ref, kv_ref = update_cache_and_attend(
        cache, q, kk, vv, positions, impl="xla"
    )
    a_fused, kv_fused = update_cache_and_attend(
        cache, q, kk, vv, positions, impl="fused"
    )
    np.testing.assert_allclose(a_fused, a_ref, atol=2e-6)
    for key in kv_ref:
        np.testing.assert_array_equal(kv_fused[key], kv_ref[key])


def test_resolve_kv_layout_routes_fused_to_dense():
    """serve/main: the fused kernel lives on the dense slot-cache path —
    asking for it must select that layout (llama defaults to paged, which
    would silently bypass the kernel), and fused+paged is a rejected
    contradiction."""
    from substratus_tpu.serve.main import resolve_kv_layout

    assert resolve_kv_layout({}) == "auto"
    assert resolve_kv_layout({"decode_attn_impl": "fused"}) == "dense"
    assert resolve_kv_layout(
        {"decode_attn_impl": "fused", "kv_layout": "dense"}
    ) == "dense"
    assert resolve_kv_layout({"kv_layout": "paged"}) == "paged"
    with pytest.raises(SystemExit):
        resolve_kv_layout(
            {"decode_attn_impl": "fused", "kv_layout": "paged"}
        )


def test_fused_decode_step_through_model(pallas_interpret):
    """Greedy decode logits through the llama debug model are identical
    with decode_attn_impl='fused' vs 'xla' (the end-to-end surface the
    serving engine drives)."""
    from substratus_tpu.models import llama

    cfg = llama.CONFIGS["tiny"].replace(decode_attn_impl="xla")
    params = llama.init_params(cfg, jax.random.key(0))
    prompt = [1, 5, 9, 3]
    tokens = jnp.asarray([prompt], jnp.int32)
    logits, kv = llama.forward(params, tokens, cfg)

    from substratus_tpu.ops.kvcache import insert_prefill

    outs = {}
    for impl in ("xla", "fused"):
        c = cfg.replace(decode_attn_impl=impl)
        cache = llama.init_cache(c, 1, 64)
        cache = insert_prefill(cache, kv, len(prompt))
        lg, cache2 = llama.decode_step(
            params, cache,
            jnp.asarray([2], jnp.int32),
            jnp.asarray([len(prompt)], jnp.int32), c,
        )
        lg2, _ = llama.decode_step(
            params, cache2,
            jnp.asarray([7], jnp.int32),
            jnp.asarray([len(prompt) + 1], jnp.int32), c,
        )
        outs[impl] = (lg, lg2)
    # bf16 model: blocked online softmax reorders the accumulation, so
    # logits agree to bf16 noise (and greedy decoding is unchanged)
    for step in (0, 1):
        np.testing.assert_allclose(
            outs["fused"][step], outs["xla"][step], atol=0.06
        )
        assert int(outs["fused"][step].argmax()) == int(
            outs["xla"][step].argmax()
        )


def test_out_of_range_position_clamps_no_oob():
    """ADVICE r4: inactive engine slots used to drift positions past the
    cache length; the XLA scatter dropped OOB updates silently but the
    fused kernel's DMA write would corrupt a neighbouring row. The
    wrapper now clamps, so a pos >= S behaves exactly like pos = S-1 and
    never touches another slot/head's rows."""
    S, D, B, kh, h = 64, 32, 3, 2, 4
    ks = jax.random.split(jax.random.key(7), 5)
    q = _rand(ks[0], B, 1, h, D)
    ck, cv = _rand(ks[1], B, kh, S, D), _rand(ks[2], B, kh, S, D)
    nk, nv = _rand(ks[3], B, kh, 1, D), _rand(ks[4], B, kh, 1, D)
    drifted = jnp.array([5, S + 17, 10 * S], jnp.int32)  # slots 1,2 drifted
    clamped = jnp.minimum(drifted, S - 1)

    ck2, cv2 = _scatter(ck, nk, clamped), _scatter(cv, nv, clamped)
    ref = decode_attention(q, ck2, cv2, clamped, impl="xla")
    attn, cko, cvo = fused_decode_attention(
        q, nk, nv, ck, cv, drifted, block_s=32, interpret=True
    )
    np.testing.assert_allclose(attn, ref, atol=2e-6)
    np.testing.assert_array_equal(cko, ck2)
    np.testing.assert_array_equal(cvo, cv2)


def test_block_fit_halves_for_non_pow2_cache():
    """Non-power-of-two cache lengths must still pick a lane-friendly
    block (halve-until-divides), not walk down by ones to a misaligned
    odd size."""
    S, D, B, kh, h = 96, 32, 1, 2, 2  # 96: 64 -> 32 divides
    ks = jax.random.split(jax.random.key(9), 5)
    q = _rand(ks[0], B, 1, h, D)
    ck, cv = _rand(ks[1], B, kh, S, D), _rand(ks[2], B, kh, S, D)
    nk, nv = _rand(ks[3], B, kh, 1, D), _rand(ks[4], B, kh, 1, D)
    positions = jnp.array([41], jnp.int32)
    ck2, cv2 = _scatter(ck, nk, positions), _scatter(cv, nv, positions)
    ref = decode_attention(q, ck2, cv2, positions, impl="xla")
    attn, cko, cvo = fused_decode_attention(
        q, nk, nv, ck, cv, positions, block_s=64, interpret=True
    )
    np.testing.assert_allclose(attn, ref, atol=2e-6)
    np.testing.assert_array_equal(cko, ck2)
    np.testing.assert_array_equal(cvo, cv2)


def test_drifted_position_quantized_scale_and_row_agree(pallas_interpret):
    """Code-review r5: the position clamp must be shared by the scale
    scatters (XLA, caller side) and the k/v row write (inside the
    kernel). If they disagree, row S-1 of a quantized cache pairs fresh
    int8 data with a stale scale. A drifted position must produce
    exactly the state of a position clamped to S-1."""
    B, h, kh, S, D = 2, 4, 2, 64, 16
    ks = jax.random.split(jax.random.key(11), 4)
    q = _rand(ks[0], B, 1, h, D)
    kk = _rand(ks[1], B, 1, kh, D)
    vv = _rand(ks[2], B, 1, kh, D)
    hist_k, hks = quantize_kv(_rand(ks[3], B, kh, S, D))
    cache = {
        "k": hist_k,
        "v": jnp.zeros((B, kh, S, D), jnp.int8),
        "k_scale": hks[..., 0],
        "v_scale": jnp.ones((B, kh, S), jnp.float32),
    }
    drifted = jnp.array([[5], [S + 33]], jnp.int32)
    clamped = jnp.minimum(drifted, S - 1)

    a_ref, kv_ref = update_cache_and_attend(
        cache, q, kk, vv, clamped, impl="fused"
    )
    a_drift, kv_drift = update_cache_and_attend(
        cache, q, kk, vv, drifted, impl="fused"
    )
    np.testing.assert_allclose(a_drift, a_ref, atol=2e-6)
    for key in kv_ref:
        np.testing.assert_array_equal(kv_drift[key], kv_ref[key])


def test_fused_decode_engine_under_mesh(pallas_interpret):
    """Round-5: the fused kernel's custom_partitioning rule keeps it
    per-shard under a (data x tensor) serving mesh — the engine with
    kv_layout=dense + decode_attn_impl=fused over 4 devices must be
    token-exact vs the single-device xla engine (previously sharded
    serving force-pinned xla; serve/main.py r4)."""
    from substratus_tpu.models import llama
    from substratus_tpu.parallel.mesh import build_mesh
    from substratus_tpu.serve.engine import Engine, EngineConfig

    cfg = llama.CONFIGS["tiny"].replace(
        vocab_size=258, dtype=jnp.float32, decode_attn_impl="xla"
    )
    params = llama.init_params(cfg, jax.random.key(0))
    prompts = [[256, 5, 6, 7], [256, 70, 71]]
    ec = lambda: EngineConfig(
        max_batch=4, max_seq_len=64, eos_token_id=257, kv_layout="dense"
    )

    def run(engine):
        engine.start()
        try:
            return [
                engine.generate(p, max_tokens=6, temperature=0.0)
                for p in prompts
            ]
        finally:
            engine.stop()

    single = run(Engine(cfg, params, ec()))
    fused_cfg = cfg.replace(decode_attn_impl="fused")
    mesh = build_mesh(data=2, tensor=2, fsdp=2)
    sharded = run(Engine(fused_cfg, params, ec(), mesh=mesh))
    assert sharded == single, (sharded, single)
