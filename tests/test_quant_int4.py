"""int4 weight-only quantization (ops/quant4.py).

Parity path for the reference's 4-bit serving examples
(reference: examples/llama2-70b/server.yaml MODEL_LOAD_IN_4BIT,
examples/llama2-13b-chat-gguf 4-bit GGUF): pack/unpack exactness, einsum
parity against the dequantized oracle for every model projection shape,
Pallas kernel (interpret mode) vs the XLA lowering, and model-level
logits/greedy-decode agreement on the tiny llama config.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from substratus_tpu.models import llama
from substratus_tpu.ops.quant4 import (
    Q4Tensor,
    _matmul,
    q4einsum,
    quantize4,
    quantize4_params,
)


def test_pack_roundtrip_exact():
    """Values already representable in int4 survive quantize->dequant
    bit-exactly (scale absmax/7 with integer values <= 7)."""
    w = jax.random.randint(
        jax.random.key(0), (256, 32), -7, 8, jnp.int32
    ).astype(jnp.float32)
    qt = quantize4(w, (0,))
    assert qt.packed.dtype == jnp.uint8
    assert qt.packed.shape == (128, 32)
    assert qt.scale.shape == (2, 32)  # 256 / block(128) groups
    np.testing.assert_array_equal(np.asarray(qt.dequant(jnp.float32)),
                                  np.asarray(w))


def test_quant_error_bounded():
    """Group quantization error is bounded by scale/2 per element."""
    w = jax.random.normal(jax.random.key(1), (256, 16), jnp.float32)
    qt = quantize4(w, (0,))
    back = qt.dequant(jnp.float32)
    # Per-group bound: |err| <= scale/2 (round-to-nearest on [-8, 7]).
    scale_full = jnp.repeat(qt.scale, 128, axis=0)
    assert float(jnp.max(jnp.abs(back - w) / scale_full)) <= 0.5 + 1e-6


@pytest.mark.parametrize(
    "eq,xs,ws,contr",
    [
        ("bsd,dhk->bshk", (2, 3, 256), (256, 4, 8), (0,)),   # wq/wk/wv
        ("bshk,hkd->bsd", (2, 3, 4, 8), (4, 8, 256), (0, 1)),  # wo
        ("bsd,dm->bsm", (2, 3, 256), (256, 128), (0,)),      # gate/up
        ("bsm,md->bsd", (2, 3, 128), (128, 256), (0,)),      # down
        ("bsd,dv->bsv", (2, 3, 256), (256, 300), (0,)),      # lm_head
        ("bsd,edm->bsem", (2, 3, 256), (4, 256, 128), (1,)),  # MoE fallback
    ],
)
def test_q4einsum_matches_dequant(eq, xs, ws, contr):
    x = jax.random.normal(jax.random.key(2), xs, jnp.float32)
    w = jax.random.normal(jax.random.key(3), ws, jnp.float32) * 0.1
    qt = quantize4(w, contr)
    ref = jnp.einsum(eq, x, qt.dequant(jnp.float32))
    out = q4einsum(eq, x, qt, jnp.float32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_pallas_kernel_interpret_matches():
    """The Mosaic unpack-dequant matmul kernel (interpret mode on CPU)
    against the plain dequantized matmul."""
    x2 = jax.random.normal(jax.random.key(4), (24, 512), jnp.float32)
    w = jax.random.normal(jax.random.key(5), (512, 384), jnp.float32) * 0.1
    qt = quantize4(w, (0,))
    ref = x2 @ qt.dequant(jnp.float32)
    out = _matmul(x2, qt.packed, qt.scale, qt.block, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_scan_slices_stacked_leaves():
    """lax.scan slices the leading layer dim off packed and scale in
    lockstep (the negative pack_axis stays valid)."""
    w = jax.random.normal(jax.random.key(6), (3, 256, 4, 8), jnp.float32)
    qt = quantize4(w, (1,))
    x = jax.random.normal(jax.random.key(7), (2, 5, 256), jnp.float32)

    def body(c, lw):
        return c, q4einsum("bsd,dhk->bshk", c, lw, jnp.float32)

    _, ys = jax.lax.scan(body, x, qt)
    for i in range(3):
        one = Q4Tensor(qt.packed[i], qt.scale[i], qt.pack_axis, qt.block)
        ref = q4einsum("bsd,dhk->bshk", x, one, jnp.float32)
        np.testing.assert_allclose(np.asarray(ys[i]), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)


def test_int4_logits_close():
    """Model-level: int4 tracks dense argmax on the tiny config."""
    cfg = llama.CONFIGS["tiny"]
    params = llama.init_params(cfg, jax.random.key(0))
    qparams = quantize4_params(params, llama.quant_contracting(cfg))
    from substratus_tpu.ops.quant import is_quantized

    assert is_quantized(qparams)
    tokens = jax.random.randint(jax.random.key(1), (2, 12), 0, cfg.vocab_size)
    dense, _ = llama.forward(params, tokens, cfg)
    quant, _ = llama.forward(qparams, tokens, cfg)
    # 4-bit RTN is genuinely lossier than int8 (step is 18x larger), and a
    # tiny random-init model amplifies relative error because its logit
    # spread is near-flat — so the bar is argmax-mostly + top5-always
    # (measured on this seed: int4 agree 0.75 / in-top5 1.0 vs int8 0.96).
    agree = (dense.argmax(-1) == quant.argmax(-1)).mean()
    assert agree > 0.6, float(agree)
    top5 = jax.lax.top_k(dense, 5)[1]
    in5 = (quant.argmax(-1)[..., None] == top5).any(-1).mean()
    assert in5 > 0.95, float(in5)


def test_int4_decode_agrees_with_prefill_path():
    """Cached greedy decode under int4 weights matches the no-cache
    forward on the same tokens (the serving-correctness invariant)."""
    cfg = llama.CONFIGS["tiny"]
    params = llama.init_params(cfg, jax.random.key(0))
    qparams = quantize4_params(params, llama.quant_contracting(cfg))

    prompt = [1, 5, 9]
    cache = llama.init_cache(cfg, 1, 32)
    tokens = jnp.array([prompt], jnp.int32)
    logits, cache = llama.forward(
        params=qparams, tokens=tokens, cfg=cfg,
        positions=jnp.arange(3)[None], cache=cache,
    )
    toks = list(prompt)
    tok = logits[:, -1].argmax(-1).astype(jnp.int32)
    for i in range(5):
        toks.append(int(tok[0]))
        logits, cache = llama.decode_step(
            qparams, cache, tok, jnp.array([3 + i], jnp.int32), cfg
        )
        tok = logits.argmax(-1).astype(jnp.int32)
    toks.append(int(tok[0]))

    # Re-run the whole sequence through the no-cache path: the last
    # incremental decode logits must match the full forward's logits at
    # the same position (cache path == prefill path under int4).
    full, _ = llama.forward(qparams, jnp.array([toks], jnp.int32), cfg)
    np.testing.assert_allclose(
        np.asarray(full[0, len(toks) - 2]),
        np.asarray(logits[0]),
        rtol=2e-2, atol=2e-2,
    )


def test_int4_sharding_tree():
    """sharding_tree handles Q4Tensor leaves: packed and scale flatten in
    lockstep and mesh axes that no longer divide a child dim replicate."""
    from substratus_tpu.parallel.mesh import build_mesh
    from substratus_tpu.parallel.sharding import sharding_tree

    cfg = llama.CONFIGS["tiny"]
    params = llama.init_params(cfg, jax.random.key(0))
    qparams = quantize4_params(params, llama.quant_contracting(cfg))
    mesh = build_mesh(data=2, tensor=2, devices=jax.devices()[:4])
    tree = sharding_tree(qparams, mesh, llama.param_logical_axes(cfg))
    wq = tree["layers"]["wq"]
    assert isinstance(wq, Q4Tensor)
    # Leaf counts line up so device_put/jit can zip the trees.
    assert len(jax.tree.leaves(tree)) == len(jax.tree.leaves(qparams))


def test_int4_engine_end_to_end():
    """The serving engine runs int4 weights through prefill + continuous
    decode and produces the same greedy tokens as straight-line
    prefill+decode with the same quantized params."""
    from substratus_tpu.ops.kvcache import insert_prefill
    from substratus_tpu.serve.engine import Engine, EngineConfig

    cfg = llama.CONFIGS["tiny"].replace(vocab_size=258, dtype=jnp.float32)
    params = llama.init_params(cfg, jax.random.key(0))
    qparams = quantize4_params(params, llama.quant_contracting(cfg))
    eng = Engine(cfg, qparams,
                 EngineConfig(max_batch=2, max_seq_len=64, eos_token_id=257))
    eng.start()
    try:
        prompt = [256, 65, 66, 67]
        logits, kv = llama.forward(
            qparams, jnp.asarray([prompt], jnp.int32), cfg
        )
        cache = llama.init_cache(cfg, 1, 64)
        cache = insert_prefill(cache, kv, len(prompt))
        tok = int(logits[0, -1].argmax())
        pos, want = len(prompt), []
        for _ in range(6):
            want.append(tok)
            lg, cache = llama.decode_step(
                qparams, cache, jnp.array([tok], jnp.int32),
                jnp.array([pos], jnp.int32), cfg,
            )
            tok = int(lg[0].argmax())
            pos += 1
        got = eng.generate(prompt, max_tokens=6, temperature=0.0)
        assert got == want, (got, want)
    finally:
        eng.stop()


def test_merge_lora_over_int4_base():
    """merge_lora on a Q4Tensor base must produce bf16 merged weights
    (Q4's storage dtype is uint8 — casting merged floats to it would
    destroy the model)."""
    from substratus_tpu.train import lora as lora_lib

    cfg = llama.CONFIGS["tiny"]
    params = llama.init_params(cfg, jax.random.key(0))
    qparams = quantize4_params(params, llama.quant_contracting(cfg))
    adapters = lora_lib.init_lora(cfg, jax.random.key(1), rank=2)
    merged = lora_lib.merge_lora(qparams, adapters, scale=8.0)
    wq = merged["layers"]["wq"]
    assert wq.dtype == jnp.bfloat16, wq.dtype
    # Merged ~= dequantized base + delta: sanity that values are sane.
    base = qparams["layers"]["wq"].dequant(jnp.float32)
    assert float(jnp.abs(wq.astype(jnp.float32) - base).mean()) < 1.0
