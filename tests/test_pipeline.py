"""Pipeline parallelism: pipelined forward/backward must match the plain
scan-over-layers model exactly (pipelining is a schedule, not a model)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from substratus_tpu.models import llama
from substratus_tpu.parallel.mesh import build_mesh
from substratus_tpu.parallel.pipeline import pipeline_forward, stage_params
from substratus_tpu.train.trainer import cross_entropy_loss


@pytest.fixture(scope="module")
def setup():
    cfg = llama.CONFIGS["tiny"].replace(n_layers=4, dtype=jnp.float32)
    params = llama.init_params(cfg, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (8, 16), 0, cfg.vocab_size)
    return cfg, params, tokens


@pytest.mark.parametrize("n_stages,n_micro", [(2, 4), (4, 4), (2, 8)])
def test_pipeline_forward_matches_plain(setup, n_stages, n_micro):
    cfg, params, tokens = setup
    ref, _ = llama.forward(params, tokens, cfg)

    mesh = build_mesh(stage=n_stages, data=8 // n_stages)
    staged = stage_params(params, n_stages)
    with jax.set_mesh(mesh):
        out, aux = jax.jit(
            lambda p, t: pipeline_forward(p, t, cfg, n_stages, n_micro)
        )(staged, tokens)
    assert float(aux) == 0.0  # dense model
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-4, rtol=2e-4
    )


def test_pipeline_backward_matches_plain(setup):
    cfg, params, tokens = setup
    n_stages, n_micro = 2, 4
    mesh = build_mesh(stage=n_stages, data=4)

    def loss_plain(p):
        logits, _ = llama.forward(p, tokens, cfg)
        return cross_entropy_loss(logits[:, :-1], tokens[:, 1:])

    def loss_pp(staged):
        logits, _ = pipeline_forward(staged, tokens, cfg, n_stages, n_micro)
        return cross_entropy_loss(logits[:, :-1], tokens[:, 1:])

    g_plain = jax.grad(loss_plain)(params)
    staged = stage_params(params, n_stages)
    with jax.set_mesh(mesh):
        g_pp = jax.jit(jax.grad(loss_pp))(staged)

    # Compare a few representative leaves (reshape staged grads back).
    for name in ("wq", "w_down"):
        a = np.asarray(g_plain["layers"][name])
        b = np.asarray(g_pp["layers"][name]).reshape(a.shape)
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-3)
    np.testing.assert_allclose(
        np.asarray(g_plain["lm_head"]),
        np.asarray(g_pp["lm_head"]),
        atol=1e-4,
        rtol=1e-3,
    )


def test_pipeline_moe_matches_plain():
    """MoE through the pipelined region: exact (inference) routing matches
    the plain model; the training path yields finite loss + aux."""
    from substratus_tpu.models import llama as llama_mod

    cfg = llama_mod.CONFIGS["tiny-moe"].replace(
        n_layers=4, dtype=jnp.float32
    )
    params = llama_mod.init_params(cfg, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (8, 16), 0, cfg.vocab_size)
    ref, kv = llama_mod.forward(params, tokens, cfg)

    mesh = build_mesh(stage=2, data=4)
    staged = stage_params(params, 2)
    with jax.set_mesh(mesh):
        out, aux = jax.jit(
            lambda p, t: pipeline_forward(p, t, cfg, 2, 4)
        )(staged, tokens)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-4, rtol=2e-4
    )
    # Aux pools per MICROBATCH (what pipelined dispatch actually sees), so
    # the oracle is the mean of per-microbatch plain-forward auxes — not
    # the full-batch aux (load x importance is nonlinear in batch pooling).
    micro_auxes = []
    for m in range(4):
        _, kv_m = llama_mod.forward(params, tokens[2 * m : 2 * m + 2], cfg)
        micro_auxes.append(float(kv_m["moe_aux"].mean()))
    np.testing.assert_allclose(float(aux), np.mean(micro_auxes), atol=1e-4)

    def loss_pp(staged):
        logits, aux = pipeline_forward(staged, tokens, cfg, 2, 4, train=True)
        return (
            cross_entropy_loss(logits[:, :-1], tokens[:, 1:])
            + cfg.router_aux_weight * aux
        )

    with jax.set_mesh(mesh):
        loss, grads = jax.jit(jax.value_and_grad(loss_pp))(staged)
    assert np.isfinite(float(loss))
    assert np.isfinite(np.asarray(grads["layers"]["router"])).all()


def test_1f1b_matches_gpipe_loss_and_grads():
    """The 1F1B schedule (explicit vjp backward, O(stages) activation
    memory) must produce the same loss and gradients as GPipe-under-grad."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from substratus_tpu.models import llama
    from substratus_tpu.parallel.mesh import build_mesh
    from substratus_tpu.parallel.pipeline import (
        pipeline_forward,
        pipeline_train_step_1f1b,
        stage_params,
    )
    from substratus_tpu.train.trainer import cross_entropy_loss

    cfg = llama.CONFIGS["tiny"].replace(dtype=jnp.float32, n_layers=4)
    params = llama.init_params(cfg, jax.random.key(0))
    staged = stage_params(params, 2)
    tokens = jax.random.randint(jax.random.key(1), (8, 16), 0, cfg.vocab_size)
    mesh = build_mesh(data=4, stage=2)

    def gpipe_loss(p):
        logits, _ = pipeline_forward(p, tokens, cfg, 2, 4, train=True)
        return cross_entropy_loss(logits[:, :-1], tokens[:, 1:])

    with jax.set_mesh(mesh):
        loss_g, grads_g = jax.jit(jax.value_and_grad(gpipe_loss))(staged)
        loss_f, grads_f, aux = jax.jit(
            lambda p: pipeline_train_step_1f1b(p, tokens, cfg, 2, 4)
        )(p=staged)

    np.testing.assert_allclose(
        float(loss_f), float(loss_g), rtol=1e-5, atol=1e-5
    )
    flat_g = jax.tree.leaves_with_path(grads_g)
    flat_f = dict(jax.tree.leaves_with_path(grads_f))
    assert len(flat_g) == len(flat_f)
    for path, g in flat_g:
        f = flat_f[path]
        np.testing.assert_allclose(
            np.asarray(jax.device_get(f)), np.asarray(jax.device_get(g)),
            rtol=2e-4, atol=2e-5, err_msg=str(path),
        )


def test_1f1b_moe_runs_and_matches_gpipe_loss():
    """MoE through 1F1B: router aux gradient flows inside the ticks and the
    reported loss matches the GPipe-equivalent objective."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from substratus_tpu.models import llama
    from substratus_tpu.parallel.mesh import build_mesh
    from substratus_tpu.parallel.pipeline import (
        pipeline_forward,
        pipeline_train_step_1f1b,
        stage_params,
    )
    from substratus_tpu.train.trainer import cross_entropy_loss

    cfg = llama.CONFIGS["tiny-moe"].replace(dtype=jnp.float32)
    params = llama.init_params(cfg, jax.random.key(0))
    staged = stage_params(params, 2)
    tokens = jax.random.randint(jax.random.key(1), (4, 16), 0, cfg.vocab_size)
    mesh = build_mesh(data=4, stage=2)

    def gpipe_obj(p):
        logits, aux = pipeline_forward(p, tokens, cfg, 2, 2, train=True)
        return (
            cross_entropy_loss(logits[:, :-1], tokens[:, 1:])
            + cfg.router_aux_weight * aux
        )

    with jax.set_mesh(mesh):
        loss_g, grads_g = jax.jit(jax.value_and_grad(gpipe_obj))(staged)
        loss_f, grads_f, aux = jax.jit(
            lambda p: pipeline_train_step_1f1b(p, tokens, cfg, 2, 2)
        )(staged)

    np.testing.assert_allclose(
        float(loss_f), float(loss_g), rtol=1e-5, atol=1e-5
    )
    router_g = np.asarray(jax.device_get(grads_g["layers"]["router"]))
    router_f = np.asarray(jax.device_get(grads_f["layers"]["router"]))
    np.testing.assert_allclose(router_f, router_g, rtol=3e-4, atol=3e-5)
