"""GPipe-style pipeline parallelism over the "stage" mesh axis.

Greenfield (SURVEY.md §2.3 — the reference has no parallelism at all). The
transformer's layer stack is split into `n_stages` contiguous groups, one
per device along the "stage" axis; activations flow stage-to-stage via
`jax.lax.ppermute` (XLA lowers to neighbor transfers — ICI within a slice,
DCN across slices, which is why "stage" sits next to "data" in MESH_AXES).

Two schedules:

* `pipeline_forward` — classic GPipe. M microbatches enter stage 0 one step
  apart; step t has stage s working on microbatch t-s; after M + S - 1 steps
  every microbatch has exited the last stage. Backward is jax.grad through
  the same scan (ppermute is differentiable): synchronous fill-drain, so
  activation memory grows O(M) with the microbatch count.

* `pipeline_train_step_1f1b` — one-forward-one-backward with an explicit
  hand-written backward (jax.vjp per stage, inputs stashed and the stage
  recomputed at backward time, Megatron-style remat). Each stage holds at
  most 2S-1 in-flight microbatch inputs, so activation memory is O(S) —
  INDEPENDENT of M. That is 1F1B's point: M can grow to amortize the
  bubble (fraction (2S-2)/(M+2S-2)) without blowing up memory, where GPipe
  under jax.grad cannot. Under XLA's SPMD lockstep all stages execute every
  tick (invalid slots compute on garbage and are masked out), the same
  trade the GPipe path already makes in its warmup/drain steps.

Embedding and the LM head are replicated; the GPipe path applies the head
outside the pipelined region, the 1F1B path folds head+loss into the last
stage's tick (the backward needs dL/d(out) as soon as a microbatch exits).
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from substratus_tpu.models import llama
from substratus_tpu.models.llama import LlamaConfig, Params
from substratus_tpu.ops.basics import rms_norm
from substratus_tpu.ops.quant import materialize

AXIS = "stage"


def stage_params(params: Params, n_stages: int) -> Params:
    """Reshape stacked layers [L, ...] -> [n_stages, L/S, ...]; embed/norm/
    head stay replicated."""
    L = jax.tree.leaves(params["layers"])[0].shape[0]
    if L % n_stages:
        raise ValueError(f"{L} layers not divisible into {n_stages} stages")
    out = dict(params)
    out["layers"] = jax.tree.map(
        lambda x: x.reshape((n_stages, L // n_stages) + x.shape[1:]),
        params["layers"],
    )
    return out


def _stage_fn(local_layers: Params, x: jnp.ndarray, positions, cfg, train):
    """Apply this stage's local layer stack (scan over layers). Returns
    (x_out, summed MoE aux for the stage — 0 for dense models)."""

    def body(carry, lp):
        x_out, _, aux = llama._block(carry, lp, positions, cfg, None, train=train)
        return x_out, aux

    x, auxes = lax.scan(body, x, local_layers)
    return x, auxes.sum()


def pipeline_forward(
    params: Params,  # stage_params() output, "layers" sharded on stage
    tokens: jnp.ndarray,  # [B, S]
    cfg: LlamaConfig,
    n_stages: int,
    n_microbatches: int,
    train: bool = False,
):
    """Pipelined (logits [B, S, vocab], moe_aux scalar). Call inside jit
    with an ambient mesh (jax.set_mesh) that has a "stage" axis of size
    n_stages. For MoE models the router load-balancing aux is accumulated
    across stages and valid microbatches (0.0 for dense models); `train`
    selects the capacity-dispatch expert path like llama.forward."""
    B, S = tokens.shape
    if B % n_microbatches:
        raise ValueError(f"batch {B} not divisible by {n_microbatches} microbatches")
    mb = B // n_microbatches
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (mb, S))

    x = materialize(params["tok_embed"], cfg.dtype)[tokens]
    micro = x.reshape(n_microbatches, mb, S, cfg.dim)

    layers_spec = P(AXIS)  # leading stage dim sharded; rest replicated

    def pipelined(layers_local, micro):
        # layers_local leaves: [1, L/S, ...] (this stage's group).
        local = jax.tree.map(lambda a: a[0], layers_local)
        stage = lax.axis_index(AXIS)
        n = n_stages
        M = n_microbatches
        perm = [(i, (i + 1) % n) for i in range(n)]

        def step(carry, t):
            act = carry  # activation arriving from the previous stage
            inject = micro[jnp.clip(t, 0, M - 1)]
            inp = jnp.where(stage == 0, inject, act)
            out, aux = _stage_fn(local, inp, positions, cfg, train)
            # This stage processes microbatch t - stage; aux from warmup/
            # drain steps (garbage inputs) must not count.
            mb_idx = t - stage
            valid = jnp.logical_and(mb_idx >= 0, mb_idx < M)
            aux = jnp.where(valid, aux, 0.0)
            # The last stage's output at step t is microbatch t-(n-1).
            collect = jnp.where(stage == n - 1, out, jnp.zeros_like(out))
            act_next = lax.ppermute(out, AXIS, perm)
            return act_next, (collect, aux)

        init = jnp.zeros((mb, S, cfg.dim), cfg.dtype)
        # Mark the carry as stage-varying: the scan's output (post-ppermute)
        # is device-varying, and scan requires carry types to match.
        init = lax.pcast(init, (AXIS,), to="varying")
        _, (collected, auxes) = lax.scan(step, init, jnp.arange(M + n - 1))
        # Valid outputs live at steps n-1 .. n-1+M-1; broadcast them off the
        # last stage to every stage (zeros elsewhere -> psum is a select).
        outs = collected[n - 1:]
        outs = lax.psum(outs, AXIS)
        # Mean aux per (layer, microbatch): sum over stages/steps, then
        # normalize like llama.forward's kv["moe_aux"].mean().
        aux_total = lax.psum(auxes.sum(), AXIS) / (cfg.n_layers * M)
        return outs, aux_total  # [M, mb, S, D], scalar

    outs, aux = jax.shard_map(
        pipelined,
        in_specs=(layers_spec, P()),
        out_specs=(P(), P()),
        axis_names={AXIS},
    )(params["layers"], micro)

    x = outs.reshape(B, S, cfg.dim)
    x = rms_norm(x, params["out_norm"], cfg.norm_eps)
    if cfg.tie_embeddings:
        logits = jnp.einsum(
            "bsd,vd->bsv", x, materialize(params["tok_embed"], cfg.dtype)
        )
    else:
        logits = jnp.einsum(
            "bsd,dv->bsv", x, materialize(params["lm_head"], cfg.dtype)
        )
    return logits.astype(jnp.float32), aux


def pipeline_train_step_1f1b(
    params: Params,  # stage_params() output
    tokens: jnp.ndarray,  # [B, S] int32 (next-token loss computed inside)
    cfg: LlamaConfig,
    n_stages: int,
    n_microbatches: int,
    weights: Optional[jnp.ndarray] = None,  # [B, S] loss mask
    train: bool = True,
):
    """One 1F1B forward+backward: returns (loss, grads, moe_aux) with grads
    matching the stage_params() tree. Call inside jit with an ambient mesh
    holding a "stage" axis of size n_stages.

    Schedule (full ticks, fwd-then-bwd per tick): stage s forwards
    microbatch f = t - s and backwards b = t - (2S-2-s); the last stage
    computes head+loss and starts a microbatch's backward the same tick its
    forward finishes. A microbatch's input is stashed at forward time and
    the stage recomputed at backward time (jax.vjp), so the stash — a ring
    of 2S-1 inputs — is the only activation state, independent of M.
    """
    if cfg.tie_embeddings:
        raise NotImplementedError("1F1B with tied embeddings")
    B, S = tokens.shape
    if B % n_microbatches:
        raise ValueError(
            f"batch {B} not divisible by {n_microbatches} microbatches"
        )
    M = n_microbatches
    n = n_stages
    mb = B // M
    K = 2 * n - 1  # stash ring size (max in-flight at stage 0)
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (mb, S))
    dt = cfg.dtype

    x = materialize(params["tok_embed"], dt)[tokens]
    micro_x = x.reshape(M, mb, S, cfg.dim)
    micro_tok = tokens.reshape(M, mb, S)
    if weights is None:
        weights = jnp.ones((B, S), jnp.float32)
    micro_w = weights.reshape(M, mb, S).astype(jnp.float32)

    layers_spec = P(AXIS)
    aux_ct_unit = (
        cfg.router_aux_weight / (cfg.n_layers * M)
        if cfg.n_experts > 0
        else 0.0
    )

    # The CE normalizer is known up front (it's just the mask sum), so the
    # head loss is computed pre-normalized: gradients then need NO final
    # rescaling — crucial because the MoE router-aux objective shares the
    # same backward and must NOT be divided by the token count.
    denom = jnp.maximum(micro_w[:, :, 1:].sum(), 1.0)

    def head_loss(out, norm_w, head_w, toks, w):
        """Mean next-token CE contribution of one microbatch."""
        h = rms_norm(out, norm_w, cfg.norm_eps)
        logits = jnp.einsum(
            "bsd,dv->bsv", h, materialize(head_w, dt)
        ).astype(jnp.float32)
        logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
        nll = -jnp.take_along_axis(
            logp, toks[:, 1:, None], axis=-1
        )[..., 0]
        return (nll * w[:, 1:]).sum() / denom

    def pipelined(layers_local, head_w, micro_x, micro_tok, micro_w):
        local = jax.tree.map(lambda a: a[0], layers_local)
        norm_w, lm_head = head_w
        # Replicated params must become stage-VARYING before any grad is
        # taken wrt them: differentiating an unvarying input used in a
        # varying computation transposes the implicit broadcast into a
        # psum over stages — which would silently sum the masked-out
        # garbage gradients from invalid ticks on other stages into the
        # valid one's BEFORE the validity mask can drop them.
        norm_w = lax.pcast(norm_w, (AXIS,), to="varying")
        lm_head = lax.pcast(lm_head, (AXIS,), to="varying")
        s = lax.axis_index(AXIS)
        is_last = s == n - 1
        is_first = s == 0
        perm_fwd = [(i, (i + 1) % n) for i in range(n)]
        perm_bwd = [(i, (i - 1) % n) for i in range(n)]

        def stage(p, x):
            return _stage_fn(p, x, positions, cfg, train)

        def loss_of(out, nw, hw, f_idx):
            return head_loss(out, nw, hw, micro_tok[f_idx], micro_w[f_idx])

        def tick(carry, t):
            act, grad_in, stash, g_layers, g_head, g_embed, nll_a, aux_a = carry

            # ---- forward: microbatch f = t - s
            f = t - s
            f_ok = jnp.logical_and(f >= 0, f < M)
            f_c = jnp.clip(f, 0, M - 1)
            inp = jnp.where(is_first, micro_x[f_c], act)
            out, aux_f = stage(local, inp)
            aux_a = aux_a + jnp.where(f_ok, aux_f, 0.0)
            # Stash this input for the backward-time recompute (only when
            # valid — never clobber a live slot with garbage).
            slot = f_c % K
            stash = stash.at[slot].set(
                jnp.where(f_ok, inp, stash[slot])
            )

            # ---- last stage: head + loss for f (== the bwd microbatch b)
            (nll, (g_out, g_norm, g_hw)) = jax.value_and_grad(
                lambda o, nw, hw: loss_of(o, nw, hw, f_c),
                argnums=(0, 1, 2),
            )(out.astype(dt), norm_w, lm_head)
            last_ok = jnp.logical_and(is_last, f_ok)
            nll_a = nll_a + jnp.where(last_ok, nll, 0.0)
            g_head = jax.tree.map(
                lambda a, g: a + jnp.where(last_ok, g, 0).astype(a.dtype),
                g_head, (g_norm, g_hw),
            )

            # ---- backward: microbatch b = t - (2n - 2 - s), recomputed
            b = t - (2 * n - 2 - s)
            b_ok = jnp.logical_and(b >= 0, b < M)
            b_c = jnp.clip(b, 0, M - 1)
            x_b = stash[b_c % K]
            _, vjp = jax.vjp(stage, local, x_b)
            g_up = jnp.where(is_last, g_out.astype(dt), grad_in)
            if aux_ct_unit == 0.0:
                # Dense model: the aux primal is a constant zero and hence
                # UNVARYING over the stage axis; its cotangent must match
                # that type (a stage-dependent where() would be varying).
                aux_ct = jnp.zeros((), jnp.float32)
            else:
                aux_ct = jnp.where(b_ok, aux_ct_unit, 0.0).astype(
                    jnp.float32
                )
            g_local, g_x = vjp((g_up, aux_ct))
            bscale = b_ok.astype(jnp.float32)
            g_layers = jax.tree.map(
                lambda a, g: a + g.astype(jnp.float32) * bscale,
                g_layers, g_local,
            )
            g_embed = g_embed + jnp.where(
                jnp.logical_and(is_first, b_ok),
                jnp.zeros_like(g_embed).at[micro_tok[b_c]].add(
                    g_x.astype(jnp.float32)
                ),
                0.0,
            )

            act_next = lax.ppermute(out, AXIS, perm_fwd)
            grad_next = lax.ppermute(g_x, AXIS, perm_bwd)
            return (act_next, grad_next, stash, g_layers, g_head, g_embed,
                    nll_a, aux_a), None

        zeros_act = jnp.zeros((mb, S, cfg.dim), dt)
        init = (
            lax.pcast(zeros_act, (AXIS,), to="varying"),
            lax.pcast(zeros_act, (AXIS,), to="varying"),
            lax.pcast(jnp.zeros((K, mb, S, cfg.dim), dt), (AXIS,), to="varying"),
            lax.pcast(
                jax.tree.map(
                    lambda a: jnp.zeros(a.shape, jnp.float32), local
                ), (AXIS,), to="varying",
            ),
            lax.pcast(
                jax.tree.map(
                    lambda a: jnp.zeros(a.shape, jnp.float32),
                    (norm_w, lm_head),
                ), (AXIS,), to="varying",
            ),
            lax.pcast(
                jnp.zeros((cfg.vocab_size, cfg.dim), jnp.float32),
                (AXIS,), to="varying",
            ),
            lax.pcast(jnp.zeros((), jnp.float32), (AXIS,), to="varying"),
            lax.pcast(jnp.zeros((), jnp.float32), (AXIS,), to="varying"),
        )
        T = M + 2 * n - 2
        carry, _ = lax.scan(tick, init, jnp.arange(T))
        (_, _, _, g_layers, g_head, g_embed, nll_a, aux_a) = carry

        # Scalars and replicated-param grads live on one stage each —
        # psum selects + replicates them.
        nll = lax.psum(nll_a, AXIS)
        aux = lax.psum(aux_a, AXIS) / (cfg.n_layers * M)
        g_head = jax.tree.map(lambda g: lax.psum(g, AXIS), g_head)
        g_embed = lax.psum(g_embed, AXIS)
        g_layers = jax.tree.map(lambda g: g[None], g_layers)
        return nll, aux, g_layers, g_head, g_embed

    loss, aux, g_layers, g_head, g_embed = jax.shard_map(
        pipelined,
        in_specs=(layers_spec, P(), P(), P(), P()),
        out_specs=(P(), P(), layers_spec, P(), P()),
        axis_names={AXIS},
    )(
        params["layers"], (params["out_norm"], params["lm_head"]),
        micro_x, micro_tok, micro_w,
    )

    grads = {
        "tok_embed": g_embed,
        "layers": g_layers,
        "out_norm": g_head[0],
        "lm_head": g_head[1],
    }
    # The MoE router aux already contributed its gradient inside the ticks
    # (aux cotangent); the reported loss mirrors trainer semantics.
    return loss + (cfg.router_aux_weight * aux if cfg.n_experts else 0.0), grads, aux
