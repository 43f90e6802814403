"""pjit trainer: FSDP/TP/SP-sharded training with optional LoRA.

This is the in-repo replacement for the reference's external
`substratusai/model-trainer-huggingface` image (SURVEY.md §2.2). Where that
image ran single-pod HF Trainer on CUDA (max seen: 8xL4 on one node,
examples/falcon-40b/finetuned-model.yaml), this trainer is written for SPMD
over a TPU mesh from the start:

  * one jitted train step with NamedSharding-annotated params/opt-state;
    XLA inserts the all-gathers/reduce-scatters FSDP needs;
  * optional LoRA mode: base params frozen (optionally int8), gradients and
    optimizer state only for adapters;
  * remat (jax.checkpoint) over each scanned block to trade FLOPs for HBM;
  * loss masking via a per-token weight array (padding / prompt masking).

Container contract: `python -m substratus_tpu.train.main` reads
/content/params.json, data from /content/data, base model from
/content/model, writes checkpoints to /content/artifacts (reference:
docs/container-contract.md:5-56).
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from substratus_tpu.models import llama
from substratus_tpu.models.llama import LlamaConfig, Params
from substratus_tpu.parallel.sharding import (
    DEFAULT_RULES,
    LogicalRules,
    shard_tree,
    sharding_tree,
)
from substratus_tpu.train import lora as lora_lib


@dataclass(frozen=True)
class TrainConfig:
    learning_rate: float = 2e-5
    weight_decay: float = 0.0
    warmup_steps: int = 10
    total_steps: int = 100
    grad_clip: float = 1.0
    b1: float = 0.9
    b2: float = 0.999
    # LoRA: rank 0 disables (full finetune)
    lora_rank: int = 0
    lora_alpha: float = 16.0
    # Projections to adapt (train/lora.py); on MoE models the mlp names
    # (w_gate/w_up/w_down) select expert-routed adapters.
    lora_targets: tuple = ("wq", "wv")
    remat: bool = True
    seed: int = 0
    # Gradient accumulation: the global batch splits into this many
    # microbatches scanned inside the jitted step (activation memory scales
    # with the microbatch, optimizer cadence with the global batch).
    grad_accum_steps: int = 1


def cross_entropy_sum(
    logits: jnp.ndarray,  # [B, S, V] float32
    targets: jnp.ndarray,  # [B, S] int32
    weights: Optional[jnp.ndarray] = None,  # [B, S] 0/1 loss mask
) -> tuple:
    """(weighted nll sum, weight sum) — the accumulation-friendly form."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    if weights is None:
        weights = jnp.ones_like(nll)
    weights = weights.astype(jnp.float32)
    return (nll * weights).sum(), weights.sum()


def cross_entropy_loss(
    logits: jnp.ndarray,
    targets: jnp.ndarray,
    weights: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    s, w = cross_entropy_sum(logits, targets, weights)
    return s / jnp.maximum(w, 1.0)


def make_optimizer(tc: TrainConfig) -> optax.GradientTransformation:
    schedule = optax.warmup_cosine_decay_schedule(
        init_value=0.0,
        peak_value=tc.learning_rate,
        warmup_steps=tc.warmup_steps,
        decay_steps=max(tc.total_steps, tc.warmup_steps + 1),
    )
    return optax.chain(
        optax.clip_by_global_norm(tc.grad_clip),
        optax.adamw(
            schedule, b1=tc.b1, b2=tc.b2, weight_decay=tc.weight_decay
        ),
    )


class Trainer:
    """Owns sharded params/opt-state and the jitted train step.

    In LoRA mode `trainable` is the adapter tree and `params` stays frozen;
    otherwise `trainable` IS the params tree.
    """

    def __init__(
        self,
        cfg: LlamaConfig,
        tc: TrainConfig,
        mesh: Mesh,
        params: Optional[Params] = None,
        rules: LogicalRules = DEFAULT_RULES,
        model=None,
    ):
        """model: the model-family module; resolved from the config type via
        models/registry.py when omitted, so any registered family trains."""
        from substratus_tpu.models import registry

        self.model = model if model is not None else registry.module_of(cfg)
        self.cfg, self.tc, self.mesh, self.rules = cfg, tc, mesh, rules
        self.optimizer = make_optimizer(tc)
        key_params, key_lora = jax.random.split(jax.random.key(tc.seed))

        # sharding_tree (not logical_sharding): it sees the shapes, so
        # non-divisible dims (e.g. MQA's single kv head vs a tensor axis)
        # fall back to replication instead of erroring.
        param_shapes = jax.eval_shape(
            partial(self.model.init_params, cfg), jax.random.key(0)
        )
        param_sh = sharding_tree(
            param_shapes, mesh, self.model.param_logical_axes(cfg), rules
        )
        if params is None:
            init = jax.jit(
                partial(self.model.init_params, cfg), out_shardings=param_sh
            )
            params = init(key_params)
        else:
            # shard_tree handles both dense and int8-QTensor (QLoRA) bases.
            params = shard_tree(
                params, mesh, self.model.param_logical_axes(cfg), rules
            )
        self.params = params
        self.param_shardings = param_sh

        if tc.lora_rank > 0 and not getattr(self.model, "SUPPORTS_LORA", False):
            raise NotImplementedError(
                f"LoRA is not implemented for the "
                f"{self.model.__name__.split('.')[-1]} family; use full "
                "finetuning (lora_rank: 0)"
            )
        if tc.lora_rank > 0:
            adapters = lora_lib.init_lora(
                cfg, key_lora, rank=tc.lora_rank, alpha=tc.lora_alpha,
                targets=tuple(tc.lora_targets),
            )
            self.lora_scale = tc.lora_alpha / tc.lora_rank
            # Shape-aware (like params): MQA kv adapters replicate rather
            # than error when kv_heads doesn't divide the tensor axis.
            self.lora_shardings = sharding_tree(
                adapters, mesh, lora_lib.lora_logical_axes(adapters), rules
            )
            self.lora = jax.tree.map(
                jax.device_put, adapters, self.lora_shardings
            )
            trainable_sh = self.lora_shardings
            trainable = self.lora
        else:
            self.lora = None
            self.lora_scale = None
            self.lora_shardings = None
            trainable_sh = param_sh
            trainable = params

        self.opt_state = jax.jit(
            self.optimizer.init,
            out_shardings=self._opt_shardings(trainable_sh),
        )(trainable)
        self.step = 0

        batch_spec = rules.mesh_axes(("batch", "seq"))
        self.batch_sharding = NamedSharding(mesh, batch_spec)
        self._train_step = self._build_train_step()

    def _opt_shardings(self, trainable_sh):
        """Optimizer-state shardings: moment buffers mirror their param's
        sharding (matched structurally via optax's param-tree mapping),
        scalars (step counts) replicate."""
        import optax.tree_utils as otu

        trainable_shapes = self._trainable_shapes(trainable_sh)
        opt_shapes = jax.eval_shape(self.optimizer.init, trainable_shapes)
        replicated = NamedSharding(self.mesh, P())
        return otu.tree_map_params(
            self.optimizer,
            lambda _, sh: sh,
            opt_shapes,
            trainable_sh,
            transform_non_params=lambda _: replicated,
        )

    def _trainable_shapes(self, trainable_sh):
        src = self.lora if self.lora is not None else self.params
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), src
        )

    def _build_train_step(self):
        cfg, tc = self.cfg, self.tc
        optimizer = self.optimizer
        lora_mode = tc.lora_rank > 0

        lora_scale = self.lora_scale if lora_mode else None

        def loss_fn(trainable, frozen_params, batch):
            if lora_mode:
                params = frozen_params
                lora = {"layers": trainable, "scale": lora_scale}
            else:
                params, lora = trainable, None
            logits, kv = self.model.forward(
                params,
                batch["tokens"],
                cfg,
                lora=lora,
                remat=tc.remat,
                train=True,
            )
            loss = cross_entropy_loss(
                logits[:, :-1], batch["tokens"][:, 1:], batch["weights"][:, 1:]
            )
            if "moe_aux" in kv:  # router load balancing (MoE models)
                loss = loss + cfg.router_aux_weight * kv["moe_aux"].mean()
            return loss

        accum = max(1, tc.grad_accum_steps)

        def sum_loss_fn(trainable, frozen_params, mb):
            """(weighted-nll sum [+ token-weighted moe aux], weight sum) —
            summing (not averaging) per microbatch makes accumulation
            exactly equal to the single-step update even when loss-mask
            token counts differ across microbatches."""
            if lora_mode:
                params = frozen_params
                lora = {"layers": trainable, "scale": lora_scale}
            else:
                params, lora = trainable, None
            logits, kv = self.model.forward(
                params, mb["tokens"], cfg, lora=lora, remat=tc.remat,
                train=True,
            )
            s, w = cross_entropy_sum(
                logits[:, :-1], mb["tokens"][:, 1:], mb["weights"][:, 1:]
            )
            if "moe_aux" in kv:
                s = s + cfg.router_aux_weight * kv["moe_aux"].mean() * w
            return s, w

        def train_step(trainable, frozen_params, opt_state, batch):
            if accum == 1:
                loss, grads = jax.value_and_grad(loss_fn)(
                    trainable, frozen_params, batch
                )
            else:
                # Scan microbatches, accumulating grad-of-sum in f32; one
                # optimizer update per global batch, normalized once by the
                # total token weight.
                micro = jax.tree.map(
                    lambda x: x.reshape(
                        (accum, x.shape[0] // accum) + x.shape[1:]
                    ),
                    batch,
                )

                def acc_step(carry, mb):
                    s_sum, w_sum, grads = carry
                    (s, w), g = jax.value_and_grad(
                        sum_loss_fn, has_aux=True
                    )(trainable, frozen_params, mb)
                    grads = jax.tree.map(
                        lambda a, b: a + b.astype(jnp.float32), grads, g
                    )
                    return (s_sum + s, w_sum + w, grads), None

                zero = jax.tree.map(
                    lambda x: jnp.zeros(x.shape, jnp.float32), trainable
                )
                (s_sum, w_sum, grads), _ = jax.lax.scan(
                    acc_step,
                    (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32), zero),
                    micro,
                )
                denom = jnp.maximum(w_sum, 1.0)
                loss = s_sum / denom
                # Cast back to param dtype so optimizer-state dtypes match
                # the non-accumulated path (donation needs stable types).
                grads = jax.tree.map(
                    lambda g, p: (g / denom).astype(p.dtype), grads, trainable
                )
            updates, opt_state = optimizer.update(
                grads, opt_state, trainable
            )
            trainable = optax.apply_updates(trainable, updates)
            return trainable, opt_state, loss

        donate = (0, 2)  # trainable + opt_state buffers
        return jax.jit(train_step, donate_argnums=donate)

    def train_step(
        self, batch: Dict[str, jnp.ndarray], batch_is_global: bool = False
    ) -> float:
        """batch: {"tokens": [B, S] int32, "weights": [B, S] 0/1}.

        Multi-process: B is the PER-PROCESS slice (global/N); the global
        batch assembles from every process's local rows via
        make_array_from_process_local_data, so no host ever materializes
        (or needs to agree on) the whole batch.

        batch_is_global: every process passed the IDENTICAL full global
        batch (train/main.py falls back to this when dp_total doesn't
        divide across processes) — placement then slices each process's
        addressable rows out of the full array instead of concatenating
        per-process shards."""
        nproc = jax.process_count()
        b = batch["tokens"].shape[0] * (1 if batch_is_global else nproc)
        dp = self.mesh.shape["data"] * self.mesh.shape["fsdp"]
        if b % dp:
            raise ValueError(
                f"global batch size {b} must be divisible by data*fsdp={dp} "
                f"(mesh {dict(self.mesh.shape)})"
            )
        accum = max(1, self.tc.grad_accum_steps)
        if b % accum or (b // accum) % dp:
            raise ValueError(
                f"global batch size {b} must split into "
                f"grad_accum_steps={accum} microbatches each divisible by "
                f"data*fsdp={dp}"
            )
        if nproc > 1:
            batch = jax.tree.map(
                lambda x: jax.make_array_from_process_local_data(
                    self.batch_sharding, np.asarray(x),  # sublint: allow[hostsync]: incoming batch is host data; numpy is what every process can feed identically
                    global_shape=(
                        np.asarray(x).shape if batch_is_global else None  # sublint: allow[hostsync]: same host-side batch, shape probe only
                    ),
                ),
                batch,
            )
        else:
            batch = jax.tree.map(
                lambda x: jax.device_put(x, self.batch_sharding), batch
            )
        trainable = self.lora if self.lora is not None else self.params
        # Ambient mesh: the ring-attention path (cfg.attn_impl == "ring")
        # opens a shard_map over the "sequence" axis inside the jitted step.
        with jax.set_mesh(self.mesh):
            trainable, self.opt_state, loss = self._train_step(
                trainable, self.params if self.lora is not None else None,
                self.opt_state, batch,
            )
        if self.lora is not None:
            self.lora = trainable
        else:
            self.params = trainable
        self.step += 1
        return float(loss)

    def snapshot_params(self) -> Params:
        """A host-resident COPY of the live param tree, safe to hand to
        a consumer that outlives the next train_step. The jitted step
        donates the trainable buffers (donate_argnums=(0, 2)), so
        `self.params` leaves are invalidated and rewritten every step —
        handing the live tree to `Engine.swap_params` would alias
        buffers the next step clobbers. The copy is device_get, not
        jnp.array: a device copy would keep the trainer's mesh sharding,
        and installing mesh-sharded leaves into a single-device engine
        turns its decode step into a multi-device collective program
        (which deadlocks against the trainer's own collectives when both
        run in one process). Host numpy is the placement-neutral
        interchange — each engine re-places it for its own topology on
        install. The RL actor-learner loop (rl/loop.py) ships weights
        to actors exclusively through this."""
        return jax.device_get(self.params)
