"""Sharded training step: mesh + rules + loss -> one jitted XLA program.

Parity reference: this is the TPU shape of atorch's
``auto_accelerate`` application path (auto/accelerate.py:35
``model_transform``) — where the reference wraps the model in
DDP/FSDP/TP-rewritten modules and hacks the optimizer, we jit ONE train
step whose in/out shardings carry the whole strategy; XLA inserts every
collective (grad reduce == psum from sharded batch; ZeRO gather/scatter ==
all_gather/reduce_scatter from sharded params).

Gradient accumulation (elastic fixed-global-batch, parity
dlrover/trainer/torch/elastic.py:170) is a ``lax.scan`` over a leading
microbatch axis, fused into the same program.
"""

from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dlrover_tpu.common.log import default_logger as logger
from dlrover_tpu.parallel import sharding as shd
from dlrover_tpu.parallel.mesh import create_mesh
from dlrover_tpu.telemetry.registry import gauge


class ShardedTrainer:
    """Builds sharded init / train-step functions for a pytree model.

    Args:
      loss_fn: ``loss_fn(params, batch) -> scalar`` (already closed over
        the model config).
      init_fn: ``init_fn(rng) -> params``.
      axes_tree: logical-axes pytree mirroring params (see models.*).
      mesh: the device mesh (parallel.mesh.create_mesh).
      strategy: rule-table name in parallel.sharding.STRATEGIES.
      optimizer: optax transformation (default: adamw 3e-4).
      accum_steps: microbatches per optimizer update.
      batch_extra_axes: logical axes of batch dims after "batch"
        (e.g. ("seq",) for token arrays under sequence parallelism).
      frozen: a tree of bools mirroring params, True at the leaves
        the optimizer leaves as they are: its update there is
        dropped, weight decay with it (a buffer kept among the
        parameters, such as a router's selection bias).
      move_buffers: ``(loss_and_seen, move)``, a rule of the model's
        own for such buffers (a selection bias moved by the step's
        load). ``loss_and_seen(params, batch) -> (loss, seen)`` is
        differentiated in ``loss_fn``'s place, ``seen`` summed over
        the step's microbatches, and ``move(params, seen) -> params``
        applied with the optimizer's update. Not beside a
        ``value_and_grad`` of the caller's own.
    """

    def __init__(
        self,
        loss_fn: Callable,
        init_fn: Callable,
        axes_tree: Any,
        mesh: Mesh,
        strategy: str = "fsdp",
        optimizer: Optional[optax.GradientTransformation] = None,
        accum_steps: int = 1,
        batch_extra_axes: Tuple[Optional[str], ...] = ("seq",),
        value_and_grad: Optional[Callable] = None,
        frozen: Any = None,
        move_buffers: Optional[Tuple[Callable, Callable]] = None,
    ):
        if move_buffers is not None and value_and_grad is not None:
            raise ValueError(
                "move_buffers brings the function that is "
                "differentiated (loss_and_seen): give no "
                "value_and_grad beside it"
            )
        self.mesh = mesh
        self.rules = shd.get_rules(strategy)
        self.strategy = strategy
        self.accum_steps = accum_steps
        self.optimizer = optimizer or optax.adamw(3e-4)
        self._loss_fn = loss_fn
        self._init_fn = init_fn
        # custom (params, batch) -> (loss, grads), e.g. optim.wsam's
        # sharpness-aware double evaluation
        self._value_and_grad = value_and_grad
        self._frozen = frozen
        self._move_buffers = move_buffers
        self.param_shardings = shd.tree_shardings(
            axes_tree, mesh, self.rules
        )
        self.batch_sharding = shd.batch_sharding(
            mesh, self.rules, batch_extra_axes
        )
        # ZeRO-1/2: optimizer state (and for zero2 the grad buffer) laid
        # out under its own rule table while params stay replicated
        self.opt_shardings = None
        self._grad_shardings = None
        opt_rules = shd.opt_state_rules(strategy)
        if opt_rules is not None:
            abs_params = jax.eval_shape(init_fn, jax.random.key(0))
            abs_opt = jax.eval_shape(self.optimizer.init, abs_params)
            opt_param_shards = shd.tree_shardings(
                axes_tree, mesh, opt_rules
            )
            self.opt_shardings = shd.opt_state_shardings(
                abs_opt, abs_params, opt_param_shards, mesh
            )
        g_rules = shd.grad_rules(strategy)
        if g_rules is not None:
            self._grad_shardings = shd.tree_shardings(
                axes_tree, mesh, g_rules
            )
        self._jit_init = None
        self._jit_step = None

    # -- init ------------------------------------------------------------
    def init(self, rng: jax.Array):
        """Initialize (params, opt_state) in the layout
        ``abstract_state()`` names: the one a restore gives. (Left to
        propagation, Adam's moments come out replicated under ``fsdp``:
        zeros propagate nothing.)
        """
        if self._jit_init is None:

            def _init(rng):
                params = self._init_fn(rng)
                opt_state = self.optimizer.init(params)
                return params, opt_state

            self._jit_init = jax.jit(
                _init,
                out_shardings=jax.tree.map(
                    lambda a: a.sharding, self.abstract_state()
                ),
            )
        with self.mesh:
            return self._jit_init(rng)

    def abstract_state(self):
        """``(params, opt_state)`` as ShapeDtypeStructs in the layout
        of the strategy: a restore target that costs no second state
        on the device, and what an ahead-of-time lowering takes."""
        abs_params = jax.eval_shape(self._init_fn, jax.random.key(0))
        abs_opt = jax.eval_shape(self.optimizer.init, abs_params)
        opt_shardings = self.opt_shardings or shd.opt_state_shardings(
            abs_opt, abs_params, self.param_shardings, self.mesh
        )

        def place(tree, shardings):
            return jax.tree.map(
                lambda a, s: jax.ShapeDtypeStruct(
                    a.shape, a.dtype, sharding=s
                ),
                tree, shardings,
            )

        return (
            place(abs_params, self.param_shardings),
            place(abs_opt, opt_shardings),
        )

    # -- train step ------------------------------------------------------
    @property
    def train_step(self):
        """``step(params, opt_state, batch) -> (params, opt_state, loss)``.

        ``batch`` leaves have a leading microbatch axis of length
        ``accum_steps`` (use :meth:`microbatch`); each microbatch's leading
        dim is the per-step global batch, sharded over data axes.
        """
        if self._jit_step is not None:
            return self._jit_step

        accum = self.accum_steps
        gshard = self._grad_shardings
        if self._move_buffers is not None:
            loss_and_seen, move = self._move_buffers
            grad_fn = jax.value_and_grad(loss_and_seen, has_aux=True)
        else:
            # one form for both: nothing seen beside the loss
            move = None
            plain_grad_fn = self._value_and_grad or jax.value_and_grad(
                self._loss_fn
            )

            def grad_fn(params, mb):
                loss, grads = plain_grad_fn(params, mb)
                return (loss, None), grads

        def constrain_grads(grads):
            if gshard is None:
                return grads
            return jax.tree.map(
                jax.lax.with_sharding_constraint, grads, gshard
            )

        def step(params, opt_state, batch):
            batch = jax.tree.map(
                lambda x: jax.lax.with_sharding_constraint(
                    x,
                    NamedSharding(
                        self.mesh,
                        P(None, *self.batch_sharding.spec),
                    ),
                ),
                batch,
            )
            # stable names in the lowered program and the device
            # trace: "loss" is forward and backward, "optimizer" the
            # update and its application
            if accum == 1:
                with jax.named_scope("loss"):
                    (loss, seen), grads = grad_fn(
                        params, jax.tree.map(lambda x: x[0], batch)
                    )
                grads = constrain_grads(grads)
            else:

                def micro(carry, mb):
                    loss_sum, grads_sum = carry
                    with jax.named_scope("loss"):
                        (loss, seen), grads = grad_fn(params, mb)
                    grads = constrain_grads(grads)
                    return (
                        loss_sum + loss,
                        jax.tree.map(jnp.add, grads_sum, grads),
                    ), seen

                zeros = constrain_grads(jax.tree.map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), params
                ))
                (loss_sum, grads_sum), seen = jax.lax.scan(
                    micro, (jnp.zeros(()), zeros), batch
                )
                # a microbatch a row (None where nothing is seen)
                seen = jax.tree.map(lambda a: jnp.sum(a, axis=0), seen)
                loss = loss_sum / accum
                # summed in f32, handed on in the params' dtype: the
                # optimizer then sees what it sees without accumulation
                # (f32 grads would turn bf16 Adam moments into f32 ones
                # at the first update — twice the state, and a second
                # compile for the step that takes it back in)
                grads = jax.tree.map(
                    lambda g, p: (g / accum).astype(p.dtype),
                    grads_sum, params,
                )
            with jax.named_scope("optimizer"):
                updates, opt_state = self.optimizer.update(
                    grads, opt_state, params
                )
                if self._frozen is not None:
                    updates = jax.tree.map(
                        lambda u, still: jnp.zeros_like(u) if still else u,
                        updates, self._frozen,
                    )
                params = optax.apply_updates(params, updates)
                if move is not None:
                    params = move(params, seen)
            return params, opt_state, loss

        self._jit_step = jax.jit(
            step,
            donate_argnums=(0, 1),
            out_shardings=(
                self.param_shardings, self.opt_shardings, None,
            ),
        )
        return self._jit_step

    # -- data helpers ----------------------------------------------------
    @property
    def microbatch_sharding(self) -> NamedSharding:
        """Sharding of a [accum, batch, ...] microbatched array — the
        single source of truth for shard_batch and external loaders
        (DevicePrefetch)."""
        return NamedSharding(
            self.mesh, P(None, *self.batch_sharding.spec)
        )

    def microbatch(self, batch):
        """[global_batch, ...] -> [accum, global_batch/accum, ...]."""
        a = self.accum_steps
        return jax.tree.map(
            lambda x: x.reshape((a, x.shape[0] // a) + x.shape[1:]), batch
        )

    def shard_batch(self, batch):
        """Device-put numpy microbatches with the strategy's layout."""
        sh = self.microbatch_sharding
        return jax.tree.map(lambda x: jax.device_put(x, sh), batch)


def make_trainer_for_llama(
    cfg,
    mesh: Optional[Mesh] = None,
    strategy: str = "fsdp",
    accum_steps: int = 1,
    optimizer: Optional[optax.GradientTransformation] = None,
    attn_fn=None,
) -> ShardedTrainer:
    """Convenience constructor for the flagship model."""
    from dlrover_tpu.models import llama

    if mesh is None:
        mesh = create_mesh([(shd.DATA_AXIS, 1), (shd.FSDP_AXIS, -1)])
    rules = shd.get_rules(strategy)
    constrain = None
    if mesh.size > 1:
        # pin the activations to the rule table's own axes: every
        # matmul's input and output stay on their batch shards, so the
        # only way left to resolve a weight's sharded contraction dim
        # is to gather the weight (and reduce-scatter its gradient).
        # Left free, GSPMD moves the smaller operand: it all-to-alls
        # the activations onto the weight's shards and gathers the MLP
        # hidden back whole. NamedShardings, so the loss can be jitted
        # on its own outside a mesh context.
        def constrain(x, logical_axes):
            return shd.constrain(x, mesh, rules, logical_axes)

    if attn_fn is None and strategy == "sequence":
        # the sequence strategy's entire point: without ring attention
        # GSPMD gathers K/V and materializes the [seq, seq] scores —
        # at 16k that is a silent gigabyte-scale dense fallback
        from dlrover_tpu.parallel.context_parallel import (
            make_context_parallel_attn,
        )

        attn_fn = make_context_parallel_attn(mesh, kind="ring")
    elif attn_fn is None and mesh.size > 1:
        # GSPMD cannot partition a Pallas kernel: hand each device its
        # own sequences (and, under a tensor axis, its own kv-head
        # groups) explicitly — attention needs no collective for either
        from dlrover_tpu.ops.attention import make_sharded_attention

        attn_fn = make_sharded_attention(
            mesh,
            q_spec=shd.spec_for_axes(
                ("batch", None, "heads", None), rules, mesh
            ),
            kv_spec=shd.spec_for_axes(
                ("batch", None, "kv_heads", None), rules, mesh
            ),
        )
    # experts sharded over an ``expert`` axis take the capacity-bucketed
    # einsum path; with every expert on each device the routing is
    # dropless (parallel/moe.py): the mesh decides, no flag
    expert_parallel = mesh.shape.get(shd.EXPERT_AXIS, 1) > 1
    if cfg.num_experts > 0:
        # a dropless config on such a mesh is refused here, before
        # anything is traced
        llama._expert_mlp(cfg, expert_parallel)
    # where the step gathers each layer's weights (ZeRO-3), the
    # gradients' reduce-scatters get deadlines inside the layer, or the
    # compiler leaves every ring's last hop for the end of the layer
    # loop's body (models/llama.py _tie): the mesh and the rule table
    # decide, no flag
    param_axes = llama.param_axes(cfg)
    gathered_weights = shd.gathers_params(
        param_axes["period" if cfg.by_position else "blocks"], mesh, rules
    )
    gauge(
        "dlrover_trainer_gradient_deadlines",
        "1 where the layers of the trainer's step tie their weights' "
        "gradients to the backward's own progress, else 0",
    ).set(int(gathered_weights))
    operator_layers = llama.operator_layers(cfg)
    alone = set(llama.ONE_DEVICE_OPERATORS) & set(operator_layers)
    if mesh.size > 1 and alone:
        raise ValueError(
            f"{sorted(alone)} layers take a device's whole sequences "
            "through kernels that no shard_map wraps yet: a mesh of "
            f"{dict(mesh.shape)}, were it over the batch alone, would "
            "hand the partitioner a kernel, and is refused, not guessed"
        )
    layers_gauge = gauge(
        "dlrover_model_operator_layers",
        "layers of the trainer's model whose operator is `operator`, "
        "prediction modules' blocks among them",
        ("operator",),
    )
    for operator in llama.OPERATORS:  # 0 where the last model had some
        layers_gauge.labels(operator=operator).set(
            operator_layers.get(operator, 0))
    model = dict(
        cfg=cfg, attn_fn=attn_fn, constrain=constrain,
        expert_parallel=expert_parallel, gathered_weights=gathered_weights,
    )
    loss = lambda params, batch: llama.next_token_loss(  # noqa: E731
        params, batch, **model
    )
    move_buffers = None
    if cfg.moe_bias_update_rate:
        # the rule that moves the selection bias reads the assignments
        # of the step's own forward pass: they leave beside the loss
        move_buffers = (
            lambda params, batch: llama.loss_and_expert_counts(
                params, batch, **model
            ),
            lambda params, counts: llama.moved_expert_bias(
                params, counts, cfg
            ),
        )
    init = lambda rng: llama.init_params(rng, cfg)  # noqa: E731
    logger.info(
        "ShardedTrainer: %s params=%.1fM operator_layers=%s mesh=%s "
        "strategy=%s accum=%d gradient_deadlines=%d",
        type(cfg).__name__, llama.param_count(cfg) / 1e6,
        operator_layers, dict(mesh.shape), strategy, accum_steps,
        gathered_weights,
    )
    return ShardedTrainer(
        loss, init, param_axes, mesh, strategy=strategy,
        optimizer=optimizer, accum_steps=accum_steps,
        frozen=llama.frozen_params(cfg), move_buffers=move_buffers,
    )
