"""SPMD train step.

One jitted function over the whole mesh: forward, backward, optimizer update.
GSPMD inserts every collective (gradient reductions over data/fsdp, activation
collectives over tensor/sequence) from the sharding annotations — there is no
hand-written gradient allreduce anywhere, which is exactly what replaces the
reference's PS/Horovod machinery (SURVEY.md §2.2). State is donated so
parameters and optimizer slots update in place in HBM.
"""

from __future__ import annotations

from typing import Any

import chex
import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from kubeflow_tpu.models.registry import ModelSpec
from kubeflow_tpu.observability.tracing import SCOPE_OPTIMIZER, scope
from kubeflow_tpu.parallel.sharding import tree_shardings
from kubeflow_tpu.train.optimizers import OptimizerConfig, build as build_opt


@chex.dataclass
class TrainState:
    step: Any
    params: Any
    opt_state: Any


def state_shardings(state: TrainState, mesh: Mesh, model: ModelSpec):
    """Shardings for the whole TrainState in one pass: the model's path rules
    match the param pytree and, because rules are substring regexes, the same
    param subpaths inside optimizer slots (`opt_state/…/mu/layers/attn/wq`);
    scalars (step, counts, schedules) fall through to replicated P()."""
    rules = model.partition_rules(model.config)
    return tree_shardings(mesh, state, rules)


def init_state(
    key,
    model: ModelSpec,
    opt_cfg: OptimizerConfig,
    mesh: Mesh | None = None,
) -> TrainState:
    """Initialize params + optimizer state, sharded over ``mesh`` at creation
    (jitted init with out_shardings — weights are born distributed, no
    host-memory spike for large models)."""
    opt = build_opt(opt_cfg)

    def make_state():
        params = model.init(key, model.config)
        return TrainState(
            step=jnp.zeros((), jnp.int32),
            params=params,
            opt_state=opt.init(params),
        )

    if mesh is None:
        return make_state()
    abstract = jax.eval_shape(make_state)
    shardings = state_shardings(abstract, mesh, model)
    return jax.jit(make_state, out_shardings=shardings)()


def build_train_step(model: ModelSpec, opt_cfg: OptimizerConfig,
                     mesh: Mesh | None = None, *, accum_steps: int = 1):
    """Returns jitted ``(state, batch) -> (state, metrics)`` with donated
    state.

    ``accum_steps > 1`` turns the step into gradient-accumulation
    microbatching: ``batch`` leaves carry a leading [accum_steps, ...]
    axis (data.stack_microbatches) and the step scans the microbatches,
    accumulating the MEAN gradient in the gradient dtype
    (``opt_cfg.grad_dtype`` or the param dtype) before ONE optimizer
    update — effective batch ``accum_steps × batch_size`` at the HBM
    footprint of a single microbatch. Averaging microbatch-mean grads
    equals the grad of the equivalent single large batch, so loss/grad
    parity holds to dtype tolerance (pinned in tests). The accumulator
    lives in the scan carry, which XLA updates in place (donated
    buffers), and composes with every mesh axis: the scan axis is
    replicated, each microbatch keeps the model's batch sharding.
    """
    if accum_steps < 1:
        raise ValueError(f"accum_steps must be >= 1, got {accum_steps}")
    opt = build_opt(opt_cfg)

    def grads_of(params, batch):
        def loss_of(p):
            return model.loss_fn(p, batch, model.config, mesh=mesh)

        diff_params = params
        if opt_cfg.grad_dtype:
            gdt = jnp.dtype(opt_cfg.grad_dtype)
            diff_params = jax.tree.map(
                lambda p: p.astype(gdt)
                if jnp.issubdtype(p.dtype, jnp.floating) else p,
                params,
            )
        (loss, metrics), grads = jax.value_and_grad(loss_of, has_aux=True)(
            diff_params
        )
        return loss, dict(metrics), grads

    @scope(SCOPE_OPTIMIZER)
    def apply_update(state, metrics, grads):
        updates, opt_state = opt.update(grads, state.opt_state, state.params)
        params = optax.apply_updates(state.params, updates)
        state_updates = metrics.pop("_state_updates", None)
        if state_updates is not None and model.update_state is not None:
            params = model.update_state(params, state_updates)
        metrics["grad_norm"] = optax.global_norm(grads)
        metrics["step"] = state.step
        return (
            TrainState(step=state.step + 1, params=params,
                       opt_state=opt_state),
            metrics,
        )

    def step_fn(state: TrainState, batch):
        _, metrics, grads = grads_of(state.params, batch)
        return apply_update(state, metrics, grads)

    def accum_step_fn(state: TrainState, batch):
        def body(acc, microbatch):
            _, metrics, grads = grads_of(state.params, microbatch)
            # Divide per-microbatch: the accumulator holds a running
            # MEAN, so low-precision grad dtypes never see a k×-scaled
            # partial sum.
            acc = jax.tree.map(
                lambda a, g: a + g.astype(a.dtype) / accum_steps,
                acc, grads)
            return acc, metrics

        zeros = jax.tree.map(
            lambda p: jnp.zeros(
                p.shape,
                jnp.dtype(opt_cfg.grad_dtype)
                if opt_cfg.grad_dtype and jnp.issubdtype(p.dtype,
                                                         jnp.floating)
                else p.dtype),
            state.params)
        grads, metrics = jax.lax.scan(body, zeros, batch)
        state_updates = metrics.pop("_state_updates", None)
        # Scalar metrics average over microbatches (mean loss over the
        # effective batch = mean of equal-size microbatch means); the
        # non-gradient state channel keeps the LAST microbatch's updates,
        # matching the trajectory of sequential small steps.
        metrics = {k: jnp.mean(v, axis=0) for k, v in metrics.items()}
        if state_updates is not None:
            metrics["_state_updates"] = jax.tree.map(
                lambda x: x[-1], state_updates)
        return apply_update(state, metrics, grads)

    fn = accum_step_fn if accum_steps > 1 else step_fn
    if mesh is None:
        return jax.jit(fn, donate_argnums=0)

    batch_spec = model.batch_partition_spec(model.config)
    lead = (None,) if accum_steps > 1 else ()

    def sharded_step(state, batch):
        # Truncate the spec to each leaf's rank: a rank-4 image spec must
        # not be applied to the rank-1 labels riding the same batch. The
        # accumulation scan axis (leading dim) stays replicated.
        def leaf_sharding(x):
            spec = lead + tuple(batch_spec)[: x.ndim - len(lead)]
            spec += (None,) * (x.ndim - len(spec))
            return NamedSharding(mesh, P(*spec))

        batch = jax.lax.with_sharding_constraint(
            batch, jax.tree.map(leaf_sharding, batch),
        )
        return fn(state, batch)

    return jax.jit(sharded_step, donate_argnums=0)
