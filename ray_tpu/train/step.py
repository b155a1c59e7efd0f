"""Functional SPMD training step.

TPU-native replacement for the reference's DDP/FSDP wrapping
(reference: python/ray/train/torch/train_loop_utils.py:74 prepare_model —
torch DDP/FSDP over NCCL): here a single jitted step over a Mesh; gradient
reduction, parameter sharding (FSDP) and tensor parallelism all come from
the shardings — XLA inserts psum/all-gather/reduce-scatter over ICI.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import optax

from ..models.transformer import (
    TransformerConfig,
    init_params,
    init_params_sharded,
    loss_fn,
    param_logical_axes,
)
from ..parallel.mesh import make_mesh
from ..parallel.plan import ParallelPlan
from ..parallel.sharding import logical_to_sharding, tree_shardings


class TrainState(NamedTuple):
    step: jax.Array
    params: Any
    opt_state: Any


def make_optimizer(lr: float = 3e-4, *, warmup_steps: int = 100,
                   total_steps: int = 10_000, weight_decay: float = 0.1,
                   b1: float = 0.9, b2: float = 0.95,
                   grad_clip: float = 1.0) -> optax.GradientTransformation:
    schedule = optax.warmup_cosine_decay_schedule(
        0.0, lr, warmup_steps, max(total_steps, warmup_steps + 1), lr * 0.1)
    return optax.chain(
        optax.clip_by_global_norm(grad_clip),
        optax.adamw(schedule, b1=b1, b2=b2, weight_decay=weight_decay),
    )


def opt_state_shardings(optimizer, params, p_shardings, mesh):
    """Target shardings for optimizer.init's output: leaves that mirror
    a param (adam mu/nu, ...) inherit that param's sharding, scalars
    (schedule/clip counts) replicate. Sharding CANNOT be left to GSPMD
    propagation here — optimizer.init is pure zeros_like with no data
    dependence on the params, so XLA drops the unused sharded inputs
    and the state comes back single-device (un-ZeRO'd, then relaid out
    + recompiled on the first step). Mirroring is keyed by tree-path
    suffix: the mu['layers']['wq'] leaf ends with the params'
    ['layers']['wq'] path; bracketed keys make suffix matches exact."""
    import jax.tree_util as jtu

    replicated = jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec())
    p_leaves = jtu.tree_flatten_with_path(p_shardings)[0]
    p_map = sorted(((jtu.keystr(path), sh) for path, sh in p_leaves),
                   key=lambda kv: -len(kv[0]))
    struct = jax.eval_shape(optimizer.init, params)
    flat, treedef = jtu.tree_flatten_with_path(struct)
    out = []
    for path, leaf in flat:
        ks = jtu.keystr(path)
        sh = next((psh for pk, psh in p_map if ks.endswith(pk)),
                  replicated)
        out.append(sh if getattr(leaf, "ndim", 0) else replicated)
    return jtu.tree_unflatten(treedef, out)


def init_state(cfg: TransformerConfig, mesh, optimizer,
               seed: int = 0) -> TrainState:
    """Initialize params directly into their target shardings (no host
    round-trip; each device materializes only its shard)."""
    p_shardings = tree_shardings(param_logical_axes(cfg), mesh)
    params = init_params_sharded(cfg, jax.random.key(seed), mesh)
    with jax.sharding.set_mesh(mesh):
        o_shardings = opt_state_shardings(
            optimizer, params, p_shardings, mesh)
        opt_state = jax.jit(
            optimizer.init, out_shardings=o_shardings)(params)
        step = jnp.zeros((), jnp.int32)
    return TrainState(step=step, params=params, opt_state=opt_state)


def make_train_step(cfg: TransformerConfig, optimizer, *, loss=None,
                    param_pspecs=None):
    """Returns step(state, tokens, targets, mask) -> (state, metrics),
    jit-compiled; call under `jax.sharding.set_mesh(mesh)`. `loss`
    overrides the loss closure (signature of loss_fn minus cfg).
    `param_pspecs` (pytree of PartitionSpecs matching params) pins the
    OUTPUT params' shardings — needed when params' at-rest sharding
    differs from what GSPMD would pick for the update math (ZeRO-1/2:
    updates compute fsdp-sharded, params must come back whole, or the
    state silently drifts to stage-3 sharding and recompiles)."""

    def _loss(params, tokens, targets, mask):
        if loss is not None:
            return loss(params, tokens, targets, mask)
        return loss_fn(cfg, params, tokens, targets, mask)

    @partial(jax.jit, donate_argnums=(0,))
    def train_step(state: TrainState, tokens, targets, mask
                   ) -> Tuple[TrainState, Dict[str, jax.Array]]:
        grad_fn = jax.value_and_grad(_loss, has_aux=True)
        (_, metrics), grads = grad_fn(state.params, tokens, targets, mask)
        # Forward and backward name themselves in `loss_fn` (`fwd`,
        # `loss_head`); the rest of the step is the optimizer's.
        with jax.named_scope("optimizer"):
            updates, opt_state = optimizer.update(
                grads, state.opt_state, state.params)
            params = optax.apply_updates(state.params, updates)
            if param_pspecs is not None:
                params = jax.lax.with_sharding_constraint(
                    params, param_pspecs)
            gnorm = optax.global_norm(grads)
        new_state = TrainState(
            step=state.step + 1, params=params, opt_state=opt_state)
        metrics = dict(metrics)
        metrics["grad_norm"] = gnorm
        return new_state, metrics

    return train_step


def shard_batch(batch: Dict[str, jax.Array], mesh) -> Dict[str, jax.Array]:
    """Place a host batch onto the mesh with (batch, seq) sharding."""
    sh = logical_to_sharding(("batch", "seq"), mesh)
    return {k: jax.device_put(v, sh) for k, v in batch.items()}


def init_pp_state(cfg: TransformerConfig, mesh, optimizer, *, pp: int,
                  seed: int = 0) -> TrainState:
    """init_state with the layer stack partitioned into pp stages, each
    leaf sharded (stage -> pp mesh axis) at init (no host round-trip)."""
    from ..parallel.pipeline import (
        partition_layer_params,
        pp_param_logical_axes,
    )

    p_shardings = tree_shardings(pp_param_logical_axes(cfg), mesh)

    @partial(jax.jit, out_shardings=p_shardings)
    def _init(key):
        params = init_params(cfg, key)
        params["layers"] = partition_layer_params(params["layers"], pp)
        return params

    with jax.sharding.set_mesh(mesh):
        params = _init(jax.random.key(seed))
        o_shardings = opt_state_shardings(
            optimizer, params, p_shardings, mesh)
        opt_state = jax.jit(
            optimizer.init, out_shardings=o_shardings)(params)
        step = jnp.zeros((), jnp.int32)
    return TrainState(step=step, params=params, opt_state=opt_state)


def make_pp_train_step(cfg: TransformerConfig, optimizer, *, pp: int,
                       num_microbatches: Optional[int] = None,
                       schedule: str = "gpipe"):
    """Pipelined train step, compiled into one jit (parallel/pipeline.py).
    Same signature as make_train_step.

    schedule:
      "gpipe" — forward scan + autodiff backward; residuals for all M
                microbatches live at once (fine for modest M).
      "1f1b"  — interleaved forward/backward with O(pp) in-flight
                microbatches per stage (the schedule that matters at
                real pp depths / large M).
    """
    if schedule == "gpipe":
        from ..parallel.pipeline import pipeline_loss_fn

        def _loss(params, tokens, targets, mask):
            return pipeline_loss_fn(
                cfg, params, tokens, targets, mask,
                pp=pp, num_microbatches=num_microbatches)

        return make_train_step(cfg, optimizer, loss=_loss)
    if schedule != "1f1b":
        raise ValueError(f"unknown pipeline schedule {schedule!r}")

    from ..parallel.pipeline import pipeline_1f1b_grads

    @partial(jax.jit, donate_argnums=(0,))
    def train_step(state: TrainState, tokens, targets, mask
                   ) -> Tuple[TrainState, Dict[str, jax.Array]]:
        grads, metrics = pipeline_1f1b_grads(
            cfg, state.params, tokens, targets, mask,
            pp=pp, num_microbatches=num_microbatches)
        updates, opt_state = optimizer.update(
            grads, state.opt_state, state.params)
        params = optax.apply_updates(state.params, updates)
        gnorm = optax.global_norm(grads)
        new_state = TrainState(
            step=state.step + 1, params=params, opt_state=opt_state)
        metrics = dict(metrics)
        metrics["grad_norm"] = gnorm
        return new_state, metrics

    return train_step


def make_eval_step(cfg: TransformerConfig):
    @jax.jit
    def eval_step(params, tokens, targets, mask):
        _, metrics = loss_fn(cfg, params, tokens, targets, mask)
        return metrics

    return eval_step
