"""Mesh-sharded GRPO learner — the RLHF pipeline's learner plane.

`rl/grpo.py` runs GRPO single-chip with its own adam state; this module
is the model-scale variant: the learner takes a `ParallelPlan`, holds a
`train.step.TrainState` initialized directly into its target shardings
(dp/fsdp/tp — same `init_state` path the trainer uses), and runs
advantage normalization + the clipped update inside ONE jitted SPMD
program over the mesh. Rollout data arrives from the serve engine's
logprob capture (`GenRequest.logprobs`) — the ratio term's
old-policy logps are recorded at sampling time, never recomputed with a
second forward.

Reference capability: RLlib's LearnerGroup sharding a learner across
GPUs (rllib/core/learner/learner_group.py:71); here the "group" is one
SPMD program and XLA inserts the gradient collectives.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from ..models.transformer import (
    TransformerConfig,
    forward,
    param_logical_axes,
)
from ..parallel.mesh import make_mesh
from ..parallel.plan import ParallelPlan
from ..parallel.sharding import logical_to_sharding, tree_shardings
from ..train.step import TrainState, init_state, make_optimizer


@dataclass(frozen=True)
class GRPOLearnerConfig:
    model: TransformerConfig = field(
        default_factory=lambda: TransformerConfig(
            vocab_size=128, d_model=64, n_layers=2, n_heads=4,
            n_kv_heads=4, d_ff=128, max_seq_len=64,
            dtype=jnp.float32, param_dtype=jnp.float32, remat=False))
    group_size: int = 4
    clip_eps: float = 0.2
    kl_coef: float = 0.02
    lr: float = 1e-4
    warmup_steps: int = 5
    total_steps: int = 1000
    grad_clip: float = 1.0
    weight_decay: float = 0.0
    seed: int = 0


def make_grpo_step(cfg: GRPOLearnerConfig, optimizer, *,
                   param_pspecs=None):
    """→ jitted step(state, tokens, old_logp, rewards, comp_mask) →
    (state, metrics), call under `jax.sharding.set_mesh(mesh)`.

    Advantage normalization happens IN-JIT from the raw rewards —
    rewards arrive batch-sharded like everything else and the group
    mean/std reductions run on-device, so the whole iteration is one
    SPMD program. `param_pspecs` pins the updated params' at-rest
    shardings (same ZeRO-drift hazard make_train_step documents).
    """
    mcfg = cfg.model
    G = cfg.group_size

    def _loss(params, tokens, old_logp, advantages, comp_mask):
        logits, _ = forward(mcfg, params, tokens)
        lp_all = jax.nn.log_softmax(
            logits[:, :-1, :].astype(jnp.float32), axis=-1)
        lp = jnp.take_along_axis(
            lp_all, tokens[:, 1:, None], axis=-1)[..., 0]
        ratio = jnp.exp(lp - old_logp)
        adv = advantages[:, None]
        unclipped = ratio * adv
        clipped = jnp.clip(ratio, 1 - cfg.clip_eps,
                           1 + cfg.clip_eps) * adv
        pg = jnp.minimum(unclipped, clipped)
        # k3 KL estimator against the sampling policy.
        log_r = old_logp - lp
        kl = jnp.exp(log_r) - log_r - 1.0
        per_tok = -(pg - cfg.kl_coef * kl) * comp_mask
        denom = jnp.maximum(comp_mask.sum(), 1.0)
        loss = per_tok.sum() / denom
        return loss, {"pg_loss": -(pg * comp_mask).sum() / denom,
                      "kl": (kl * comp_mask).sum() / denom}

    @partial(jax.jit, donate_argnums=(0,))
    def grpo_step(state: TrainState, tokens, old_logp, rewards,
                  comp_mask) -> Tuple[TrainState, Dict[str, jax.Array]]:
        groups = rewards.reshape(-1, G)
        mean = groups.mean(axis=1, keepdims=True)
        std = groups.std(axis=1, keepdims=True) + 1e-6
        advantages = ((groups - mean) / std).reshape(-1)
        (loss, metrics), grads = jax.value_and_grad(
            _loss, has_aux=True)(state.params, tokens, old_logp,
                                 advantages, comp_mask)
        updates, opt_state = optimizer.update(
            grads, state.opt_state, state.params)
        params = optax.apply_updates(state.params, updates)
        if param_pspecs is not None:
            params = jax.lax.with_sharding_constraint(
                params, param_pspecs)
        new_state = TrainState(
            step=state.step + 1, params=params, opt_state=opt_state)
        return new_state, {"loss": loss,
                           "reward_mean": rewards.mean(),
                           "grad_norm": optax.global_norm(grads),
                           **metrics}

    return grpo_step


class GRPOLearner:
    """GRPO update plane over a `ParallelPlan` mesh.

    `update()` takes one rollout batch (host numpy), shards it onto the
    mesh, and runs the jitted sharded step; `param_blocks()` exposes
    the current policy as size-balanced leaf blocks for the relay
    weight refresh; get_state/set_state round-trip through host arrays
    while PRESERVING the live sharding layout on restore.
    """

    def __init__(self, cfg: GRPOLearnerConfig,
                 plan: Optional[ParallelPlan] = None, *, devices=None):
        self.cfg = cfg
        self.plan = plan or ParallelPlan()
        self.mesh = make_mesh(self.plan, devices=devices)
        self.optimizer = make_optimizer(
            cfg.lr, warmup_steps=cfg.warmup_steps,
            total_steps=cfg.total_steps, weight_decay=cfg.weight_decay,
            grad_clip=cfg.grad_clip)
        self.state = init_state(cfg.model, self.mesh, self.optimizer,
                                seed=cfg.seed)
        p_pspecs = jax.tree.map(
            lambda s: s.spec,
            tree_shardings(param_logical_axes(cfg.model), self.mesh))
        self._step = make_grpo_step(cfg, self.optimizer,
                                    param_pspecs=p_pspecs)
        # Leaf order is the weight-refresh wire contract: param_blocks
        # ships (leaf index, array) pairs and the rollout side
        # reassembles against its own flatten of the same model config.
        self._treedef = jax.tree.structure(self.state.params)

    @property
    def step_count(self) -> int:
        return int(jax.device_get(self.state.step))

    # -- update -------------------------------------------------------

    def _place(self, arr: np.ndarray, axes) -> jax.Array:
        return jax.device_put(
            jnp.asarray(arr), logical_to_sharding(axes, self.mesh))

    def update(self, tokens: np.ndarray, old_logp: np.ndarray,
               rewards: np.ndarray,
               comp_mask: np.ndarray) -> Dict[str, float]:
        """One GRPO update from a rollout batch.

        tokens (N, S) int32 full sequences (prompt + completion);
        old_logp (N, S-1) f32 sampling-time logp of tokens[:, 1:]
        (zeros where comp_mask is zero); rewards (N,) raw sequence
        rewards, N = num_groups * group_size ordered group-major;
        comp_mask (N, S-1) f32 completion mask over the shifted axis.
        """
        N = tokens.shape[0]
        if N % self.cfg.group_size:
            raise ValueError(
                f"batch of {N} sequences is not a multiple of "
                f"group_size={self.cfg.group_size}")
        with jax.sharding.set_mesh(self.mesh):
            self.state, metrics = self._step(
                self.state,
                self._place(np.asarray(tokens, np.int32),
                            ("batch", "seq")),
                self._place(np.asarray(old_logp, np.float32),
                            ("batch", "seq")),
                self._place(np.asarray(rewards, np.float32),
                            ("batch",)),
                self._place(np.asarray(comp_mask, np.float32),
                            ("batch", "seq")))
        return {k: float(v) for k, v in metrics.items()}

    # -- weight publication -------------------------------------------

    def param_blocks(self, num_blocks: int = 8):
        """Current policy as `num_blocks` contiguous, byte-balanced
        blocks of (leaf index, host array) pairs — the unit the
        pipeline `put()`s so the relay broadcast pipelines block-sized
        transfers instead of one monolithic object. Sharded leaves
        gather to host here (the producer pays one device→host copy
        per refresh; the object plane owns all further fan-out)."""
        leaves = jax.tree.leaves(self.state.params)
        host = jax.device_get(leaves)
        sizes = [x.nbytes for x in host]
        total = max(sum(sizes), 1)
        num_blocks = max(1, min(num_blocks, len(host)))
        per_block = total / num_blocks
        blocks, cur, acc = [], [], 0
        for i, x in enumerate(host):
            cur.append((i, np.asarray(x)))
            acc += sizes[i]
            if acc >= per_block * (len(blocks) + 1) \
                    and len(blocks) < num_blocks - 1:
                blocks.append(cur)
                cur = []
        if cur:
            blocks.append(cur)
        return blocks

    def params_host(self):
        """Full policy pytree on host (tiny-model tests/checkpoints)."""
        return jax.device_get(self.state.params)

    # -- state round-trip ---------------------------------------------

    def get_state(self) -> Dict[str, Any]:
        return {"step": int(jax.device_get(self.state.step)),
                "params": jax.device_get(self.state.params),
                "opt_state": jax.device_get(self.state.opt_state)}

    def set_state(self, state: Dict[str, Any]) -> None:
        """Restore from host arrays, re-placing every leaf into the
        sharding the LIVE state uses — a restored learner must hold
        the same dp/fsdp layout it trains with, not silently-replicated
        host uploads (that would double memory under fsdp and recompile
        the step)."""
        live = (self.state.params, self.state.opt_state)
        shardings = jax.tree.map(lambda x: x.sharding, live)
        # Checkpoint IO rewrites containers (optax namedtuples come
        # back as dicts, EmptyState as None) — rebuild against the
        # live treedef by leaf order before placing.
        restored = jax.tree.unflatten(
            jax.tree.structure(live),
            jax.tree.leaves((state["params"], state["opt_state"])))
        params, opt_state = jax.device_put(restored, shardings)
        self.state = TrainState(
            step=jnp.asarray(int(state["step"]), jnp.int32),
            params=params, opt_state=opt_state)


def aot_compile_grpo_step(cfg: GRPOLearnerConfig, plan: ParallelPlan,
                          *, batch: int, seq: int, devices) -> None:
    """XLA-compile the sharded GRPO update from abstract inputs — the
    8B dryrun path: proves the learner's shardings/collectives/memory
    plan at north-star scale without materializing the weights."""
    import jax.tree_util as jtu

    from ..models.transformer import init_params

    mesh = make_mesh(plan, devices=devices)
    optimizer = make_optimizer(
        cfg.lr, warmup_steps=cfg.warmup_steps,
        total_steps=cfg.total_steps, weight_decay=cfg.weight_decay,
        grad_clip=cfg.grad_clip)
    with jax.sharding.set_mesh(mesh):
        p_shardings = tree_shardings(param_logical_axes(cfg.model),
                                     mesh)
        p_struct = jtu.tree_map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                               sharding=sh),
            jax.eval_shape(lambda k: init_params(cfg.model, k),
                           jax.random.key(0)),
            p_shardings)
        state = TrainState(
            step=jax.ShapeDtypeStruct((), jnp.int32),
            params=p_struct,
            opt_state=jax.eval_shape(optimizer.init, p_struct))
        bsh = logical_to_sharding(("batch", "seq"), mesh)
        rsh = logical_to_sharding(("batch",), mesh)
        tok = jax.ShapeDtypeStruct((batch, seq), jnp.int32,
                                   sharding=bsh)
        lp = jax.ShapeDtypeStruct((batch, seq - 1), jnp.float32,
                                  sharding=bsh)
        rew = jax.ShapeDtypeStruct((batch,), jnp.float32, sharding=rsh)
        msk = jax.ShapeDtypeStruct((batch, seq - 1), jnp.float32,
                                   sharding=bsh)
        p_pspecs = jtu.tree_map(lambda s: s.spec, p_shardings)
        make_grpo_step(cfg, optimizer, param_pspecs=p_pspecs).lower(
            state, tok, lp, rew, msk).compile()
