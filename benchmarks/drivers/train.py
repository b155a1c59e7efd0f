"""Training: `TpuTrainer.fit()` with the cell's `ParallelPlan`, the loop
that a user of the trainer writes (`init_state`, `make_train_step`),
steps closed by `block_until_ready`. Token ids come from `--seed`; the
batch shape comes from the traffic file."""

from __future__ import annotations

import math
import os
import time
from typing import Any, Dict

from lib import harness, modelcfg, reference, stats

# Loss of the sharded step at step 0 against the float32 reference loss
# of the same batch, absolute, on a loss near 11.85 (ln(vocab) = 11.4
# and the spread of the untrained logits). The step computes in bf16
# activations with f32 weights and reductions: its per-token error
# averages out over 32k tokens. Measured on the chip: 6.4e-5 to 3.7e-4
# over 13 runs and 7 seeds (my chip runs, PR 24); the bound is four
# times the largest. What it can tell apart: the targets are random, so
# the per-token loss scatters by about 0.9 and the mean of 32,768 of
# them by 0.005; a wrong shift or mask is another draw of that mean and
# passes this bound about one time in five, while logits computed in
# anything narrower than bf16 move the loss by far more. Per-token
# log-probabilities would be sharper; the step returns only the mean.
LOSS_TOL = 1.5e-3


def _loop(config: Dict[str, Any]) -> None:
    """train_loop_per_worker: runs in the trainer's worker."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import ray_tpu.train as train
    from ray_tpu.train.step import (
        init_state,
        make_optimizer,
        make_train_step,
        shard_batch,
    )

    # The worker is a thread of this process (one process owns the
    # chips), so the run's context is handed over by key, not pickled.
    ctx, cfg = harness.CONTEXTS[config["ctx_key"]], config["cfg"]
    tr = ctx.spec.traffic
    mesh = train.get_mesh()
    batch, seq = int(tr["batch_size"]), int(tr["seq_len"])
    n_batches = int(tr.get("distinct_batches", 4))
    opt = make_optimizer(lr=float(tr.get("lr", 3e-4)), warmup_steps=1,
                         total_steps=100_000)
    with jax.sharding.set_mesh(mesh):
        state = init_state(cfg, mesh, opt, seed=(ctx.seed ^ (ctx.seed >> 31))
                           & 0x7FFFFFFF)
        step_fn = make_train_step(cfg, opt)
        ids = jax.jit(lambda k: jax.random.randint(
            k, (n_batches, batch, seq + 1), 0, cfg.vocab_size))(
                modelcfg.seed_key(ctx.seed + 1))
        batches = []
        for i in range(n_batches):
            b = shard_batch({"tokens": ids[i, :, :-1],
                             "targets": ids[i, :, 1:],
                             "mask": jnp.ones((batch, seq), jnp.float32)},
                            mesh)
            batches.append((b["tokens"], b["targets"], b["mask"]))
        # The reference's loss of the first batch under the weights of
        # step 0, before the step donates them.
        host = np.asarray(ids[0])
        ref_loss = reference.loss(ctx.spec.config, state.params,
                                  host[:, :-1], host[:, 1:])
        step = step_fn.lower(state, *batches[0]).compile()
        custom_calls = step.as_text().count("tpu_custom_call")
        state, metrics = step(state, *batches[0])
        loss0 = float(jax.block_until_ready(metrics["loss"]))
        for i in range(int(tr.get("warm_steps", 2))):
            state, metrics = step(state, *batches[(i + 1) % n_batches])
            jax.block_until_ready(metrics["loss"])

        t_open = ctx.open_window()
        ends, losses, n = [], [], 0
        while True:
            with ctx.span("step_dispatch"):
                state, metrics = step(state, *batches[n % n_batches])
            with ctx.span("block_until_ready"):
                losses.append(float(jax.block_until_ready(metrics["loss"])))
            n += 1
            ends.append(time.monotonic())
            if ends[-1] - t_open >= ctx.seconds:
                break
        ctx.close_window()
    train.report({
        "final": True, "t_open": t_open, "ends": ends, "losses": losses,
        "loss0": loss0, "ref_loss": ref_loss,
        "custom_calls": custom_calls, "pid": os.getpid(),
        "mesh": {k: int(v) for k, v in mesh.shape.items() if v > 1}})


def run(ctx, devs) -> Dict[str, Any]:
    import ray_tpu
    from ray_tpu.parallel import ParallelPlan
    from ray_tpu.train import RunConfig, ScalingConfig, TpuTrainer

    sizes, tr = ctx.spec.sizes, ctx.spec.traffic
    cfg = modelcfg.transformer_config(ctx.spec.config, sizes)
    plan = ParallelPlan(**sizes["plan"])
    harness.CONTEXTS[ctx.spec.name] = ctx
    ray_tpu.init()
    try:
        result = TpuTrainer(
            _loop, train_loop_config={"ctx_key": ctx.spec.name, "cfg": cfg},
            scaling_config=ScalingConfig(
                num_workers=1,
                tpus_per_worker=0 if ctx.rehearse else ctx.spec.chips,
                plan=plan),
            run_config=RunConfig(
                name="bench_" + ctx.spec.name,
                storage_path=os.path.join(ctx.out_dir, "train")),
        ).fit()
    finally:
        ray_tpu.shutdown()
    if result.error is not None:
        raise result.error
    m = result.metrics
    ends, t_open = m["ends"], m["t_open"]
    chips = ctx.spec.chips
    tokens_step = int(tr["batch_size"]) * int(tr["seq_len"])
    elapsed = ends[-1] - t_open
    rate_chip = len(ends) * tokens_step / elapsed / chips
    step_ms = [(b - a) * 1e3 for a, b in zip([t_open] + ends[:-1], ends)]
    loss_err = abs(m["loss0"] - m["ref_loss"])
    finite = all(math.isfinite(x) for x in m["losses"])
    return {
        "correct": bool(finite and loss_err <= LOSS_TOL),
        "attempted": len(ends), "failed": 0 if finite else 1,
        "end_to_end": {"train_tok_s_chip": rate_chip},
        "info": {"steps": len(ends), "elapsed_s": elapsed,
                 "loss0": m["loss0"], "ref_loss": m["ref_loss"],
                 "loss_abs_err": loss_err, "loss_tol": LOSS_TOL,
                 "loss_last": m["losses"][-1], "mesh": m["mesh"],
                 "tpu_custom_calls": m["custom_calls"],
                 "step_ms_p50": stats.percentile(step_ms, 50)},
        "measure": {"step_ms": step_ms, "tokens_per_step": tokens_step,
                    "step_intervals": list(zip([t_open] + ends[:-1], ends)),
                    "tok_s_chip": rate_chip, "arch": ctx.spec.config,
                    "seq_len": int(tr["seq_len"]),
                    "batch_size": int(tr["batch_size"]),
                    "custom_calls": m["custom_calls"]},
    }
