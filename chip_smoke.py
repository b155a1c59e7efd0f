#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that ray_tpu still starts on the chip.

Drives the two engines this repo is for, through the entry points a user
calls, at the full width of llama-654m (all sixteen layers, random
weights from --seed), in ONE process (one process owns a chip):

  0 device   jax.devices() is a TPU whose kind is in the peak table
  1 kernels  flash attention fwd+bwd compiled for the device (a
             tpu_custom_call in the program), against a float32 reference
  2 trainer  TpuTrainer(...).fit() under ray_tpu.init(): five optimizer
             steps, loss finite and falling, then the state released
  3 server   LLMEngine answering concurrent submit() calls plus one
             request through serve.run and the HTTP proxy on loopback;
             correctness on logits against a plain forward

`--chips 4` runs instead, and only, what exists across chips: the same
five steps under ParallelPlan.auto(4) against the one-device loss
trajectory, and the engine at tp=4 against the one-chip engine.

Every phase prints one JSON line: name, ok, compile and run seconds,
compilations and persistent-cache hits counted in the phase, device
memory at its end, each measured error beside its tolerance, and on
failure the traceback — flushed before the process exits non-zero. A
failed phase stops the run. Only a run in which every phase passed
prints the last line, `{"ok": true, "device": {...}}`, and exits 0.

The script calls no git, needs no network, and writes only under the
checkout (.chip_smoke/) and the compile-cache directory. It fails at
phase 0 where JAX finds no accelerator; there is no CPU mode. The tests
rehearse the phase functions at a tiny width on the CPU by calling them
with their own sizes (tests/test_chip_smoke.py).
"""

from __future__ import annotations

import argparse
import collections
import dataclasses
import gc
import json
import math
import os
import shutil
import sys
import threading
import time
import traceback
import urllib.request
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

ROOT = os.path.dirname(os.path.abspath(__file__))
SCRATCH = os.path.join(ROOT, ".chip_smoke")

# -- tolerances ------------------------------------------------------------
# All for bf16 inputs/activations on the MXU, whose default precision
# rounds matmul operands to bf16 (8 significant bits, half-ulp 2^-9 =
# 0.002 relative). A tolerance set on the CPU (tests/test_ops.py: 2e-5
# on float32 interpret-mode kernels) fails a correct kernel here. Each
# was set from the error budget in its comment, then checked against
# what the chip measured (CHANGES.md, PR 22, lists measured vs. bound).
#
# Flash kernel vs float32 HIGHEST reference on the same bf16 inputs,
# max |a-b| / max |ref|. Forward: P is rounded to bf16 before P·V and
# the output is rounded to bf16: ~2 roundings of 2^-9 on values near the
# maximum, x2.5 margin.
FLASH_FWD_TOL = 1e-2
# Backward: dS, P and dO are each rounded to bf16 before their matmuls
# and dq/dk/dv are bf16 sums over up to 4096 rows whose roundings add in
# quadrature; twice the forward bound.
FLASH_BWD_TOL = 2e-2
# Engine logits vs a plain forward of the same bf16 weights, absolute.
# Logits of the random 654M model have std 0.02*sqrt(1536) = 0.78 and
# reach |3|, where one bf16 ulp is 2^-6 = 0.0156; two code paths through
# sixteen bf16 layers may differ by a few ulps.
LOGIT_TOL = 8e-2
# The engine at tp=4 vs the one-chip engine. Each chip rounds its partial
# sum of the two row-parallel matmuls per layer (wo, w_down) to bf16
# before the cross-chip sum: 32 more roundings along the residual stream
# than one chip's float32 accumulation makes. Three times the one-chip
# bound (measured: 0.108 against 0.038 on one chip, my chip runs, PR 22).
TP_LOGIT_TOL = 3 * LOGIT_TOL
# Sharded vs one-device loss at the same step, absolute on a loss near
# ln(32768) = 10.4: same seed, same batch, different reduction orders.
LOSS_TOL = 5e-2
# Most loaded device over least loaded, for state that should be sharded
# evenly over four chips.
BALANCE_TOL = 1.25
# What may stay on the device after a phase released its state: the
# compiled programs' constants (44 MB after phase 3, my chip run, PR 22),
# far below anything a phase could leak (bf16 weights: 1.3 GB).
RELEASE_SLACK_BYTES = 256 << 20
RELEASE_WAIT_S = 20.0


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What a run is sized by. `real()` is the only set main() uses."""

    train_cfg: Any
    serve_cfg: Any
    batch: int
    seq: int
    steps: int
    slots: int
    max_seq_len: int
    prompt_len: int
    new_tokens: Tuple[int, ...]      # one concurrent request each
    http_new_tokens: int
    flash: Tuple[int, int, int, int, int]   # B, S, H, KVH, D
    seed: int = 0

    @staticmethod
    def real(seed: int = 0) -> "Sizes":
        import jax.numpy as jnp

        from ray_tpu.models import configs

        cfg = configs.llama_654m()
        return Sizes(
            train_cfg=cfg,
            serve_cfg=dataclasses.replace(
                cfg, param_dtype=jnp.bfloat16, max_seq_len=1024),
            batch=8, seq=1024, steps=5,
            slots=16, max_seq_len=1024, prompt_len=128,
            # Mixed budgets so that two decode block sizes compile here
            # (16 then 8) and a third (32) for the lone HTTP request.
            new_tokens=(24, 12, 24, 12, 24, 24), http_new_tokens=24,
            # Above the kv crossover, so the natural dispatch compiles
            # the kernels: the 654M head geometry at S=4096.
            flash=(1, 4096, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim),
            seed=seed)


# -- the phase runner ------------------------------------------------------

class SmokeFailed(Exception):
    """A phase failed; its line (with the traceback) is already out."""


class Report:
    """What one phase has to say: notes, and measured-vs-tolerance checks
    that are all recorded before any of them fails the phase."""

    def __init__(self) -> None:
        self.notes: Dict[str, Any] = {}
        self.checks: List[Dict[str, Any]] = []

    def note(self, **kv: Any) -> None:
        self.notes.update(kv)

    def check(self, name: str, measured: float, tolerance: float) -> None:
        """Passes when measured <= tolerance (NaN never does)."""
        measured = float(measured)
        self.checks.append({"name": name, "measured": measured,
                            "tolerance": tolerance,
                            "ok": bool(measured <= tolerance)})

    def require(self, name: str, ok: bool, detail: Any = None) -> None:
        """A yes/no check; `detail` is printed when it fails."""
        self.checks.append({"name": name, "ok": bool(ok)}
                           | ({} if ok else {"detail": detail}))


class CompileCounter:
    """Compile requests, their seconds, and persistent-cache hits and
    writes, from jax's own monitoring events (process-wide: the
    trainer's loop thread and the engine's thread count too)."""

    _EVENTS = {"/jax/compilation_cache/cache_hits": "cache_hits",
               "/jax/compilation_cache/cache_misses": "cache_writes"}
    _COMPILE = "/jax/core/compile/backend_compile_duration"
    _FRONT = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration")

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.counts = {"compilations": 0, "compile_s": 0.0,
                       "cache_hits": 0, "cache_writes": 0}

    def install(self) -> None:
        import jax.monitoring as mon

        mon.register_event_listener(self._on_event)
        mon.register_event_duration_secs_listener(self._on_duration)

    def uninstall(self) -> None:
        import jax.monitoring as mon

        mon.unregister_event_listener(self._on_event)
        mon.unregister_event_duration_listener(self._on_duration)

    def _on_event(self, event: str, **_kw: Any) -> None:
        key = self._EVENTS.get(event)
        if key:
            with self._lock:
                self.counts[key] += 1

    def _on_duration(self, event: str, duration_secs: float,
                     **_kw: Any) -> None:
        if event == self._COMPILE or event in self._FRONT:
            with self._lock:
                self.counts["compile_s"] += duration_secs
                self.counts["compilations"] += event == self._COMPILE

    def snapshot(self) -> Dict[str, float]:
        with self._lock:
            return dict(self.counts)


_MEMORY_KEYS = ("bytes_in_use", "peak_bytes_in_use")


def device_memory() -> Dict[str, List[Optional[int]]]:
    """memory_stats() of every device, one list per key (None where the
    backend keeps no statistics; phase 0 requires them on a TPU)."""
    import jax

    stats = [d.memory_stats() or {} for d in jax.devices()]
    return {k: [st.get(k) for st in stats] for k in _MEMORY_KEYS}


def live_bytes() -> int:
    """Bytes of every jax array this process still references."""
    import jax

    return int(sum(a.nbytes for a in jax.live_arrays()))


class Runner:
    """Runs phases in order, one JSON line each; the first failure
    prints its traceback in its line and ends the run."""

    def __init__(self, out=None) -> None:
        self.out = out if out is not None else sys.stdout
        self.compiles = CompileCounter()

    def emit(self, obj: Dict[str, Any]) -> None:
        self.out.write(json.dumps(obj) + "\n")
        self.out.flush()

    def phase(self, name: str, fn: Callable[..., Any], *args: Any) -> Any:
        rep = Report()
        before = self.compiles.snapshot()
        t0 = time.perf_counter()
        tb = result = None
        try:
            result = fn(rep, *args)
            bad = [c["name"] for c in rep.checks if not c["ok"]]
            if bad:
                raise SmokeFailed(f"checks failed: {', '.join(bad)}")
        except BaseException:  # noqa: BLE001 — reported, then fatal
            tb = traceback.format_exc()
        wall = time.perf_counter() - t0
        after = self.compiles.snapshot()
        line: Dict[str, Any] = {"phase": name, "ok": tb is None}
        line["compile_s"] = round(
            after["compile_s"] - before["compile_s"], 3)
        line["run_s"] = round(wall - line["compile_s"], 3)
        for k in ("compilations", "cache_hits", "cache_writes"):
            line[k] = after[k] - before[k]
        try:
            line["memory"] = device_memory()
            line["live_bytes"] = live_bytes()
        except Exception as e:  # noqa: BLE001 — no backend to ask
            line["memory"] = f"unavailable: {type(e).__name__}"
        line["checks"] = rep.checks
        line.update(rep.notes)
        if tb is not None:
            line["traceback"] = tb
        self.emit(line)
        if tb is not None:
            raise SmokeFailed(name)
        return result


def _release(rep: Report, baseline: Dict[str, Any]) -> None:
    """After a phase dropped its state: the device must be back to what
    it held at `baseline` (taken before the state existed). Killed
    actors' threads let go of their instances a moment after shutdown
    returns, so this waits, and says for how long."""
    t0 = time.perf_counter()
    while True:
        gc.collect()
        live = live_bytes() - baseline["live_bytes"]
        held = [None if a is None else a - b for b, a in zip(
            baseline["memory"]["bytes_in_use"],
            device_memory()["bytes_in_use"])]
        waited = time.perf_counter() - t0
        if waited > RELEASE_WAIT_S or max(
                [live] + [h for h in held if h is not None]
        ) <= RELEASE_SLACK_BYTES:
            break
        time.sleep(0.2)
    rep.note(release_waited_s=round(waited, 2))
    rep.check("live_bytes_after_release", live, RELEASE_SLACK_BYTES)
    for i, h in enumerate(held):
        if h is not None:
            rep.check(f"device{i}_bytes_in_use_after_release", h,
                      RELEASE_SLACK_BYTES)


def _baseline() -> Dict[str, Any]:
    gc.collect()
    return {"memory": device_memory(), "live_bytes": live_bytes()}


# -- phase 0: device -------------------------------------------------------

def phase_device(rep: Report, chips: int) -> Dict[str, Any]:
    import jax
    import jaxlib

    from ray_tpu._private import compile_cache
    from ray_tpu._private.accelerators import chip_peaks

    devs = jax.devices()
    d = devs[0]
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(devs)}
    rep.note(device=device, jax=jax.__version__, jaxlib=jaxlib.__version__,
             pid=os.getpid())
    if d.platform != "tpu":
        raise RuntimeError(
            f"chip_smoke needs a TPU and JAX found {len(devs)} x "
            f"{d.platform}:{d.device_kind} (JAX_PLATFORMS="
            f"{os.environ.get('JAX_PLATFORMS')!r}). There is no CPU "
            "mode: run it through the chip tool.")
    if len(devs) < chips:
        raise RuntimeError(
            f"--chips {chips} needs {chips} devices, JAX found "
            f"{len(devs)}")
    rep.note(peaks=chip_peaks(d))   # raises for an unknown kind
    if None in device_memory()["bytes_in_use"]:
        raise RuntimeError("device.memory_stats() has no bytes_in_use")
    rep.note(compile_cache_dir=compile_cache.enable(),
             compile_cache_from_env=bool(
                 os.environ.get(compile_cache.ENV)))
    return device


# -- phase 1: kernels ------------------------------------------------------

def _rel_err(a, b) -> float:
    import jax.numpy as jnp

    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))


def _attention_dispatch() -> "collections.Counter[str]":
    """A copy of the kernel-vs-reference counts (counted at trace time)."""
    from ray_tpu.ops.flash_attention import DISPATCH_COUNTS

    return collections.Counter(DISPATCH_COUNTS)


def phase_kernels(rep: Report, sz: Sizes) -> None:
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.flash_attention import (
        FLASH_GRID,
        _reference,
        flash_attention,
    )

    B, S, H, KVH, D = sz.flash
    kq, kk, kv, kg = jax.random.split(jax.random.key(sz.seed), 4)
    q = jax.random.normal(kq, (B, S, H, D), jnp.bfloat16)
    k = jax.random.normal(kk, (B, S, KVH, D), jnp.bfloat16)
    v = jax.random.normal(kv, (B, S, KVH, D), jnp.bfloat16)
    g = jax.random.normal(kg, (B, S, H, D), jnp.bfloat16)  # cotangent

    def weighted(attn):
        def f(q, k, v):
            return jnp.sum(attn(q, k, v).astype(jnp.float32)
                           * g.astype(jnp.float32))
        return f

    def kernel(q, k, v):   # the dispatch the models use, untouched
        return flash_attention(q, k, v, causal=True)

    def reference(q, k, v):   # float32, HIGHEST, same bf16 inputs
        qt, kt, vt = (jnp.swapaxes(x.astype(jnp.float32), 1, 2)
                      for x in (q, k, v))
        out, _ = _reference(   # a kv head a query head: only here
            qt, jnp.repeat(kt, H // KVH, axis=1),
            jnp.repeat(vt, H // KVH, axis=1),
            jnp.zeros((1, 2), jnp.float32),
            sm_scale=1.0 / math.sqrt(D), causal=True)
        return jnp.swapaxes(out, 1, 2)

    counts0, grid0 = _attention_dispatch(), collections.Counter(FLASH_GRID)
    fwd = jax.jit(kernel).lower(q, k, v).compile()
    bwd = jax.jit(jax.grad(weighted(kernel), argnums=(0, 1, 2))
                  ).lower(q, k, v).compile()
    dispatch = dict(_attention_dispatch() - counts0)
    n_fwd = fwd.as_text().count("tpu_custom_call")
    n_bwd = bwd.as_text().count("tpu_custom_call")
    rep.note(shape={"B": B, "S": S, "H": H, "KVH": KVH, "D": D,
                    "dtype": "bfloat16"},
             dispatch=dispatch, tpu_custom_calls_fwd=n_fwd,
             tpu_custom_calls_bwd=n_bwd,
             grid=dict(collections.Counter(FLASH_GRID) - grid0))
    rep.require("fwd_program_has_tpu_custom_call", n_fwd > 0)
    rep.require("bwd_program_has_tpu_custom_call", n_bwd > 0)
    rep.require("dispatch_counted_pallas_only",
                set(dispatch) == {"pallas"}, dispatch)

    with jax.default_matmul_precision("highest"):
        ref_o = jax.jit(reference)(q, k, v)
        ref_g = jax.jit(jax.grad(weighted(reference),
                                 argnums=(0, 1, 2)))(q, k, v)
    out = fwd(q, k, v)
    grads = bwd(q, k, v)
    rep.require("fwd_finite", bool(jnp.all(jnp.isfinite(
        out.astype(jnp.float32)))))
    rep.check("fwd_rel_err", _rel_err(out, ref_o), FLASH_FWD_TOL)
    for name, a, b in zip(("dq", "dk", "dv"), grads, ref_g):
        rep.check(f"bwd_{name}_rel_err", _rel_err(a, b), FLASH_BWD_TOL)
    _latent_kernels(rep, sz)


def _latent_kernels(rep: Report, sz: Sizes) -> None:
    """The two kernels as latent attention calls them (models/latent.py),
    one shot each against their plain forms in float32: the flash forward
    with keys 192 wide over values 128 wide, and the decode kernel with
    one array for keys and values (rows of 512 + 64 in whole lanes under
    128 query heads; one slot holds no row, one ends inside a block)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import decode_attention as da
    from ray_tpu.ops.flash_attention import flash_attention

    S, H, dk, dv = sz.flash[1], 16, 192, 128
    ks = jax.random.split(jax.random.key(sz.seed + 1), 5)
    q = jax.random.normal(ks[0], (1, S, H, dk), jnp.bfloat16)
    k = jax.random.normal(ks[1], (1, S, H, dk), jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, S, H, dv), jnp.bfloat16)
    scale = 1.0 / math.sqrt(dk)
    out = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, causal=True, sm_scale=scale, force_pallas=True))(q, k, v)
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(lambda q, k, v: flash_attention(
            q, k, v, causal=True, sm_scale=scale, force_reference=True))(
                *(x.astype(jnp.float32) for x in (q, k, v)))
    rep.check("flash_fwd_192_over_128_rel_err", _rel_err(out, ref),
              FLASH_FWD_TOL)

    L, B, rows, C, kvr, heads = 2, 4, sz.flash[1], 640, 512, 128
    c_all = jax.random.normal(ks[3], (L, B, rows, C), jnp.bfloat16)
    qd = jax.random.normal(ks[4], (B, 1, heads, C), jnp.bfloat16)
    n = jnp.asarray([rows, 0, rows // 2 + 3, 1], jnp.int32)
    rep.require("latent_rows_tile", da.usable(c_all, C, kvr))
    got = jax.jit(lambda q, c, n: da.decode_attention(
        q, c, None, jnp.int32(1), n, sm_scale=scale, v_width=kvr))(
            qd, c_all, n).reshape(B, heads, kvr)

    def plain(q, c, n):
        held = c[1].astype(jnp.float32)
        s = jnp.einsum("bhc,bsc->bhs", q[:, 0].astype(jnp.float32),
                       held) * scale
        seen = jnp.arange(rows)[None, None, :] < n[:, None, None]
        p = jnp.where(seen, jax.nn.softmax(
            jnp.where(seen, s, -jnp.inf), axis=-1), 0.0)
        return jnp.einsum("bhs,bsc->bhc", p, held[..., :kvr])

    with jax.default_matmul_precision("highest"):
        want = jax.jit(plain)(qd, c_all, n)
    rep.require("latent_decode_empty_slot_is_zero",
                not bool(jnp.any(got[1].astype(jnp.float32))))
    rep.check("latent_decode_rel_err", _rel_err(got, want), FLASH_FWD_TOL)


# -- phase 2: trainer ------------------------------------------------------

def _train_loop(config: Dict[str, Any]) -> None:
    """train_loop_per_worker: runs in the TpuTrainer's worker actor."""
    import jax
    import jax.numpy as jnp

    import ray_tpu
    import ray_tpu.train as train
    from ray_tpu.train.step import (
        init_state,
        make_optimizer,
        make_train_step,
        shard_batch,
    )

    sz: Sizes = config["sizes"]
    cfg = sz.train_cfg
    mesh = train.get_mesh()
    # 3e-4 as bench.py trains at. At 1e-3 straight after one warm-up
    # step the 654M loss bounces (10.10 -> 10.57 -> 9.33, my chip run,
    # PR 22) and a 4.5e-4 difference in the first loss between one and
    # four chips grew to 7e-2 by step 4: a comparison of trajectories
    # needs a stable one.
    opt = make_optimizer(lr=3e-4, warmup_steps=1, total_steps=100)
    with jax.sharding.set_mesh(mesh):
        state = init_state(cfg, mesh, opt, seed=sz.seed)
        step_fn = make_train_step(cfg, opt)
        tokens = jax.random.randint(
            jax.random.key(sz.seed + 1), (sz.batch, sz.seq), 0,
            cfg.vocab_size)
        b = shard_batch(
            {"tokens": tokens, "targets": jnp.roll(tokens, -1, axis=1),
             "mask": jnp.ones_like(tokens, dtype=jnp.float32)}, mesh)
        args = (b["tokens"], b["targets"], b["mask"])
        # Compile ahead of time so the text of the program that runs can
        # be read: is the pallas kernel on this step or not?
        counts0 = _attention_dispatch()
        t0 = time.perf_counter()
        step = step_fn.lower(state, *args).compile()
        compile_s = time.perf_counter() - t0
        dispatch = dict(_attention_dispatch() - counts0)
        state_mem = device_memory()
        for i in range(sz.steps):
            t0 = time.perf_counter()
            state, metrics = step(state, *args)
            loss = float(jax.block_until_ready(metrics["loss"]))
            train.report({"step": i + 1, "loss": loss,
                          "step_s": time.perf_counter() - t0})
        train.report({
            "final": True, "pid": os.getpid(),
            "thread": threading.current_thread().name,
            "mesh": {k: int(n) for k, n in mesh.shape.items() if n > 1},
            "n_params": sum(int(x.size)
                            for x in jax.tree.leaves(state.params)),
            "train_step_compile_s": compile_s,
            "train_step_tpu_custom_calls":
                step.as_text().count("tpu_custom_call"),
            "attention_dispatch": dispatch,
            "memory_with_state": state_mem,
            "memory_after_steps": device_memory(),
            "tpu_available_while_claimed":
                ray_tpu.available_resources().get("TPU", 0.0),
        })


def run_trainer(rep: Report, sz: Sizes, plan, tpus: int, label: str
                ) -> List[float]:
    """Five steps through TpuTrainer.fit() under ray_tpu.init(); returns
    the loss trajectory. The state lives and dies inside the loop."""
    import ray_tpu
    from ray_tpu.train import RunConfig, ScalingConfig, TpuTrainer

    base = _baseline()
    ray_tpu.init()
    try:
        tpu_total = ray_tpu.cluster_resources().get("TPU", 0.0)
        result = TpuTrainer(
            _train_loop,
            train_loop_config={"sizes": sz},
            # tpus_per_worker: the scheduler's TPU resource is really
            # claimed; the default local runtime runs the worker actor
            # as a thread of this process, which owns the chip.
            scaling_config=ScalingConfig(
                num_workers=1, tpus_per_worker=tpus, plan=plan),
            run_config=RunConfig(
                name=f"chip_smoke_{label}",
                storage_path=os.path.join(SCRATCH, "train")),
        ).fit()
    finally:
        ray_tpu.shutdown()
    if result.error is not None:
        raise result.error
    steps = [m for m in result.metrics_history if "loss" in m]
    final = result.metrics
    losses = [m["loss"] for m in steps]
    rep.note(**{label: {
        "plan": plan.describe(), "losses": losses,
        "step_s": [round(m["step_s"], 4) for m in steps],
        **{k: v for k, v in final.items() if k != "final"},
        "driver_pid": os.getpid(), "tpu_resources": tpu_total}})
    rep.require(f"{label}_ran_all_steps", len(losses) == sz.steps,
                len(losses))
    rep.require(f"{label}_losses_finite",
                all(math.isfinite(x) for x in losses), losses)
    rep.require(f"{label}_loss_fell", losses[-1] < losses[0], losses)
    rep.require(f"{label}_loop_in_driver_process",
                final.get("pid") == os.getpid(),
                {"loop": final.get("pid"), "driver": os.getpid()})
    rep.require(f"{label}_scheduler_tpu_claimed",
                final.get("tpu_available_while_claimed")
                == tpu_total - tpus,
                {"total": tpu_total, "claimed": tpus,
                 "left": final.get("tpu_available_while_claimed")})
    # The program says which attention ran; the counter must agree.
    rep.require(f"{label}_dispatch_counter_agrees_with_program",
                (final["train_step_tpu_custom_calls"] > 0)
                == (final["attention_dispatch"].get("pallas", 0) > 0),
                final["attention_dispatch"])
    _release(rep, base)
    return losses


def phase_trainer(rep: Report, sz: Sizes) -> List[float]:
    from ray_tpu.parallel import ParallelPlan

    return run_trainer(rep, sz, ParallelPlan(), 1, "one_device")


# -- phase 3: server -------------------------------------------------------

def _prompts(sz: Sizes, n: int) -> List[List[int]]:
    import numpy as np

    rng = np.random.default_rng(sz.seed)
    return [rng.integers(0, sz.serve_cfg.vocab_size,
                         size=sz.prompt_len).tolist() for _ in range(n)]


def _answer(engine, prompts: Sequence[Sequence[int]],
            new_tokens: Sequence[int]) -> Dict[str, Any]:
    """Concurrent submit() calls against a started engine."""
    t0 = time.perf_counter()
    reqs = [engine.submit(p, max_new_tokens=n)
            for p, n in zip(prompts, new_tokens)]
    outs = [r.result(timeout=900) for r in reqs]
    wall = time.perf_counter() - t0
    return {"tokens": outs, "wall_s": wall,
            "ttft_s": [round(r.ttft_s, 4) for r in reqs],
            "tokens_per_s": sum(len(o) for o in outs) / wall}


def _prefill_logits(cfg, params, prompts, mesh=None):
    """First-token logits (R, V) from the engine's batched prefill core
    on one of the engine's own admission tiles (R <= the tile's rows)."""
    import contextlib

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models.generate import _prefill_batch_core
    from ray_tpu.serve.llm import LLMEngine, _init_kv_cache

    W, R = LLMEngine._ADMIT_TILE, len(prompts)
    bucket = max(len(p) for p in prompts)
    buf, lens, _ = LLMEngine._build_tile(bucket, W,
                                         [(p, 0.0) for p in prompts])
    slot_idx = np.full((W,), W, np.int32)   # padding rows drop
    slot_idx[:R] = np.arange(R)
    with (jax.sharding.set_mesh(mesh) if mesh is not None
          else contextlib.nullcontext()):
        _, logits, _extras = jax.jit(_prefill_batch_core,
                                     static_argnums=(0,))(
            cfg, params, _init_kv_cache(cfg, W, bucket), jnp.asarray(buf),
            jnp.asarray(lens), jnp.asarray(slot_idx))
    return np.asarray(logits)[:R]


def _forward_logits(cfg, params, seqs: Sequence[Sequence[int]]):
    """Plain forward (the training model, no cache) over padded
    sequences: (R, S, V) float32 logits as a numpy array."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models.transformer import forward

    S = -(-max(len(s) for s in seqs) // 8) * 8
    buf = np.zeros((len(seqs), S), np.int32)
    for j, s in enumerate(seqs):
        buf[j, :len(s)] = s
    logits, _ = jax.jit(forward, static_argnums=(0,))(
        cfg, params, jnp.asarray(buf))
    return np.asarray(logits)


def _check_tokens(rep: Report, name: str, prompts, answers, ref_logits,
                  tol: float = LOGIT_TOL) -> None:
    """Generated tokens against the reference's argmax, teacher-forced
    on the engine's own sequence, and only where the reference's top-2
    margin exceeds twice the logit tolerance `tol`: a random model's
    logits are near flat, and below that margin either token is a right
    answer."""
    import numpy as np

    checked = skipped = wrong = 0
    for j, (p, toks) in enumerate(zip(prompts, answers)):
        for i, tok in enumerate(toks):
            row = ref_logits[j, len(p) - 1 + i]
            if _top2_margin(row) > 2 * tol:
                checked += 1
                wrong += int(tok != int(np.argmax(row)))
            else:
                skipped += 1
    rep.note(**{name: {"positions_checked": checked,
                       "positions_skipped_low_margin": skipped,
                       "mismatches": wrong}})
    rep.require(f"{name}_some_positions_decidable", checked > 0)
    rep.require(f"{name}_match_reference_argmax", wrong == 0, wrong)


def _top2_margin(row) -> float:
    import numpy as np

    top2 = np.partition(row, -2)[-2:]
    return float(top2[1] - top2[0])


def _stop_engine(engine) -> None:
    """Stop and wait for the loop thread, which holds the weights."""
    engine.stop()
    engine._loop_thread.join(timeout=30)
    if engine._loop_thread.is_alive():
        raise RuntimeError("engine loop did not stop")


def _http_request(sz: Sizes, prompt: List[int]) -> Dict[str, Any]:
    """One request through serve.run and the HTTP proxy on loopback, as
    examples/serve_llm.py does: the replica actor claims the scheduler's
    TPU and runs in this process, its engine on the chip."""
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.train.trainer import _free_port

    @serve.deployment(ray_actor_options={"num_tpus": 1})
    class Llm:
        def __init__(self):
            from ray_tpu.serve.llm import LLMServer

            self.server = LLMServer(
                sz.serve_cfg, num_slots=sz.slots,
                max_seq_len=sz.max_seq_len, seed=sz.seed)

        def __call__(self, payload):
            out = self.server.generate(
                payload["prompt"], max_new_tokens=payload["max_tokens"])
            return {"tokens": out["tokens"], "ttft_s": out["ttft_s"],
                    "pid": os.getpid()}

        def stop(self):
            _stop_engine(self.server.engine)

    port = _free_port()
    ray_tpu.init()
    try:
        handle = serve.run(Llm.bind(), name="llm", http=True,
                           http_port=port)
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/llm",
            data=json.dumps({"prompt": prompt,
                             "max_tokens": sz.http_new_tokens}).encode(),
            headers={"Content-Type": "application/json"})
        t0 = time.perf_counter()
        with urllib.request.urlopen(req, timeout=900) as r:
            status, body = r.status, json.load(r)
        out = dict(body["result"], status=status,
                   wall_s=time.perf_counter() - t0)
        handle.stop.remote().result(timeout=60)
    finally:
        serve.shutdown()
        ray_tpu.shutdown()
    return out


def phase_server(rep: Report, sz: Sizes) -> None:
    import jax
    import numpy as np

    from ray_tpu.models.generate import greedy_generate
    from ray_tpu.models.transformer import init_params
    from ray_tpu.ops import decode_attention
    from ray_tpu.serve.llm import LLMEngine

    base = _baseline()
    cfg = sz.serve_cfg
    prompts = _prompts(sz, len(sz.new_tokens) + 1)
    direct, http_prompt = prompts[:-1], prompts[-1]
    params = init_params(cfg, jax.random.key(sz.seed))
    counts0 = _attention_dispatch()
    engine = LLMEngine(cfg, params, num_slots=sz.slots,
                       max_seq_len=sz.max_seq_len, seed=sz.seed)
    # Where the cache's rows tile (they do at the real sizes) the decode
    # blocks read it through the kernel of ops/decode_attention.py,
    # fewer slots owned than the engine has; the tokens are held to the
    # plain forward below either way.
    by_kernel = decode_attention.usable(engine.cache.k, cfg.head_dim)
    engine.start()
    try:
        ans = _answer(engine, direct, sz.new_tokens)
        stats = engine.stats()
    finally:
        _stop_engine(engine)
    traced = (_attention_dispatch() - counts0).get("decode_attn", 0)
    rep.note(engine={"requests": len(direct),
                     "new_tokens": [len(t) for t in ans["tokens"]],
                     "ttft_s": ans["ttft_s"],
                     "wall_s": round(ans["wall_s"], 3),
                     "tokens_per_s": round(ans["tokens_per_s"], 1),
                     "decode_ticks": stats["decode_ticks"],
                     "decode_attn_kernels_traced": traced,
                     "cache_rows_held": stats["counts"]["cache_rows_held"],
                     "cache_rows": stats["counts"]["cache_rows"]},
             memory_with_engine=device_memory())
    rep.require("engine_answered_every_request",
                [len(t) for t in ans["tokens"]] == list(sz.new_tokens))
    rep.require("decode_blocks_read_the_cache_through_the_kernel",
                traced > 0 if by_kernel else traced == 0,
                {"usable": by_kernel, "traced": traced})

    http = _http_request(sz, http_prompt)
    rep.note(http={k: http[k] for k in ("status", "pid", "ttft_s",
                                        "wall_s")}
             | {"new_tokens": len(http["tokens"])})
    rep.require("http_200_and_full_answer",
                http["status"] == 200
                and len(http["tokens"]) == sz.http_new_tokens)
    rep.require("http_replica_in_driver_process",
                http["pid"] == os.getpid())

    # Correctness is on logits. Reference: one plain forward over each
    # prompt followed by the tokens the engine itself produced.
    answers = ans["tokens"] + [http["tokens"]]
    ref = _forward_logits(
        cfg, params, [p + t[:-1] for p, t in zip(prompts, answers)])
    first_ref = np.stack([ref[j, len(p) - 1]
                          for j, p in enumerate(prompts)])
    first_eng = _prefill_logits(cfg, params, prompts)
    rep.require("logits_finite", bool(np.isfinite(first_eng).all()
                                      and np.isfinite(ref).all()))
    rep.note(logits_std=float(first_ref.std()),
             logits_abs_max=float(np.abs(first_ref).max()))
    rep.check("first_token_logits_abs_err",
              float(np.abs(first_eng - first_ref).max()), LOGIT_TOL)
    _check_tokens(rep, "tokens_vs_forward", prompts, answers, ref)
    # greedy_generate, the repo's own reference generator, on the first
    # prompt. Token for token it need not agree on near-flat logits: the
    # two may part only where the reference could not decide either.
    mine = ans["tokens"][0]
    greedy = np.asarray(greedy_generate(
        cfg, params, np.asarray(direct[0], np.int32), len(mine))).tolist()
    parted = next((i for i, (a, b) in enumerate(zip(greedy, mine))
                   if a != b), None)
    rep.note(greedy_generate_first_divergence=parted)
    if parted is not None:
        rep.check("greedy_generate_divergence_margin",
                  _top2_margin(ref[0, len(direct[0]) - 1 + parted]),
                  2 * LOGIT_TOL)
    del params, engine   # what holds device memory
    _release(rep, base)


# -- four chips ------------------------------------------------------------

def _balance(rep: Report, name: str, vals: List[Optional[int]],
             whole: int) -> None:
    rep.note(**{name: vals})
    if None in vals:   # a backend without memory statistics
        return
    rep.check(f"{name}_max_over_min", max(vals) / max(1, min(vals)),
              BALANCE_TOL)
    # A model that sat whole on one device shows here.
    rep.require(f"{name}_no_device_holds_the_whole", max(vals) < whole,
                {"most_loaded": max(vals), "whole": whole})


def phase_trainer_sharded(rep: Report, sz: Sizes) -> List[float]:
    from ray_tpu.parallel import ParallelPlan

    losses = run_trainer(rep, sz, ParallelPlan.auto(4), 4, "four_chips")
    got = rep.notes["four_chips"]
    # f32 params + two Adam moments: what one device would hold whole.
    whole = 3 * 4 * got["n_params"]
    _balance(rep, "state_bytes_in_use_per_device",
             got["memory_with_state"]["bytes_in_use"][:4], whole)
    _balance(rep, "peak_bytes_per_device",
             got["memory_after_steps"]["peak_bytes_in_use"][:4], whole)
    return losses


def phase_trainer_reference(rep: Report, sz: Sizes, sharded: List[float]
                            ) -> None:
    one = phase_trainer(rep, sz)
    rep.note(loss_abs_err_by_step=[abs(a - b)
                                   for a, b in zip(sharded, one)])
    rep.check("sharded_vs_one_device_loss_abs_err",
              max(abs(a - b) for a, b in zip(sharded, one)), LOSS_TOL)


def phase_server_sharded(rep: Report, sz: Sizes) -> None:
    """The engine at tp=4 against the one-chip engine: same prompts,
    same seed; first-token logits and decidable tokens agree."""
    import jax
    import numpy as np

    from ray_tpu.models.transformer import init_params, init_params_sharded
    from ray_tpu.parallel import ParallelPlan, make_mesh
    from ray_tpu.serve.llm import LLMEngine

    base = _baseline()
    cfg = sz.serve_cfg
    prompts = _prompts(sz, len(sz.new_tokens))
    key = jax.random.key(sz.seed)
    whole = sum(math.prod(x.shape) * x.dtype.itemsize
                for x in jax.tree.leaves(
                    jax.eval_shape(lambda: init_params(cfg, key))))

    def serve_on(mesh):
        params = (init_params(cfg, key) if mesh is None
                  else init_params_sharded(cfg, key, mesh))
        engine = LLMEngine(cfg, params, num_slots=sz.slots,
                           max_seq_len=sz.max_seq_len, seed=sz.seed,
                           mesh=mesh)
        engine.start()
        try:
            ans = _answer(engine, prompts, sz.new_tokens)
            mem = device_memory()
        finally:
            _stop_engine(engine)
        logits = _prefill_logits(cfg, params, prompts, mesh)
        return params, ans, mem, logits

    # tp=4 first: bytes_in_use then shows this engine and nothing else.
    mesh = make_mesh(ParallelPlan(tp=4), devices=jax.devices()[:4])
    _, tp_ans, tp_mem, tp_logits = serve_on(mesh)
    _balance(rep, "tp4_engine_bytes_in_use_per_device",
             tp_mem["bytes_in_use"][:4], whole)
    gc.collect()
    params, one_ans, _, one_logits = serve_on(None)
    for label, a in (("tp4", tp_ans), ("one_chip", one_ans)):
        rep.note(**{f"{label}_engine": {
            "ttft_s": a["ttft_s"], "wall_s": round(a["wall_s"], 3),
            "tokens_per_s": round(a["tokens_per_s"], 1)}})
        rep.require(f"{label}_answered_every_request",
                    [len(t) for t in a["tokens"]] == list(sz.new_tokens))
    rep.note(whole_model_bytes=whole,
             logits_abs_max=float(np.abs(one_logits).max()))
    rep.check("tp4_vs_one_chip_first_token_logits_abs_err",
              float(np.abs(tp_logits - one_logits).max()), TP_LOGIT_TOL)
    # Both engines' tokens against one plain forward, on one chip.
    n = len(prompts)
    ref = _forward_logits(
        cfg, params,
        [p + t[:-1] for a in (tp_ans, one_ans)
         for p, t in zip(prompts, a["tokens"])])
    _check_tokens(rep, "tp4_tokens_vs_forward", prompts,
                  tp_ans["tokens"], ref[:n], TP_LOGIT_TOL)
    _check_tokens(rep, "one_chip_tokens_vs_forward", prompts,
                  one_ans["tokens"], ref[n:])
    del params
    _release(rep, base)


# -- entry -----------------------------------------------------------------

def one_chip(run: Runner, sz: Sizes) -> Dict[str, Any]:
    device = run.phase("0 device", phase_device, 1)
    run.phase("1 kernels", phase_kernels, sz)
    run.phase("2 trainer", phase_trainer, sz)
    run.phase("3 server", phase_server, sz)
    return device


def four_chips(run: Runner, sz: Sizes) -> Dict[str, Any]:
    device = run.phase("0 device", phase_device, 4)
    sharded = run.phase("4a trainer fsdp=4", phase_trainer_sharded, sz)
    run.phase("4b trainer one device", phase_trainer_reference, sz,
              sharded)
    run.phase("4c server tp=4 vs one chip", phase_server_sharded, sz)
    return device


def main(argv: Optional[Sequence[str]] = None, out=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the paths across four chips and what "
                         "they are compared with")
    ap.add_argument("--seed", type=int, default=0,
                    help="weights, batch and prompts are made from it")
    args = ap.parse_args(argv)

    # The runtime's session directory and the trainer's storage stay
    # inside the checkout (read when ray_tpu is first imported).
    shutil.rmtree(SCRATCH, ignore_errors=True)
    os.environ.setdefault("RAY_TPU_TMPDIR", os.path.join(SCRATCH, "tmp"))

    run = Runner(out)
    run.compiles.install()
    try:
        sz = Sizes.real(args.seed)
        device = (one_chip if args.chips == 1 else four_chips)(run, sz)
    except SmokeFailed:
        return 1
    except BaseException:  # noqa: BLE001 — before the first phase
        run.emit({"phase": "start", "ok": False,
                  "traceback": traceback.format_exc()})
        return 1
    finally:
        run.compiles.uninstall()
    run.emit({"ok": True, "device": device})
    return 0


if __name__ == "__main__":
    sys.exit(main())
