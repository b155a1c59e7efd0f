"""What the serving drivers share: the engine built from a cell's files,
the correctness check against the plain reference, the warm-up of every
shape the cell's traffic can reach, and the one client thread that
offers the load and reads every token with a client-side stamp."""

from __future__ import annotations

import gc
import json
import os
import queue
import time
from typing import Any, Dict, List, Optional

import numpy as np

from . import modelcfg, reference, stats, traffic
from .harness import Context

POLL_S = 0.002          # how often the client looks at its streams
SAMPLE_S = 0.05         # how often it samples the engine's occupancy
# Engine logits against the float32 reference: the root-mean-square
# error over every logit checked, as a share of the logits' own
# root-mean-square. The weights are bf16 and every activation the program
# stores is rounded to bf16 (half-ulp 2^-9 = 0.002 relative); the
# reference rounds nothing. Measured on the chip as the largest error
# over the largest logit, which is a little above this ratio (the two
# agree within a fifth in a CPU emulation), the same at the prefill
# position and at every decode step: 0.044 to 0.054 for Mistral-7B-l16
# (0.25 to 0.34 on logits reaching |6|), 0.027 for InternLM2-1.8B, 0.037
# through the flash path at a 2,200-token prompt (my chip runs, PR 24:
# 10 readings). The error follows the size of the pre-activations, 0.02 x
# sqrt(width) under the program's fixed-sigma initialisation, and grows
# slowly with depth, which is why it is 0.013 at PR 22's 1536 and 0.046
# here (PERF.md, Findings). A single rounding to anything narrower than
# bf16 (fp8: 2^-4 = 0.06 a rounding, dozens of them) fails the bound.
LOGIT_REL_TOL = 0.08
# A token the engine streamed in the window, greedy, against the
# reference's logits for the same position (teacher-forced on the
# engine's own sequence): the reference's largest logit less its logit
# of that token, against twice LOGIT_REL_TOL of the largest logit. An
# engine whose every logit is within 0.08 of that scale (0.054 is the
# most measured) picks a token at most twice that below the reference's
# best; a wrong slot, cache row or block picks one some four standard
# deviations of the logits below it (a random token), five times this
# limit. Measured on the chip: at most 0.11 against limits of 0.80 to
# 0.99, 94% of the tokens the reference's own best (my chip runs, PR 24).
TOKEN_DEFICIT_TOLS = 2.0


class Row:
    """One request as its client saw it."""

    __slots__ = ("index", "due", "submit", "first", "last", "tokens",
                 "expected", "prompt_len", "done", "error", "req")

    def __init__(self, index: int, due: float, submit: float, expected: int,
                 prompt_len: int, req: Any):
        self.index, self.due, self.submit = index, due, submit
        self.first = self.last = 0.0
        self.tokens, self.expected = 0, expected
        self.prompt_len = prompt_len
        self.done, self.error, self.req = False, None, req

    @property
    def ok(self) -> bool:
        """Answered in full, or still streaming when the drain limit cut
        the run (a run may not wait out its longest answer: its per-token
        time is then taken over the tokens read so far). An error, a
        wrong count or no first token by the limit is a failure."""
        if self.error is not None:
            return False
        if self.done:
            return self.tokens == self.expected
        return 0 < self.tokens < self.expected

    def as_dict(self) -> Dict[str, Any]:
        r = self.req
        return {"index": self.index, "due": self.due, "submit": self.submit,
                "first": self.first, "last": self.last,
                "tokens": self.tokens, "expected": self.expected,
                "prompt_len": self.prompt_len, "ok": self.ok,
                "error": self.error,
                "engine_submit": r.submit_ts, "engine_admit": r.admit_ts,
                "engine_first": r.first_token_ts,
                "engine_finish": r.finish_ts}


def build(ctx: Context, devs) -> Dict[str, Any]:
    """Weights from the seed, the check against the reference, the
    engine, and its warm-up. Everything here is set-up."""
    import jax

    from ray_tpu.serve.llm import LLMEngine

    sizes = ctx.spec.sizes
    cfg = modelcfg.transformer_config(ctx.spec.config, sizes)
    slots, max_seq = int(sizes["slots"]), int(sizes["max_seq_len"])
    t = time.monotonic()
    params = modelcfg.make_params(cfg, ctx.seed)
    jax.block_until_ready(params)
    t_params = time.monotonic()
    check = check_against_reference(ctx, cfg, params, slots, max_seq)
    t_check = time.monotonic()
    engine = LLMEngine(cfg, params, num_slots=slots, max_seq_len=max_seq,
                       seed=ctx.seed & 0x7FFFFFFF,
                       decode_block=int(sizes.get("decode_block", 64)))
    trace = traffic.make_trace(ctx.spec.traffic)
    t_engine = time.monotonic()
    tr = ctx.spec.traffic
    warm = warm_up(engine, trace, queueing=tr["driver"] == "serve_open"
                   or int(tr.get("clients", 1)) > 1)
    ctx.log(phase="serve_setup", before_s=t - ctx.t_start,
            params_s=t_params - t, check_s=t_check - t_params, engine_s=t_engine - t_check,
            warm_s=time.monotonic() - t_engine, warm=warm, check=check)
    engine.start()
    ctx.probe = lambda: {"decode_ticks": engine.decode_ticks}
    return {"engine": engine, "cfg": cfg, "params": params, "trace": trace,
            "check": check,
            "prompts": traffic.token_ids(ctx.seed, trace, cfg.vocab_size)}


def check_against_reference(ctx: Context, cfg, params, slots: int,
                            max_seq: int) -> Dict[str, Any]:
    """The program's prefill, then decode through the cache, against the
    reference's full forward over the same tokens: logits, not tokens."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.generate import decode_step, init_kv_cache, prefill
    from ray_tpu.serve.llm import default_buckets

    spec = ctx.spec.sizes.get("check", {})
    lens = [int(n) for n in spec.get("prompt_lens", [48])]
    steps = int(spec.get("decode_steps", 4))
    buckets = default_buckets(max_seq)
    rng = np.random.default_rng([ctx.seed, 0x636865636B])
    seqs = [rng.integers(0, cfg.vocab_size, size=n).tolist() for n in lens]
    cache = jax.jit(init_kv_cache, static_argnums=(0, 1, 2))(
        cfg, slots, max_seq)
    got: List[List[Any]] = [[] for _ in seqs]
    cur = np.zeros((slots,), np.int32)
    for i, seq in enumerate(seqs):
        b = next(b for b in buckets if b >= len(seq))
        buf = np.zeros((1, b), np.int32)
        buf[0, :len(seq)] = seq
        cache, last = prefill(cfg, params, cache, jnp.asarray(buf),
                              jnp.asarray(len(seq), jnp.int32),
                              jnp.asarray(i, jnp.int32))
        got[i].append(np.asarray(last, np.float32))
        cur[i] = int(np.argmax(got[i][-1]))
    full = [list(s) + [int(cur[i])] for i, s in enumerate(seqs)]
    for _ in range(steps):
        cache, logits = decode_step(cfg, params, cache, jnp.asarray(cur))
        logits = np.asarray(logits, np.float32)
        for i in range(len(seqs)):
            got[i].append(logits[i])
            cur[i] = int(np.argmax(logits[i]))
            full[i].append(int(cur[i]))
    del cache
    errs, refs = [], []
    for i, seq in enumerate(seqs):
        ref = np.asarray(reference.forward_logits(
            ctx.spec.config, params, full[i][:-1]), np.float32)
        refs.append(ref[len(seq) - 1:])
        errs.append(np.stack(got[i]) - refs[-1])
    gc.collect()
    err, ref = np.concatenate(errs), np.concatenate(refs)
    rel = float(np.sqrt(np.mean(err * err) / np.mean(ref * ref)))
    return {"logit_rel_rms_err": rel, "tolerance_rel": LOGIT_REL_TOL,
            "ok": bool(rel <= LOGIT_REL_TOL),
            "positions": len(seqs) * (steps + 1),
            "logit_rms": float(np.sqrt(np.mean(ref * ref))),
            # The largest single error over the largest logit: what the
            # token check's limit is made from. It is the maximum of some
            # 300,000 draws and creeps up with the seeds tried, so it
            # decides nothing.
            "logit_max_abs_err": float(np.max(np.abs(err))),
            "logit_max_abs": float(np.max(np.abs(ref)))}


def _pow2_sizes(limit: int) -> List[int]:
    out, k = [], 1
    while k <= limit:
        out.append(k)
        k *= 2
    return out


def warm_up(engine, trace: List[traffic.Request], queueing: bool
            ) -> Dict[str, Any]:
    """Run every program the cell's traffic can reach, before the engine's
    thread starts: one prefill tile per prompt bucket in the trace, every
    decode block size the adaptive block can choose, and, where requests
    can wait (an open loop, or several callers: a request sent while a
    step runs waits for the next), the queue-side first-token program per
    bucket and the small programs that fuse first tokens (one per count
    of requests admitted together and of queue-side tiles). The decode
    blocks are reached through prompts of the engine's smallest bucket:
    one small program more, against a full-size prefill for each block
    size."""
    import jax.numpy as jnp

    buckets = sorted({next(b for b in engine.buckets if b >= r.prompt_len)
                      for r in trace})
    longest = max(r.output_len for r in trace)
    ks = [k for k in _pow2_sizes(engine.decode_block) if k < 2 * longest]
    ks.sort(reverse=True)
    small = engine.buckets[0]
    prev = {b: ([0] + engine.buckets)[engine.buckets.index(b)]
            for b in buckets + [small]}

    def prompt_for(bucket: int, new: int) -> List[int]:
        # As long as the bucket allows, but the answer has to fit too.
        size = min(bucket, engine.max_seq_len - 2 - 2 * new)
        return [1] * max(size, prev[bucket] + 1)

    def drain(reqs) -> None:
        deadline = time.monotonic() + 600
        while any(r.finish_ts == 0.0 for r in reqs):
            engine.step()
            if time.monotonic() > deadline:
                raise RuntimeError("warm-up did not finish")

    t_phase = [time.monotonic()]
    for k in ks:
        # The block is sized when a request is admitted, before its first
        # token counts: a lone request asking k tokens gets a block of k.
        drain([engine.submit(prompt_for(small, k), max_new_tokens=k)])
    for b in buckets:
        drain([engine.submit(prompt_for(b, 1), max_new_tokens=1)])
    t_phase.append(time.monotonic())
    slots = engine.num_slots
    if queueing:
        first = [engine.submit(prompt_for(small, 3), max_new_tokens=3)
                 for _ in range(slots)]
        engine.step()
        late = [engine.submit(prompt_for(b, 2), max_new_tokens=2)
                for b in buckets]
        drain(first + late)
    t_phase.append(time.monotonic())
    # First-token fusion: stack of n admitted scalars, joined with m
    # queue-side tiles. The engine runs these as eager jnp calls, so the
    # same calls on the same avals fill the same cache.
    tile = jnp.zeros((engine._ADMIT_TILE,), jnp.int32)
    scalar = tile[0]
    tiles = len(buckets) + 1 if queueing else 0
    for a in range(0, (slots if queueing else 1) + 1):
        head = [jnp.stack([scalar] * a)] if a else []
        for m in range(0, tiles + 1):
            if head or m:
                jnp.concatenate(head + [tile] * m).block_until_ready()
    t_phase.append(time.monotonic())
    return {"buckets": buckets, "block_sizes": ks,
            "blocks_and_buckets_s": t_phase[1] - t_phase[0],
            "queue_path_s": t_phase[2] - t_phase[1],
            "fusion_grid_s": t_phase[3] - t_phase[2]}


class Client:
    """The load and its reader, on one thread. Requests are submitted
    when due, every stream is read every POLL_S with a client-side stamp,
    and the engine's occupancy is sampled every SAMPLE_S."""

    def __init__(self, engine, trace: List[traffic.Request],
                 prompts: List[List[int]]):
        self.engine = engine
        self.trace, self.prompts = trace, prompts
        self.rows: List[Row] = []
        self.inflight: List[Row] = []
        self.samples: List[Any] = []       # (t, active, waiting)
        self.token_stamps: List[Any] = []  # (t, tokens read at t)
        self.cursor = 0
        self._next_sample = 0.0
        self.ticks_open = self.ticks_close = 0

    def submit_next(self, due: Optional[float] = None) -> Row:
        r = self.trace[self.cursor % len(self.trace)]
        prompt = self.prompts[self.cursor % len(self.trace)]
        self.cursor += 1
        now = time.monotonic()
        req = self.engine.submit(prompt, max_new_tokens=r.output_len,
                                 temperature=0.0, eos_token=None)
        row = Row(r.index, now if due is None else due, now, r.output_len,
                  r.prompt_len, req)
        self.rows.append(row)
        self.inflight.append(row)
        return row

    def poll(self) -> List[Row]:
        """Read what has arrived; returns the requests that ended."""
        now = time.monotonic()
        ended, read = [], 0
        for row in self.inflight:
            while True:
                try:
                    tok = row.req.stream.get_nowait()
                except queue.Empty:
                    break
                if tok is None:
                    row.done, row.error = True, row.req.error
                    ended.append(row)
                    break
                row.tokens += 1
                read += 1
                if row.first == 0.0:
                    row.first = now
                row.last = now
        if ended:
            self.inflight = [r for r in self.inflight if not r.done]
        if read:
            self.token_stamps.append((now, read))
        if now >= self._next_sample:
            self._next_sample = now + SAMPLE_S
            self.samples.append((now, sum(s is not None
                                          for s in self.engine.slots),
                                 len(self.engine.waiting)))
        return ended


def mean_backlog(client: Client, t0: float, t1: float,
                 waiting_only: bool = False) -> float:
    """Mean over [t0, t1) of the requests in the engine (waiting for a
    slot and being answered), or of those waiting alone, from the
    client's samples."""
    vals = [w + (0 if waiting_only else a)
            for t, a, w in client.samples if t0 <= t < t1]
    return sum(vals) / len(vals) if vals else 0.0


def in_flight_by_third(client: Client, t0: float, t1: float) -> List[float]:
    """Whether the lead-in reached the steady state: the mean number in
    the engine in each third of the window."""
    third = (t1 - t0) / 3
    return [mean_backlog(client, t0 + i * third, t0 + (i + 1) * third)
            for i in range(3)]


def summarise(ctx: Context, client: Client, measured: List[Row]
              ) -> Dict[str, Any]:
    """End-to-end numbers of a serving run, from client-side stamps."""
    ok = [r for r in measured if r.ok]
    ttft = [(r.first - r.due) * 1e3 for r in ok]
    tpot = [v for v in (stats.tpot_ms(r.first, r.last, r.tokens)
                        for r in ok) if v is not None]
    t0, t1 = ctx.t_open, ctx.t_close
    stamps = [(t, n) for t, n in client.token_stamps if t0 <= t < t1]
    toks = sum(n for _, n in stamps)
    e2e = {"ttft_p90_ms": stats.percentile(ttft, 90),
           "tpot_p90_ms": stats.percentile(tpot, 90),
           "serve_out_tok_s": stats.window_rate(stamps, t0, t1)}
    info = {"requests_measured": len(measured), "requests_ok": len(ok),
            "requests_cut_by_drain_limit": sum(not r.done for r in ok),
            "ttft_samples": len(ttft), "tpot_samples": len(tpot),
            "ttft_p50_ms": stats.percentile(ttft, 50),
            "tpot_p50_ms": stats.percentile(tpot, 50),
            "tokens_in_window": toks,
            "delivery_rate_tok_s": stats.delivery_rate(stamps),
            "lateness_p50_ms": stats.percentile(
                [(r.submit - r.due) * 1e3 for r in measured], 50),
            "lateness_max_ms": max(
                [(r.submit - r.due) * 1e3 for r in measured], default=None)}
    return {"end_to_end": e2e, "info": info}


def check_window_tokens(ctx: Context, built: Dict[str, Any],
                        client: Client, measured: List[Row]
                        ) -> Dict[str, Any]:
    """What the window itself streamed, through the engine's thread, its
    admission tiles, slots and fused decode blocks, against the
    reference: the shortest few requests that were answered in full
    (measured ones first, else any the client sent), every token of
    each. All requests are greedy, so the reference's forward over
    prompt and answer says how far below its own best each token was."""
    want = int(ctx.spec.sizes.get("check", {}).get("window_requests", 3))
    full = [r for r in measured if r.done and r.ok] \
        or [r for r in client.rows if r.done and r.ok]
    full.sort(key=lambda r: (r.prompt_len + r.tokens, r.index))
    # A list shorter than the run comes round again: each request once.
    rows = list({r.index: r for r in reversed(full)}.values())[::-1][:want]
    if not rows:
        return {"ok": False, "requests": 0, "positions": 0}
    # One padded length for all: one program each. Padding comes after
    # the answer and the mask is causal, so it changes nothing before it.
    size = -(-max(r.prompt_len + r.tokens for r in rows) // 128) * 128
    worst, limit = float("-inf"), 0.0   # the request nearest its limit
    positions = agree = 0
    for r in rows:
        prompt, answer = list(r.req.prompt), list(r.req.tokens)
        seq = prompt + answer[:-1]
        ref = np.asarray(reference.forward_logits(
            ctx.spec.config, built["params"],
            seq + [0] * (size - len(seq))), np.float32)
        ref = ref[len(prompt) - 1:len(seq)]
        tol = TOKEN_DEFICIT_TOLS * LOGIT_REL_TOL * float(np.max(np.abs(ref)))
        deficit = ref.max(axis=-1) - ref[np.arange(len(answer)), answer]
        if float(deficit.max()) - tol > worst - limit:
            worst, limit = float(deficit.max()), tol
        positions += len(answer)
        agree += int(np.sum(deficit == 0.0))
    return {"ok": bool(worst <= limit), "requests": len(rows),
            "request_indices": [r.index for r in rows],
            "positions": positions, "argmax_agree": agree,
            "token_deficit_max": worst, "token_deficit_tol": limit}


def stop_engine(engine) -> None:
    """Stop the engine and wait for its thread: a process that exits
    while the thread still dispatches dies in the runtime's teardown."""
    engine.stop()
    loop = getattr(engine, "_loop_thread", None)
    if loop is not None:
        loop.join(timeout=60)


def finish(ctx: Context, built: Dict[str, Any], client: Client,
           measured: List[Row], extra_info: Dict[str, Any]
           ) -> Dict[str, Any]:
    """Stop the engine and shape the driver's result."""
    engine = built["engine"]
    stop_engine(engine)
    s = summarise(ctx, client, measured)
    t = time.monotonic()
    tokens_check = check_window_tokens(ctx, built, client, measured)
    ctx.log(phase="window_tokens_check", seconds=time.monotonic() - t,
            **tokens_check)
    with open(os.path.join(ctx.out_dir, "rows.jsonl"), "w") as f:
        for r in client.rows:
            f.write(json.dumps(r.as_dict()) + "\n")
    with open(os.path.join(ctx.out_dir, "stamps.json"), "w") as f:
        json.dump({"t_open": ctx.t_open, "t_close": ctx.t_close,
                   "stamps": client.token_stamps}, f)
    failed = sum(not r.ok for r in measured)
    info = dict(s["info"], **extra_info)
    return {
        "correct": bool(built["check"]["ok"]) and tokens_check["ok"]
        and failed == 0 and len(measured) > 0,
        "attempted": len(measured), "failed": failed,
        "end_to_end": s["end_to_end"], "info": info,
        "measure": {"rows": measured, "all_rows": client.rows,
                    "samples": client.samples,
                    "token_stamps": client.token_stamps,
                    "slots": engine.num_slots,
                    "decode_ticks": client.ticks_close - client.ticks_open,
                    "arch": ctx.spec.config},
    }
