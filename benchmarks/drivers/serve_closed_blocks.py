"""Closed loop over a model that generates by diffusion over blocks
(`block_length` in its configuration): `serve_closed`'s loop, with a
build and a finish of its own. `lib/serving`'s two checks judge a causal
forward a token at a time; here a step of the program is a pass over a
block of positions a slot, so both are replaced by their block-wise
forms, against the same reference interface and under the harness's own
limits (`serving.LOGIT_REL_TOL`, `serving.TOKEN_DEFICIT_TOLS`, imported,
not restated):

(a) `check_blocks`: the program's prefill of each prompt's whole blocks
    and then, through the cache, every pass of its first `blocks` blocks
    by the one-pass program, against the reference's full forward over
    the committed tokens and the pass's block: logits, over all prompts
    and over the shortest alone (at six tokens of context a wrong mask
    or a block read from a stale pass moves a third of what a row sees);
(b) `check_window_blocks`: what the window itself streamed: for the
    shortest requests answered in full, the first and last blocks, each
    pass's input rebuilt from the final tokens and the pass that
    unmasked each (`GenRequest.unmasked_at`), and every token held to
    the reference's logits at the pass that unmasked it. Which positions
    a pass chose is not judged here (the confidences of seeded weights
    tie within rounding); the CPU tests hold it exactly;
(c) no measured request failed, at least one measured.
"""

from __future__ import annotations

import gc
import json
import os
import time
from typing import Any, Dict, List, Optional

import numpy as np

from lib import modelcfg, serving, traffic


def _rel(err: np.ndarray, ref: np.ndarray) -> float:
    return float(np.sqrt(np.mean(err * err) / np.mean(ref * ref)))


def _padded(n: int) -> int:
    """One length a prompt's forwards share: a compile a prompt, not a
    pass. Padding stands behind the last block and the mask is causal
    between blocks, so it changes nothing before it."""
    return -(-n // 128) * 128


def _static_take(conf: np.ndarray, masked: np.ndarray, n: int) -> np.ndarray:
    """The n masked positions of largest confidence, ties to the lower."""
    idx = np.flatnonzero(masked)
    return idx[np.argsort(-conf[idx], kind="stable")][:n]


def check_blocks(ctx, cfg, params, slots: int, max_seq: int,
                 ref_arch: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """(a). Greedy, the cell's `denoise_steps` under the static rule (the
    check's own schedule: a pass's logits are judged, whatever positions
    it unmasks). `ref_arch`: the architecture handed to the reference,
    where a control reads the program against another mask."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.generate import (decode_block_step, init_block_state,
                                         init_kv_cache, prefill_block_batch)
    from ray_tpu.serve.llm import LLMEngine, default_buckets

    spec = ctx.spec.sizes.get("check", {})
    lens = [int(n) for n in spec.get("prompt_lens", [48])]
    blocks = int(spec.get("blocks", 4))
    Bd, mask_id = cfg.block_length, cfg.mask_token_id
    share = ctx.spec.reference.schedule(Bd, cfg.denoise_steps)
    arch = ref_arch or ctx.spec.config
    buckets = [b for b in default_buckets(max_seq) if b % Bd == 0]
    rng = np.random.default_rng([ctx.seed, 0x626C6F636B])
    seqs = [rng.integers(0, cfg.vocab_size, size=n).tolist() for n in lens]
    cache = jax.jit(init_kv_cache, static_argnums=(0, 1, 2))(
        cfg, slots, max_seq)
    state = jax.jit(init_block_state, static_argnums=(0, 1))(cfg, slots)
    done, x, masked, n_pass, left = [], [], [], [], []
    for i, seq in enumerate(seqs):
        whole = len(seq) // Bd * Bd
        b = next(b for b in buckets if b >= whole)
        W = LLMEngine._tile_rows(b)       # the engine's tile: one compile
        buf = np.zeros((W, b), np.int32)
        buf[0, :whole] = seq[:whole]
        tile_lens = np.ones((W,), np.int32)
        tile_lens[0] = whole
        slot_idx = np.full((W,), slots, np.int32)
        slot_idx[0] = i
        ones = np.ones((W,), np.int32)
        cache, state, *_ = prefill_block_batch(
            cfg, params, cache, state, jnp.asarray(buf),
            jnp.asarray(tile_lens), jnp.asarray(slot_idx),
            jnp.full((W, Bd), mask_id, jnp.int32), jnp.ones((W, Bd), bool),
            jnp.asarray(ones), jnp.asarray(ones - 1),
            jnp.zeros((W,), jnp.float32))
        rest = seq[whole:]
        done.append(seq[:whole])
        x.append(np.asarray(rest + [mask_id] * (Bd - len(rest)), np.int64))
        masked.append(np.arange(Bd) >= len(rest))
        n_pass.append(0)
        left.append(blocks)
    del state
    live = np.zeros((slots,), bool)
    errs: List[List[np.ndarray]] = [[] for _ in seqs]
    refs: List[List[np.ndarray]] = [[] for _ in seqs]
    while any(left):
        tokens = np.full((slots, Bd), mask_id, np.int32)
        p0 = np.zeros((slots,), np.int32)
        for i in range(len(seqs)):
            live[i] = left[i] > 0
            tokens[i], p0[i] = x[i], len(done[i])
        cache, logits = decode_block_step(
            cfg, params, cache, jnp.asarray(tokens), jnp.asarray(p0),
            jnp.asarray(live))
        logits = np.asarray(logits[:len(seqs)], np.float32)
        for i in range(len(seqs)):
            if not left[i]:
                continue
            seq = done[i] + x[i].tolist()
            size = _padded(len(seqs[i]) + blocks * Bd)
            ref = np.asarray(ctx.spec.reference.forward_logits(
                arch, params, seq + [0] * (size - len(seq))),
                np.float32)[len(done[i]):len(seq)]
            errs[i].append(logits[i] - ref)
            refs[i].append(ref)
            if not masked[i].any():        # that was the commit pass
                done[i] = seq
                x[i] = np.full(Bd, mask_id, np.int64)
                masked[i], n_pass[i] = np.ones(Bd, bool), 0
                left[i] -= 1
                continue
            got = logits[i].astype(np.float64)
            x0 = np.argmax(got, axis=-1)
            z = got - got.max(axis=-1, keepdims=True)
            conf = np.where(masked[i], 1.0 / np.exp(z).sum(axis=-1), -np.inf)
            take = _static_take(conf, masked[i], share[min(
                n_pass[i], len(share) - 1)])
            x[i][take], masked[i][take] = x0[take], False
            n_pass[i] += 1
    del cache
    gc.collect()
    err = np.concatenate([np.concatenate(e) for e in errs])
    ref = np.concatenate([np.concatenate(r) for r in refs])
    short = int(np.argmin(lens))
    rel = _rel(err, ref)
    rel_short = _rel(np.concatenate(errs[short]), np.concatenate(refs[short]))
    return {"logit_rel_rms_err": rel, "logit_rel_rms_err_shortest": rel_short,
            "shortest_prompt": lens[short],
            "tolerance_rel": serving.LOGIT_REL_TOL,
            "ok": bool(rel <= serving.LOGIT_REL_TOL
                       and rel_short <= serving.LOGIT_REL_TOL),
            "passes": sum(len(e) for e in errs),
            "positions": sum(len(e) for e in errs) * Bd,
            "logit_rms": float(np.sqrt(np.mean(ref * ref))),
            "logit_max_abs_err": float(np.max(np.abs(err))),
            "logit_max_abs": float(np.max(np.abs(ref)))}


def check_window_blocks(ctx, built: Dict[str, Any], client: serving.Client,
                        measured: List[serving.Row]) -> Dict[str, Any]:
    """(b). The shortest few requests answered in full (measured ones
    first, else any the client sent); of each, the first and last
    `window_blocks` / 2 blocks; of each block, every denoising pass:
    its input is the block's final tokens with the mask token wherever
    `unmasked_at` says a later-or-equal pass unmasked the position, and
    every token that pass unmasked is held to `reference's largest logit
    - its logit of the token <= TOKEN_DEFICIT_TOLS x LOGIT_REL_TOL x the
    largest |logit|` at that position."""
    check = ctx.spec.sizes.get("check", {})
    want = int(check.get("window_requests", 2))
    n_blocks = int(check.get("window_blocks", 16))
    cfg = built["cfg"]
    Bd, mask_id = cfg.block_length, cfg.mask_token_id
    full = [r for r in measured if r.done and r.ok] \
        or [r for r in client.rows if r.done and r.ok]
    full.sort(key=lambda r: (r.prompt_len + r.tokens, r.index))
    rows = list({r.index: r for r in reversed(full)}.values())[::-1][:want]
    if not rows:
        return {"ok": False, "requests": 0, "positions": 0}
    size = _padded(max(r.prompt_len + r.tokens for r in rows) + Bd)
    worst, limit = float("-inf"), 0.0   # the token nearest its limit
    positions = agree = passes = 0
    for r in rows:
        prompt, answer = list(r.req.prompt), list(r.req.tokens)
        at_pass = [0] * len(prompt) + list(r.req.unmasked_at)
        seq = prompt + answer
        first = len(prompt) // Bd
        last = (len(seq) - 1) // Bd          # its end may be cut: all masks
        ids = list(range(first, last + 1))
        if len(ids) > n_blocks:
            ids = ids[:n_blocks // 2] + ids[-(n_blocks // 2):]
        for b in ids:
            lo = b * Bd
            final = (seq[lo:lo + Bd] + [mask_id] * Bd)[:Bd]
            when = (at_pass[lo:lo + Bd] + [0] * Bd)[:Bd]
            cut = [lo + j >= len(seq) for j in range(Bd)]
            for n in sorted({w for w in when if w > 0}):
                if any(cut) and n > 1:
                    # A position cut off the answer's end was unmasked
                    # by a pass the client never saw: only the block's
                    # first pass (nothing unmasked yet) can be rebuilt.
                    break
                block = [final[j] if not cut[j] and when[j] < n else mask_id
                         for j in range(Bd)]
                now = [j for j in range(Bd) if when[j] == n and not cut[j]]
                ref = np.asarray(ctx.spec.reference.forward_logits(
                    ctx.spec.config, built["params"],
                    seq[:lo] + block + [0] * (size - lo - Bd)),
                    np.float32)[lo:lo + Bd]
                passes += 1
                for j in now:
                    tol = serving.TOKEN_DEFICIT_TOLS * serving.LOGIT_REL_TOL \
                        * float(np.max(np.abs(ref[j])))
                    deficit = float(ref[j].max() - ref[j][final[j]])
                    if deficit - tol > worst - limit:
                        worst, limit = deficit, tol
                    positions += 1
                    agree += int(deficit == 0.0)
    return {"ok": bool(positions > 0 and worst <= limit),
            "requests": len(rows),
            "request_indices": [r.index for r in rows],
            "passes": passes, "positions": positions, "argmax_agree": agree,
            "token_deficit_max": worst, "token_deficit_tol": limit}


def build(ctx, devs) -> Dict[str, Any]:
    """`serving.build` with the block-wise check in the logits check's
    place, and every fused block size compiled: everything here is
    set-up."""
    import jax

    from ray_tpu.serve.llm import LLMEngine

    sizes = ctx.spec.sizes
    cfg = modelcfg.transformer_config(ctx.spec.config, sizes)
    slots, max_seq = int(sizes["slots"]), int(sizes["max_seq_len"])
    t = time.monotonic()
    params = modelcfg.make_params(cfg, ctx.seed)
    jax.block_until_ready(params)
    t_params = time.monotonic()
    check = check_blocks(ctx, cfg, params, slots, max_seq)
    t_check = time.monotonic()
    engine = LLMEngine(cfg, params, num_slots=slots, max_seq_len=max_seq,
                       seed=ctx.seed & 0x7FFFFFFF,
                       decode_block=int(sizes.get("decode_block", 64)))
    trace = traffic.make_trace(ctx.spec.traffic)
    t_engine = time.monotonic()
    # No queue side: a waiting request gets no first token from a
    # cache-free program here, so there is neither such a program nor a
    # fusion of first tokens to warm.
    warm = serving.warm_up(engine, trace, queueing=False)
    # A pass budget is not a token budget: the sizes `warm_up`'s requests
    # did not reach are run with no slot owned.
    warm["block_sizes_forced"] = engine.warm_decode_blocks()
    ctx.log(phase="serve_setup", before_s=t - ctx.t_start,
            params_s=t_params - t, check_s=t_check - t_params,
            engine_s=t_engine - t_check, warm_s=time.monotonic() - t_engine,
            warm=warm, check=check)
    engine.start()
    ctx.probe = lambda: {"decode_ticks": engine.decode_ticks}
    return {"engine": engine, "cfg": cfg, "params": params, "trace": trace,
            "check": check,
            "prompts": traffic.token_ids(ctx.seed, trace, cfg.vocab_size)}


def finish(ctx, built: Dict[str, Any], client: serving.Client,
           measured: List[serving.Row], extra_info: Dict[str, Any]
           ) -> Dict[str, Any]:
    """`serving.finish` with the block-wise window check; the same
    files and the same `measure` keys (the readers take them)."""
    engine = built["engine"]
    serving.stop_engine(engine)
    s = serving.summarise(ctx, client, measured)
    t = time.monotonic()
    tokens_check = check_window_blocks(ctx, built, client, measured)
    ctx.log(phase="window_tokens_check", seconds=time.monotonic() - t,
            **tokens_check)
    with open(os.path.join(ctx.out_dir, "rows.jsonl"), "w") as f:
        for r in client.rows:
            f.write(json.dumps(r.as_dict()) + "\n")
    with open(os.path.join(ctx.out_dir, "stamps.json"), "w") as f:
        json.dump({"t_open": ctx.t_open, "t_close": ctx.t_close,
                   "stamps": client.token_stamps}, f)
    failed = sum(not r.ok for r in measured)
    counts = engine.stats()["counts"]
    info = dict(s["info"], **extra_info, engine_counts={
        k: counts[k] for k in (
            "blocks", "blocks_by_k", "slot_steps", "denoise_passes",
            "commit_passes", "blocks_committed", "positions_unmasked",
            "tokens_truncated", "tokens_discarded") if k in counts})
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


def run(ctx, devs) -> Dict[str, Any]:
    tr = ctx.spec.traffic
    built = build(ctx, devs)
    engine = built["engine"]
    client = serving.Client(engine, built["trace"], built["prompts"])
    lead_in = float(tr["lead_in_s"])
    t_zero = time.monotonic()
    t_open, t_close = None, t_zero + lead_in + ctx.seconds
    measured = []
    for _ in range(int(tr["clients"])):
        client.submit_next()
    while True:
        now = time.monotonic()
        if t_open is None and now >= t_zero + lead_in:
            t_open = ctx.open_window()
            t_close = t_open + ctx.seconds
            client.ticks_open = engine.decode_ticks
        with ctx.span("client_poll"):
            ended = client.poll()
        closing = t_open is not None and now >= t_close
        if closing and not client.ticks_close:
            client.ticks_close = engine.decode_ticks
        for old in ended:
            # The caller whose request ended sends its next one.
            with ctx.span("submit"):
                client.submit_next()
            if t_open is not None and t_open <= now < t_close:
                measured.append(old)      # `ended_in_window`
        if closing:
            break
        with ctx.span("generator_wait"):
            time.sleep(serving.POLL_S)
    ctx.close_window()
    return finish(ctx, built, client, measured, {"clients": tr["clients"]})
