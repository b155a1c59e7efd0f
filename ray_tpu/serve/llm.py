"""Continuous-batching LLM inference engine + serve deployment.

TPU-native serving path (SURVEY.md §7 step 9: "continuous batching
replica — KV cache in HBM, prefill/decode split — for the Llama-8B
serving target"; the reference delegates this entirely to vLLM,
doc/source/serve/doc_code/vllm_example.py).

Engine design around XLA's static shapes:
- a fixed pool of `num_slots` sequence slots backed by one static KV
  cache (models/generate.py); admission = prefill into a free slot,
  one bucketed-compile per prompt-length bucket;
- every engine tick runs ONE compiled decode step for ALL slots (the
  continuous-batching property: sequences join/leave between ticks,
  the compiled program never changes shape);
- per-slot temperature rides a (B,) operand, so mixed sampling configs
  share the tick; finished slots are ignored until readmission.

TTFT = submit→first-token (prefill-bound); per-request metrics are
recorded for the serving benchmark (BASELINE.md north-star: req/s +
p50 TTFT).
"""

from __future__ import annotations

import contextlib
import queue
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import (
    Any, Dict, Iterator, List, Optional, Sequence, Tuple)

import jax
import jax.numpy as jnp
import numpy as np

from ..core.graphable import graphable
from ..models.generate import (
    PASS_COMMIT,
    PASS_DENOISE,
    PROGRAM_NAMES,
    BlockState,
    Extras,
    KVCache,
    compute_prefix_kv,
    decode_block_multi,
    decode_multi,
    decode_step,
    first_token_sample,
    first_token_suffix_sample,
    init_block_state,
    init_kv_cache,
    prefill_block_batch,
    prefill_sample_batch,
    prefill_suffix_batch,
    program,
    sample_logp,
)
from ..models.moe import dot_terms
from ..models.transformer import (
    REMASK_RULES,
    TransformerConfig,
    init_params,
    init_params_sharded,
    stack,
)
from ..util import tracing

# Jitted so that under an ambient mesh the cache is created sharded
# (eagerly, jnp.zeros would first place it whole on the default device).
_init_kv_cache = jax.jit(init_kv_cache, static_argnums=(0, 1, 2))
_init_block_state = jax.jit(init_block_state, static_argnums=(0, 1))


def default_buckets(max_prompt_len: int) -> List[int]:
    out, b = [], 16
    while b < max_prompt_len:
        out.append(b)
        b *= 2
    out.append(max_prompt_len)
    return out


# The sampler after a one-step block: (B, V) logits -> ((B,) tokens, (B,)
# log-probs of those tokens).
_sample_batch = program("sample_batch", static_argnums=(3,))(sample_logp)


def _copy_to_host_async(*arrays: Optional[jax.Array]) -> None:
    """Start the host copy of what the host will read a tick later."""
    for arr in arrays:
        if arr is not None:
            try:
                arr.copy_to_host_async()
            except Exception:  # noqa: BLE001 — no async copy
                pass


# What `engine.device_call` spans say they are (`op`; docs/METRICS.md): the
# key's `split`; host arrays sent (`to_device`, `n` of them in one call);
# a jitted `program` of `PROGRAM_NAMES`; the eager programs `slice` (an
# index of a device array), `scatter` (an `.at[].set`), `pad`, `stack`,
# `concatenate`; the start of a result's copy back (`copy_start`, `n`
# arrays) and the host read that waits for it (`to_host`, `n` arrays).
DEVICE_OPS = ("split", "to_device", "program", "slice", "scatter", "pad",
              "stack", "concatenate", "copy_start", "to_host")


@dataclass
class GenRequest:
    prompt: List[int]
    max_new_tokens: int = 64
    temperature: float = 0.0
    eos_token: Optional[int] = None
    # Where the model generates a block of positions a pass
    # (`TransformerConfig.block_length`): denoising passes a block, the
    # rule that unmasks (`transformer.REMASK_RULES`) and the dynamic
    # rule's threshold; None = the configuration's.
    denoise_steps: Optional[int] = None
    remask: Optional[str] = None
    confidence_threshold: Optional[float] = None
    # filled by the engine
    id: int = 0
    submit_ts: float = 0.0
    # First time engine compute touched the request (slot admission or
    # the queue-side early-first-token pass): splits TTFT into
    # queue_s (submit→admit) vs prefill_s (admit→first token) for the
    # critpath/TTFT waterfall.
    admit_ts: float = 0.0
    # The engine tick (`engine.tick` span, attribute `tick`) that first
    # touched the request, and the decode steps that stood between it
    # and the device at that moment: steps dispatched by then, less
    # those whose tokens the host had already read when the request
    # was submitted. A request that missed the engine's look at its
    # queue reads one block more than its twin that did not.
    admit_tick: int = -1
    steps_waited: int = 0
    first_token_ts: float = 0.0
    finish_ts: float = 0.0
    stream: "queue.Queue" = field(default_factory=queue.Queue)
    tokens: List[int] = field(default_factory=list)
    # log π(tok) per emitted token (raw-logits log_softmax),
    # index-aligned with `tokens`.
    logprobs: List[float] = field(default_factory=list)
    # Block generation: the denoising pass of its block (from 1) that
    # unmasked each emitted token, index-aligned with `tokens`.
    unmasked_at: List[int] = field(default_factory=list)
    error: Optional[str] = None
    # Set once the terminal None has been consumed (engine-internal).
    _done: bool = field(default=False, repr=False)
    # First token served queue-side before any slot freed (engine-
    # internal; _admit resumes decode from it).
    _early_tok: Optional[int] = field(default=None, repr=False)
    # engine.steps_processed at submit (engine-internal).
    _steps_seen: int = field(default=0, repr=False)

    @property
    def ttft_s(self) -> float:
        return self.first_token_ts - self.submit_ts

    @property
    def latency_s(self) -> float:
        return self.finish_ts - self.submit_ts

    @property
    def queue_s(self) -> float:
        """Admission-queue wait (0.0 until admitted)."""
        if self.admit_ts == 0.0:
            return 0.0
        return self.admit_ts - self.submit_ts

    @property
    def prefill_s(self) -> float:
        """Admission → first token (0.0 until the first token)."""
        if self.admit_ts == 0.0 or self.first_token_ts == 0.0:
            return 0.0
        return self.first_token_ts - self.admit_ts

    @property
    def decode_s(self) -> float:
        """First token → finish (0.0 until finished)."""
        if self.first_token_ts == 0.0 or self.finish_ts == 0.0:
            return 0.0
        return self.finish_ts - self.first_token_ts

    def __iter__(self) -> Iterator[int]:
        if self._done:
            # Replay: the stream was already drained (by result() or a
            # prior iteration) — blocking on it again would hang.
            if self.error is not None:
                raise RuntimeError(f"generation failed: {self.error}")
            yield from list(self.tokens)
            return
        while True:
            tok = self.stream.get()
            if tok is None:
                self._done = True  # result() must not block after this
                if self.error is not None:
                    raise RuntimeError(f"generation failed: {self.error}")
                return
            yield tok

    def result(self, timeout: Optional[float] = None) -> List[int]:
        """ALL generated tokens, regardless of how many were already
        consumed via streaming — idempotent and safe after __iter__."""
        if self._done:
            if self.error is not None:
                raise RuntimeError(f"generation failed: {self.error}")
            return list(self.tokens)
        deadline = (time.monotonic() + timeout
                    if timeout is not None else None)
        while True:
            left = (max(0.0, deadline - time.monotonic())
                    if deadline is not None else None)
            tok = self.stream.get(timeout=left)
            if tok is None:
                self._done = True
                if self.error is not None:
                    raise RuntimeError(
                        f"generation failed: {self.error}")
                # self.tokens has every emitted token (the engine
                # appends there before the stream put).
                return list(self.tokens)


def _ids(reqs) -> str:
    """Request ids as a span attribute: space-separated (the profiler
    cuts a string value at a comma)."""
    return " ".join(str(r.id) for r in reqs)


# Steps past the shortest budget from which a fused block is rounded down
# and not up. At 8 only what would have been a block of 32 or 64 steps is
# ever split, and no piece but the last is shorter than 16 steps: 57 ms of
# device time at the shortest step any cell has (mellum2-repoctx-lone,
# 3.583 ms: ledger, PR 47) against the 5.4-6.4 ms a tick's own code takes
# there (`engine.host_self_ms_tick.online`), so the piece queued behind
# costs the device no gap, only its launch: 0.44-0.59 ms (blocks of 64 /
# 32 / 16 steps took 229.36 / 114.90 / 57.74 ms there) against the 8 steps
# or more it saves. That cell's budget of 48 ran as one block of 64 and
# runs as 32 + 16: `tpot_p90_ms` 4.905 -> 3.694, six pairs (my chip runs,
# PR 49).
_ROUND_DOWN_FROM = 8


def block_steps(remaining: int, cap: int, headroom: int) -> Tuple[int, int]:
    """Steps of the next fused block, and the steps rounding up alone
    would have run, from the smallest budget an active slot has left
    (`remaining`), `decode_block` (`cap`) and the cache rows the fullest
    slot has left (`headroom`). Both are powers of two within `cap` and
    `headroom` (at least 1): each size is one compiled program. The
    budget is rounded up where that runs fewer than `_ROUND_DOWN_FROM`
    steps past it, and down where not: the next tick dispatches the rest
    behind this block, so a budget of b takes at most log2(b) blocks and
    an exact power of two one."""
    remaining = max(1, remaining)
    up = 1 << (remaining - 1).bit_length()
    k = up // 2 if up - remaining >= _ROUND_DOWN_FROM else up
    limit = max(1, min(cap, headroom))
    limit = 1 << (limit.bit_length() - 1)
    return min(k, limit), min(up, limit)


class _Slot:
    __slots__ = ("req", "emitted", "length", "inflight", "blocks_left")

    def __init__(self, req: GenRequest, prompt_len: int):
        self.req = req
        self.emitted = 0
        # Final rows in the cache: grows by a row a step, or, where the
        # model generates a block a pass, by a block a commit.
        self.length = prompt_len
        # Decode steps dispatched to the device but not yet processed
        # on the host (the pipelined block in flight). One token a
        # step: the device-side cache position for this slot is length
        # + inflight.
        self.inflight = 0
        # Block generation: blocks the request still needs committed.
        self.blocks_left = 0


class LLMEngine:
    """Host-side continuous-batching loop over the compiled
    prefill/decode steps. Thread-safe submit; `step()` is driven either
    by `run_forever()` (background thread) or manually (tests)."""

    def __init__(self, cfg: TransformerConfig, params: Any, *,
                 num_slots: int = 4, max_seq_len: Optional[int] = None,
                 top_k: int = 0, seed: int = 0, decode_block: int = 64,
                 auto_prefix_min_hits: int = 0,
                 auto_prefix_lens: Sequence[int] = (64, 128, 256, 512),
                 mesh: Optional["jax.sharding.Mesh"] = None):
        from .._private import compile_cache

        compile_cache.enable()
        self.cfg = cfg
        self.num_slots = num_slots
        self.max_seq_len = max_seq_len or cfg.max_seq_len
        self.top_k = top_k
        # Multi-chip serving (VERDICT r4 #3): with a mesh, weights are
        # laid out by their logical axes (megatron TP via "heads"/"mlp"/
        # "vocab"→tp, ZeRO-style "embed"→fsdp) and the KV cache shards
        # across kv-heads; every compiled prefill/decode step then runs
        # SPMD with XLA-inserted collectives over ICI. An 8B model that
        # cannot fit one 16 GiB chip serves on tp=4/fsdp=2. The
        # reference reaches multi-GPU serving only through vLLM TP
        # (doc/source/serve/doc_code/vllm_example.py). Params that
        # arrive whole are resharded here, which places them on one
        # device first: a model that needs the mesh to fit must be made
        # sharded (init_params_sharded, or a sharded checkpoint load).
        self.mesh = mesh
        if mesh is not None:
            from ..models.transformer import param_logical_axes
            from ..parallel.sharding import shard_pytree

            with jax.sharding.set_mesh(mesh):
                params = shard_pytree(params, param_logical_axes(cfg),
                                      mesh)
        self.params = params
        # UPPER BOUND on ticks fused per dispatch (decode_multi); the
        # actual block size adapts ONLINE each step to the minimum
        # remaining generation budget among active slots
        # (`block_steps`): a power of two that ends fewer than
        # `_ROUND_DOWN_FROM` steps past the first slot to complete, or
        # before it, a shorter block then following (until PR 49 the
        # next power of two, up to twice the budget less one). The cap
        # only bounds the number of compiled block sizes and the
        # worst-case admission latency. A fused block costs one
        # dispatch and one host fetch for its tokens.
        self.decode_block = max(1, decode_block)
        # Positions a slot a decode step: 1, or the block a pass of the
        # model works on (generation by diffusion over blocks). Then a
        # step is a pass, a slot's open block lives on the device
        # (`_blocks`) and tokens are emitted a committed block at a time.
        self.block_length = cfg.block_length
        self._step_rows = max(1, cfg.block_length)
        if self.block_length and mesh is not None:
            raise NotImplementedError(
                "block generation (block_length) is served on one chip: "
                "the period stack has no sharding rules yet")
        with self._mesh_ctx():
            self.cache: KVCache = _init_kv_cache(cfg, num_slots,
                                                 self.max_seq_len)
            self._blocks: Optional[BlockState] = _init_block_state(
                cfg, num_slots) if self.block_length else None
            self.cur_tokens = jnp.zeros((num_slots,), jnp.int32)
            # Device-resident per-slot temperatures: updated by scatter
            # at admission, never re-uploaded per tick.
            self._temps = jnp.zeros((num_slots,), jnp.float32)
            self._key = jax.random.key(seed)
        self.slots: List[Optional[_Slot]] = [None] * num_slots
        # One decode block pipelined: dispatched last tick, its tokens
        # fetched/emitted next tick (overlaps the round trip with the
        # next block's compute).
        self._pending = None
        self.waiting: deque = deque()
        self.lock = threading.Lock()
        self._work = threading.Event()
        self._stop = False
        self.buckets = [b for b in default_buckets(self.max_seq_len)
                        if b % self._step_rows == 0]
        # Registered prompt prefixes (system prompts): token-tuple ->
        # {"k","v"} device KV computed once; admission copies it into
        # the slot and prefills only the suffix (vLLM-style prefix
        # caching scoped to explicit registration — the KV cache here
        # is slot-contiguous, not paged).
        from collections import OrderedDict

        self._prefixes: "OrderedDict[tuple, Dict[str, Any]]" = \
            OrderedDict()
        self.max_cached_prefixes = 8
        self.prefix_hits = 0
        self.prefix_tokens_saved = 0
        # Automatic capture (vLLM's "automatic" in automatic prefix
        # caching, at registered-prefix granularity): count block-length
        # prompt prefixes at submit; one that repeats
        # auto_prefix_min_hits times registers itself on the next engine
        # tick (registration prefills once — done engine-side so no
        # client submit blocks on it). 0 = off.
        self.auto_prefix_min_hits = int(auto_prefix_min_hits)
        self.auto_prefix_lens = tuple(sorted(auto_prefix_lens))
        self._auto_counts: "OrderedDict[tuple, int]" = OrderedDict()
        self._auto_pending: deque = deque()
        self._auto_inflight: set = set()
        self.prefix_register_failures = 0
        # aggregate stats
        # decode steps dispatched (block generation: passes)
        self.decode_ticks = 0
        self.steps_processed = 0    # ... whose tokens the host has read
        self.tokens_out = 0
        # What the spans carry as attributes, summed where the work
        # happens (stats()["counts"]; docs/METRICS.md): an operator has
        # them without a profiler.
        self.counts: Dict[str, Any] = {
            "ticks": 0, "blocks": 0, "blocks_by_k": {},
            "blocks_rounded_down": 0, "slot_steps": 0,
            "tokens_discarded": 0, "prefill_tiles": 0, "prefill_rows": 0,
            "prefill_tile_rows": 0, "prefill_tokens": 0,
            "prefill_tile_tokens": 0, "queue_side_first_tokens": 0,
            "cache_rows": 0, "cache_rows_held": 0,
            # The engine thread's clock: requests submitted, device
            # programs it called (`engine.launch`, by program), the
            # ticks' length and the thread's CPU time inside them
            # (`engine.tick`: `cpu_us`), and the time it stood waiting
            # for the device (`engine.fetch`) and for work
            # (`engine.idle_wait`).
            "submitted": 0, "launches": {}, "tick_ns": 0, "tick_cpu_ns": 0,
            "fetch_wait_ns": 0, "idle_wait_ns": 0,
            # What the thread asked of the device or of the transfer
            # engine, one call at a time (`engine.device_call`), and the
            # time those calls took, both by `op` (`DEVICE_OPS`).
            "device_calls": {}, "device_call_ns": {}}
        # The running number of those calls: a span's `call`.
        self._calls = 0
        if self.block_length:
            # What `engine.process_block` says of its passes, summed.
            self.counts.update(denoise_passes=0, commit_passes=0,
                               blocks_committed=0, positions_unmasked=0,
                               tokens_truncated=0)
        # What the stack counts of its own mechanism beside those
        # (`transformer.STACKS`: `counters`), reckoned by the stack where
        # a tile or a block is dispatched and where its results are read.
        self._stack = stack(cfg)
        self.counts.update(self._stack.counters(cfg))
        # Whether the programs hand back anything beside their tokens
        # (`Extras`): a one-step block whose program does not may run
        # `decode_step` and the engine's own sampler.
        self._by_products = self._stack.by_products(cfg)
        # The bf16 terms the model multiplies an activation as, which a
        # slot-side tile's positions follow (`_tile_rows`).
        self._dot_terms = dot_terms(cfg.dtype, cfg.param_dtype)
        # Admission tiles' `Extras` on their way to the host
        # (`_keep_tile_extras`): the exit passes, a tile's array with
        # which of its rows' tokens the tile delivers, read with the
        # first tokens (`_deliver_first_tokens`), and the routing stats,
        # read where the host next waits for a tile (`_read_tile_moe`).
        self._tile_exits: List[Tuple[jax.Array, List[int]]] = []
        self._tile_moe: List[jax.Array] = []
        # The last FINISHED_RING completed requests (ttft percentiles
        # in stats() are over these).
        self.finished: deque = deque(maxlen=self.FINISHED_RING)
        self._n_finished = 0
        # Recent-TTFT EWMA: the router's SLO-aware tiebreak signal
        # (cheap to read every stats poll, unlike the sorted
        # percentiles in stats()).
        self._ttft_ewma: Optional[float] = None

    def _mesh_ctx(self):
        """Ambient-mesh context for every device dispatch: the in-jit
        logical-axis constraints (models/generate.py wsc calls) resolve
        against it, turning the same compiled steps into SPMD programs.
        No-op (and zero-cost) for single-chip engines."""
        if self.mesh is None:
            return contextlib.nullcontext()
        return jax.sharding.set_mesh(self.mesh)

    # -- submission ---------------------------------------------------

    def submit(self, prompt: Sequence[int], *, max_new_tokens: int = 64,
               temperature: float = 0.0,
               eos_token: Optional[int] = None,
               denoise_steps: Optional[int] = None,
               remask: Optional[str] = None,
               confidence_threshold: Optional[float] = None) -> GenRequest:
        """`denoise_steps`, `remask`, `confidence_threshold`: how a block
        is unmasked, where the model generates a block of positions a
        pass (`TransformerConfig.block_length`); None = the
        configuration's."""
        if self._stop:
            raise RuntimeError("engine is stopped")
        if len(prompt) == 0:
            raise ValueError("empty prompt")
        if len(prompt) >= self.max_seq_len:
            raise ValueError(
                f"prompt len {len(prompt)} >= max_seq_len {self.max_seq_len}")
        asked = (denoise_steps, remask, confidence_threshold)
        if self.block_length:
            cfg = self.cfg
            denoise_steps = cfg.denoise_steps if denoise_steps is None \
                else int(denoise_steps)
            remask = cfg.remask if remask is None else remask
            if confidence_threshold is None:
                confidence_threshold = cfg.confidence_threshold
            if not 1 <= denoise_steps <= self.block_length \
                    or remask not in REMASK_RULES:
                raise ValueError(
                    f"denoise_steps {denoise_steps} must be 1 to "
                    f"{self.block_length} and remask {remask!r} one of "
                    f"{REMASK_RULES}")
        elif any(a is not None for a in asked):
            raise ValueError(
                "denoise_steps, remask and confidence_threshold are for a "
                "model that generates by blocks (block_length): this one "
                "generates one token a step")
        span = tracing.span("engine.submit", prompt_tokens=len(prompt))
        with span:      # on the caller's thread
            req = GenRequest(prompt=list(prompt),
                             max_new_tokens=max_new_tokens,
                             temperature=temperature, eos_token=eos_token,
                             denoise_steps=denoise_steps, remask=remask,
                             confidence_threshold=confidence_threshold)
            with self.lock:
                req.id = self.counts["submitted"]
                self.counts["submitted"] += 1
            span.set(req=req.id)
            req.submit_ts = time.monotonic()
            req._steps_seen = self.steps_processed
            if self.auto_prefix_min_hits > 0:
                self._note_prefix_candidates(prompt)
            with self.lock:
                self.waiting.append(req)
            self._work.set()
        return req

    def generate(self, prompt: Sequence[int], *,
                 max_new_tokens: int = 64, temperature: float = 0.0,
                 eos_token: Optional[int] = None,
                 return_logprobs: bool = False,
                 timeout: Optional[float] = None,
                 **block_options) -> Dict[str, Any]:
        """Synchronous generation: submit + wait for completion
        (`block_options`: `submit`'s `denoise_steps`, `remask`,
        `confidence_threshold`).

        With `return_logprobs=True` the result carries per-token
        log-probabilities of the sampled tokens — log_softmax of the
        RAW logits, index-aligned with `tokens` — which is what the
        RLHF rollout plane feeds the GRPO ratio term.

        If no background loop is running (`start()` not called), the
        engine is driven from this thread — deterministic single-thread
        mode for tests and rollout actors that own their engine."""
        req = self.submit(prompt, max_new_tokens=max_new_tokens,
                          temperature=temperature, eos_token=eos_token,
                          **block_options)
        loop = getattr(self, "_loop_thread", None)
        if loop is None or not loop.is_alive():
            deadline = (time.monotonic() + timeout
                        if timeout is not None else None)
            while req.finish_ts == 0.0:
                if deadline is not None and time.monotonic() > deadline:
                    raise TimeoutError("generate timed out")
                self.step()
        tokens = req.result(timeout=timeout)
        out: Dict[str, Any] = {"tokens": tokens, "ttft_s": req.ttft_s,
                               "latency_s": req.latency_s,
                               "queue_s": req.queue_s,
                               "prefill_s": req.prefill_s,
                               "decode_s": req.decode_s}
        if return_logprobs:
            out["logprobs"] = list(req.logprobs)
        return out

    def _note_prefix_candidates(self, prompt: Sequence[int]) -> None:
        """Count every applicable block-length prefix BEYOND what a
        registered prefix already covers. Counting only the longest
        length would miss the feature's main target — a hot short
        system prompt followed by divergent user content (all
        longest-length keys distinct, none ever hot); counting covered
        lengths would re-register what longest-match already serves.
        Hot keys enqueue for engine-side registration; the drain's
        longest-first + covered-skip keeps nested keys of identical
        prompts from each costing a registration. Bounded table
        (LRU, 512)."""
        tokens = [int(t) for t in prompt]
        with self.lock:
            covered = 0
            for reg in self._prefixes:
                if (len(reg) > covered and len(reg) < len(tokens)
                        and tokens[:len(reg)] == list(reg)):
                    covered = len(reg)
            for L in self.auto_prefix_lens:
                if L >= len(tokens) or L >= self.max_seq_len - 1:
                    break
                if L <= covered:
                    continue
                key = tuple(tokens[:L])
                if key in self._prefixes or key in self._auto_inflight:
                    continue
                n = self._auto_counts.get(key, 0) + 1
                self._auto_counts[key] = n
                self._auto_counts.move_to_end(key)
                if n >= self.auto_prefix_min_hits:
                    del self._auto_counts[key]
                    self._auto_inflight.add(key)
                    self._auto_pending.append(key)
            while len(self._auto_counts) > 512:
                self._auto_counts.popitem(last=False)

    def _drain_auto_registrations(self) -> bool:
        """Register ONE pending hot prefix per tick (each registration
        is a prefill-sized dispatch; spreading them keeps admission
        latency bounded). Longest pending first; a pending key that is
        a PREFIX of an already-registered one is dropped — its prompts
        are almost always served by the longer registration, and if
        genuinely divergent traffic reappears it simply re-accumulates."""
        with self.lock:
            while self._auto_pending:
                key = max(self._auto_pending, key=len)
                self._auto_pending.remove(key)
                if any(len(reg) >= len(key)
                       and reg[:len(key)] == key
                       for reg in self._prefixes):
                    self._auto_inflight.discard(key)
                    continue
                break
            else:
                return False
        try:
            self.register_prefix(key)
        except ValueError:
            # The documented race: prompt family no longer fits (e.g.
            # max_seq_len shrunk relative to the candidate length).
            pass
        except Exception:  # noqa: BLE001 — device/XLA failure
            # Dropped, counted, and logged: a silently-vanishing hot
            # prefix would read as "caching stopped working".
            self.prefix_register_failures += 1
            import logging

            logging.getLogger("ray_tpu.serve").warning(
                "auto prefix registration failed (len %d); dropping",
                len(key), exc_info=True)
        finally:
            with self.lock:
                self._auto_inflight.discard(key)
        return True

    def set_params(self, params: Any) -> None:
        """Swap in a new policy (RLHF weight refresh). Device-puts
        (mesh-sharded when serving multi-chip), then recomputes every
        registered prefix — their pinned KV was built under the OLD
        weights, and serving it onward would silently mix policies in
        the captured logps."""
        if self.mesh is not None:
            from ..models.transformer import param_logical_axes
            from ..parallel.sharding import shard_pytree

            with jax.sharding.set_mesh(self.mesh):
                params = shard_pytree(
                    params, param_logical_axes(self.cfg), self.mesh)
        else:
            params = jax.device_put(params)
        with self.lock:
            self.params = params
            keys = list(self._prefixes)
            self._prefixes.clear()
        for key in keys:
            self.register_prefix(key)

    def register_prefix(self, tokens: Sequence[int]) -> None:
        """Precompute + pin the KV of a shared prompt prefix (system
        prompt). Later prompts starting with it skip its prefill
        entirely. LRU-capped at max_cached_prefixes."""
        key = tuple(int(t) for t in tokens)
        if not key:
            raise ValueError("empty prefix")
        if len(key) >= self.max_seq_len - 1:
            raise ValueError(
                f"prefix len {len(key)} leaves no room for a suffix "
                f"(max_seq_len {self.max_seq_len})")
        with self.lock:
            if key in self._prefixes:
                self._prefixes.move_to_end(key)
                return
        with self._mesh_ctx():
            pk, pv = compute_prefix_kv(self.cfg, self.params, key)
        with self.lock:
            self._prefixes[key] = {"k": pk, "v": pv}
            while len(self._prefixes) > self.max_cached_prefixes:
                self._prefixes.popitem(last=False)

    def _match_prefix(self, prompt: List[int]):
        """Longest registered prefix that strictly prefixes `prompt`
        (>=1 suffix token must remain) and whose install still fits the
        cache after suffix-bucket rounding. Returns the key or None."""
        if not self._prefixes:
            return None
        with self.lock:
            cands = sorted(self._prefixes, key=len, reverse=True)
        for key in cands:
            sp = len(key)
            if len(prompt) <= sp or tuple(prompt[:sp]) != key:
                continue
            if sp + self._bucket_for(len(prompt) - sp) > self.max_seq_len:
                continue
            with self.lock:
                entry = self._prefixes.get(key)
                if entry is not None:
                    self._prefixes.move_to_end(key)
                    # Entry captured under the lock: a concurrent
                    # register_prefix may LRU-evict the key before
                    # dispatch; the captured arrays stay valid.
                    return key, entry
        return None

    def _group_by_route(self, items: List, prompt_of, width):
        """Shared admission/early-token routing: split items into
        full-prefill tiles and prefix-suffix tiles, a bucket's items cut
        into chunks of `width(bucket)` rows (a suffix tile's bucket is
        the suffix's). Returns (full [(bucket, chunk)], suffix [(pkey,
        entry, bucket, chunk)]) — ONE implementation so the two call
        sites can never route the same prompt differently."""
        by_bucket: Dict[int, List] = {}
        by_prefix: Dict[tuple, List] = {}
        entries: Dict[tuple, Dict[str, Any]] = {}
        for it in items:
            prompt = prompt_of(it)
            match = self._match_prefix(prompt)
            if match is not None:
                pkey, entry = match
                entries[pkey] = entry
                by_prefix.setdefault(pkey, []).append(it)
            else:
                by_bucket.setdefault(
                    self._bucket_for(len(prompt)), []).append(it)

        def cut(bucket, its):
            W = width(bucket)
            return [its[off:off + W] for off in range(0, len(its), W)]

        full = [(b, chunk) for b, p in sorted(by_bucket.items())
                for chunk in cut(b, p)]
        suffix = []
        for pkey, its in by_prefix.items():
            sub: Dict[int, List] = {}
            for it in its:
                sub.setdefault(
                    self._bucket_for(len(prompt_of(it)) - len(pkey)),
                    []).append(it)
            suffix += [(pkey, entries[pkey], b, chunk)
                       for b, p in sorted(sub.items())
                       for chunk in cut(b, p)]
        return full, suffix

    # -- engine internals ---------------------------------------------

    def _bucket_for(self, n: int) -> int:
        for b in self.buckets:
            if n <= b:
                return b
        return self.buckets[-1]

    def _emit(self, slot: _Slot, tok: int, lp: float, rows: int = 1) -> None:
        """`rows`: final rows the token adds to the slot's cache (0 where
        a committed block has counted its rows already)."""
        slot.req.tokens.append(tok)
        slot.req.logprobs.append(float(lp))
        slot.req.stream.put(tok)
        slot.emitted += 1
        slot.length += rows
        self.tokens_out += 1

    def _cache_ended(self, slot: _Slot) -> bool:
        """Whether the slot's cache has no room for another step, stated
        once for a row and for a block: a row's step reads the token at
        `length` and writes its row, so it needs `length + 1` rows under
        `max_seq_len`; a block's pass writes rows [length, length +
        block_length), which may end at `max_seq_len`."""
        if self.block_length:
            return slot.length + self.block_length > self.max_seq_len
        return slot.length >= self.max_seq_len - 1

    def _complete(self, req: GenRequest, new_tokens: int) -> None:
        """Single place for request-completion bookkeeping (slot-path
        and queue-side finishes alike)."""
        req.finish_ts = time.monotonic()
        req.stream.put(None)
        self._n_finished += 1
        self.finished.append({
            "id": req.id,
            "ttft_s": req.ttft_s,
            "latency_s": req.latency_s,
            # TTFT waterfall: queue wait vs prefill vs decode (the
            # serve row bench.py --critpath records).
            "queue_s": req.queue_s,
            "prefill_s": req.prefill_s,
            "decode_s": req.decode_s,
            "new_tokens": new_tokens,
        })
        self._ttft_ewma = (
            req.ttft_s if self._ttft_ewma is None
            else 0.8 * self._ttft_ewma + 0.2 * req.ttft_s)

    def _finish(self, idx: int) -> None:
        slot = self.slots[idx]
        self._complete(slot.req, slot.emitted)
        self.slots[idx] = None

    # The widest prefill tile, and the width of every queue-side tile
    # (_early_first_tokens). Rows share one read of the weights, which
    # pays while that read bounds the tile: a bf16 weight is 2 bytes and
    # 2 operations a position and a term (`moe.dot_terms`: a float32
    # activation on a bf16 weight is multiplied as two bf16 terms, every
    # position two rows of the product), so a tile of P positions does
    # P x terms operations a byte, and a v5e turns from memory-bound to
    # compute-bound at 197 TFLOP/s / 819 GB/s = 240: ~240 positions
    # under one term, ~120 under two. Under the ridge a tile more rows
    # are free; past it each row costs its own arithmetic, real or not.
    _ADMIT_TILE = 8
    # Positions of ONE term a slot-side tile is filled up to: the next
    # power of two past the ridge with room (256 measured against 512:
    # PERF.md, section 6, PR 29). _tile_rows() makes the width from it
    # and the model's terms: 512 positions under one, 256 under two, the
    # same distance past either ridge (128 measured against 256:
    # PERF.md, section 6, PR 56).
    _TILE_POSITIONS = 512
    # Positions a queue-side tile holds at most: _ADMIT_TILE rows up to
    # the 1024 bucket, fewer past it, one from 8192 on (a tile's
    # temporaries grow with its positions: eight rows of 8192 do not fit
    # a chip beside 12 GB of weights and cache).
    _QUEUE_TILE_POSITIONS = 8192
    FINISHED_RING = 1024

    @classmethod
    def _tile_rows(cls, bucket: int, terms: int = 1) -> int:
        """Rows of a slot-side admission tile of `bucket` positions a
        row, for a model that multiplies a position as `terms` bf16
        terms (an engine asks `_slot_tile_rows`, under its own): a
        function of the bucket alone in one engine, so there is ONE
        program a bucket and a lone request runs what a full tile runs.
        Under one term buckets up to 64 keep _ADMIT_TILE rows, 128 gets
        4, 256 gets 2, 512 and longer 1; under two 32 keeps them, 64
        gets 4, 128 gets 2, 256 and longer 1. Several requests of a long
        bucket are several tiles in one tick, at the same arithmetic."""
        return max(1, min(cls._ADMIT_TILE,
                          cls._TILE_POSITIONS // terms // bucket))

    def _slot_tile_rows(self, bucket: int) -> int:
        """`_tile_rows` under the terms this engine's model multiplies a
        position as."""
        return self._tile_rows(bucket, self._dot_terms)

    @classmethod
    def _queue_tile_rows(cls, bucket: int) -> int:
        """Rows of a queue-side tile (`_early_first_tokens`) of `bucket`
        positions a row: _ADMIT_TILE while they fit
        _QUEUE_TILE_POSITIONS."""
        return max(1, min(cls._ADMIT_TILE,
                          cls._QUEUE_TILE_POSITIONS // bucket))

    def _touch(self, reqs: Sequence[GenRequest]) -> None:
        """Stamp the requests that engine compute touches for the first
        time in this tick (slot admission or the queue-side pass)."""
        now = time.monotonic()
        for req in reqs:
            if req.admit_ts == 0.0:
                req.admit_ts = now
                req.admit_tick = self.counts["ticks"] - 1
                req.steps_waited = self.decode_ticks - req._steps_seen

    def _tile_span(self, side: str, bucket: int, W: int,
                   reqs: Sequence[GenRequest], skip: int = 0,
                   tokens: Optional[int] = None, **more) -> tracing.span:
        """The span of one prefill tile of W rows (build, transfer,
        program call), with its counts: `rows` real of `tile_rows`,
        `tokens` real prompt tokens of tile_rows x bucket computed
        (`skip`: tokens a cached prefix already holds; `tokens`: where
        not every token of a prompt is prefilled, how many are)."""
        if tokens is None:
            tokens = sum(len(r.prompt) - skip for r in reqs)
        c = self.counts
        c["prefill_tiles"] += 1
        c["prefill_rows"] += len(reqs)
        c["prefill_tile_rows"] += W
        c["prefill_tokens"] += tokens
        c["prefill_tile_tokens"] += W * bucket
        if side == "queue":
            c["queue_side_first_tokens"] += len(reqs)
        # The rows' lengths as the program sees them: a row nobody fills
        # is built one token long, and a queue-side tile hands over none.
        lengths = [bucket] * W if side == "queue" else \
            [len(r.prompt) - skip for r in reqs] + [1] * (W - len(reqs))
        more.update(self._count(self._stack.tile_counts(
            self.cfg, bucket, lengths, tokens)))
        return tracing.span(
            "engine.prefill_tile", side=side, bucket=bucket, rows=len(reqs),
            tile_rows=W, tokens=tokens, req_ids=_ids(reqs), **more)

    def _count(self, found) -> Dict[str, Any]:
        """What the stack reckoned (`stackparts.counters`) onto the
        counters, entry by entry where a counter is a list. Returns what
        the span is to say."""
        counted, said = found
        for name, n in counted.items():
            old = self.counts[name]
            self.counts[name] = [a + b for a, b in zip(old, n)] \
                if isinstance(old, list) else old + n
        return said

    def _keep_tile_extras(self, extras: Extras, taken: List[int]) -> None:
        """An admission tile's `Extras`, their host copies started, until
        the host next waits for a tile. `taken`: a real row, whether the
        tile's token is the one its request is given."""
        if extras.exits is not None:
            self._tile_exits.append((extras.exits, taken))
        self._start_host_copy(extras.exits)
        if extras.routing is not None:
            self._tile_moe.append(extras.routing)
        self._start_host_copy(extras.routing)

    def _launch_span(self, program: str) -> tracing.span:
        """The span of one device program the engine's thread calls
        (its transfers and the call), numbered by program: the n-th
        `engine.launch` of a program is the n-th event of its module on
        the device, which is how a trace joins the two."""
        by_program = self.counts["launches"]
        seq = by_program.get(program, 0)
        by_program[program] = seq + 1
        return tracing.span("engine.launch", cpu=True, program=program,
                            seq=seq)

    @contextlib.contextmanager
    def _device_call(self, op: str, **attributes):
        """The span of ONE thing the engine's thread asks of the device
        or of the transfer engine (`op`, one of `DEVICE_OPS`), numbered:
        `call` is the engine's running count of them, which is how an
        `engine.fetch` says whose result it waits for. Wall-clock, so it
        reads the same where the thread's CPU clock is coarse; a call
        that stands blocked behind work in flight shows as a long one.
        Gives the `call`."""
        call = self._calls
        self._calls = call + 1
        t0 = time.monotonic_ns()
        try:
            with tracing.span("engine.device_call", op=op, call=call,
                              **attributes):
                yield call
        finally:
            took = time.monotonic_ns() - t0
            calls, ns = (self.counts["device_calls"],
                         self.counts["device_call_ns"])
            calls[op] = calls.get(op, 0) + 1
            ns[op] = ns.get(op, 0) + took

    def _program_call(self, launch: tracing.span, **more):
        """The `engine.device_call` of a launch's own program: it carries
        the launch's `program` and `seq` (and `k`, a decode block's)."""
        return self._device_call(
            "program", program=launch.attributes["program"],
            seq=launch.attributes["seq"], **more)

    def _start_host_copy(self, *arrays: Optional[jax.Array]) -> None:
        """`_copy_to_host_async` of the arrays that are there, as one
        `engine.device_call`; none at all, no call."""
        arrays = [a for a in arrays if a is not None]
        if arrays:
            with self._device_call("copy_start", n=len(arrays)):
                _copy_to_host_async(*arrays)

    @contextlib.contextmanager
    def _wait_span(self, name: str, count: str, **attributes):
        """A span in which the engine's thread only waits, for the
        device (`engine.fetch`: `attributes` name the call whose result
        it reads) or for work (`engine.idle_wait`), its length added to
        `counts[count]`."""
        t0 = time.monotonic_ns()
        try:
            with tracing.span(name, **attributes):
                yield
        finally:
            self.counts[count] += time.monotonic_ns() - t0

    @staticmethod
    def _build_tile(bucket: int, W: int, rows: Sequence):
        """Pad up to W token lists into one (W, bucket) host tile
        (+ lengths and temps). rows: [(tokens, temperature)].
        Padding on the HOST: an eager .at[].set() per prompt would
        compile a scatter kernel per distinct length (seconds each);
        numpy + one transfer doesn't."""
        with tracing.span("engine.tile_build"):
            buf = np.zeros((W, bucket), np.int32)
            lens = np.ones((W,), np.int32)
            temps = np.zeros((W,), np.float32)
            for j, (tokens, temp) in enumerate(rows):
                pl = len(tokens)
                buf[j, :pl] = np.asarray(tokens, np.int32)
                lens[j] = pl
                temps[j] = temp
        return buf, lens, temps

    def _admit(self) -> List:
        """Prefill waiting requests into free slots (arrival order).

        Admissions are BATCHED per prompt-length bucket into tiles of
        _tile_rows(bucket, the model's terms) rows and dispatched
        through prefill_sample_batch. Rows share one read of the
        weights, so in a short bucket (a tile under the ridge, ~240
        positions a bf16 term the model multiplies a position as:
        memory-bound) W serial prefills would cost ~W x one batched
        call; a long bucket's row is compute-bound alone, and there each
        request is its own one-row tile, several a tick. All dispatches
        are async; first tokens are fetched later by
        _deliver_first_tokens with one fused host sync. Requests whose
        first token was already served by _early_first_tokens() are
        prefilled in the same batch (their sampled token is discarded
        and decode continues from the token the client saw). Returns
        [(idx, tok_dev, the tile's log-probs, the request's row in
        them)].
        """
        with self.lock:
            free = [i for i, s in enumerate(self.slots) if s is None]
            take: List = []
            while free[len(take):] and self.waiting:
                take.append(self.waiting.popleft())
        if not take:
            return []
        with tracing.span("engine.admit", cpu=True, side="slot",
                          req_ids=_ids(take)):
            return self._admit_taken(take, free)

    def _admit_taken(self, take: List[GenRequest], free: List[int]) -> List:
        """`_admit`'s tiles, for the requests it took off the queue and
        the free slots they go to."""
        self._touch(take)
        if self.block_length:
            self._admit_blocks(take, free)
            return []

        admitted: List = []  # (idx, tok_dev, lps_dev, row) — pending
        # Route: prompts strictly extending a registered prefix go
        # through the suffix path (prefix KV copied, only the suffix
        # prefilled); the rest through the full path.
        full, suffix = self._group_by_route(
            list(zip(take, free)), lambda it: it[0].prompt,
            self._slot_tile_rows)
        chunks: List = [(bucket, None, None, chunk)
                        for bucket, chunk in full]
        chunks += [(bucket, pkey, entry, chunk)
                   for pkey, entry, bucket, chunk in suffix]

        for ci, (bucket, pkey, entry, chunk) in enumerate(chunks):
            W = self._slot_tile_rows(bucket)
            # Padding rows scatter out of bounds (slot==num_slots) and
            # are dropped on device.
            slot_idx = np.full((W,), self.num_slots, np.int32)
            for j, (_, idx) in enumerate(chunk):
                slot_idx[j] = idx
            with self._device_call("split"):
                self._key, sub = jax.random.split(self._key)
            reqs = [req for req, _ in chunk]
            try:
                if pkey is None:
                    with self._tile_span("slot", bucket, W, reqs):
                        buf, lens, temps = self._build_tile(
                            bucket, W,
                            [(req.prompt, req.temperature)
                             for req in reqs])
                        launch = self._launch_span(
                            PROGRAM_NAMES["prefill_sample_batch"])
                        with launch:
                            with self._device_call("to_device", n=4):
                                tile = (jnp.asarray(buf), jnp.asarray(lens),
                                        jnp.asarray(slot_idx),
                                        jnp.asarray(temps))
                            with self._program_call(launch):
                                self.cache, toks, lps, extras = \
                                    prefill_sample_batch(
                                        self.cfg, self.params, self.cache,
                                        *tile[:3], self.top_k, tile[3], sub)
                    # A request whose first token the queue side gave is
                    # counted there.
                    self._keep_tile_extras(extras, [
                        int(req._early_tok is None) for req in reqs])
                else:
                    sp = len(pkey)
                    with self._tile_span("slot", bucket, W, reqs, skip=sp):
                        buf, lens, temps = self._build_tile(
                            bucket, W,
                            [(req.prompt[sp:], req.temperature)
                             for req in reqs])
                        launch = self._launch_span(
                            PROGRAM_NAMES["prefill_suffix_batch"])
                        with launch:
                            with self._device_call("to_device", n=4):
                                tile = (jnp.asarray(buf), jnp.asarray(lens),
                                        jnp.asarray(slot_idx),
                                        jnp.asarray(temps))
                            with self._program_call(launch):
                                self.cache, toks, lps = prefill_suffix_batch(
                                    self.cfg, self.params, self.cache,
                                    entry["k"], entry["v"], *tile[:3],
                                    self.top_k, tile[3], sub)
                    self.prefix_hits += len(chunk)
                    self.prefix_tokens_saved += sp * len(chunk)
            except Exception:
                # put this and every unprocessed request back so
                # _fail_all can notify their clients
                with self.lock:
                    for _, _, _, later in reversed(chunks[ci:]):
                        for req, _ in reversed(later):
                            self.waiting.appendleft(req)
                raise
            self._start_host_copy(lps)
            with self._device_call("to_device", n=1):
                temps = jnp.asarray(temps)
            with self._device_call("scatter"):
                self._temps = self._temps.at[slot_idx].set(
                    temps, mode="drop")
            with self._device_call("scatter"):
                self.cur_tokens = self.cur_tokens.at[slot_idx].set(
                    toks, mode="drop")
            for j, (req, idx) in enumerate(chunk):
                slot = _Slot(req, len(req.prompt))
                self.slots[idx] = slot
                early_tok = getattr(req, "_early_tok", None)
                if early_tok is not None:
                    # First token already delivered queue-side: decode
                    # continues from the token the client saw, not this
                    # batch's sample.
                    slot.emitted = len(req.tokens)
                    slot.length = len(req.prompt) + slot.emitted
                    with self._device_call("scatter"):
                        self.cur_tokens = self.cur_tokens.at[idx].set(
                            int(early_tok))
                else:
                    with self._device_call("slice"):
                        tok = toks[j]
                    admitted.append((idx, tok, lps, j))
        return admitted

    def _admit_blocks(self, take: List[GenRequest], free: List[int]) -> None:
        """Admission where the model generates a block a pass: the whole
        blocks of each prompt are prefilled under the block-causal mask
        (a tile a bucket of that length, as `_admit_taken` cuts them) and
        what is left of the prompt opens the slot's first block, fixed,
        beside masks. No first token comes from the prompt's last logits:
        the first tokens are the first block's, a few passes on."""
        Bd = self.block_length
        by_bucket: Dict[int, List] = {}
        for req, idx in zip(take, free):
            whole = len(req.prompt) // Bd * Bd
            by_bucket.setdefault(self._bucket_for(whole), []).append(
                (req, idx, whole))
        rows = self._slot_tile_rows
        chunks = [(bucket, its[off:off + rows(bucket)])
                  for bucket, its in sorted(by_bucket.items())
                  for off in range(0, len(its), rows(bucket))]
        for ci, (bucket, chunk) in enumerate(chunks):
            W = rows(bucket)
            slot_idx = np.full((W,), self.num_slots, np.int32)
            first_x = np.full((W, Bd), self.cfg.mask_token_id, np.int32)
            first_masked = np.ones((W, Bd), bool)
            steps = np.ones((W,), np.int32)
            rule = np.zeros((W,), np.int32)
            threshold = np.zeros((W,), np.float32)
            reqs = [req for req, _, _ in chunk]
            for j, (req, idx, whole) in enumerate(chunk):
                rest = req.prompt[whole:]
                slot_idx[j] = idx
                first_x[j, :len(rest)] = rest
                first_masked[j, :len(rest)] = False
                steps[j] = req.denoise_steps
                rule[j] = REMASK_RULES.index(req.remask)
                threshold[j] = req.confidence_threshold
            try:
                with self._tile_span(
                        "slot", bucket, W, reqs,
                        tokens=sum(whole for _, _, whole in chunk),
                        mask="block_causal"):
                    buf, lens, temps = self._build_tile(
                        bucket, W, [(req.prompt[:whole], req.temperature)
                                    for req, _, whole in chunk])
                    launch = self._launch_span(
                        PROGRAM_NAMES["prefill_block_batch"])
                    with launch:
                        with self._device_call("to_device", n=8):
                            tile = [jnp.asarray(a) for a in (
                                buf, lens, slot_idx, first_x, first_masked,
                                steps, rule, threshold)]
                        with self._program_call(launch):
                            self.cache, self._blocks, extras = \
                                prefill_block_batch(
                                    self.cfg, self.params, self.cache,
                                    self._blocks, *tile)
                        with self._device_call("to_device", n=1):
                            temps = jnp.asarray(temps)
                        with self._device_call("scatter"):
                            self._temps = self._temps.at[slot_idx].set(
                                temps, mode="drop")
            except Exception:
                with self.lock:
                    for _, later in reversed(chunks[ci:]):
                        for req, _, _ in reversed(later):
                            self.waiting.appendleft(req)
                raise
            self._keep_tile_extras(extras, [])
            for req, idx, whole in chunk:
                slot = _Slot(req, whole)
                # What the prompt left over stands in the first block.
                slot.blocks_left = -(-(len(req.prompt) - whole
                                       + req.max_new_tokens) // Bd)
                self.slots[idx] = slot

    def _read_tile_moe(self, span) -> None:
        """The admission tiles' routing stats, once the device has them:
        onto the counters and onto `span`."""
        if not self._tile_moe:
            return
        tiles, self._tile_moe = self._tile_moe, []
        with self._device_call("to_host", n=len(tiles)):
            routing = np.sum([np.asarray(m) for m in tiles], 0)
        span.set(moe_tiles=len(tiles), **self._count(
            self._stack.result_counts(self.cfg, 0, Extras(routing=routing),
                                      ())))

    def _early_first_tokens(self) -> List:
        """TTFT decoupled from slot availability: queued requests that
        could not be admitted get their FIRST token from a cache-free
        batched forward (models/generate.first_token_sample), in
        arrival order, one dispatch per prompt-bucket tile. When a slot
        frees, _admit prefills the prompt and decode resumes from this
        token — the client's stream stays consistent. Returns
        [(chunk_requests, toks_dev, lps_dev)]; fetched by
        _deliver_first_tokens."""
        if self.block_length:
            return []       # no token comes from a prompt's last logits
        with self.lock:
            todo = [r for r in self.waiting
                    if r.first_token_ts == 0.0]
        if not todo:
            return []
        with tracing.span("engine.admit", cpu=True, side="queue",
                          req_ids=_ids(todo)):
            return self._first_token_tiles(todo)

    def _first_token_tiles(self, todo: List[GenRequest]) -> List:
        """`_early_first_tokens`' tiles."""
        # Queue-side compute IS a request's admission for TTFT-waterfall
        # purposes (prefill starts here).
        self._touch(todo)
        outs = []
        # Queue-side tiles are _ADMIT_TILE wide up to the 1024 bucket
        # (_queue_tile_rows); a narrower tile's results are padded to
        # that width, so the first-token fusion sees one shape a tile.
        full, suffix = self._group_by_route(todo, lambda r: r.prompt,
                                            self._queue_tile_rows)
        for bucket, chunk in full:
            W = self._queue_tile_rows(bucket)
            with self._tile_span("queue", bucket, W, chunk):
                buf, lens, temps = self._build_tile(
                    bucket, W, [(r.prompt, r.temperature) for r in chunk])
                with self._device_call("split"):
                    self._key, sub = jax.random.split(self._key)
                launch = self._launch_span(
                    PROGRAM_NAMES["first_token_sample"])
                with launch:
                    with self._device_call("to_device", n=3):
                        tile = (jnp.asarray(buf), jnp.asarray(lens),
                                jnp.asarray(temps))
                    with self._program_call(launch):
                        toks, lps, extras = first_token_sample(
                            self.cfg, self.params, *tile, self.top_k, sub)
                self._keep_tile_extras(extras, [1] * len(chunk))
                if W < self._ADMIT_TILE:
                    with self._device_call("pad"):
                        toks = jnp.pad(toks, (0, self._ADMIT_TILE - W))
                    with self._device_call("pad"):
                        lps = jnp.pad(lps, (0, self._ADMIT_TILE - W))
            self._start_host_copy(lps)
            outs.append((chunk, toks, lps))
        W = self._ADMIT_TILE      # a suffix tile's: its bucket is short
        # Prefix-matched queued requests: suffix-only forward against
        # the stored prefix KV (same FLOP saving as slot admission).
        for pkey, entry, bucket, chunk in suffix:
            sp = len(pkey)
            with self._tile_span("queue", bucket, W, chunk, skip=sp):
                buf, lens, temps = self._build_tile(
                    bucket, W, [(r.prompt[sp:], r.temperature)
                                for r in chunk])
                with self._device_call("split"):
                    self._key, sub = jax.random.split(self._key)
                launch = self._launch_span(
                    PROGRAM_NAMES["first_token_suffix_sample"])
                with launch:
                    with self._device_call("to_device", n=3):
                        tile = (jnp.asarray(buf), jnp.asarray(lens),
                                jnp.asarray(temps))
                    with self._program_call(launch):
                        toks, lps = first_token_suffix_sample(
                            self.cfg, self.params, entry["k"], entry["v"],
                            *tile, self.top_k, sub)
            self.prefix_hits += len(chunk)
            self.prefix_tokens_saved += sp * len(chunk)
            self._start_host_copy(lps)
            outs.append((chunk, toks, lps))
        return outs

    def _fuse_first_tokens(self, admitted: List, outs: List):
        """Concatenate every pending first token into ONE device array
        and start its host copy — enqueued BEFORE the decode block so
        the device serves it first (device execution is in-order; a
        fetch enqueued after the block would wait out the whole
        block). Tokens alone: the stack and the concatenation are eager,
        one compiled program per aval, and a benchmark warms them for
        int32. The log-probabilities come over tile by tile, as their
        programs returned them. Returns the array with the `call` that
        made it, or None."""
        if not admitted and not outs:
            return None
        with tracing.span("engine.fuse_first"):
            parts = []
            if admitted:
                with self._device_call("stack", n=len(admitted)):
                    parts.append(jnp.stack([t for _, t, _, _ in admitted]))
            parts += [t for _, t, _ in outs]
            with self._device_call("concatenate", n=len(parts)) as call:
                fused = jnp.concatenate(parts)
            self._start_host_copy(fused)
        # With the call the first tokens' fetch waits for.
        return fused, call

    def _deliver_first_tokens(self, fused, admitted: List,
                              outs: List) -> None:
        """Emit the fused first tokens (one host sync, usually already
        in flight via copy_to_host_async)."""
        if fused is None:
            return
        fused, call = fused
        span = tracing.span("engine.deliver_first", tokens=len(admitted)
                            + sum(len(reqs) for reqs, _, _ in outs))
        with span:
            # the host waits here, for the fusion's concatenation (the
            # eager programs between it and the tiles carry the calls
            # before it)
            with self._wait_span("engine.fetch", "fetch_wait_ns", call=call):
                with self._device_call("to_host", n=1):
                    fused = np.asarray(fused)
                with self._device_call(
                        "to_host", n=len(admitted) + len(outs)):
                    host_lps = [np.asarray(lps)[j:j + 1]
                                for _, _, lps, j in admitted] \
                        + [np.asarray(lps) for _, _, lps in outs]
                fused_lp = np.concatenate(host_lps)
                # What the admission tiles behind these tokens (and any
                # whose tokens the queue side had served) routed: a
                # tile's span ends at its dispatch, before the device
                # knows, so the numbers ride the span that waits for it.
                self._read_tile_moe(span)
                if self._tile_exits:
                    tiles, self._tile_exits = self._tile_exits, []
                    with self._device_call("to_host", n=len(tiles)):
                        exits = np.concatenate([np.asarray(a)[:len(taken)]
                                                for a, taken in tiles])
                    span.set(**self._count(self._stack.result_counts(
                        self.cfg, 0, Extras(exits=exits),
                        [t for _, taken in tiles for t in taken])))
            slots = (self.slots[idx] for idx, _, _, _ in admitted)
            first = [s.req for s in slots if s is not None] \
                + [r for reqs, _, _ in outs for r in reqs]
            with self._emit_span(first=1, req_ids=_ids(first)):
                self._emit_first_tokens(fused, fused_lp, admitted, outs)

    @contextlib.contextmanager
    def _emit_span(self, **attributes):
        """The span of handing a block's tokens, or a tick's first
        tokens, to their requests: how many, and how many requests that
        ended."""
        tokens, finished = self.tokens_out, self._n_finished
        span = tracing.span("engine.emit", cpu=True, **attributes)
        with span:
            yield
            span.set(tokens=self.tokens_out - tokens,
                     finished=self._n_finished - finished)

    def _emit_first_tokens(self, fused, fused_lp, admitted: List,
                           outs: List) -> None:
        pos = 0
        now = time.monotonic()
        if admitted:
            for j, ((idx, _, _, _), tok) in enumerate(
                    zip(admitted, fused[:len(admitted)])):
                slot = self.slots[idx]
                if slot is None:  # drained by a concurrent stop()
                    continue
                tok = int(tok)
                slot.req.first_token_ts = now
                self._emit(slot, tok, fused_lp[j])
                if (tok == slot.req.eos_token
                        or slot.emitted >= slot.req.max_new_tokens):
                    self._finish(idx)
            pos = len(admitted)
        for reqs, toks, _ in outs:
            host = fused[pos:pos + toks.shape[0]]
            host_lp = fused_lp[pos:pos + toks.shape[0]]
            pos += toks.shape[0]
            for j, r in enumerate(reqs):
                tok = int(host[j])
                r.first_token_ts = now
                r._early_tok = tok
                r.tokens.append(tok)
                r.logprobs.append(float(host_lp[j]))
                r.stream.put(tok)
                self.tokens_out += 1
                if tok == r.eos_token or r.max_new_tokens <= 1:
                    # Finished before ever occupying a slot.
                    with self.lock:
                        try:
                            self.waiting.remove(r)
                        except ValueError:
                            continue  # already admitted concurrently
                    self._complete(r, len(r.tokens))

    def step(self) -> bool:
        """One engine tick: admit, serve queued requests' first tokens
        (cache-free path — TTFT does not wait for a slot), dispatch one
        fused block of decode steps for all slots, then process the
        PREVIOUS tick's block. The one-block pipeline means the host
        fetch of block N overlaps the device computing block N+1 —
        without it the chip idles while the host fetches and emits each
        block. The on-device dependency chain (cache, cur_tokens) is
        exact; the host only lags by one block in observing tokens, so
        EOS/finish frees a slot one tick late (bounded overshoot, same
        class as mid-block overshoot). Returns False when idle."""
        c = self.counts
        tick = c["ticks"]
        c["ticks"] = tick + 1
        span = tracing.span(
            "engine.tick", cpu=True, tick=tick, waiting=len(self.waiting),
            active=sum(s is not None for s in self.slots))
        t0 = time.monotonic_ns()
        try:
            with self._mesh_ctx(), span:
                return self._step_impl()
        finally:
            c["tick_ns"] += time.monotonic_ns() - t0
            c["tick_cpu_ns"] += span.attributes.get("cpu_us", 0) * 1000

    def _step_impl(self) -> bool:
        registered = (self._drain_auto_registrations()
                      if self.auto_prefix_min_hits > 0 else False)
        admitted = self._admit()
        outs = self._early_first_tokens()
        # Snapshot: a concurrent stop()/_fail_all may None-out entries
        # under us; every later access goes through the snapshot or
        # re-checks self.slots[i].
        fused = self._fuse_first_tokens(admitted, outs)
        snap = list(self.slots)
        active = [i for i, s in enumerate(snap) if s is not None]
        block = None
        if active:
            # Block size (adaptive, per step): `block_steps` of the
            # minimum remaining generation budget among active slots,
            # counting ticks already in flight: a power of two (each
            # distinct size is its own XLA compile), rounded UP where
            # that overshoots the budget by a few steps and DOWN where
            # by more, the rest following as a shorter block a tick
            # later, queued behind this one before it is fetched.
            # (Always up until PR 49: a budget of 48 ran 64 steps, and
            # with one caller the finishing slot is the only slot: its
            # last token left when the block ended, 17 steps late.)
            # Capped by self.decode_block (compile-cache/latency bound)
            # and by every slot's DEVICE-side cache headroom (length +
            # inflight) so no in-block write can run past max_seq_len.
            # Where a step is a pass over a block, a slot's budget is
            # the passes its blocks can still take (a commit behind each
            # block's denoising passes), and the program itself stops a
            # slot at the cache's end.
            left = [self._steps_left(snap[i]) for i in active]
            headroom = self.decode_block if self.block_length else min(
                self.max_seq_len - 1 - snap[i].length - snap[i].inflight
                for i in active)
            budget = max(left)
            if budget > 0 or self._pending is None:
                k_block, k_up = block_steps(
                    min(left), self.decode_block, headroom)
                block = self._dispatch_block(k_block, snap, active, k_up)
            # else: every active slot's budget is already covered by
            # the in-flight block — dispatching more would only burn
            # wasted ticks; process the pending block instead.

        # First tokens (this step's admissions + queued requests) were
        # enqueued for copy before the block — emit them while the
        # block computes.
        self._deliver_first_tokens(fused, admitted, outs)
        prev, self._pending = self._pending, block
        if prev is not None:
            self._process_block(prev)
        return bool(admitted or outs or block or prev or registered)

    def _steps_left(self, slot: _Slot) -> int:
        """Decode steps the slot's request can still use beyond those in
        flight: its tokens left, or, where a step is a pass over a block,
        the passes its blocks left can take (each its denoising passes
        and a commit: fewer where the dynamic rule finishes a block
        early)."""
        if self.block_length:
            return slot.blocks_left * (slot.req.denoise_steps + 1) \
                - slot.inflight
        return slot.req.max_new_tokens - slot.emitted - slot.inflight

    def _rows_held(self, k_block: int, slot: _Slot) -> int:
        """Cache rows the slot's attention reads over the next `k_block`
        steps, once a step. One token a step: a slot at device position
        p holds p + 1 once the step's row is written. A block a pass: a
        pass reads the committed rows and the block's own, and the rows
        grow by a block a commit, reckoned here from the request's
        static schedule (a commit every `denoise_steps` + 1 passes; the
        program does not say before the host has to count)."""
        S = self.max_seq_len
        if self.block_length:
            Bd, cycle = self.block_length, slot.req.denoise_steps + 1
            p0 = slot.length + Bd * (slot.inflight // cycle)
            return sum(min(p0 + Bd * (t // cycle) + Bd, S)
                       for t in range(k_block))
        return min(k_block * (slot.length + slot.inflight + 1)
                   + k_block * (k_block - 1) // 2, k_block * S)

    def _dispatch_block(self, k_block: int, snap: List, active: List[int],
                        k_up: int = 0):
        """One fused block of `k_block` decode steps for every slot (the
        program computes all `num_slots`; `active` of them hold a
        request, and it is told which: the others' cache rows are not
        read). Where the model generates a block of positions a pass, a
        step is a pass. `k_up`: the steps rounding the budget up would
        have run, where more than `k_block`. Returns the pending block
        `_process_block` takes."""
        c = self.counts
        number = c["blocks"]
        c["blocks"] = number + 1
        c["blocks_by_k"][k_block] = c["blocks_by_k"].get(k_block, 0) + 1
        # Positions computed: a slot a step, times the block a pass
        # works on.
        c["slot_steps"] += k_block * self.num_slots * self._step_rows
        # Cache rows the block's steps could read, and the rows its
        # owned slots hold over those steps.
        rows = k_block * self.num_slots * self.max_seq_len
        held = sum(self._rows_held(k_block, snap[i]) for i in active)
        c["cache_rows"] += rows
        c["cache_rows_held"] += held
        owned = np.zeros((self.num_slots,), bool)
        owned[active] = True
        more = dict(passes=k_block, block_length=self.block_length) \
            if self.block_length else {}
        if k_up > k_block:
            c["blocks_rounded_down"] += 1
            more.update(short_of=k_up)
        more.update(self._count(self._stack.block_counts(
            self.cfg, k_block, self.num_slots, self.max_seq_len,
            [snap[i].length + snap[i].inflight + 1 for i in active], held)))
        with tracing.span("engine.dispatch_block", block=number, k=k_block,
                          active=len(active), slots=self.num_slots,
                          cache_rows=rows, cache_rows_held=held, **more):
            # Everything the block asks of the device, a call at a time:
            # the key's split, the transfer, the program, the slice and
            # the copies' start.
            launch = self._launch_span(
                PROGRAM_NAMES["decode_multi"].format(k=k_block))
            with launch:
                with self._device_call("split"):
                    self._key, sub = jax.random.split(self._key)
                with self._device_call("to_device", n=1):
                    live = jnp.asarray(owned)
                lps = sampler = None
                extras = Extras()
                if self.block_length:
                    with self._program_call(launch, k=k_block) as call:
                        self.cache, self._blocks, toks, extras = \
                            decode_block_multi(
                                self.cfg, self.params, self.cache,
                                self._blocks, self._temps, k_block,
                                self.top_k, sub, live)
                    self._start_host_copy(*toks)
                elif k_block == 1 and not self._by_products:
                    with self._program_call(launch, k=k_block):
                        self.cache, logits = decode_step(
                            self.cfg, self.params, self.cache,
                            self.cur_tokens, live)
                    # The sampler's tokens are what the host reads: the
                    # fetch names this call (a program of the table's,
                    # no `engine.launch` and so no `seq` of its own).
                    sampler = PROGRAM_NAMES["sample_batch"]
                    with self._device_call("program",
                                           program=sampler) as call:
                        toks, lps = _sample_batch(logits, self._temps, sub,
                                                  self.top_k)
                    with self._device_call("slice"):
                        toks = toks[None]                  # (1, B)
                else:
                    with self._program_call(launch, k=k_block) as call:
                        self.cache, toks, lps, extras = decode_multi(
                            self.cfg, self.params, self.cache,
                            self.cur_tokens, self._temps, k_block,
                            self.top_k, sub, live)         # (k, B)
                # Start the host copy NOW, before the next tick enqueues
                # prefills and the next block behind it.
                if not self.block_length:
                    with self._device_call("slice"):
                        self.cur_tokens = toks[-1]
                    self._start_host_copy(toks, lps, extras.exits)
                self._start_host_copy(extras.routing)
            # Whose result `_process_block`'s fetch waits for.
            source = dict(call=call, program=sampler) if sampler else dict(
                call=call, program=launch.attributes["program"],
                seq=launch.attributes["seq"])
            self.decode_ticks += k_block
            for i in active:
                snap[i].inflight += k_block
        return (toks, lps, k_block, [(i, snap[i]) for i in active], number,
                extras, source)

    def warm_decode_blocks(self) -> List[int]:
        """Run the fused decode program of every size the adaptive block
        can choose that has not run yet, with no slot owned: each
        compiles, the cache and the slots stay as they are. For a caller
        that must not compile later (a benchmark's set-up), before the
        engine's thread starts. Returns the sizes it ran."""
        ran, k = [], 1
        while k <= self.decode_block:
            if k not in self.counts["blocks_by_k"]:
                self._process_block(self._dispatch_block(
                    k, list(self.slots), []))
                ran.append(k)
            k *= 2
        return ran

    def _process_block(self, block) -> None:
        """Fetch a dispatched decode block's tokens and emit them.

        The block's slot snapshot carries the _Slot OBJECTS from
        dispatch time: a slot index freed and readmitted while the
        block was in flight now holds a different request, and the
        identity check keeps the dead request's overshoot tokens out
        of the new request's stream."""
        toks, lps, k_block, slot_snap, number, extras, source = block
        # `slots`: positions a step computes (a slot's block a pass).
        span = tracing.span("engine.process_block", block=number, k=k_block,
                            slots=self.num_slots * self._step_rows,
                            active=len(slot_snap))
        with span:
            # the host waits here
            with self._wait_span("engine.fetch", "fetch_wait_ns", **source):
                if self.block_length:
                    with self._device_call("to_host", n=len(toks)):
                        host = [np.asarray(a) for a in toks]
                    # The tiles behind these passes: no first token
                    # waits for them, so their numbers ride this span.
                    self._read_tile_moe(span)
                else:
                    with self._device_call("to_host", n=2):
                        host_toks = np.asarray(toks)
                        # (B,) after a one-step block's own sampler
                        host_lps = np.asarray(lps).reshape(host_toks.shape)
                extras = Extras(*(a if a is None else self._to_host(a)
                                  for a in extras))
            self.steps_processed += k_block
            before = self.tokens_out
            with self._emit_span():
                if self.block_length:
                    passes = self._emit_passes(host, k_block, slot_snap)
                    taken = ()      # a block's tokens: nothing read of them
                else:
                    taken = self._emit_block(host_toks, host_lps, k_block,
                                             slot_snap)
            emitted = self.tokens_out - before
            discarded = k_block * len(slot_snap) * self._step_rows - emitted
            if self.block_length:
                for name, n in passes.items():
                    self.counts[name] += n
                span.set(block_length=self.block_length, **passes)
            self.counts["tokens_discarded"] += discarded
            span.set(emitted=emitted, discarded=discarded)
            span.set(**self._count(self._stack.result_counts(
                self.cfg, k_block, extras, taken)))

    def _to_host(self, array: jax.Array) -> np.ndarray:
        """One array's host read, as one `engine.device_call`."""
        with self._device_call("to_host", n=1):
            return np.asarray(array)

    def _emit_block(self, host_toks, host_lps, k_block: int,
                    slot_snap: List) -> List[int]:
        """Returns, a slot, the steps of the block whose tokens it was
        given, its first ones."""
        took = [0] * self.num_slots
        for i, slot0 in slot_snap:
            slot0.inflight -= k_block
            slot = self.slots[i]
            if slot is not slot0:
                continue  # freed (and possibly readmitted) meanwhile
            for t in range(k_block):
                if slot is None or slot is not slot0:
                    break  # drained by stop() / finished below
                tok = int(host_toks[t, i])
                self._emit(slot, tok, host_lps[t, i])
                took[i] = t + 1
                done = (tok == slot.req.eos_token
                        or slot.emitted >= slot.req.max_new_tokens
                        or self._cache_ended(slot))
                if done:
                    # Remaining in-block tokens for this slot are
                    # discarded; the slot frees for readmission.
                    self._finish(i)
                    break
                slot = self.slots[i]
        return took

    def _emit_passes(self, host, k_block: int, slot_snap: List
                     ) -> Dict[str, int]:
        """A fused block of passes (`decode_block_multi`): where a pass
        committed a slot's block, the block's new tokens go to the
        request, each with the pass that unmasked it. The last block is
        generated whole and cut at `max_new_tokens`, or behind an eos.
        Returns what the passes did, for the span and the counters."""
        kind, x, at_pass, logp, unmasked = host
        Bd = self.block_length
        owned = [i for i, _ in slot_snap]
        done = dict(
            denoise_passes=int(np.sum(kind[:, owned] == PASS_DENOISE)),
            commit_passes=int(np.sum(kind[:, owned] == PASS_COMMIT)),
            blocks_committed=0,
            positions_unmasked=int(np.sum(unmasked[:, owned])),
            tokens_truncated=0)
        now = time.monotonic()
        for i, slot0 in slot_snap:
            slot0.inflight -= k_block
            if self.slots[i] is not slot0:
                continue  # freed (and possibly readmitted) meanwhile
            req = slot0.req
            for t in np.flatnonzero(kind[:, i] == PASS_COMMIT):
                # The block stood at rows [length, length + Bd): what the
                # prompt left over of its last whole block came fixed.
                fixed = max(0, len(req.prompt) - slot0.length)
                slot0.length += Bd
                slot0.blocks_left -= 1
                done["blocks_committed"] += 1
                ended = False
                for j in range(fixed, Bd):
                    if ended or slot0.emitted >= req.max_new_tokens:
                        done["tokens_truncated"] += 1
                        continue
                    if req.first_token_ts == 0.0:
                        req.first_token_ts = now
                    tok = int(x[t, i, j])
                    req.unmasked_at.append(int(at_pass[t, i, j]))
                    self._emit(slot0, tok, logp[t, i, j], rows=0)
                    ended = tok == req.eos_token
                if ended or slot0.emitted >= req.max_new_tokens \
                        or self._cache_ended(slot0):
                    # What the program ran on for this slot is discarded.
                    self._finish(i)
                    break
        return done

    def run_forever(self) -> None:
        while not self._stop:
            try:
                busy = self.step()
            except Exception as e:  # noqa: BLE001 — device/XLA errors
                self._fail_all(e)
                raise
            if not busy:
                self._work.clear()
                with self._wait_span("engine.idle_wait", "idle_wait_ns"):
                    self._work.wait(timeout=0.1)

    def _fail_all(self, exc: Exception) -> None:
        """A step blew up (OOM, XLA error): unblock every waiting client
        with the error instead of hanging their streams forever."""
        self._stop = True
        msg = f"{type(exc).__name__}: {exc}"
        with self.lock:
            pending = list(self.waiting)
            self.waiting.clear()
        for i, slot in enumerate(self.slots):
            if slot is not None:
                slot.req.error = msg
                slot.req.finish_ts = time.monotonic()
                slot.req.stream.put(None)
                self.slots[i] = None
        for req in pending:
            req.error = msg
            req.finish_ts = time.monotonic()
            req.stream.put(None)

    def start(self) -> threading.Thread:
        def loop() -> None:
            # Before its first span: a profile then keeps this thread's
            # spans apart from its callers' (`engine.submit`).
            tracing.name_thread("llm-engine")
            self.run_forever()

        t = threading.Thread(target=loop, daemon=True, name="llm-engine")
        self._loop_thread = t
        t.start()
        return t

    def stop(self) -> None:
        """Stop the engine and drain pending requests: every in-flight or
        waiting client gets an 'engine stopped' error instead of hanging
        on its stream."""
        self._stop = True
        self._work.set()
        self._fail_all(RuntimeError("engine stopped"))

    def stats(self) -> Dict[str, Any]:
        """Totals since the engine started, and `counts` (what the
        engine's spans carry, summed: docs/METRICS.md). `ttft_p50_s` and
        `ttft_p99_s` are over the last FINISHED_RING (1,024) completed
        requests, not over the engine's life."""
        fin = list(self.finished)
        ttfts = sorted(f["ttft_s"] for f in fin)
        out: Dict[str, Any] = {
            "finished": self._n_finished,
            "counts": {k: type(v)(v) if isinstance(v, (dict, list)) else v
                       for k, v in self.counts.items()},
            "decode_ticks": self.decode_ticks,
            "tokens_out": self.tokens_out,
            "waiting": len(self.waiting),
            "active": sum(s is not None for s in self.slots),
            "prefix_hits": self.prefix_hits,
            "prefix_tokens_saved": self.prefix_tokens_saved,
            "cached_prefixes": len(self._prefixes),
        }
        if ttfts:
            out["ttft_p50_s"] = ttfts[len(ttfts) // 2]
            out["ttft_p99_s"] = ttfts[min(len(ttfts) - 1,
                                          int(len(ttfts) * 0.99))]
        if self._ttft_ewma is not None:
            out["ewma_ttft_s"] = self._ttft_ewma
        return out

    def serve_routing_stats(self) -> Dict[str, Any]:
        """Routing signals the serve Replica wrapper merges into its
        stats() payload (controller polls it, routers use it for
        queue-depth + TTFT-aware replica choice)."""
        out: Dict[str, Any] = {"engine_queue": len(self.waiting)}
        if self._ttft_ewma is not None:
            out["ewma_ttft_s"] = self._ttft_ewma
        return out


class LLMServer:
    """Serve deployment wrapper: one engine per replica, background
    loop. Use with @serve.deployment / serve.run; methods are invoked
    through DeploymentHandles."""

    def __init__(self, cfg: TransformerConfig, params: Any = None, *,
                 num_slots: int = 4, max_seq_len: Optional[int] = None,
                 seed: int = 0, auto_prefix_min_hits: int = 0,
                 auto_prefix_lens: Sequence[int] = (64, 128, 256, 512),
                 plan: Any = None,
                 mesh: Optional["jax.sharding.Mesh"] = None):
        if mesh is None and plan is not None:
            # Replica-level sharding plan (tp/fsdp) → device mesh; the
            # deployment config carries the plan, each replica builds
            # its mesh from its own visible devices.
            from ..parallel import make_mesh
            mesh = make_mesh(plan)
        if params is None:
            key = jax.random.key(seed)
            params = (init_params(cfg, key) if mesh is None
                      else init_params_sharded(cfg, key, mesh))
        self.engine = LLMEngine(cfg, params, num_slots=num_slots,
                                max_seq_len=max_seq_len,
                                auto_prefix_min_hits=auto_prefix_min_hits,
                                auto_prefix_lens=auto_prefix_lens,
                                mesh=mesh)
        self.engine.start()

    def generate(self, prompt: Sequence[int], *, max_new_tokens: int = 64,
                 temperature: float = 0.0,
                 eos_token: Optional[int] = None,
                 return_logprobs: bool = False) -> Dict[str, Any]:
        return self.engine.generate(
            prompt, max_new_tokens=max_new_tokens,
            temperature=temperature, eos_token=eos_token,
            return_logprobs=return_logprobs)

    def register_prefix(self, tokens: Sequence[int]) -> None:
        """Precompute a shared prompt prefix's KV on this replica."""
        self.engine.register_prefix(tokens)

    def stats(self) -> Dict[str, Any]:
        return self.engine.stats()

    def serve_routing_stats(self) -> Dict[str, Any]:
        """Merged into Replica.stats() → controller poll → router."""
        return self.engine.serve_routing_stats()


class LLMIngress:
    """Front deployment of the two-stage LLM app: takes the server's
    DeploymentHandle as an init arg (serve.run resolves the nested
    Application into the handle) and forwards generation requests —
    the composition pattern of the reference's serving app graphs
    (router/ingress -> engine deployment)."""

    def __init__(self, server):
        self.server = server

    def generate(self, prompt: Sequence[int], *,
                 max_new_tokens: int = 64, temperature: float = 0.0,
                 eos_token: Optional[int] = None,
                 timeout: Optional[float] = 60.0) -> Dict[str, Any]:
        return self.server.generate.remote(
            prompt, max_new_tokens=max_new_tokens,
            temperature=temperature,
            eos_token=eos_token).result(timeout=timeout)

    def stats(self, *, timeout: Optional[float] = 60.0) -> Dict[str, Any]:
        return self.server.stats.remote().result(timeout=timeout)


@graphable(name="serve.llm_app")
def build_llm_app(cfg: TransformerConfig, *, num_slots: int = 4,
                  num_replicas: int = 1, seed: int = 0,
                  auto_prefix_min_hits: int = 0):
    """Build the LLM serving application graph: ingress -> server.

    The composition is declared with `.bind()` and materialized by
    `serve.run(...)`; `@graphable` marks it as a capture entry so
    raylint's graphcap pass extracts the deployment graph statically
    and tests/test_graph_capture.py verifies it against the
    controller's dynamic `app_graph()` view.
    """
    from .deployment import deployment

    server_dep = deployment(LLMServer, name="llm_server",
                            num_replicas=num_replicas)
    server_app = server_dep.bind(cfg, num_slots=num_slots, seed=seed,
                                 auto_prefix_min_hits=auto_prefix_min_hits)
    ingress_dep = deployment(LLMIngress, name="llm_ingress")
    return ingress_dep.bind(server_app)
