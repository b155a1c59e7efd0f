"""The serving programs: what `serve/llm.py` launches on the device, each
jitted under the name `PROGRAM_NAMES` gives it. They are written once
for every architecture: a program reaches the model through the stack
module of its configuration (`transformer.stack(cfg)`, `offered`) and
never asks which one that is (tests/test_stacks.py). Designed for XLA's
compilation model:

- **Static shapes everywhere.** Sequences occupy slots of a cache whose
  shape is fixed for the life of an engine (`init_kv_cache`; what it
  holds is its stack's business: `stackparts.KVCache`); every program
  that takes it donates it and returns it, one contiguous layout a slot
  (a page table would force gathers on attention's read path). Prompt
  lengths are bucketed (powers of two), so an admission tile compiles
  once a bucket and a decode block once a size (`decode_k<k>`).
- **Prefill/decode split.** An admission tile (`prefill_sample_batch`;
  `prefill_suffix_batch` behind a registered prefix, `prefill_block_batch`
  where generation is by diffusion over blocks) runs a few padded
  prompts through the stack's tile walk into their slots and samples
  each one's first token; `first_token_sample` does that for requests
  still queued, with no cache. A decode block (`decode_multi`,
  `decode_block_multi`) runs k steps for ALL slots in one launch, the
  cache carried through its loops and sampling on the device.
- **Per-slot positions.** Each slot sits at its own position, so one
  compiled decode step serves any mix of sequence lengths; a slot no
  request owns reads and writes no cache row (`live`).
"""

from __future__ import annotations

import types
from typing import Any, Dict, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .stackparts import Extras, KVCache
from .transformer import TransformerConfig, offered, stack


# What the device's trace calls each serving program: its module events
# read `jit_<name>`. The jitted functions below are built from this
# table, so a refactor cannot rename one by accident. Two families, told
# apart by name alone: a decode program's name contains `decode` and
# neither `prefill` nor `first_token`; a prefill or first-token
# program's name the reverse. A fused decode block carries its size
# (`decode_k8`: 8 steps a launch), so the steps the device ran are the
# sum of k x launches over the `jit_decode*_k<k>` events of a trace.
PROGRAM_NAMES: Dict[str, str] = {
    "prefill": "prefill",
    "prefill_sample_batch": "prefill_sample_batch",
    "prefill_suffix_batch": "prefill_suffix_batch",
    "first_token_sample": "first_token_sample",
    "first_token_suffix_sample": "first_token_suffix_sample",
    "decode_step": "decode_k1",
    "decode_multi": "decode_k{k}",
    # A configuration with `block_length` (generation by diffusion over
    # blocks) runs these in their place, under the same names: a step of
    # its decode programs is one pass of the model over a block of
    # positions a slot, and `decode_k8` eight such passes.
    "prefill_block_batch": "prefill_block_batch",
    "decode_block_step": "decode_k1",
    "decode_block_multi": "decode_k{k}",
    # The engine's own sampler after a one-step block (serve/llm.py).
    "sample_batch": "sample_batch",
}


def _named(fn, name: str):
    """`fn` again under another name: the same code, signature and
    globals. jit names a module after the function's `__name__`, and a
    wrapper taking `*args` would not be the same function to it."""
    twin = types.FunctionType(fn.__code__, fn.__globals__, name,
                              fn.__defaults__, fn.__closure__)
    twin.__kwdefaults__ = fn.__kwdefaults__
    twin.__annotations__ = dict(fn.__annotations__)
    twin.__doc__, twin.__module__ = fn.__doc__, fn.__module__
    twin.__qualname__ = name
    return twin


def program(key: str, **jit_kw):
    """Decorator: `jax.jit(fn, **jit_kw)` under the name the table gives
    `key`."""

    def build(fn):
        return jax.jit(_named(fn, PROGRAM_NAMES[key]), **jit_kw)

    return build


class _BlockPrograms:
    """The fused decode block, one jitted program per block size, each
    named by its size. Called and lowered like the one jitted function it
    stands for: `num_steps` is argument 5."""

    def __init__(self, key: str, body, **jit_kw):
        self._key, self._body, self._jit_kw = key, body, jit_kw
        self._by_k: Dict[int, Any] = {}
        self.__doc__ = body.__doc__

    def program_for(self, num_steps: int):
        fn = self._by_k.get(num_steps)
        if fn is None:
            name = PROGRAM_NAMES[self._key].format(k=num_steps)
            fn = self._by_k[num_steps] = jax.jit(
                _named(self._body, name), **self._jit_kw)
        return fn

    def __call__(self, *args):
        return self.program_for(args[5])(*args)

    def lower(self, *args):
        return self.program_for(args[5]).lower(*args)


def init_kv_cache(cfg: TransformerConfig, num_slots: int,
                  max_seq_len: Optional[int] = None) -> KVCache:
    return stack(cfg).init_cache(cfg, num_slots,
                                 max_seq_len or cfg.max_seq_len)


# ---------------------------------------------------------------------------
# Prefill / decode steps
# ---------------------------------------------------------------------------

@program("prefill", static_argnums=(0,), donate_argnums=(2,))
def prefill(cfg: TransformerConfig, params, cache: KVCache,
            tokens: jax.Array, length: jax.Array, slot: jax.Array
            ) -> Tuple[KVCache, jax.Array]:
    """Run one padded prompt (1, S_bucket) through the model, write its
    KV into `slot`, return last-real-token logits (V,): the batch body
    at one row.

    `length` = real prompt length; `slot` = cache row. Compiles once per
    (S_bucket,) — callers bucket prompt lengths.
    """
    cache, logits, _ = _prefill_batch_core(cfg, params, cache, tokens,
                                           length[None], slot[None])
    return cache, logits[0]


def token_logp(logits: jax.Array, toks: jax.Array) -> jax.Array:
    """log π(tok): log_softmax of the RAW logits (no temperature, no
    top-k mask) gathered at the sampled token — the policy probability
    an RLHF ratio term needs, matching rl/grpo.py's token_logp over
    forward logits. (..., V), (...,) int -> (...,) float32."""
    lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return jnp.take_along_axis(
        lp, toks[..., None].astype(jnp.int32), axis=-1)[..., 0]


def sample_logp(logits: jax.Array, temps: jax.Array, key: jax.Array,
                top_k: int) -> Tuple[jax.Array, jax.Array]:
    """What every program that samples returns of its logits (..., V):
    the tokens drawn (temps <= 0: greedily) and `token_logp` of each,
    float32. The engine fetches the log-probabilities as the program
    hands them over; it runs no eager operation on them (its eager
    first-token fusion is compiled for the tokens' int32 alone)."""
    toks = sample(logits, key, temperature=temps, top_k=top_k)
    return toks, token_logp(logits, toks)


def _last_exits(extras: Extras, lengths) -> Extras:
    """A tile walk's `extras` with its exit passes (W, S), where it has
    them, at each row's last real position -> (W,)."""
    if extras.exits is None:
        return extras
    return extras._replace(exits=jnp.take_along_axis(
        extras.exits, (lengths - 1).astype(jnp.int32)[:, None], axis=1)[:, 0])


def _prefill_batch_core(cfg: TransformerConfig, params, cache: KVCache,
                        tokens: jax.Array, lengths: jax.Array,
                        slots: jax.Array):
    """Batched-prefill body: write each prompt's KV into its slot,
    return (cache', last-real-token logits (W, V), the walk's `Extras`
    with `exits` the exit pass (W,) behind those logits)."""
    st = stack(cfg)
    cache, x, extras = st.prefill(cfg, params, cache, tokens, lengths, slots)
    return cache, st.last_logits(cfg, params, x, lengths), \
        _last_exits(extras, lengths)


@program("prefill_sample_batch", static_argnums=(0, 6), donate_argnums=(2,))
def prefill_sample_batch(cfg: TransformerConfig, params, cache: KVCache,
                         tokens: jax.Array, lengths: jax.Array,
                         slots: jax.Array, top_k: int,
                         temps: jax.Array, key: jax.Array):
    """Prefill a BATCH of padded prompts (W, S_bucket) into their cache
    slots and sample each one's first token in ONE dispatch. Returns
    (cache', first tokens (W,), their log-probabilities (W,), `Extras`:
    routing stats of the tile's W x S_bucket positions, each token's
    exit pass (W,)).

    Every row shares one read of the weights. While that read bounds
    the tile (under ~240 positions a tile on a v5e: 197 TFLOP/s over
    819 GB/s, two operations a position for a bf16 weight's two bytes;
    ~120 where a float32 position is two bf16 terms) W serial prefills
    cost ~W× one batched prefill; past it every row, padding too, costs
    its own arithmetic. So the engine chooses W by the bucket and the
    model's terms (serve/llm.py, `LLMEngine._tile_rows`): one row from
    512 positions up (256 under two terms). Rows whose slot index is
    out of range (the tile's padding) are dropped by the scatter and
    their sampled token is garbage the caller ignores. Compiles once
    per (W, S_bucket)."""
    cache, logits, extras = _prefill_batch_core(
        cfg, params, cache, tokens, lengths, slots)
    return (cache,) + sample_logp(logits, temps, key, top_k) + (extras,)


@program("prefill_suffix_batch", static_argnums=(0, 8), donate_argnums=(2,))
def prefill_suffix_batch(cfg: TransformerConfig, params, cache: KVCache,
                         prefix_k: jax.Array, prefix_v: jax.Array,
                         tokens: jax.Array, suffix_lens: jax.Array,
                         slots: jax.Array, top_k: int, temps: jax.Array,
                         key: jax.Array
                         ) -> Tuple[KVCache, jax.Array, jax.Array]:
    """Prefix-cached admission: install a REGISTERED prefix's KV
    (prefix_k/v: (L, Sp, KVH, Dh), computed once at registration) into
    each request's cache slot by copy — zero FLOPs — then prefill only
    the SUFFIX tokens (W, Sq_bucket) at global positions [Sp, Sp+Sq),
    attending to the prefix via flash attention's q_offset. The prefill
    FLOPs for the shared prefix are paid once per registration instead
    of once per request (capability of vLLM-style automatic prefix
    caching, scoped to explicitly registered prefixes — this cache is
    slot-contiguous, not paged; reference delegates the whole feature
    to vLLM, doc/source/serve/doc_code/vllm_example.py).

    suffix_lens: REAL suffix token counts (>= 1; the engine never
    routes an exact-prefix prompt here). Returns (cache', first tokens
    (W,), their log-probabilities (W,)). Compiles once per (W, Sp,
    Sq_bucket). Only for a stack with a `suffix`."""
    st, suffix = stack(cfg), offered(cfg, "suffix")
    W, Sq = tokens.shape
    Sp = prefix_k.shape[1]
    # 1. Prefix KV into the slot rows (broadcast copy; padding rows
    #    drop out of bounds).
    k = cache.k.at[:, slots, :Sp].set(
        jnp.broadcast_to(prefix_k[:, None],
                         (prefix_k.shape[0], W) + prefix_k.shape[1:]
                         ).astype(cache.k.dtype), mode="drop")
    v = cache.v.at[:, slots, :Sp].set(
        jnp.broadcast_to(prefix_v[:, None],
                         (prefix_v.shape[0], W) + prefix_v.shape[1:]
                         ).astype(cache.v.dtype), mode="drop")

    # 2. Suffix forward at offset positions: the walk the queue-side
    #    first token runs too, so the two paths cannot drift apart.
    x, ks, vs = suffix(cfg, params, prefix_k, prefix_v, tokens)

    # 3. Suffix KV behind the prefix (static offset).
    k = k.at[:, slots, Sp:Sp + Sq].set(ks.astype(k.dtype), mode="drop")
    v = v.at[:, slots, Sp:Sp + Sq].set(vs.astype(v.dtype), mode="drop")
    seq_lens = cache.seq_lens.at[slots].set(
        Sp + suffix_lens, mode="drop")

    # 4. Logits at the last REAL suffix position.
    logits = st.last_logits(cfg, params, x, suffix_lens)
    return (KVCache(k=k, v=v, seq_lens=seq_lens),) + sample_logp(
        logits, temps, key, top_k)


@program("first_token_suffix_sample", static_argnums=(0, 7))
def first_token_suffix_sample(cfg: TransformerConfig, params,
                              prefix_k: jax.Array, prefix_v: jax.Array,
                              tokens: jax.Array, suffix_lens: jax.Array,
                              temps: jax.Array, top_k: int,
                              key: jax.Array
                              ) -> Tuple[jax.Array, jax.Array]:
    """Cache-free first token for prompts sharing a REGISTERED prefix:
    runs only the suffix forward against the stored prefix KV (the
    queue-side analog of prefill_suffix_batch — without it, every
    queued request's early first token would re-pay the full-prefix
    FLOPs the prefix cache exists to save). tokens (W, Sq_bucket),
    suffix_lens (W,) real counts; returns (tokens (W,), their
    log-probabilities (W,))."""
    x, _, _ = offered(cfg, "suffix")(cfg, params, prefix_k, prefix_v, tokens)
    logits = stack(cfg).last_logits(cfg, params, x, suffix_lens)
    return sample_logp(logits, temps, key, top_k)


def compute_prefix_kv(cfg: TransformerConfig, params,
                      prefix: Sequence[int]
                      ) -> Tuple[jax.Array, jax.Array]:
    """KV for a prompt prefix, computed ONCE (registration-time half of
    prefix caching): (L, Sp, KVH, Dh) k/v in the cache dtype."""
    offered(cfg, "suffix")      # refused here, not at the first admission
    Sp = len(prefix)
    scratch = init_kv_cache(cfg, 1, Sp)
    tokens = jnp.asarray(list(prefix), jnp.int32)[None]    # (1, Sp)
    scratch, _ = prefill(cfg, params, scratch, tokens,
                         jnp.asarray(Sp, jnp.int32),
                         jnp.asarray(0, jnp.int32))
    return scratch.k[:, 0], scratch.v[:, 0]


@program("first_token_sample", static_argnums=(0, 5))
def first_token_sample(cfg: TransformerConfig, params, tokens: jax.Array,
                       lengths: jax.Array, temps: jax.Array, top_k: int,
                       key: jax.Array
                       ) -> Tuple[jax.Array, jax.Array, Extras]:
    """First token for a BATCH of prompts without touching any KV cache
    (tokens (W, S_bucket), lengths (W,), temps (W,) → (tokens (W,),
    their log-probabilities (W,), `Extras`: their exit passes (W,))).

    The serving engine uses this to give QUEUED requests their first
    token while every cache slot is busy — TTFT decoupled from slot
    availability. When a slot frees, the request is prefilled normally
    and decode continues from this token (the engine overrides the
    slot's cur_token), so no recomputed sample can diverge from what
    the client already saw."""
    st = stack(cfg)
    x, _, extras = st.forward_free(cfg, params, tokens)
    return sample_logp(st.last_logits(cfg, params, x, lengths), temps, key,
                       top_k) + (_last_exits(extras, lengths),)


@program("decode_step", static_argnums=(0,), donate_argnums=(2,))
def decode_step(cfg: TransformerConfig, params, cache: KVCache,
                tokens: jax.Array, live: Optional[jax.Array] = None
                ) -> Tuple[KVCache, jax.Array]:
    """One decode step for every slot. tokens: (B,) int32 (last emitted
    token per slot). Returns (cache', logits (B, V)). Slots advance their
    seq_lens by 1; inactive slots are advanced too — the host engine
    simply ignores their output and reuses the slot via prefill. `live`
    (B,) bool: the slots a request owns (None: all of them); the others'
    cache rows are not read (`stackparts._attend_cache`)."""
    cache, logits, _ = stack(cfg).decode(cfg, params, cache, tokens, live)
    return cache, logits


def _decode_multi(cfg: TransformerConfig, params, cache: KVCache,
                 tokens: jax.Array, temps: jax.Array, num_steps: int,
                 top_k: int, key: jax.Array,
                 live: Optional[jax.Array] = None):
    """`num_steps` fused decode+sample ticks in ONE dispatch, under the
    name `decode_k<num_steps>`.

    tokens: (B,) last emitted token per slot; temps: (B,) per-slot
    temperature; live: (B,) bool, the slots a request owns (`decode_step`).
    Returns (cache', toks (num_steps, B), `token_logp` of
    each (num_steps, B) float32, `Extras`: routing stats summed over the
    steps, each token's exit pass (num_steps, B)). The
    host engine truncates per-slot output at eos/max_new_tokens — slots
    that finish mid-block burn at most num_steps-1 wasted ticks, the
    price of one dispatch and one host fetch per num_steps tokens. The
    cache is the scan's carry and the program's donated argument, so a
    block is one buffer updated in place. Every step writes one row a
    slot a layer at `seq_lens` and moves `seq_lens` past it: a row is
    final the step it is written (where a model generates a block of
    positions a pass, `_decode_block_multi` stands in this program's
    place and a block's rows are final only at its commit pass). A step
    costs the weights and
    the cache rows the owned slots hold (`stackparts._attend_cache`): on a
    v5e 7.6
    ms at 32 slots x 1024 of internlm2-1.8b holding 43% of their rows
    and 10.5 ms at 4 x 4096 of Mistral-7B's 16 layers with one slot
    owned (11.6 and 12.3 while every row was read; PERF.md section 5,
    PR 31).
    """
    st = stack(cfg)

    def body(carry, sub):
        cache, tok, routed = carry
        cache, logits, extras = st.decode(cfg, params, cache, tok, live)
        tok, lp = sample_logp(logits, temps, sub, top_k)
        if extras.routing is not None:
            routed = routed + extras.routing
        return (cache, tok, routed), (tok, lp, extras.exits)

    subs = jax.random.split(key, num_steps)
    routed = jnp.zeros((st.routing_stats(cfg),), jnp.int32) \
        if st.routed_layers(cfg) else None
    (cache, _, routed), (toks, lps, exits) = lax.scan(
        body, (cache, tokens, routed), subs)
    return cache, toks, lps, Extras(routed, exits)


decode_multi = _BlockPrograms("decode_multi", _decode_multi,
                              static_argnums=(0, 5, 6),
                              donate_argnums=(2,))


# ---------------------------------------------------------------------------
# Generation by diffusion over blocks (`cfg.block_length`)
# ---------------------------------------------------------------------------

class BlockState(NamedTuple):
    """Each slot's open block, on the device between dispatches, beside
    the cache (whose `seq_lens` is the block's first position). Which
    positions are masked is state of its own, never read off a token id.

    x (B, Bd) int32: the block's tokens, `mask_token_id` where `masked`
    (B, Bd) bool; at_pass (B, Bd) int32: the denoising pass of the block
    (from 1) that unmasked a position, 0 for one that came fixed (what a
    prompt left over of its last whole block); logp (B, Bd) float32:
    `token_logp` of each token at the pass that unmasked it; passes
    (B,) int32: denoising passes the block has had; and what the slot's
    request asked for: steps (B,) int32 passes a block, rule (B,) int32
    (index into `transformer.REMASK_RULES`), threshold (B,) float32."""

    x: jax.Array
    masked: jax.Array
    at_pass: jax.Array
    logp: jax.Array
    passes: jax.Array
    steps: jax.Array
    rule: jax.Array
    threshold: jax.Array


# What a pass did for a slot (`decode_block_multi`'s `kind`).
PASS_IDLE, PASS_DENOISE, PASS_COMMIT = 0, 1, 2


def init_block_state(cfg: TransformerConfig, num_slots: int) -> BlockState:
    shape = (num_slots, cfg.block_length)
    return BlockState(
        x=jnp.full(shape, cfg.mask_token_id, jnp.int32),
        masked=jnp.ones(shape, bool), at_pass=jnp.zeros(shape, jnp.int32),
        logp=jnp.zeros(shape, jnp.float32),
        passes=jnp.zeros((num_slots,), jnp.int32),
        steps=jnp.full((num_slots,), cfg.denoise_steps, jnp.int32),
        rule=jnp.zeros((num_slots,), jnp.int32),
        threshold=jnp.full((num_slots,), cfg.confidence_threshold,
                           jnp.float32))


@program("prefill_block_batch", static_argnums=(0,), donate_argnums=(2, 3))
def prefill_block_batch(cfg: TransformerConfig, params, cache: KVCache,
                        blocks: BlockState, tokens: jax.Array,
                        lengths: jax.Array, slots: jax.Array,
                        first_x: jax.Array, first_masked: jax.Array,
                        steps: jax.Array, rule: jax.Array,
                        threshold: jax.Array):
    """Admission where a model generates a block at a time: the whole
    blocks of a tile of prompts (tokens (W, S_bucket), `lengths` (W,)
    multiples of `block_length`, 0 for a prompt shorter than a block)
    into their slots' rows under the block-causal mask, and each slot's
    first block opened: `first_x` (W, Bd) holds what the prompt left
    over, fixed, then the mask token where `first_masked`. No token is
    sampled: a prompt's last logits are not how such a model starts.
    Returns (cache', blocks', `Extras`: routing stats of the tile)."""
    cache, _, extras = stack(cfg).prefill(cfg, params, cache, tokens,
                                          lengths, slots)

    def put(old, new):
        return old.at[slots].set(new.astype(old.dtype), mode="drop")

    W = slots.shape[0]
    blocks = BlockState(
        x=put(blocks.x, first_x), masked=put(blocks.masked, first_masked),
        at_pass=put(blocks.at_pass, jnp.zeros_like(first_x)),
        logp=put(blocks.logp, jnp.zeros(first_x.shape, jnp.float32)),
        passes=put(blocks.passes, jnp.zeros((W,), jnp.int32)),
        steps=put(blocks.steps, steps), rule=put(blocks.rule, rule),
        threshold=put(blocks.threshold, threshold))
    return cache, blocks, extras


@program("decode_block_step", static_argnums=(0,), donate_argnums=(2,))
def decode_block_step(cfg: TransformerConfig, params, cache: KVCache,
                      tokens: jax.Array, p0: jax.Array,
                      live: Optional[jax.Array] = None
                      ) -> Tuple[KVCache, jax.Array]:
    """One pass of the model over a block a slot: tokens (B, Bd) at
    positions [p0, p0 + Bd) -> (cache', logits (B, Bd, V)): the stack's
    `decode_block`. The caller keeps the blocks and advances `seq_lens`
    (checks and tests; the engine runs `decode_block_multi`)."""
    cache, logits, _ = stack(cfg).decode_block(cfg, params, cache, tokens,
                                               p0, live)
    return cache, logits


def _unmask(blocks: BlockState, conf: jax.Array) -> jax.Array:
    """Which masked positions (B, Bd) a denoising pass unmasks, from the
    confidence `conf` (B, Bd) float32 of its samples (-inf where not
    masked). The pass's share of the schedule is n = Bd // steps, one
    more on the first Bd % steps passes. `low_confidence_static`: the n
    masked positions of largest confidence, ties to the lower position;
    `low_confidence_dynamic`: every masked position surer than the
    threshold where there are n of them at least, else the static rule;
    `sequential`: the first n masked positions."""
    Bd = conf.shape[1]
    masked = blocks.masked
    steps = jnp.maximum(blocks.steps, 1)
    n = Bd // steps + (blocks.passes < Bd % steps)
    n = jnp.where(blocks.passes >= steps, Bd, n)[:, None]     # never stuck
    pos = jnp.arange(Bd)
    # Positions that go before j: surer, or as sure and lower.
    before = (conf[:, None, :] > conf[:, :, None]) | (
        (conf[:, None, :] == conf[:, :, None])
        & (pos[None, None, :] < pos[None, :, None]))
    static = masked & (jnp.sum(before, axis=-1) < n)
    sure = masked & (conf > blocks.threshold[:, None])
    enough = jnp.sum(sure, axis=-1, keepdims=True) >= n
    in_order = masked & (jnp.cumsum(masked, axis=-1) <= n)
    rule = blocks.rule[:, None]
    return jnp.where(rule == 2, in_order,
                     jnp.where((rule == 1) & enough, sure, static))


def _block_pass(cfg: TransformerConfig, params, cache: KVCache,
                blocks: BlockState, temps, top_k: int, key, live):
    """One pass for every slot, whatever pass of its block a slot is at.
    A block with a masked position left is denoised: the model runs on
    it, each position's token is sampled (greedily at temperature 0)
    with its confidence softmax(logits / T)[token] (T = 1 when greedy),
    and `_unmask` says which masked positions take theirs. A block with
    none left is committed: the same walk over its final tokens makes
    rows [p0, p0 + Bd) the block's for good, `seq_lens` moves past them
    and the next block opens all masked. Returns (cache', blocks',
    (kind, x, at_pass, logp, unmasked): what the pass did a slot
    (`PASS_*`), the block as it came in (final where kind is
    `PASS_COMMIT`), and how many positions it unmasked; the walk's
    `Extras`)."""
    st = stack(cfg)
    Bd, S = cfg.block_length, cache.max_seq_len
    p0 = cache.seq_lens
    ok = p0 + Bd <= S
    ok = ok if live is None else ok & live
    cache, logits, extras = st.decode_block(cfg, params, cache, blocks.x, p0,
                                            ok)
    open_ = jnp.any(blocks.masked, axis=-1)
    denoise, commit = ok & open_, ok & ~open_
    hot = jnp.broadcast_to(temps[:, None], blocks.x.shape)

    def greedy():
        x0 = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        lp = token_logp(logits, x0)
        return x0, lp, jnp.exp(lp)

    def drawn():
        x0, lp = sample_logp(logits, hot, key, top_k)
        scaled = logits / jnp.where(hot > 0.0, hot, 1.0)[..., None]
        return x0, lp, jnp.exp(token_logp(scaled, x0))

    with jax.named_scope("block_sample"):
        # The draw costs a random number a logit: only where a slot asks.
        x0, lp, conf = lax.cond(jnp.any(hot > 0.0), drawn, greedy)
        conf = jnp.where(blocks.masked, conf, -jnp.inf)
        take = _unmask(blocks, conf) & denoise[:, None]
    out = (jnp.where(commit, PASS_COMMIT,
                     jnp.where(denoise, PASS_DENOISE, PASS_IDLE)),
           blocks.x, blocks.at_pass, blocks.logp,
           jnp.sum(take, axis=-1).astype(jnp.int32))
    fresh = commit[:, None]
    blocks = blocks._replace(
        x=jnp.where(fresh, cfg.mask_token_id, jnp.where(take, x0, blocks.x)),
        masked=fresh | (blocks.masked & ~take),
        at_pass=jnp.where(fresh, 0, jnp.where(
            take, blocks.passes[:, None] + 1, blocks.at_pass)),
        logp=jnp.where(fresh, 0.0, jnp.where(take, lp, blocks.logp)),
        passes=jnp.where(commit, 0, blocks.passes + denoise))
    cache = cache._replace(seq_lens=jnp.where(commit, p0 + Bd, p0))
    return cache, blocks, out, extras


def _decode_block_multi(cfg: TransformerConfig, params, cache: KVCache,
                        blocks: BlockState, temps: jax.Array, num_steps: int,
                        top_k: int, key: jax.Array,
                        live: Optional[jax.Array] = None):
    """`num_steps` fused passes (`_block_pass`) in ONE dispatch, under
    the name `decode_k<num_steps>`: `_decode_multi`'s place where a model
    generates a block of positions a pass. Slots admitted at different
    times are at different passes of their blocks and one pass serves
    them all. Returns (cache', blocks', (kind (num_steps, B), the block's
    tokens (num_steps, B, Bd), the pass that unmasked each, `token_logp`
    of each, positions unmasked (num_steps, B)), `Extras`: routing stats
    over B x Bd rows a pass, summed over the passes). The host emits a
    block where `kind` says its pass committed it."""
    def body(carry, sub):
        cache, blocks, routed = carry
        cache, blocks, out, extras = _block_pass(cfg, params, cache, blocks,
                                                 temps, top_k, sub, live)
        if extras.routing is not None:
            routed = routed + extras.routing
        return (cache, blocks, routed), out

    st = stack(cfg)
    subs = jax.random.split(key, num_steps)
    routed = jnp.zeros((st.routing_stats(cfg),), jnp.int32) \
        if st.routed_layers(cfg) else None
    (cache, blocks, routed), out = lax.scan(
        body, (cache, blocks, routed), subs)
    return cache, blocks, out, Extras(routing=routed)


decode_block_multi = _BlockPrograms("decode_block_multi",
                                    _decode_block_multi,
                                    static_argnums=(0, 5, 6),
                                    donate_argnums=(2, 3))


def sample(logits: jax.Array, key: jax.Array, *,
           temperature=0.0, top_k: int = 0) -> jax.Array:
    """Greedy (temperature<=0) or temperature/top-k sampling.
    (..., V) -> (...,). `temperature` may be a scalar or a per-row array
    (continuous batching: each slot has its own config)."""
    temps = jnp.broadcast_to(
        jnp.asarray(temperature, jnp.float32), logits.shape[:-1])
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    if top_k > 0:
        kth = jnp.sort(logits, axis=-1)[..., -top_k][..., None]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    scaled = logits / jnp.maximum(temps, 1e-6)[..., None]
    sampled = jax.random.categorical(key, scaled, axis=-1).astype(jnp.int32)
    return jnp.where(temps <= 0.0, greedy, sampled)


def greedy_generate(cfg: TransformerConfig, params, prompt: jax.Array,
                    max_new_tokens: int) -> jax.Array:
    """Reference single-sequence generation (tests / simple use):
    prefill then greedy decode. prompt: (S,) int32 → (max_new_tokens,)."""
    S = int(prompt.shape[0])
    bucket = max(8, 1 << (S - 1).bit_length())
    cache = init_kv_cache(cfg, num_slots=1,
                          max_seq_len=bucket + max_new_tokens)
    padded = jnp.zeros((1, bucket), jnp.int32).at[0, :S].set(prompt)
    cache, logits = prefill(cfg, params, cache, padded,
                            jnp.int32(S), jnp.int32(0))
    out = []
    tok = jnp.argmax(logits)[None].astype(jnp.int32)
    for _ in range(max_new_tokens):
        out.append(int(tok[0]))
        cache, logits = decode_step(cfg, params, cache, tok)
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jnp.asarray(out, jnp.int32)
