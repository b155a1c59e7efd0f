"""The period stack (the architectures of `transformer.PERIOD_FORMS`): a
decoder whose layers are not all alike, served through the programs of
`generate.py`.

`n_dense_layers` leading layers with a dense SwiGLU, then whole periods
of `global_attn_every` layers with the routed layer of `models/moe.py`
(every expert held, or the share `moe_first_expert` names of the
`moe_router_experts` scored; plus always-on shared experts where the
configuration has them). One layer of a period, the global one, attends
to every earlier position and stands where the form or the
configuration says (`global_place`); the others are of one other kind:
window layers, which attend to the last `sliding_window` (as the leading
layers do), or layers that keep a state of fixed size and no keys
(`cfg.period_form.recurrent`): linear layers, the gated delta rule, or
state-space layers, Mamba's selective scan. A period's FFNs are routed
or, with no experts, dense. Every layer: an RMS norm before attention
and before the FFN. What else a layer has is data,
`cfg.period_form`: a learned norm over each head of q and k, norms on
the attention's and the FFN's output, the attention output gated by
`sigmoid(h @ wg)` before `wo`, the embedding scaled by sqrt(d_model), a
selection bias, where in its period the global layer stands, and which
kinds of layer rotate q and k, each kind with the table of its own
section of `cfg.rope_parameters` (Trinity: window layers only; Mellum
2: both, the global ones by YaRN; Solar Open 2, Jamba and Kimi Linear:
none, their layers have no position but the order the state saw them
in). Where the form says so (`PeriodForm.latent`: Kimi Linear) the global
layer is multi-head latent attention, `models/mla.py`'s attention half:
its leaves, its rows in the cache (`KVCache.c`, (Lg, slots, S_max, lanes),
in place of `k`/`v`), a tile per head and a decode step in the latent
space, under the scopes `mla_proj` and `attn_latent`. Where the
configuration lists each layer's kind (`cfg.linear_attn_config`:
`kda_layers`, `full_attn_layers`) the plan is read from the lists
(`layer_plan`): the leading layers, whole periods all alike, and a last
period cut short as a step of its own (`tail_layers`); a leading layer is
then of the kind the lists say (Kimi Linear's: a linear layer with a
dense SwiGLU).

A linear layer (`_linear_half`, under the scope `attn_linear`): q, k, v
= silu of a causal depthwise convolution over the last
`linear_conv_kernel` positions of three projections, q and k
l2-normalised a head; a log-decay a channel `-exp(A_log) softplus(f_b(
f_a(h)) + dt_bias)` and a step a head `2 sigmoid(h wb)` (both through
rank `linear_head_dim`); the gated delta rule of `ops/delta_rule` over
a (dk, dv) float32 state a head; the heads' outputs through an RMS norm
over dv, gated by `sigmoid(g_b(g_a(h)))`, into `wo`.

A state-space layer (`_ssm_half`, under the scope `attn_ssm`): `[u, z] =
h w_in`; u = silu of a causal depthwise convolution over the last
`mamba_d_conv` positions (with a bias); `[r, B, C] = u w_x`, each
through an RMS norm of its own; the step a channel `dt = softplus(r
w_dt + dt_bias)`; the selective scan of `ops/selective_scan` over a
(mamba_d_state, channels) float32 state with `A = -exp(A_log)`; `(y + D
u) silu(z)` into `wo`. The scan, the step and the state are float32
whatever `cfg.dtype` is; the projections take operands of `cfg.dtype`.

Weights: `dense_layers` (leaves stacked over the leading layers) and
`periods` (leaves stacked over periods, then over a period's layers;
where a period's kinds of layer have different attention leaves, those
lie a layer under its kind and its place among the period's layers of
the kind, `global0`, `linear0`, `linear1`, ..., stacked over periods; a
dense FFN's matrices lie there with them).
One layer definition (`layer`) and one walk (`stackparts.run`) serve
prefill, the cache-free first token and decode; they differ in the
`attend` they hand in, which owns the cache.

Cache slabs and weight layers are counted apart (`cache_layers` counts
the slabs; nothing outside this module sizes a cache from
`cfg.n_layers`). The cache holds three kinds of state in one `KVCache`: `k`/`v` for the
global layers, (Lg, slots, S_max, KVH, Dh); `kw`/`vw` for the window
layers, (Lw, slots, min(sliding_window, S_max), KVH, Dh), a ring written
at `position mod rows` (softmax does not care in which order the ring
holds its rows, and a key carries its rotary phase from when it was
written, so decode reads the ring as it lies); and for the linear
layers `s`, (Ll, slots, H, dk, dv) float32, with `tails`, (Ll, slots,
conv - 1, 3 x H x dk), the projections' last positions (state-space
layers: `s` (Ls, slots, mamba_d_state, channels) float32, the channels
on the minor axis as the kernels hold them, and `tails` (Ls, slots,
conv - 1, channels)). Rows grow with
the tokens held and are final once written; a state is rewritten whole
by every step of its slot, so a tile writes it as the prompt's last
real token left it, padding changes nothing, and a slot nobody owns is
not written.

A looped configuration (`cfg.ut_steps` > 1: `_walk`) runs the same
leaves that many times a token, the final norm after each pass and its
output the next pass's input. Pass t of global layer l keeps slab
t x Lg + l of `k`/`v`, (ut_steps x Lg, slots, S_max, KVH, Dh), written by
pass t and read by pass t alone; every pass runs and writes its slab
whatever the exit gate says, and a row's hidden state, logits and exit
pass are those `stackparts.exit_select` picks from the gate's mass. One
walk serves prefill, the cache-free first token and decode here too.

A configuration that generates by diffusion over blocks
(`cfg.block_length`: every layer global) takes the same layer and the
same walk: `prefill` masks block-causally (a query sees its own block
whole) and `decode_block` runs a block of positions a slot against the
rows the slot holds and the block's own; `decode` is not its walk.

Precision follows `cfg.dtype`, the dtype of the activations and of the
cache. bfloat16: every product takes bf16 operands, as the dense stack
does. float32 (with bf16 weights): nothing between the embedding and the
head is rounded to bf16; a product against a weight takes the
activation as two bf16 terms (`moe.dot`); the cache keeps a key or a
value as two bf16 terms too (`cache_terms`: layer l's rows rounded to
bf16 at [l], what the rounding left at [L + l], so a layer's slab is
the size a bf16 cache's is and twice as many are read); and only the
head's product takes bf16 (its error is continuous). That is what a
routed stack needs to choose the experts a float32 reference chooses:
one token-layer pair in seven flips under bf16 activations, and a flip
moves that token's logits by a tenth of their size or more (PERF.md).
"""

from __future__ import annotations

import functools
import math
from functools import partial
from typing import Any, Callable, Dict, List, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..parallel.sharding import with_sharding_constraint as wsc
from . import mla, stackparts
# `routing_stats` and `last_logits` are the seam's (`transformer.STACKS`).
from .moe import bf16_terms, dot as _dot, dot_terms, \
    routing_stats  # noqa: F401
from .stackparts import (Extras, Group, KVCache,  # noqa: F401
                         _attend_cache, _attend_cache_block, _final, _norm,
                         _rope, ffn_half, head_logits, joins, last_logits,
                         masked_softmax, rows_held)
from .transformer import TransformerConfig, rope_tables

WINDOW, GLOBAL, LINEAR, SSM = "window", "global", "linear", "ssm"
KINDS = (WINDOW, GLOBAL, LINEAR, SSM)
# A kind's section of `TransformerConfig.rope_parameters`, under the key a
# published config.json gives it.
ROPE_SECTION = {WINDOW: "sliding_attention", GLOBAL: "full_attention"}

# What the dense stack offers and this one does not (`transformer.offered`).
MISSING = {
    # The prefix programs install one (L, Sp, KVH, Dh) block of keys and
    # values a layer. A window layer's ring holds a slot's last rows at
    # `position mod rows`, not a prefix at [0, Sp): sharing it needs a
    # layout of its own. A linear or state-space layer's state behind a
    # prefix is a snapshot a registered prefix would have to carry and a
    # suffix would have to start from.
    "suffix": "prefix sharing (prefill_suffix_*, first_token_suffix_*, "
              "compute_prefix_kv) is not written for a windowed cache or "
              "for a recurrent state (the delta rule's, the selective "
              "scan's with its convolution's tail), whose value behind the "
              "prefix a registered prefix would have to carry: where the "
              "global layer is latent, a snapshot of every linear layer's "
              "state, their tails and the prefix's latent rows "
              "(models/periodic.py)",
    # One tile a prompt: the engine's buckets reach `max_seq_len`.
    "chunked_prefill": "a prompt past one tile is not walked a chunk at a "
                       "time: `ops/delta_rule.chunk_scan` takes a carried "
                       "state and `models/latent.py` attends rows a tile did "
                       "not write, but only in its indexer's walk, and the "
                       "two are not joined for a stack that keeps both",
    "param_logical_axes": "the period stack has no sharding rules yet: it "
                          "is served on one chip (models/periodic.py)",
    "forward_train": "the period stack is served only (models/generate.py): "
                     "training lacks a dropless routed layer under "
                     "autodiff (moe_ffn drops tokens over capacity), the "
                     "backward of windowed flash attention, of the delta "
                     "rule's chunked scan and of the selective scan's "
                     "kernel, and the load-balancing update of the "
                     "selection bias; a looped walk (ut_steps) under "
                     "value_and_grad is not written either",
    # A looped configuration runs every pass for every token, as the
    # published implementation does.
    "early_stop": "a looped walk that stops at a token's exit pass (the "
                  "passes behind it skipped, their slabs left unwritten) "
                  "is not written: every pass runs whatever the gate says",
    "shared_slabs": "one cache slab shared by several passes of a layer "
                    "(the cache-sharing variants of arXiv:2510.25741) is "
                    "not written: a slab a (pass, layer)",
}
# What a configuration with `block_length` lacks of this stack, and one
# without it of the block walk.
NOT_ITS_WALK = {
    "decode": "a configuration with block_length generates a block of "
              "positions a pass (decode_block, generate.decode_block_*): "
              "one token a step (decode_step, decode_multi) is not how it "
              "generates",
    "decode_block": "decode_block is the walk of a configuration with "
                    "block_length; this one generates one token a step",
    "terms": "decode_block is not written for a cache of two bf16 terms "
             "(float32 activations on bf16 weights)",
}


DENSE, PERIODS, TAIL = "dense_layers", "periods", "tail_layers"


def layer_plan(cfg: TransformerConfig) -> List[Group]:
    """The leading layers, a layer a scan step, then whole periods, a
    period a step, and, where the configuration lists each layer's kind
    and the lists end in a period cut short, that one as a last step of
    its own. Lists that do not group so are refused (`_listed_steps`)."""
    every = cfg.global_attn_every
    plan = []
    if cfg.n_dense_layers:
        plan.append(Group(DENSE, (cfg.n_dense_layers,), False))
    periods, tail = divmod(cfg.n_layers - cfg.n_dense_layers, every)
    if periods:
        plan.append(Group(PERIODS, (periods, every), cfg.is_moe))
    if cfg.listed_global is not None:
        if tail:
            plan.append(Group(TAIL, (1, tail), cfg.is_moe))
        _listed_steps(cfg)
    return plan


def _other_kind(cfg: TransformerConfig) -> str:
    """The kind of a period's layers other than its global one, and of
    the leading layers."""
    return cfg.period_form.recurrent or (
        WINDOW if cfg.sliding_window else GLOBAL)


def _listed_steps(cfg: TransformerConfig) -> Dict[str, Tuple[str, ...]]:
    """{group: the kinds of one of its scan steps' layers} from the
    configuration's lists (`TransformerConfig.listed_global`): the
    leading layers alike, every whole period as the first, what is left
    shorter than a period."""
    other, every = _other_kind(cfg), cfg.global_attn_every
    kinds = [GLOBAL if g else other for g in cfg.listed_global]
    lead, body = kinds[:cfg.n_dense_layers], kinds[cfg.n_dense_layers:]
    whole = len(body) // every * every
    periods = [tuple(body[i:i + every]) for i in range(0, whole, every)]
    if len(set(lead)) > 1 or len(set(periods)) > 1:
        raise ValueError(
            f"{cfg.arch}: the listed layers {kinds} are not "
            f"n_dense_layers ({cfg.n_dense_layers}) leading layers of one "
            f"kind, then periods of global_attn_every ({every}) layers "
            "all alike (and a last one cut short)")
    steps = {DENSE: tuple(lead[:1]), PERIODS: tuple(body[:every]),
             TAIL: tuple(body[whole:])}
    return {key: ks for key, ks in steps.items() if ks}


def _period_kinds(cfg: TransformerConfig) -> Tuple[str, ...]:
    """The kinds of a period's layers, in order."""
    other, at = _other_kind(cfg), global_place(cfg)
    return (other,) * at + (GLOBAL,) \
        + (other,) * (cfg.global_attn_every - 1 - at)


def global_place(cfg: TransformerConfig) -> int:
    """Where in its period the global layer stands: the form's place (0
    opens the period, -1 closes it) or the configuration's."""
    at = cfg.period_form.global_at
    return (cfg.attn_layer_offset if at is None else at) \
        % cfg.global_attn_every


def step_kinds(cfg: TransformerConfig) -> List[Tuple[str, ...]]:
    """The kinds of a scan step's layers, a group of `layer_plan`."""
    if cfg.listed_global is not None:
        listed = _listed_steps(cfg)
        return [listed[group.key] for group in layer_plan(cfg)]
    return [(_other_kind(cfg),) if group.key == DENSE else _period_kinds(cfg)
            for group in layer_plan(cfg)]


def routed_layers(cfg: TransformerConfig) -> int:
    """Layers whose use of their experts `decode` reports."""
    return stackparts.routed_layers(layer_plan(cfg))


def by_products(cfg: TransformerConfig) -> bool:
    return bool(routed_layers(cfg)) or cfg.ut_steps > 1


def _layers_of(cfg: TransformerConfig) -> Dict[str, int]:
    """How many weight layers are of each kind (a recurrent kind where
    the form has such layers)."""
    recurrent = cfg.period_form.recurrent
    return {kind: sum(group.lead[0] * kinds.count(kind)
                      for group, kinds in zip(layer_plan(cfg),
                                              step_kinds(cfg)))
            for kind in (WINDOW, GLOBAL) + ((recurrent,) if recurrent
                                            else ())}


def cache_layers(cfg: TransformerConfig) -> Dict[str, int]:
    """How many cache slabs keep each kind of state: one a layer of the
    kind, times the passes a token walks (`cfg.ut_steps`), each (pass,
    layer) its own. Not the count of weight layers."""
    return {kind: cfg.ut_steps * n for kind, n in _layers_of(cfg).items()}


# ---------------------------------------------------------------------------
# Weights
# ---------------------------------------------------------------------------

def _layer_shapes(cfg: TransformerConfig, group: Group
                  ) -> Dict[str, Tuple[int, ...]]:
    d, hd = cfg.d_model, cfg.head_dim
    q, kv = cfg.n_heads * hd, cfg.n_kv_heads * hd
    form, routed = cfg.period_form, group.routed
    shapes = {"attn_norm": (d,), "ffn_norm": (d,)}
    attn = {"wq": (d, q), "wk": (d, kv), "wv": (d, kv), "wo": (q, d)}
    if form.qk_norm:
        attn.update(q_norm=(hd,), k_norm=(hd,))
    if form.attn_gate:
        attn["wg"] = (d, q)
    if form.latent:
        attn = mla.attention_shapes(cfg)
    if form.post_norms:
        shapes.update(post_attn_norm=(d,), post_ffn_norm=(d,))
    ffn = stackparts.ffn_shapes(cfg, routed, form.router_bias)
    if form.recurrent:
        # Two kinds of attention leaves a period: a layer's under its
        # kind and its place among the period's layers of the kind. A
        # dense FFN's matrices lie with them (a routed one's experts are
        # never scanned): a step cuts what is stacked over its layers out
        # of the stack by a copy, 0.13 GB a matrix a layer at 2560 x 8192.
        own = {} if routed else ffn
        kinds = step_kinds(cfg)[layer_plan(cfg).index(group)]
        for j, kind in enumerate(kinds):
            shapes[f"{kind}{kinds[:j].count(kind)}"] = {
                **(attn if kind == GLOBAL
                   else _RECURRENT[kind].shapes(cfg)), **own}
        return {**shapes, **ffn} if routed else shapes
    return {**shapes, **attn, **ffn}


def _linear_shapes(cfg: TransformerConfig) -> Dict[str, Tuple[int, ...]]:
    """A linear layer's attention leaves: keys and values alike wide."""
    d, H, D = cfg.d_model, cfg.linear_n_heads, cfg.linear_head_dim
    return {
        "wq": (d, H * D), "wk": (d, H * D), "wv": (d, H * D),
        "wo": (H * D, d), "conv": (cfg.linear_conv_kernel, 3 * H * D),
        "f_a": (d, D), "f_b": (D, H * D), "A_log": (H,),
        "dt_bias": (H * D,), "wb": (d, H), "g_a": (d, D),
        "g_b": (D, H * D), "g_bias": (H * D,), "o_norm": (D,)}


def _ssm_shapes(cfg: TransformerConfig) -> Dict[str, Tuple[int, ...]]:
    """A state-space layer's leaves; `A_log` lies as the state does, a
    coordinate a row."""
    d, C, N = cfg.d_model, cfg.mamba_d_inner, cfg.mamba_d_state
    R, K = cfg.mamba_dt_rank, cfg.mamba_d_conv
    shapes = {"w_in": (d, 2 * C), "conv": (K, C), "w_x": (C, R + 2 * N),
              "dt_norm": (R,), "b_norm": (N,), "c_norm": (N,),
              "w_dt": (R, C), "dt_bias": (C,), "A_log": (N, C), "D": (C,),
              "wo": (C, d)}
    if cfg.mamba_conv_bias:
        shapes["conv_bias"] = (C,)
    return shapes


def _uniform(lo: float, hi: float, of=lambda u: u):
    return lambda key, shape: of(jax.random.uniform(
        key, shape, jnp.float32, lo, hi))


# A recurrent layer's leaves that a scaled normal would make degenerate
# (a decay of a half a token everywhere): drawn as the published
# implementations initialise them. The decay's time step log-uniform in
# [0.001, 0.1], `dt_bias` its inverse softplus, and the convolution
# uniform in +-1/sqrt(its 4 inputs), both kinds. A linear layer:
# exp(A_log) uniform in [1, 16]; no bias on the output gate. A
# state-space layer: exp(A_log) = 1 .. d_state down a channel's
# coordinates, the skip `D` one.
_STEP_DRAWS = {
    "dt_bias": _uniform(math.log(0.001), math.log(0.1),
                        lambda u: jnp.log(jnp.expm1(jnp.exp(u)))),
    "conv": _uniform(-0.5, 0.5),
}
_DRAWS = {
    LINEAR: {**_STEP_DRAWS, "A_log": _uniform(1.0, 16.0, jnp.log),
             "g_bias": lambda key, shape: jnp.zeros(shape, jnp.float32)},
    SSM: {**_STEP_DRAWS,
          "A_log": lambda key, shape: jnp.broadcast_to(jnp.log(jnp.arange(
              1, shape[-2] + 1, dtype=jnp.float32))[:, None], shape),
          "D": lambda key, shape: jnp.ones(shape, jnp.float32)},
}


def num_params(cfg: TransformerConfig) -> int:
    return stackparts.num_params(cfg, layer_plan(cfg), _layer_shapes)


def init_params(cfg: TransformerConfig, key: jax.Array) -> Dict[str, Any]:
    return stackparts.init_params(cfg, key, layer_plan(cfg), _layer_shapes,
                                  _DRAWS.get(cfg.period_form.recurrent))


def cache_terms(cfg: TransformerConfig) -> int:
    """The bf16 terms a cached key or value is kept as: two for float32
    activations on bf16 weights (hi + lo carry 16 bits of mantissa, and
    the products of attention take bf16), else one value of `cfg.dtype`."""
    return dot_terms(cfg.dtype, cfg.param_dtype) \
        if cfg.cache_dtype is None else 1


def init_cache(cfg: TransformerConfig, num_slots: int, max_seq_len: int
               ) -> KVCache:
    n = cache_layers(cfg)
    terms = cache_terms(cfg)

    def zeros(layers: int, rows: int):
        z = jnp.zeros((terms * layers, num_slots, rows, cfg.n_kv_heads,
                       cfg.head_dim),
                      jnp.bfloat16 if terms == 2
                      else jnp.dtype(cfg.cache_dtype or cfg.dtype).type)
        return wsc(z, ("layers", None, None, "act_kv_heads", None))

    ring = min(cfg.sliding_window, max_seq_len)
    s = tails = c = None
    if LINEAR in n:
        H, D = cfg.linear_n_heads, cfg.linear_head_dim
        s = jnp.zeros((n[LINEAR], num_slots, H, D, D), jnp.float32)
        tails = jnp.zeros((n[LINEAR], num_slots, cfg.linear_conv_kernel - 1,
                           3 * H * D), cfg.dtype)
    if SSM in n:
        s = jnp.zeros((n[SSM], num_slots, cfg.mamba_d_state,
                       cfg.mamba_d_inner), jnp.float32)
        tails = jnp.zeros((n[SSM], num_slots, cfg.mamba_d_conv - 1,
                           cfg.mamba_d_inner), cfg.dtype)
    if cfg.period_form.latent:
        c = mla.init_rows(cfg, n[GLOBAL], num_slots, max_seq_len,
                             jnp.dtype(cfg.cache_dtype or cfg.dtype).type)
    return KVCache(
        k=None if c is not None else zeros(n[GLOBAL], max_seq_len),
        v=None if c is not None else zeros(n[GLOBAL], max_seq_len), c=c,
        seq_lens=jnp.zeros((num_slots,), jnp.int32),
        kw=zeros(n[WINDOW], ring) if n[WINDOW] else None,
        vw=zeros(n[WINDOW], ring) if n[WINDOW] else None, s=s, tails=tails)


@functools.cache
def cache_bytes(cfg: TransformerConfig) -> Tuple[int, int]:
    """(bytes of a slot's recurrent states and convolution tails, all
    its layers of the kind; bytes a held token's rows come to over the
    global layers: a latent layer's `kv_lora_rank + qk_rope_head_dim`
    values, never the lanes the row is padded to, else a key and a value
    a KV head, every term): what `cache_state_bytes_live` and
    `cache_row_bytes_held` multiply (`block_counts`), read off the
    cache of one slot and one row. A window layer's ring is neither: it
    does not grow with the tokens held."""
    one = jax.eval_shape(lambda: init_cache(cfg, 1, 1))

    def nbytes(*arrays):
        return sum(a.size * a.dtype.itemsize for a in arrays
                   if a is not None)

    if one.c is not None:
        return nbytes(one.s, one.tails), one.c.shape[0] \
            * one.c.dtype.itemsize * mla.cache_width(cfg)
    return nbytes(one.s, one.tails), nbytes(one.k, one.v)


# What the engine counts of this stack, on the host (`stackparts.counters`;
# docs/METRICS.md says what each counter means).

@functools.cache
def _state_layers(cfg: TransformerConfig) -> int:
    """Cache slabs that keep a recurrent state a slot (`KVCache.s`)."""
    return cache_layers(cfg).get(cfg.period_form.recurrent, 0)


def counters(cfg: TransformerConfig) -> Dict[str, Any]:
    found = stackparts.routing_counters(routed_layers(cfg))
    if _state_layers(cfg):
        found.update(linear_slot_steps=0, linear_slot_steps_live=0,
                     linear_tokens=0, cache_state_bytes_live=0,
                     cache_row_bytes_held=0)
        if cfg.linear_n_heads:
            found.update(linear_chunks=0, linear_chunks_of=0)
    if cfg.ut_steps > 1:
        found.update(loop_passes=0, loop_exit_hist=[0] * cfg.ut_steps)
    return found


def tile_counts(cfg: TransformerConfig, bucket: int, lengths, tokens: int):
    """The (token, layer that keeps a state) pairs a tile runs through the
    recurrence and, where that is cut in chunks (a linear layer's; a
    state-space layer's is not), the (chunk, layer) pairs it runs of those
    it is asked for, the chunks its rows span: where `ops/delta_rule.
    chunk_scan` takes its kernel at the tile's shape a row runs to the
    chunk that holds its last token; on the XLA walk every chunk runs."""
    from ..ops import delta_rule

    layers = _state_layers(cfg)
    if not layers:
        return {}, {}
    found = dict(linear_tokens=tokens * layers)
    if cfg.linear_n_heads:
        ran, asked = delta_rule.scan_chunks(bucket, lengths)
        heads = jax.ShapeDtypeStruct(
            (1, bucket, cfg.linear_n_heads, cfg.linear_head_dim), cfg.dtype)
        if not delta_rule.scan_usable(heads, heads, heads):
            ran = asked
        found.update(linear_chunks=ran * layers,
                     linear_chunks_of=asked * layers)
    return found, found


def block_counts(cfg: TransformerConfig, k: int, num_slots: int,
                 max_seq_len: int, first_rows, held: int):
    """The state updates a block's steps span and those a request owns,
    which alone read and write a state; the owned slots' states and tails,
    which every step rewrites whole, beside the bytes their held rows come
    to (`cache_bytes`)."""
    layers = _state_layers(cfg)
    if not layers:
        return {}, {}
    state_bytes, row_bytes = cache_bytes(cfg)
    steps, live = k * layers, len(first_rows)
    found = dict(linear_slot_steps=steps * num_slots,
                 linear_slot_steps_live=steps * live,
                 cache_state_bytes_live=k * live * state_bytes,
                 cache_row_bytes_held=held * row_bytes)
    return found, found


def result_counts(cfg: TransformerConfig, k: int, extras: Extras, taken):
    """The routing sums and, of a looped configuration's delivered tokens,
    the passes walked for them (every pass runs whatever the gate says)
    and how many left at each pass (the counter a list, the span
    `loop_exit_p<t>`, t from 1)."""
    counted = stackparts.routing_counts(cfg, routed_layers(cfg), k,
                                        extras.routing)
    if extras.exits is None:
        return counted, counted
    exits = np.asarray(extras.exits).reshape(-1, len(taken))
    got = exits[np.arange(len(exits))[:, None] < np.asarray(taken)[None, :]]
    hist = np.bincount(got, minlength=cfg.ut_steps).tolist()
    passes = cfg.ut_steps * len(got)
    return dict(counted, loop_passes=passes, loop_exit_hist=hist), \
        dict(counted, loop_passes=passes,
             **{f"loop_exit_p{t + 1}": n for t, n in enumerate(hist)})


# ---------------------------------------------------------------------------
# The layer, and the walk over the stack
# ---------------------------------------------------------------------------

def layer(cfg: TransformerConfig, lp, x, kind: str, experts_at, rope,
          attend, state, rows=None):
    """One layer on x (B, S, D) in the activation dtype. `rope`: {kind:
    (sin, cos)} for the kinds that rotate (`rope_by_kind`). `attend(kind,
    q, k, v, state) -> (out (B, S, H, Dh), state)` does the attention and
    whatever it keeps of k and v (a linear layer's: `_linear_half`; a
    state-space layer's: `_ssm_half`).
    `experts_at`, `rows`: as `ffn_half` takes them. Returns (x, state,
    routing stats, experts chosen (B*S, K) or None)."""
    B, S, _ = x.shape
    H, KVH, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    dt, eps = cfg.dtype, cfg.norm_eps
    form = cfg.period_form

    # Every product hands back float32; what lies between two products
    # (norms, rotary, gates) stays float32 and is rounded to `dt` once,
    # where it enters the next product or the cache (float32: never).
    def normed():
        return _norm(x, lp["attn_norm"], eps).astype(dt)

    if _latent(cfg, kind):
        # `attend(kind, (the layer's leaves, q_nope, q_r), the row the
        # cache keeps, None, state) -> (out (B, S, H x v_head_dim),
        # state)`, under the scopes `mla_proj` and `attn_latent`.
        branch, state = mla.attention_half(
            cfg, lp, x, rope.get(kind),
            lambda lp, q_nope, q_r, row, idx, state: attend(
                kind, (lp, q_nope, q_r), row, None, state), state)
    elif kind in _RECURRENT:
        h = normed()
        with jax.named_scope("attn_" + kind):
            branch, state = _RECURRENT[kind].half(cfg, lp, h, attend, state)
    else:
        h = normed()
        q = _dot(h, lp["wq"]).reshape(B, S, H, Dh)
        k = _dot(h, lp["wk"]).reshape(B, S, KVH, Dh)
        v = _dot(h, lp["wv"]).reshape(B, S, KVH, Dh).astype(dt)
        gate = _dot(h, lp["wg"]) if form.attn_gate else None
        if form.qk_norm:
            q = _norm(q, lp["q_norm"], eps)
            k = _norm(k, lp["k_norm"], eps)
        if kind in rope:                   # else the kind has no position
            q, k = _rope(q, *rope[kind]), _rope(k, *rope[kind])
        with jax.named_scope("attn_" + kind):
            out, state = attend(kind, q.astype(dt), k.astype(dt), v, state)
        out = out.reshape(B, S, H * Dh)
        if gate is not None:
            out = (out.astype(jnp.float32) * jax.nn.sigmoid(gate)).astype(dt)
        branch = _dot(out, lp["wo"])
    x = joins(x, branch,
              lp["post_attn_norm"] if form.post_norms else None, eps)
    # A dense layer's stats are zeros, which the walk adds to its sum as
    # it adds a routed layer's: without that add the tile and the decode
    # block of a configuration with leading dense layers compile to
    # another module (ROADMAP D19).
    zeros = jnp.zeros((routing_stats(cfg),), jnp.int32)
    x, stats, experts = ffn_half(cfg, lp, x, experts_at, form.post_norms,
                                 rows)
    return x, state, zeros if stats is None else stats, experts


def _latent(cfg: TransformerConfig, kind: str) -> bool:
    """Whether a layer of `kind` is latent attention
    (`PeriodForm.latent`: the global one)."""
    return kind == GLOBAL and cfg.period_form.latent


def _linear_half(cfg: TransformerConfig, lp, h, attend, state):
    """The attention half of a linear layer on its normed input h (B, S,
    D) in the activation dtype -> (the branch (B, S, D) float32, state).
    `attend(LINEAR, mix, conv, (g, beta), state) -> (o (B, S, H, dv)
    float32, state)` owns the convolution's tails and the recurrent
    state: `mix` (B, S, 3 x H x dk) the q, k and v projections before
    their convolution, `conv` its weights, g (B, S, H, dk) the log-decay
    and beta (B, S, H) the step (`_linear_core` is what every one of
    them runs on the positions it has gathered)."""
    B, S, _ = h.shape
    H, D = cfg.linear_n_heads, cfg.linear_head_dim
    dt, f32 = cfg.dtype, jnp.float32
    mix = jnp.concatenate([_dot(h, lp[w]) for w in ("wq", "wk", "wv")],
                          axis=-1).astype(dt)
    decay = _dot(_dot(h, lp["f_a"]).astype(dt), lp["f_b"]) \
        + lp["dt_bias"].astype(f32)
    g = -jnp.exp(lp["A_log"].astype(f32))[:, None] \
        * jax.nn.softplus(decay.reshape(B, S, H, D))
    beta = jax.nn.sigmoid(_dot(h, lp["wb"]))
    if cfg.period_form.neg_eigval:
        beta = 2.0 * beta
    o, state = attend(LINEAR, mix, lp["conv"], (g, beta), state)
    gate = _dot(_dot(h, lp["g_a"]).astype(dt), lp["g_b"]) \
        + lp["g_bias"].astype(f32)
    o = _norm(o, lp["o_norm"], cfg.norm_eps) \
        * jax.nn.sigmoid(gate.reshape(B, S, H, D))
    return _dot(o.reshape(B, S, H * D).astype(dt), lp["wo"]), state


def _causal_conv(window, conv):
    """A depthwise convolution over the last K positions: `window` (B,
    K - 1 + S, C), the inputs with the K - 1 positions before them,
    `conv` (K, C) -> (B, S, C) float32."""
    K = conv.shape[0]
    S = window.shape[1] - (K - 1)
    return sum(conv[i].astype(jnp.float32)
               * window[:, i:i + S].astype(jnp.float32) for i in range(K))


def _linear_core(cfg: TransformerConfig, window, conv):
    """The convolution, the activation and the heads' norms: `window`
    (B, conv - 1 + S, 3 x H x dk), the projections with the positions
    before them -> q, k, v (B, S, H, dk) float32, q and k l2-normalised
    and q scaled by dk^-0.5."""
    B = window.shape[0]
    S = window.shape[1] - (conv.shape[0] - 1)
    H, D = cfg.linear_n_heads, cfg.linear_head_dim
    q, k, v = (a.reshape(B, S, H, D) for a in jnp.split(
        jax.nn.silu(_causal_conv(window, conv)), 3, axis=-1))

    def unit(a):
        return a * lax.rsqrt(jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)

    return unit(q) * D ** -0.5, unit(k), v


def _linear_tile(cfg: TransformerConfig, mix, conv, gates, lengths=None):
    """A tile's linear attention from a zero state: (o (B, S, H, dv)
    float32, the state behind each row's last real position, the
    projections behind conv - 1 zero positions)."""
    from ..ops import delta_rule

    window = jnp.pad(mix, ((0, 0), (conv.shape[0] - 1, 0), (0, 0)))
    q, k, v = (a.astype(cfg.dtype) for a in _linear_core(cfg, window, conv))
    with jax.named_scope("kda_scan"):
        o, last = delta_rule.chunk_scan(q, k, v, *gates, lengths)
    return o, last, window


def _ssm_half(cfg: TransformerConfig, lp, h, attend, state):
    """The mixer of a state-space layer on its normed input h (B, S, D)
    in the activation dtype -> (the branch (B, S, D) float32, state).
    `attend(SSM, mix, lp, z, state) -> (out (B, S, channels), state)`
    owns the convolution's tails and the recurrent state: `mix` the
    input projection's first half before its convolution (`_ssm_core` is
    what every one of them runs on the positions it has gathered), `z`
    its second half, the gate, and `out` what `wo` multiplies,
    `ops/selective_scan.gated`: the scan's output with the skip `D u`
    under `silu(z)` (a tile applies it in XLA, the decode step's kernel
    in the grid step that holds the slot)."""
    C = cfg.mamba_d_inner
    with jax.named_scope("ssm_in"):
        xz = _dot(h, lp["w_in"])
    out, state = attend(SSM, xz[..., :C].astype(cfg.dtype), lp, xz[..., C:],
                        state)
    with jax.named_scope("ssm_out"):
        return _dot(out.astype(cfg.dtype), lp["wo"]), state


def _ssm_core(cfg: TransformerConfig, lp, window):
    """The convolution, the activation and what a token makes of itself:
    `window` (B, conv - 1 + S, channels), the projection with the
    positions before it -> u (B, S, channels), the step (B, S, channels)
    before its bias and softplus (`ops/selective_scan.step_size`) and B,
    C (B, S, d_state), float32, with `A` (d_state, channels)."""
    R, N = cfg.mamba_dt_rank, cfg.mamba_d_state
    dt, f32, eps = cfg.dtype, jnp.float32, cfg.norm_eps
    with jax.named_scope("ssm_conv"):
        y = _causal_conv(window, lp["conv"])
        if cfg.mamba_conv_bias:
            y = y + lp["conv_bias"].astype(f32)
        u = jax.nn.silu(y)
    with jax.named_scope("ssm_dt"):
        x = _dot(u.astype(dt), lp["w_x"])
        r = _norm(x[..., :R], lp["dt_norm"], eps)
        return (u, _dot(r.astype(dt), lp["w_dt"]),
                _norm(x[..., R:R + N], lp["b_norm"], eps),
                _norm(x[..., R + N:], lp["c_norm"], eps),
                -jnp.exp(lp["A_log"].astype(f32)))


def _ssm_tile(cfg: TransformerConfig, mix, lp, z, lengths=None):
    """A tile's selective scan from a zero state: (what `wo` multiplies
    (B, S, channels) float32, the state behind each row's last real
    position, the projection behind conv - 1 zero positions)."""
    from ..ops import selective_scan

    f32 = jnp.float32
    window = jnp.pad(mix, ((0, 0), (cfg.mamba_d_conv - 1, 0), (0, 0)))
    u, pre, b, c, A = _ssm_core(cfg, lp, window)
    with jax.named_scope("ssm_dt"):
        step = selective_scan.step_size(pre, lp["dt_bias"].astype(f32))
    with jax.named_scope("ssm_scan"):
        y, last = selective_scan.scan(step, u, b, c, A, lengths)
    return selective_scan.gated(y, u, lp["D"].astype(f32), z), last, window


def rope_by_kind(cfg: TransformerConfig, seq_len: int, positions=None):
    """{kind: (sin, cos)} for the kinds of layer that rotate q and k,
    each from its own section of `cfg.rope_parameters`: tables (S, half)
    for a tile, or, with `positions` (B,), each slot's row of a table of
    `seq_len` positions, (B, 1, half); with `positions` (B, Bd), a row a
    position of each slot's block, (B, Bd, half) (one past the table's
    end reads its last row: such a slot's pass is dropped)."""
    out = {}
    for kind in cfg.period_form.rotary:
        sin, cos = rope_tables(
            cfg, seq_len, ROPE_SECTION[kind],
            cfg.qk_rope_head_dim if _latent(cfg, kind) else 0)
        if positions is not None and positions.ndim == 2:
            sin, cos = sin[positions], cos[positions]
        elif positions is not None:
            sin, cos = sin[positions][:, None, :], cos[positions][:, None, :]
        out[kind] = (sin, cos)
    return out


def _run(cfg: TransformerConfig, params, x, rope, attend, state, rows=None):
    """`stackparts.run` over the plan. `attend(l, kind, q, k, v, state)`
    is told which layer of its kind it serves, counted over the whole
    stack: the cache layer it keeps."""
    plan, kinds = layer_plan(cfg), step_kinds(cfg)
    if plan[0].key == DENSE:
        # The leading layers walk as periods of one layer, their leaves
        # given that axis here: scanned as they lie, a decode block
        # compiles to another module (ROADMAP D19).
        # (a leading layer's own leaves, under its kind, have no such
        # axis to begin with: `leaves_at`).
        params = {**params, DENSE: {
            k: a if isinstance(a, dict) else a[:, None]
            for k, a in params[DENSE].items()}}
        plan = [plan[0]._replace(lead=plan[0].lead + (1,))] + plan[1:]
    per = [{kind: ks.count(kind) for kind in KINDS} for ks in kinds]
    # Each group's first layer of a kind: those the groups before it hold.
    base = [{kind: sum(g.lead[0] * p[kind]
                       for g, p in zip(plan[:i], per[:i])) for kind in KINDS}
            for i in range(len(plan))]

    def layer_at(i, g, j):
        kind = kinds[i][j]
        l = base[i][kind] + g * per[i][kind] + kinds[i][:j].count(kind)
        return lambda lp, x, experts_at, state: layer(
            cfg, lp, x, kind, experts_at, rope, partial(attend, l), state,
            rows)

    def leaves_at(i, weights, j):
        # The step's j-th layer: what every layer has, at j, and the
        # leaves of its own, under its kind and its place among the
        # step's layers of the kind.
        kind = kinds[i][j]
        return {**{k: a[j] for k, a in weights.items()
                   if not isinstance(a, dict)},
                **weights[f"{kind}{kinds[i][:j].count(kind)}"]}

    return stackparts.run(cfg, params, plan, x, layer_at, state,
                          leaves_at if cfg.period_form.recurrent else None)


def _walk(cfg: TransformerConfig, params, x, rope, attend, state, rows=None):
    """The whole stack on x, what `prefill`, `forward_free` and `decode`
    share: `_run`, as it returns. A looped configuration (`cfg.ut_steps`
    > 1): `_run` that many times over the same leaves, a scan step a
    pass under the scope `ut_pass`, the final norm after each pass and
    its output the next pass's input; pass t tells `attend` the cache
    slab t x (layers of the kind) + l. Every pass runs and writes its
    slab whatever the exit gate says, and x comes back as every pass's
    final-normed output, (ut_steps, ..., D). `_leave` makes either x
    the rows' final-normed state."""
    if cfg.ut_steps == 1:
        return _run(cfg, params, x, rope, attend, state, rows)
    per_pass = _layers_of(cfg)

    def one(carry, t):
        x, state = carry
        with jax.named_scope("ut_pass"):
            x, state, _, _ = _run(
                cfg, params, x, rope,
                lambda l, kind, *a: attend(t * per_pass[kind] + l, kind, *a),
                state, rows)
            x = _final(cfg, params, x)
        return (x, state), x

    (_, state), hs = lax.scan(one, (x, state), jnp.arange(cfg.ut_steps))
    return hs, state, None, ()


def _leave(cfg: TransformerConfig, params, x):
    """`_walk`'s x -> (each row's final-normed state, each row's exit
    pass, x's shape without D; None where one pass is the walk): the
    final norm, or, of a looped configuration's passes, the one
    `stackparts.exit_select` picks a row."""
    if cfg.ut_steps == 1:
        return _final(cfg, params, x), None
    x, exits, _ = stackparts.exit_select(cfg, params, x)
    return x, exits


def _embed(cfg: TransformerConfig, params, tokens):
    x = params["embed"][tokens].astype(jnp.float32)
    if cfg.period_form.embed_scale:
        x = x * math.sqrt(cfg.d_model)
    return x.astype(cfg.dtype)


_QUERY_BLOCK = 256
_ROW_ALONE = 512        # positions from which `forward_free` walks rows singly


def _attention_f32(q, k, v, window: int, block_len: int = 0):
    """Causal (windowed; `block_len`: block-causal, a query standing at its
    block's last position) attention of float32 q (B, S, H, Dh) over float32
    k, v (B, S, KVH, Dh), both products at the highest precision (the
    flash kernel multiplies in bf16), a block of queries at a time so
    that the scores held are (B, H, block, keys). A block meets all S
    keys and a mask, or, where a window reaches fewer, the slice of
    `window` + a block (in whole blocks) that ends with the block's last
    query: a window layer's work follows its window and not S (8,192
    positions under a window of 1,024: 1,280 keys a block, not 8,192)."""
    B, S, H, Dh = q.shape
    KVH = k.shape[2]
    blk = _QUERY_BLOCK if S % _QUERY_BLOCK == 0 else S
    hi = lax.Precision.HIGHEST
    qb = q.reshape(B, S // blk, blk, KVH, H // KVH, Dh)
    j = jnp.arange(S)[None, :]
    keys = blk * (-(-window // blk) + 1) if window else S

    def block(args):
        qs, start = args                                # (B, blk, KVH, G, Dh)
        kb, vb, jb = k, v, j
        if keys < S:
            first = jnp.clip(start + blk - keys, 0, S - keys)
            kb, vb = (lax.dynamic_slice_in_dim(x, first, keys, axis=1)
                      for x in (k, v))
            jb = first + jnp.arange(keys)[None, :]
        s = jnp.einsum("bqkgd,bskd->bkgqs", qs, kb, precision=hi) \
            / math.sqrt(Dh)
        i = start + jnp.arange(blk)[:, None]
        if block_len:
            i = i | (block_len - 1)
        seen = jb <= i
        if window:
            seen = seen & (i - jb < window)
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("bkgqs,bskd->bqkgd", p, vb, precision=hi)

    out = lax.map(block, (jnp.moveaxis(qb, 1, 0),
                          jnp.arange(S // blk) * blk))
    return jnp.moveaxis(out, 0, 1).reshape(B, S, H, Dh)


def _flash(cfg: TransformerConfig, kind: str, q, k, v):
    w = cfg.sliding_window if kind == WINDOW else 0
    if q.dtype == jnp.float32:
        return _attention_f32(q, k, v, w, cfg.block_length)
    from ..ops import flash_attention

    if cfg.block_length:
        return flash_attention(q, k, v, causal=True, block=cfg.block_length)
    if not w or q.shape[1] <= w:
        return flash_attention(q, k, v, causal=True)
    # The kernel chooses its blocks and gives a step to the blocks a window
    # reaches and no other (`ops/flash_attention._fwd_blocks`, `_fwd_grid`);
    # PERF.md section 6, PR 33, has what a layer costs with and without.
    return flash_attention(q, k, v, causal=True, window=w)


def _put(cfg, cache, l, slots, rows):
    """rows (W, R, KVH, Dh) into layer l of `cache`, rows [0, R) of each
    slot (a slot out of range is dropped)."""
    R = rows.shape[1]
    if cfg.ut_steps > 1 and rows.shape[0] == 1:
        # A looped tile of one row writes as a tile of two, the twin aimed
        # past the cache's end and dropped. XLA turns a scatter at one
        # index into a dynamic-update-slice, and behind a walk of several
        # passes it then lays the whole carried cache out as the tile's
        # keys lie for their product (rows minor) instead of copying the
        # tile: two copies of 3.75 GB in and out at Ouro-2.6B's 192 slabs,
        # which the compiler refuses for memory (PERF.md section 6, PR 55).
        rows = jnp.concatenate([rows, rows])
        slots = jnp.concatenate([slots, jnp.full_like(slots, cache.shape[1])])
    if cache_terms(cfg) == 1:
        return cache.at[l, slots, :R].set(rows.astype(cache.dtype),
                                          mode="drop")
    hi, lo = bf16_terms(rows)
    L = cache.shape[0] // 2
    return cache.at[l, slots, :R].set(hi, mode="drop") \
        .at[L + l, slots, :R].set(lo, mode="drop")


def _prefill_attend(cfg, slots, lengths, l, kind, q, k, v, state):
    """Causal (windowed) attention over the tile itself, and the tile's
    k and v into each row's slot: a global layer's at [0, S), a window
    layer's last `ring` real positions at `position mod ring` (a row at
    or past `length` holds padding, which decode overwrites before it
    reads it). A row whose slot is out of range is dropped."""
    kg, vg, kw, vw, s, tails = state
    if _latent(cfg, kind):
        # q, k: (the layer's leaves, q_nope, q_r) and the tile's rows
        # (`layer`); `kg` the latent rows.
        out, (kg, _, _) = mla._prefill_attend(cfg, slots, l, *q, k, None,
                                                 (kg, None, None))
        return out, (kg, vg, kw, vw, s, tails)
    if kind in _RECURRENT:
        # q, k, v: the projections, the convolution's weights and (g,
        # beta) (`_linear_half`), or the projection and the layer's
        # leaves (`_ssm_half`). The state and the projections' last
        # conv - 1 positions as the prompt's last token left them.
        out, last, window = _RECURRENT[kind].tile(cfg, q, k, v, lengths)
        at = lengths[:, None] + jnp.arange(tails.shape[2])[None, :]
        tail = jnp.take_along_axis(window, at[:, :, None], axis=1)
        return out, (kg, vg, kw, vw, s.at[l, slots].set(last, mode="drop"),
                     tails.at[l, slots].set(tail.astype(tails.dtype),
                                            mode="drop"))
    S = q.shape[1]
    out = _flash(cfg, kind, q, k, v)
    if kind == GLOBAL:
        return out, (_put(cfg, kg, l, slots, k), _put(cfg, vg, l, slots, v),
                     kw, vw, s, tails)
    ring = kw.shape[2]
    if S > ring:
        r = jnp.arange(ring)[None, :]
        last = (lengths - 1).astype(jnp.int32)[:, None]
        # Ring row r keeps the latest real position congruent to r.
        src = jnp.where(r <= last, r + ring * ((last - r) // ring), r)
        src = src[:, :, None, None]
        k = jnp.take_along_axis(k, src, axis=1)
        v = jnp.take_along_axis(v, src, axis=1)
    return out, (kg, vg, _put(cfg, kw, l, slots, k),
                 _put(cfg, vw, l, slots, v), s, tails)


def _attend_terms(cfg, q, k, v, k_all, v_all, l, write_at, positions,
                  live=None):
    """`stackparts._attend_cache` over a cache of two bf16 terms (`k_all`
    (2L, B, S, KVH, Dh): see `cache_terms`), q, k, v float32: every
    product takes bf16 operands, the float32 side (q, then the
    probabilities) as two terms stacked beside the heads of a group, the
    cached side as its two slabs, one product each. The kernel of
    `ops/decode_attention` makes the same four products a block of the
    rows held; the code below makes them over every row."""
    from ..ops import decode_attention as da

    L, B, S = k_all.shape[0] // 2, k_all.shape[1], k_all.shape[2]
    KVH, Dh = cfg.n_kv_heads, cfg.head_dim
    G = cfg.n_heads // KVH
    at = (jnp.stack([l, L + l])[:, None], jnp.arange(B)[None, :],
          write_at[None, :])
    k_all = k_all.at[at].set(bf16_terms(k[:, 0]), mode="drop")
    v_all = v_all.at[at].set(bf16_terms(v[:, 0]), mode="drop")
    n_rows = rows_held(positions, S, live)
    qg = q.reshape(B, KVH, G, Dh)
    if da.usable(k_all, Dh):
        return da.decode_attention(qg, k_all, v_all, l, n_rows), k_all, v_all

    def against(x, eq, cached):       # x (B, KVH, G, .) float32
        two = jnp.concatenate(list(bf16_terms(x)), axis=2)
        y = sum(jnp.einsum(eq, two, lax.dynamic_index_in_dim(
            cached, i, 0, keepdims=False),
            preferred_element_type=jnp.float32) for i in (l, L + l))
        return y[:, :, :G] + y[:, :, G:]

    scores = against(qg, "bkgd,bskd->bkgs", k_all) / (Dh ** 0.5)
    out = against(masked_softmax(scores, n_rows, live), "bkgs,bskd->bkgd",
                  v_all)
    return out.reshape(B, 1, KVH * G * Dh), k_all, v_all


def _linear_step(cfg, live, l, mix, conv, gates, s, tails):
    """One token a slot through linear layer `l`: the convolution over
    the slot's tail and this token, one update of its state
    (`ops/delta_rule.decode_update`), the tail moved on a position
    (`move_tails`). A slot that is not `live` keeps both as they were."""
    from ..ops import delta_rule

    g, beta = gates
    tail = lax.dynamic_index_in_dim(tails, l, 0, keepdims=False)
    new = mix.astype(tail.dtype)
    q, k, v = _linear_core(cfg, jnp.concatenate([tail, new], axis=1), conv)
    out, s = delta_rule.decode_update(s, l, q[:, 0], k[:, 0], v[:, 0],
                                      g[:, 0], beta[:, 0], live)
    return out[:, None], s, delta_rule.move_tails(tails, l, new, live)


def _ssm_step(cfg, live, l, mix, lp, z, s, tails):
    """One token a slot through state-space layer `l`: XLA makes what the
    recurrence takes of the token (`_ssm_core` over the slot's tail and
    this token), and one call does the rest a slot
    (`ops/selective_scan.decode_update`): the step's bias and softplus,
    one update of the state, the skip and the gate, the tail a position
    on. A slot that is not `live` keeps state and tail as they were."""
    from ..ops import selective_scan

    tail = lax.dynamic_index_in_dim(tails, l, 0, keepdims=False)
    new = mix.astype(tail.dtype)
    u, pre, b, c, A = _ssm_core(cfg, lp,
                                jnp.concatenate([tail, new], axis=1))
    out, s, tails = selective_scan.decode_update(
        s, tails, l, new[:, 0], pre[:, 0], lp["dt_bias"], u[:, 0], b[:, 0],
        c[:, 0], z[:, 0], A, lp["D"], live)
    return out[:, None], s, tails


class _Recurrent(NamedTuple):
    """What a kind of layer that keeps a recurrent state brings: its
    leaves, its half of `layer`, a tile from a zero state, one token a
    slot."""

    shapes: Callable
    half: Callable
    tile: Callable
    step: Callable


_RECURRENT = {
    LINEAR: _Recurrent(_linear_shapes, _linear_half, _linear_tile,
                       _linear_step),
    SSM: _Recurrent(_ssm_shapes, _ssm_half, _ssm_tile, _ssm_step)}


def _decode_attend(cfg, positions, live, l, kind, q, k, v, state):
    kg, vg, kw, vw, s, tails = state
    if _latent(cfg, kind):
        out, (kg, _, _) = mla._attend_rows(cfg, positions, live, l, *q, k,
                                              None, (kg, None, None))
        return out, (kg, vg, kw, vw, s, tails)
    if kind in _RECURRENT:
        out, s, tails = _RECURRENT[kind].step(cfg, live, l, q, k, v, s, tails)
        return out, (kg, vg, kw, vw, s, tails)
    attend = _attend_terms if cache_terms(cfg) == 2 else _attend_cache
    if kg.dtype != q.dtype and cache_terms(cfg) == 1:
        # Rows of another dtype than the activations'
        # (`TransformerConfig.cache_dtype`): the step attends in theirs.
        q, k, v = (a.astype(kg.dtype) for a in (q, k, v))
    if kind == GLOBAL:
        out, kg, vg = attend(cfg, q, k, v, kg, vg, l, positions, positions,
                             live)
    else:
        out, kw, vw = attend(cfg, q, k, v, kw, vw, l,
                             positions % kw.shape[2], positions, live)
    B = q.shape[0]
    return out.reshape(B, 1, cfg.n_heads, cfg.head_dim), \
        (kg, vg, kw, vw, s, tails)


def _block_attend(cfg, p0, live, l, kind, q, k, v, state):
    """A block of positions a slot (`decode_block`): every layer of such
    a configuration is global."""
    kg, vg, *rest = state
    out, kg, vg = _attend_cache_block(cfg, q, k, v, kg, vg, l, p0, live)
    return out.reshape(q.shape), (kg, vg, *rest)


def _free_attend(cfg, l, kind, q, k, v, state):
    if _latent(cfg, kind):
        return mla._attend_tile(cfg, *q, k), state
    if kind in _RECURRENT:
        return _RECURRENT[kind].tile(cfg, q, k, v)[0], state
    return _flash(cfg, kind, q, k, v), state


# ---------------------------------------------------------------------------
# What generate.py's programs call
# ---------------------------------------------------------------------------

def _state(cache: KVCache):
    """What rides in the walk's carry: the cache without `seq_lens`; the
    global layers' rows first, keys (and values) a head or, of a latent
    global layer, `c`."""
    return (cache.c if cache.k is None else cache.k, cache.v, cache.kw,
            cache.vw, cache.s, cache.tails)


def _cache(state, seq_lens, latent: bool = False) -> KVCache:
    kg, vg, kw, vw, s, tails = state
    if latent:
        return KVCache(k=None, v=None, seq_lens=seq_lens, c=kg, s=s,
                       tails=tails)
    return KVCache(k=kg, v=vg, seq_lens=seq_lens, kw=kw, vw=vw, s=s,
                   tails=tails)


def prefill(cfg: TransformerConfig, params, cache: KVCache, tokens, lengths,
            slots) -> Tuple[KVCache, jax.Array, Extras]:
    """tokens (W, S) into the slots' cache rows -> (cache', final-normed
    hidden states (W, S, D), `Extras`: routing stats of the tile as
    `decode` gives a step's, over all W x S positions, padding too; None
    with no routed layer). With `cfg.block_length` the mask is block-causal and
    `lengths` are whole blocks (what is left of a prompt opens the
    slot's first block: `generate.prefill_block_batch`). A looped
    configuration: x is each position's state at its exit pass, and
    `exits` the exit passes (W, S) (`_walk`)."""
    rope = rope_by_kind(cfg, tokens.shape[1])
    x, state, stats, _ = _walk(
        cfg, params, _embed(cfg, params, tokens), rope,
        partial(_prefill_attend, cfg, slots, lengths), _state(cache))
    seq_lens = cache.seq_lens.at[slots].set(lengths, mode="drop")
    x, exits = _leave(cfg, params, x)
    return _cache(state, seq_lens, cfg.period_form.latent), x, \
        Extras(stats if routed_layers(cfg) else None, exits)


def forward_free(cfg: TransformerConfig, params, tokens):
    """tokens (W, S) with no cache -> (final-normed hidden states (W, S,
    D), the experts every routed layer chose: see `stackparts.run`,
    `Extras`: a looped configuration's exit passes (W, S)). A
    tile of several long rows through recurrent layers runs a row at a
    time: a linear layer's projections are 6 x d_model wide in float32
    between its products, a queue-side tile of 4 x 2,048 held 3.9 GB of
    them beside the weights and a cache it does not touch, and a row of
    `_ROW_ALONE` positions fills the chip's multipliers alone."""
    W, S = tokens.shape
    if cfg.period_form.recurrent and W > 1 and S >= _ROW_ALONE:
        x, chosen, extras = lax.map(
            lambda row: forward_free(cfg, params, row[None]), tokens)
        return x[:, 0], jax.tree.map(
            lambda a: jnp.moveaxis(a, 0, 1).reshape(
                a.shape[1], W * S, a.shape[-1]), chosen), \
            jax.tree.map(lambda a: a[:, 0], extras)
    rope = rope_by_kind(cfg, tokens.shape[1])
    x, _, _, chosen = _walk(cfg, params, _embed(cfg, params, tokens), rope,
                            partial(_free_attend, cfg), None)
    x, exits = _leave(cfg, params, x)
    return x, chosen, Extras(exits=exits)


def decode(cfg: TransformerConfig, params, cache: KVCache, tokens,
           live=None) -> Tuple[KVCache, jax.Array, Extras]:
    """One token a slot -> (cache', logits (B, V), `Extras`: routing stats
    of the step (`moe.routed_ffn`'s, summed over the routed layers): experts
    that took a row, pairs routed, the pairs of the expert most chosen,
    rows the experts took; None with no routed layer). `live` (B,) bool:
    the slots a request owns (None: every one): any other slot reads and
    writes no cache row and its token meets no expert. A looped
    configuration: the logits of each slot's exit pass, and `exits` the
    exit passes (B,) (`_walk`)."""
    if cfg.block_length:
        raise NotImplementedError(NOT_ITS_WALK["decode"])
    positions = cache.seq_lens
    rope = rope_by_kind(cfg, cache.max_seq_len, positions)
    x, state, stats, _ = _walk(
        cfg, params, _embed(cfg, params, tokens)[:, None, :], rope,
        partial(_decode_attend, cfg, positions, live), _state(cache), live)
    cache = _cache(state, positions + 1, cfg.period_form.latent)
    x, exits = _leave(cfg, params, x)
    return cache, head_logits(cfg, params, x[:, 0]), Extras(
        stats if routed_layers(cfg) else None,
        None if exits is None else exits[:, 0])


def decode_block(cfg: TransformerConfig, params, cache: KVCache, tokens, p0,
                 live=None) -> Tuple[KVCache, jax.Array, Extras]:
    """One pass over a block a slot: tokens (B, Bd) (the mask token's id
    where a position is still masked) at positions p0 .. p0 + Bd - 1 (p0
    (B,): the rows the slot has committed, a multiple of Bd) -> (cache',
    logits (B, Bd, V), `Extras`: routing stats of the pass as `decode`
    gives a step's, over B x Bd rows; None with no routed layer). Each layer
    writes the block's keys and values at rows [p0, p0 + Bd) and every
    query of the block attends over rows [0, p0 + Bd): the committed
    prefix and the block itself, no mask inside it. `seq_lens` is left
    as it is: the rows are the block's for good only when the caller
    advances it (a pass over the block's final tokens), and the block's
    next pass overwrites them until then. A slot that is not `live`, or
    whose block would pass the cache's end, writes and reads nothing and
    its block's positions meet no expert."""
    if not cfg.block_length:
        raise NotImplementedError(NOT_ITS_WALK["decode_block"])
    if cache_terms(cfg) == 2:
        raise NotImplementedError(NOT_ITS_WALK["terms"])
    B, Bd = tokens.shape
    S = cache.max_seq_len
    fits = p0 + Bd <= S
    live = fits if live is None else live & fits
    positions = jnp.minimum(p0[:, None] + jnp.arange(Bd)[None, :], S - 1)
    rope = rope_by_kind(cfg, S, positions)
    x, state, stats, _ = _run(
        cfg, params, _embed(cfg, params, tokens), rope,
        partial(_block_attend, cfg, p0, live), _state(cache),
        jnp.repeat(live, Bd))
    cache = _cache(state, cache.seq_lens)
    with jax.named_scope("block_head"):
        logits = head_logits(cfg, params, _final(cfg, params, x))
    return cache, logits, Extras(stats if routed_layers(cfg) else None)


def chosen_experts(cfg: TransformerConfig, params, tokens) -> List[jax.Array]:
    """For tests and for telling a routing flip from arithmetic: the
    experts each routed layer chose for tokens (S,), in layer order, each
    (S, K)."""
    _, chosen, _ = jax.jit(partial(forward_free, cfg))(
        params, jnp.asarray(tokens, jnp.int32)[None])
    return stackparts.chosen_by_layer(chosen)
