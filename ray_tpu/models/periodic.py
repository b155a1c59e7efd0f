"""The period stack (the architectures of `transformer.PERIOD_FORMS`): a
decoder whose layers are not all alike, served through the programs of
`generate.py`.

`n_dense_layers` leading layers with a dense SwiGLU, then whole periods
of `global_attn_every` layers with the routed layer of `models/moe.py`
(plus always-on shared experts where the configuration has them); the
last layer of a period attends to every earlier position, the others and
the leading layers to the last `sliding_window`. Every layer: an RMS norm
before attention and before the FFN and a learned norm over each head of
q and k. What else a layer has is data, `cfg.period_form`: norms on the
attention's and the FFN's output, the attention output gated by
`sigmoid(h @ wg)` before `wo`, the embedding scaled by sqrt(d_model), a
selection bias, and which kinds of layer rotate q and k, each kind with
the table of its own section of `cfg.rope_parameters` (Trinity: window
layers only; Mellum 2: both, the global ones by YaRN).

Weights: `dense_layers` (leaves stacked over the leading layers) and
`periods` (leaves stacked over periods, then over a period's layers).
One layer definition (`layer`) and one walk (`stackparts.run`) serve
prefill, the cache-free first token and decode; they differ in the
`attend` they hand in, which owns the cache.

The cache holds two kinds of state in one `KVCache`: `k`/`v` for the
global layers, (Lg, slots, S_max, KVH, Dh), and `kw`/`vw` for the window
layers, (Lw, slots, min(sliding_window, S_max), KVH, Dh), a ring written
at `position mod rows`. Softmax does not care in which order the ring
holds its rows, and a key carries its rotary phase from when it was
written, so decode reads the ring as it lies.

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

import math
from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..parallel.sharding import with_sharding_constraint as wsc
from . import stackparts
# `routing_stats` and `last_logits` are the seam's (`transformer.STACKS`).
from .moe import bf16_terms, dot as _dot, routing_stats  # noqa: F401
from .stackparts import (Group, KVCache, _attend_cache,  # noqa: F401
                         _attend_cache_block, _final, _norm, _rope, ffn_half,
                         head_logits, joins, last_logits, masked_softmax,
                         rows_held)
from .transformer import TransformerConfig, rope_tables

WINDOW, GLOBAL = "window", "global"
KINDS = (WINDOW, GLOBAL)
# A kind's section of `TransformerConfig.rope_parameters`, under the key a
# published config.json gives it.
ROPE_SECTION = {WINDOW: "sliding_attention", GLOBAL: "full_attention"}

# What the dense stack offers and this one does not (`transformer.offered`).
MISSING = {
    # The prefix programs install one (L, Sp, KVH, Dh) block of keys and
    # values a layer. A window layer's ring holds a slot's last rows at
    # `position mod rows`, not a prefix at [0, Sp): sharing it needs a
    # layout of its own.
    "suffix": "prefix sharing (prefill_suffix_*, first_token_suffix_*, "
              "compute_prefix_kv) is not written for a windowed cache "
              "(models/periodic.py)",
    "param_logical_axes": "the period stack has no sharding rules yet: it "
                          "is served on one chip (models/periodic.py)",
    "forward_train": "the period stack is served only (models/generate.py): "
                     "training lacks a dropless routed layer under "
                     "autodiff (moe_ffn drops tokens over capacity), the "
                     "backward of windowed flash attention, and the "
                     "load-balancing update of the selection bias",
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


DENSE, PERIODS = "dense_layers", "periods"


def layer_plan(cfg: TransformerConfig) -> List[Group]:
    """The leading layers, a layer a scan step, then whole periods, a
    period a step."""
    every = cfg.global_attn_every
    plan = []
    if cfg.n_dense_layers:
        plan.append(Group(DENSE, (cfg.n_dense_layers,), False))
    periods = (cfg.n_layers - cfg.n_dense_layers) // every
    if periods:
        plan.append(Group(PERIODS, (periods, every), cfg.is_moe))
    return plan


def step_kinds(cfg: TransformerConfig) -> List[Tuple[str, ...]]:
    """The kinds of a scan step's layers, a group of `layer_plan`."""
    win = WINDOW if cfg.sliding_window else GLOBAL
    period = (win,) * (cfg.global_attn_every - 1) + (GLOBAL,)
    return [(win,) if group.key == DENSE else period
            for group in layer_plan(cfg)]


def routed_layers(cfg: TransformerConfig) -> int:
    """Layers whose use of their experts `decode` reports."""
    return stackparts.routed_layers(layer_plan(cfg))


def cache_layers(cfg: TransformerConfig) -> Dict[str, int]:
    """How many layers keep each kind of state."""
    return {kind: sum(group.lead[0] * kinds.count(kind)
                      for group, kinds in zip(layer_plan(cfg),
                                              step_kinds(cfg)))
            for kind in KINDS}


# ---------------------------------------------------------------------------
# Weights
# ---------------------------------------------------------------------------

def _layer_shapes(cfg: TransformerConfig, routed: bool
                  ) -> Dict[str, Tuple[int, ...]]:
    d, hd = cfg.d_model, cfg.head_dim
    q, kv = cfg.n_heads * hd, cfg.n_kv_heads * hd
    form = cfg.period_form
    shapes = {
        "attn_norm": (d,), "wq": (d, q), "wk": (d, kv), "wv": (d, kv),
        "wo": (q, d), "q_norm": (hd,), "k_norm": (hd,), "ffn_norm": (d,),
    }
    if form.attn_gate:
        shapes["wg"] = (d, q)
    if form.post_norms:
        shapes.update(post_attn_norm=(d,), post_ffn_norm=(d,))
    return {**shapes, **stackparts.ffn_shapes(cfg, routed, form.router_bias)}


def num_params(cfg: TransformerConfig) -> int:
    return stackparts.num_params(cfg, layer_plan(cfg), _layer_shapes)


def init_params(cfg: TransformerConfig, key: jax.Array) -> Dict[str, Any]:
    return stackparts.init_params(cfg, key, layer_plan(cfg), _layer_shapes)


def cache_terms(cfg: TransformerConfig) -> int:
    """The bf16 terms a cached key or value is kept as: two for float32
    activations on bf16 weights (hi + lo carry 16 bits of mantissa, and
    the products of attention take bf16), else one value of `cfg.dtype`."""
    return 2 if (cfg.dtype == jnp.float32
                 and cfg.param_dtype == jnp.bfloat16) else 1


def init_cache(cfg: TransformerConfig, num_slots: int, max_seq_len: int
               ) -> KVCache:
    n = cache_layers(cfg)
    terms = cache_terms(cfg)

    def zeros(layers: int, rows: int):
        z = jnp.zeros((terms * layers, num_slots, rows, cfg.n_kv_heads,
                       cfg.head_dim),
                      jnp.bfloat16 if terms == 2 else cfg.dtype)
        return wsc(z, ("layers", None, None, "act_kv_heads", None))

    ring = min(cfg.sliding_window, max_seq_len)
    return KVCache(
        k=zeros(n[GLOBAL], max_seq_len), v=zeros(n[GLOBAL], max_seq_len),
        seq_lens=jnp.zeros((num_slots,), jnp.int32),
        kw=zeros(n[WINDOW], ring) if n[WINDOW] else None,
        vw=zeros(n[WINDOW], ring) if n[WINDOW] else None)


# ---------------------------------------------------------------------------
# The layer, and the walk over the stack
# ---------------------------------------------------------------------------

def layer(cfg: TransformerConfig, lp, x, kind: str, experts_at, rope,
          attend, state, rows=None):
    """One layer on x (B, S, D) in the activation dtype. `rope`: {kind:
    (sin, cos)} for the kinds that rotate (`rope_by_kind`). `attend(kind,
    q, k, v, state) -> (out (B, S, H, Dh), state)` does the attention and
    whatever it keeps of k and v. `experts_at`, `rows`: as `ffn_half`
    takes them. Returns (x, state, routing stats, experts chosen (B*S,
    K) or None)."""
    B, S, _ = x.shape
    H, KVH, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    dt, eps = cfg.dtype, cfg.norm_eps
    form = cfg.period_form

    # Every product hands back float32; what lies between two products
    # (norms, rotary, gates) stays float32 and is rounded to `dt` once,
    # where it enters the next product or the cache (float32: never).
    h = _norm(x, lp["attn_norm"], eps).astype(dt)
    q = _dot(h, lp["wq"]).reshape(B, S, H, Dh)
    k = _dot(h, lp["wk"]).reshape(B, S, KVH, Dh)
    v = _dot(h, lp["wv"]).reshape(B, S, KVH, Dh).astype(dt)
    gate = _dot(h, lp["wg"]) if form.attn_gate else None
    q = _norm(q, lp["q_norm"], eps)
    k = _norm(k, lp["k_norm"], eps)
    if kind in rope:                   # else the kind has no position
        q, k = _rope(q, *rope[kind]), _rope(k, *rope[kind])
    with jax.named_scope("attn_" + kind):
        out, state = attend(kind, q.astype(dt), k.astype(dt), v, state)
    out = out.reshape(B, S, H * Dh)
    if gate is not None:
        out = (out.astype(jnp.float32) * jax.nn.sigmoid(gate)).astype(dt)
    x = joins(x, _dot(out, lp["wo"]),
              lp["post_attn_norm"] if form.post_norms else None, eps)
    # A dense layer's stats are zeros, which the walk adds to its sum as
    # it adds a routed layer's: without that add the tile and the decode
    # block of a configuration with leading dense layers compile to
    # another module (ROADMAP D19).
    zeros = jnp.zeros((routing_stats(cfg),), jnp.int32)
    x, stats, experts = ffn_half(cfg, lp, x, experts_at, form.post_norms,
                                 rows)
    return x, state, zeros if stats is None else stats, experts


def rope_by_kind(cfg: TransformerConfig, seq_len: int, positions=None):
    """{kind: (sin, cos)} for the kinds of layer that rotate q and k,
    each from its own section of `cfg.rope_parameters`: tables (S, half)
    for a tile, or, with `positions` (B,), each slot's row of a table of
    `seq_len` positions, (B, 1, half); with `positions` (B, Bd), a row a
    position of each slot's block, (B, Bd, half) (one past the table's
    end reads its last row: such a slot's pass is dropped)."""
    out = {}
    for kind in cfg.period_form.rotary:
        sin, cos = rope_tables(cfg, seq_len, ROPE_SECTION[kind])
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
        params = {**params, DENSE: jax.tree.map(lambda a: a[:, None],
                                                params[DENSE])}
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

    return stackparts.run(cfg, params, plan, x, layer_at, state)


def _embed(cfg: TransformerConfig, params, tokens):
    x = params["embed"][tokens].astype(jnp.float32)
    if cfg.period_form.embed_scale:
        x = x * math.sqrt(cfg.d_model)
    return x.astype(cfg.dtype)


_QUERY_BLOCK = 256


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
    kg, vg, kw, vw = state
    S = q.shape[1]
    out = _flash(cfg, kind, q, k, v)
    if kind == GLOBAL:
        return out, (_put(cfg, kg, l, slots, k), _put(cfg, vg, l, slots, v),
                     kw, vw)
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
                 _put(cfg, vw, l, slots, v))


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


def _decode_attend(cfg, positions, live, l, kind, q, k, v, state):
    kg, vg, kw, vw = state
    attend = _attend_terms if cache_terms(cfg) == 2 else _attend_cache
    if kind == GLOBAL:
        out, kg, vg = attend(cfg, q, k, v, kg, vg, l, positions, positions,
                             live)
    else:
        out, kw, vw = attend(cfg, q, k, v, kw, vw, l,
                             positions % kw.shape[2], positions, live)
    B = q.shape[0]
    return out.reshape(B, 1, cfg.n_heads, cfg.head_dim), (kg, vg, kw, vw)


def _block_attend(cfg, p0, live, l, kind, q, k, v, state):
    """A block of positions a slot (`decode_block`): every layer of such
    a configuration is global."""
    kg, vg, kw, vw = state
    out, kg, vg = _attend_cache_block(cfg, q, k, v, kg, vg, l, p0, live)
    return out.reshape(q.shape), (kg, vg, kw, vw)


def _free_attend(cfg, l, kind, q, k, v, state):
    return _flash(cfg, kind, q, k, v), state


# ---------------------------------------------------------------------------
# What generate.py's programs call
# ---------------------------------------------------------------------------

def prefill(cfg: TransformerConfig, params, cache: KVCache, tokens, lengths,
            slots) -> Tuple[KVCache, jax.Array, Optional[jax.Array]]:
    """tokens (W, S) into the slots' cache rows -> (cache', final-normed
    hidden states (W, S, D), routing stats of the tile as `decode`
    gives a step's, over all W x S positions, padding too; None with no
    routed layer). With `cfg.block_length` the mask is block-causal and
    `lengths` are whole blocks (what is left of a prompt opens the
    slot's first block: `generate.prefill_block_batch`)."""
    rope = rope_by_kind(cfg, tokens.shape[1])
    x, (kg, vg, kw, vw), stats, _ = _run(
        cfg, params, _embed(cfg, params, tokens), rope,
        partial(_prefill_attend, cfg, slots, lengths),
        (cache.k, cache.v, cache.kw, cache.vw))
    seq_lens = cache.seq_lens.at[slots].set(lengths, mode="drop")
    return KVCache(k=kg, v=vg, seq_lens=seq_lens, kw=kw, vw=vw), \
        _final(cfg, params, x), stats if routed_layers(cfg) else None


def forward_free(cfg: TransformerConfig, params, tokens):
    """tokens (W, S) with no cache -> (final-normed hidden states (W, S,
    D), the experts every routed layer chose: see `stackparts.run`)."""
    rope = rope_by_kind(cfg, tokens.shape[1])
    x, _, _, chosen = _run(cfg, params, _embed(cfg, params, tokens), rope,
                           partial(_free_attend, cfg), None)
    return _final(cfg, params, x), chosen


def decode(cfg: TransformerConfig, params, cache: KVCache, tokens,
           live=None) -> Tuple[KVCache, jax.Array, Optional[jax.Array]]:
    """One token a slot -> (cache', logits (B, V), routing stats of the
    step (`moe.routed_ffn`'s, summed over the routed layers): experts
    that took a row, pairs routed, the pairs of the expert most chosen,
    rows the experts took; None with no routed layer). `live` (B,) bool:
    the slots a request owns (None: every one): any other slot reads and
    writes no cache row and its token meets no expert."""
    if cfg.block_length:
        raise NotImplementedError(NOT_ITS_WALK["decode"])
    positions = cache.seq_lens
    rope = rope_by_kind(cfg, cache.max_seq_len, positions)
    x, (kg, vg, kw, vw), stats, _ = _run(
        cfg, params, _embed(cfg, params, tokens)[:, None, :], rope,
        partial(_decode_attend, cfg, positions, live),
        (cache.k, cache.v, cache.kw, cache.vw), live)
    cache = KVCache(k=kg, v=vg, seq_lens=positions + 1, kw=kw, vw=vw)
    return cache, head_logits(cfg, params, _final(cfg, params, x)[:, 0]), \
        stats if routed_layers(cfg) else None


def decode_block(cfg: TransformerConfig, params, cache: KVCache, tokens, p0,
                 live=None) -> Tuple[KVCache, jax.Array, Optional[jax.Array]]:
    """One pass over a block a slot: tokens (B, Bd) (the mask token's id
    where a position is still masked) at positions p0 .. p0 + Bd - 1 (p0
    (B,): the rows the slot has committed, a multiple of Bd) -> (cache',
    logits (B, Bd, V), routing stats of the pass as `decode` gives a
    step's, over B x Bd rows; None with no routed layer). Each layer
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
    x, (kg, vg, kw, vw), stats, _ = _run(
        cfg, params, _embed(cfg, params, tokens), rope,
        partial(_block_attend, cfg, p0, live),
        (cache.k, cache.v, cache.kw, cache.vw), jnp.repeat(live, Bd))
    cache = KVCache(k=kg, v=vg, seq_lens=cache.seq_lens, kw=kw, vw=vw)
    with jax.named_scope("block_head"):
        logits = head_logits(cfg, params, _final(cfg, params, x))
    return cache, logits, stats if routed_layers(cfg) else None


def chosen_experts(cfg: TransformerConfig, params, tokens) -> List[jax.Array]:
    """For tests and for telling a routing flip from arithmetic: the
    experts each routed layer chose for tokens (S,), in layer order, each
    (S, K)."""
    _, chosen = jax.jit(partial(forward_free, cfg))(
        params, jnp.asarray(tokens, jnp.int32)[None])
    return stackparts.chosen_by_layer(chosen)
