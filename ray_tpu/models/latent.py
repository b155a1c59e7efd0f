"""The latent-attention stack (multi-head latent attention, arXiv:2405.04434
section 2.1, with the routed layer of `models/moe.py`), served through the
programs of `generate.py`. One stack, two architectures told apart by
data (`transformer.LATENT_FORMS`, `TransformerConfig.index_topk`):
openPangu-Ultra-MoE and GLM-5.

`n_dense_layers` leading layers with a dense SwiGLU, then layers of routed
experts beside always-on shared ones. RMS norms before the attention and
before the FFN; where the architecture has sandwich norms
(`LatentForm.post_norms`) two more, on each branch's output before it
joins the residual stream. The router may add a bias an expert for its
choice alone (`LatentForm.router_bias`).

The attention half (the projections, a tile's attention per head, a
decode step's in the latent space, the cache rows) is `models/mla.py`'s,
which a period stack whose global layer is latent calls too
(`mla.attention_half`); this module adds the indexer, the chunk walk, the
layer's FFN half and the entry points.

Attention keeps no key and no value a head. A token's keys and values are
one vector, `c = N(x W_kva)[:kv_lora_rank]`, from which every head's key
part without position and its value are products (`wk_b` (H, nope, rank),
`wv_b` (H, rank, vd): the two halves of the published `kv_b_proj`, kept
apart and a head at a time, as a decode step multiplies by them, so that
neither is ever sliced or transposed there), and one rotary key `k_r`
that all heads share. The cache holds `[c | k_r]`, `kv_lora_rank +
qk_rope_head_dim` values a token a layer, `c` after its norm and `k_r`
after its rotation, and nothing else (`KVCache.c`, (L, slots, S_max, C):
C is that width in whole lanes of 128, the lanes behind the rotary key
zero; a row narrower than its lanes is laid out rows-minor on the chip
and copied whole into and out of every program that reads it by rows).
Queries go through a rank of their own (`wq_a`, a norm, then `wq_nope`
and `wq_rope`, the published `q_b_proj`'s columns by what they make;
straight from the layer's input where `q_lora_rank` is 0) to `[q_nope |
q_r]` a head.

The same products in two orders:

- a tile (`prefill`, `forward_free`) up-projects rows and attends per
  head: scores `(q_nope . k_nope + q_r . k_r) / sqrt(nope + rope)` over
  keys `nope + rope` wide, values `v_head_dim` wide (the flash kernel
  takes values of another width than the keys);
- a decode step never up-projects a cached row. `W_UK` goes into the
  query (`q_nope W_UK^T`, `kv_lora_rank` wide a head) and `W_UV` into the
  output, so the step attends as one key head of C under all the query
  heads, whose first `kv_lora_rank` columns are also the values:
  `ops/decode_attention` with one array, each row it attends read once.

Learned sparse attention (`cfg.index_topk` > 0; DeepSeek-V3.2's, as
GLM-5's `glm_moe_dsa` follows it): which rows a query attends is decided
by the data. A layer has a second, small scorer, the indexer: `qI = c_q
WqI` (`index_n_heads` heads of `index_head_dim`, from the query's normed
rank), one key a token `kI = LayerNorm(a WkI)` (weight and bias) and a
weight a head a query `wI = a WwI / sqrt(heads x width)`, the first
`qk_rope_head_dim` values of every `qI` head and of `kI` rotated as the
attention's rotary part is. Query t scores every row it may see, `I_ts =
sum_h wI_th relu(qI_th . kI_s)` in float32, and attends the `index_topk`
rows of largest score, exactly (ties to the lower row; every row while
it sees no more than `index_topk`). The indexer's keys are a cache of
their own beside the latent rows (`KVCache.ki`, (L, slots, S_max,
index_head_dim)). What the choice is made in is the configuration's,
`cfg.index_dtype` (None: the activation dtype, as everything else): the
residual stream between such a stack's layers, the indexer's projections
of it, the cached keys and the scores' operands (`_embed`,
`_index_project`). A query's chosen set turns on the last bits of
thousands of scores, a row that changes sides changes the layer's
output, and that output feeds the next layer's indexer, so what a
rounding leaves is amplified a layer at a time: under seeded weights a
bf16 choice differs from a float32 reference's in a few rows of a
hundred (PERF.md section 6, PR 41), and a cell that wants the
reference's choices states float32 there. Every other product and the
latent cache take the activation dtype whatever it says. The steps are
`ops/sparse_attention`'s:

- a decode step writes its latent row and its indexer key, scores its
  slot's keys (`index_scores_rows`, rows past those held at `-inf`),
  takes the `index_topk` best (`lax.top_k`), gathers those latent rows
  and attends them in the latent space as above: no latent row it did
  not choose is read (the gather has one shape, so a slot nobody owns
  still fetches `index_topk` rows, which the kernel then skips);
- a tile writes its rows and keys, scores every row of its bucket each
  query may see (`index_scores_tile`), finds each query's chosen set
  (`topk_bias`: the k-th largest score a bit at a time, no sort, over
  the columns at or below the block's last query alone) and attends per
  head under the chosen sets as a bias (`masked_attention`),
  the rows of a chunk of keys at a time up-projected from the cache. A
  bucket no longer than `index_topk` chooses every row: the tile over
  itself, as without an indexer.

A tile longer than `PREFILL_CHUNK` rows is walked a chunk at a time
inside its one program (`_walk`), both caches in the loop's carry: a
chunk goes through every layer, each writing the chunk's rows and
attending rows [0, chunk's end) of the cache, before the next chunk;
the walk ends at the last chunk that holds a prompt's token. So a prompt
may be longer than what one pass's temporaries allow beside the weights;
without an indexer (openPangu's cells) a tile still goes through whole
and its programs are what they were.

Precision follows `cfg.dtype` as the period stack's does: every product
hands back float32, what lies between two products stays float32 and is
rounded to `cfg.dtype` once, where it enters the next product or a
cache; float32 activations against bf16 weights go in as two bf16 terms
(`moe.dot`) and the caches are then float32.

The routed layer holds `cfg.moe_experts` of the `cfg.router_experts` its
router scores, from `cfg.moe_first_expert` on (`moe.held_experts`): on
one chip of a group that shares each layer it computes its own part of
the sum and no exchange runs.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental.layout import Layout, with_layout_constraint

from . import stackparts
# The attention half, which a period stack's latent layer shares.
from .mla import (_attend_rows, _attend_tile, _chosen_rows,  # noqa: F401
                  _heads_dot, _prefill_attend, _project, _scale,
                  attention_half, attention_shapes, cache_lanes, cache_width,
                  init_rows)
# `routing_stats` and `last_logits` are the seam's (`transformer.STACKS`).
from .moe import dot as _dot, routing_stats  # noqa: F401
from .stackparts import (Extras, Group, KVCache, _final,  # noqa: F401
                         _norm, _rope, _swiglu, ffn_half, head_logits, joins,
                         last_logits)
from .transformer import TransformerConfig, rope_tables

# What the dense stack offers and this one does not (`transformer.offered`).
MISSING = {
    "suffix": "prefix sharing (prefill_suffix_*, first_token_suffix_*, "
              "compute_prefix_kv) installs a block of keys and values a "
              "layer; a latent cache has one array of rows (two with an "
              "indexer's keys) and its suffix walk would up-project the "
              "prefix's rows a tile; the chunk walk of a stack with an "
              "indexer already attends rows of the cache it did not write "
              "in this pass, so a registered prefix's rows and keys "
              "copied into a slot and a walk begun at their end is what "
              "is left: not written (models/latent.py)",
    "param_logical_axes": "the latent stack has no sharding rules yet: it "
                          "is served on one chip, which holds its share of "
                          "each layer's experts (models/latent.py)",
    "forward_train": "the latent stack is served only (models/generate.py): "
                     "training lacks a dropless routed layer under autodiff "
                     "and the backward of attention whose values are "
                     "another width than its keys",
}

DENSE, ROUTED = "dense_layers", "routed_layers"


def layer_plan(cfg: TransformerConfig) -> List[Group]:
    """The leading layers, then the routed ones: a layer a scan step."""
    plan = [Group(DENSE, (cfg.n_dense_layers,), False),
            Group(ROUTED, (cfg.n_layers - cfg.n_dense_layers,), cfg.is_moe)]
    return [group for group in plan if group.layers]


def routed_layers(cfg: TransformerConfig) -> int:
    """Layers whose use of their experts `decode` reports."""
    return stackparts.routed_layers(layer_plan(cfg))


def by_products(cfg: TransformerConfig) -> bool:
    return bool(routed_layers(cfg))


# ---------------------------------------------------------------------------
# Weights
# ---------------------------------------------------------------------------


def _layer_shapes(cfg: TransformerConfig, routed: bool
                  ) -> Dict[str, Tuple[int, ...]]:
    d, qr = cfg.d_model, cfg.q_lora_rank
    form = cfg.latent_form
    shapes = {"attn_norm": (d,), **attention_shapes(cfg), "ffn_norm": (d,)}
    if form.post_norms:
        shapes.update(post_attn_norm=(d,), post_ffn_norm=(d,))
    if cfg.index_topk:
        Hi, Di = cfg.index_n_heads, cfg.index_head_dim
        shapes.update(idx_wq=(qr, Hi * Di), idx_wk=(d, Di),
                      idx_k_norm=(Di,), idx_k_bias=(Di,), idx_wp=(d, Hi))
    return {**shapes, **stackparts.ffn_shapes(cfg, routed, form.router_bias)}


def _group_shapes(cfg: TransformerConfig, group: Group):
    return _layer_shapes(cfg, group.routed)


def num_params(cfg: TransformerConfig) -> int:
    return stackparts.num_params(cfg, layer_plan(cfg), _group_shapes)


def init_params(cfg: TransformerConfig, key: jax.Array) -> Dict[str, Any]:
    return stackparts.init_params(cfg, key, layer_plan(cfg), _group_shapes)


def index_dtype(cfg: TransformerConfig):
    """What the indexer and the stream that feeds it are kept in."""
    return jnp.dtype(cfg.index_dtype or cfg.dtype).type


def init_cache(cfg: TransformerConfig, num_slots: int, max_seq_len: int
               ) -> KVCache:
    rows = (cfg.n_layers, num_slots, max_seq_len)
    return KVCache(
        k=None, v=None, seq_lens=jnp.zeros((num_slots,), jnp.int32),
        c=init_rows(cfg, *rows, cfg.dtype),
        ki=jnp.zeros(rows + (cfg.index_head_dim,), index_dtype(cfg))
        if cfg.index_topk else None)


# ---------------------------------------------------------------------------
# The layer
# ---------------------------------------------------------------------------


def _rope_tables(cfg: TransformerConfig, seq_len: int, positions=None):
    """(sin, cos) over the rotary part's width: tables (S, half) for a
    tile, or each slot's row, (B, 1, half), with `positions` (B,)."""
    sin, cos = rope_tables(cfg, seq_len, dim=cfg.qk_rope_head_dim)
    if positions is not None:
        sin, cos = sin[positions][:, None, :], cos[positions][:, None, :]
    return sin, cos


# The indexer key's LayerNorm (guess: the family's published code builds
# it with this epsilon; the catalog row has the RMS norms' alone).
_INDEX_NORM_EPS = 1e-6


def _index_project(cfg: TransformerConfig, lp, h, rope):
    """The indexer's projections of a layer's normed input h (B, S, D)
    float32 -> (q (B, S, Hi, Di) and the key the cache keeps (B, S, Di),
    the first `qk_rope_head_dim` of each rotated, both in
    `index_dtype(cfg)`; w (B, S, Hi) float32, a weight a head a query,
    the two scales folded in). What enters a product is rounded to that
    dtype, as the layer's other products round to the activation dtype:
    in bf16 the query's rank is `_project`'s own, the same expression,
    which the compiler computes once; in float32 every product takes h,
    and the rank made from it once more, as two bf16 terms (`moe.dot`),
    and nothing is rounded on the way to the scores. The key goes through a LayerNorm (mean taken out,
    weight and bias)."""
    B, S, _ = h.shape
    Hi, Di, r = cfg.index_n_heads, cfg.index_head_dim, cfg.qk_rope_head_dim
    it = index_dtype(cfg)
    h = h.astype(it)

    def rotated(x):                                     # (B, S, heads, Di)
        return jnp.concatenate([_rope(x[..., :r], *rope), x[..., r:]],
                               axis=-1)

    c_q = _norm(_dot(h, lp["wq_a"]), lp["q_a_norm"], cfg.norm_eps).astype(it)
    q = rotated(_dot(c_q, lp["idx_wq"]).reshape(B, S, Hi, Di))
    k = _dot(h, lp["idx_wk"])
    k = k - jnp.mean(k, axis=-1, keepdims=True)
    k = k * lax.rsqrt(jnp.mean(k * k, axis=-1, keepdims=True)
                      + _INDEX_NORM_EPS)
    k = k * lp["idx_k_norm"].astype(jnp.float32) \
        + lp["idx_k_bias"].astype(jnp.float32)
    w = _dot(h, lp["idx_wp"]) * (Hi ** -0.5 * Di ** -0.5)
    return q.astype(it), w, rotated(k[:, :, None, :])[:, :, 0].astype(it)


# Rows of a tile that go through the layers together where a stack
# chooses the rows a query attends: a longer bucket is walked a chunk at
# a time against the cache (`prefill`). A chunk's temporaries (its
# scores against every row of the bucket, float32, and the chosen-set
# bias behind them) grow with chunk x bucket: 0.8 GB at 4,096 x 32,768.
PREFILL_CHUNK = 2048
# Queries of a chunk whose scores against the bucket are held at once.
_CHOICE_ROWS = 1024


def chunk_rows(cfg: TransformerConfig, bucket: int) -> int:
    """Rows a chunk of a tile of `bucket` positions: all of them where the
    stack attends every row or the bucket is no longer than a chunk."""
    if cfg.index_topk and bucket > PREFILL_CHUNK \
            and bucket % PREFILL_CHUNK == 0:
        return PREFILL_CHUNK
    return bucket


def _choice_rows(T: int) -> int:
    """Queries of a chunk of T rows that make their choices together:
    blocks of `_CHOICE_ROWS` where T is whole blocks, else all T."""
    return _CHOICE_ROWS if T % _CHOICE_ROWS == 0 else T


def _write_rows(rows_all, l, slots, start, rows):
    """rows (W, T, width) into rows [start, start + T) of layer `l` of
    each of `slots` (W,) of a cache (L, B, S, width); a slot out of
    range (a tile's padding) is dropped. A tile from its first row is
    one scatter of whole runs; a chunk further in (`start` a number the
    device counts) is written a row of the tile at a time, where it
    lies."""
    if isinstance(start, int):
        return rows_all.at[l, slots, start:start + rows.shape[1]].set(
            rows, mode="drop")
    B = rows_all.shape[1]
    for w in range(rows.shape[0]):
        at = (l, slots[w], start, 0)
        old = lax.dynamic_slice(rows_all, at, (1, 1) + rows.shape[1:])
        new = jnp.where((slots[w] >= 0) & (slots[w] < B), rows[w][None, None],
                        old)
        rows_all = lax.dynamic_update_slice(rows_all, new, at)
    return rows_all


def _slot_rows(rows_all, l, slots, start, n: int):
    """Rows [start, start + n) of layer `l` of each of `slots` (W,), from
    a cache (L, B, S, width) -> (W, n, width)."""
    width = rows_all.shape[-1]
    return jax.vmap(lambda s: lax.dynamic_slice(
        rows_all, (l, s, start, 0), (1, 1, n, width))[0, 0])(slots)


def _attend_chunk(cfg: TransformerConfig, slots, start, bucket: int, l, lp,
                  q_nope, q_r, row, idx, state):
    """A chunk of a tile, rows [start, start + T) of a bucket of `bucket`
    positions, against layer `l` of the cache: the chunk's latent rows
    and indexer keys are written to their slots, then each query t
    scores the indexer keys of rows [0, start + T) it may see (s <= t),
    chooses its `index_topk` best, and attends them per head: the rows
    of a chunk of keys at a time are up-projected from the cache (so a
    chunk, the chunks behind it and the decode steps behind those see
    the same rounding) and attended under the chosen sets as a bias, the
    chunks' parts added up by their log-sum-exp. A bucket in one chunk and
    no longer than `index_topk` chooses every row a query sees: the tile
    over itself,
    as a stack without an indexer attends it (`_attend_tile`). `state` as
    `_attend_rows` has it; the chosen sets for whoever asks are (L, W,
    bucket, bucket) bool. -> (out (W, T, H*vd), state)."""
    from ..ops import sparse_attention as sa
    from ..ops.flash_attention import NEG_INF

    c_all, ki_all, picks = state
    W, T, H, _ = q_nope.shape
    dt, kvr = cfg.dtype, cfg.kv_lora_rank
    q_idx, w_idx, key = idx
    rows_major = Layout(major_to_minor=tuple(range(c_all.ndim)))
    with jax.named_scope("attn_index"):
        ki_all = with_layout_constraint(
            _write_rows(ki_all, l, slots, start, key), rows_major)
    with jax.named_scope("attn_sparse"):
        # Rows-major: see `_prefill_attend`.
        c_all = with_layout_constraint(
            _write_rows(c_all, l, slots, start, row), rows_major)
    if bucket <= cfg.index_topk and T == bucket:
        out = _attend_tile(cfg, lp, q_nope, q_r, row, scope="attn_sparse")
        if picks is not None:
            picks = picks.at[l].set(jnp.broadcast_to(
                jnp.tril(jnp.ones((bucket, bucket), bool)), picks.shape[1:]))
        return out, (c_all, ki_all, picks)

    with jax.named_scope("attn_index"):
        keys = _slot_rows(ki_all, l, slots, 0, bucket)

        def choose(part):
            # A block of the chunk's queries: their scores against every
            # row of the bucket, float32, live only until the choice.
            q_part, w_part, first = part
            return sa.topk_bias(
                sa.index_scores_tile(q_part, w_part, keys, first),
                cfg.index_topk, first, dtype=dt)

        rows = _choice_rows(T)
        n = T // rows
        bias = lax.map(choose, (
            jnp.moveaxis(q_idx.reshape((W, n, rows) + q_idx.shape[2:]),
                         1, 0),
            jnp.moveaxis(w_idx.reshape(W, n, rows, -1), 1, 0),
            start + jnp.arange(n) * rows))
        bias = jnp.moveaxis(bias, 0, 1).reshape(W, T, bucket)
        if picks is not None:
            picks = lax.dynamic_update_slice(
                picks, (bias == 0)[None], (l, 0, start, 0))
    with jax.named_scope("attn_sparse"):
        q = jnp.concatenate([q_nope, q_r], axis=-1).astype(dt)

    def one(j, carry):
        out, lse = carry
        rows = _slot_rows(c_all, l, slots, j * T, T)          # (W, T, C)
        with jax.named_scope("mla_proj"):
            c = rows[..., :kvr]
            k_nope = _heads_dot("bsc,hdc->bshd", c, lp["wk_b"]).astype(dt)
            v = _heads_dot("bsc,hcd->bshd", c, lp["wv_b"]).astype(dt)
        with jax.named_scope("attn_sparse"):
            k_r = jnp.broadcast_to(rows[:, :, None, kvr:cache_width(cfg)],
                                   (W, T, H, cfg.qk_rope_head_dim))
            k = jnp.concatenate([k_nope, k_r], axis=-1)
            part, part_lse = sa.masked_attention(
                q, k, v, lax.dynamic_slice_in_dim(bias, j * T, T, 2),
                _scale(cfg))
            return sa.merge_parts(out, lse, part, part_lse)

    out, _ = lax.fori_loop(
        0, start // T + 1, one,
        (jnp.zeros((W, T, H, cfg.v_head_dim), jnp.float32),
         jnp.full((W, T, H), NEG_INF, jnp.float32)))
    return out.reshape(W, T, -1).astype(dt), (c_all, ki_all, picks)


def layer(cfg: TransformerConfig, lp, x, experts_at, rope, attend, state,
          rows=None):
    """One layer on x (B, S, D) in the activation dtype. `attend(lp,
    q_nope, q_r, row, idx, state) -> (out (B, S, H*vd), state)` does the
    attention and whatever it keeps of the row; `idx` is what the
    layer's indexer projects (`_index_project`), None without one.
    `experts_at`, `rows`: as `ffn_half` takes them. A branch joins the
    residual stream through a norm of its own where the architecture has
    one (`LatentForm.post_norms`). Returns (x, state, routing stats and
    experts chosen (B*S, K), both None for a dense FFN)."""
    post_norms = cfg.latent_form.post_norms

    index = None
    if cfg.index_topk:
        def index(h):
            with jax.named_scope("attn_index"):
                return _index_project(cfg, lp, h, rope)
    branch, state = attention_half(cfg, lp, x, rope, attend, state, index)
    with jax.named_scope("mla_proj"):
        x = joins(x, branch, lp["post_attn_norm"] if post_norms else None,
                  cfg.norm_eps)
    x, stats, experts = ffn_half(cfg, lp, x, experts_at, post_norms, rows)
    return x, state, stats, experts


def _run(cfg: TransformerConfig, params, x, rope, attend, state, rows=None):
    """`stackparts.run` over the plan. `attend(l, lp, q_nope, q_r, row,
    idx, state)` is told which layer it serves."""
    plan = layer_plan(cfg)
    base = [sum(group.layers for group in plan[:i])
            for i in range(len(plan))]

    def layer_at(i, g, j):
        return lambda lp, x, experts_at, state: layer(
            cfg, lp, x, experts_at, rope, partial(attend, base[i] + g),
            state, rows)

    return stackparts.run(cfg, params, plan, x, layer_at, state)


def _embed(cfg: TransformerConfig, params, tokens):
    """The residual stream's first value, in the dtype it is carried in
    between the layers: the activation dtype, or the indexer's where a
    stack has one (what enters a product is rounded to the activation
    dtype either way)."""
    return params["embed"][tokens].astype(
        index_dtype(cfg) if cfg.index_topk else cfg.dtype)


# ---------------------------------------------------------------------------
# What generate.py's programs call
# ---------------------------------------------------------------------------


def _walk(cfg: TransformerConfig, params, state, tokens, lengths, slots,
          whole: bool = False):
    """A tile of a stack with an indexer: tokens (W, S) through the layers
    against `state` (`_attend_rows`'s) -> (final-normed hidden states (W,
    S, D), state, routing stats, experts chosen or ()). A bucket longer
    than a chunk (`chunk_rows`) is walked a chunk at a time inside this
    one program, the caches in the loop's carry: a chunk's rows go
    through every layer, each layer writing them and attending the
    cache, before the next chunk's; the walk ends at the last chunk that
    holds a token of any prompt (`lengths` (W,); None: every chunk), so a
    padding chunk is not run: its hidden states stay zero and its
    positions are in no routing count. `whole`: the tile in one chunk
    whatever its length (for `chosen_experts`, which wants every
    position's)."""
    W, S = tokens.shape
    rope = _rope_tables(cfg, S)
    T = S if whole else chunk_rows(cfg, S)
    if T == S:
        x, state, stats, chosen = _run(
            cfg, params, _embed(cfg, params, tokens), rope,
            partial(_attend_chunk, cfg, slots, 0, S), state)
        return _final(cfg, params, x), state, stats, chosen

    def chunk(i, carry):
        state, out, stats = carry
        start = i * T
        x, state, st, _ = _run(
            cfg, params,
            _embed(cfg, params, lax.dynamic_slice_in_dim(tokens, start, T, 1)),
            tuple(lax.dynamic_slice_in_dim(t, start, T, 0) for t in rope),
            partial(_attend_chunk, cfg, slots, start, S), state)
        out = lax.dynamic_update_slice_in_dim(out, _final(cfg, params, x),
                                              start, 1)
        return state, out, stats + st

    run = S // T if lengths is None else jnp.clip(
        (jnp.max(lengths) + T - 1) // T, 1, S // T)
    state, out, stats = lax.fori_loop(
        0, run, chunk,
        (state, jnp.zeros((W, S, cfg.d_model), cfg.dtype),
         jnp.zeros((routing_stats(cfg),), jnp.int32)))
    return out, state, stats, ()


def prefill_chunks(cfg: TransformerConfig, bucket: int, length: int
                   ) -> Tuple[int, int]:
    """(chunks a tile of `bucket` positions runs for a prompt of `length`
    tokens, chunks the bucket has): `_walk`'s count, for the host."""
    T = chunk_rows(cfg, bucket)
    return min(max(-(-length // T), 1), bucket // T), bucket // T


def choice_columns(cfg: TransformerConfig, bucket: int, length: int
                   ) -> Tuple[int, int]:
    """(columns the choices of such a tile count a layer a row, columns
    its bucket spans for the same blocks of queries): what `topk_bias`
    is told of where each block stands (`_attend_chunk`), for the host.
    A tile that chooses every row it sees counts nothing."""
    from ..ops import sparse_attention as sa

    T = chunk_rows(cfg, bucket)
    if bucket <= cfg.index_topk and T == bucket:
        return 0, 0
    rows = _choice_rows(T)
    # The chunks run abut: their blocks of queries are those of one run.
    firsts = np.arange(0, prefill_chunks(cfg, bucket, length)[0] * T, rows)
    counted = np.minimum(sa.columns_counted(firsts, rows, bucket), bucket)
    return int(counted.sum()), len(firsts) * bucket


# What the engine counts of this stack, on the host (`stackparts.counters`;
# docs/METRICS.md says what each counter means).

def counters(cfg: TransformerConfig) -> Dict[str, Any]:
    found = stackparts.routing_counters(routed_layers(cfg))
    if cfg.index_topk:
        found.update(sparse_rows_read=0, prefill_chunks=0,
                     prefill_chunks_of=0, choice_columns=0,
                     choice_columns_of=0)
    return found


def tile_counts(cfg: TransformerConfig, bucket: int, lengths, tokens: int):
    """A tile walked a chunk at a time: the chunks it runs, to the longest
    row's last token, of those its bucket has (a span's `chunks` of
    `chunks_of`; the counters, which hold blocks' numbers beside,
    `prefill_chunks` of `prefill_chunks_of`), and the columns its blocks
    of queries count to choose their rows, of those the bucket spans."""
    if not cfg.index_topk:
        return {}, {}
    run, of = prefill_chunks(cfg, bucket, max(lengths))
    cols, cols_of = choice_columns(cfg, bucket, max(lengths))
    columns = dict(choice_columns=cols, choice_columns_of=cols_of)
    return dict(prefill_chunks=run, prefill_chunks_of=of, **columns), \
        dict(chunks=run, chunks_of=of, **columns)


def block_counts(cfg: TransformerConfig, k: int, num_slots: int,
                 max_seq_len: int, first_rows, held: int):
    """The latent rows the owned slots' attention is to read over the
    block: of the rows a slot holds at a step `index_topk`, all of them
    while it holds no more. What the program was asked for, not what it
    was seen to do."""
    if not cfg.index_topk:
        return {}, {}
    cap = min(cfg.index_topk, max_seq_len)
    found = dict(sparse_rows_read=sum(
        min(first + t, cap) for first in first_rows for t in range(k)))
    return found, found


def result_counts(cfg: TransformerConfig, k: int, extras: Extras, taken):
    found = stackparts.routing_counts(cfg, routed_layers(cfg), k,
                                      extras.routing)
    return found, found


def prefill(cfg: TransformerConfig, params, cache: KVCache, tokens, lengths,
            slots) -> Tuple[KVCache, jax.Array, Extras]:
    """tokens (W, S) into the slots' cache rows -> (cache', final-normed
    hidden states (W, S, D), `Extras`: routing stats of the tile as
    `decode` gives a step's, over all W x S positions, padding too; None
    with no routed layer). Without an indexer the tile goes through whole
    and attends itself; with one, `_walk` (a chunk that is not run
    counts nothing)."""
    if cfg.index_topk:
        x, (c_all, ki_all, _), stats, _ = _walk(
            cfg, params, (cache.c, cache.ki, None), tokens, lengths, slots)
        seq_lens = cache.seq_lens.at[slots].set(lengths, mode="drop")
        return cache._replace(c=c_all, ki=ki_all, seq_lens=seq_lens), x, \
            Extras(stats if routed_layers(cfg) else None)
    rope = _rope_tables(cfg, tokens.shape[1])
    x, (c_all, _, _), stats, _ = _run(
        cfg, params, _embed(cfg, params, tokens), rope,
        partial(_prefill_attend, cfg, slots), (cache.c, None, None))
    seq_lens = cache.seq_lens.at[slots].set(lengths, mode="drop")
    return cache._replace(c=c_all, seq_lens=seq_lens), \
        _final(cfg, params, x), Extras(stats if routed_layers(cfg) else None)


def _free_attend(cfg, l, lp, q_nope, q_r, row, idx, state):
    return _attend_tile(cfg, lp, q_nope, q_r, row), state


def _scratch(cfg: TransformerConfig, W: int, S: int):
    """A cache of W slots x S rows that lives as long as one program."""
    cache = init_cache(cfg, W, S)
    return cache.c, cache.ki


def forward_free(cfg: TransformerConfig, params, tokens, whole: bool = False):
    """tokens (W, S) with no cache -> (final-normed hidden states (W, S,
    D), the experts every routed layer chose: see `stackparts.run`; none
    where the tile is walked in chunks, unless `whole`; `Extras` with
    nothing in it). A stack that chooses its rows walks the tile against
    a cache of its own, one slot a row."""
    if cfg.index_topk:
        W, S = tokens.shape
        x, _, _, chosen = _walk(cfg, params, _scratch(cfg, W, S) + (None,),
                                tokens, None, jnp.arange(W), whole)
        return x, chosen, Extras()
    rope = _rope_tables(cfg, tokens.shape[1])
    x, _, _, chosen = _run(cfg, params, _embed(cfg, params, tokens), rope,
                           partial(_free_attend, cfg), None)
    return _final(cfg, params, x), chosen, Extras()


def _decode(cfg: TransformerConfig, params, cache: KVCache, tokens, live,
            picks=None):
    positions = cache.seq_lens
    rope = _rope_tables(cfg, cache.max_seq_len, positions)
    x, (c_all, ki_all, picks), stats, _ = _run(
        cfg, params, _embed(cfg, params, tokens)[:, None, :], rope,
        partial(_attend_rows, cfg, positions, live),
        (cache.c, cache.ki, picks), live)
    cache = cache._replace(c=c_all, ki=ki_all, seq_lens=positions + 1)
    return cache, head_logits(cfg, params, _final(cfg, params, x)[:, 0]), \
        Extras(stats if routed_layers(cfg) else None), picks


def decode(cfg: TransformerConfig, params, cache: KVCache, tokens,
           live=None) -> Tuple[KVCache, jax.Array, Extras]:
    """One token a slot -> (cache', logits (B, V), `Extras`: routing stats
    of the step (`moe.routed_ffn`'s, summed over the routed layers): held
    experts that took a row, pairs kept, the pairs of the held expert
    most chosen, rows the experts took and, where the layer holds a
    share, the pairs routed; None with no routed layer). `live` (B,)
    bool: the slots a request owns (None: every one): any other slot
    reads and writes no cache row and its token meets no expert."""
    return _decode(cfg, params, cache, tokens, live)[:3]


def chosen_experts(cfg: TransformerConfig, params, tokens) -> List[jax.Array]:
    """For tests and for telling a routing flip from arithmetic: the
    experts each routed layer chose for tokens (S,), in layer order, each
    (S, K), numbered as the router numbers them."""
    _, chosen, _ = jax.jit(partial(forward_free, cfg, whole=True))(
        params, jnp.asarray(tokens, jnp.int32)[None])
    return stackparts.chosen_by_layer(chosen)


def chosen_rows(cfg: TransformerConfig, params, tokens) -> jax.Array:
    """For tests and for telling a flip of the indexer's choice from
    arithmetic: the rows each layer's queries chose for tokens (S,), as a
    tile chooses them -> bool (L, S, S): [l, t, s] says that query t of
    layer l attends row s."""
    S = len(tokens)

    def walk(params, tokens):
        picks = jnp.zeros((cfg.n_layers, 1, S, S), bool)
        _, (_, _, picks), _, _ = _walk(
            cfg, params, _scratch(cfg, 1, S) + (picks,), tokens, None,
            jnp.arange(1))
        return picks[:, 0]

    return jax.jit(walk)(params, jnp.asarray(tokens, jnp.int32)[None])


def decode_chosen_rows(cfg: TransformerConfig, params, cache: KVCache,
                       tokens, live=None):
    """`decode` that also says which rows each layer's step chose ->
    (cache', logits, rows (L, B, k) int32, best first: of a slot's k the
    first min(rows it holds, k) count)."""
    k = min(cfg.index_topk, cache.max_seq_len)
    picks = jnp.zeros((cfg.n_layers, cache.num_slots, k), jnp.int32)
    cache, logits, _, picks = _decode(cfg, params, cache, tokens, live,
                                      picks)
    return cache, logits, picks
