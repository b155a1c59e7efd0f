"""Multi-head latent attention (arXiv:2405.04434 section 2.1), the
attention half alone: what the latent stack (`models/latent.py`) and a
period stack whose global layer is latent (`models/periodic.py`,
`PeriodForm.latent`) both build their layer from. Below the stacks: this
module imports no stack and not `generate.py`.

A token's keys and values are one vector, `c = N(x W_kva)[:kv_lora_rank]`,
from which every head's key part without position and its value are
products (`wk_b` (H, nope, rank), `wv_b` (H, rank, vd): the two halves of
the published `kv_b_proj`, kept apart and a head at a time), and one
further key part `k_r` that all heads share: rotated where the layer has
a position (`rope` given), as it stands where it has none. The cache
holds `[c | k_r]`, `cache_width` values a token a layer in whole lanes of
128 (`cache_lanes`, `init_rows`). Queries go through a rank of their own
where the configuration has one (`q_lora_rank`: `wq_a`, a norm), else
straight from the layer's normed input, to `[q_nope | q_r]` a head
(`attention_shapes`, `_project`).

The same products in two orders: a tile up-projects rows and attends per
head (`_attend_tile`, `_prefill_attend`: scores `(q_nope . k_nope + q_r .
k_r) / sqrt(nope + rope)`, values `v_head_dim` wide); a decode step never
up-projects a cached row: `W_UK` goes into the query and `W_UV` into the
output, and the step attends as one key head of C under all the query
heads (`_attend_rows`: `ops/decode_attention` with one array), over every
held row or, handed an indexer's projections, over the rows it chooses
(`_chosen_rows`). `attention_half` is the half whole: norm, projections,
the caller's `attend`, `wo`.
"""

from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental.layout import Layout, with_layout_constraint

from .moe import _exact, _split, bf16_terms, dot as _dot
from .stackparts import _norm, _rope, masked_softmax, rows_held
from .transformer import TransformerConfig


def cache_width(cfg: TransformerConfig) -> int:
    """Values a token a layer keeps: the latent vector and the rotary key."""
    return cfg.kv_lora_rank + cfg.qk_rope_head_dim


def cache_lanes(cfg: TransformerConfig) -> int:
    """A cached row's width: `cache_width` in whole lanes of 128."""
    return -(-cache_width(cfg) // 128) * 128


def attention_shapes(cfg: TransformerConfig) -> Dict[str, Tuple[int, ...]]:
    """The attention half's leaves (`attention_half`). The query comes
    through a rank of its own where the configuration has one
    (`q_lora_rank`: `wq_a`, a norm, then `wq_nope` / `wq_rope` from the
    rank), else straight from the layer's input."""
    d, H = cfg.d_model, cfg.n_heads
    qr, kvr = cfg.q_lora_rank, cfg.kv_lora_rank
    nope, rope, vd = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                      cfg.v_head_dim)
    low_rank = {"wq_a": (d, qr), "q_a_norm": (qr,)} if qr else {}
    return {**low_rank,
            "wq_nope": (qr or d, H * nope), "wq_rope": (qr or d, H * rope),
            "wkv_a": (d, kvr + rope), "kv_a_norm": (kvr,),
            "wk_b": (H, nope, kvr), "wv_b": (H, kvr, vd), "wo": (H * vd, d)}


def init_rows(cfg: TransformerConfig, layers: int, num_slots: int,
              max_seq_len: int, dtype) -> jax.Array:
    """The latent rows of `layers` layers, zero: (layers, slots, S_max,
    `cache_lanes`)."""
    return jnp.zeros((layers, num_slots, max_seq_len, cache_lanes(cfg)),
                     dtype)


def _heads_dot(eq: str, x: jax.Array, w: jax.Array) -> jax.Array:
    """`jnp.einsum(eq, x, w)` of an activation against a weight seen a
    head at a time, float32 out; float32 x against a bf16 w as two bf16
    terms, as `moe.dot` takes them."""
    from ..ops.flash_attention import on_tpu

    two = _split(x, w)
    if two:
        ins, out = eq.split("->")
        x, eq = bf16_terms(x), f"t{ins}->t{out}"
    if x.dtype == jnp.bfloat16 and not on_tpu():
        # A CPU has no bf16 x bf16 -> float32 product over a batch of
        # heads. The products of bf16 operands are exact in float32, so
        # this is the same sum.
        x, w = x.astype(jnp.float32), w.astype(jnp.float32)
    y = jnp.einsum(eq, x, w.astype(x.dtype), precision=_exact(x),
                   preferred_element_type=jnp.float32)
    return y[0] + y[1] if two else y


def _project(cfg: TransformerConfig, lp, x, rope):
    """x (B, S, D) -> (q_nope (B, S, H, nope) float32, q_r (B, S, H,
    rope) float32 and rotated, the row the cache keeps (B, S, C) in the
    activation dtype: the latent vector after its norm, the rotary key
    after its rotation, zeros up to whole lanes; and the layer's normed
    input (B, S, D) float32, before it is rounded for the products).
    `rope` None: nothing is rotated (a layer with no position: the
    "rotary" values are so many more that all heads' keys share). The
    query goes through its rank and that rank's norm where the
    configuration has one (`attention_shapes`)."""
    B, S, _ = x.shape
    H, dt, eps = cfg.n_heads, cfg.dtype, cfg.norm_eps
    kvr = cfg.kv_lora_rank

    def rotated(a):
        return a if rope is None else _rope(a, *rope)

    h32 = _norm(x, lp["attn_norm"], eps)
    h = c_q = h32.astype(dt)
    if cfg.q_lora_rank:
        c_q = _norm(_dot(h, lp["wq_a"]), lp["q_a_norm"], eps).astype(dt)
    q_nope = _dot(c_q, lp["wq_nope"]).reshape(B, S, H, -1)
    q_r = _dot(c_q, lp["wq_rope"]).reshape(B, S, H, -1)
    kv = _dot(h, lp["wkv_a"])                    # (B, S, rank + rope) float32
    c = _norm(kv[..., :kvr], lp["kv_a_norm"], eps)
    k_r = rotated(kv[..., None, kvr:])[:, :, 0]         # one head, shared
    row = jnp.concatenate([c, k_r], axis=-1).astype(dt)
    row = jnp.pad(row, ((0, 0), (0, 0),
                        (0, cache_lanes(cfg) - cache_width(cfg))))
    return q_nope, rotated(q_r), row, h32


def _scale(cfg: TransformerConfig) -> float:
    return 1.0 / math.sqrt(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)


_QUERY_BLOCK = 256


def _attention_f32(q, k, v, sm_scale: float):
    """Causal attention of float32 q, k (B, S, H, Dk) and v (B, S, H, Dv),
    both products at the highest precision, a block of queries at a time
    (the scores held are (B, H, block, S))."""
    B, S, H, _ = q.shape
    blk = _QUERY_BLOCK if S % _QUERY_BLOCK == 0 else S
    hi = lax.Precision.HIGHEST
    j = jnp.arange(S)[None, :]

    def block(args):
        qs, start = args                                # (B, blk, H, Dk)
        s = jnp.einsum("bqhd,bshd->bhqs", qs, k, precision=hi) * sm_scale
        seen = j <= start + jnp.arange(blk)[:, None]
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("bhqs,bshd->bqhd", p, v, precision=hi)

    out = lax.map(block, (jnp.moveaxis(
        q.reshape(B, S // blk, blk, H, -1), 1, 0),
        jnp.arange(S // blk) * blk))
    return jnp.moveaxis(out, 0, 1).reshape(B, S, H, -1)


def _attend_tile(cfg: TransformerConfig, lp, q_nope, q_r, row,
                 scope: str = "attn_latent"):
    """A tile over itself, per head: the rows' keys and values
    up-projected from what the cache keeps of them (so a tile and the
    decode steps behind it see the same rounding), causal attention with
    keys nope + rope wide and values `v_head_dim` wide. -> (B, S, H*vd)."""
    B, S, H, _ = q_nope.shape
    dt, kvr = cfg.dtype, cfg.kv_lora_rank
    with jax.named_scope("mla_proj"):
        c = row[..., :kvr]
        k_nope = _heads_dot("bsc,hdc->bshd", c, lp["wk_b"]).astype(dt)
        v = _heads_dot("bsc,hcd->bshd", c, lp["wv_b"]).astype(dt)
    with jax.named_scope(scope):
        k_r = jnp.broadcast_to(row[:, :, None, kvr:cache_width(cfg)],
                               (B, S, H, cfg.qk_rope_head_dim))
        q = jnp.concatenate([q_nope, q_r], axis=-1).astype(dt)
        k = jnp.concatenate([k_nope, k_r], axis=-1)
        if dt == jnp.float32:
            out = _attention_f32(q, k, v, _scale(cfg))
        else:
            from ..ops import flash_attention
            out = flash_attention(q, k, v, causal=True, sm_scale=_scale(cfg))
    return out.reshape(B, S, -1)


def _attend_rows(cfg: TransformerConfig, positions, live, l, lp, q_nope,
                 q_r, row, idx, state):
    """One token a slot against layer `l` of the carried cache (L, B, S,
    C), in the latent space: this step's row is written at `positions`,
    `W_UK` goes into the query and `W_UV` onto the weighted rows, and
    every held row is read once, for scores and values together. With an
    indexer (`idx`: `_index_project`'s three) the step's indexer key is
    written beside the row, the slot's held keys are scored, and only the
    `index_topk` rows of largest score are gathered and attended
    (`_chosen_rows`): no other latent row is read. `state`: (the latent
    cache, the indexer's or None, the chosen rows a layer (L, B, k) for
    whoever asks or None). -> (out (B, 1, H*vd), state)."""
    from ..ops import decode_attention as da

    c_all, ki_all, picks = state
    B, S, C = c_all.shape[1:]
    H, dt, kvr = cfg.n_heads, cfg.dtype, cfg.kv_lora_rank
    # Rows of another dtype than the activations' (a period stack's
    # `TransformerConfig.cache_dtype`): the step attends in theirs.
    ct = c_all.dtype
    with jax.named_scope("mla_proj"):
        q_lat = _heads_dot("bhd,hdc->bhc", q_nope[:, 0].astype(dt),
                           lp["wk_b"])
        q = jnp.concatenate([q_lat, q_r[:, 0]], axis=-1).astype(ct)
        q = jnp.pad(q, ((0, 0), (0, 0), (0, C - q.shape[-1])))
    if idx is not None:
        with jax.named_scope("attn_index"):
            n_rows = rows_held(positions, S, live)
            ki_all, chosen = _chosen_rows(cfg, positions, l, idx, ki_all,
                                          n_rows)
            if picks is not None:
                picks = picks.at[l].set(chosen)
    with jax.named_scope("attn_latent" if idx is None else "attn_sparse"):
        # A slot the engine no longer owns keeps advancing and can reach
        # S: its write falls out of bounds and is dropped.
        c_all = c_all.at[l, jnp.arange(B), positions].set(
            row[:, 0].astype(ct), mode="drop")
        if idx is not None:
            # The chosen rows alone, gathered: the best first, so the
            # rows that count are the first `n_rows` of them where a
            # slot holds fewer than it may choose.
            rows_all = c_all[l, jnp.arange(B)[:, None], chosen][None]
            n_rows = jnp.minimum(n_rows, chosen.shape[1])
            at = jnp.int32(0)
        else:
            rows_all, at = c_all, l
            n_rows = rows_held(positions, S, live)
        if da.usable(rows_all, C, kvr):
            o_lat = da.decode_attention(
                q[:, None], rows_all, None, at, n_rows, sm_scale=_scale(cfg),
                v_width=kvr).reshape(B, H, kvr)
        else:
            rows = lax.dynamic_index_in_dim(rows_all, at, 0, keepdims=False)
            hi = _exact(q)
            scores = jnp.einsum("bhc,bsc->bhs", q, rows, precision=hi,
                                preferred_element_type=jnp.float32)
            probs = masked_softmax((scores * _scale(cfg))[:, None], n_rows,
                                   live)[:, 0].astype(rows.dtype)
            o_lat = jnp.einsum("bhs,bsc->bhc", probs, rows[..., :kvr],
                               precision=hi,
                               preferred_element_type=jnp.float32)
    with jax.named_scope("mla_proj"):
        out = _heads_dot("bhc,hcd->bhd", o_lat.astype(dt), lp["wv_b"])
    return out.reshape(B, 1, -1).astype(dt), (c_all, ki_all, picks)


def _chosen_rows(cfg: TransformerConfig, positions, l, idx, ki_all, n_rows):
    """A decode step's choice: the step's indexer key into layer `l` of
    the indexer's cache (L, B, S, Di) at `positions`, every key a slot
    holds scored against the step's indexer query (`n_rows` (B,) of
    them), and the `index_topk` rows of largest score, exactly
    (`lax.top_k`: ties to the lower row), best first: a row past
    `n_rows` scores `-inf` and comes last. -> (ki_all, rows (B, k))."""
    from ..ops import sparse_attention as sa

    q, w, key = idx
    B, S = ki_all.shape[1:3]
    ki_all = ki_all.at[l, jnp.arange(B), positions].set(key[:, 0],
                                                         mode="drop")
    scores = sa.index_scores_rows(q[:, 0], w[:, 0], ki_all, l, n_rows)
    _, chosen = lax.top_k(scores, min(cfg.index_topk, S))
    return ki_all, chosen


def attention_half(cfg: TransformerConfig, lp, x, rope, attend, state,
                   index=None):
    """The attention half of a layer on the residual stream x (B, S, D)
    -> (the branch (B, S, D) float32, before it joins x; state): the
    layer's first norm, the projections (`_project`; `rope`: the rotary
    part's tables, None where nothing is rotated), `attend(lp, q_nope,
    q_r, row, idx, state) -> (out (B, S, H*vd), state)`, which does the
    attention and whatever it keeps of the row, and `wo`. `index`: what a
    stack with an indexer projects of the layer's normed input for it
    (`latent._index_project`), handed to `attend` as `idx`; None without
    one. What the latent stack's `layer` and a period stack whose global
    layer is latent (`PeriodForm.latent`) both call."""
    with jax.named_scope("mla_proj"):
        q_nope, q_r, row, h = _project(cfg, lp, x, rope)
    idx = None if index is None else index(h)
    out, state = attend(lp, q_nope, q_r, row, idx, state)
    with jax.named_scope("mla_proj"):
        return _dot(out, lp["wo"]), state


def _prefill_attend(cfg, slots, l, lp, q_nope, q_r, row, idx, state):
    c_all, ki_all, picks = state
    out = _attend_tile(cfg, lp, q_nope, q_r, row)
    with jax.named_scope("attn_latent"):
        # The tile's rows into each row's slot, [0, S); a slot out of
        # range (the tile's padding) is dropped.
        c_all = c_all.at[l, slots, :row.shape[1]].set(
            row.astype(c_all.dtype), mode="drop")
        # Rows-major, as the cache arrives and as the decode kernel reads
        # it. Left to itself the compiler lays the carried cache out
        # rows-minor for this write (the tile's rows come off a product
        # that way) and copies all of it in and out of the program.
        c_all = with_layout_constraint(
            c_all, Layout(major_to_minor=tuple(range(c_all.ndim))))
    return out, (c_all, ki_all, picks)
