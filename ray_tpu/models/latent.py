"""The latent-attention stack (multi-head latent attention, arXiv:2405.04434
section 2.1, with the routed layer of `models/moe.py`), served through the
programs of `generate.py`.

`n_dense_layers` leading layers with a dense SwiGLU, then layers of routed
experts beside always-on shared ones. Four RMS norms a layer: before the
attention and before the FFN, and on each branch's output before it joins
the residual stream.

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
and `wq_rope`, the published `q_b_proj`'s columns by what they make) to
`[q_nope | q_r]` a head.

The same products in two orders:

- a tile (`prefill`, `forward_free`) up-projects the tile's own rows and
  attends per head: scores `(q_nope . k_nope + q_r . k_r) / sqrt(nope +
  rope)` over keys `nope + rope` wide, values `v_head_dim` wide (the
  flash kernel takes values of another width than the keys);
- a decode step never up-projects a cached row. `W_UK` goes into the
  query (`q_nope W_UK^T`, `kv_lora_rank` wide a head) and `W_UV` into the
  output, so the step attends as one key head of C under all the query
  heads, whose first `kv_lora_rank` columns are also the values:
  `ops/decode_attention` with one array, each held row read once.

Precision follows `cfg.dtype` as the period stack's does: every product
hands back float32, what lies between two products stays float32 and is
rounded to `cfg.dtype` once, where it enters the next product or the
cache; float32 activations against bf16 weights go in as two bf16 terms
(`moe.dot`) and the cache is then float32.

The routed layer holds `cfg.moe_experts` of the `cfg.router_experts` its
router scores, from `cfg.moe_first_expert` on (`moe.held_experts`): on
one chip of a group that shares each layer it computes its own part of
the sum and no exchange runs.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental.layout import Layout, with_layout_constraint

from .generate import KVCache, _rope, masked_softmax, rows_held
from .moe import EXPERT_LEAVES, _exact, _split, bf16_terms, dot as _dot, \
    routed_ffn, routing_stats  # noqa: F401 (routing_stats: the seam's)
from .periodic import _norm, _swiglu, head_logits, last_logits  # noqa: F401
from .transformer import TransformerConfig, rope_tables

# What the dense stack offers and this one does not (`transformer.offered`).
MISSING = {
    "suffix": "prefix sharing (prefill_suffix_*, first_token_suffix_*, "
              "compute_prefix_kv) installs a block of keys and values a "
              "layer; a latent cache has one array of rows and its suffix "
              "walk would up-project the prefix's rows a tile: not written "
              "(models/latent.py)",
    "param_logical_axes": "the latent stack has no sharding rules yet: it "
                          "is served on one chip, which holds its share of "
                          "each layer's experts (models/latent.py)",
    "forward_train": "the latent stack is served only (models/generate.py): "
                     "training lacks a dropless routed layer under autodiff "
                     "and the backward of attention whose values are "
                     "another width than its keys",
}

DENSE, ROUTED = "dense_layers", "routed_layers"


def layer_plan(cfg: TransformerConfig) -> List[Tuple[str, int, bool]]:
    """[(weights' key, layers, routed)]."""
    plan = [(DENSE, cfg.n_dense_layers, False),
            (ROUTED, cfg.n_layers - cfg.n_dense_layers, cfg.is_moe)]
    return [p for p in plan if p[1]]


def routed_layers(cfg: TransformerConfig) -> int:
    """Layers whose use of their experts `decode` reports."""
    return sum(n for _, n, routed in layer_plan(cfg) if routed)


def cache_width(cfg: TransformerConfig) -> int:
    """Values a token a layer keeps: the latent vector and the rotary key."""
    return cfg.kv_lora_rank + cfg.qk_rope_head_dim


def cache_lanes(cfg: TransformerConfig) -> int:
    """A cached row's width: `cache_width` in whole lanes of 128."""
    return -(-cache_width(cfg) // 128) * 128


# ---------------------------------------------------------------------------
# Weights
# ---------------------------------------------------------------------------

def _layer_shapes(cfg: TransformerConfig, routed: bool
                  ) -> Dict[str, Tuple[int, ...]]:
    d, H = cfg.d_model, cfg.n_heads
    qr, kvr = cfg.q_lora_rank, cfg.kv_lora_rank
    nope, rope, vd = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                      cfg.v_head_dim)
    shapes = {
        "attn_norm": (d,), "wq_a": (d, qr), "q_a_norm": (qr,),
        "wq_nope": (qr, H * nope), "wq_rope": (qr, H * rope),
        "wkv_a": (d, kvr + rope), "kv_a_norm": (kvr,),
        "wk_b": (H, nope, kvr), "wv_b": (H, kvr, vd),
        "wo": (H * vd, d), "post_attn_norm": (d,), "ffn_norm": (d,),
        "post_ffn_norm": (d,),
    }
    if not routed:
        f = cfg.d_ff
        shapes.update(w_gate=(d, f), w_up=(d, f), w_down=(f, d))
        return shapes
    E, f = cfg.moe_experts, cfg.expert_d_ff
    shapes.update(router=(d, cfg.router_experts), w_gate=(E, d, f),
                  w_up=(E, d, f), w_down=(E, f, d))
    if cfg.moe_shared_experts:
        fs = f * cfg.moe_shared_experts
        shapes.update(shared_gate=(d, fs), shared_up=(d, fs),
                      shared_down=(fs, d))
    return shapes


def num_params(cfg: TransformerConfig) -> int:
    total = cfg.vocab_size * cfg.d_model * (1 if cfg.tie_embeddings else 2) \
        + cfg.d_model
    for _, n, routed in layer_plan(cfg):
        total += n * sum(math.prod(s)
                         for s in _layer_shapes(cfg, routed).values())
    return total


def init_params(cfg: TransformerConfig, key: jax.Array) -> Dict[str, Any]:
    """Scaled-normal weights as `periodic.init_params` makes them: norm
    gains one, residual-branch outputs scaled down by depth, each leaf
    drawn, scaled and cast in one expression."""
    pd = cfg.param_dtype
    k_emb, k_head, k_layers = jax.random.split(key, 3)

    def normal(key, shape, scale):
        return (jax.random.normal(key, shape, dtype=jnp.float32)
                * scale).astype(pd)

    d = cfg.d_model
    params = {"embed": normal(k_emb, (cfg.vocab_size, d), 0.02),
              "final_norm": jnp.ones((d,), dtype=pd)}
    if not cfg.tie_embeddings:
        params["lm_head"] = normal(k_head, (d, cfg.vocab_size), 0.02)
    plan = layer_plan(cfg)
    for (name, n, routed), k_group in zip(
            plan, jax.random.split(k_layers, len(plan))):
        shapes = _layer_shapes(cfg, routed)
        leaves = {}
        for (leaf, shape), k in zip(
                sorted(shapes.items()),
                jax.random.split(k_group, len(shapes))):
            full = (n,) + shape
            if leaf.endswith("norm"):
                leaves[leaf] = jnp.ones(full, dtype=pd)
            elif leaf in ("wo", "w_down", "shared_down"):
                leaves[leaf] = normal(
                    k, full, 0.02 / math.sqrt(2 * cfg.n_layers))
            else:
                leaves[leaf] = normal(k, full, 0.02)
        params[name] = leaves
    return params


def init_cache(cfg: TransformerConfig, num_slots: int, max_seq_len: int
               ) -> KVCache:
    return KVCache(
        k=None, v=None, seq_lens=jnp.zeros((num_slots,), jnp.int32),
        c=jnp.zeros((cfg.n_layers, num_slots, max_seq_len,
                     cache_lanes(cfg)), cfg.dtype))


# ---------------------------------------------------------------------------
# The layer
# ---------------------------------------------------------------------------

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


def _rope_tables(cfg: TransformerConfig, seq_len: int, positions=None):
    """(sin, cos) over the rotary part's width: tables (S, half) for a
    tile, or each slot's row, (B, 1, half), with `positions` (B,)."""
    sin, cos = rope_tables(cfg, seq_len, dim=cfg.qk_rope_head_dim)
    if positions is not None:
        sin, cos = sin[positions][:, None, :], cos[positions][:, None, :]
    return sin, cos


def _project(cfg: TransformerConfig, lp, x, rope):
    """x (B, S, D) -> (q_nope (B, S, H, nope) float32, q_r (B, S, H,
    rope) float32 and rotated, the row the cache keeps (B, S, C) in the
    activation dtype: the latent vector after its norm, the rotary key
    after its rotation, zeros up to whole lanes)."""
    B, S, _ = x.shape
    H, dt, eps = cfg.n_heads, cfg.dtype, cfg.norm_eps
    kvr = cfg.kv_lora_rank
    h = _norm(x, lp["attn_norm"], eps).astype(dt)
    c_q = _norm(_dot(h, lp["wq_a"]), lp["q_a_norm"], eps).astype(dt)
    q_nope = _dot(c_q, lp["wq_nope"]).reshape(B, S, H, -1)
    q_r = _dot(c_q, lp["wq_rope"]).reshape(B, S, H, -1)
    kv = _dot(h, lp["wkv_a"])                    # (B, S, rank + rope) float32
    c = _norm(kv[..., :kvr], lp["kv_a_norm"], eps)
    k_r = _rope(kv[..., None, kvr:], *rope)[:, :, 0]    # one head, shared
    row = jnp.concatenate([c, k_r], axis=-1).astype(dt)
    row = jnp.pad(row, ((0, 0), (0, 0),
                        (0, cache_lanes(cfg) - cache_width(cfg))))
    return q_nope, _rope(q_r, *rope), row


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


def _attend_tile(cfg: TransformerConfig, lp, q_nope, q_r, row):
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
    with jax.named_scope("attn_latent"):
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
                 q_r, row, c_all):
    """One token a slot against layer `l` of the carried cache (L, B, S,
    C), in the latent space: this step's row is written at `positions`,
    `W_UK` goes into the query and `W_UV` onto the weighted rows, and
    every held row is read once, for scores and values together. ->
    (out (B, 1, H*vd), c_all)."""
    from ..ops import decode_attention as da

    B, S, C = c_all.shape[1:]
    H, dt, kvr = cfg.n_heads, cfg.dtype, cfg.kv_lora_rank
    with jax.named_scope("mla_proj"):
        q_lat = _heads_dot("bhd,hdc->bhc", q_nope[:, 0].astype(dt),
                           lp["wk_b"])
        q = jnp.concatenate([q_lat, q_r[:, 0]], axis=-1).astype(dt)
        q = jnp.pad(q, ((0, 0), (0, 0), (0, C - q.shape[-1])))
    with jax.named_scope("attn_latent"):
        # A slot the engine no longer owns keeps advancing and can reach
        # S: its write falls out of bounds and is dropped.
        c_all = c_all.at[l, jnp.arange(B), positions].set(row[:, 0],
                                                          mode="drop")
        n_rows = rows_held(positions, S, live)
        if dt == c_all.dtype and da.usable(c_all, C, kvr):
            o_lat = da.decode_attention(
                q[:, None], c_all, None, l, n_rows, sm_scale=_scale(cfg),
                v_width=kvr).reshape(B, H, kvr)
        else:
            rows = lax.dynamic_index_in_dim(c_all, l, 0, keepdims=False)
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
    return out.reshape(B, 1, -1).astype(dt), c_all


def layer(cfg: TransformerConfig, lp, x, experts_at, rope, attend, state,
          rows=None):
    """One layer on x (B, S, D) in the activation dtype. `attend(lp,
    q_nope, q_r, row, state) -> (out (B, S, H*vd), state)` does the
    attention and whatever it keeps of the row. `experts_at`: None for a
    dense FFN, else (the stack's expert matrices, this layer's first
    group in them). `rows` (B*S,) bool: the rows somebody owns, the only
    ones the routed experts take (`moe.routed_ffn`; None: every row).
    Returns (x, state, routing stats or None, experts chosen (B*S, K) or
    None)."""
    B, S, _ = x.shape
    dt, eps = cfg.dtype, cfg.norm_eps

    def joins(branch, norm):
        return x + _norm(branch, lp[norm], eps).astype(x.dtype)

    with jax.named_scope("mla_proj"):
        q_nope, q_r, row = _project(cfg, lp, x, rope)
    out, state = attend(lp, q_nope, q_r, row, state)
    with jax.named_scope("mla_proj"):
        x = joins(_dot(out, lp["wo"]), "post_attn_norm")

    m = _norm(x, lp["ffn_norm"], eps)                      # float32
    stats = experts = None
    if experts_at is not None:
        flat = m.reshape(B * S, -1)
        f, stats, experts = routed_ffn(cfg, lp, flat, dt, *experts_at,
                                       rows=rows)
        if cfg.moe_shared_experts:
            with jax.named_scope("moe_shared"):
                f = f + _swiglu(flat.astype(dt), lp["shared_gate"],
                                lp["shared_up"], lp["shared_down"])
        f = f.reshape(B, S, -1)
    else:
        f = _swiglu(m.astype(dt), lp["w_gate"], lp["w_up"], lp["w_down"])
    return joins(f, "post_ffn_norm"), state, stats, experts


def _run(cfg: TransformerConfig, params, x, rope, attend, state, rows=None):
    """x through every layer: one `lax.scan` a group of the plan, `state`
    (the cache, or nothing) riding in the carry beside x. `attend(l, lp,
    q_nope, q_r, row, state)` is told which layer it serves; `rows`
    (see `layer`) are the rows somebody owns. Returns (x, state, routing
    stats summed over layers, experts chosen: one array (layers, B*S, K)
    a routed group)."""
    stats = jnp.zeros((routing_stats(cfg),), jnp.int32)
    chosen, base = [], 0
    for name, n, routed in layer_plan(cfg):
        stacked = params[name]
        # The expert matrices stay whole, every layer's groups in one
        # array, and are not scanned over: models/moe.grouped_experts.
        expert_w = {k: stacked[k].reshape((-1,) + stacked[k].shape[-2:])
                    for k in EXPERT_LEAVES} if routed else None
        if routed:
            stacked = {k: v for k, v in stacked.items()
                       if k not in EXPERT_LEAVES}

        def body(carry, scanned, expert_w=expert_w, base=base):
            x, state, stats = carry
            lp, g = scanned
            x, state, st, ex = layer(
                cfg, lp, x, expert_w and (expert_w, g * cfg.moe_experts),
                rope, partial(attend, base + g), state, rows)
            if st is not None:
                stats = stats + st
            return (x, state, stats), ex

        (x, state, stats), experts = lax.scan(
            body, (x, state, stats), (stacked, jnp.arange(n)))
        if routed:
            chosen.append(experts)
        base += n
    return x, state, stats, tuple(chosen)


def _embed(cfg: TransformerConfig, params, tokens):
    return params["embed"][tokens].astype(cfg.dtype)


def _final(cfg: TransformerConfig, params, x):
    return _norm(x, params["final_norm"], cfg.norm_eps).astype(cfg.dtype)


# ---------------------------------------------------------------------------
# What generate.py's programs call
# ---------------------------------------------------------------------------

def _prefill_attend(cfg, slots, l, lp, q_nope, q_r, row, c_all):
    out = _attend_tile(cfg, lp, q_nope, q_r, row)
    with jax.named_scope("attn_latent"):
        # The tile's rows into each row's slot, [0, S); a slot out of
        # range (the tile's padding) is dropped.
        c_all = c_all.at[l, slots, :row.shape[1]].set(row, mode="drop")
        # Rows-major, as the cache arrives and as the decode kernel reads
        # it. Left to itself the compiler lays the carried cache out
        # rows-minor for this write (the tile's rows come off a product
        # that way) and copies all of it in and out of the program.
        c_all = with_layout_constraint(
            c_all, Layout(major_to_minor=tuple(range(c_all.ndim))))
    return out, c_all


def prefill(cfg: TransformerConfig, params, cache: KVCache, tokens, lengths,
            slots) -> Tuple[KVCache, jax.Array, Optional[jax.Array]]:
    """tokens (W, S) into the slots' cache rows -> (cache', final-normed
    hidden states (W, S, D), routing stats of the tile as `decode` gives a
    step's, over all W x S positions, padding too; None with no routed
    layer)."""
    rope = _rope_tables(cfg, tokens.shape[1])
    x, c_all, stats, _ = _run(cfg, params, _embed(cfg, params, tokens), rope,
                              partial(_prefill_attend, cfg, slots), cache.c)
    seq_lens = cache.seq_lens.at[slots].set(lengths, mode="drop")
    return cache._replace(c=c_all, seq_lens=seq_lens), \
        _final(cfg, params, x), stats if routed_layers(cfg) else None


def _free_attend(cfg, l, lp, q_nope, q_r, row, state):
    return _attend_tile(cfg, lp, q_nope, q_r, row), state


def forward_free(cfg: TransformerConfig, params, tokens):
    """tokens (W, S) with no cache -> (final-normed hidden states (W, S,
    D), the experts every routed layer chose: see `_run`)."""
    rope = _rope_tables(cfg, tokens.shape[1])
    x, _, _, chosen = _run(cfg, params, _embed(cfg, params, tokens), rope,
                           partial(_free_attend, cfg), None)
    return _final(cfg, params, x), chosen


def decode(cfg: TransformerConfig, params, cache: KVCache, tokens,
           live=None) -> Tuple[KVCache, jax.Array, Optional[jax.Array]]:
    """One token a slot -> (cache', logits (B, V), routing stats of the
    step (`moe.routed_ffn`'s, summed over the routed layers): held
    experts that took a row, pairs kept, the pairs of the held expert
    most chosen, rows the experts took and, where the layer holds a
    share, the pairs routed; None with no routed layer). `live` (B,)
    bool: the slots a request owns (None: every one): any other slot
    reads and writes no cache row and its token meets no expert."""
    positions = cache.seq_lens
    rope = _rope_tables(cfg, cache.max_seq_len, positions)
    x, c_all, stats, _ = _run(
        cfg, params, _embed(cfg, params, tokens)[:, None, :], rope,
        partial(_attend_rows, cfg, positions, live), cache.c, live)
    cache = cache._replace(c=c_all, seq_lens=positions + 1)
    return cache, head_logits(cfg, params, _final(cfg, params, x)[:, 0]), \
        stats if routed_layers(cfg) else None


def chosen_experts(cfg: TransformerConfig, params, tokens) -> List[jax.Array]:
    """For tests and for telling a routing flip from arithmetic: the
    experts each routed layer chose for tokens (S,), in layer order, each
    (S, K), numbered as the router numbers them."""
    _, chosen = jax.jit(partial(forward_free, cfg))(
        params, jnp.asarray(tokens, jnp.int32)[None])
    return [layers[g] for layers in chosen for g in range(layers.shape[0])]
