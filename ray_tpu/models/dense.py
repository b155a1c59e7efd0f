"""The dense stack (`TransformerConfig.arch == "llama"`): every layer alike
(pre-norm, rotary, GQA, SwiGLU or the routed FFN of `models/moe.py`),
served through the programs of `generate.py`.

Weights: `layers`, leaves stacked over the layers for `lax.scan`. One
layer definition (`layer`) serves prefill, decode and the walk behind a
shared prefix; they differ in the `attend` they hand in, which owns what
is kept of k and v. The cache is one kind of state: `KVCache.k` / `.v`,
(L, slots, S_max, KVH, Dh) in the activation dtype.

Training walks the same weights through `transformer.forward_hidden`,
whose `_layer` is this layer's second definition (ROADMAP D1); the
cache-free first token still runs that walk too.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..parallel.sharding import with_sharding_constraint as wsc
# Nothing of this stack's is counted on the host: the seam's defaults.
from .stackparts import (Extras, KVCache, _attend_cache,  # noqa: F401
                         _last_rows, _rope, block_counts, by_products,
                         counters, result_counts, tile_counts)
from .transformer import (
    TransformerConfig,
    dense_ffn,
    forward_hidden as forward_train,
    rms_norm,
    rope_tables,
)


# ---------------------------------------------------------------------------
# Weights
# ---------------------------------------------------------------------------

def _layer_shapes(cfg: TransformerConfig) -> Dict[str, Tuple[int, ...]]:
    d, hd = cfg.d_model, cfg.head_dim
    shapes = {
        "attn_norm": (d,),
        "wq": (d, cfg.n_heads * hd),
        "wk": (d, cfg.n_kv_heads * hd),
        "wv": (d, cfg.n_kv_heads * hd),
        "wo": (cfg.n_heads * hd, d),
        "ffn_norm": (d,),
    }
    if cfg.is_moe:
        shapes.update({
            "router": (d, cfg.moe_experts),
            "w_gate": (cfg.moe_experts, d, cfg.d_ff),
            "w_up": (cfg.moe_experts, d, cfg.d_ff),
            "w_down": (cfg.moe_experts, cfg.d_ff, d),
        })
    else:
        shapes.update({
            "w_gate": (d, cfg.d_ff),
            "w_up": (d, cfg.d_ff),
            "w_down": (cfg.d_ff, d),
        })
    return shapes


def num_params(cfg: TransformerConfig) -> int:
    per_layer = sum(math.prod(s) for s in _layer_shapes(cfg).values())
    emb = cfg.vocab_size * cfg.d_model * (1 if cfg.tie_embeddings else 2)
    return cfg.n_layers * per_layer + emb + cfg.d_model


def param_logical_axes(cfg: TransformerConfig) -> Dict[str, Any]:
    """Same pytree structure as params, leaves = logical-axis tuples."""
    if cfg.is_moe:
        ffn_axes = {
            "router": ("layers", "embed", "expert"),
            "w_gate": ("layers", "expert", "embed", "mlp"),
            "w_up": ("layers", "expert", "embed", "mlp"),
            "w_down": ("layers", "expert", "mlp", "embed"),
        }
    else:
        ffn_axes = {
            "w_gate": ("layers", "embed", "mlp"),
            "w_up": ("layers", "embed", "mlp"),
            "w_down": ("layers", "mlp", "embed"),
        }
    axes = {
        "embed": ("vocab", "embed"),
        "layers": {
            "attn_norm": ("layers", None),
            "wq": ("layers", "embed", "heads"),
            "wk": ("layers", "embed", "kv_heads"),
            "wv": ("layers", "embed", "kv_heads"),
            "wo": ("layers", "heads", "embed"),
            "ffn_norm": ("layers", None),
            **ffn_axes,
        },
        "final_norm": (None,),
    }
    if not cfg.tie_embeddings:
        axes["lm_head"] = ("embed", "vocab")
    return axes


def init_params(cfg: TransformerConfig, key: jax.Array) -> Dict[str, Any]:
    """Scaled-normal init; layer params stacked on a leading L axis for
    lax.scan."""
    pd = cfg.param_dtype
    k_emb, k_layers, k_head = jax.random.split(key, 3)

    def normal(key, shape, scale):
        return (jax.random.normal(key, shape, dtype=jnp.float32)
                * scale).astype(pd)

    d = cfg.d_model
    layer_shapes = _layer_shapes(cfg)
    keys = jax.random.split(k_layers, len(layer_shapes))
    layers = {}
    for (name, shape), k in zip(sorted(layer_shapes.items()), keys):
        full = (cfg.n_layers,) + shape
        if name.endswith("norm"):
            layers[name] = jnp.ones(full, dtype=pd)
        elif name in ("wo", "w_down"):
            # residual-branch outputs: scale down by depth
            layers[name] = normal(
                k, full, 0.02 / math.sqrt(2 * cfg.n_layers))
        else:
            layers[name] = normal(k, full, 0.02)
    params = {
        "embed": normal(k_emb, (cfg.vocab_size, d), 0.02),
        "layers": layers,
        "final_norm": jnp.ones((d,), dtype=pd),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = normal(k_head, (d, cfg.vocab_size), 0.02)
    return params


def init_cache(cfg: TransformerConfig, num_slots: int, max_seq_len: int
               ) -> KVCache:
    shape = (cfg.n_layers, num_slots, max_seq_len, cfg.n_kv_heads,
             cfg.head_dim)
    k = jnp.zeros(shape, cfg.dtype)
    k = wsc(k, ("layers", None, None, "act_kv_heads", None))
    v = jnp.zeros(shape, cfg.dtype)
    v = wsc(v, ("layers", None, None, "act_kv_heads", None))
    return KVCache(k=k, v=v, seq_lens=jnp.zeros((num_slots,), jnp.int32))


def routed_layers(cfg: TransformerConfig) -> int:
    """Layers whose use of their experts `decode` reports: none (the
    routed FFN of a `moe_experts` configuration keeps no count)."""
    return 0


# ---------------------------------------------------------------------------
# The layer, and its three attentions
# ---------------------------------------------------------------------------

def _qkv(cfg: TransformerConfig, lp, x, sin, cos):
    B, S, _ = x.shape
    H, KVH, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = (x @ lp["wq"].astype(x.dtype)).reshape(B, S, H, Dh)
    k = (x @ lp["wk"].astype(x.dtype)).reshape(B, S, KVH, Dh)
    v = (x @ lp["wv"].astype(x.dtype)).reshape(B, S, KVH, Dh)
    return _rope(q, sin, cos), _rope(k, sin, cos), v


def _ffn(cfg: TransformerConfig, lp, x, rows=None):
    if cfg.is_moe:
        # Routed, nothing dropped (models/moe.py): scores and selection
        # in float32 from the norm's float32 output.
        from .moe import routed_ffn
        B, S, D = x.shape
        m = rms_norm(x.astype(jnp.float32), lp["ffn_norm"], cfg.norm_eps)
        f, _, _ = routed_ffn(cfg, lp, m.reshape(B * S, D), x.dtype,
                             rows=rows)
        return x + f.reshape(B, S, D).astype(x.dtype)
    return x + dense_ffn(lp, rms_norm(x, lp["ffn_norm"], cfg.norm_eps))


def layer(cfg: TransformerConfig, lp, x, sin, cos, attend, rows=None):
    """One layer on x (B, S, D). `attend(q, k, v) -> (out, kept)` does
    the attention (out: B x S rows of H*Dh, in any grouping) and says
    what it keeps of k and v. `rows` (B*S,) bool: the rows somebody owns,
    the only ones a routed FFN's experts take (None: every row). Returns
    (x, kept)."""
    B, S, _ = x.shape
    h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
    q, k, v = _qkv(cfg, lp, h, sin, cos)
    out, kept = attend(q, k, v)
    x = x + (out.reshape(B, S, -1) @ lp["wo"].astype(x.dtype))
    return _ffn(cfg, lp, x, rows), kept


def _tile_attend(q, k, v):
    """Causal attention over the tile itself; the tile's k and v are
    kept whole, for the walk to write into the slots' rows."""
    from ..ops import flash_attention

    return flash_attention(q, k, v, causal=True), (k, v)


def _cache_attend(cfg, k_all, v_all, l, positions, live, q, k, v):
    """One token a slot: append its k and v to layer `l` of the carried
    cache at the slot's position, and read the rows the slot holds."""
    out, k_all, v_all = _attend_cache(cfg, q, k, v, k_all, v_all, l,
                                      positions, positions, live)
    return out, (k_all, v_all)


def _prefix_attend(pk, pv, q, k, v):
    """Queries at positions [Sp, Sp + Sq) over a shared prefix's keys
    and values (pk, pv: (Sp, KVH, Dh)) and then their own causal block;
    the block's k and v are kept."""
    from ..ops import flash_attention

    W = q.shape[0]
    pk_b = jnp.broadcast_to(pk[None].astype(q.dtype), (W,) + pk.shape)
    pv_b = jnp.broadcast_to(pv[None].astype(q.dtype), (W,) + pv.shape)
    kk = jnp.concatenate([pk_b, k], axis=1)       # (W, Sp+Sq, KVH, Dh)
    vv = jnp.concatenate([pv_b, v], axis=1)
    out = flash_attention(q, kk, vv, causal=True, q_offset=pk.shape[0])
    return out, (k, v)


# ---------------------------------------------------------------------------
# What generate.py's programs call
# ---------------------------------------------------------------------------

def _embed(cfg: TransformerConfig, params, tokens):
    return params["embed"].astype(cfg.dtype)[tokens]


def _final(cfg: TransformerConfig, params, x):
    return rms_norm(x, params["final_norm"], cfg.norm_eps)


def prefill(cfg: TransformerConfig, params, cache: KVCache, tokens, lengths,
            slots) -> Tuple[KVCache, jax.Array, Extras]:
    """tokens (W, S) into the slots' cache rows -> (cache', final-normed
    hidden states (W, S, D), `Extras` with nothing in it: no routed layer
    to report on). A row whose slot is out of range (a tile's padding)
    is dropped."""
    S = tokens.shape[1]
    x = _embed(cfg, params, tokens)
    sin, cos = rope_tables(cfg, S)

    def body(carry, lp):
        x, sin, cos = carry
        x, kv = layer(cfg, lp, x, sin, cos, _tile_attend)
        return (x, sin, cos), kv

    (x, _, _), (ks, vs) = lax.scan(body, (x, sin, cos), params["layers"])
    # ks: (L, W, S, KVH, Dh) -> the slots' rows [0, S).
    k = cache.k.at[:, slots, :S].set(ks.astype(cache.k.dtype), mode="drop")
    v = cache.v.at[:, slots, :S].set(vs.astype(cache.v.dtype), mode="drop")
    seq_lens = cache.seq_lens.at[slots].set(lengths, mode="drop")
    return KVCache(k=k, v=v, seq_lens=seq_lens), _final(cfg, params, x), \
        Extras()


def forward_free(cfg: TransformerConfig, params, tokens):
    """tokens (W, S) with no cache -> (final-normed hidden states (W, S,
    D), None: no routed layer reports what it chose, `Extras`)."""
    x, _aux = forward_train(cfg, params, tokens)
    return x, None, Extras()


def decode(cfg: TransformerConfig, params, cache: KVCache, tokens,
           live=None) -> Tuple[KVCache, jax.Array, Extras]:
    """One token a slot -> (cache', logits (B, V), `Extras` with nothing
    in it: see `routed_layers`). The cache rides in the scan's carry, so
    no layer's slab is sliced out of it or stacked back into it. `live`
    (B,) bool: the slots a request owns (None: every one)."""
    positions = cache.seq_lens                              # (B,)
    x = _embed(cfg, params, tokens)[:, None, :]             # (B, 1, D)
    sin_t, cos_t = rope_tables(cfg, cache.max_seq_len)
    sin = sin_t[positions][:, None, :]                      # (B, 1, half)
    cos = cos_t[positions][:, None, :]

    def body(carry, scanned):
        x, k_all, v_all = carry
        lp, l = scanned
        x, (k_all, v_all) = layer(
            cfg, lp, x, sin, cos,
            partial(_cache_attend, cfg, k_all, v_all, l, positions, live),
            live)
        return (x, k_all, v_all), None

    (x, k, v), _ = lax.scan(
        body, (x, cache.k, cache.v),
        (params["layers"], jnp.arange(cfg.n_layers)))
    logits = head_logits(cfg, params, _final(cfg, params, x))[:, 0]
    return KVCache(k=k, v=v, seq_lens=positions + 1), logits, Extras()


def suffix(cfg: TransformerConfig, params, prefix_k, prefix_v, tokens):
    """tokens (W, Sq) at positions [Sp, Sp + Sq) behind a shared prefix
    (prefix_k/v: (L, Sp, KVH, Dh)) -> (final-normed hidden states (W, Sq,
    D), the suffix's own ks, vs (L, W, Sq, KVH, Dh))."""
    Sq = tokens.shape[1]
    Sp = prefix_k.shape[1]
    x = _embed(cfg, params, tokens)
    sin_t, cos_t = rope_tables(cfg, Sp + Sq)
    sin, cos = sin_t[Sp:], cos_t[Sp:]

    def body(carry, scanned):
        lp, pk, pv = scanned
        x, kv = layer(cfg, lp, carry[0], sin, cos,
                      partial(_prefix_attend, pk, pv))
        return (x,), kv

    (x,), (ks, vs) = lax.scan(
        body, (x,), (params["layers"], prefix_k, prefix_v))
    return _final(cfg, params, x), ks, vs


def head_logits(cfg: TransformerConfig, params, x) -> jax.Array:
    """Final-normed x (..., D) -> float32 logits (..., V)."""
    head = (params["embed"].T if cfg.tie_embeddings
            else params["lm_head"]).astype(cfg.dtype)
    return (x @ head).astype(jnp.float32)


def last_logits(cfg: TransformerConfig, params, x, lengths) -> jax.Array:
    """Logits (W, V) at the last real position of final-normed x (W, S, D)."""
    return head_logits(cfg, params, _last_rows(x, lengths))[:, 0]
