"""The plain reference of the `afmoe` decoder (Arcee Trinity): its forward
pass and next-token loss in straightforward `jax.numpy`, float32, highest
matmul precision, to the interface `references/dense_decoder.py`
describes; and the least bytes its routed products can move, for
`kernels.moe_experts_roofline_pct`. Independent of `ray_tpu/models`: the
weights are read by leaf name (`dense_layers`: leaves stacked over the
leading dense layers; `periods`: stacked over periods, then over a
period's layers), the architecture from the configuration file's keys.

The layer, for input x (T x d), as the configuration file's `published`
and `assumed` state it:

    x0     = Embed[tok] * sqrt(d)
    a      = RMSNorm_in(x)
    q,k,v  = a Wq, a Wk, a Wv ;  g = a Wg
    q, k   = RMSNorm_q(q), RMSNorm_k(k)         over each head's 128
    sliding layer: q, k = RoPE(q), RoPE(k)      half-split pairs; a full layer has none
    s_ij   = q_i . k_j / sqrt(head_dim), j <= i, on a sliding layer also i - j < window
    o      = softmax(s) v * sigmoid(g) ;  x = x + RMSNorm_post_attn(o Wo)
    m      = RMSNorm_pre_mlp(x)
    dense layer:  f = Wdown(silu(Wgate m) * Wup m)
    routed layer: sc = sigmoid(m Wr); I = the K largest of sc + b (ties to
                  the lower index); w = sc[I] / (sum sc[I] + 1e-20) * route_scale;
                  f = sum_{e in I} w_e E_e(m) + Shared(m)
    x      = x + RMSNorm_post_mlp(f)
    logits = RMSNorm_final(x_L) Whead

No kernels, no cache, no sort, no scan over layers. Every expert is
applied to all the sequence's tokens and weighted by a (T x E) matrix
that is zero where the token did not choose it. It runs beside 8.5 GB of
weights and a live engine: one layer's weights are read at a time,
experts are cast to float32 sixteen at a time, the head in eight blocks
of its rows, attention in blocks of 512 queries.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32
EXPERT_CHUNK = 16
QUERY_BLOCK = 512
HEAD_BLOCKS = 8


def layer_table(arch: Dict[str, Any]
                ) -> List[Tuple[str, Tuple[int, ...], bool, bool]]:
    """[(weights' key, index into its stacked leaves, sliding?, routed?)]
    in layer order: the leading dense layers (sliding), then periods of
    `global_attn_every` routed layers, the last of each full."""
    dense, every = int(arch["n_dense_layers"]), int(arch["global_attn_every"])
    table = [("dense_layers", (i,), True, False) for i in range(dense)]
    for p in range((int(arch["n_layers"]) - dense) // every):
        table += [("periods", (p, j), j < every - 1, True)
                  for j in range(every)]
    return table


def _rms(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * w.astype(F32)


def _rope(x, theta):
    """x (S, H, D): rotate the pairs (i, i + D/2) by pos * theta^(-2i/D)."""
    S, _, D = x.shape
    half = D // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=F32) * 2.0 / D))
    ang = jnp.arange(S, dtype=F32)[:, None] * inv[None, :]
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _attention(q, k, v, window):
    """q (S, H, D), k, v (S, H, D) -> (S, H, D); `window` 0 = all."""
    S, _, D = q.shape
    out = []
    for a in range(0, S, QUERY_BLOCK):
        b = min(S, a + QUERY_BLOCK)
        s = jnp.einsum("qhd,khd->hqk", q[a:b], k[:b]) / math.sqrt(D)
        i = jnp.arange(a, b)[:, None]
        j = jnp.arange(b)[None, :]
        seen = j <= i
        if window:
            seen = seen & (i - j < window)
        s = jnp.where(seen[None], s, -jnp.inf)
        out.append(jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1),
                              v[:b]))
    return jnp.concatenate(out, axis=0)


def _swiglu(m, gate, up, down):
    return (jax.nn.silu(m @ gate) * (m @ up)) @ down


def _route(m, lp, top_k, route_norm, route_scale):
    """(weights (T, E), zero where not chosen; chosen (T, K))."""
    sc = jax.nn.sigmoid(m @ lp["router"].astype(F32))
    pick = sc + lp["router_bias"].astype(F32)
    # A stable sort of the negated scores: ties go to the lower index.
    chosen = jnp.argsort(-pick, axis=-1, stable=True)[:, :top_k]
    w = jnp.take_along_axis(sc, chosen, axis=-1)
    if route_norm:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    w = w * route_scale
    rows = jnp.arange(m.shape[0])[:, None]
    return jnp.zeros_like(sc).at[rows, chosen].set(w), chosen


def _experts(m, lp, weights):
    """sum_e weights[:, e] * E_e(m), sixteen experts cast at a time."""
    E = lp["w_gate"].shape[0]
    chunk = math.gcd(E, EXPERT_CHUNK)

    def body(c, acc):
        part = {n: lax.dynamic_slice_in_dim(lp[n], c * chunk, chunk, 0)
                .astype(F32) for n in ("w_gate", "w_up", "w_down")}
        w = lax.dynamic_slice_in_dim(weights, c * chunk, chunk, 1)
        for e in range(chunk):
            acc = acc + w[:, e:e + 1] * _swiglu(
                m, part["w_gate"][e], part["w_up"][e], part["w_down"][e])
        return acc

    return lax.fori_loop(0, E // chunk, body, jnp.zeros_like(m))


@partial(jax.jit, static_argnums=(3, 4, 5))
def _layer(x, leaves, index, sliding: bool, routed: bool, a: Tuple):
    """One layer; `leaves` are a group's stacked weights, `index` says
    which layer of them (only that one is read)."""
    n_heads, n_kv, hd, theta, eps, window, top_k, norm, scale = a
    lp = leaves
    for i in index:
        lp = {k: lax.dynamic_index_in_dim(v, i, 0, keepdims=False)
              for k, v in lp.items()}
    small = {k: v.astype(F32) for k, v in lp.items() if v.ndim <= 2}
    S = x.shape[0]
    h = _rms(x, small["attn_norm"], eps)
    q = (h @ small["wq"]).reshape(S, n_heads, hd)
    k = (h @ small["wk"]).reshape(S, n_kv, hd)
    v = (h @ small["wv"]).reshape(S, n_kv, hd)
    g = h @ small["wg"]
    q, k = _rms(q, small["q_norm"], eps), _rms(k, small["k_norm"], eps)
    if sliding:
        q, k = _rope(q, theta), _rope(k, theta)
    rep = n_heads // n_kv
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    o = _attention(q, k, v, window if sliding else 0)
    o = o.reshape(S, n_heads * hd) * jax.nn.sigmoid(g)
    x = x + _rms(o @ small["wo"], small["post_attn_norm"], eps)
    m = _rms(x, small["ffn_norm"], eps)
    chosen = jnp.zeros((S, 0), jnp.int32)
    if routed:
        weights, chosen = _route(m, small, top_k, norm, scale)
        f = _experts(m, lp, weights)
        if "shared_gate" in small:
            f = f + _swiglu(m, small["shared_gate"], small["shared_up"],
                            small["shared_down"])
    else:
        f = _swiglu(m, small["w_gate"], small["w_up"], small["w_down"])
    return x + _rms(f, small["post_ffn_norm"], eps), chosen


@jax.jit
def _embed(table, tokens):
    return table[tokens].astype(F32) * math.sqrt(table.shape[1])


@partial(jax.jit, static_argnums=(3, 4))
def _head(x, norm, head, eps, tied: bool):
    """RMSNorm_final(x) Whead, the head cast a block of its rows (the
    vocabulary) at a time."""
    xn = _rms(x, norm, eps)
    V = head.shape[0] if tied else head.shape[1]
    n = math.gcd(V, HEAD_BLOCKS)
    out = []
    for b in range(n):
        cols = slice(b * V // n, (b + 1) * V // n)
        w = head[cols].astype(F32).T if tied else head[:, cols].astype(F32)
        out.append(xn @ w)
    return jnp.concatenate(out, axis=-1)


def _static(arch: Dict[str, Any]) -> Tuple:
    if arch.get("score_func", "sigmoid") != "sigmoid":
        raise ValueError("afmoe_decoder: score_func must be 'sigmoid'")
    return (int(arch["n_heads"]), int(arch["n_kv_heads"]),
            int(arch["head_dim"]), float(arch["rope_theta"]),
            float(arch["norm_eps"]), int(arch["sliding_window"]),
            int(arch["moe_top_k"]), bool(arch.get("route_norm", True)),
            float(arch.get("route_scale", 1.0)))


def _forward(arch, params, tokens):
    with jax.default_matmul_precision("highest"):
        x = _embed(params["embed"], jnp.asarray(tokens, jnp.int32))
        a, chosen = _static(arch), []
        for key, index, sliding, routed in layer_table(arch):
            x, picked = _layer(x, params[key],
                               tuple(jnp.int32(i) for i in index), sliding,
                               routed, a)
            if routed:
                chosen.append(picked)
        tied = bool(arch.get("tie_embeddings"))
        head = params["embed"] if tied else params["lm_head"]
        return _head(x, params["final_norm"], head, a[4], tied), chosen


def forward_logits(arch: Dict[str, Any], params: Dict[str, Any], tokens
                   ) -> jax.Array:
    """tokens (S,) -> float32 logits (S, V) of one sequence."""
    return _forward(arch, params, tokens)[0]


def chosen_experts(arch: Dict[str, Any], params: Dict[str, Any], tokens
                   ) -> List[jax.Array]:
    """The experts each routed layer chooses, in layer order, each (S, K):
    beside the program's own, they tell a routing flip from arithmetic."""
    return _forward(arch, params, tokens)[1]


@jax.jit
def _nll_sum(logits, targets):
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
    return jnp.sum(logz - gold)


def loss(arch: Dict[str, Any], params: Dict[str, Any], tokens, targets
         ) -> float:
    """Mean next-token cross entropy over a batch (B, S), one sequence
    at a time."""
    total, count = 0.0, 0
    for row, tgt in zip(tokens, targets):
        logits = forward_logits(arch, params, row)
        total += float(_nll_sum(logits, jnp.asarray(tgt, jnp.int32)))
        count += len(tgt)
    return total / count


# -- what the routed products must move --------------------------------------

def moe_experts_min_bytes(arch: Dict[str, Any], experts_hit: float,
                          rows: float, bytes_per: int = 2) -> float:
    """The least bytes the routed products of decode can move, for
    `experts_hit` (expert, layer, step) triples that held a row and
    `rows` token-expert pairs: the three matrices of each expert hit,
    once, and each pair's row in and out. A kernel that reads every
    expert, or one expert twice, moves more and reads lower."""
    d = int(arch["d_model"])
    f = int(arch.get("moe_d_ff") or arch["d_ff"])
    return bytes_per * (experts_hit * 3 * d * f + rows * 2 * d)


def moe_experts_flops(arch: Dict[str, Any], rows: float) -> float:
    """Operations of the routed products for `rows` token-expert pairs:
    three matrices of d x f, a multiply and an add each."""
    d = int(arch["d_model"])
    f = int(arch.get("moe_d_ff") or arch["d_ff"])
    return rows * 3 * 2 * d * f


# -- what the architecture costs ---------------------------------------------

def train_flops_per_token(arch: Dict[str, Any], seq: int) -> float:
    """Forward and backward operations a trained token requires: 6 per
    matmul parameter *the token uses* (its `moe_top_k` experts and the
    shared ones, not the experts held) plus 12·d_attn·S a layer of
    attention, a window layer seeing at most `sliding_window` keys
    (masking and recomputation not counted). The system does not train
    this architecture (`transformer.forward` raises); the count is here
    because every reference brings one."""
    d, hd = int(arch["d_model"]), int(arch["head_dim"])
    q, kv = int(arch["n_heads"]) * hd, int(arch["n_kv_heads"]) * hd
    attn = 3 * d * q + 2 * d * kv                  # wq, wg, wo; wk, wv
    f = int(arch.get("moe_d_ff") or arch["d_ff"])
    used = int(arch["moe_top_k"]) + int(arch.get("moe_shared_experts", 0))
    params, keys = d * int(arch["vocab_size"]), 0
    for _, _, sliding, routed in layer_table(arch):
        params += attn + (3 * d * f * used + d * int(arch["moe_experts"])
                          if routed else 3 * d * int(arch["d_ff"]))
        keys += min(seq, int(arch["sliding_window"])) if sliding else seq
    return 6.0 * params + 12.0 * q * keys
