"""The plain reference of the `mellum` decoder (JetBrains Mellum 2): its
forward pass and next-token loss in straightforward `jax.numpy`, float32,
highest matmul precision, to the interface `references/dense_decoder.py`
describes; what its routed products must move and compute, for the
roofline readers; and what a prompt's prefill asks for
(`prefill_flops`). Independent of `ray_tpu/models`: the weights are read
by leaf name (`periods`: leaves stacked over periods, then over a
period's layers), the architecture from the configuration file's keys,
the kind of each layer from the published `layer_types` where the file
has them.

The layer, for input x (T x d), as the configuration file's `published`
and `assumed` state it:

    x0      = Embed[tok]                                  (no scaling)
    a       = RMSNorm_in(x)
    q, k, v = a Wq, a Wk, a Wv                            no bias
    q, k    = RMSNorm_q(q), RMSNorm_k(k)                  over each head   [assumed]
    q, k    = RoPE_kind(q), RoPE_kind(k)                  half-split pairs, the layer's own table:
       sliding: inv_freq_i = theta^(-2i/D), cos/sin unscaled
       full (YaRN): dim(r) = D ln(L0 / (2 pi r)) / (2 ln theta); low = floor(dim(beta_fast)),
                high = ceil(dim(beta_slow)), clamped to [0, D - 1];
                ramp_i = clip((i - low) / (high - low), 0, 1), i in [0, D/2);
                inv_freq_i = (1 - ramp_i) theta^(-2i/D) + ramp_i theta^(-2i/D) / factor;
                cos and sin multiplied by attention_factor
    s_ij    = q_i . k_j / sqrt(D), j <= i, on a sliding layer also i - j < window
    x       = x + softmax(s) v Wo
    m       = RMSNorm_ffn(x)
    p       = softmax(m Wr) over the experts; I = the K largest (ties to the lower index)
    w       = p[I] / sum p[I]                             (norm_topk_prob)
    x       = x + sum_{e in I} w_e Wdown_e(silu(Wgate_e m) * Wup_e m)
    logits  = RMSNorm_final(x_L) Whead

Departures from the published model: none known beyond `assumed` (the
file's list); the multi-token-prediction head `described_as` mentions
has no key in the published config and is left out.

No kernels, no cache, no sort, no scan over layers. Every expert is
applied to all the sequence's tokens and weighted by a (T x E) matrix
that is zero where the token did not choose it. It runs beside 7.6 GB of
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
SLIDING, FULL = "sliding_attention", "full_attention"


def layer_kinds(arch: Dict[str, Any]) -> List[str]:
    """The kind of each layer held, in order: the first `n_layers` of the
    published `layer_types`, else periods of `global_attn_every` layers
    whose last is full."""
    n = int(arch["n_layers"])
    if arch.get("layer_types"):
        return list(arch["layer_types"][:n])
    every = int(arch["global_attn_every"])
    return [FULL if l % every == every - 1 else SLIDING for l in range(n)]


def _rms(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * w.astype(F32)


def _inv_freq(rope: Dict[str, Any], D: int):
    """(inv_freq (D/2,), the factor on cos and sin) of one section of
    `rope_parameters`."""
    theta = float(rope["rope_theta"])
    i = jnp.arange(D // 2, dtype=F32)
    inv = theta ** (-2.0 * i / D)
    if rope.get("rope_type", "default") == "default":
        return inv, 1.0
    factor = float(rope["factor"])
    L0 = float(rope["original_max_position_embeddings"])

    def dim(r):
        return D * math.log(L0 / (2 * math.pi * r)) / (2 * math.log(theta))

    low = max(math.floor(dim(float(rope["beta_fast"]))), 0)
    high = min(math.ceil(dim(float(rope["beta_slow"]))), D - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((i - low) / (high - low), 0.0, 1.0)
    return (1.0 - ramp) * inv + ramp * inv / factor, \
        float(rope["attention_factor"])


def _rope(x, rope: Dict[str, Any]):
    """x (S, H, D): rotate the pairs (i, i + D/2) by pos * inv_freq_i."""
    S, _, D = x.shape
    half = D // 2
    inv, scale = _inv_freq(rope, D)
    ang = jnp.arange(S, dtype=F32)[:, None] * inv[None, :]
    sin = (jnp.sin(ang) * scale)[:, None, :]
    cos = (jnp.cos(ang) * scale)[:, None, :]
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


def _route(m, router, top_k):
    """(weights (T, E), zero where not chosen; chosen (T, K))."""
    p = jax.nn.softmax(m @ router, axis=-1)
    # A stable sort of the negated scores: ties go to the lower index.
    chosen = jnp.argsort(-p, axis=-1, stable=True)[:, :top_k]
    w = jnp.take_along_axis(p, chosen, axis=-1)
    w = w / jnp.sum(w, axis=-1, keepdims=True)
    rows = jnp.arange(m.shape[0])[:, None]
    return jnp.zeros_like(p).at[rows, chosen].set(w), chosen


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


@partial(jax.jit, static_argnums=(3, 4))
def _layer(x, leaves, index, kind: str, a: Tuple):
    """One layer; `leaves` are the stacked weights, `index` says which
    layer of them (only that one is read)."""
    n_heads, n_kv, hd, eps, window, top_k, rope = a
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
    q, k = _rms(q, small["q_norm"], eps), _rms(k, small["k_norm"], eps)
    section = dict(dict(rope)[kind])
    q, k = _rope(q, section), _rope(k, section)
    rep = n_heads // n_kv
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    o = _attention(q, k, v, window if kind == SLIDING else 0)
    x = x + o.reshape(S, n_heads * hd) @ small["wo"]
    m = _rms(x, small["ffn_norm"], eps)
    weights, chosen = _route(m, small["router"], top_k)
    return x + _experts(m, lp, weights), chosen


@jax.jit
def _embed(table, tokens):
    return table[tokens].astype(F32)


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


def _hashable(value):
    """A JSON value as a static argument: dicts as sorted pairs."""
    if isinstance(value, dict):
        return tuple(sorted((k, _hashable(v)) for k, v in value.items()))
    if isinstance(value, (list, tuple)):
        return tuple(_hashable(v) for v in value)
    return value


def _static(arch: Dict[str, Any]) -> Tuple:
    if arch.get("score_func", "softmax") != "softmax":
        raise ValueError("mellum_decoder: score_func must be 'softmax'")
    if int(arch.get("moe_shared_experts", 0)) \
            or int(arch.get("n_dense_layers", 0)):
        raise ValueError("mellum_decoder: no shared expert, no dense layer")
    return (int(arch["n_heads"]), int(arch["n_kv_heads"]),
            int(arch["head_dim"]), float(arch["norm_eps"]),
            int(arch["sliding_window"]), int(arch["moe_top_k"]),
            _hashable(arch["rope_parameters"]))


def _forward(arch, params, tokens):
    with jax.default_matmul_precision("highest"):
        x = _embed(params["embed"], jnp.asarray(tokens, jnp.int32))
        a, chosen = _static(arch), []
        every = int(arch["global_attn_every"])
        for l, kind in enumerate(layer_kinds(arch)):
            index = (jnp.int32(l // every), jnp.int32(l % every))
            x, picked = _layer(x, params["periods"], index, kind, a)
            chosen.append(picked)
        tied = bool(arch.get("tie_embeddings"))
        head = params["embed"] if tied else params["lm_head"]
        return _head(x, params["final_norm"], head, a[3], tied), chosen


def forward_logits(arch: Dict[str, Any], params: Dict[str, Any], tokens
                   ) -> jax.Array:
    """tokens (S,) -> float32 logits (S, V) of one sequence."""
    return _forward(arch, params, tokens)[0]


def chosen_experts(arch: Dict[str, Any], params: Dict[str, Any], tokens
                   ) -> List[jax.Array]:
    """The experts each layer chooses, in layer order, each (S, K):
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


# -- what the routed products must move and compute --------------------------

def moe_experts_min_bytes(arch: Dict[str, Any], experts_hit: float,
                          rows: float, bytes_per: int = 2) -> float:
    """The least bytes the routed products can move, for `experts_hit`
    (expert, layer, step or tile) triples that held a row and `rows`
    token-expert pairs: the three matrices of each expert hit, once, and
    each pair's row in and out. A kernel that reads every expert, or one
    expert twice, moves more and reads lower."""
    d, f = int(arch["d_model"]), int(arch["moe_d_ff"])
    return bytes_per * (experts_hit * 3 * d * f + rows * 2 * d)


def moe_experts_flops(arch: Dict[str, Any], rows: float) -> float:
    """Operations of the routed products for `rows` token-expert pairs:
    three matrices of d x f, a multiply and an add each. The model's
    operations: a program that multiplies an activation as two bf16 terms
    does twice as many and reads at most half its peak by this count."""
    d, f = int(arch["d_model"]), int(arch["moe_d_ff"])
    return rows * 3 * 2 * d * f


# -- what the architecture costs ---------------------------------------------

def _matmul_params_used(arch: Dict[str, Any]) -> int:
    """Matmul parameters a token uses in one layer: the attention
    projections, the router, and its `moe_top_k` experts."""
    d, hd = int(arch["d_model"]), int(arch["head_dim"])
    q, kv = int(arch["n_heads"]) * hd, int(arch["n_kv_heads"]) * hd
    return 2 * d * q + 2 * d * kv + d * int(arch["moe_experts"]) \
        + 3 * d * int(arch["moe_d_ff"]) * int(arch["moe_top_k"])


def prefill_flops(arch: Dict[str, Any], n_tokens: int) -> float:
    """Operations a prompt of `n_tokens` asks of its prefill: two a
    matmul parameter a token uses, every layer; the attention of each
    (query, key) pair the mask lets through (q . k and p v: 4 x heads x
    head size a pair; a full layer n (n + 1) / 2 pairs, a sliding layer
    at most `sliding_window` a query); and the head at the one position
    whose logits a prefill needs. Padding, masked-out pairs and a second
    bf16 term are the program's, not the model's."""
    n = int(n_tokens)
    w = int(arch["sliding_window"])
    q = int(arch["n_heads"]) * int(arch["head_dim"])
    kinds = layer_kinds(arch)
    pairs_full = n * (n + 1) // 2
    m = min(n, w)
    pairs_slide = m * (m + 1) // 2 + (n - m) * w
    pairs = sum(pairs_slide if k == SLIDING else pairs_full for k in kinds)
    return 2.0 * n * _matmul_params_used(arch) * len(kinds) \
        + 4.0 * q * pairs \
        + 2.0 * int(arch["d_model"]) * int(arch["vocab_size"])


def train_flops_per_token(arch: Dict[str, Any], seq: int) -> float:
    """Forward and backward operations a trained token requires: 6 per
    matmul parameter the token uses (its `moe_top_k` experts, not the
    experts held) plus 12 x d_attn x keys a layer of attention, a sliding
    layer seeing at most `sliding_window` keys (masking and recomputation
    not counted). The system does not train this architecture
    (`transformer.forward` raises); the count is here because every
    reference brings one."""
    q = int(arch["n_heads"]) * int(arch["head_dim"])
    kinds = layer_kinds(arch)
    keys = sum(min(seq, int(arch["sliding_window"])) if k == SLIDING
               else seq for k in kinds)
    return 6.0 * (_matmul_params_used(arch) * len(kinds)
                  + int(arch["d_model"]) * int(arch["vocab_size"])) \
        + 12.0 * q * keys
