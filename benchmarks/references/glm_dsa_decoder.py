"""The plain reference of the `glm_moe_dsa` decoder (GLM-5: multi-head
latent attention over rows that a learned indexer chooses, a pre-norm
layer of two norms, a sigmoid-routed expert layer with a selection
bias): its forward pass in straightforward `jax.numpy`, float32, highest
matmul precision, to the interface `references/dense_decoder.py`
describes; and the least bytes and the operations of its routed
products, of its indexer and of its attention over the chosen rows, for
the roofline readers. Independent of `ray_tpu/models`: the weights are
read by leaf name (`dense_layers`, `routed_layers`: leaves stacked over
the group's layers), the architecture from the configuration file's keys.

The layer, for input x (T x d), as the configuration file's `published`
and `assumed` state it:

    x0       = Embed[tok]
    a        = RMSNorm_in(x)
    c_q      = RMSNorm_qa(a Wqa)                          (q_lora_rank)
    q        = c_q Wqb -> heads x [q_nope (nope) | q_r (rope)]   (Wqb's columns
                                  kept as two leaves by what they make)
    [c | kr] = a Wkva                                     (kv_lora_rank + rope)
    c        = RMSNorm_kva(c) ;  q_r, k_r = RoPE(q_r), RoPE(kr)   half-split
                                  pairs; k_r is one vector, shared by all heads
    k_nope   = c Wkb, v = c Wvb   per head (Wkb | Wvb: the published kv_b_proj,
                                  kept a head at a time: (H, nope, rank), (H, rank, v))
    indexer: qI = c_q WqI -> index_n_heads x index_head_dim, RoPE on the first
                  `rope` of each ;  kI = LayerNorm(a WkI) (weight and bias),
                  RoPE on its first `rope`
             wI = (a WwI) * index_n_heads^-0.5 * index_head_dim^-0.5
             I_ts = sum_h wI_th relu(qI_th . kI_s)            s <= t
             S_t  = the index_topk largest I_ts over s <= t (ties to the lower
                    s); every s <= t while t < index_topk
    s_tj     = (q_nope_t . k_nope_j + q_r_t . k_r_j) / sqrt(nope + rope), j in S_t
    o        = softmax(s) v ;  x = x + o Wo
    m        = RMSNorm_ffn(x)
    dense layer:  f = Wdown(silu(Wgate m) * Wup m)
    routed layer: sc = sigmoid(m Wr) over all the router's experts; I = the
                  K largest of sc + bias (ties to the lower index); w = sc[I] /
                  (sum sc[I] + 1e-20) * route_scale;
                  f = Shared(m) + sum_{e in I, e held} w_e E_e(m)
    x        = x + f
    logits   = RMSNorm_final(x_L) Whead

No cache, no absorption (every row is up-projected and attended per head),
no gather (a query's chosen set is a mask on the scores of every row), no
kernels: the indexer's scores for a block of queries against all keys,
`lax.top_k` a query (exact, ties to the lower row), the chosen set as a
mask. "Held": the configuration says which experts this chip holds
(`moe_first_expert`, `moe_experts` of the router's `moe_router_experts`);
a chosen expert that is not held is another chip's and its term is left
out, as the program leaves it out. With all of them held this is the
uncut layer. It runs 20,000 positions beside 7.8 GB of weights: one
layer's weights are read at a time, attention a block of heads and of
queries at a time, a dense FFN a slice of its width at a time, experts
one at a time, the head in blocks of its columns.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32
HEAD_BLOCK = 16          # heads attended together
QUERY_BLOCK = 512
INDEX_BLOCK = 128        # queries the indexer scores together
INDEX_NORM_EPS = 1e-6    # the indexer key's LayerNorm (the file's `assumed`)
FFN_BLOCKS = 6           # slices of a dense FFN's width
VOCAB_BLOCKS = 4


def layer_table(arch: Dict[str, Any]) -> List[Tuple[str, int, bool]]:
    """[(weights' key, index into its stacked leaves, routed?)] in layer
    order: the leading dense layers, then the routed ones."""
    dense = int(arch["n_dense_layers"])
    return [("dense_layers", i, False) for i in range(dense)] + \
        [("routed_layers", i, True)
         for i in range(int(arch["n_layers"]) - dense)]


def _rms(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * w.astype(F32)


def _rope(x, theta):
    """x (S, ..., D): rotate the pairs (i, i + D/2) by pos * theta^(-2i/D)."""
    S, D = x.shape[0], x.shape[-1]
    half = D // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=F32) * 2.0 / D))
    ang = jnp.arange(S, dtype=F32)[:, None] * inv[None, :]
    ang = ang.reshape((S,) + (1,) * (x.ndim - 2) + (half,))
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _blocks(x, blk):
    """x (S, ...) -> (blocks, blk, ...), zero rows behind the last."""
    pad = -x.shape[0] % blk
    x = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
    return x.reshape((-1, blk) + x.shape[1:])


def _chosen(h, c_q, lp, idx):
    """The indexer: which rows each query attends -> bool (S, S), [t, s].
    A block of queries at a time: its scores against every key, the rows
    ahead of a query at `-inf`, `lax.top_k` a query (ties to the lower
    row), and the chosen rows it may see set in a mask."""
    Hi, Di, topk, rope, theta = idx
    S = h.shape[0]

    def rotated(x):                                      # (S, heads, Di)
        return jnp.concatenate([_rope(x[..., :rope], theta), x[..., rope:]],
                               axis=-1)

    q = rotated((c_q @ lp["idx_wq"].astype(F32)).reshape(S, Hi, Di))
    k = h @ lp["idx_wk"].astype(F32)
    k = k - jnp.mean(k, axis=-1, keepdims=True)
    k = k * lax.rsqrt(jnp.mean(k * k, axis=-1, keepdims=True)
                      + INDEX_NORM_EPS)
    k = k * lp["idx_k_norm"].astype(F32) + lp["idx_k_bias"].astype(F32)
    k = rotated(k[:, None, :])[:, 0]
    w = (h @ lp["idx_wp"].astype(F32)) * (Hi ** -0.5 * Di ** -0.5)
    blk = min(INDEX_BLOCK, S)
    j = jnp.arange(S)[None, :]

    def block(args):
        qs, ws, start = args
        s = jnp.einsum("qhd,sd->qhs", qs, k)
        score = jnp.sum(jnp.maximum(s, 0.0) * ws[:, :, None], axis=1)
        seen = j <= start + jnp.arange(blk)[:, None]
        _, best = lax.top_k(jnp.where(seen, score, -jnp.inf), min(topk, S))
        picked = jnp.zeros((blk, S), bool).at[
            jnp.arange(blk)[:, None], best].set(True)
        return picked & seen

    q, w = _blocks(q, blk), _blocks(w, blk)
    out = lax.map(block, (q, w, jnp.arange(q.shape[0]) * blk))
    return out.reshape(-1, S)[:S]


def _masked(q, k, v, scale, chosen):
    """q, k (S, h, Dk), v (S, h, Dv), chosen (S, S) bool -> (S, h, Dv), a
    block of queries at a time over all the keys under the mask."""
    S = q.shape[0]
    blk = min(QUERY_BLOCK, S)

    def block(args):
        qs, may = args
        s = jnp.einsum("qhd,khd->hqk", qs, k) * scale
        p = jax.nn.softmax(jnp.where(may[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v)

    # A padding query sees row 0, so that its softmax is a number.
    may = _blocks(chosen, blk)
    may = may.at[..., 0].set(may[..., 0] | ~jnp.any(may, axis=-1))
    out = lax.map(block, (_blocks(q, blk), may))
    return out.reshape((-1,) + out.shape[2:])[:S]


def _attention(h, lp, a, idx):
    """The latent attention of normed h (S, d) over the rows its indexer
    chooses -> ((S, d), chosen (S, S) bool), a block of heads at a time:
    the block's columns of Wqb, Wkb, Wvb and rows of Wo are cast and
    used, the next block's follow."""
    H, nope, rope, vd, kvr, theta, eps = a
    S = h.shape[0]
    c_q = _rms(h @ lp["wq_a"].astype(F32), lp["q_a_norm"], eps)
    chosen = _chosen(h, c_q, lp, idx + (rope, theta))
    kv = h @ lp["wkv_a"].astype(F32)
    c = _rms(kv[:, :kvr], lp["kv_a_norm"], eps)
    k_r = _rope(kv[:, kvr:], theta)                        # (S, rope)
    hb = math.gcd(H, HEAD_BLOCK)
    scale = 1.0 / math.sqrt(nope + rope)

    def heads(b, out):
        def cols(w, width):
            return lax.dynamic_slice_in_dim(
                w, b * hb * width, hb * width, 1).astype(F32)

        def these(w):
            return lax.dynamic_slice_in_dim(w, b * hb, hb, 0).astype(F32)

        q_nope = (c_q @ cols(lp["wq_nope"], nope)).reshape(S, hb, nope)
        q_r = (c_q @ cols(lp["wq_rope"], rope)).reshape(S, hb, rope)
        q = jnp.concatenate([q_nope, _rope(q_r, theta)], -1)
        k_nope = jnp.einsum("sc,hdc->shd", c, these(lp["wk_b"]))
        v = jnp.einsum("sc,hcd->shd", c, these(lp["wv_b"]))
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_r[:, None, :], (S, hb, rope))], -1)
        o = _masked(q, k, v, scale, chosen).reshape(S, hb * vd)
        wo = lax.dynamic_slice_in_dim(lp["wo"], b * hb * vd, hb * vd, 0)
        return out + o @ wo.astype(F32)

    return lax.fori_loop(0, H // hb, heads,
                         jnp.zeros((S, lp["wo"].shape[1]), F32)), chosen


def _swiglu(m, gate, up, down):
    return (jax.nn.silu(m @ gate) * (m @ up)) @ down


def _dense_ffn(m, lp):
    f = lp["w_gate"].shape[1]
    n = math.gcd(f, FFN_BLOCKS)

    def part(b, out):
        cut = partial(lax.dynamic_slice_in_dim, start_index=b * (f // n),
                      slice_size=f // n)
        return out + _swiglu(m, cut(lp["w_gate"], axis=1).astype(F32),
                             cut(lp["w_up"], axis=1).astype(F32),
                             cut(lp["w_down"], axis=0).astype(F32))

    return lax.fori_loop(0, n, part, jnp.zeros_like(m))


def _route(m, router, bias, top_k, route_norm, route_scale):
    """(weights (T, E) over all the router's experts, zero where not
    chosen; chosen (T, K)). `bias` (E,) is added for the choice alone."""
    sc = jax.nn.sigmoid(m @ router.astype(F32))
    # A stable sort of the negated scores: ties go to the lower index.
    chosen = jnp.argsort(-(sc + bias.astype(F32)), axis=-1,
                         stable=True)[:, :top_k]
    w = jnp.take_along_axis(sc, chosen, axis=-1)
    if route_norm:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    w = w * route_scale
    rows = jnp.arange(m.shape[0])[:, None]
    return jnp.zeros_like(sc).at[rows, chosen].set(w), chosen


def _held_experts(m, lp, weights, first):
    """sum over the held experts e of weights[:, first + e] * E_e(m): every
    held expert on every token, one expert cast at a time."""
    E = lp["w_gate"].shape[0]

    def one(e, acc):
        w = lax.dynamic_slice_in_dim(weights, first + e, 1, 1)
        return acc + w * _swiglu(m, *(lax.dynamic_index_in_dim(
            lp[n], e, 0, keepdims=False).astype(F32)
            for n in ("w_gate", "w_up", "w_down")))

    return lax.fori_loop(0, E, one, jnp.zeros_like(m))


@partial(jax.jit, static_argnums=(3, 4))
def _layer(x, leaves, index, routed: bool, a: Tuple):
    """One layer; `leaves` are a group's stacked weights, `index` says
    which layer of them (only that one is read). -> (x, experts chosen
    (T, K), rows chosen (T, T) bool)."""
    *attn, top_k, norm, scale, first, Hi, Di, topk = a
    eps = attn[-1]
    lp = {k: lax.dynamic_index_in_dim(v, index, 0, keepdims=False)
          for k, v in leaves.items()}
    o, rows = _attention(_rms(x, lp["attn_norm"], eps), lp, tuple(attn),
                         (Hi, Di, topk))
    x = x + o
    m = _rms(x, lp["ffn_norm"], eps)
    chosen = jnp.zeros((x.shape[0], 0), jnp.int32)
    if routed:
        weights, chosen = _route(m, lp["router"], lp["router_bias"], top_k,
                                 norm, scale)
        f = _held_experts(m, lp, weights, first)
        if "shared_gate" in lp:
            f = f + _swiglu(m, *(lp[n].astype(F32) for n in (
                "shared_gate", "shared_up", "shared_down")))
    else:
        f = _dense_ffn(m, lp)
    return x + f, chosen, rows


@jax.jit
def _embed(table, tokens):
    return table[tokens].astype(F32)


@partial(jax.jit, static_argnums=(3,))
def _head(x, norm, head, eps):
    xn = _rms(x, norm, eps)
    V = head.shape[1]
    n = math.gcd(V, VOCAB_BLOCKS)
    return jnp.concatenate(
        [xn @ head[:, b * V // n:(b + 1) * V // n].astype(F32)
         for b in range(n)], axis=-1)


def _static(arch: Dict[str, Any]) -> Tuple:
    if arch.get("score_func", "sigmoid") != "sigmoid":
        raise ValueError("glm_dsa_decoder: score_func must be 'sigmoid'")
    return (int(arch["n_heads"]), int(arch["qk_nope_head_dim"]),
            int(arch["qk_rope_head_dim"]), int(arch["v_head_dim"]),
            int(arch["kv_lora_rank"]), float(arch["rope_theta"]),
            float(arch["norm_eps"]), int(arch["moe_top_k"]),
            bool(arch.get("route_norm", True)),
            float(arch.get("route_scale", 1.0)),
            int(arch.get("moe_first_expert", 0)),
            int(arch["index_n_heads"]), int(arch["index_head_dim"]),
            int(arch["index_topk"]))


def _forward(arch, params, tokens, keep_rows: bool = False):
    if arch.get("tie_embeddings"):
        raise ValueError("glm_dsa_decoder: the head is untied")
    with jax.default_matmul_precision("highest"):
        x = _embed(params["embed"], jnp.asarray(tokens, jnp.int32))
        a, chosen, rows = _static(arch), [], []
        for key, index, routed in layer_table(arch):
            x, picked, seen = _layer(x, params[key], jnp.int32(index),
                                     routed, a)
            if routed:
                chosen.append(picked)
            if keep_rows:
                rows.append(seen)
        return _head(x, params["final_norm"], params["lm_head"],
                     float(arch["norm_eps"])), chosen, rows


def forward_logits(arch: Dict[str, Any], params: Dict[str, Any], tokens
                   ) -> jax.Array:
    """tokens (S,) -> float32 logits (S, V) of one sequence."""
    return _forward(arch, params, tokens)[0]


def chosen_experts(arch: Dict[str, Any], params: Dict[str, Any], tokens
                   ) -> List[jax.Array]:
    """The experts each routed layer chooses among all its router scores,
    in layer order, each (S, K): beside the program's own, they tell a
    routing flip from arithmetic."""
    return _forward(arch, params, tokens)[1]


def chosen_rows(arch: Dict[str, Any], params: Dict[str, Any], tokens
                ) -> List[jax.Array]:
    """The rows each layer's indexer chooses for each query, in layer
    order, each bool (S, S) ([t, s]: query t attends row s): beside the
    program's own, they tell a flip of the choice from arithmetic."""
    return _forward(arch, params, tokens, keep_rows=True)[2]


@jax.jit
def _nll_sum(logits, targets):
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
    return jnp.sum(logz - gold)


def loss(arch: Dict[str, Any], params: Dict[str, Any], tokens, targets
         ) -> float:
    """Mean next-token cross entropy over a batch (B, S), one sequence at
    a time. The system does not train this architecture; the loss is here
    because every reference brings one."""
    total, count = 0.0, 0
    for row, tgt in zip(tokens, targets):
        logits = forward_logits(arch, params, row)
        total += float(_nll_sum(logits, jnp.asarray(tgt, jnp.int32)))
        count += len(tgt)
    return total / count


def routed_layer_output(arch: Dict[str, Any], lp: Dict[str, Any], m
                        ) -> jax.Array:
    """What one routed layer adds for normed m (T, d) before its
    post-norm: shared expert and the held experts' part. For the test that
    ties a share to the uncut layer."""
    a = _static(arch)
    with jax.default_matmul_precision("highest"):
        m = jnp.asarray(m, F32)
        weights, _ = _route(m, lp["router"], lp["router_bias"], a[7], a[8],
                            a[9])
        f = _held_experts(m, lp, weights, a[10])
        if "shared_gate" in lp:
            f = f + _swiglu(m, *(lp[n].astype(F32) for n in (
                "shared_gate", "shared_up", "shared_down")))
        return f


# -- what the routed products must move and compute --------------------------

def moe_experts_min_bytes(arch: Dict[str, Any], experts_hit: float,
                          rows: float, bytes_per: int = 2) -> float:
    """The least bytes the routed products can move, for `experts_hit`
    (held expert, layer, step) triples that held a row and `rows` kept
    token-expert pairs: the three matrices of each expert hit, once, and
    each pair's row in and out."""
    d, f = int(arch["d_model"]), int(arch["moe_d_ff"])
    return bytes_per * (experts_hit * 3 * d * f + rows * 2 * d)


def moe_experts_flops(arch: Dict[str, Any], rows: float) -> float:
    """Operations of the routed products for `rows` kept pairs: three
    matrices of d x f, a multiply and an add each."""
    d, f = int(arch["d_model"]), int(arch["moe_d_ff"])
    return rows * 3 * 2 * d * f


# -- what the indexer and the attention over its choice must move and compute --

def latent_row_bytes(arch: Dict[str, Any], bytes_per: int = 2) -> int:
    """Bytes a token a layer keeps for attention: the latent vector and
    the rotary key."""
    return bytes_per * (int(arch["kv_lora_rank"])
                        + int(arch["qk_rope_head_dim"]))


def sparse_attn_min_bytes(arch: Dict[str, Any], rows_read: float,
                          bytes_per: int = 2) -> float:
    """The least bytes a decode step's attention can move over `rows_read`
    chosen rows a layer (a slot's min(rows held, index_topk), summed over
    the slots): each chosen row once, keys and values together, every
    layer. Queries, outputs and the chosen rows' indices (4 B a row) are
    left out."""
    return rows_read * latent_row_bytes(arch, bytes_per) \
        * int(arch["n_layers"])


def sparse_attn_flops(arch: Dict[str, Any], rows_read: float) -> float:
    """Operations of a decode step's attention in the latent space over
    `rows_read` chosen rows a layer: every head's query against the row's
    kv_lora_rank + rope values, and its probability times the row's
    kv_lora_rank values, a multiply and an add each, every layer."""
    kvr, rope = int(arch["kv_lora_rank"]), int(arch["qk_rope_head_dim"])
    return rows_read * int(arch["n_heads"]) * 2 * (2 * kvr + rope) \
        * int(arch["n_layers"])


def indexer_min_bytes(arch: Dict[str, Any], rows_scored: float,
                      bytes_per: int = 2) -> float:
    """The least bytes a decode step's indexer can move over `rows_scored`
    held rows a layer: each row's indexer key once, every layer. The
    scores written and read again by the choice (4 B a row each way) are
    left out: the choice could be made where the scores are."""
    return rows_scored * bytes_per * int(arch["index_head_dim"]) \
        * int(arch["n_layers"])


def indexer_flops(arch: Dict[str, Any], rows_scored: float) -> float:
    """Operations of a decode step's indexer over `rows_scored` held rows
    a layer: every indexer head's query against the row's key, a multiply
    and an add each, every layer. The choice itself counts nothing."""
    return rows_scored * int(arch["index_n_heads"]) * 2 \
        * int(arch["index_head_dim"]) * int(arch["n_layers"])


def chosen_pairs(arch: Dict[str, Any], n_tokens: int) -> float:
    """(query, row) pairs a prompt of `n_tokens` attends, a layer: query
    t attends min(t + 1, index_topk) rows."""
    n, k = int(n_tokens), int(arch["index_topk"])
    full = min(n, k)
    return full * (full + 1) / 2 + (n - full) * k


def sparse_prefill_attn_flops(arch: Dict[str, Any], n_tokens: int) -> float:
    """Operations of a prompt's attention over the chosen pairs, every
    layer, in the per-head order (scores nope + rope wide, values
    v_head_dim wide, a multiply and an add each): the same work whatever
    order the program takes."""
    per_pair = 2.0 * int(arch["n_heads"]) * (
        int(arch["qk_nope_head_dim"]) + int(arch["qk_rope_head_dim"])
        + int(arch["v_head_dim"]))
    return chosen_pairs(arch, n_tokens) * per_pair * int(arch["n_layers"])


def indexer_prefill_flops(arch: Dict[str, Any], n_tokens: int) -> float:
    """Operations of the indexer's scores over every causal pair of a
    prompt, every layer."""
    n = int(n_tokens)
    return n * (n + 1) / 2 * 2.0 * int(arch["index_n_heads"]) \
        * int(arch["index_head_dim"]) * int(arch["n_layers"])


# -- what the architecture costs ---------------------------------------------

def _matmul_params_used(arch: Dict[str, Any], routed: bool) -> int:
    """Matmul parameters a token uses in one layer on this chip: the
    latent attention's five projections, the indexer's three and, routed,
    the router, the shared expert and the token's kept experts (its
    `moe_top_k` by the share of the router's experts held here); else the
    dense FFN."""
    d, H = int(arch["d_model"]), int(arch["n_heads"])
    qr, kvr = int(arch["q_lora_rank"]), int(arch["kv_lora_rank"])
    nope, rope, vd = (int(arch["qk_nope_head_dim"]),
                      int(arch["qk_rope_head_dim"]), int(arch["v_head_dim"]))
    Hi, Di = int(arch["index_n_heads"]), int(arch["index_head_dim"])
    attn = d * qr + qr * H * (nope + rope) + d * (kvr + rope) \
        + kvr * H * (nope + vd) + H * vd * d \
        + qr * Hi * Di + d * Di + d * Hi
    if not routed:
        return attn + 3 * d * int(arch["d_ff"])
    f = int(arch["moe_d_ff"])
    routed_e = int(arch.get("moe_router_experts") or arch["moe_experts"])
    kept = int(arch["moe_top_k"]) * int(arch["moe_experts"]) / routed_e
    return attn + d * routed_e + 3 * d * f * (
        int(arch.get("moe_shared_experts", 0)) + kept)


def prefill_flops(arch: Dict[str, Any], n_tokens: int) -> float:
    """Operations a prompt of `n_tokens` asks of its prefill on this chip:
    two a matmul parameter a token uses, every layer, the indexer's
    included (the experts by the share a uniform router keeps here); the
    indexer's scores over every causal pair (2 x index_n_heads x
    index_head_dim a pair); per-head attention over the *chosen* pairs
    only, min(t + 1, index_topk) a query (2 x heads x (nope + rope +
    v_head_dim) a pair); and the head at the one position whose logits a
    prefill needs. Padding, and the pairs a program attends under a mask
    without their being chosen, are the program's, not the model's."""
    n = int(n_tokens)
    table = layer_table(arch)
    return 2.0 * n * sum(_matmul_params_used(arch, r) for _, _, r in table) \
        + indexer_prefill_flops(arch, n) \
        + sparse_prefill_attn_flops(arch, n) \
        + 2.0 * int(arch["d_model"]) * int(arch["vocab_size"])


def train_flops_per_token(arch: Dict[str, Any], seq: int) -> float:
    """Forward and backward operations a trained token requires (6 per
    matmul parameter the token uses, 3 x the forward's indexer scores and
    chosen-pair attention at `seq` positions, a token's mean). The system
    does not train this architecture (`transformer.forward` raises); the
    count is here because every reference brings one."""
    table = layer_table(arch)
    pairs = (indexer_prefill_flops(arch, seq)
             + sparse_prefill_attn_flops(arch, seq)) / max(int(seq), 1)
    return 6.0 * (sum(_matmul_params_used(arch, r) for _, _, r in table)
                  + int(arch["d_model"]) * int(arch["vocab_size"])) \
        + 3.0 * pairs
