"""The plain reference of the `pangu_ultra_moe` decoder (openPangu-Ultra-MoE:
multi-head latent attention, sandwich norms, a sigmoid-routed expert
layer): its forward pass in straightforward `jax.numpy`, float32, highest
matmul precision, to the interface `references/dense_decoder.py`
describes; and the least bytes and the operations of its routed products
and of its two orders of attention, for the roofline readers. Independent
of `ray_tpu/models`: the weights are read by leaf name (`dense_layers`,
`routed_layers`: leaves stacked over the group's layers), the architecture
from the configuration file's keys.

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
    s_ij     = (q_nope_i . k_nope_j + q_r_i . k_r_j) / sqrt(nope + rope), j <= i
    o        = softmax(s) v ;  x = x + RMSNorm_post_attn(o Wo)
    m        = RMSNorm_pre_mlp(x)
    dense layer:  f = Wdown(silu(Wgate m) * Wup m)
    routed layer: sc = sigmoid(m Wr) over all the router's experts; I = the
                  K largest (ties to the lower index); w = sc[I] / (sum sc[I]
                  + 1e-20) * route_scale;
                  f = Shared(m) + sum_{e in I, e held} w_e E_e(m)
    x        = x + RMSNorm_post_mlp(f)
    logits   = RMSNorm_final(x_L) Whead

No cache, no absorption (every row is up-projected and attended per head),
no kernels, no sort. "Held": the configuration says which experts this
chip holds (`moe_first_expert`, `moe_experts` of the router's
`moe_router_experts`); a chosen expert that is not held is another chip's
and its term is left out, as the program leaves it out. With all of them
held this is the uncut layer. It runs beside 9.8 GB of weights: one
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


def _causal(q, k, v, scale):
    """q, k (S, h, Dk), v (S, h, Dv) -> (S, h, Dv), a block of queries at
    a time over all the keys under a mask."""
    S = q.shape[0]
    blk = min(QUERY_BLOCK, S)
    pad = -S % blk
    qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
    j = jnp.arange(S)[None, :]

    def block(args):
        qs, start = args
        s = jnp.einsum("qhd,khd->hqk", qs, k) * scale
        seen = j <= start + jnp.arange(blk)[:, None]
        p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v)

    out = lax.map(block, (qp.reshape((-1, blk) + q.shape[1:]),
                          jnp.arange((S + pad) // blk) * blk))
    return out.reshape((S + pad,) + out.shape[2:])[:S]


def _attention(h, lp, a):
    """The latent attention of normed h (S, d) -> (S, d) before the
    post-norm, a block of heads at a time: the block's columns of Wqb,
    Wkb, Wvb and rows of Wo are cast and used, the next block's follow."""
    H, nope, rope, vd, kvr, theta, eps = a
    S = h.shape[0]
    c_q = _rms(h @ lp["wq_a"].astype(F32), lp["q_a_norm"], eps)
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
        o = _causal(q, k, v, scale).reshape(S, hb * vd)
        wo = lax.dynamic_slice_in_dim(lp["wo"], b * hb * vd, hb * vd, 0)
        return out + o @ wo.astype(F32)

    return lax.fori_loop(0, H // hb, heads,
                         jnp.zeros((S, lp["wo"].shape[1]), F32))


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


def _route(m, router, top_k, route_norm, route_scale):
    """(weights (T, E) over all the router's experts, zero where not
    chosen; chosen (T, K))."""
    sc = jax.nn.sigmoid(m @ router.astype(F32))
    # A stable sort of the negated scores: ties go to the lower index.
    chosen = jnp.argsort(-sc, axis=-1, stable=True)[:, :top_k]
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
    which layer of them (only that one is read)."""
    *attn, top_k, norm, scale, first = a
    eps = attn[-1]
    lp = {k: lax.dynamic_index_in_dim(v, index, 0, keepdims=False)
          for k, v in leaves.items()}
    o = _attention(_rms(x, lp["attn_norm"], eps), lp, tuple(attn))
    x = x + _rms(o, lp["post_attn_norm"], eps)
    m = _rms(x, lp["ffn_norm"], eps)
    chosen = jnp.zeros((x.shape[0], 0), jnp.int32)
    if routed:
        weights, chosen = _route(m, lp["router"], top_k, norm, scale)
        f = _held_experts(m, lp, weights, first)
        if "shared_gate" in lp:
            f = f + _swiglu(m, *(lp[n].astype(F32) for n in (
                "shared_gate", "shared_up", "shared_down")))
    else:
        f = _dense_ffn(m, lp)
    return x + _rms(f, lp["post_ffn_norm"], eps), chosen


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
        raise ValueError("pangu_mla_decoder: score_func must be 'sigmoid'")
    return (int(arch["n_heads"]), int(arch["qk_nope_head_dim"]),
            int(arch["qk_rope_head_dim"]), int(arch["v_head_dim"]),
            int(arch["kv_lora_rank"]), float(arch["rope_theta"]),
            float(arch["norm_eps"]), int(arch["moe_top_k"]),
            bool(arch.get("route_norm", True)),
            float(arch.get("route_scale", 1.0)),
            int(arch.get("moe_first_expert", 0)))


def _forward(arch, params, tokens):
    if arch.get("tie_embeddings"):
        raise ValueError("pangu_mla_decoder: the head is untied")
    with jax.default_matmul_precision("highest"):
        x = _embed(params["embed"], jnp.asarray(tokens, jnp.int32))
        a, chosen = _static(arch), []
        for key, index, routed in layer_table(arch):
            x, picked = _layer(x, params[key], jnp.int32(index), routed, a)
            if routed:
                chosen.append(picked)
        return _head(x, params["final_norm"], params["lm_head"],
                     float(arch["norm_eps"])), chosen


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
        weights, _ = _route(m, lp["router"], a[7], a[8], a[9])
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


# -- what attention must move and compute, in its two orders -----------------

def latent_row_bytes(arch: Dict[str, Any], bytes_per: int = 2) -> int:
    """Bytes a token a layer keeps: the latent vector and the rotary key."""
    return bytes_per * (int(arch["kv_lora_rank"])
                        + int(arch["qk_rope_head_dim"]))


def latent_attn_min_bytes(arch: Dict[str, Any], rows_held: float,
                          bytes_per: int = 2) -> float:
    """The least bytes a decode step's attention can move over `rows_held`
    held rows a layer: each row once, keys and values together, every
    layer. Queries and outputs (a slot's heads x C, once) are left out: a
    thousandth of the rows at the cell's lengths."""
    return rows_held * latent_row_bytes(arch, bytes_per) \
        * int(arch["n_layers"])


def latent_attn_flops(arch: Dict[str, Any], rows_held: float) -> float:
    """Operations of a decode step's attention in the latent space over
    `rows_held` held rows a layer: every head's query against the row's
    kv_lora_rank + rope values, and its probability times the row's
    kv_lora_rank values, a multiply and an add each, every layer."""
    kvr, rope = int(arch["kv_lora_rank"]), int(arch["qk_rope_head_dim"])
    return rows_held * int(arch["n_heads"]) * 2 * (2 * kvr + rope) \
        * int(arch["n_layers"])


def prefill_attn_flops_bytes(arch: Dict[str, Any], rows: int, seq: int,
                             bytes_per: int = 2) -> Dict[str, float]:
    """One layer's attention over a tile of `rows` x `seq` positions, per
    head after the up-projection: the pairs at or under the diagonal,
    scores nope + rope wide and values v_head_dim wide, a multiply and an
    add each; q, k, v read and o written once."""
    H = int(arch["n_heads"])
    dk = int(arch["qk_nope_head_dim"]) + int(arch["qk_rope_head_dim"])
    dv = int(arch["v_head_dim"])
    pairs = rows * H * seq * (seq + 1) / 2
    return {"flops": 2.0 * pairs * (dk + dv),
            "bytes": float(bytes_per * rows * seq * H * 2 * (dk + dv))}


# -- what the architecture costs ---------------------------------------------

def _matmul_params_used(arch: Dict[str, Any], routed: bool) -> int:
    """Matmul parameters a token uses in one layer on this chip: the
    latent attention's five projections and, routed, the router, the
    shared expert and the token's kept experts (its `moe_top_k` by the
    share of the router's experts held here); else the dense FFN."""
    d, H = int(arch["d_model"]), int(arch["n_heads"])
    qr, kvr = int(arch["q_lora_rank"]), int(arch["kv_lora_rank"])
    nope, rope, vd = (int(arch["qk_nope_head_dim"]),
                      int(arch["qk_rope_head_dim"]), int(arch["v_head_dim"]))
    attn = d * qr + qr * H * (nope + rope) + d * (kvr + rope) \
        + kvr * H * (nope + vd) + H * vd * d
    if not routed:
        return attn + 3 * d * int(arch["d_ff"])
    f = int(arch["moe_d_ff"])
    routed_e = int(arch.get("moe_router_experts") or arch["moe_experts"])
    kept = int(arch["moe_top_k"]) * int(arch["moe_experts"]) / routed_e
    return attn + d * routed_e + 3 * d * f * (
        int(arch.get("moe_shared_experts", 0)) + kept)


def prefill_flops(arch: Dict[str, Any], n_tokens: int) -> float:
    """Operations a prompt of `n_tokens` asks of its prefill on this chip:
    two a matmul parameter a token uses, every layer (the experts by the
    share a uniform router keeps here); per-head attention of each
    (query, key) pair under the diagonal (2 x heads x (nope + rope +
    v_head_dim) a pair); and the head at the one position whose logits a
    prefill needs. Padding is the program's, not the model's."""
    n = int(n_tokens)
    table = layer_table(arch)
    attn = sum(prefill_attn_flops_bytes(arch, 1, n)["flops"] for _ in table)
    return 2.0 * n * sum(_matmul_params_used(arch, r) for _, _, r in table) \
        + attn + 2.0 * int(arch["d_model"]) * int(arch["vocab_size"])


def train_flops_per_token(arch: Dict[str, Any], seq: int) -> float:
    """Forward and backward operations a trained token requires (6 per
    matmul parameter the token uses, 3 x the forward's attention at `seq`
    keys). The system does not train this architecture
    (`transformer.forward` raises); the count is here because every
    reference brings one."""
    table = layer_table(arch)
    H = int(arch["n_heads"])
    per_key = 2.0 * H * (int(arch["qk_nope_head_dim"])
                         + int(arch["qk_rope_head_dim"])
                         + int(arch["v_head_dim"]))
    return 6.0 * (sum(_matmul_params_used(arch, r) for _, _, r in table)
                  + int(arch["d_model"]) * int(arch["vocab_size"])) \
        + 3.0 * per_key * seq / 2 * len(table)
