"""The plain reference of the `solar_open2` decoder (Upstage Solar Open 2:
periods of one softmax GQA layer with no position and an output gate,
then gated delta-rule linear-attention layers with a decay a channel,
Kimi Linear's "KDA", arXiv:2510.26692; every layer sigmoid-routed with a
selection bias beside a shared expert): its forward pass in
straightforward `jax.numpy`, float32, highest matmul precision, to the
interface `references/dense_decoder.py` describes; and the bytes and the
operations of its routed products and of its recurrence, for the
roofline readers. Independent of `ray_tpu/models` and `ray_tpu/ops`: the
weights are read by leaf name (`periods`: what every layer has stacked
over periods and a period's layers; `global0`, `linear0`, `linear1`, ...
under it, a layer's own leaves under its kind and its place among the
period's layers of the kind, stacked over periods), the architecture from
the configuration file's keys.

The layer, for input x (T x d), as the configuration file's `published`
and `assumed` state it; H heads of dk = dv = `linear_head_dim`:

    x0       = Embed[tok]
    h        = RMSNorm_in(x)
    GQA layer (the first of a period):
      q, k, v = h Wq, h Wk, h Wv          no rotary, no q/k norm
      o       = softmax(q k^T / sqrt(head_dim), causal) v
      x       = x + (o * sigmoid(h Wg)) Wo
    linear layer (the others):
      q, k, v = silu(conv(h Wq)), silu(conv(h Wk)), silu(conv(h Wv))
                conv: causal, depthwise, over the last `linear_conv_kernel`
                positions, zeros before the first
      q, k    = q / sqrt(|q|^2 + 1e-6) * dk^-0.5,  k / sqrt(|k|^2 + 1e-6)
      g_t     = -exp(A_log) * softplus((h f_a) f_b + dt_bias)    (H, dk)
      beta_t  = 2 sigmoid(h Wb)                                   (H,)
      S'      = diag(exp(g_t)) S_{t-1}                 S (dk, dv) a head
      S_t     = S' + beta_t k_t (v_t - S'^T k_t)^T ;   o_t = S_t^T q_t
      x       = x + (RMSNorm_o(o) * sigmoid((h g_a) g_b + g_bias)) Wo
    m        = RMSNorm_ffn(x)
    sc       = sigmoid(m Wr) over all the router's experts; I = the K
               largest of sc + bias (ties to the lower index); w = sc[I] /
               (sum sc[I] + 1e-20) * route_scale
    x        = x + Shared(m) + sum_{e in I, e held} w_e E_e(m)
    logits   = RMSNorm_final(x_L) Whead

No cache, no chunks, no kernels: the recurrence a token at a time
(`lax.scan` over the positions, every head's state in its carry), the
GQA layer's scores a key-value head's group at a time. "Held": the configuration
says which experts this chip holds (`moe_first_expert`, `moe_experts` of
the router's `moe_router_experts`); a chosen expert that is not held is
another chip's and its term is left out, as the program leaves it out.
With all of them held this is the uncut layer. One layer's weights are
read at a time, experts one at a time, the head in blocks of its columns.

Departures from the published description: none known; what the catalog
row does not carry is listed under the configuration file's `assumed`.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32
VOCAB_BLOCKS = 4
L2_EPS = 1e-6            # the l2 norm of a linear layer's q and k
GLOBAL, LINEAR = "global", "linear"


def layer_table(arch: Dict[str, Any]) -> List[Tuple[int, int, str, int]]:
    """[(period, place in it, kind, place among the period's layers of
    the kind)] in layer order: a GQA layer opens each period."""
    every = int(arch["global_attn_every"])
    return [(p, j, GLOBAL if j == 0 else LINEAR, max(j - 1, 0))
            for p in range(int(arch["n_layers"]) // every)
            for j in range(every)]


def _rms(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * w.astype(F32)


def _gqa(h, lp, a):
    H, KVH, Dh = a
    S = h.shape[0]
    q = (h @ lp["wq"].astype(F32)).reshape(S, KVH, H // KVH, Dh)
    k = (h @ lp["wk"].astype(F32)).reshape(S, KVH, Dh)
    v = (h @ lp["wv"].astype(F32)).reshape(S, KVH, Dh)
    seen = jnp.tril(jnp.ones((S, S), bool))

    def group(args):                     # one KV head and its query heads
        qg, kg, vg = args                # (S, G, Dh), (S, Dh), (S, Dh)
        s = jnp.einsum("tgd,sd->gts", qg, kg) / math.sqrt(Dh)
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("gts,sd->tgd", p, vg)

    o = lax.map(group, (jnp.moveaxis(q, 1, 0), jnp.moveaxis(k, 1, 0),
                        jnp.moveaxis(v, 1, 0)))          # (KVH, S, G, Dh)
    o = jnp.moveaxis(o, 0, 1).reshape(S, H * Dh)
    return (o * jax.nn.sigmoid(h @ lp["wg"].astype(F32))) \
        @ lp["wo"].astype(F32)


def _conv(x, w):
    """Causal depthwise convolution: x (S, C), w (K, C); y_t = sum_i w_i
    x_{t - K + 1 + i}, zeros before the first position."""
    K, S = w.shape[0], x.shape[0]
    padded = jnp.pad(x, ((K - 1, 0), (0, 0)))
    return sum(w[i].astype(F32) * padded[i:i + S] for i in range(K))


def _recurrence(q, k, v, g, beta):
    """The gated delta rule a token at a time: q, k, g (S, H, dk), v (S,
    H, dv), beta (S, H) -> o (S, H, dv)."""
    H, dk, dv = k.shape[1], k.shape[2], v.shape[2]

    def one(S0, xs):
        q, k, v, g, beta = xs
        S1 = jnp.exp(g)[:, :, None] * S0
        u = beta[:, None] * (v - jnp.einsum("hk,hkv->hv", k, S1))
        S1 = S1 + k[:, :, None] * u[:, None, :]
        return S1, jnp.einsum("hk,hkv->hv", q, S1)

    return lax.scan(one, jnp.zeros((H, dk, dv), F32), (q, k, v, g, beta))[1]


def _linear(h, lp, a, eps):
    H, D = a
    S = h.shape[0]
    mix = jnp.concatenate([h @ lp[w].astype(F32) for w in ("wq", "wk", "wv")],
                          axis=-1)
    q, k, v = (x.reshape(S, H, D) for x in jnp.split(
        jax.nn.silu(_conv(mix, lp["conv"])), 3, axis=-1))

    def unit(x):
        return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)

    q, k = unit(q) * D ** -0.5, unit(k)
    decay = (h @ lp["f_a"].astype(F32)) @ lp["f_b"].astype(F32) \
        + lp["dt_bias"].astype(F32)
    g = -jnp.exp(lp["A_log"].astype(F32))[:, None] \
        * jax.nn.softplus(decay.reshape(S, H, D))
    beta = 2.0 * jax.nn.sigmoid(h @ lp["wb"].astype(F32))
    o = _recurrence(q, k, v, g, beta)
    gate = (h @ lp["g_a"].astype(F32)) @ lp["g_b"].astype(F32) \
        + lp["g_bias"].astype(F32)
    o = _rms(o, lp["o_norm"], eps) * jax.nn.sigmoid(gate.reshape(S, H, D))
    return o.reshape(S, H * D) @ lp["wo"].astype(F32)


def _swiglu(m, gate, up, down):
    return (jax.nn.silu(m @ gate) * (m @ up)) @ down


def _route(m, router, bias, top_k, route_norm, route_scale):
    """(weights (T, E) over all the router's experts, zero where not
    chosen; chosen (T, K))."""
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


def _ffn(m, lp, r):
    top_k, norm, scale, first = r
    weights, chosen = _route(m, lp["router"], lp["router_bias"], top_k, norm,
                             scale)
    f = _held_experts(m, lp, weights, first)
    if "shared_gate" in lp:
        f = f + _swiglu(m, *(lp[n].astype(F32) for n in (
            "shared_gate", "shared_up", "shared_down")))
    return f, chosen


@partial(jax.jit, static_argnums=(3, 4, 5))
def _layer(x, leaves, at, kind: str, own_at: int, a: Tuple):
    """One layer; `leaves` are the periods' stacked weights, `at` (period,
    place) says which layer of them, `own_at` its place among the
    period's layers of its kind (only that layer is read)."""
    gqa, linear, eps, routing = a

    def pick(v, i):
        return lax.dynamic_index_in_dim(v, i, 0, keepdims=False)

    lp = {k: pick(pick(v, at[0]), at[1]) for k, v in leaves.items()
          if not isinstance(v, dict)}
    lp.update({k: pick(v, at[0])
               for k, v in leaves[f"{kind}{own_at}"].items()})
    h = _rms(x, lp["attn_norm"], eps)
    x = x + (_gqa(h, lp, gqa) if kind == GLOBAL
             else _linear(h, lp, linear, eps))
    f, chosen = _ffn(_rms(x, lp["ffn_norm"], eps), lp, routing)
    return x + f, chosen


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
        raise ValueError("solar_kda_decoder: score_func must be 'sigmoid'")
    head = int(arch.get("head_dim") or arch["d_model"] // arch["n_heads"])
    return ((int(arch["n_heads"]), int(arch["n_kv_heads"]), head),
            (int(arch["linear_n_heads"]), int(arch["linear_head_dim"])),
            float(arch["norm_eps"]),
            (int(arch["moe_top_k"]), bool(arch.get("route_norm", True)),
             float(arch.get("route_scale", 1.0)),
             int(arch.get("moe_first_expert", 0))))


def _forward(arch, params, tokens):
    if arch.get("tie_embeddings"):
        raise ValueError("solar_kda_decoder: the head is untied")
    with jax.default_matmul_precision("highest"):
        x = _embed(params["embed"], jnp.asarray(tokens, jnp.int32))
        a, chosen = _static(arch), []
        for p, j, kind, own in layer_table(arch):
            x, picked = _layer(x, params["periods"],
                               jnp.asarray([p, j], jnp.int32), kind, own, a)
            chosen.append(picked)
        return _head(x, params["final_norm"], params["lm_head"],
                     float(arch["norm_eps"])), chosen


def forward_logits(arch: Dict[str, Any], params: Dict[str, Any], tokens
                   ) -> jax.Array:
    """tokens (S,) -> float32 logits (S, V) of one sequence."""
    return _forward(arch, params, tokens)[0]


def chosen_experts(arch: Dict[str, Any], params: Dict[str, Any], tokens
                   ) -> List[jax.Array]:
    """The experts each layer chooses among all its router scores, in
    layer order, each (S, K): beside the program's own, they tell a
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
    """What one routed layer adds for normed m (T, d): the shared expert
    and the held experts' part. For the test that ties a share to the
    uncut layer."""
    with jax.default_matmul_precision("highest"):
        return _ffn(jnp.asarray(m, F32), lp, _static(arch)[3])[0]


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


# -- what the recurrence must move and compute -------------------------------

def linear_layers(arch: Dict[str, Any]) -> int:
    return sum(kind == LINEAR for *_, kind, _ in layer_table(arch))


def kda_state_bytes(arch: Dict[str, Any], live_slot_steps: float) -> float:
    """The least bytes the decode steps' recurrence can move over
    `live_slot_steps` updates (an owned slot, a step, a linear layer: the
    engine's `linear_slot_steps_live`): every head's float32 state read
    once and written once. What else an update reads (q, k, v, g, o: 5 x
    H x dk values against H x dk x dv) is a hundredth of it and left
    out."""
    H, D = int(arch["linear_n_heads"]), int(arch["linear_head_dim"])
    return live_slot_steps * 2.0 * 4 * H * D * D


def kda_flops_bytes(arch: Dict[str, Any], tokens: float, bytes_per: int = 2
                    ) -> Dict[str, float]:
    """The recurrence of `tokens` real (token, linear layer) pairs of a
    tile (the engine's `linear_tokens`), whatever order implements it: a
    head's update a token is the decay of S (dk dv), S'^T k, the rank-one
    term and S^T q, a multiply and an add each but the decay: 7 dk dv;
    q, k, v and o are read or written once in the activation dtype, g in
    float32 and beta a head. A chunked order that multiplies chunks of C
    tokens does more operations than these (the triangular system and
    the products inside a chunk) and moves the state once a chunk."""
    H, D = int(arch["linear_n_heads"]), int(arch["linear_head_dim"])
    return {"flops": tokens * H * 7.0 * D * D,
            "bytes": tokens * H * (bytes_per * 4 * D + 4 * D + 4)}


# -- what the architecture costs ---------------------------------------------

def _matmul_params_used(arch: Dict[str, Any], kind: str) -> float:
    """Matmul parameters a token uses in one layer on this chip: the
    attention half's projections (a linear layer's gates through their
    low rank) and the router, the shared expert and the token's kept
    experts (its `moe_top_k` by the share of the router's experts held
    here)."""
    d = int(arch["d_model"])
    if kind == GLOBAL:
        head = int(arch.get("head_dim") or d // arch["n_heads"])
        q, kv = int(arch["n_heads"]) * head, int(arch["n_kv_heads"]) * head
        attn = d * (3 * q + 2 * kv)
    else:
        H, D = int(arch["linear_n_heads"]), int(arch["linear_head_dim"])
        attn = 4 * d * H * D + 2 * (d * D + D * H * D) + d * H
    f = int(arch["moe_d_ff"])
    routed = int(arch.get("moe_router_experts") or arch["moe_experts"])
    kept = int(arch["moe_top_k"]) * int(arch["moe_experts"]) / routed
    return attn + d * routed + 3 * d * f * (
        int(arch.get("moe_shared_experts", 0)) + kept)


def prefill_flops(arch: Dict[str, Any], n_tokens: int) -> float:
    """Operations a prompt of `n_tokens` asks of its prefill on this chip:
    two a matmul parameter a token uses, every layer (the experts by the
    share a uniform router keeps here); a GQA layer's attention of each
    (query, key) pair under the diagonal (2 x heads x 2 x head_dim a
    pair); a linear layer's recurrence (`kda_flops_bytes`) and its
    convolution; and the head at the one position whose logits a prefill
    needs. Padding is the program's, not the model's."""
    n = int(n_tokens)
    table = layer_table(arch)
    head = int(arch.get("head_dim") or arch["d_model"] // arch["n_heads"])
    H, D = int(arch["linear_n_heads"]), int(arch["linear_head_dim"])
    pairs = n * (n + 1) / 2
    total = 2.0 * int(arch["d_model"]) * int(arch["vocab_size"])
    for *_, kind, _ in table:
        total += 2.0 * n * _matmul_params_used(arch, kind)
        if kind == GLOBAL:
            total += 2.0 * pairs * int(arch["n_heads"]) * 2 * head
        else:
            total += kda_flops_bytes(arch, n)["flops"] + 2.0 * n * 3 * H * D \
                * int(arch.get("linear_conv_kernel", 4))
    return total


def train_flops_per_token(arch: Dict[str, Any], seq: int) -> float:
    """Forward and backward operations a trained token requires (6 per
    matmul parameter the token uses, 3 x the forward's attention at `seq`
    keys and 3 x its recurrence). The system does not train this
    architecture (`transformer.forward` raises); the count is here
    because every reference brings one."""
    table = layer_table(arch)
    head = int(arch.get("head_dim") or arch["d_model"] // arch["n_heads"])
    total = 6.0 * int(arch["d_model"]) * int(arch["vocab_size"])
    for *_, kind, _ in table:
        total += 6.0 * _matmul_params_used(arch, kind)
        if kind == GLOBAL:
            total += 3.0 * 2 * int(arch["n_heads"]) * 2 * head * seq / 2
        else:
            total += 3.0 * kda_flops_bytes(arch, 1)["flops"]
    return total
