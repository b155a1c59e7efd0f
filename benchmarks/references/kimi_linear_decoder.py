"""The plain reference of the `kimi_linear` decoder (Moonshot Kimi Linear,
arXiv:2510.26692: gated delta-rule linear-attention layers with a decay a
channel, "KDA", three to each multi-head latent attention layer with no
position, "NoPE MLA"; a leading layer with a dense SwiGLU, every other
layer sigmoid-routed with a selection bias beside a shared expert): its
forward pass in straightforward `jax.numpy`, float32, highest matmul
precision, to the interface `references/dense_decoder.py` describes; and
the bytes and the operations of its routed products, of its recurrence,
of its two orders of latent attention and of its whole decode step, for
the roofline readers. Independent of `ray_tpu` and of the other
references: the weights are read by leaf name, the architecture from the
configuration file's keys.

Which layer is of which kind is read from the published lists
(`linear_attn_config.kda_layers`, `full_attn_layers`, layers counted from
1). The weights lie in up to three groups: `dense_layers` (the leading
`n_dense_layers`, a layer a step), `periods` (whole periods of
`global_attn_every` layers, stacked over periods and a period's layers)
and `tail_layers` (what is left, shorter than a period, one step); what
every layer of a group has is stacked over its layers, a layer's own
attention leaves lie under its kind and its place among its step's layers
of the kind (`linear0`, `linear1`, `global0`, ...), stacked over steps; a
dense FFN's matrices lie with them.

The layer, for input x (T x d), as the configuration file's `published`
and `assumed` state it; H heads of dk = dv = `linear_head_dim` in a KDA
layer, `n_heads` heads in an MLA layer:

    x0       = Embed[tok]
    h        = RMSNorm_in(x)
    KDA layer:
      q, k, v = silu(conv(h Wq)), silu(conv(h Wk)), silu(conv(h Wv))
                conv: causal, depthwise, over the last `linear_conv_kernel`
                positions, zeros before the first
      q, k    = q / sqrt(|q|^2 + 1e-6) * dk^-0.5,  k / sqrt(|k|^2 + 1e-6)
      g_t     = -exp(A_log) * softplus((h f_a) f_b + dt_bias)    (H, dk)
      beta_t  = sigmoid(h Wb)                                     (H,)
      S'      = diag(exp(g_t)) S_{t-1}                 S (dk, dv) a head
      S_t     = S' + beta_t k_t (v_t - S'^T k_t)^T ;   o_t = S_t^T q_t
      x       = x + (RMSNorm_o(o) * sigmoid((h g_a) g_b + g_bias)) Wo
    MLA layer (no rank on the query, nothing rotated):
      q        = h Wq -> heads x [q_nope (nope) | q_r (rope)]   (Wq's columns
                                  kept as two leaves by what they make)
      [c | kr] = h Wkva                              (kv_lora_rank + rope)
      c        = RMSNorm_kva(c) ;  kr is one vector, shared by all heads
      k_nope   = c Wkb, v = c Wvb   per head (the published kv_b_proj, kept
                                  a head at a time: (H, nope, rank), (H, rank, v))
      s_ij     = (q_nope_i . k_nope_j + q_r_i . kr_j) / sqrt(nope + rope), j <= i
      x        = x + (softmax(s) v) Wo
    m        = RMSNorm_ffn(x)
    leading layer: f = Wdown(silu(Wgate m) * Wup m)
    routed layer:  sc = sigmoid(m Wr) over all the router's experts; I = the
                   K largest of sc + bias (ties to the lower index); w = sc[I]
                   / (sum sc[I] + 1e-20) * route_scale;
                   f = Shared(m) + sum_{e in I, e held} w_e E_e(m)
    x        = x + f
    logits   = RMSNorm_final(x_L) Whead

No cache, no chunks, no absorption, no kernels: the recurrence a token at
a time (`lax.scan` over the positions, every head's state in its carry),
every latent row up-projected and attended per head. "Held": the
configuration says which experts this chip holds (`moe_first_expert`,
`moe_experts` of the router's `moe_router_experts`); a chosen expert that
is not held is another chip's and its term is left out, as the program
leaves it out. With all of them held this is the uncut layer. One layer's
weights are read at a time, experts one at a time, attention a block of
heads and of queries at a time, the head in blocks of its columns.

Departures from the published description: none known; what the catalog
row does not carry is listed under the configuration file's `assumed`.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, List, NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32
VOCAB_BLOCKS = 4
HEAD_BLOCK = 16          # latent heads attended together
QUERY_BLOCK = 512
FFN_BLOCKS = 6           # slices of the dense FFN's width
L2_EPS = 1e-6            # the l2 norm of a KDA layer's q and k
GLOBAL, LINEAR = "global", "linear"


class Layer(NamedTuple):
    group: str      # the weights' key in `params`
    step: int       # its scan step there
    place: int      # its place among the step's layers
    kind: str       # GLOBAL or LINEAR
    own: int        # its place among the step's layers of its kind
    routed: bool


def layer_table(arch: Dict[str, Any]) -> List[Layer]:
    """Every layer in order, from the published lists."""
    n, dense = int(arch["n_layers"]), int(arch.get("n_dense_layers", 0))
    every = int(arch["global_attn_every"])
    lists = arch["linear_attn_config"]
    full = set(lists["full_attn_layers"])
    if sorted(list(lists["kda_layers"]) + list(full)) != list(range(1, n + 1)):
        raise ValueError("kimi_linear_decoder: kda_layers and "
                         "full_attn_layers must cover 1..n_layers once")
    kinds = [GLOBAL if i + 1 in full else LINEAR for i in range(n)]
    whole = (n - dense) // every * every
    table = []
    for i, kind in enumerate(kinds):
        if i < dense:
            group, step, place, first = "dense_layers", i, 0, i
        elif i < dense + whole:
            step, place = divmod(i - dense, every)
            group, first = "periods", i - place
        else:
            group, step, place = "tail_layers", 0, i - dense - whole
            first = dense + whole
        table.append(Layer(group, step, place, kind,
                           kinds[first:i].count(kind), i >= dense))
    return table


def _count(arch: Dict[str, Any], kind: str) -> int:
    return sum(layer.kind == kind for layer in layer_table(arch))


def linear_layers(arch: Dict[str, Any]) -> int:
    """The KDA layers (20 of the published 27)."""
    return _count(arch, LINEAR)


def latent_layers(arch: Dict[str, Any]) -> int:
    """The MLA layers (7 of the published 27)."""
    return _count(arch, GLOBAL)


def _rms(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * w.astype(F32)


# -- the KDA layer -----------------------------------------------------------

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
    beta = jax.nn.sigmoid(h @ lp["wb"].astype(F32))
    o = _recurrence(q, k, v, g, beta)
    gate = (h @ lp["g_a"].astype(F32)) @ lp["g_b"].astype(F32) \
        + lp["g_bias"].astype(F32)
    o = _rms(o, lp["o_norm"], eps) * jax.nn.sigmoid(gate.reshape(S, H, D))
    return o.reshape(S, H * D) @ lp["wo"].astype(F32)


# -- the MLA layer -----------------------------------------------------------

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


def _latent(h, lp, a, eps):
    """The latent attention of normed h (S, d) -> (S, d), a block of heads
    at a time: the block's columns of Wq, Wkb, Wvb and rows of Wo are cast
    and used, the next block's follow. Nothing is rotated."""
    H, nope, rope, vd, kvr = a
    S = h.shape[0]
    kv = h @ lp["wkv_a"].astype(F32)
    c = _rms(kv[:, :kvr], lp["kv_a_norm"], eps)
    k_r = kv[:, kvr:]                                      # (S, rope)
    hb = math.gcd(H, HEAD_BLOCK)
    scale = 1.0 / math.sqrt(nope + rope)

    def heads(b, out):
        def cols(w, width):
            return lax.dynamic_slice_in_dim(
                w, b * hb * width, hb * width, 1).astype(F32)

        def these(w):
            return lax.dynamic_slice_in_dim(w, b * hb, hb, 0).astype(F32)

        q = jnp.concatenate(
            [(h @ cols(lp["wq_nope"], nope)).reshape(S, hb, nope),
             (h @ cols(lp["wq_rope"], rope)).reshape(S, hb, rope)], -1)
        k_nope = jnp.einsum("sc,hdc->shd", c, these(lp["wk_b"]))
        v = jnp.einsum("sc,hcd->shd", c, these(lp["wv_b"]))
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_r[:, None, :], (S, hb, rope))], -1)
        o = _causal(q, k, v, scale).reshape(S, hb * vd)
        wo = lax.dynamic_slice_in_dim(lp["wo"], b * hb * vd, hb * vd, 0)
        return out + o @ wo.astype(F32)

    return lax.fori_loop(0, H // hb, heads,
                         jnp.zeros((S, lp["wo"].shape[1]), F32))


# -- the FFN -----------------------------------------------------------------

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


def _routed_ffn(m, lp, r):
    top_k, norm, scale, first = r
    weights, chosen = _route(m, lp["router"], lp["router_bias"], top_k, norm,
                             scale)
    f = _held_experts(m, lp, weights, first)
    if "shared_gate" in lp:
        f = f + _swiglu(m, *(lp[n].astype(F32) for n in (
            "shared_gate", "shared_up", "shared_down")))
    return f, chosen


# -- the stack ---------------------------------------------------------------

@partial(jax.jit, static_argnums=(3, 4, 5, 6))
def _layer(x, leaves, at, kind: str, own: int, routed: bool, a: Tuple):
    """One layer; `leaves` are a group's stacked weights, `at` (step,
    place) says which layer of them, `own` its place among the step's
    layers of its kind (only that layer is read)."""
    latent, linear, eps, routing = a

    def pick(v, i):
        return lax.dynamic_index_in_dim(v, i, 0, keepdims=False)

    # What every layer of the group has is stacked over (steps, places),
    # or over steps alone where a step is one layer (the leading ones).
    stacked = leaves["attn_norm"].ndim - 1
    lp = {k: (pick(pick(v, at[0]), at[1]) if stacked == 2
              else pick(v, at[0]))
          for k, v in leaves.items() if not isinstance(v, dict)}
    lp.update({k: pick(v, at[0])
               for k, v in leaves[f"{kind}{own}"].items()})
    h = _rms(x, lp["attn_norm"], eps)
    x = x + (_latent(h, lp, latent, eps) if kind == GLOBAL
             else _linear(h, lp, linear, eps))
    m = _rms(x, lp["ffn_norm"], eps)
    if routed:
        f, chosen = _routed_ffn(m, lp, routing)
    else:
        f, chosen = _dense_ffn(m, lp), jnp.zeros((x.shape[0], 0), jnp.int32)
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
        raise ValueError("kimi_linear_decoder: score_func must be 'sigmoid'")
    if arch.get("q_lora_rank"):
        raise ValueError("kimi_linear_decoder: the query has no rank "
                         "(q_lora_rank null)")
    return ((int(arch["n_heads"]), int(arch["qk_nope_head_dim"]),
             int(arch["qk_rope_head_dim"]), int(arch["v_head_dim"]),
             int(arch["kv_lora_rank"])),
            (int(arch["linear_n_heads"]), int(arch["linear_head_dim"])),
            float(arch["norm_eps"]),
            (int(arch["moe_top_k"]), bool(arch.get("route_norm", True)),
             float(arch.get("route_scale", 1.0)),
             int(arch.get("moe_first_expert", 0))))


def _forward(arch, params, tokens):
    if arch.get("tie_embeddings"):
        raise ValueError("kimi_linear_decoder: the head is untied")
    with jax.default_matmul_precision("highest"):
        x = _embed(params["embed"], jnp.asarray(tokens, jnp.int32))
        a, chosen = _static(arch), []
        for layer in layer_table(arch):
            x, picked = _layer(x, params[layer.group],
                               jnp.asarray([layer.step, layer.place],
                                           jnp.int32),
                               layer.kind, layer.own, layer.routed, a)
            if layer.routed:
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
    """What one routed layer adds for normed m (T, d): the shared expert
    and the held experts' part. For the test that ties a share to the
    uncut layer."""
    with jax.default_matmul_precision("highest"):
        return _routed_ffn(jnp.asarray(m, F32), lp, _static(arch)[3])[0]


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

def _state_values(arch: Dict[str, Any]) -> int:
    """Values of one KDA layer's state a slot: H heads of dk x dv."""
    H, D = int(arch["linear_n_heads"]), int(arch["linear_head_dim"])
    return H * D * D


def kda_state_bytes(arch: Dict[str, Any], live_slot_steps: float) -> float:
    """The least bytes the decode steps' recurrence can move over
    `live_slot_steps` updates (an owned slot, a step, a KDA layer: the
    engine's `linear_slot_steps_live`, which counts the stack's 20 such
    layers and no other): every head's float32 state read once and
    written once. What else an update reads (q, k, v, g, o: 5 x H x dk
    values against H x dk x dv) is a hundredth of it and left out."""
    return live_slot_steps * 2.0 * 4 * _state_values(arch)


def kda_flops_bytes(arch: Dict[str, Any], tokens: float, bytes_per: int = 2
                    ) -> Dict[str, float]:
    """The recurrence of `tokens` real (token, KDA layer) pairs of a tile
    (the engine's `linear_tokens`, over the 20 KDA layers), whatever
    order implements it: a head's update a token is the decay of S (dk
    dv), S'^T k, the rank-one term and S^T q, a multiply and an add each
    but the decay: 7 dk dv; q, k, v and o are read or written once in the
    activation dtype, g in float32 and beta a head. A chunked order that
    multiplies chunks of C tokens does more operations than these and
    moves the state once a chunk."""
    H, D = int(arch["linear_n_heads"]), int(arch["linear_head_dim"])
    return {"flops": tokens * H * 7.0 * D * D,
            "bytes": tokens * H * (bytes_per * 4 * D + 4 * D + 4)}


# -- what latent attention must move and compute, in its two orders ----------

def latent_row_bytes(arch: Dict[str, Any], bytes_per: int = 2) -> int:
    """Bytes a token an MLA layer keeps: the latent vector and the key
    values all heads share (512 + 64)."""
    return bytes_per * (int(arch["kv_lora_rank"])
                        + int(arch["qk_rope_head_dim"]))


def latent_attn_min_bytes(arch: Dict[str, Any], rows_held: float,
                          bytes_per: int = 2) -> float:
    """The least bytes a decode step's latent attention can move over
    `rows_held` held tokens (the engine's `cache_rows_held`: a held token
    once, not once a layer): each token's row once, keys and values
    together, in each of the 7 MLA layers and no other."""
    return rows_held * latent_row_bytes(arch, bytes_per) \
        * latent_layers(arch)


def latent_attn_flops(arch: Dict[str, Any], rows_held: float) -> float:
    """Operations of a decode step's attention in the latent space over
    `rows_held` held tokens: every head's query against the row's
    kv_lora_rank + rope values, and its probability times the row's
    kv_lora_rank values, a multiply and an add each, each MLA layer."""
    kvr, rope = int(arch["kv_lora_rank"]), int(arch["qk_rope_head_dim"])
    return rows_held * int(arch["n_heads"]) * 2 * (2 * kvr + rope) \
        * latent_layers(arch)


def _tile_attn(arch: Dict[str, Any], rows: int, seq: int, bytes_per: int
               ) -> Dict[str, float]:
    """One MLA layer's attention over a tile of `rows` x `seq` positions,
    per head after the up-projection: the pairs at or under the diagonal,
    scores nope + rope wide and values v_head_dim wide, a multiply and an
    add each; q, k, v read and o written once."""
    H = int(arch["n_heads"])
    dk = int(arch["qk_nope_head_dim"]) + int(arch["qk_rope_head_dim"])
    dv = int(arch["v_head_dim"])
    pairs = rows * H * seq * (seq + 1) / 2
    return {"flops": 2.0 * pairs * (dk + dv),
            "bytes": float(bytes_per * rows * seq * H * 2 * (dk + dv))}


def prefill_attn_flops_bytes(arch: Dict[str, Any], rows: int, seq: int,
                             bytes_per: int = 2) -> Dict[str, float]:
    """A tile's latent attention as its reader asks for it
    (`kernels.latent_prefill_attn_roofline_pct`: one layer's count, which
    it multiplies by `n_layers`): the 7 MLA layers' (`_tile_attn`) spread
    over all `n_layers`, so that the reader's product is the 7 layers'
    and never 27 layers'."""
    share = latent_layers(arch) / int(arch["n_layers"])
    return {k: v * share
            for k, v in _tile_attn(arch, rows, seq, bytes_per).items()}


# -- what a whole decode step must move --------------------------------------

def _attn_params(arch: Dict[str, Any], kind: str) -> int:
    """An attention half's matmul parameters, by kind of layer."""
    d = int(arch["d_model"])
    if kind == GLOBAL:
        H = int(arch["n_heads"])
        nope, rope, vd, kvr = (int(arch[k]) for k in (
            "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
            "kv_lora_rank"))
        return d * H * (nope + rope) + d * (kvr + rope) \
            + kvr * H * (nope + vd) + H * vd * d
    H, D = int(arch["linear_n_heads"]), int(arch["linear_head_dim"])
    return 4 * d * H * D + 2 * (d * D + D * H * D) + d * H


def _small_params(arch: Dict[str, Any], kind: str) -> int:
    """A layer's leaves that are no matrix: its two norms and, by kind,
    the latent norm, or the convolutions, A_log, dt_bias, the gate's bias
    and the heads' gain."""
    d = int(arch["d_model"])
    if kind == GLOBAL:
        return 2 * d + int(arch["kv_lora_rank"])
    H, D = int(arch["linear_n_heads"]), int(arch["linear_head_dim"])
    return 2 * d + int(arch.get("linear_conv_kernel", 4)) * 3 * H * D \
        + H + 2 * H * D + D


def expert_params(arch: Dict[str, Any]) -> int:
    """One routed expert's three matrices."""
    return 3 * int(arch["d_model"]) * int(arch["moe_d_ff"])


def resident_params(arch: Dict[str, Any]) -> Dict[str, int]:
    """The parameters this chip holds, by part: the attention halves of
    each kind, the routed layers' routers, selection biases and shared
    experts, the held experts, the leading dense FFNs, embedding and
    head with the final norm."""
    d = int(arch["d_model"])
    table = layer_table(arch)
    routed = sum(layer.routed for layer in table)
    routers = int(arch.get("moe_router_experts") or arch["moe_experts"])
    return {
        "kda": linear_layers(arch) * (_attn_params(arch, LINEAR)
                                      + _small_params(arch, LINEAR)),
        "mla": latent_layers(arch) * (_attn_params(arch, GLOBAL)
                                      + _small_params(arch, GLOBAL)),
        "routers_shared": routed * (
            d * routers + routers
            + int(arch.get("moe_shared_experts", 0)) * expert_params(arch)),
        "experts": routed * int(arch["moe_experts"]) * expert_params(arch),
        "dense_ffn": (len(table) - routed) * 3 * d * int(arch["d_ff"]),
        "embed_head": 2 * d * int(arch["vocab_size"]) + d}


def decode_bytes(arch: Dict[str, Any], rows_held: float, live: float,
                 experts_hit: float, element: int = 2,
                 cache_element: int = 2, tail_element: int = 2) -> float:
    """Bytes one decode step cannot avoid moving, at `element` bytes a
    weight, `cache_element` a cached latent value and `tail_element` a
    value of a convolution's tail: every weight outside the routed
    experts once (the attention halves of the 20 KDA and 7 MLA layers,
    the routers, the shared experts, the leading dense FFN, the head and
    the final norm) and the embedding's rows of the `live` slots' tokens;
    the three matrices of each of the `experts_hit` (held expert, routed
    layer) pairs that took a row, once; the `live` owned slots' float32
    states in each KDA layer, read once and written once, and their
    tails, read and written; and the latent rows of the `rows_held` held
    tokens, once in each MLA layer. Temporaries, the step's one new row a
    layer and the routed rows in and out are left out."""
    d, V = int(arch["d_model"]), int(arch["vocab_size"])
    parts = resident_params(arch)
    weights = parts["kda"] + parts["mla"] + parts["routers_shared"] \
        + parts["dense_ffn"] + d * V + d
    H, D = int(arch["linear_n_heads"]), int(arch["linear_head_dim"])
    tail = (int(arch.get("linear_conv_kernel", 4)) - 1) * 3 * H * D
    per_slot = linear_layers(arch) * 2.0 * (
        4 * _state_values(arch) + tail_element * tail)
    return element * (weights + live * d
                      + experts_hit * expert_params(arch)) \
        + live * per_slot \
        + latent_attn_min_bytes(arch, rows_held, cache_element)


# -- what the architecture costs ---------------------------------------------

def _matmul_params_used(arch: Dict[str, Any], layer: Layer) -> float:
    """Matmul parameters a token uses in one layer on this chip: the
    attention half's projections (a KDA layer's gates through their low
    rank) and, routed, the router, the shared expert and the token's kept
    experts (its `moe_top_k` by the share of the router's experts held
    here); else the dense FFN."""
    d = int(arch["d_model"])
    attn = _attn_params(arch, layer.kind)
    if not layer.routed:
        return attn + 3 * d * int(arch["d_ff"])
    routed = int(arch.get("moe_router_experts") or arch["moe_experts"])
    kept = int(arch["moe_top_k"]) * int(arch["moe_experts"]) / routed
    return attn + d * routed + expert_params(arch) * (
        int(arch.get("moe_shared_experts", 0)) + kept)


def prefill_flops(arch: Dict[str, Any], n_tokens: int) -> float:
    """Operations a prompt of `n_tokens` asks of its prefill on this chip:
    two a matmul parameter a token uses, every layer (the experts by the
    share a uniform router keeps here); an MLA layer's per-head attention
    of each (query, key) pair under the diagonal; a KDA layer's
    recurrence (`kda_flops_bytes`) and its convolution; and the head at
    the one position whose logits a prefill needs. Padding is the
    program's, not the model's."""
    n = int(n_tokens)
    H, D = int(arch["linear_n_heads"]), int(arch["linear_head_dim"])
    total = 2.0 * int(arch["d_model"]) * int(arch["vocab_size"])
    for layer in layer_table(arch):
        total += 2.0 * n * _matmul_params_used(arch, layer)
        if layer.kind == GLOBAL:
            total += _tile_attn(arch, 1, n, 2)["flops"]
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
    total = 6.0 * int(arch["d_model"]) * int(arch["vocab_size"])
    for layer in layer_table(arch):
        total += 6.0 * _matmul_params_used(arch, layer)
        if layer.kind == GLOBAL:
            total += 3.0 * _tile_attn(arch, 1, seq, 2)["flops"] / seq
        else:
            total += 3.0 * kda_flops_bytes(arch, 1)["flops"]
    return total
