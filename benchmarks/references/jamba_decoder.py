"""The plain reference of the `jamba` decoder (AI21 Jamba, arXiv:2403.19887,
at Jamba2-3B's shape: periods of Mamba-1 layers, arXiv:2312.00752, around
one softmax attention layer with one key-value head and no position;
every FFN a dense SwiGLU; the head tied to the embedding): its forward
pass in straightforward `jax.numpy`, float32, highest matmul precision,
to the interface `references/dense_decoder.py` describes; and the bytes
its recurrence must move, for the roofline readers. Independent of
`ray_tpu/models` and `ray_tpu/ops`: the weights are read by leaf name
(`periods`: what every layer has, the two norms and the SwiGLU, stacked
over periods and a period's layers; a layer's mixer under its kind and
its place among the period's layers of the kind, `global0`, `ssm0`,
`ssm1`, ..., stacked over periods), the architecture from the
configuration file's keys.

The layer, for input x (T x d), as the configuration file's `published`
and `assumed` state it; C = `mamba_expand` x d channels, N =
`mamba_d_state`, R = `mamba_dt_rank`:

    x0       = Embed[tok]
    h        = RMSNorm_in(x)
    attention layer (place `attn_layer_offset` of each period):
      q, k, v = h Wq, h Wk, h Wv      no rotary, no q/k norm, no bias
      x       = x + softmax(q k^T / sqrt(head_dim), causal) v Wo
    Mamba layer (the others):
      [u, z]  = h W_in
      u       = silu(conv(u) + b_conv)   causal, depthwise, over the last
                `mamba_d_conv` positions, zeros before the first
      [r,B,C] = u W_x ;  r, B, C = RMSNorm_dt(r), RMSNorm_b(B), RMSNorm_c(C)
      dt      = softplus(r W_dt + b_dt)                          (C,)
      h_t     = exp(dt_t A) h_{t-1} + (dt_t u_t) B_t^T   A = -exp(A_log),
                h (N, C): `A_log` lies a coordinate a row, as the state
      y_t     = C_t h_t + D u_t
      x       = x + (y silu(z)) W_out
    m        = RMSNorm_ffn(x)
    x        = x + (silu(m Wg) * (m Wu)) Wd
    logits   = RMSNorm_final(x_L) Embed^T

No cache, no chunks, no kernels: the recurrence a token at a time
(`lax.scan` over the positions, the layer's whole state in its carry),
the attention layer's scores a block of queries at a time. One layer's weights are read
at a time, the head in blocks of its rows.

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
GLOBAL, SSM = "global", "ssm"


def layer_table(arch: Dict[str, Any]) -> List[Tuple[int, int, str, int]]:
    """[(period, place in it, kind, place among the period's layers of
    the kind)] in layer order: layer i attends where i mod
    `global_attn_every` (the published `attn_layer_period`) is
    `attn_layer_offset`, as `JambaConfig.layers_block_type` has it."""
    every, at = int(arch["global_attn_every"]), int(arch["attn_layer_offset"])
    return [(p, j, GLOBAL, 0) if j == at else (p, j, SSM, j - (j > at))
            for p in range(int(arch["n_layers"]) // every)
            for j in range(every)]


def _rms(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * w.astype(F32)


QUERY_BLOCK = 128


def _attention(h, lp, a):
    """The scores a block of queries at a time where the positions come
    in whole blocks (20 heads x S x S float32 is 2 GB at 5,120)."""
    H, KVH, Dh = a
    S = h.shape[0]
    blk = QUERY_BLOCK if S % QUERY_BLOCK == 0 else S
    q = (h @ lp["wq"].astype(F32)).reshape(S // blk, blk, KVH, H // KVH, Dh)
    k = (h @ lp["wk"].astype(F32)).reshape(S, KVH, Dh)
    v = (h @ lp["wv"].astype(F32)).reshape(S, KVH, Dh)

    def block(args):
        qs, start = args
        s = jnp.einsum("tkgd,skd->kgts", qs, k) / math.sqrt(Dh)
        seen = jnp.arange(S)[None, :] <= start + jnp.arange(blk)[:, None]
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("kgts,skd->tkgd", p, v)

    o = lax.map(block, (q, jnp.arange(S // blk) * blk))
    return o.reshape(S, H * Dh) @ lp["wo"].astype(F32)


def _conv(x, w):
    """Causal depthwise convolution: x (S, C), w (K, C); y_t = sum_i w_i
    x_{t - K + 1 + i}, zeros before the first position."""
    K, S = w.shape[0], x.shape[0]
    padded = jnp.pad(x, ((K - 1, 0), (0, 0)))
    return sum(w[i].astype(F32) * padded[i:i + S] for i in range(K))


def recurrence(dt, u, Bm, Cm, A, state=None):
    """The selective scan a token at a time: dt, u (S, C), Bm, Cm (S, N),
    A (N, C) -> (y (S, C), the state behind the last position (N, C));
    `state`: what it starts from (None: zeros)."""
    def one(h, xs):
        dt, u, b, c = xs
        h = jnp.exp(dt[None, :] * A) * h + (dt * u)[None, :] * b[:, None]
        return h, jnp.sum(h * c[:, None], axis=0)

    h0 = jnp.zeros(A.shape, F32) if state is None else state
    last, y = lax.scan(one, h0, (dt, u, Bm, Cm))
    return y, last


def _mamba(h, lp, a, eps):
    C, N, R = a
    xz = h @ lp["w_in"].astype(F32)
    u, z = xz[:, :C], xz[:, C:]
    u = _conv(u, lp["conv"])
    if "conv_bias" in lp:
        u = u + lp["conv_bias"].astype(F32)
    u = jax.nn.silu(u)
    x = u @ lp["w_x"].astype(F32)
    r = _rms(x[:, :R], lp["dt_norm"], eps)
    Bm = _rms(x[:, R:R + N], lp["b_norm"], eps)
    Cm = _rms(x[:, R + N:], lp["c_norm"], eps)
    dt = jax.nn.softplus(r @ lp["w_dt"].astype(F32)
                         + lp["dt_bias"].astype(F32))
    y, _ = recurrence(dt, u, Bm, Cm, -jnp.exp(lp["A_log"].astype(F32)))
    y = y + lp["D"].astype(F32) * u
    return (y * jax.nn.silu(z)) @ lp["wo"].astype(F32)


@partial(jax.jit, static_argnums=(4, 5))
def _layer(x, shared, own, at, kind: str, a: Tuple):
    """One layer: `shared` the leaves every layer has, stacked over
    periods and places, `own` its mixer's, stacked over periods, `at` its
    (period, place); only that layer is read, and one program serves
    every layer of a kind."""
    attention, mamba, eps = a

    def pick(v, i):
        return lax.dynamic_index_in_dim(v, i, 0, keepdims=False)

    lp = {k: pick(pick(v, at[0]), at[1]) for k, v in shared.items()}
    lp.update({k: pick(v, at[0]) for k, v in own.items()})
    h = _rms(x, lp["attn_norm"], eps)
    x = x + (_attention(h, lp, attention) if kind == GLOBAL
             else _mamba(h, lp, mamba, eps))
    m = _rms(x, lp["ffn_norm"], eps)
    return x + (jax.nn.silu(m @ lp["w_gate"].astype(F32))
                * (m @ lp["w_up"].astype(F32))) @ lp["w_down"].astype(F32)


@jax.jit
def _embed(table, tokens):
    return table[tokens].astype(F32)


@partial(jax.jit, static_argnums=(3,))
def _head(x, norm, embed, eps):
    xn = _rms(x, norm, eps)
    V = embed.shape[0]
    n = math.gcd(V, VOCAB_BLOCKS)
    return jnp.concatenate(
        [xn @ embed[b * V // n:(b + 1) * V // n].astype(F32).T
         for b in range(n)], axis=-1)


def _widths(arch: Dict[str, Any]) -> Tuple[int, int, int]:
    return (int(arch["mamba_expand"]) * int(arch["d_model"]),
            int(arch["mamba_d_state"]), int(arch["mamba_dt_rank"]))


def _static(arch: Dict[str, Any]) -> Tuple:
    if int(arch.get("moe_experts", 0)) or arch.get("mamba_proj_bias"):
        raise ValueError("jamba_decoder: every FFN is dense and the "
                         "Mamba projections have no bias")
    head = int(arch.get("head_dim") or arch["d_model"] // arch["n_heads"])
    return ((int(arch["n_heads"]), int(arch["n_kv_heads"]), head),
            _widths(arch), float(arch["norm_eps"]))


def forward_logits(arch: Dict[str, Any], params: Dict[str, Any], tokens
                   ) -> jax.Array:
    """tokens (S,) -> float32 logits (S, V) of one sequence."""
    if not arch.get("tie_embeddings"):
        raise ValueError("jamba_decoder: the head is the embedding's "
                         "transpose")
    with jax.default_matmul_precision("highest"):
        x = _embed(params["embed"], jnp.asarray(tokens, jnp.int32))
        a = _static(arch)
        periods = params["periods"]
        shared = {k: v for k, v in periods.items()
                  if not isinstance(v, dict)}
        for p, j, kind, own in layer_table(arch):
            x = _layer(x, shared, periods[f"{kind}{own}"],
                       jnp.asarray([p, j], jnp.int32), kind, a)
        return _head(x, params["final_norm"], params["embed"],
                     float(arch["norm_eps"]))


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


# -- what the recurrence must move -------------------------------------------

def ssm_layers(arch: Dict[str, Any]) -> int:
    return sum(kind == SSM for _, _, kind, _ in layer_table(arch))


def ssm_state_bytes(arch: Dict[str, Any], live_slot_steps: float) -> float:
    """The least bytes the decode steps' recurrence can move over
    `live_slot_steps` updates (an owned slot, a step, a Mamba layer: the
    engine's `linear_slot_steps_live`): the layer's float32 state read
    once and written once. What else an update reads (dt, u and y a
    channel, B and C a coordinate: 3 C + 2 N values against N C) is a
    fifth of a state's one way and left out."""
    C, N, _ = _widths(arch)
    return live_slot_steps * 2.0 * 4 * N * C


def ssm_scan_bytes(arch: Dict[str, Any], tokens: float, rows: float = 0.0,
                   bytes_per: int = 2) -> float:
    """What the scan of `tokens` real (token, Mamba layer) pairs of a
    tile (the engine's `linear_tokens`) must stream, whatever walks it:
    dt and u in and y out a channel in float32 (the recurrence's own
    precision), the gate z in the activation dtype, B and C a coordinate
    in float32; and a float32 state out a (row, layer), `rows` of them.
    The state between two positions never leaves the chip."""
    C, N, _ = _widths(arch)
    return tokens * (C * (3 * 4 + bytes_per) + 2 * N * 4) \
        + rows * 4.0 * N * C


# -- what the architecture costs ---------------------------------------------

def _matmul_params_used(arch: Dict[str, Any], kind: str) -> float:
    """Matmul parameters a token uses in one layer: the mixer's
    projections and the SwiGLU's three matrices."""
    d = int(arch["d_model"])
    if kind == GLOBAL:
        head = int(arch.get("head_dim") or d // arch["n_heads"])
        q, kv = int(arch["n_heads"]) * head, int(arch["n_kv_heads"]) * head
        mixer = d * (2 * q + 2 * kv)
    else:
        C, N, R = _widths(arch)
        mixer = d * 2 * C + C * (R + 2 * N) + R * C + C * d
    return mixer + 3 * d * int(arch["d_ff"])


def prefill_flops(arch: Dict[str, Any], n_tokens: int) -> float:
    """Operations a prompt of `n_tokens` asks of its prefill: two a
    matmul parameter a token uses, every layer; an attention layer's
    (query, key) pairs under the diagonal (2 x heads x 2 x head_dim a
    pair); a Mamba layer's recurrence (the decay, its product with the
    state, the input's term and its sum, the state against C: 6 a state
    element, its exponential counted as one) and its convolution; and the
    head at the one position whose logits a prefill needs. Padding is the
    program's, not the model's."""
    n = int(n_tokens)
    head = int(arch.get("head_dim") or arch["d_model"] // arch["n_heads"])
    C, N, _ = _widths(arch)
    pairs = n * (n + 1) / 2
    total = 2.0 * int(arch["d_model"]) * int(arch["vocab_size"])
    for _, _, kind, _ in layer_table(arch):
        total += 2.0 * n * _matmul_params_used(arch, kind)
        if kind == GLOBAL:
            total += 2.0 * pairs * int(arch["n_heads"]) * 2 * head
        else:
            total += n * (6.0 * N * C
                          + 2.0 * C * int(arch.get("mamba_d_conv", 4)))
    return total


def train_flops_per_token(arch: Dict[str, Any], seq: int) -> float:
    """Forward and backward operations a trained token requires (6 per
    matmul parameter the token uses, 3 x the forward's attention at `seq`
    keys and 3 x its recurrence). The system does not train this
    architecture (`transformer.forward` raises); the count is here
    because every reference brings one."""
    head = int(arch.get("head_dim") or arch["d_model"] // arch["n_heads"])
    C, N, _ = _widths(arch)
    total = 6.0 * int(arch["d_model"]) * int(arch["vocab_size"])
    for _, _, kind, _ in layer_table(arch):
        total += 6.0 * _matmul_params_used(arch, kind)
        if kind == GLOBAL:
            total += 3.0 * 2 * int(arch["n_heads"]) * 2 * head * seq / 2
        else:
            total += 3.0 * 6.0 * N * C
    return total
