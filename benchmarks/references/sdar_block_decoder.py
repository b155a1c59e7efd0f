"""The plain reference of the `sdar_moe` decoder (JetLM SDAR-30B-A3B):
its forward pass under the block-causal mask and its generation loop,
diffusion over blocks, in straightforward `jax.numpy`, float32, highest
matmul precision, to the interface `references/dense_decoder.py`
describes; and what a pass, a prompt's prefill, the routed products and
the attention over the held rows must move and compute, for the readers.
Independent of `ray_tpu/models`: the weights are read by leaf name
(`periods`: leaves stacked over periods of one layer), the architecture
from the configuration file's keys.

The layer, for input x (T x d), as the configuration file's `published`
and `assumed` state it, every layer alike:

    x0      = Embed[tok]                                  (no scaling; a masked position: Embed[mask_token_id])
    a       = RMSNorm_in(x)
    q, k, v = a Wq, a Wk, a Wv                            no bias
    q, k    = RMSNorm_q(q), RMSNorm_k(k)                  over each head   [assumed]
    q, k    = RoPE(q), RoPE(k)                            half-split pairs, inv_freq_i = theta^(-2i/D)
    s_ij    = q_i . k_j / sqrt(D), j // Bd <= i // Bd     block-causal: a query sees its own block whole
    x       = x + softmax(s) v Wo
    m       = RMSNorm_ffn(x)
    p       = softmax(m Wr) over the experts; I = the K largest (ties to the lower index)
    w       = p[I] / sum p[I]                             (norm_topk_prob)
    x       = x + sum_{e in I} w_e Wdown_e(silu(Wgate_e m) * Wup_e m)
    logits  = RMSNorm_final(x_L) Whead                    of the token AT the position (no shift) [assumed]

Generation (`generate`): the prompt's whole blocks stand; the block
behind them opens with what the prompt left over, fixed, and the mask
token elsewhere. A denoising pass is a full forward over everything
committed and the block: x0_j = argmax logits_j, c_j = softmax(logits_j)
[x0_j] at the masked j; the pass's share of the schedule n = Bd // steps
(one more on the first Bd % steps passes) and the rule say which masked
positions take their x0 (`_chosen`). A block with no mask left is
committed (the program's pass over its final tokens has no counterpart
here: there is no cache) and the next opens all masked. The loop stops at
`max_new_tokens` (the last block whole, then cut), behind an eos, or
where another block would pass `max_seq_len`.

`loss` is the mean cross entropy of the logits at each position against
the `targets` it is handed (for this model the token AT the position,
under whatever masking the caller applied to `tokens`): the system does
not train this architecture and no cell calls it; it and
`train_flops_per_token` are here because every reference brings them.

No kernels, no cache, no sort, no scan over layers. Every expert is
applied to all the sequence's tokens and weighted by a (T x E) matrix
that is zero where the token did not choose it. It runs beside 10 GB of
weights: one layer's weights are read at a time, experts are cast to
float32 sixteen at a time, the head in eight blocks of its rows,
attention in blocks of 512 queries.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

F32 = jnp.float32
EXPERT_CHUNK = 16
QUERY_BLOCK = 512
HEAD_BLOCKS = 8
RULES = ("low_confidence_static", "low_confidence_dynamic", "sequential")


def _rms(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * w.astype(F32)


def _rope(x, theta: float):
    """x (S, H, D): rotate the pairs (i, i + D/2) by pos * theta^(-2i/D)."""
    S, _, D = x.shape
    half = D // 2
    inv = theta ** (-2.0 * jnp.arange(half, dtype=F32) / D)
    ang = jnp.arange(S, dtype=F32)[:, None] * inv[None, :]
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _attention(q, k, v, block: int):
    """q, k, v (S, H, D) -> (S, H, D): key j for query i where
    j // block <= i // block."""
    S, _, D = q.shape
    out = []
    for a in range(0, S, QUERY_BLOCK):
        b = min(S, a + QUERY_BLOCK)
        s = jnp.einsum("qhd,khd->hqk", q[a:b], k) / math.sqrt(D)
        i = jnp.arange(a, b)[:, None]
        j = jnp.arange(S)[None, :]
        seen = j // block <= i // block
        s = jnp.where(seen[None], s, -jnp.inf)
        out.append(jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v))
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


@partial(jax.jit, static_argnums=(3,))
def _layer(x, leaves, index, a: Tuple):
    """One layer; `leaves` are the stacked weights, `index` says which
    layer of them (only that one is read)."""
    n_heads, n_kv, hd, eps, top_k, theta, block = a
    lp = {k: lax.dynamic_index_in_dim(v, index, 0, keepdims=False)[0]
          for k, v in leaves.items()}
    small = {k: v.astype(F32) for k, v in lp.items() if v.ndim <= 2}
    S = x.shape[0]
    h = _rms(x, small["attn_norm"], eps)
    q = (h @ small["wq"]).reshape(S, n_heads, hd)
    k = (h @ small["wk"]).reshape(S, n_kv, hd)
    v = (h @ small["wv"]).reshape(S, n_kv, hd)
    q, k = _rms(q, small["q_norm"], eps), _rms(k, small["k_norm"], eps)
    q, k = _rope(q, theta), _rope(k, theta)
    rep = n_heads // n_kv
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    o = _attention(q, k, v, block)
    x = x + o.reshape(S, n_heads * hd) @ small["wo"]
    m = _rms(x, small["ffn_norm"], eps)
    weights, chosen = _route(m, small["router"], top_k)
    return x + _experts(m, lp, weights), chosen


@jax.jit
def _embed(table, tokens):
    return table[tokens].astype(F32)


@partial(jax.jit, static_argnums=(3,))
def _head(x, norm, head, eps):
    """RMSNorm_final(x) Whead (untied), the head cast a block of its
    columns (the vocabulary) at a time."""
    xn = _rms(x, norm, eps)
    V = head.shape[1]
    n = math.gcd(V, HEAD_BLOCKS)
    return jnp.concatenate(
        [xn @ head[:, b * V // n:(b + 1) * V // n].astype(F32)
         for b in range(n)], axis=-1)


def _static(arch: Dict[str, Any]) -> Tuple:
    if arch.get("score_func", "softmax") != "softmax" \
            or not arch.get("route_norm", True):
        raise ValueError("sdar_block_decoder: softmax scores, renormalised")
    if int(arch.get("moe_shared_experts") or 0) \
            or int(arch.get("n_dense_layers") or 0) \
            or int(arch.get("sliding_window") or 0) \
            or int(arch.get("global_attn_every") or 1) != 1 \
            or arch.get("tie_embeddings"):
        raise ValueError("sdar_block_decoder: every layer full attention "
                         "and routed, no shared expert, an untied head")
    return (int(arch["n_heads"]), int(arch["n_kv_heads"]),
            int(arch["head_dim"]), float(arch["norm_eps"]),
            int(arch["moe_top_k"]), float(arch["rope_theta"]),
            int(arch["block_length"]))


def _forward(arch, params, tokens):
    with jax.default_matmul_precision("highest"):
        x = _embed(params["embed"], jnp.asarray(tokens, jnp.int32))
        a, chosen = _static(arch), []
        for l in range(int(arch["n_layers"])):
            x, picked = _layer(x, params["periods"], jnp.int32(l), a)
            chosen.append(picked)
        return _head(x, params["final_norm"], params["lm_head"], a[3]), chosen


def forward_logits(arch: Dict[str, Any], params: Dict[str, Any], tokens
                   ) -> jax.Array:
    """tokens (S,) -> float32 logits (S, V) of one sequence under the
    block-causal mask; row j is of the token AT j. A masked position is
    the token `mask_token_id`. Padding behind the last whole block
    changes nothing before it."""
    return _forward(arch, params, tokens)[0]


def chosen_experts(arch: Dict[str, Any], params: Dict[str, Any], tokens
                   ) -> List[jax.Array]:
    """The experts each layer chooses, in layer order, each (S, K)."""
    return _forward(arch, params, tokens)[1]


@jax.jit
def _nll_sum(logits, targets):
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
    return jnp.sum(logz - gold)


def loss(arch: Dict[str, Any], params: Dict[str, Any], tokens, targets
         ) -> float:
    """Mean cross entropy over a batch (B, S) of the logits at each
    position against `targets`, one sequence at a time."""
    total, count = 0.0, 0
    for row, tgt in zip(tokens, targets):
        logits = forward_logits(arch, params, row)
        total += float(_nll_sum(logits, jnp.asarray(tgt, jnp.int32)))
        count += len(tgt)
    return total / count


# -- generation --------------------------------------------------------------

def schedule(block: int, steps: int) -> List[int]:
    """Positions each denoising pass of a block unmasks: block // steps,
    one more on the first block % steps passes."""
    return [block // steps + (i < block % steps) for i in range(steps)]


def _chosen(conf: np.ndarray, masked: np.ndarray, n: int, rule: str,
            threshold: float) -> np.ndarray:
    """Which masked positions a pass unmasks (see the module's text)."""
    idx = np.flatnonzero(masked)
    if rule == "sequential":
        return idx[:n]
    # Surest first, ties to the lower position.
    order = idx[np.argsort(-conf[idx], kind="stable")]
    if rule == "low_confidence_dynamic":
        sure = idx[conf[idx] > threshold]
        if len(sure) >= n:
            return sure
    return order[:n]


def generate(arch: Dict[str, Any], params: Dict[str, Any],
             prompt: Sequence[int], max_new_tokens: int,
             denoise_steps: Optional[int] = None,
             remask: Optional[str] = None,
             threshold: Optional[float] = None, *,
             eos_token: Optional[int] = None,
             max_seq_len: Optional[int] = None
             ) -> Tuple[List[int], List[int]]:
    """Greedy generation by diffusion over blocks, a full forward a pass
    -> (the new tokens, the denoising pass of its block (from 1) that
    unmasked each)."""
    Bd, mask_id = int(arch["block_length"]), int(arch["mask_token_id"])
    steps = int(denoise_steps or arch.get("denoise_steps") or Bd)
    rule = remask or arch.get("remask", RULES[1])
    if rule not in RULES:
        raise ValueError(f"remask must be one of {RULES}, got {rule!r}")
    thr = float(arch.get("confidence_threshold", 0.9)
                if threshold is None else threshold)
    share = schedule(Bd, steps)
    prompt = [int(t) for t in prompt]
    p0 = len(prompt) // Bd * Bd
    done, block = prompt[:p0], prompt[p0:]
    masked = np.arange(Bd) >= len(block)
    fixed = len(block)
    block = np.asarray(block + [mask_id] * (Bd - fixed), np.int64)
    at_pass = np.zeros(Bd, np.int64)
    out: List[int] = []
    passes: List[int] = []
    while True:
        if max_seq_len is not None and len(done) + Bd > max_seq_len:
            break
        n_pass = 0
        while masked.any():
            logits = np.asarray(forward_logits(
                arch, params, done + block.tolist()), np.float64)[len(done):]
            x0 = np.argmax(logits, axis=-1)
            z = logits - logits.max(axis=-1, keepdims=True)
            conf = 1.0 / np.exp(z).sum(axis=-1)      # softmax at the argmax
            n = share[n_pass] if n_pass < steps else Bd
            take = _chosen(np.where(masked, conf, -np.inf), masked, n, rule,
                           thr)
            n_pass += 1
            block[take], masked[take], at_pass[take] = x0[take], False, n_pass
        done += block.tolist()
        ended = False
        for j in range(fixed, Bd):
            if ended or len(out) >= max_new_tokens:
                break
            out.append(int(block[j]))
            passes.append(int(at_pass[j]))
            ended = block[j] == eos_token
        if ended or len(out) >= max_new_tokens:
            break
        block = np.full(Bd, mask_id, np.int64)
        masked, at_pass, fixed = np.ones(Bd, bool), np.zeros(Bd, np.int64), 0
    return out, passes


# -- what the routed products must move and compute --------------------------

def moe_experts_min_bytes(arch: Dict[str, Any], experts_hit: float,
                          rows: float, bytes_per: int = 2) -> float:
    """The least bytes the routed products can move, for `experts_hit`
    (expert, layer, pass) triples that held a row and `rows` token-expert
    pairs: the three matrices of each expert hit, once, and each pair's
    row in and out."""
    d, f = int(arch["d_model"]), int(arch["moe_d_ff"])
    return bytes_per * (experts_hit * 3 * d * f + rows * 2 * d)


def moe_experts_flops(arch: Dict[str, Any], rows: float) -> float:
    """Operations of the routed products for `rows` token-expert pairs:
    three matrices of d x f, a multiply and an add each."""
    d, f = int(arch["d_model"]), int(arch["moe_d_ff"])
    return rows * 3 * 2 * d * f


def block_attn_min_bytes(arch: Dict[str, Any], held_rows: float,
                         bytes_per: int = 2) -> float:
    """The least bytes a pass's attention can move: the K and V of the
    `held_rows` rows its slots' queries see, every layer, each once
    (a slot's block of queries shares one read)."""
    return held_rows * 2 * int(arch["n_kv_heads"]) * int(arch["head_dim"]) \
        * bytes_per * int(arch["n_layers"])


# -- what the architecture costs ---------------------------------------------

def _matmul_params_used(arch: Dict[str, Any]) -> int:
    """Matmul parameters a token uses in one layer: the attention
    projections, the router, and its `moe_top_k` experts."""
    d, hd = int(arch["d_model"]), int(arch["head_dim"])
    q, kv = int(arch["n_heads"]) * hd, int(arch["n_kv_heads"]) * hd
    return 2 * d * q + 2 * d * kv + d * int(arch["moe_experts"]) \
        + 3 * d * int(arch["moe_d_ff"]) * int(arch["moe_top_k"])


def pass_flops(arch: Dict[str, Any], rows: float, held_rows: float,
               head_rows: float) -> float:
    """Operations one pass of the model asks for: two a matmul parameter
    each of its `rows` positions uses, every layer; the attention of each
    (query, key) pair (q . k and p v: 4 x heads x head size a pair), a
    slot's `block_length` queries each seeing the slot's `held_rows`;
    and the head for the `head_rows` positions whose logits a denoising
    pass needs (a commit pass needs none)."""
    q = int(arch["n_heads"]) * int(arch["head_dim"])
    layers = int(arch["n_layers"])
    return 2.0 * rows * _matmul_params_used(arch) * layers \
        + 4.0 * q * held_rows * int(arch["block_length"]) * layers \
        + 2.0 * head_rows * int(arch["d_model"]) * int(arch["vocab_size"])


def prefill_flops(arch: Dict[str, Any], n_tokens: int) -> float:
    """Operations the prefill of `n_tokens` (a prompt's whole blocks)
    asks for: two a matmul parameter a token uses, every layer, and the
    attention of the pairs under the block diagonal (a query sees its
    block whole and every block before). No head: a prompt's logits are
    not how this model starts."""
    n, Bd = int(n_tokens), int(arch["block_length"])
    whole, rest = divmod(n, Bd)
    pairs = Bd * Bd * whole * (whole + 1) // 2 + rest * n
    q = int(arch["n_heads"]) * int(arch["head_dim"])
    layers = int(arch["n_layers"])
    return 2.0 * n * _matmul_params_used(arch) * layers + 4.0 * q * pairs * layers


def train_flops_per_token(arch: Dict[str, Any], seq: int) -> float:
    """Forward and backward operations a trained token requires: 6 per
    matmul parameter the token uses (its `moe_top_k` experts, not the
    experts held) plus 12 x d_attn x keys a layer of attention (masking
    and recomputation not counted). The system does not train this
    architecture (`transformer.forward` raises); the count is here
    because every reference brings one."""
    q = int(arch["n_heads"]) * int(arch["head_dim"])
    layers = int(arch["n_layers"])
    return 6.0 * (_matmul_params_used(arch) * layers
                  + int(arch["d_model"]) * int(arch["vocab_size"])) \
        + 12.0 * q * seq * layers
