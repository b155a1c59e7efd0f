"""The plain reference of a looped decoder (ByteDance Ouro, "Scaling
Latent Reasoning via Looped Language Models", arXiv:2510.25741): its
forward pass in straightforward `jax.numpy`, float32, highest matmul
precision, to the contract `references/dense_decoder.py` states. No
kernel, no cache, no scan: `total_ut_steps` Python passes over the full
sequence, one layer's weights cast to float32 at a time, the weights read
by leaf name and nothing of `ray_tpu/models`.

The equations, as the configuration file's `assumed` marks what the
published config.json leaves to the family's `modeling_ouro.py`:

- `h = E[tokens]`; the head is untied.
- For pass t = 0 .. T - 1 and layer l = 0 .. L - 1, the same weights
  every pass: `h = h + N2a_l(Attn_l(N1a_l(h)))`, `h = h + N2f_l(SwiGLU_l(
  N1f_l(h)))`: four RMS norms a layer, the second of each pair on the
  branch's output (sandwich norm). `Attn`: MHA (one query head a KV
  head), no bias, no q/k norm, no gate, rotary over the whole head on
  half-split pairs, causal, scale head_dim^-0.5; a cached implementation
  keeps the keys and values of (t, l) in slab t x L + l, and pass t
  attends to pass t's rows only, which with no cache is what attending
  over the pass's own sequence is. `SwiGLU`: `down(silu(gate(x)) x up(x))`.
- After each pass `h = N_final(h)`, and the normed h is both `h_t` and the
  next pass's input. Gate: `lam_t = sigmoid(h_t . w + b)`. Exit mass:
  `p_t = lam_t x prod_{j<t} (1 - lam_j)` for t < T - 1, the last pass the
  rest. A token exits at the first pass whose cumulative mass reaches
  `early_exit_threshold`, else at the last; its logits are
  `h_exit @ W_head`. Every pass runs whatever the gate says.

The leaves: `embed`, `periods` (every layer's, stacked (L, 1, ...)),
`final_norm`, `exit_gate` {`w` (D,), `b` ()}, `lm_head`.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * w.astype(F32)


def _rope(x, theta):
    """x (S, H, D): rotate the pairs (i, i + D/2) by pos x theta^(-2i/D)."""
    S, _, D = x.shape
    half = D // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=F32) * 2.0 / D))
    ang = jnp.arange(S, dtype=F32)[:, None] * inv[None, :]
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _layer(x, layers, i, n_heads, n_kv_heads, head, theta, eps):
    """Layer `i` of the stacked weights on x (S, D); only that layer is
    cast."""
    S, _ = x.shape
    lp = {k: jax.lax.dynamic_index_in_dim(v, i, 0, keepdims=False)[0]
          .astype(F32) for k, v in layers.items()}
    h = _rms(x, lp["attn_norm"], eps)
    q = _rope((h @ lp["wq"]).reshape(S, n_heads, head), theta)
    k = _rope((h @ lp["wk"]).reshape(S, n_kv_heads, head), theta)
    v = (h @ lp["wv"]).reshape(S, n_kv_heads, head)
    rep = n_heads // n_kv_heads
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(F32(head))
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool))[None], s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)
    x = x + _rms(o.reshape(S, n_heads * head) @ lp["wo"],
                 lp["post_attn_norm"], eps)
    h = _rms(x, lp["ffn_norm"], eps)
    f = (jax.nn.silu(h @ lp["w_gate"]) * (h @ lp["w_up"])) @ lp["w_down"]
    return x + _rms(f, lp["post_ffn_norm"], eps)


_layer_jit = jax.jit(_layer, static_argnums=(3, 4, 5, 6, 7))


@jax.jit
def _embed(table, tokens):
    return table[tokens].astype(F32)


@jax.jit
def _final(x, norm, eps):
    return _rms(x, norm, eps)


@jax.jit
def _head(x, head):
    return x @ head.astype(F32)


def _sizes(arch: Dict[str, Any]):
    d, H = int(arch["d_model"]), int(arch["n_heads"])
    return (d, int(arch["n_layers"]), H, int(arch["n_kv_heads"]),
            int(arch.get("head_dim") or d // H), int(arch["d_ff"]),
            int(arch["vocab_size"]), int(arch.get("ut_steps", 1)))


def pass_states(arch: Dict[str, Any], params: Dict[str, Any], tokens):
    """tokens (S,) -> (T, S, D): the final-normed state after each pass."""
    _, L, H, KVH, head, _, _, T = _sizes(arch)
    theta, eps = float(arch["rope_theta"]), float(arch["norm_eps"])
    with jax.default_matmul_precision("highest"):
        x = _embed(params["embed"], jnp.asarray(tokens, jnp.int32))
        states = []
        for _ in range(T):
            for i in range(L):
                x = _layer_jit(x, params["periods"], jnp.int32(i), H, KVH,
                               head, theta, eps)
            x = _final(x, params["final_norm"], eps)
            states.append(x)
        return jnp.stack(states)


@jax.jit
def _mass(states, w, b):
    lam = jax.nn.sigmoid(jnp.sum(states * w.astype(F32), axis=-1)
                         + b.astype(F32)).T                        # (S, T)
    out, left = [], jnp.ones_like(lam[:, 0])
    for t in range(lam.shape[1] - 1):
        out.append(lam[:, t] * left)
        left = left * (1.0 - lam[:, t])
    return jnp.stack(out + [left], axis=1)


def exit_mass(arch: Dict[str, Any], params: Dict[str, Any], tokens,
              states=None) -> jax.Array:
    """tokens (S,) -> (S, T) float32: the mass with which each token exits
    at each pass; rows sum to one."""
    if states is None:
        states = pass_states(arch, params, tokens)
    gate = params["exit_gate"]
    return _mass(states, gate["w"], gate["b"])


def exit_pass(arch: Dict[str, Any], mass) -> jax.Array:
    """(S, T) exit mass -> (S,) int32: the first pass whose cumulative
    mass reaches `early_exit_threshold`, else the last."""
    reached = jnp.cumsum(mass, axis=-1) >= float(
        arch.get("early_exit_threshold", 1.0))
    return jnp.where(jnp.any(reached, axis=-1), jnp.argmax(reached, axis=-1),
                     mass.shape[-1] - 1).astype(jnp.int32)


def forward_logits(arch: Dict[str, Any], params: Dict[str, Any], tokens
                   ) -> jax.Array:
    """tokens (S,) -> float32 logits (S, V) of one sequence, each
    position's at its exit pass."""
    with jax.default_matmul_precision("highest"):
        states = pass_states(arch, params, tokens)
        at = exit_pass(arch, exit_mass(arch, params, tokens, states))
        x = jnp.take_along_axis(states, at[None, :, None], axis=0)[0]
        return _head(x, params["lm_head"])


@jax.jit
def _nll_sum(logits, targets):
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
    return jnp.sum(logz - gold)


def loss(arch: Dict[str, Any], params: Dict[str, Any], tokens, targets
         ) -> float:
    """Mean next-token cross entropy over a batch (B, S) of the exit
    pass's logits, one sequence at a time. The system does not train this
    architecture; the loss is here because every reference brings one."""
    total, count = 0.0, 0
    for row, tgt in zip(tokens, targets):
        logits = forward_logits(arch, params, row)
        total += float(_nll_sum(logits, jnp.asarray(tgt, jnp.int32)))
        count += len(tgt)
    return total / count


# -- what the architecture costs ---------------------------------------------

def layer_matmul_params(arch: Dict[str, Any]) -> int:
    """One layer's parameters that take part in a product for every
    token: q, k, v, o and the SwiGLU's three matrices (not its norms)."""
    d, _, H, KVH, head, f, _, _ = _sizes(arch)
    return 2 * d * H * head + 2 * d * KVH * head + 3 * d * f


def prefill_flops(arch: Dict[str, Any], n_tokens: int) -> float:
    """Operations a prompt of `n_tokens` asks of its prefill: two a
    layer's matmul parameter a token, every layer, every pass (the same
    weights, their products made `ut_steps` times); the (query, key)
    pairs under the diagonal of all `ut_steps x n_layers` attentions (2 x
    heads x 2 x head_dim a pair); the gate's D a pass; and the head once,
    at the one position whose logits a prefill needs. Padding is the
    program's, not the model's."""
    d, L, H, _, head, _, V, T = _sizes(arch)
    n = int(n_tokens)
    pairs = n * (n + 1) / 2
    return T * (L * (2.0 * n * layer_matmul_params(arch)
                     + 2.0 * pairs * H * 2 * head) + 2.0 * d) + 2.0 * d * V


def decode_bytes(arch: Dict[str, Any], rows_held: float, live: float,
                 element: int = 2, cache_element: int = 2) -> float:
    """Bytes one decode step cannot avoid reading, at `element` bytes a
    weight and `cache_element` a cached value: the layers' weights
    `ut_steps` times (a
    pass streams all of them again: 48 layers do not stay in the chip's
    fast memory between passes), the head, the final norm and the gate
    once, the embedding's rows of the `live` slots' tokens, and K and V
    of the `rows_held` rows the owned slots hold, in each of the `ut_steps
    x n_layers` slabs. The step's writes (a row a slab a live slot) are
    left out."""
    d, L, _, KVH, head, _, V, T = _sizes(arch)
    weights = T * L * (layer_matmul_params(arch) + 4 * d) * element
    once = (d * V + 2 * d + 1 + live * d) * element
    rows = T * L * 2 * KVH * head * rows_held * cache_element
    return weights + once + rows


def train_flops_per_token(arch: Dict[str, Any], seq: int) -> float:
    """Forward and backward operations a trained token requires (6 a
    matmul parameter a pass, 3 x the forward's attention at `seq` keys a
    pass, the head once). The system does not train this architecture
    (`transformer.forward` raises); the count is here because every
    reference brings one."""
    d, L, H, _, head, _, V, T = _sizes(arch)
    return T * L * (6.0 * layer_matmul_params(arch)
                    + 3.0 * 2 * H * 2 * head * seq / 2) + 6.0 * d * V
