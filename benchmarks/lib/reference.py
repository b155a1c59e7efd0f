"""The plain reference: the decoder's forward pass and its next-token
loss in straightforward `jax.numpy`, float32, highest matmul precision.
No kernels, no cache, no batching, no scan: one layer's weights are cast
to float32 at a time, so it fits beside the engine or the trainer.

It follows the published description of both families (pre-norm decoder,
RMS norm, rotary embedding on half-split pairs, grouped-query attention,
SwiGLU, untied head). InternLM2's checkpoint fuses q, k and v into one
`wqkv`; separate projections are the same mathematics.

Independent of `ray_tpu/models`: it reads the weight pytree's leaves by
name and nothing else.
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
    """x (S, H, D): rotate the pairs (i, i + D/2) by pos * theta^(-2i/D)."""
    S, _, D = x.shape
    half = D // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=F32) * 2.0 / D))
    ang = jnp.arange(S, dtype=F32)[:, None] * inv[None, :]
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _layer(x, layers, i, n_heads, n_kv_heads, theta, eps):
    """Layer `i` of the stacked weights; only that layer is cast."""
    S, d = x.shape
    hd = d // n_heads
    lp = {k: jax.lax.dynamic_index_in_dim(v, i, 0, keepdims=False)
          .astype(F32) for k, v in layers.items()}
    h = _rms(x, lp["attn_norm"], eps)
    q = _rope((h @ lp["wq"]).reshape(S, n_heads, hd), theta)
    k = _rope((h @ lp["wk"]).reshape(S, n_kv_heads, hd), theta)
    v = (h @ lp["wv"]).reshape(S, n_kv_heads, hd)
    rep = n_heads // n_kv_heads
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(F32(hd))
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool))[None], s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)
    x = x + o.reshape(S, n_heads * hd) @ lp["wo"]
    h = _rms(x, lp["ffn_norm"], eps)
    return x + (jax.nn.silu(h @ lp["w_gate"]) * (h @ lp["w_up"])) \
        @ lp["w_down"]


_layer_jit = jax.jit(_layer, static_argnums=(3, 4, 5, 6))


@jax.jit
def _embed(table, tokens):
    return table[tokens].astype(F32)


@jax.jit
def _head(x, norm, head, eps):
    return _rms(x, norm, eps) @ head.astype(F32)


def forward_logits(arch: Dict[str, Any], params: Dict[str, Any], tokens
                   ) -> jax.Array:
    """tokens (S,) -> float32 logits (S, V) of one sequence."""
    with jax.default_matmul_precision("highest"):
        x = _embed(params["embed"], jnp.asarray(tokens, jnp.int32))
        for i in range(int(arch["n_layers"])):
            x = _layer_jit(x, params["layers"], jnp.int32(i),
                           int(arch["n_heads"]),
                           int(arch["n_kv_heads"]),
                           float(arch["rope_theta"]),
                           float(arch["norm_eps"]))
        head = params["embed"].T if arch.get("tie_embeddings") \
            else params["lm_head"]
        return _head(x, params["final_norm"], head, float(arch["norm_eps"]))


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
