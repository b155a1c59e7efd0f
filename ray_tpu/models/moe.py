"""The routed feed-forward layer of the serving path: nothing dropped.

`transformer.moe_ffn` (training) gives every expert a fixed capacity and
drops the tokens over it; its `(T, K, E, capacity)` dispatch tensor is
also out of reach at serving sizes. Here the token-expert pairs are
sorted by expert and multiplied by groups (`grouped_dot`: a grouped-matmul
kernel that visits only the experts that hold rows), then put back in
order, weighted and summed. Router scores and the selection
are float32 from a float32 input: routing is discontinuous, and a score
rounded to bf16 picks another expert where two lie close.

Float32 activations against bf16 weights (`dot`, `grouped_experts`): the
activation goes in as two bf16 terms, hi + lo, stacked as rows of one
product, so the weights are read once, as they lie, and the result
carries 2^-17 of the activation's rounding and not 2^-9. A routed stack
is served so where its routing has to agree with a float32 reference
(models/periodic.py).
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax


def bf16_terms(x: jax.Array) -> jax.Array:
    """x float32 (...) -> bfloat16 (2, ...): hi = x rounded to bf16 (by
    `reduce_precision`: a cast down and up again the compiler may drop),
    lo = x - hi rounded; hi + lo is x to 2^-17."""
    hi = lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    return jnp.stack([hi, x - hi]).astype(jnp.bfloat16)


def _split(x: jax.Array, w: jax.Array) -> bool:
    return x.dtype == jnp.float32 and w.dtype == jnp.bfloat16


def _exact(x: jax.Array):
    # float32 x float32 is one bf16 pass on a TPU unless told otherwise.
    return lax.Precision.HIGHEST if x.dtype == jnp.float32 else None


def dot(x: jax.Array, w: jax.Array) -> jax.Array:
    """x (..., D) @ w (D, N), accumulated and returned float32: operands
    of x's dtype, but float32 x against bf16 w as two bf16 terms."""
    if _split(x, w):
        y = jnp.dot(bf16_terms(x), w, preferred_element_type=jnp.float32)
        return y[0] + y[1]
    return jnp.dot(x, w.astype(x.dtype), precision=_exact(x),
                   preferred_element_type=jnp.float32)


def route(cfg, lp: Dict[str, jax.Array], m: jax.Array
          ) -> Tuple[jax.Array, jax.Array]:
    """m (T, D) float32, the FFN norm's output -> (weights (T, K)
    float32, experts (T, K) int32; `lax.top_k` breaks ties towards the
    lower index).

    "softmax": softmax over all experts, the K largest, renormalised.
    "sigmoid": a sigmoid score an expert; the K largest of score + bias
    (`router_bias`, a per-expert leaf used for the selection only);
    weights are the scores themselves, renormalised where `route_norm`,
    times `route_scale`."""
    logits = jnp.dot(m.astype(jnp.float32),
                     lp["router"].astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    if cfg.score_func == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        chosen = scores
        if "router_bias" in lp:
            chosen = scores + lp["router_bias"].astype(jnp.float32)
        _, experts = lax.top_k(chosen, cfg.moe_top_k)
        weights = jnp.take_along_axis(scores, experts, axis=-1)
        if cfg.route_norm:
            weights = weights / (
                jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
        return weights * cfg.route_scale, experts
    if cfg.score_func != "softmax":
        raise ValueError(f"score_func must be 'softmax' or 'sigmoid', got "
                         f"{cfg.score_func!r}")
    weights, experts = lax.top_k(jax.nn.softmax(logits, axis=-1),
                                 cfg.moe_top_k)
    return weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-9), \
        experts


EXPERT_LEAVES = ("w_gate", "w_up", "w_down")


_GMM_TILE = 2 ** 20         # elements of an expert a grid step: 2 MB
# (k, n) -> tn where a chip run read another tile faster than the rule's.
# 896 x 2304 (mellum's down product): all of n, 3.71 against 1152's 3.85
# ms at 131,072 rows and 0.29 against 0.36 at 128 (my chip runs, PR 32).
_GMM_MEASURED_TN = {(896, 2304): 2304}


def _gmm_tiling(rows: int, k: int, n: int):
    """(tm, tk, tn) for megablox's kernel over `rows` (a multiple of 128:
    `grouped_dot` pads), or None where it does not tile the shape. The
    whole contraction a tile, so that an expert's slab is fetched once
    for all its row tiles, and of n the multiple of 128 dividing it whose
    slab is nearest 2 MB (2048 x 1024: 512 of 1024; 1024 x 2048: 1024;
    2304 x 896: all 896, since 896 = 7 x 128 leaves only 128 and 896 and
    a slab of 128 re-reads the rows seven times), or the tile a chip run
    found faster (`_GMM_MEASURED_TN`). It reads each expert hit once and
    is bound by those bytes at decode's rows (0.69 ms against
    `lax.ragged_dot`'s 1.58 for 512 rows over 110 of 128 experts of
    2048 x 1024; 4.3 against 6.4 for an admission tile's 131,072 rows: my
    chip runs, PR 28)."""
    if rows % 128 or k % 128 or n % 128 or k > 4096:
        return None
    tm = 256 if rows % 256 == 0 and rows >= 4096 else 128
    want = _GMM_TILE / k
    tn = _GMM_MEASURED_TN.get((k, n)) or min(
        (128 * d for d in range(1, n // 128 + 1) if n % (128 * d) == 0),
        key=lambda t: max(t / want, want / t))
    return tm, k, tn


def grouped_dot(a: jax.Array, w: jax.Array, groups: jax.Array,
                kernel=None) -> jax.Array:
    """a (R, D), rows sorted by group, against w (G, D, N); `groups` (G,)
    rows a group -> float32 (R, N). Operands of a's dtype, but float32 a
    against bf16 w as two bf16 terms (a row's hi and lo lie together, in
    its group). `kernel`: None = megablox's pallas kernel on a TPU where
    it tiles the shape, `lax.ragged_dot` anywhere else; True / False
    force one (False is what a CPU runs); "interpret" runs the kernel in
    the pallas interpreter."""
    R = a.shape[0]
    if _split(a, w):
        two = jnp.swapaxes(bf16_terms(a), 0, 1).reshape(2 * R, a.shape[-1])
        y = grouped_dot(two, w, 2 * groups, kernel)
        return jnp.sum(y.reshape(R, 2, -1), axis=1)
    # Rows up to the kernel's row tile: the rows added belong to no
    # group, so the kernel neither reads nor writes them (a lone caller's
    # decode is 64 rows, and `lax.ragged_dot` costs twice the kernel).
    pad = -R % 128
    tiling = _gmm_tiling(R + pad, a.shape[1], w.shape[2])
    if kernel is None:
        from ..ops.flash_attention import on_tpu
        kernel = on_tpu()
    if kernel and tiling and a.dtype == w.dtype == jnp.bfloat16:
        from jax.experimental.pallas.ops.tpu import megablox
        if pad:
            a = jnp.pad(a, ((0, pad), (0, 0)))
        y = megablox.gmm(a, w, groups, jnp.float32, tiling,
                         interpret=kernel == "interpret")
        return y[:R] if pad else y
    return lax.ragged_dot(a, w.astype(a.dtype), groups, precision=_exact(a),
                          preferred_element_type=jnp.float32)


def grouped_experts(w: Dict[str, jax.Array], x: jax.Array,
                    weights: jax.Array, experts: jax.Array, n_experts: int,
                    first=0) -> Tuple[jax.Array, jax.Array]:
    """x (T, D) in the products' dtype; weights, experts (T, K). Returns
    (sum over a token's experts of weight x SwiGLU_e(x), float32 (T, D);
    rows an expert holds, int32 (E,)).

    `w` holds the three expert matrices, (G, D, F) and (G, F, D): this
    layer's E experts are groups [first, first + E) of G. A stack hands
    in all its layers' experts as one array and says where the layer's
    begin, so no layer's experts are ever sliced out (a grouped product
    is a kernel, and a kernel's operand is copied where it is a slice:
    0.8 GB a layer a step at 128 experts of 2048 x 1024)."""
    T, K = experts.shape
    E, G = n_experts, w["w_gate"].shape[0]
    flat = experts.reshape(T * K)
    order = jnp.argsort(flat, stable=True)       # pairs, sorted by expert
    sizes = jnp.bincount(flat, length=E).astype(jnp.int32)
    groups = sizes if G == E else lax.dynamic_update_slice(
        jnp.zeros((G,), jnp.int32), sizes, (first,))
    xs = x[order // K]                           # (T*K, D)

    h = jax.nn.silu(grouped_dot(xs, w["w_gate"], groups)) \
        * grouped_dot(xs, w["w_up"], groups)
    ys = grouped_dot(h.astype(x.dtype), w["w_down"], groups)
    ys = ys[jnp.argsort(order)].reshape(T, K, -1)     # back in order
    out = jnp.sum(ys * weights[..., None], axis=1)
    return out, sizes


def routed_ffn(cfg, lp: Dict[str, jax.Array], m: jax.Array, dtype,
               expert_weights=None, first=0
               ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """The routed experts of one layer on m (T, D) float32: (out (T, D)
    float32, stats int32 (3,) = [experts holding a row, rows, rows of
    the fullest expert], experts (T, K)). The expert matrices are `lp`'s
    own, or `expert_weights` from group `first` on (`grouped_experts`)."""
    with jax.named_scope("moe_router"):
        weights, experts = route(cfg, lp, m)
    with jax.named_scope("moe_experts"):
        out, sizes = grouped_experts(
            lp if expert_weights is None else expert_weights,
            m.astype(dtype), weights, experts, cfg.moe_experts, first)
    stats = jnp.stack([jnp.sum(sizes > 0), jnp.sum(sizes),
                       jnp.max(sizes)]).astype(jnp.int32)
    return out, stats, experts
