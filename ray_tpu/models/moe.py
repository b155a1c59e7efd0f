"""The routed feed-forward layer of the serving path: nothing dropped.

`transformer.moe_ffn` (training) gives every expert a fixed capacity and
drops the tokens over it; its `(T, K, E, capacity)` dispatch tensor is
also out of reach at serving sizes. Here the token-expert pairs are
sorted by expert and multiplied by groups (`grouped_dot`: a grouped-matmul
kernel that visits only the experts that hold rows; `grouped_swiglu`: the
gate and up products and the activation between them as one such kernel),
then put back in order, weighted and summed (`down_and_combine`: on a TPU
the down product leaves each row by itself and one kernel fetches a
token's rows, weights and adds them). Router scores and the selection
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


def dot_terms(act_dtype, weight_dtype) -> int:
    """The bf16 terms `dot` multiplies an activation as: two for float32
    on a bf16 weight (every position is two rows of the product), else
    one. The one reading of the two dtypes: `_split` asks it of two
    arrays, `periodic.cache_terms` of a configuration's cache, and the
    engine of the positions its admission tile holds (serve/llm.py)."""
    return 2 if (act_dtype == jnp.float32
                 and weight_dtype == jnp.bfloat16) else 1


def _split(x: jax.Array, w: jax.Array) -> bool:
    return dot_terms(x.dtype, w.dtype) == 2


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
    if rows % 128 or k % 128 or n % 128 or k > 8192:
        return None
    tm = 256 if rows % 256 == 0 and rows >= 4096 else 128
    want = _GMM_TILE / k
    tn = _GMM_MEASURED_TN.get((k, n)) or min(
        (128 * d for d in range(1, n // 128 + 1) if n % (128 * d) == 0),
        key=lambda t: max(t / want, want / t))
    return tm, k, tn


def _kernel_rows(a: jax.Array, w: jax.Array, kernel):
    """Where a pallas kernel takes a (R, D) against w (G, D, N) by
    groups: (a padded to the kernel's row tile, its tiling, whether it
    runs in the interpreter); None where XLA's `ragged_dot` does: off
    the TPU, a shape that does not tile, operands not both bf16. `kernel`
    as `grouped_dot`'s."""
    pad = -a.shape[0] % 128
    tiling = _gmm_tiling(a.shape[0] + pad, a.shape[1], w.shape[2])
    if kernel is None:
        from ..ops.flash_attention import on_tpu
        kernel = on_tpu()
    if not (kernel and tiling and a.dtype == w.dtype == jnp.bfloat16):
        return None
    # The rows added belong to no group, so a grouped kernel neither
    # reads nor writes them (a lone caller's decode is 64 rows, and
    # `lax.ragged_dot` costs twice the kernel).
    rows = jnp.pad(a, ((0, pad), (0, 0))) if pad else a
    return rows, tiling, kernel == "interpret"


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
    takes = _kernel_rows(a, w, kernel)
    if takes:
        from jax.experimental.pallas.ops.tpu import megablox
        rows, tiling, interpret = takes
        return megablox.gmm(rows, w, groups, jnp.float32, tiling,
                            interpret=interpret)[:R]
    return lax.ragged_dot(a, w.astype(a.dtype), groups, precision=_exact(a),
                          preferred_element_type=jnp.float32)


def grouped_swiglu(a: jax.Array, w_gate: jax.Array, w_up: jax.Array,
                   groups: jax.Array, kernel=None) -> jax.Array:
    """`silu(a @ w_gate) * (a @ w_up)` by groups, in a's dtype: both
    products accumulated and the activation taken in float32, rounded
    once. Where `grouped_dot` would take the kernel for both products
    (`kernel` as there) they are one kernel, `ops/grouped_swiglu`: the
    rows are read once and no float32 (R, N) array is written. Anywhere
    else two `grouped_dot` calls and XLA's activation: off the TPU, a
    shape that does not tile, and float32 rows over bf16 weights, whose
    two terms a row are summed before the activation."""
    takes = _kernel_rows(a, w_gate, kernel)
    if takes:
        from ..ops.grouped_swiglu import gmm_swiglu
        rows, tiling, interpret = takes
        return gmm_swiglu(rows, w_gate, w_up, groups, tiling,
                          interpret=interpret)[:a.shape[0]]
    h = jax.nn.silu(grouped_dot(a, w_gate, groups, kernel)) \
        * grouped_dot(a, w_up, groups, kernel)
    return h.astype(a.dtype)


def _grouped_swiglu(w: Dict[str, jax.Array], xs: jax.Array,
                    groups: jax.Array) -> jax.Array:
    """SwiGLU_g of rows xs sorted by group, a group's own matrices."""
    h = grouped_swiglu(xs, w["w_gate"], w["w_up"], groups)
    return grouped_dot(h, w["w_down"], groups)


def combine(ys: jax.Array, inv: jax.Array, weights: jax.Array,
            rows=None) -> jax.Array:
    """ys (P, D) float32, a row a pair; inv (T * K,), where the row of
    token t's j-th pair lies; weights (T, K) -> float32 (T, D): each
    token's rows weighted and summed, and zero for a token nobody owns
    (`rows` (T,) bool), whatever its rows hold."""
    T, K = weights.shape
    ys = ys[inv].reshape(T, K, -1) * weights[..., None]
    if rows is not None:
        # A row of no group was never written: what lies there must not
        # reach the sum.
        ys = jnp.where(rows[:, None, None], ys, 0.0)
    return jnp.sum(ys, axis=1)


def down_and_combine(h: jax.Array, w_down: jax.Array, groups: jax.Array,
                     inv: jax.Array, weights: jax.Array, rows=None,
                     kernel=None) -> jax.Array:
    """`combine` of `grouped_dot(h, w_down, groups)`: h (P, F) sorted by
    group, the rest as `combine`'s. Where `grouped_dot` would take the
    kernel for bf16 rows (`kernel` as there) and the product's float32
    rows are more than XLA's gather keeps on the chip
    (`ops/moe_combine.MIN_ROW_BYTES`, a measured crossover: an admission
    tile's rows are, a decode step's are not) the two are
    `ops/moe_combine`'s pair: the product writes each row by itself, (P,
    1, D), and one kernel copies a token's K rows from there, weights
    them and adds them in the order of j. The (P, D) array goes through
    memory twice, not four times: XLA's gather writes all of it again for
    the sum to read (6.37 -> 4.05 ms at mellum's 65,536 x 2,304, my chip
    run, PR 47). Anywhere else the lines of `combine`: off the TPU, a
    shape that does not tile, fewer rows than that or more pairs than
    the kernel's scalar memory holds, and float32 rows over bf16 weights,
    whose two terms a row XLA sums (its reshape into rows apart is a pass
    more: 1.04 against 0.91 ms at trinity's tile)."""
    from ..ops import moe_combine
    P, D = h.shape[0], w_down.shape[2]
    takes = _kernel_rows(h, w_down, kernel) \
        if 4 * P * D >= moe_combine.MIN_ROW_BYTES \
        and P <= moe_combine.MAX_PAIRS else None
    if not takes:
        return combine(grouped_dot(h, w_down, groups, kernel), inv, weights,
                       rows)
    padded, tiling, interpret = takes
    ys = moe_combine.gmm_rows_apart(padded, w_down, groups, tiling,
                                    interpret=interpret)
    return moe_combine.moe_combine(ys, inv, weights, rows,
                                   interpret=interpret)


def _count(key: jax.Array, n: int) -> jax.Array:
    """How many of `key` (P,) are each of 0 .. n-1, int32 (n,): a
    compare and a sum (`jnp.bincount` is a scatter-add, 0.57 ms for
    65,536 keys on a v5e: PERF.md, PR 44)."""
    return jnp.sum(key[None, :] == jnp.arange(n, dtype=key.dtype)[:, None],
                   axis=1, dtype=jnp.int32)


def grouped_experts(w: Dict[str, jax.Array], x: jax.Array,
                    weights: jax.Array, experts: jax.Array, n_experts: int,
                    first=0, rows=None
                    ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x (T, D) in the products' dtype; weights, experts (T, K). Returns
    (sum over a token's experts of weight x SwiGLU_e(x), float32 (T, D);
    rows an expert took, int32 (E,); pairs that chose it, int32 (E,)).

    `rows` (T,) bool: the rows somebody owns (None: every one). A pair
    of any other row chooses no expert: it sorts last and counts in no
    group, so the products neither read nor write its row, the experts
    only it chose are not fetched, and its row of the result is zero.
    The pairs that chose an expert count it all the same.

    `w` holds the three expert matrices, (G, D, F) and (G, F, D): this
    layer's E experts are groups [first, first + E) of G. A stack hands
    in all its layers' experts as one array and says where the layer's
    begin, so no layer's experts are ever sliced out (a grouped product
    is a kernel, and a kernel's operand is copied where it is a slice:
    0.8 GB a layer a step at 128 experts of 2048 x 1024)."""
    T, K = experts.shape
    E, G = n_experts, w["w_gate"].shape[0]
    flat = experts.reshape(T * K)
    # An unowned pair of expert e takes the key E + e: last in the sort,
    # and counted apart in the one count.
    key = flat if rows is None else jnp.where(
        jnp.repeat(rows, K), flat, E + flat)
    order = jnp.argsort(key, stable=True)        # pairs, sorted by expert
    count = _count(key, E if rows is None else 2 * E)
    sizes = chose = count
    if rows is not None:
        sizes, chose = count[:E], count[:E] + count[E:]
    groups = sizes if G == E else lax.dynamic_update_slice(
        jnp.zeros((G,), jnp.int32), sizes, (first,))
    h = grouped_swiglu(x[order // K], w["w_gate"], w["w_up"], groups)
    out = down_and_combine(h, w["w_down"], groups, jnp.argsort(order),
                           weights, rows)
    return out, sizes, chose


def held_pass_rows(pairs: int, held: int, routed: int) -> int:
    """Rows a pass of `held_experts` takes of `pairs` token-expert pairs
    when `held` of the `routed` experts are here: twice the share a
    uniform router would keep, in whole row tiles of the grouped kernel,
    and never more than there are pairs."""
    want = -(-2 * pairs * held // routed)
    return min(-(-max(want, 1) // 128) * 128, -(-pairs // 128) * 128)


def held_experts(w: Dict[str, jax.Array], x: jax.Array, weights: jax.Array,
                 experts: jax.Array, n_held: int, first_held: int,
                 n_routed: int, first=0, rows=None
                 ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """`grouped_experts` for a layer that holds experts [first_held,
    first_held + n_held) of the `n_routed` its router chose among:
    `experts` (T, K) name any of them, the pairs that chose a held one
    are kept, and the result is the part of the sum the held experts
    give, float32 (T, D), beside the rows each held expert took and the
    pairs that chose it, int32 (n_held,) each. The rest of the sum is
    other chips'; nothing stands in for it here. A pair of a row nobody
    owns (`rows`) is an absent pair.

    Kept pairs come first in the sort, by expert, and are worked off a
    pass of `held_pass_rows` at a time: a pass gathers its rows of x,
    multiplies them by groups, weights them and adds each to its token's
    row: one product of a 0 / 1 matrix (token t owns row r of the pass)
    against the weighted rows, in x's dtype or, float32 over bf16
    weights, as two bf16 terms (a scatter-add of 8,192 rows of 7,680 took
    22 ms a layer on a v5e where this takes 7: PERF.md, PR 34). The
    passes are a loop the
    device counts, as many as the kept pairs need: none where no pair
    chose a held expert (no product runs, the result is zeros), one at a
    uniform router's load, more where the load leans on this share. No
    kept pair is dropped and no absent pair is gathered or multiplied."""
    T, K = experts.shape
    E, G, P = n_held, w["w_gate"].shape[0], T * K
    C = held_pass_rows(P, E, n_routed)
    local = experts.reshape(P) - first_held
    key = jnp.where((local >= 0) & (local < E), local, E)   # absent: last
    if rows is not None:
        # An unowned pair is absent too, and counted apart: E + 1 + key.
        key = jnp.where(jnp.repeat(rows, K), key, E + 1 + key)
    order = jnp.argsort(key, stable=True)
    count = _count(key, E + 1 if rows is None else 2 * E + 2)
    sizes = chose = count[:E]
    if rows is not None:
        chose = sizes + count[E + 1:2 * E + 1]
    ends = jnp.cumsum(sizes)
    starts, kept = ends - sizes, ends[-1]
    room = -(-P // C) * C - P           # the last pass may reach past P
    token = jnp.pad(order // K, (0, room))
    weight = jnp.pad(weights.reshape(P)[order], (0, room))
    # Float32 rows against bf16 weights keep to bf16 products here too.
    terms = jnp.bfloat16 if _split(x, w["w_down"]) else x.dtype

    def one_pass(i, out):
        lo = i * C
        tok = lax.dynamic_slice_in_dim(token, lo, C)
        here = jnp.clip(ends - lo, 0, C) - jnp.clip(starts - lo, 0, C)
        groups = here if G == E else lax.dynamic_update_slice(
            jnp.zeros((G,), jnp.int32), here, (first,))
        ys = _grouped_swiglu(w, x[tok], groups)         # (C, D)
        # A row past the kept pairs belongs to no group: the kernel
        # never wrote it, and what lies there must not reach the sum.
        live = lo + jnp.arange(C) < kept
        ys = jnp.where(live[:, None], ys * lax.dynamic_slice_in_dim(
            weight, lo, C)[:, None], 0.0)
        owns = ((jnp.arange(T)[:, None] == tok[None, :])
                & live[None, :]).astype(terms)          # (T, C)
        return out + _own_rows(owns, ys.astype(x.dtype))

    out = lax.fori_loop(0, (kept + C - 1) // C, one_pass,
                        jnp.zeros((T, w["w_down"].shape[-1]), jnp.float32))
    return out, sizes, chose


def _own_rows(owns: jax.Array, ys: jax.Array) -> jax.Array:
    """owns (T, C) of 0 and 1 @ ys (C, D) -> float32 (T, D): each token's
    sum of the rows it owns. bf16 ys in one product, float32 ys as two
    bf16 terms (0 and 1 are exact, so the sum carries 2^-17 of ys)."""
    if ys.dtype == jnp.float32 and owns.dtype == jnp.bfloat16:
        two = jnp.einsum("tc,kcd->ktd", owns, bf16_terms(ys),
                         preferred_element_type=jnp.float32)
        return two[0] + two[1]
    return jnp.dot(owns, ys, precision=_exact(ys),
                   preferred_element_type=jnp.float32)


def routing_stats(cfg) -> int:
    """Entries of `routed_ffn`'s stats: four, and the pairs routed
    where the layer holds a share of the experts its router scores."""
    return 4 if cfg.router_experts == cfg.moe_experts else 5


def routing_sums(stats) -> Dict[str, int]:
    """`routed_ffn`'s stats, summed over layers and steps and once on the
    host, under the counters' names: experts that took a row; the pairs
    that chose an expert held here and those of the expert most chosen,
    every slot's; the pairs the products took (the slots a request
    owns); and the token-expert pairs routed beside the pairs that chose
    an expert held here (a layer that holds all its experts keeps every
    pair; one that holds a share says how many were routed in a fifth
    entry)."""
    hit, rows, fullest, taken, *pairs = (int(n) for n in stats)
    return dict(moe_experts_hit=hit, moe_rows=rows, moe_rows_max=fullest,
                moe_rows_taken=taken,
                moe_pairs=pairs[0] if pairs else rows, moe_pairs_held=rows)


def routed_ffn(cfg, lp: Dict[str, jax.Array], m: jax.Array, dtype,
               expert_weights=None, first=0, rows=None
               ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """The routed experts of one layer on m (T, D) float32: (out (T, D)
    float32, stats int32 (4,) = [experts that took a row, pairs that
    chose an expert here, pairs of the expert most chosen, rows the
    products took], experts (T, K)). The expert matrices are `lp`'s
    own, or `expert_weights` from group `first` on (`grouped_experts`).

    `rows` (T,) bool: the rows somebody owns (None: every one). The
    router scores every row, and the second and third entries count
    every row's pairs: the router's balance. The products take the owned
    rows' pairs alone, an unowned row comes back zero, and the first and
    fourth entries count what the products took and fetched.

    Where the layer holds a share of the experts its router scores
    (`cfg.router_experts` > `cfg.moe_experts`: `held_experts`) the four
    count over the experts held and the pairs kept, and a fifth entry
    has the pairs routed, kept or not."""
    with jax.named_scope("moe_router"):
        weights, experts = route(cfg, lp, m)
    w = lp if expert_weights is None else expert_weights
    share = routing_stats(cfg) == 5
    with jax.named_scope("moe_experts"):
        if not share:
            out, sizes, chose = grouped_experts(
                w, m.astype(dtype), weights, experts, cfg.moe_experts,
                first, rows)
        else:
            out, sizes, chose = held_experts(
                w, m.astype(dtype), weights, experts, cfg.moe_experts,
                cfg.moe_first_expert, cfg.router_experts, first, rows)
    stats = [jnp.sum(sizes > 0), jnp.sum(chose), jnp.max(chose),
             jnp.sum(sizes)]
    if share:
        stats.append(experts.size)
    return out, jnp.stack(stats).astype(jnp.int32), experts
