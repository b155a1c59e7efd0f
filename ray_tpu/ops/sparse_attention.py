"""Learned sparse attention's own steps (pallas, TPU; XLA anywhere else).

A second scorer (the indexer of `models/latent.py`) gives every row a
query may see one float32 score, `I_ts = sum_h w_th relu(q_th . k_s)`
over a few narrow heads, and the query attends the `k` rows of largest
score: exactly those, ties to the lower row. A decode step's scorer,
`index_scores_rows`, is one sum in XLA (one query a slot against its
slot's keys in the carried cache). A tile's three steps are each a
kernel with the same sums in XLA beside it (a CPU, a shape that does not
tile):

- `index_scores_tile`: a chunk of queries against a run of keys, the
  causal edge applied; a tile's scorer.
- `topk_bias`: the rows chosen, as what a softmax adds to a score (0
  where chosen, `NEG_INF` elsewhere). The k-th largest score of a row
  is found exactly, a bit at a time over the scores' own bit patterns
  (32 counts of a row held in VMEM; no sort, no approximation); the
  rows above it are chosen, and of the rows equal to it the lowest.
  One kernel counts and writes the bias; told where its block of
  queries stands, it reads only the columns a query of the block may
  see.
- `masked_attention`: per-head attention of a chunk of queries over a
  chunk of keys under such a bias, returning the chunk's part with its
  log-sum-exp so that the parts of several key chunks add up
  (`merge_parts`).

The two scorers take bf16 operands in one product or float32 ones as two
bf16 terms each in three (`lax.Precision.HIGH` in XLA): a choice among
thousands of scores turns on their last bits, and a query's or a key's
rounding to bf16 moves rows across the k-th place
(`TransformerConfig.index_dtype` says which a configuration runs).

A decode step takes its chosen rows' indices from `lax.top_k` (exact,
ties to the lower index) and gathers them; a tile never gathers: every
query of a chunk has a set of its own, and the chosen set is a mask on
the attention of all the rows the chunk may see.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import NEG_INF, _LANES

_NT = (((1,), (1,)), ((), ()))      # a b^T
_TILE_Q, _TILE_K = 256, 512         # a block of `index_scores_tile`
_ATTN_Q, _ATTN_K = 512, 512         # a block of `masked_attention`
_THRESHOLD_ROWS = 16                # query rows `topk_bias` holds at once
_COUNT_COLS = 1024                  # columns it counts or skips together


def use_kernels(dtype, interpret: Optional[bool] = None,
                also=()) -> bool:
    """Whether the kernels run here: bf16 operands (or a dtype of `also`)
    on a TPU outside any used mesh axis (`interpret`: in the pallas
    interpreter, for tests)."""
    from .flash_attention import on_tpu

    if interpret:
        return True
    mesh = jax.sharding.get_abstract_mesh()
    sharded = any(n > 1 for n in dict(getattr(mesh, "shape", None)
                                      or {}).values())
    return on_tpu() and not sharded and (dtype == jnp.bfloat16
                                         or dtype in also)


def _terms(x):
    """float32 x -> (hi, lo) bfloat16 with hi + lo = x to 2^-17 (inside a
    kernel: the cast; Pallas drops none)."""
    hi = x.astype(jnp.bfloat16)
    return hi, (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)


def _dot_nt(a, b):
    """a b^T, float32 out, of bf16 operands in one product or of float32
    operands, each given as its two bf16 terms, in three (hi hi + hi lo +
    lo hi: 2^-16 of the product, where one bf16 product leaves 2^-8)."""
    if isinstance(a, tuple):
        (ah, al), (bh, bl) = a, b
        return _dot_nt(ah, bh) + _dot_nt(ah, bl) + _dot_nt(al, bh)
    return lax.dot_general(a, b, _NT, preferred_element_type=jnp.float32)


def _operand(x):
    return _terms(x) if x.dtype == jnp.float32 else x


def _xla_precision(dtype):
    """float32 operands as three bf16 products, like the kernels'."""
    return lax.Precision.HIGH if dtype == jnp.float32 else None


# ---------------------------------------------------------------------------
# Scores of one query a slot against the carried cache
# ---------------------------------------------------------------------------

def index_scores_rows(q: jax.Array, w: jax.Array, k_all: jax.Array,
                      l: jax.Array, n_rows: jax.Array) -> jax.Array:
    """q (B, Hi, D), w (B, Hi) float32, against rows [0, n_rows[b]) of
    layer `l` of the indexer's cache `k_all` (L, B, S, D) -> float32
    (B, S): `sum_h w[b, h] relu(q[b, h] . k[b, s])`, `-inf` from
    `n_rows[b]` on."""
    S = k_all.shape[2]
    keys = lax.dynamic_index_in_dim(k_all, l, 0, keepdims=False)
    s = jnp.einsum("bhd,bsd->bhs", q, keys.astype(q.dtype),
                   precision=_xla_precision(q.dtype),
                   preferred_element_type=jnp.float32)
    s = jnp.sum(jnp.maximum(s, 0.0) * w[:, :, None], axis=1)
    return jnp.where(jnp.arange(S)[None, :] < n_rows[:, None], s, -jnp.inf)


# ---------------------------------------------------------------------------
# Scores of a chunk of queries against a run of keys
# ---------------------------------------------------------------------------

def _tile_kernel(off_ref, q_ref, w_ref, k_ref, o_ref, *, heads, tq, tk):
    q0 = off_ref[0] + pl.program_id(1) * tq
    k0 = pl.program_id(2) * tk

    @pl.when(k0 <= q0 + tq - 1)
    def _seen():
        keys, w = _operand(k_ref[...]), w_ref[...]
        acc = jnp.zeros((tq, tk), jnp.float32)
        for h in range(heads):
            s = _dot_nt(_operand(q_ref[h]), keys)
            acc = acc + jnp.maximum(s, 0.0) * w[:, h:h + 1]
        qpos = q0 + lax.broadcasted_iota(jnp.int32, (tq, tk), 0)
        kpos = k0 + lax.broadcasted_iota(jnp.int32, (tq, tk), 1)
        o_ref[...] = jnp.where(kpos <= qpos, acc, -jnp.inf)

    @pl.when(k0 > q0 + tq - 1)
    def _ahead():
        o_ref[...] = jnp.full((tq, tk), -jnp.inf, jnp.float32)


def index_scores_tile(q: jax.Array, w: jax.Array, keys: jax.Array,
                      q_offset, *, interpret: Optional[bool] = None
                      ) -> jax.Array:
    """q (W, T, Hi, D), w (W, T, Hi) float32, keys (W, S, D): the queries
    stand at rows [q_offset, q_offset + T) of the keys' run -> float32
    (W, T, S): `sum_h w[t, h] relu(q[t, h] . keys[s])` where `s <=
    q_offset + t`, `-inf` elsewhere."""
    W, T, Hi, D = q.shape
    S = keys.shape[1]
    q_offset = jnp.asarray(q_offset, jnp.int32)
    tq, tk = min(_TILE_Q, T), min(_TILE_K, S)
    if not (use_kernels(q.dtype, interpret, (jnp.float32,))
            and q.dtype == keys.dtype
            and T % tq == 0 and S % tk == 0 and tq % 8 == 0
            and tk % _LANES == 0 and D % _LANES == 0):
        s = jnp.einsum("wthd,wsd->wths", q, keys.astype(q.dtype),
                       precision=_xla_precision(q.dtype),
                       preferred_element_type=jnp.float32)
        s = jnp.sum(jnp.maximum(s, 0.0) * w[..., None], axis=2)
        seen = jnp.arange(S)[None, :] <= q_offset + jnp.arange(T)[:, None]
        return jnp.where(seen[None], s, -jnp.inf)
    return pl.pallas_call(
        functools.partial(_tile_kernel, heads=Hi, tq=tq, tk=tk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(W, T // tq, S // tk),
            in_specs=[
                pl.BlockSpec((None, Hi, tq, D),
                             lambda b, i, j, off: (b, 0, i, 0)),
                pl.BlockSpec((None, tq, Hi), lambda b, i, j, off: (b, i, 0)),
                pl.BlockSpec((None, tk, D), lambda b, i, j, off: (b, j, 0))],
            out_specs=pl.BlockSpec((None, tq, tk),
                                   lambda b, i, j, off: (b, i, j))),
        out_shape=jax.ShapeDtypeStruct((W, T, S), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=bool(interpret),
        metadata={"kernel": "index_scores_tile"},
    )(q_offset.reshape(1), jnp.swapaxes(q, 1, 2), w.astype(jnp.float32),
      keys)


# ---------------------------------------------------------------------------
# The k best of a row, exactly
# ---------------------------------------------------------------------------

_SIGN = -2 ** 31
_LOWEST = _SIGN + 0x7FFFFF            # `_ordered(-inf)`


def _ordered(x: jax.Array) -> jax.Array:
    """float32 -> int32 that compare alike (-0.0 below 0.0)."""
    b = lax.bitcast_convert_type(x, jnp.int32)
    return jnp.where(b < 0, b ^ jnp.int32(0x7FFFFFFF), b)


def _count_cols(S: int) -> int:
    """Columns of a row of S (a multiple of the lanes) that `topk_bias`
    counts or skips together."""
    return math.gcd(S, _COUNT_COLS)


def columns_counted(q_offset, T: int, S: int):
    """Of the S columns a row has, those `topk_bias` counts for T queries
    at rows [q_offset, q_offset + T): whole blocks of `_count_cols(S)` up
    to the last query's own column (a number, or one the device counts;
    past S where the queries are)."""
    tc = _count_cols(S)
    return (q_offset + T + tc - 1) // tc * tc


def _threshold_kernel(n_ref, x_ref, bias_ref, thr_ref, cnt_ref, key_ref, *,
                      k, tc, neg):
    """A block of rows: `n_ref[0]` column blocks of `tc` hold every score
    that is not `-inf`; no other is read. `neg`: `NEG_INF` as the bias'
    dtype holds it."""
    n = n_ref[0]
    rows, S = key_ref.shape

    def at(j):
        return pl.ds(pl.multiple_of(j * tc, tc), tc)

    def order(j, _):
        key_ref[:, at(j)] = _ordered(x_ref[:, at(j)])

    lax.fori_loop(0, n, order, None)

    def count(thr):
        # Sums of ones, in lanes first: whole numbers, exact in any order.
        return jnp.sum(lax.fori_loop(
            0, n, lambda j, acc: acc + jnp.where(key_ref[:, at(j)] >= thr,
                                                 1.0, 0.0),
            jnp.zeros((rows, tc), jnp.float32)), axis=1, keepdims=True)

    # The k-th largest key, built from its top bit down in the order of
    # unsigned patterns (a signed key with its sign bit turned).
    best = jnp.zeros((rows, 1), jnp.int32)
    for bit in range(31, -1, -1):
        cand = best | jnp.int32(_SIGN if bit == 31 else 1 << bit)
        best = jnp.where(count(cand ^ jnp.int32(_SIGN)) >= k, cand, best)
    # Fewer than k columns counted: no bit is kept, and the threshold lies
    # below every key, where `-inf` in the other columns would put it at
    # theirs: every column seen is chosen either way, and no tie counted.
    thr = best ^ jnp.int32(_SIGN)
    thr_ref[...] = jnp.broadcast_to(thr, thr_ref.shape)
    cnt_ref[...] = jnp.broadcast_to(count(thr), cnt_ref.shape)

    def choose(j, _):
        chosen = (key_ref[:, at(j)] >= thr) & (x_ref[:, at(j)] > -jnp.inf)
        bias_ref[:, at(j)] = jnp.where(chosen, 0.0, neg).astype(
            bias_ref.dtype)

    def ahead(j, _):
        bias_ref[:, at(j)] = jnp.full((rows, tc), neg, bias_ref.dtype)

    lax.fori_loop(0, n, choose, None)
    lax.fori_loop(n, S // tc, ahead, None)


def _exact_choice(key, thr, k):
    """Where rows tie at the threshold: of the rows equal to it, the
    lowest as far as `k` reaches."""
    above = key > thr
    equal = key == thr
    room = k - jnp.sum(above, axis=-1, keepdims=True)
    rank = jnp.cumsum(equal, axis=-1) - equal          # ties before this one
    return above | (equal & (rank < room))


def _bias_of(chosen, scores, dtype):
    return jnp.where(chosen & (scores > -jnp.inf), 0.0,
                     NEG_INF).astype(dtype)


def _topk_bias_xla(scores: jax.Array, k: int, dtype) -> jax.Array:
    """`topk_bias` as XLA makes it, over whole rows: what a CPU and a
    shape that does not tile take, and the definition the kernel is held
    to."""
    W, T, S = scores.shape
    _, idx = lax.top_k(scores, min(int(k), S))
    chosen = jnp.zeros((W, T, S), bool)
    return _bias_of(chosen.at[jnp.arange(W)[:, None, None],
                              jnp.arange(T)[None, :, None], idx].set(True),
                    scores, dtype)


def _threshold_bias(scores, k: int, q_offset, dtype, interpret
                    ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """The kernel: (bias (W, T, S) `dtype` with every column at or above
    a row's threshold chosen, the k-th largest ordered key a row (W, T,
    1) int32, the columns at or above it (W, T, 1) float32)."""
    W, T, S = scores.shape
    rows, tc = _THRESHOLD_ROWS, _count_cols(S)
    counted = S if q_offset is None else jnp.minimum(
        columns_counted(q_offset, T, S), S)
    bias, thr, cnt = pl.pallas_call(
        functools.partial(
            _threshold_kernel, k=float(k), tc=tc,
            # Exact in `dtype`, so that the kernel's cast rounds nothing.
            neg=float(np.float32(NEG_INF).astype(jnp.dtype(dtype)))),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(W, T // rows),
            in_specs=[pl.BlockSpec((None, rows, S), lambda b, i, n: (b, i, 0))],
            out_specs=[
                pl.BlockSpec((None, rows, S), lambda b, i, n: (b, i, 0)),
                pl.BlockSpec((None, rows, _LANES), lambda b, i, n: (b, i, 0)),
                pl.BlockSpec((None, rows, _LANES), lambda b, i, n: (b, i, 0))],
            scratch_shapes=[pltpu.VMEM((rows, S), jnp.int32)]),
        out_shape=[jax.ShapeDtypeStruct((W, T, S), dtype),
                   jax.ShapeDtypeStruct((W, T, _LANES), jnp.int32),
                   jax.ShapeDtypeStruct((W, T, _LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=64 * 2 ** 20),
        interpret=bool(interpret),
        metadata={"kernel": "topk_threshold"},
    )((jnp.asarray(counted, jnp.int32) // tc).reshape(1), scores)
    return bias, thr[..., :1], cnt[..., :1]


def _ties(thr, cnt, k: int) -> jax.Array:
    """Whether any row has more columns at or above its threshold than
    k: some tie at it. A query that sees fewer than k rows has its
    threshold below every key: no tie to settle."""
    return jnp.any((cnt > k) & (thr > _LOWEST))


def topk_bias(scores: jax.Array, k: int, q_offset=None, *,
              dtype=jnp.bfloat16, interpret: Optional[bool] = None
              ) -> jax.Array:
    """scores (W, T, S) float32, `-inf` where a query may not look ->
    (W, T, S) `dtype`: 0 at the `k` largest scores of each row that are
    not `-inf` (ties to the lower column; all of them where fewer are
    not `-inf`), `NEG_INF` elsewhere. `q_offset`: the queries stand at
    rows [q_offset, q_offset + T) of the columns' run and every score
    from column q_offset + T on is `-inf`, as `index_scores_tile` leaves
    them: the kernel reads none of those (`columns_counted`)."""
    W, T, S = scores.shape
    k = min(int(k), S)
    if not (use_kernels(jnp.bfloat16, interpret)
            and T % _THRESHOLD_ROWS == 0 and S % _LANES == 0):
        return _topk_bias_xla(scores, k, dtype)
    bias, thr, cnt = _threshold_bias(scores, k, q_offset, dtype, interpret)
    # Ties are settled in XLA over whole rows, and only where there are
    # any (two float32 sums alike at a row's k-th place: a tenth of the
    # blocks of 1,024 queries in glm5-longctx's tiles, PERF.md section 5).
    return lax.cond(
        _ties(thr, cnt, k),
        lambda: _bias_of(_exact_choice(_ordered(scores), thr, k), scores,
                         dtype),
        lambda: bias)

# ---------------------------------------------------------------------------
# Per-head attention under a chosen-set bias
# ---------------------------------------------------------------------------

def _attn_kernel(q_ref, k_ref, v_ref, b_ref, o_ref, lse_ref, acc_ref, m_ref,
                 sum_ref, *, sm_scale):
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        sum_ref[...] = jnp.zeros_like(sum_ref)

    s = lax.dot_general(q_ref[...], k_ref[...], _NT,
                        preferred_element_type=jnp.float32) * sm_scale \
        + b_ref[...].astype(jnp.float32)
    m_prev, sum_prev = m_ref[:, :1], sum_ref[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    # A row with nothing chosen so far has m_new = NEG_INF and s - m_new
    # = 0: such a pair must weigh nothing.
    p = jnp.where(s > NEG_INF / 2, jnp.exp(s - m_new), 0.0)
    sum_new = alpha * sum_prev + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + lax.dot(
        p.astype(v_ref.dtype), v_ref[...],
        preferred_element_type=jnp.float32)
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    sum_ref[...] = jnp.broadcast_to(sum_new, sum_ref.shape)

    @pl.when(j == pl.num_programs(3) - 1)
    def _finalize():
        total = sum_ref[...]
        o_ref[...] = acc_ref[...] / jnp.maximum(total[:, :1], 1e-30)
        lse_ref[...] = jnp.where(total > 0.0,
                                 m_ref[...] + jnp.log(
                                     jnp.maximum(total, 1e-30)), NEG_INF)


def masked_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     bias: jax.Array, sm_scale: float, *,
                     interpret: Optional[bool] = None
                     ) -> Tuple[jax.Array, jax.Array]:
    """q (W, T, H, Dk), k (W, Tk, H, Dk), v (W, Tk, H, Dv), bias (W, T,
    Tk) (0 where query t may attend key s, `NEG_INF` elsewhere; the same
    for every head) -> (out (W, T, H, Dv) float32: softmax(q k^T *
    sm_scale + bias) v over these Tk keys alone, zeros for a query with
    none; lse (W, T, H) float32: the log of that softmax's denominator,
    `NEG_INF` for a query with none). `merge_parts` adds up the parts of
    several runs of keys."""
    W, T, H, Dk = q.shape
    Tk, Dv = k.shape[1], v.shape[-1]
    tq, tk = min(_ATTN_Q, T), min(_ATTN_K, Tk)
    if not (use_kernels(q.dtype, interpret) and T % tq == 0 and Tk % tk == 0
            and tq % 8 == 0 and tk % _LANES == 0 and Dk % _LANES == 0
            and Dv % _LANES == 0):
        hi = lax.Precision.HIGHEST if q.dtype == jnp.float32 else None
        s = jnp.einsum("wthd,wshd->whts", q, k, precision=hi,
                       preferred_element_type=jnp.float32) * sm_scale \
            + bias.astype(jnp.float32)[:, None]
        m = jnp.max(s, axis=-1, keepdims=True)
        p = jnp.where(s > NEG_INF / 2, jnp.exp(s - m), 0.0)
        total = jnp.sum(p, axis=-1, keepdims=True)
        out = jnp.einsum("whts,wshd->wthd", (p / jnp.maximum(total, 1e-30)
                                             ).astype(v.dtype), v,
                         precision=hi, preferred_element_type=jnp.float32)
        lse = jnp.where(total > 0.0, m + jnp.log(jnp.maximum(total, 1e-30)),
                        NEG_INF)[..., 0]
        return out, jnp.swapaxes(lse, 1, 2)

    def heads_major(x):
        return jnp.swapaxes(x, 1, 2)

    out, lse = pl.pallas_call(
        functools.partial(_attn_kernel, sm_scale=sm_scale),
        grid=(W, H, T // tq, Tk // tk),
        in_specs=[
            pl.BlockSpec((None, None, tq, Dk), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((None, None, tk, Dk), lambda b, h, i, j: (b, h, j, 0)),
            pl.BlockSpec((None, None, tk, Dv), lambda b, h, i, j: (b, h, j, 0)),
            pl.BlockSpec((None, tq, tk), lambda b, h, i, j: (b, i, j))],
        out_specs=[
            pl.BlockSpec((None, None, tq, Dv), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((None, None, tq, _LANES),
                         lambda b, h, i, j: (b, h, i, 0))],
        out_shape=[jax.ShapeDtypeStruct((W, H, T, Dv), jnp.float32),
                   jax.ShapeDtypeStruct((W, H, T, _LANES), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((tq, Dv), jnp.float32),
                        pltpu.VMEM((tq, _LANES), jnp.float32),
                        pltpu.VMEM((tq, _LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=bool(interpret),
        metadata={"kernel": "sparse_prefill_attn"},
    )(heads_major(q), heads_major(k), heads_major(v), bias)
    return heads_major(out), heads_major(lse[..., 0])


def merge_parts(out, lse, part, part_lse):
    """Two parts of one softmax over disjoint runs of keys, each (out
    (..., Dv) float32 normalised over its own keys, lse (...)) -> the
    softmax over both."""
    both = jnp.logaddexp(lse, part_lse)
    return out * jnp.exp(lse - both)[..., None] \
        + part * jnp.exp(part_lse - both)[..., None], both
