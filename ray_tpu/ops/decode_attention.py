"""Decode attention over the carried cache (pallas, TPU).

One token a slot against one layer of the cache the decode programs
carry, `(L, B, S, KVH, Dh)`, read where it lies: the kernel takes the
cache whole, the layer index and the rows each slot holds, fetches a
slot's rows block by block and only as many blocks as `n_rows` needs,
and does the products of a block while the next one is in flight
(online softmax in float32 scratch over the blocks). Nothing is sliced
out of the cache and no layer is staged whole. The grid is the list of
the blocks that hold a row, slot after slot (`_work_list`): its size is
known only on the device, no step is spent on a block past a slot's last
or on a slot nobody owns, and the pipeline fetches the next slot's first
block under this slot's last products.

All KV heads of a block in one grid step. The cache is viewed as
`(L, B, S*KVH, Dh)` (the same bytes: a row's heads are the minor rows of
that view), so a block is one `(rows*KVH, Dh)` matrix and each product
one matmul of every query head against every row-head pair; a bias of
`-1e30` where the pair's head is not the query's group takes the other
heads' columns out in the softmax, so their probabilities are exact
zeros and probabilities x V needs no regrouping either. The matrix unit
loads each K and V element once whichever way the heads are grouped, so
the wider product costs what a product a head would.

Two bf16 terms (`models/periodic.cache_terms`): a float32 query against
a bf16 cache of `2L` leading entries (layer l rounded to bf16 at [l],
what the rounding left at [L + l]) takes q, and then the probabilities,
as two bf16 terms stacked under each other and each cached term in a
product of its own, float32 accumulation: the four products
`periodic._attend_terms` makes.

One array for keys and values (`models/latent.py`: a row is a latent
vector and a rotary key, the values are the row's first `v_width`
columns): `v_all` is None, a block is fetched once and serves both
products, and the output is `v_width` wide. The row's width then need
not fill whole lanes (576 = 512 + 64); the values' does.

`usable()` says where the kernel runs: on a TPU, where the rows tile
into a block and the head size fills the lanes, and outside any mesh
with a used axis (a pallas call is opaque to the partitioner). Anywhere
else the callers keep their XLA code.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import DISPATCH_COUNTS, NEG_INF, _LANES

# Rows of a slot a grid step fetches, the first that divides S: 256 read
# fastest at each serving cell's shape on a v5e, with 128 and 512 beside
# it (PERF.md, PR 31): wider blocks fetch more rows past a slot's last,
# narrower ones pay more grid steps.
_ROWS = (256, 128)
# One array for keys and values under many query heads (latent attention:
# 128 heads over rows of 640 lanes): a block's products are 0.3 MFLOP a
# row, so a grid step's fixed cost shows beside them and wider blocks pay.
# 32 slots x 10,240 holding 4,200-10,000 rows, five layers, ms on a v5e:
# 256 rows 4.51, 512 3.29, 1024 2.72, 2048 2.60 (my chip run, PR 34).
_ROWS_ONE_ARRAY = (1024, 512, 256, 128)


def block_rows(S: int, Dh: int, v_width: Optional[int] = None) -> int:
    """Rows a block, 0 where the shapes do not tile: the head size fills
    whole lanes and a block's rows divide S. `v_width`: the values are
    the first `v_width` columns of the keys' rows (one array); they fill
    whole lanes, and the rest of a row half of one at least."""
    if (Dh % _LANES if v_width is None
            else v_width % _LANES or (Dh - v_width) % (_LANES // 2)):
        return 0
    return next((r for r in (_ROWS if v_width is None else _ROWS_ONE_ARRAY)
                 if S % r == 0), 0)


def terms_of(q_dtype, cache_dtype) -> int:
    """The bf16 terms a cached row is kept as, read from the dtypes: a
    float32 query over a bf16 cache means two."""
    return 2 if (q_dtype == jnp.float32
                 and cache_dtype == jnp.bfloat16) else 1


def usable(k_all: jax.Array, Dh: int, v_width: Optional[int] = None
           ) -> bool:
    """Whether `decode_attention` runs for this cache here: read from the
    backend, the cache's shape and the ambient mesh."""
    from .flash_attention import on_tpu   # asked at the call, as moe.py does

    mesh = jax.sharding.get_abstract_mesh()
    sharded = any(n > 1 for n in dict(getattr(mesh, "shape", None)
                                      or {}).values())
    return (on_tpu() and not sharded
            and block_rows(k_all.shape[2], Dh, v_width) > 0)


def _bf16_terms(x, in_kernel: bool):
    """x float32 -> (hi, lo) bfloat16 with hi + lo = x to 2^-17. Outside
    the kernel the rounding is `reduce_precision`, as `models/moe.
    bf16_terms` has it: XLA may drop a cast down and up again, and lo
    would be zero. Inside it is the cast (Pallas lowers no
    `reduce_precision` for a TPU, and drops no cast)."""
    if in_kernel:
        hi = x.astype(jnp.bfloat16).astype(jnp.float32)
    else:
        hi = lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    return hi.astype(jnp.bfloat16), (x - hi).astype(jnp.bfloat16)


def _kernel(l_ref, n_ref, slot_ref, block_ref, q_ref, *refs,
            terms, H, G, KVH, rows, sm_scale, v_width):
    k_refs = refs[:terms]
    v_refs = k_refs if v_width else refs[terms:2 * terms]
    o_ref, acc_ref, m_ref, sum_ref, bias_ref = refs[
        (1 if v_width else 2) * terms:]
    t = pl.program_id(0)
    j = block_ref[t]
    n = n_ref[slot_ref[t]]
    N = rows * KVH
    R = terms * H

    @pl.when(t == 0)
    def _heads():
        # Column c of a block is row c // KVH of the slot under KV head
        # c % KVH; query row r (of either term) is head r % H.
        col = lax.broadcasted_iota(jnp.int32, (R, N), 1)
        row = lax.broadcasted_iota(jnp.int32, (R, N), 0)
        bias_ref[...] = jnp.where(col % KVH == (row % H) // G, 0.0, NEG_INF)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        sum_ref[...] = jnp.zeros_like(sum_ref)

    def step(edge: bool):
        """One block: `edge` where the slot's rows end inside it."""
        q = q_ref[...]                                   # (R, Dh)
        s = sum(lax.dot_general(q, k[...], (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
                for k in k_refs) * sm_scale + bias_ref[...]
        if terms == 2:
            s = s[:H] + s[H:]
        if edge:
            held = (n - j * rows) * KVH
            s = jnp.where(
                lax.broadcasted_iota(jnp.int32, s.shape, 1) < held, s,
                NEG_INF)
        m_prev, sum_prev = m_ref[:, :1], sum_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                           # (H, N)
        sum_new = alpha * sum_prev + jnp.sum(p, axis=-1, keepdims=True)
        if terms == 2:
            p = jnp.concatenate(_bf16_terms(p, True), axis=0)   # (2H, N)
        else:
            p = p.astype(v_refs[0].dtype)
        pv = 0.0
        for v_ref in v_refs:
            v = v_ref[:, :v_width] if v_width else v_ref[...]
            if edge:
                # 0 x NaN is NaN: a row past `n` must not reach the sum.
                vrow = lax.broadcasted_iota(jnp.int32, v.shape, 0)
                v = jnp.where(vrow < held, v, jnp.zeros_like(v))
            pv = pv + lax.dot(p, v, preferred_element_type=jnp.float32)
        if terms == 2:
            pv = pv[:H] + pv[H:]
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        sum_ref[...] = jnp.broadcast_to(sum_new, sum_ref.shape)

    end = (j + 1) * rows
    pl.when(end <= n)(functools.partial(step, False))
    pl.when((end > n) & (end - rows < n))(functools.partial(step, True))

    @pl.when(end >= n)
    def _finalize():
        o_ref[...] = (acc_ref[...] / jnp.maximum(sum_ref[:, :1], 1e-30)
                      ).astype(o_ref.dtype)


def _work_list(n_rows: jax.Array, rows: int, num_blocks: int):
    """The grid's steps: (how many, the slot of each, the block of each).
    A slot gives as many steps as it holds blocks of `rows`, and none
    where it holds no row, so a step is never spent on a block nobody
    needs and the next slot's first block is fetched under this slot's
    last products. The grid's size is the sum, known on the device; the
    two lists have room for every block of every slot."""
    B = n_rows.shape[0]
    need = (n_rows + rows - 1) // rows                   # (B,)
    ends = jnp.cumsum(need)
    t = jnp.arange(B * num_blocks, dtype=jnp.int32)
    behind = t[:, None] >= ends[None, :]                 # slots wholly before
    slot = jnp.minimum(jnp.sum(behind, axis=1), B - 1).astype(jnp.int32)
    block = t - jnp.sum(jnp.where(behind, need[None, :], 0), axis=1)
    # One step at least: a program with no step at all writes nothing.
    return jnp.maximum(ends[-1], 1), slot, block.astype(jnp.int32)


def decode_attention(q: jax.Array, k_all: jax.Array,
                     v_all: Optional[jax.Array], l: jax.Array,
                     n_rows: jax.Array, *,
                     interpret: Optional[bool] = None,
                     rows: Optional[int] = None,
                     sm_scale: Optional[float] = None,
                     v_width: Optional[int] = None) -> jax.Array:
    """q (B, KVH, G, Dh) against rows [0, n_rows[b]) of layer `l` of the
    cache `k_all`, `v_all` ((terms*L, B, S, KVH, Dh)) -> (B, 1, H*Dh), in
    q's dtype. `v_all` None: the values are the first `v_width` columns
    of `k_all`'s rows, read once for both products, and the result is
    (B, 1, H*v_width). `sm_scale`: the scores' scale where it is not
    1/sqrt(Dh). `n_rows` (B,) int32 in [0, S]: a slot holding no row
    reads nothing and returns zeros. The order the rows lie in does not
    matter (a ring is read as it lies). `rows`: rows a block, where
    `block_rows` is not to choose.

    `interpret=None` compiles the kernel (the callers ask `usable()`
    first); `True` runs it in the Pallas interpreter (tests on the CPU).
    """
    B, KVH, G, Dh = q.shape
    Lt, _, S = k_all.shape[:3]
    H = KVH * G
    terms = terms_of(q.dtype, k_all.dtype)
    L = Lt // terms
    if (v_all is None) != bool(v_width):
        raise ValueError("decode_attention: `v_width` goes with one array "
                         "for keys and values (v_all=None), and only then")
    Dv = v_width or Dh
    rows = rows or block_rows(S, Dh, v_width)
    if not rows or S % rows:
        raise ValueError(f"decode_attention: {S} rows of {KVH} x {Dh} do "
                         "not tile into blocks")
    num_blocks = S // rows
    N = rows * KVH
    DISPATCH_COUNTS["decode_attn"] += 1      # at trace time, as flash's

    n_rows = jnp.clip(n_rows.astype(jnp.int32), 0, S)
    steps, slot, block = _work_list(n_rows, rows, num_blocks)
    q2 = q.reshape(B, H, Dh)
    if terms == 2:
        q2 = jnp.concatenate(_bf16_terms(q2, False), axis=1)   # (B, 2H, Dh)
    R = terms * H
    flat = (Lt, B, S * KVH, Dh)

    def cached(term):
        def index(t, l_ref, n_ref, slot_ref, block_ref):
            return (l_ref[0] + term * L, slot_ref[t], block_ref[t], 0)
        return pl.BlockSpec((None, None, N, Dh), index)

    def own(t, l_ref, n_ref, slot_ref, block_ref):
        return (slot_ref[t], 0, 0)

    kernel = functools.partial(
        _kernel, terms=terms, H=H, G=G, KVH=KVH, rows=rows,
        sm_scale=sm_scale or 1.0 / math.sqrt(Dh), v_width=v_width)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(steps,),
            in_specs=[pl.BlockSpec((None, R, Dh), own)]
            + [cached(t) for t in range(terms)] * (1 if v_width else 2),
            out_specs=pl.BlockSpec((None, H, Dv), own),
            scratch_shapes=[
                pltpu.VMEM((H, Dv), jnp.float32),        # acc
                pltpu.VMEM((H, _LANES), jnp.float32),    # running max
                pltpu.VMEM((H, _LANES), jnp.float32),    # running sum
                pltpu.VMEM((R, N), jnp.float32),         # the heads' bias
            ]),
        out_shape=jax.ShapeDtypeStruct((B, H, Dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=bool(interpret),
        metadata={"kernel": "decode_attn"},
    )(jnp.reshape(l, (1,)).astype(jnp.int32), n_rows, slot, block, q2,
      *[k_all.reshape(flat)] * terms,
      *([] if v_all is None else [v_all.reshape(flat)] * terms))
    # A slot that holds no row was given no step, and its block of the
    # output was never written.
    out = jnp.where((n_rows > 0)[:, None, None], out, jnp.zeros_like(out))
    return out.reshape(B, 1, H * Dv)
