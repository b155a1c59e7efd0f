"""A routed layer's rows back to their tokens, weighted and summed, in
one pass: two kernels around one layout.

The grouped products leave a float32 row a token-expert pair, sorted by
expert; a token's K rows lie anywhere among them. A kernel may copy a
row by itself only where the row is a tile by itself: an (R, D) float32
array lies in tiles of 8 rows x 128 columns, and a copy of one row of
it is refused by the compiler; an (R, 1, D) array lies in tiles of one
row, each row's D values together.

`gmm_rows_apart` is the down product writing that layout: a fork of the
kernel body of megablox's `gmm` (jax 0.9.0; its `make_group_metadata`
is imported, not copied) whose result is (M, 1, N). `moe_combine` reads
it: a grid step takes a block of tokens, each of a token's K rows is
copied from where the product left it (HBM, by the pair's index, a
scalar) into VMEM by its own async copy, the next block's copies in
flight while this block's rows are weighted, added in the order j = 0
.. K-1 and written once. The (pairs, D) array is written once and read
once: XLA's gather writes all of it again, for a sum to read again.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.experimental.pallas.ops.tpu.megablox.gmm import make_group_metadata

# Tokens a grid step of `moe_combine`: 1.043 / 1.025 / 1.025 / 1.082 ms
# at 16 / 32 / 64 / 128 for 65,536 rows of 2,304 (my chip run, PR 47).
BLOCK_TOKENS = 32
# The pairs' indices are prefetched whole into scalar memory, 1 MiB a
# core on every chip since v4: a call of more pairs is not this kernel's.
MAX_PAIRS = 2 ** 17
# The float32 rows from which the pair is faster than megablox's product
# and XLA's gather and sum, bytes: under it XLA's two fusions run as if
# the gathered rows never left the chip (0.23 ms for 75 MB) and the pair
# loses by what writing rows apart costs; over it the gathered rows go
# through HBM. Product and return together, us a layer, theirs -> the
# pair's, by rows of 2,304 (my chip run, PR 47): 32 rows 57.4 -> 58.6, 256
# 374 -> 376, 4,096 580 -> 580, 8,192 (75 MB) 807 -> 917, 16,384 (151 MB)
# 2,038 -> 1,458, 32,768 3,384 -> 2,245, 65,536 6,367 -> 4,054.
MIN_ROW_BYTES = 2 ** 27
VMEM_LIMIT_BYTES = 64 * 2 ** 20


def _rows_apart_kernel(group_metadata, lhs, rhs, out, acc, tile, *,
                       tm, tn, tiles_k):
    group_offsets, group_ids, m_tile_ids, visits = group_metadata
    grid_id, k_i = pl.program_id(1), pl.program_id(2)

    @pl.when(k_i == 0)
    def _zero():
        acc[...] = jnp.zeros_like(acc)

    acc[...] += jnp.dot(lhs[...], rhs[...],
                        preferred_element_type=jnp.float32)

    @pl.when(k_i == tiles_k - 1)
    def _store():
        # The rows of this tile that belong to this group: a tile on a
        # group's edge is visited once a group, and a row of no group by
        # nobody. `tile` gathers the visits' rows as they lie in the
        # product; the tile's last visit writes them out a row apart.
        group = group_ids[grid_id]
        row = lax.broadcasted_iota(jnp.int32, (tm, tn), 0) \
            + m_tile_ids[grid_id] * tm
        mine = (row >= group_offsets[group]) & (row < group_offsets[group + 1])
        merged = jnp.where(mine, acc[...], tile[...])
        tile[...] = merged
        after = jnp.minimum(grid_id + 1, visits[0] - 1)
        last = (after == grid_id) | (m_tile_ids[after] != m_tile_ids[grid_id])

        @pl.when(last)
        def _apart():
            out[...] = merged.reshape(tm, 1, tn)


@functools.partial(jax.jit, static_argnames=["tiling", "interpret"])
def gmm_rows_apart(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array,
                   tiling: Tuple[int, int, int],
                   interpret: bool = False) -> jax.Array:
    """lhs (M, K), rows sorted by group; rhs (G, K, N); `group_sizes`
    (G,) int32 -> float32 (M, 1, N): `lhs @ rhs[g]` a group g. A row past
    the last group holds anything. `tiling` (tm, tk, tn) divides (M, K,
    N)."""
    (m, k), n = lhs.shape, rhs.shape[2]
    tm, tk, tn = tiling
    if rhs.shape[1] != k or m % tm or k % tk or n % tn:
        raise ValueError(f"tiling {tiling} does not divide {(m, k, n)}, or "
                         f"rhs {rhs.shape} is not (groups, {k}, n)")
    tiles_k, tiles_n = k // tk, n // tn
    metadata, active_tiles = make_group_metadata(
        group_sizes=group_sizes, m=m, tm=tm, start_group=jnp.int32(0),
        num_nonzero_groups=rhs.shape[0], visit_empty_groups=False)

    def rows_at(n_i, grid_id, k_i, meta):
        return meta[2][grid_id], k_i

    def weights_at(n_i, grid_id, k_i, meta):
        return meta[1][grid_id], k_i, n_i

    def out_at(n_i, grid_id, k_i, meta):
        return meta[2][grid_id], 0, n_i

    visits = metadata[1].size               # the most tiles a call visits
    return pl.pallas_call(
        functools.partial(_rows_apart_kernel, tm=tm, tn=tn, tiles_k=tiles_k),
        out_shape=jax.ShapeDtypeStruct((m, 1, n), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            in_specs=[pl.BlockSpec((tm, tk), rows_at),
                      pl.BlockSpec((None, tk, tn), weights_at)],
            out_specs=pl.BlockSpec((tm, 1, tn), out_at),
            grid=(tiles_n, active_tiles, tiles_k),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)] * 2),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=lhs.dtype.itemsize * m * k * tiles_n + 4 * m * n
            + visits * k * n * rhs.dtype.itemsize),
        interpret=interpret,
        metadata={"kernel": "gmm_rows_apart"},
    )((*metadata, active_tiles.reshape(1)), lhs, rhs)


def _combine_kernel(inv, ys, weights, *rest, tb, K, blocks, masked):
    live, out, buf, sems = rest if masked else (None,) + rest
    i = pl.program_id(0)

    def fetch(block, slot):
        def token(t, _):
            for j in range(K):
                row = inv[(block * tb + t) * K + j]
                pltpu.make_async_copy(ys.at[row], buf.at[slot, j, t],
                                      sems.at[slot]).start()
        # Unrolled where the kernel is lowered, not where it is traced:
        # a copy whose place in the buffer the compiler knows issues in
        # 15.6 ns where a loop's took 19.8, and tracing 512 copies took
        # 7 s a shape on the chip's host (my chip runs, PR 47).
        lax.fori_loop(0, tb, token, None, unroll=True)

    @pl.when(i == 0)
    def _first():
        fetch(0, 0)

    @pl.when(i + 1 < blocks)
    def _next():
        fetch(i + 1, (i + 1) % 2)

    slot = i % 2
    # One wait for the slot's tb x K copies: a semaphore counts what
    # arrived, and this descriptor is the size of all of them.
    pltpu.make_async_copy(buf.at[slot], buf.at[slot], sems.at[slot]).wait()
    w = weights[...]
    # (tb, 1, D), a row a tile -> (tb, D), eight tokens a tile again.
    acc = buf[slot, 0].reshape(out.shape) * w[:, 0:1]
    for j in range(1, K):
        acc = acc + buf[slot, j].reshape(out.shape) * w[:, j:j + 1]
    if masked:
        # Selected, not multiplied: an unowned token's rows were never
        # written and may hold anything.
        acc = jnp.where(live[...] != 0, acc, 0.0)
    out[...] = acc


@functools.partial(jax.jit,
                   static_argnames=["block_tokens", "interpret"])
def moe_combine(ys: jax.Array, inv: jax.Array, weights: jax.Array,
                rows=None, block_tokens: int = BLOCK_TOKENS,
                interpret: bool = False) -> jax.Array:
    """ys (P, 1, D) float32, a row a pair in any order; inv (T * K,)
    int32, where in ys the row of token t's j-th pair lies; weights (T,
    K) float32; `rows` (T,) bool, the tokens somebody owns (None: every
    one) -> float32 (T, D): sum over j of weights[t, j] x ys[inv[t * K +
    j]], added in the order of j, and zero for a token nobody owns
    whatever its rows hold. D a multiple of 128."""
    (T, K), (P, _, D) = weights.shape, ys.shape
    if ys.dtype != jnp.float32 or ys.shape[1] != 1 or D % 128 \
            or inv.shape != (T * K,) or T * K > MAX_PAIRS:
        raise ValueError(f"ys {ys.dtype}{ys.shape}, inv {inv.shape} and "
                         f"weights {weights.shape} are not this kernel's")
    tb = min(block_tokens, -(-T // 8) * 8)
    pad = -T % tb
    blocks = (T + pad) // tb
    masked = rows is not None
    inv = jnp.pad(inv.astype(jnp.int32), (0, pad * K))   # row 0: any row
    operands = [jnp.pad(weights.astype(jnp.float32), ((0, pad), (0, 0)))]
    specs = [pl.BlockSpec(memory_space=pl.ANY),
             pl.BlockSpec((tb, K), lambda i, inv: (i, 0))]
    if masked:
        operands.append(jnp.pad(rows.astype(jnp.int32), (0, pad))[:, None])
        specs.append(pl.BlockSpec((tb, 1), lambda i, inv: (i, 0)))
    out = pl.pallas_call(
        functools.partial(_combine_kernel, tb=tb, K=K, blocks=blocks,
                          masked=masked),
        out_shape=jax.ShapeDtypeStruct((T + pad, D), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, in_specs=specs,
            out_specs=pl.BlockSpec((tb, D), lambda i, inv: (i, 0)),
            grid=(blocks,),
            scratch_shapes=[pltpu.VMEM((2, K, tb, 1, D), jnp.float32),
                            pltpu.SemaphoreType.DMA((2,))]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        cost_estimate=pl.CostEstimate(
            flops=2 * T * K * D, transcendentals=0,
            bytes_accessed=4 * (T * K * D + T * D + 2 * T * K)),
        interpret=interpret,
        metadata={"kernel": "moe_combine"},
    )(inv, ys, *operands)
    return out[:T]
