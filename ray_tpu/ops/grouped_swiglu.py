"""A routed layer's gate and up products as one grouped kernel.

A fork of the kernel body of megablox's `gmm` (jax 0.9.0,
`jax/experimental/pallas/ops/tpu/megablox/gmm.py`; its
`make_group_metadata` is imported, not copied) with two right-hand
sides: a grid step loads a row tile once and the group's gate and up
tiles, accumulates both products in float32, and on the last k tile
stores `silu(gate) * up` in the rows' dtype under megablox's store mask.
The two float32 (rows, N) products never reach memory, and no fusion
between them runs.

Left out of the fork, since no caller here wants them: a transposed
right-hand side, an existing output, a group offset, and a contraction
that does not divide into its tile.
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

# Two weight tiles of 2304 x 896 bf16, double-buffered, are 16.5 MB: over
# the compiler's default of 16 (a v5e core has 128).
VMEM_LIMIT_BYTES = 64 * 2 ** 20


def _kernel(group_metadata, lhs, w_gate, w_up, out, acc_gate, acc_up, *,
            tm, tn, tiles_k):
    group_offsets, group_ids, m_tile_ids = group_metadata
    grid_id, k_i = pl.program_id(1), pl.program_id(2)

    @pl.when(k_i == 0)
    def _zero():
        acc_gate[...] = jnp.zeros_like(acc_gate)
        acc_up[...] = jnp.zeros_like(acc_up)

    rows = lhs[...]
    acc_gate[...] += jnp.dot(rows, w_gate[...],
                             preferred_element_type=jnp.float32)
    acc_up[...] += jnp.dot(rows, w_up[...],
                           preferred_element_type=jnp.float32)

    @pl.when(k_i == tiles_k - 1)
    def _store():
        # The rows of this tile that belong to this group: a tile on a
        # group's edge is visited once a group, and a row of no group by
        # nobody.
        group = group_ids[grid_id]
        row = lax.broadcasted_iota(jnp.int32, (tm, tn), 0) \
            + m_tile_ids[grid_id] * tm
        mine = (row >= group_offsets[group]) & (row < group_offsets[group + 1])
        gate = acc_gate[...]
        h = gate * jax.nn.sigmoid(gate) * acc_up[...]
        out[...] = jnp.where(mine, h, out[...].astype(jnp.float32)) \
            .astype(out.dtype)


@functools.partial(jax.jit, static_argnames=["tiling", "interpret"])
def gmm_swiglu(lhs: jax.Array, w_gate: jax.Array, w_up: jax.Array,
               group_sizes: jax.Array, tiling: Tuple[int, int, int],
               interpret: bool = False) -> jax.Array:
    """lhs (M, K), rows sorted by group; w_gate, w_up (G, K, N);
    `group_sizes` (G,) int32 -> (M, N) in lhs's dtype: `silu(lhs @
    w_gate[g]) * (lhs @ w_up[g])` a group g, both products accumulated
    and the activation taken in float32, rounded once. A row past the
    last group is never written. `tiling` (tm, tk, tn) divides (M, K,
    N)."""
    (m, k), n = lhs.shape, w_gate.shape[2]
    tm, tk, tn = tiling
    if (w_gate.shape, w_gate.dtype) != (w_up.shape, w_up.dtype) \
            or w_gate.shape[1] != k:
        raise ValueError(f"gate {w_gate.dtype}{w_gate.shape} and up "
                         f"{w_up.dtype}{w_up.shape} must be alike, "
                         f"(groups, {k}, n)")
    if m % tm or k % tk or n % tn:
        raise ValueError(f"tiling {tiling} does not divide {(m, k, n)}")
    tiles_k, tiles_n = k // tk, n // tn
    metadata, active_tiles = make_group_metadata(
        group_sizes=group_sizes, m=m, tm=tm, start_group=jnp.int32(0),
        num_nonzero_groups=w_gate.shape[0], visit_empty_groups=False)

    def rows_at(n_i, grid_id, k_i, meta):
        return meta[2][grid_id], k_i

    def weights_at(n_i, grid_id, k_i, meta):
        return meta[1][grid_id], k_i, n_i

    def out_at(n_i, grid_id, k_i, meta):
        return meta[2][grid_id], n_i

    weights = pl.BlockSpec((None, tk, tn), weights_at)
    visits = metadata[1].size               # the most tiles a call visits
    return pl.pallas_call(
        functools.partial(_kernel, tm=tm, tn=tn, tiles_k=tiles_k),
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            in_specs=[pl.BlockSpec((tm, tk), rows_at), weights, weights],
            out_specs=pl.BlockSpec((tm, tn), out_at),
            grid=(tiles_n, active_tiles, tiles_k),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)] * 2),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        cost_estimate=pl.CostEstimate(
            flops=4 * m * k * n, transcendentals=m * n,
            bytes_accessed=lhs.dtype.itemsize * (m * k * tiles_n + m * n)
            + 2 * visits * k * n * w_gate.dtype.itemsize),
        interpret=interpret,
        metadata={"kernel": "gmm_swiglu"},
    )(metadata, lhs, w_gate, w_up)
