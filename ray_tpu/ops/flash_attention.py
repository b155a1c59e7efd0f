"""Fused flash attention (pallas, TPU).

FlashAttention-2-style tiling for the MXU: grid over (batch, head) and,
innermost and sequential, the (q-block, kv-block) pairs the mask leaves
a live pair in (`_fwd_grid`: the forward and dq; `_dkv_grid`: dk/dv);
online-softmax statistics (m, l) and the output
accumulator live in VMEM scratch across a q block's kv steps, so HBM
traffic is O(S) per head instead of the O(S^2) score matrix. The backward
pass recomputes scores blockwise
(two kernels: dq with a kv loop, dk/dv with a loop over a kv head's
query heads and their q blocks) from the saved
logsumexp — the standard remat trade that keeps HBM residency at
activation size.

Global-position offsets (q_offset, kv_offset) parameterize the causal
mask so the same kernels serve ring attention (ops/ring_attention.py),
where each ring step attends to a rotated kv shard with a different
global offset.

`on_tpu()` is the one place that decides kernel vs reference for every
caller (the models, ring, ulysses): the kernels are compiled only for a
TPU backend; anywhere else the pure-jnp reference runs unless a test
asks for the interpreter (`interpret=True`). Every dispatch decision is
counted in `DISPATCH_COUNTS` at trace time, so a caller can see which
path a compiled program took.
"""

from __future__ import annotations

import collections
import functools
import math
from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
_LANES = 128
_SUBLANES = 8

# path -> times chosen, counted when a call is TRACED (once per compile,
# not per execution). Paths: "pallas", "pallas_interpret", and
# "reference_<why>" with why in forced / untileable / no_tpu / short_kv;
# ring_attention counts "ring_pallas" and "ring_reference_<why>",
# decode_attention "decode_attn" where its kernel is traced.
DISPATCH_COUNTS: "collections.Counter[str]" = collections.Counter()

# What the kernels' grids were given, summed over the calls traced (x batch
# x heads), the same way: `steps`, `live_steps`, `masked_steps`
# (`grid_steps`) of the calls whose offsets were Python ints, `traced_steps`
# of the others; the forward's under those names, the backward's two
# kernels' under `dq_` and `dkv_` + the same.
FLASH_GRID: "collections.Counter[str]" = collections.Counter()

# The names (`jax.ad_checkpoint.checkpoint_name`) of what the backward
# rule reads, as the kernels take them: q (B, H, S, D), k and v (B, KVH, S,
# D), the output and its row statistic (B, H, S) float32. A caller's
# `jax.checkpoint` that saves all five (`save_only_these_names`) keeps
# the forward kernel out of its recomputation; with one missing the
# forward runs again. Outside a checkpoint a name is the identity.
RESIDUAL_NAMES = ("attn_q", "attn_k", "attn_v", "attn_out", "attn_lse")


def _sds(shape, dtype, *like):
    """ShapeDtypeStruct carrying the union of the inputs' varying-manual-
    axes — required for pallas_call under shard_map (jax >= 0.8)."""
    vma = frozenset()
    for x in like:
        vma = vma | jax.typeof(x).vma
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


def on_tpu() -> bool:
    """Whether Pallas kernels compile for the device this process
    computes on. The models never ask the backend themselves."""
    return jax.default_backend() == "tpu"


# ---------------------------------------------------------------------------
# Forward kernel
# ---------------------------------------------------------------------------

# What a (q block, kv block) pair of a causal layer holds: no live pair
# (above the diagonal, or behind every query's window), live pairs only,
# or both (the diagonal or the window's far edge crosses it).
_DEAD, _INSIDE, _EDGE = 0, 1, 2

# A table of pairs longer than this (three int32 words a pair in scalar
# memory; 128k positions in blocks of 512 are 32,896) falls back to the
# grid that walks runs of kv blocks.
_MAX_PAIRS = 16384

# Fast memory the forward may take: a block's scores, their exponentials
# and the bf16 copy are some 10 bytes a pair, 10.5 MB at 1024 x 1024 and 40
# at 2048 x 2048, where the default limit of 16 MB stopped 2048 x 512 with
# the mask at 16.4 (my chip runs, PR 33). A v5e has 128 MB.
_FWD_VMEM_BYTES = 48 * 2 ** 20


def _block_kind(first_q, first_k, block_q, block_k, window):
    """(live, inside) of the block of causal scores whose first query and
    first key stand at global positions `first_q` and `first_k`: whether
    it holds a live pair at all, and whether it holds nothing else (no
    mask needed). Python ints, numpy arrays and traced scalars alike."""
    last_q, last_k = first_q + block_q - 1, first_k + block_k - 1
    live, inside = last_q >= first_k, first_q >= last_k
    if window is not None:
        live = live & (first_q - last_k < window)
        inside = inside & (last_q - first_k < window)
    return live, inside


def _live_pairs(nq, nk, block_q, block_k, causal, window, q_off, kv_off,
                by_kv=False):
    """The (q block, kv block) pairs a kernel with offsets known at trace
    time walks, as int32 arrays (qi, ki, kind), q blocks in order and each
    one's kv blocks in order: every pair that holds a live pair once, no
    other, but for a q block that sees no key at all, which keeps its
    first pair (_DEAD) as the step that writes its zeros. `by_kv`: kv
    blocks in order and each one's q blocks in order (dkv's walk), a kv
    block that no query sees keeping its first pair."""
    qi, ki = np.meshgrid(np.arange(nq, dtype=np.int32),
                         np.arange(nk, dtype=np.int32), indexing="ij")
    if causal:
        live, inside = _block_kind(q_off + qi * block_q,
                                   kv_off + ki * block_k, block_q, block_k,
                                   window)
    else:
        live = inside = np.ones((nq, nk), bool)
    kind = np.where(live, np.where(inside, _INSIDE, _EDGE), _DEAD)
    keep = live.copy()
    if by_kv:
        keep[0, ~live.any(axis=0)] = True
        qi, ki, kind, keep = qi.T, ki.T, kind.T, keep.T
    else:
        keep[~live.any(axis=1), 0] = True
    return qi[keep], ki[keep], kind[keep].astype(np.int32)


def _kv_run(nk, block_q, block_k, window):
    """kv steps a q block of the run grid: all of kv's blocks, or the
    blocks a window can reach (`window` - 1 + `block_q` keys: at most
    that span's blocks and one more, whatever the offsets)."""
    if window is None:
        return nk
    return min(nk, (window + block_q - 3) // block_k + 2)


def grid_steps(sq, skv, block_q, block_k, *, causal, window=None,
               q_offset=0, kv_offset=0, by_kv=False, group=1):
    """What a kernel's grid spends on one (batch, query head) of a call:
    `steps` it is given, `live_steps` whose block holds a live pair,
    `masked_steps` that build the mask (blocks an edge crosses). The
    forward's and dq's grid is built from the same table (`_fwd_grid`);
    `by_kv`: dkv's (`_dkv_grid`, whose table holds a kv head's `group` of
    query heads). With an offset the trace cannot see (None) only
    `traced_steps` is known: a run of kv blocks a q block, right for any
    offset."""
    nq, nk = sq // block_q, skv // block_k
    if not causal:
        q_offset = kv_offset = 0
    if q_offset is None or kv_offset is None:
        return {"traced_steps": nq * _kv_run(nk, block_q, block_k, window)}
    kind = _live_pairs(nq, nk, block_q, block_k, causal, window, q_offset,
                       kv_offset, by_kv)[2]
    steps = len(kind)
    if steps * group > _MAX_PAIRS:
        steps = nq * _kv_run(nk, block_q, block_k, window)
    return {"steps": steps, "live_steps": int(np.sum(kind != _DEAD)),
            "masked_steps": int(np.sum(kind == _EDGE))}


class _Step(NamedTuple):
    """Where a grid step stands (`_fwd_grid`'s `locate`)."""
    qi: Any          # q block
    ki: Any          # kv block scored
    fetch: Any       # block fetched on the side walked (kv; dkv: q): a
                     # dead step repeats a live one
    first: Any       # first / last step of its q block
    last: Any
    kind: Any        # _DEAD / _INSIDE / _EDGE
    q_off: Any       # global position of q's, kv's element 0
    kv_off: Any
    g: Any = 0       # dkv: query head of the kv head's group


def _table_flags(outer, kind):
    """A table entry's word: bit 0 the first and bit 1 the last step of
    its `outer` block, the kind from bit 2."""
    turn = outer[1:] != outer[:-1]
    return (np.r_[True, turn] | np.r_[turn, True] << 1
            | kind << 2).astype(np.int32)


def _fwd_grid(nq, nk, block_q, block_k, causal, window, static_offs, offs):
    """-> (grid behind (B, H), scalar-prefetch operands, locate).
    `locate(ids, refs)` places a step from its grid indices and the
    prefetched operands, in the kernel and in the index maps.

    Offsets known at trace time (`static_offs`; any `causal=False` call):
    one grid axis over the table of live pairs, so a block with no live
    pair is neither given a step nor fetched. Traced offsets (a ring
    step's): (nq, run of kv blocks), the run starting at the first block
    a q block's window reaches and a step above the diagonal repeating
    the last block under it, which fetches nothing."""
    if not causal:
        static_offs = (0, 0)
    if static_offs is not None:
        qi, ki, kind = _live_pairs(nq, nk, block_q, block_k, causal, window,
                                   *static_offs)
        if len(qi) <= _MAX_PAIRS:
            flags = _table_flags(qi, kind)

            def locate(ids, refs):
                (p,), (qi_ref, ki_ref, flag_ref) = ids, refs
                f = flag_ref[p]
                return _Step(qi_ref[p], ki_ref[p], ki_ref[p], (f & 1) == 1,
                             (f & 2) == 2, f >> 2, *static_offs)

            return (len(qi),), (qi, ki, flags), locate
        offs = np.asarray(static_offs, np.int32)
    run = _kv_run(nk, block_q, block_k, window)

    def locate(ids, refs):
        (qi, step), (offs_ref,) = ids, refs
        q_off, kv_off = offs_ref[0], offs_ref[1]
        if not causal:
            return _Step(qi, step, step, step == 0, step == run - 1,
                         _INSIDE, q_off, kv_off)
        first_q = q_off + qi * block_q
        ki = step
        if window is not None:
            ki += jnp.maximum(first_q - (window - 1) - kv_off, 0) // block_k
        under = jnp.maximum(first_q + block_q - 1 - kv_off, 0) // block_k
        live, inside = _block_kind(first_q, kv_off + ki * block_k, block_q,
                                   block_k, window)
        live = live & (ki < nk)
        kind = jnp.where(live, jnp.where(inside, _INSIDE, _EDGE), _DEAD)
        return _Step(qi, ki, jnp.minimum(jnp.minimum(ki, under), nk - 1),
                     step == 0, step == run - 1, kind, q_off, kv_off)

    return (nq, run), (jnp.asarray(offs, jnp.int32).reshape(2),), locate


def _fwd_kernel(*refs, locate, n_scalars, n_ids, sm_scale, block_q, block_k,
                causal, window, block=None):
    """One (q block, kv block) step of the online softmax."""
    (q_ref, k_ref, v_ref, o_ref, lse_ref,
     qs_ref, acc_ref, m_ref, l_ref) = refs[n_scalars:]
    at = locate(tuple(pl.program_id(2 + i) for i in range(n_ids)),
                refs[:n_scalars])

    @pl.when(at.first)
    def _init():
        # The score scale, once a q block and over (block_q, D), not once
        # a step over (block_q, block_k).
        qs_ref[...] = (q_ref[0, 0, :, :].astype(jnp.float32)
                       * sm_scale).astype(qs_ref.dtype)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def compute(masked):
        s = lax.dot_general(qs_ref[...], k_ref[0, 0, :, :],
                            (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
        if masked:
            # q_pos >= k_pos, as row - column against one scalar.
            row = lax.broadcasted_iota(jnp.int32, s.shape, 0)
            if block is not None:
                # Block-causal: a query sees its whole block, so it stands
                # at its block's last position (offsets and the kernel's
                # blocks are multiples of `block`, a power of two).
                row = row | (block - 1)
            ahead = row - lax.broadcasted_iota(jnp.int32, s.shape, 1)
            diag = (at.kv_off + at.ki * block_k) - (at.q_off
                                                    + at.qi * block_q)
            mask = ahead >= diag
            if window is not None:
                mask = mask & (ahead < diag + window)
            s = jnp.where(mask, s, NEG_INF)
        # One reduction over the lanes a step, the max's. m_ref holds a
        # row's running max in every lane; l_ref its running sum a lane
        # (summed over the lanes once a q block, in `_finalize`), so the
        # step's sum is adds between the scores' 128-lane columns. Blocks
        # that are no multiple of 128 keys keep the row's sum in lane 0.
        wide = _LANES if block_k % _LANES == 0 else block_k
        cols = range(0, block_k, wide)
        tiles = [s[:, c:c + wide] for c in cols]
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(
            functools.reduce(jnp.maximum, tiles), axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        m_tile = m_new if wide == _LANES else m_new[:, :1]
        ps = [jnp.exp(t - m_tile) for t in tiles]
        if masked:
            # A row masked whole, here and in every block before (the
            # far edge of a window): it must contribute exactly 0.
            ps = [jnp.where(mask[:, c:c + wide], t, 0.0)
                  for c, t in zip(cols, ps)]
        part = sum(ps[1:], ps[0]) if wide == _LANES else jnp.sum(
            ps[0], axis=-1, keepdims=True)
        w = part.shape[1]
        l_ref[:, :w] = alpha[:, :w] * l_ref[:, :w] + part
        m_ref[...] = m_new
        v = v_ref[0, 0, :, :]
        pv = lax.dot(jnp.concatenate(ps, axis=1).astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * (
            alpha if alpha.shape == acc_ref.shape else alpha[:, :1]) + pv

    # A block wholly inside the mask takes the body without one; a fully
    # masked row can only occur in a block an edge crosses.
    pl.when(at.kind == _INSIDE)(functools.partial(compute, False))
    if causal:
        pl.when(at.kind == _EDGE)(functools.partial(compute, True))

    @pl.when(at.last)
    def _finalize():
        l = jnp.maximum(jnp.sum(l_ref[...], axis=-1, keepdims=True), 1e-30)
        o_ref[0, 0, :, :] = (acc_ref[...] / l).astype(o_ref.dtype)
        lse_ref[0, 0, :, :] = m_ref[...] + jnp.log(l)


def _fwd_impl(q, k, v, offs, *, sm_scale, block_q, block_k, causal,
              interpret, window=None, static_offs=None, block=None
              ) -> Tuple[jax.Array, jax.Array]:
    """q (B, H, Sq, D); k (B, KVH, Skv, D), v (B, KVH, Skv, Dv), KVH
    dividing H: a query head reads kv head `h // (H // KVH)`, nothing is
    expanded; the values may be another width than the keys (latent
    attention: 192-wide scores over 128-wide values), and the output is
    theirs. -> (out, lse). `block`: the block-causal mask (`flash_attention`);
    the table of live pairs is the causal one (`block` divides the
    kernel's blocks and the offsets), only the mask of the blocks the
    diagonal crosses differs. `static_offs`: (q_offset, kv_offset) as Python ints
    where the caller knows them, and then `offs` is not read; else `offs`
    (two numbers, traced or not) reaches the index maps as a prefetched
    scalar. The grid: `_fwd_grid`."""
    B, H, Sq, D = q.shape
    Dv = v.shape[-1]
    group = H // k.shape[1]
    nq, nk = Sq // block_q, k.shape[2] // block_k
    tail, scalars, locate = _fwd_grid(nq, nk, block_q, block_k, causal,
                                      window, static_offs, offs)
    n_ids = len(tail)

    def q_block(b, h, *rest):
        return b, h, locate(rest[:n_ids], rest[n_ids:]).qi, 0

    def kv_block(b, h, *rest):
        return b, h // group, locate(rest[:n_ids], rest[n_ids:]).fetch, 0

    kernel = functools.partial(
        _fwd_kernel, locate=locate, n_scalars=len(scalars), n_ids=n_ids,
        sm_scale=sm_scale, block_q=block_q, block_k=block_k, causal=causal,
        window=window, block=block)
    out, lse = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars), grid=(B, H) + tail,
            in_specs=[
                pl.BlockSpec((1, 1, block_q, D), q_block),
                pl.BlockSpec((1, 1, block_k, D), kv_block),
                pl.BlockSpec((1, 1, block_k, Dv), kv_block),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, block_q, Dv), q_block),
                pl.BlockSpec((1, 1, block_q, _LANES), q_block),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_q, D), q.dtype),
                pltpu.VMEM((block_q, Dv), jnp.float32),
                pltpu.VMEM((block_q, _LANES), jnp.float32),
                pltpu.VMEM((block_q, _LANES), jnp.float32),
            ]),
        out_shape=[
            _sds((B, H, Sq, Dv), q.dtype, q, k, v, offs),
            _sds((B, H, Sq, _LANES), jnp.float32, q, k, v, offs),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * (n_ids + 1)
            + ("arbitrary",),
            vmem_limit_bytes=_FWD_VMEM_BYTES),
        interpret=interpret,
        metadata={"kernel": "flash_fwd"},
    )(*scalars, q, k, v)
    return out, lse[..., 0]


# ---------------------------------------------------------------------------
# Backward kernels
# ---------------------------------------------------------------------------

def _dkv_grid(nq, nk, block_q, block_k, causal, group, static_offs, offs):
    """`_fwd_grid` for the kernel that walks by kv block: behind (B, KVH),
    sequential for one kv block, every query head of its group (`g`) and
    every q block that holds a live pair with it, so dk and dv sum over
    the group where they accumulate. A `_Step`'s `fetch` is the q block
    fetched. Offsets known at trace time: the table of live pairs by kv
    block, a group's heads in turn; traced: (nk, group x nq), a head's
    run starting at the first q block under the kv block's diagonal and
    a step past the last q block repeating it. No window: the backward
    is not written for one."""
    if not causal:
        static_offs = (0, 0)
    if static_offs is not None:
        qi, ki, kind = _live_pairs(nq, nk, block_q, block_k, causal, None,
                                   *static_offs, by_kv=True)
        if group * len(qi) <= _MAX_PAIRS:
            g = np.repeat(np.arange(group, dtype=np.int32), len(qi))
            qi, ki, kind = (np.tile(x, group) for x in (qi, ki, kind))
            order = np.lexsort((qi, g, ki))
            qi, ki, kind, g = (x[order] for x in (qi, ki, kind, g))
            flags = _table_flags(ki, kind) | g << 4

            def locate(ids, refs):
                (p,), (qi_ref, ki_ref, flag_ref) = ids, refs
                f = flag_ref[p]
                return _Step(qi_ref[p], ki_ref[p], qi_ref[p], (f & 1) == 1,
                             (f & 2) == 2, (f >> 2) & 3, *static_offs,
                             f >> 4)

            return (len(qi),), (qi, ki, flags), locate
        offs = np.asarray(static_offs, np.int32)

    def locate(ids, refs):
        (ki, step), (offs_ref,) = ids, refs
        q_off, kv_off = offs_ref[0], offs_ref[1]
        g, qi = step // nq, step % nq
        first, last = step == 0, step == group * nq - 1
        if not causal:
            return _Step(qi, ki, qi, first, last, _INSIDE, q_off, kv_off, g)
        first_k = kv_off + ki * block_k
        qi += jnp.maximum(first_k - q_off, 0) // block_q
        live, inside = _block_kind(q_off + qi * block_q, first_k, block_q,
                                   block_k, None)
        live = live & (qi < nq)
        kind = jnp.where(live, jnp.where(inside, _INSIDE, _EDGE), _DEAD)
        return _Step(qi, ki, jnp.minimum(qi, nq - 1), first, last, kind,
                     q_off, kv_off, g)

    return ((nk, group * nq), (jnp.asarray(offs, jnp.int32).reshape(2),),
            locate)


_NT = (((1,), (1,)), ((), ()))      # a b^T


def _bwd_step(refs, locate, n_scalars, n_ids):
    """(where this grid step stands, the kernel's operand refs)."""
    return (locate(tuple(pl.program_id(2 + i) for i in range(n_ids)),
                   refs[:n_scalars]), refs[n_scalars:])


def _seen(shape, at, block_q, block_k, q_axis):
    """q_pos >= k_pos over a block of scores whose queries run along
    `q_axis`, as one iota difference against one scalar."""
    ahead = (lax.broadcasted_iota(jnp.int32, shape, q_axis)
             - lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis))
    return ahead >= (at.kv_off + at.ki * block_k) - (at.q_off
                                                     + at.qi * block_q)


def _on_live_blocks(at, causal, compute):
    """A block wholly inside the mask takes the body without one, a block
    an edge crosses the body with it, a dead block neither."""
    pl.when(at.kind == _INSIDE)(functools.partial(compute, False))
    if causal:
        pl.when(at.kind == _EDGE)(functools.partial(compute, True))


def _dq_kernel(*refs, locate, n_scalars, n_ids, sm_scale, block_q, block_k,
               causal):
    """One (q block, kv block) step of dq += ds k, on the forward's walk."""
    at, (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, qs_ref,
         lse_col, delta_col, dq_acc) = _bwd_step(refs, locate, n_scalars,
                                                 n_ids)

    @pl.when(at.first)
    def _init():
        # Once a q block: the scale into q (the forward's scores bit for
        # bit) and the row statistics, which arrive along the lanes,
        # stood up as columns.
        qs_ref[...] = (q_ref[0, 0, :, :].astype(jnp.float32)
                       * sm_scale).astype(qs_ref.dtype)
        lse_col[...] = lse_ref[0, 0, 0, 0, :][:, None]
        delta_col[...] = delta_ref[0, 0, 0, 0, :][:, None]
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def compute(masked):
        k = k_ref[0, 0, :, :]
        s = lax.dot_general(qs_ref[...], k, _NT,
                            preferred_element_type=jnp.float32)
        p = jnp.exp(s - lse_col[...])
        if masked:
            # Not a product with 0: a dead pair's exp is not clamped.
            p = jnp.where(_seen(s.shape, at, block_q, block_k, 0), p, 0.0)
        dp = lax.dot_general(do_ref[0, 0, :, :], v_ref[0, 0, :, :], _NT,
                             preferred_element_type=jnp.float32)
        ds = p * (dp - delta_col[...])
        dq_acc[...] += lax.dot(ds.astype(k.dtype), k,
                               preferred_element_type=jnp.float32)

    _on_live_blocks(at, causal, compute)

    @pl.when(at.last)
    def _finalize():
        dq_ref[0, 0, :, :] = (dq_acc[...] * sm_scale).astype(dq_ref.dtype)


def _dkv_kernel(*refs, locate, n_scalars, n_ids, sm_scale, block_q, block_k,
                causal):
    """One (kv block, query head of its group, q block) step of dv += p^T
    do and dk += ds^T q. The block is scored transposed (k q^T and v do^T,
    the forward's product form), so p^T and ds^T are left operands as
    they stand and a q row's lse and delta lie along the lanes."""
    at, (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
         ks_ref, dk_acc, dv_acc) = _bwd_step(refs, locate, n_scalars, n_ids)

    @pl.when(at.first)
    def _init():
        ks_ref[...] = (k_ref[0, 0, :, :].astype(jnp.float32)
                       * sm_scale).astype(ks_ref.dtype)
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def compute(masked):
        q = q_ref[0, 0, :, :]
        do = do_ref[0, 0, :, :]
        st = lax.dot_general(ks_ref[...], q, _NT,
                             preferred_element_type=jnp.float32)
        pt = jnp.exp(st - lse_ref[0, 0, 0, :, :])
        if masked:
            pt = jnp.where(_seen(st.shape, at, block_q, block_k, 1), pt,
                           0.0)
        dv_acc[...] += lax.dot(pt.astype(do.dtype), do,
                               preferred_element_type=jnp.float32)
        dpt = lax.dot_general(v_ref[0, 0, :, :], do, _NT,
                              preferred_element_type=jnp.float32)
        dst = pt * (dpt - delta_ref[0, 0, 0, :, :])
        dk_acc[...] += lax.dot(dst.astype(q.dtype), q,
                               preferred_element_type=jnp.float32)

    _on_live_blocks(at, causal, compute)

    @pl.when(at.last)
    def _finalize():
        dk_ref[0, 0, :, :] = (dk_acc[...] * sm_scale).astype(dk_ref.dtype)
        dv_ref[0, 0, :, :] = dv_acc[...].astype(dv_ref.dtype)


def _bwd_call(kernel, name, grid, outer, q_block, kv_block, operands, outs,
              scratch, *, block_q, block_k, interpret, **kw):
    """dq's or dkv's `pallas_call` over (B, `outer`) + `grid`'s axes.
    `operands`: q, k, v, do, lse, delta; `q_block`, `kv_block`: (b, h,
    step) -> the index of either side's block; `outs`: (side, dtype) an
    output."""
    tail, scalars, locate = grid
    n_ids = len(tail)
    (B, _, Sq, D), Skv = operands[0].shape, operands[1].shape[2]
    rows = {"q": Sq, "kv": Skv}

    def placed(index, stat=False):
        def block(b, h, *rest):
            at = index(b, h, locate(rest[:n_ids], rest[n_ids:]))
            return at[:3] + (0, 0) if stat else at
        return block

    side = {"q": pl.BlockSpec((1, 1, block_q, D), placed(q_block)),
            "kv": pl.BlockSpec((1, 1, block_k, D), placed(kv_block))}
    stat = pl.BlockSpec((1, 1, 1, 1, block_q), placed(q_block, True))
    return pl.pallas_call(
        functools.partial(kernel, locate=locate, n_scalars=len(scalars),
                          n_ids=n_ids, block_q=block_q, block_k=block_k,
                          **kw),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars), grid=(B, outer) + tail,
            in_specs=[side["q"], side["kv"], side["kv"], side["q"], stat,
                      stat],
            out_specs=[side[s] for s, _ in outs],
            scratch_shapes=scratch),
        out_shape=[_sds((B, outer, rows[s], D), dt, *operands, scalars[-1])
                   for s, dt in outs],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * (n_ids + 1)
            + ("arbitrary",),
            vmem_limit_bytes=_FWD_VMEM_BYTES),
        interpret=interpret,
        metadata={"kernel": name},
    )(*scalars, *operands)


def _bwd_impl(q, k, v, do, out, lse, offs, *, sm_scale, block_q, block_k,
              causal, interpret, static_offs=None):
    """q, do, out (B, H, Sq, D); k, v (B, KVH, Skv, D), KVH dividing H and
    nothing expanded; lse (B, H, Sq) -> dq (B, H, Sq, D), dk and dv (B,
    KVH, Skv, D), summed over a kv head's group in the kernel. Two
    kernels, each on a grid that follows the mask: dq on the forward's
    (`_fwd_grid`), dkv on `_dkv_grid`. `static_offs`, `offs`: as
    `_fwd_impl`'s."""
    B, H, Sq, D = q.shape
    KVH, Skv = k.shape[1], k.shape[2]
    group = H // KVH
    nq, nk = Sq // block_q, Skv // block_k
    q_off, kv_off = static_offs or (None, None)
    for name, by_kv in (("dq", False), ("dkv", True)):
        for what, n in grid_steps(
                Sq, Skv, block_q, block_k, causal=causal, q_offset=q_off,
                kv_offset=kv_off, by_kv=by_kv, group=group).items():
            FLASH_GRID[f"{name}_{what}"] += B * H * n
    # The row statistics, a q block a row of (1, block_q) along the lanes:
    # such a block is whole in its last two dimensions whatever block_q.
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)
    operands = (q, k, v, do, lse.reshape(B, H, nq, 1, block_q),
                delta.reshape(B, H, nq, 1, block_q))
    kw = dict(block_q=block_q, block_k=block_k, interpret=interpret,
              sm_scale=sm_scale, causal=causal)
    dq, = _bwd_call(
        _dq_kernel, "flash_dq",
        _fwd_grid(nq, nk, block_q, block_k, causal, None, static_offs, offs),
        H, lambda b, h, at: (b, h, at.qi, 0),
        lambda b, h, at: (b, h // group, at.fetch, 0),
        operands, [("q", q.dtype)],
        [pltpu.VMEM((block_q, D), q.dtype),
         pltpu.VMEM((block_q, 1), jnp.float32),
         pltpu.VMEM((block_q, 1), jnp.float32),
         pltpu.VMEM((block_q, D), jnp.float32)], **kw)
    dk, dv = _bwd_call(
        _dkv_kernel, "flash_dkv",
        _dkv_grid(nq, nk, block_q, block_k, causal, group, static_offs,
                  offs),
        KVH, lambda b, h, at: (b, h * group + at.g, at.fetch, 0),
        lambda b, h, at: (b, h, at.ki, 0),
        operands, [("kv", k.dtype), ("kv", v.dtype)],
        [pltpu.VMEM((block_k, D), k.dtype),
         pltpu.VMEM((block_k, D), jnp.float32),
         pltpu.VMEM((block_k, D), jnp.float32)], **kw)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# Reference fallback (pure jnp — differentiable, XLA-fused)
# ---------------------------------------------------------------------------

def _reference(q, k, v, offs, *, sm_scale, causal, window=None, block=None):
    """(B, H, S, D) layout. Returns (out, lse)."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * sm_scale
    if causal:
        Sq, Skv = q.shape[2], k.shape[2]
        q_pos = offs[0, 0].astype(jnp.int32) + jnp.arange(Sq)[:, None]
        k_pos = offs[0, 1].astype(jnp.int32) + jnp.arange(Skv)[None, :]
        if block is not None:
            q_pos = q_pos | (block - 1)      # its block's last position
        mask = q_pos >= k_pos
        if window is not None:
            mask = mask & (q_pos - k_pos < window)
        s = jnp.where(mask[None, None], s, NEG_INF)
    lse = jax.nn.logsumexp(s, axis=-1)
    p = jnp.exp(s - lse[..., None])
    out = jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v)
    return out.astype(q.dtype), lse


# ---------------------------------------------------------------------------
# custom-VJP wrapper
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(4, 5, 6, 7, 8, 9, 10, 11, 12))
def _flash(q, k, v, offs, causal, sm_scale, fwd_blocks, bwd_blocks,
           use_pallas, interpret, window=None, static_offs=None, block=None):
    """q (B, H, Sq, D); k, v (B, KVH, Skv, D): unexpanded for the kernels,
    which read kv head `h // (H // KVH)`; the reference is handed them
    expanded (KVH = H), as it always was, and its vjp sums dk and dv over
    a group through that expansion. `fwd_blocks`, `bwd_blocks`: (block_q,
    block_k) of the forward kernel and of dq / dkv."""
    return _flash_fwd(q, k, v, offs, causal, sm_scale, fwd_blocks,
                      bwd_blocks, use_pallas, interpret, window,
                      static_offs, block)[0]


def _flash_fwd(q, k, v, offs, causal, sm_scale, fwd_blocks, bwd_blocks,
               use_pallas, interpret, window=None, static_offs=None,
               block=None):
    if use_pallas:
        out, lse = _fwd_impl(q, k, v, offs, sm_scale=sm_scale,
                             block_q=fwd_blocks[0], block_k=fwd_blocks[1],
                             causal=causal, interpret=interpret,
                             window=window, static_offs=static_offs,
                             block=block)
    else:
        out, lse = _reference(q, k, v, offs, sm_scale=sm_scale,
                              causal=causal, window=window, block=block)
    q, k, v, out, lse = map(checkpoint_name, (q, k, v, out, lse),
                            RESIDUAL_NAMES)
    return out, (q, k, v, offs, out, lse)


def _flash_bwd_rule(causal, sm_scale, fwd_blocks, bwd_blocks, use_pallas,
                    interpret, window, static_offs, block, res, g):
    if window is not None or block is not None:
        raise NotImplementedError(
            "flash_attention: the backward pass is not written for a "
            "window or a block-causal mask (the dq and dkv kernels mask "
            "causally only)")
    q, k, v, offs, out, lse = res
    if v.shape[-1] != k.shape[-1]:
        raise NotImplementedError(
            "flash_attention: the backward pass is not written for values "
            "of another width than the keys")
    if use_pallas:
        dq, dk, dv = _bwd_impl(q, k, v, g, out, lse, offs,
                               sm_scale=sm_scale, block_q=bwd_blocks[0],
                               block_k=bwd_blocks[1], causal=causal,
                               interpret=interpret, static_offs=static_offs)
    else:
        def f(q, k, v):
            return _reference(q, k, v, offs, sm_scale=sm_scale,
                              causal=causal)[0]
        dq, dk, dv = jax.vjp(f, q, k, v)[1](g)
    return dq, dk, dv, jnp.zeros_like(offs)


_flash.defvjp(_flash_fwd, _flash_bwd_rule)


def _pick_block(s: int, target: int) -> int:
    """Largest block <= target the TPU lowering accepts for a dimension
    of s rows: all of s, or a multiple of 8 rows that divides s (bf16
    compiles at 8 as well; checked by tests/test_aot_tpu_compile.py).
    0 when there is none."""
    if s <= target:
        return s
    b = target - target % _SUBLANES
    while b and s % b:
        b -= _SUBLANES
    return b


def tileable(sq: int, skv: int, d: int, block_q: int, block_k: int
             ) -> Tuple[int, int]:
    """(block_q, block_k) for the kernels, or (0, 0) when the shape
    cannot be tiled (tiny or ragged sequences, odd head dims) and must
    take the reference."""
    bq, bk = _pick_block(sq, block_q), _pick_block(skv, block_k)
    if bq >= _SUBLANES and bk >= _SUBLANES and d % _SUBLANES == 0:
        return bq, bk
    return 0, 0


# (Sq, Skv, D, window) -> the forward's (block_q, block_k) where a chip run
# read another pair faster than `_fwd_blocks`' rule (chip_flash_table.py,
# bf16 on a v5e; my chip runs, PR 33). 8,192 causal positions at 32 / 4
# heads of 128: 1024 x 1024 4.62 ms against the rule's 1024 x 512 4.68.
_FWD_MEASURED_BLOCKS = {(8192, 8192, 128, None): (1024, 1024)}


def _fwd_blocks(sq: int, skv: int, d: int, window: Optional[int]
                ) -> Tuple[int, int]:
    """The forward kernel's (block_q, block_k) targets from what a call
    shows. The rule: q blocks of 1,024 rows against 512 keys, a step's
    row statistics paid once for more rows (4,096 causal positions at
    32 / 8 heads of 128: 1.39 ms, 512 x 512 1.40, the backward's 256 x
    512 1.89, 512 x 2048 1.81); 512 rows under a window, where a taller
    block only adds masked pairs at its far edge (8,192 positions, a
    window of 1,024: 2.12 ms against 2.38). `tileable` cuts either to
    what divides the call's lengths."""
    return _FWD_MEASURED_BLOCKS.get(
        (sq, skv, d, window), (1024, 512) if window is None else (512, 512))


# (Sq, Skv, D) -> dq's and dkv's (block_q, block_k) where a chip run read
# another pair faster than `_bwd_blocks`' rule (chip_flash_table.py, bf16
# on a v5e; my chip runs, PR 35), dq + dkv in ms. 4,096 causal positions
# at 2 x 16 / 8 heads of 128: 1024 x 1024 1.79 + 2.10 against the rule's
# 1.89 + 2.17; 8,192 at 32 / 4: 5.70 + 6.98 against 6.30 + 7.60.
_BWD_MEASURED_BLOCKS = {(4096, 4096, 128): (1024, 1024),
                        (8192, 8192, 128): (1024, 1024)}


def _bwd_blocks(sq: int, skv: int, d: int) -> Tuple[int, int]:
    """dq's and dkv's (block_q, block_k) targets from what a call shows.
    The rule: 512 x 512, the least of the pairs that waste an eighth of
    the blocks' pairs above the diagonal or less at 4,096 positions (256
    x 512, the pair both kernels had, 2.25 + 2.96 ms; 512 x 1024 and 1024
    x 512 level with it at a quarter wasted; 2048-wide blocks lose).
    `tileable` cuts either to what divides the call's lengths."""
    return _BWD_MEASURED_BLOCKS.get((sq, skv, d), (512, 512))


def _expand_kv(x: jax.Array, n_heads: int) -> jax.Array:
    kvh = x.shape[1]
    if kvh == n_heads:
        return x
    return jnp.repeat(x, n_heads // kvh, axis=1)


_XLA_CROSSOVER_SKV = 2048


def _static_offset(x) -> Optional[int]:
    return int(x) if isinstance(x, (int, np.integer)) else None


def flash_attention(q, k, v, *, causal: bool = True,
                    sm_scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    q_offset=0, kv_offset=0,
                    window: Optional[int] = None,
                    block: Optional[int] = None,
                    interpret: Optional[bool] = None,
                    force_reference: bool = False,
                    force_pallas: bool = False) -> jax.Array:
    """Fused multi-head attention.

    q: (B, Sq, H, D); k, v: (B, Skv, KVH, D) with H % KVH == 0 (GQA); v
    may be (B, Skv, KVH, Dv) of another width (forward only), and the
    result is then (B, Sq, H, Dv).
    Offsets are *global token positions* of element 0 of the q / kv
    sequence — the causal mask is (q_offset + i) >= (kv_offset + j).
    With `window`, a query also sees no key more than `window - 1`
    positions behind it: (q_offset + i) - (kv_offset + j) < window
    (forward only). With `block` (a power of two; no window; offsets
    Python ints and multiples of it; forward only) the mask is
    block-causal: a query sees every key of its own block of `block`
    positions and of the blocks before, (q_offset + i) // block >=
    (kv_offset + j) // block. The kernels' grids follow the mask: with
    offsets given as Python ints a block that holds no live pair gets
    neither a step nor a fetch (`grid_steps`, counted in `FLASH_GRID`).
    Returns (B, Sq, H, D).

    `block_q`, `block_k`: None lets the forward and the backward choose
    their blocks from the call's shapes (`_fwd_blocks`, `_bwd_blocks`);
    a number is a target for both.

    `interpret=None` compiles the kernels on a TPU and takes the
    reference anywhere else; `True` runs them in the Pallas interpreter
    (kernel tests on the CPU); `False` compiles them whatever the
    backend (ahead-of-time compiles for a described chip).
    """
    B, Sq, H, D = q.shape
    Skv = k.shape[1]
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(D)
    if window is not None and (not causal or window < 1):
        raise ValueError("flash_attention: a window needs causal=True "
                         f"and at least one position, got {window!r}")

    if block is not None:
        ints = all(isinstance(x, (int, np.integer))
                   for x in (q_offset, kv_offset))
        if not causal or window is not None or block < 1 \
                or block & (block - 1) or not ints \
                or q_offset % block or kv_offset % block \
                or Sq % block or Skv % block:
            raise ValueError(
                "flash_attention: a block-causal mask needs causal=True, "
                "no window, a power of two that divides both lengths, "
                f"and offsets known and multiples of it, got {block!r} "
                f"at offsets {q_offset!r}, {kv_offset!r}")
    want = _bwd_blocks(Sq, Skv, D)
    bwd = tileable(Sq, Skv, D, block_q or want[0], block_k or want[1])
    want = _fwd_blocks(Sq, Skv, D, window)
    fwd = tileable(Sq, Skv, D, block_q or want[0], block_k or want[1])
    compiled = on_tpu() if interpret is None else not interpret
    if force_reference:
        path = "reference_forced"
    elif not bwd[0]:
        path = "reference_untileable"
    elif force_pallas or interpret:
        path = "pallas" if compiled else "pallas_interpret"
    elif not compiled:
        # Off the TPU the interpreter is a test tool, never a default.
        path = "reference_no_tpu"
    elif Skv < _XLA_CROSSOVER_SKV:
        # Below the crossover the O(S^2) score buffer is still cheap and
        # XLA fuses attention with the surrounding matmuls. The value
        # dates from the earlier remote installation and has not been
        # re-measured on the local chip (ROADMAP Queue 1 item 4).
        path = "reference_short_kv"
    else:
        path = "pallas"
    DISPATCH_COUNTS[path] += 1
    use_pallas = path.startswith("pallas")
    offsets = (_static_offset(q_offset), _static_offset(kv_offset))
    static_offs = None if None in offsets else offsets
    if use_pallas:
        for name, n in grid_steps(
                Sq, Skv, *fwd, causal=causal, window=window,
                q_offset=offsets[0], kv_offset=offsets[1]).items():
            FLASH_GRID[name] += B * H * n

    # The kernels read a kv head a group of query heads; the reference
    # takes K and V expanded.
    kv_heads = k.shape[2] if use_pallas else H
    qt = jnp.swapaxes(q, 1, 2)
    kt = _expand_kv(jnp.swapaxes(k, 1, 2), kv_heads)
    vt = _expand_kv(jnp.swapaxes(v, 1, 2), kv_heads)
    offs = jnp.asarray([[q_offset, kv_offset]], jnp.float32)
    if block is not None and use_pallas and (fwd[0] % block
                                             or fwd[1] % block):
        raise ValueError(f"flash_attention: block {block} does not divide "
                         f"the kernel's blocks {fwd}")
    out = _flash(qt, kt, vt, offs, causal, sm_scale, fwd, bwd, use_pallas,
                 not compiled, window, static_offs, block)
    return jnp.swapaxes(out, 1, 2)


def attention(q, k, v, *, causal: bool = True,
              sm_scale: Optional[float] = None,
              impl: str = "auto", **kw) -> jax.Array:
    """Dispatcher: impl in {"auto", "flash", "reference"}."""
    if impl == "reference":
        return flash_attention(q, k, v, causal=causal, sm_scale=sm_scale,
                               force_reference=True, **kw)
    if impl == "flash":
        return flash_attention(q, k, v, causal=causal, sm_scale=sm_scale,
                               force_pallas=True, **kw)
    return flash_attention(q, k, v, causal=causal, sm_scale=sm_scale, **kw)
