"""Fused flash attention (pallas, TPU).

FlashAttention-2-style tiling for the MXU: grid over (batch, head,
q-block, kv-block) with the kv-block dimension innermost/sequential;
online-softmax statistics (m, l) and the output accumulator live in VMEM
scratch across kv iterations, so HBM traffic is O(S) per head instead of
the O(S^2) score matrix. The backward pass recomputes scores blockwise
(two kernels: dq with a kv loop, dk/dv with a q loop) from the saved
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
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
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

def _first_kv_block(q_off, kv_off, qi, block_q, block_k, window):
    """The kv block holding the first key that the window of q block
    `qi`'s first query reaches (block 0 where that lies before kv's
    start)."""
    first_key = q_off + qi * block_q - (window - 1) - kv_off
    return jnp.maximum(first_key, 0) // block_k


def _fwd_kernel(offs_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                acc_ref, m_ref, l_ref, *, sm_scale, block_q, block_k,
                num_kv, causal, window=None, kv_steps=None):
    """One (q block, kv block) step. The last grid axis walks all
    `num_kv` kv blocks, or, with `kv_steps` (a window: `_fwd_impl`), the
    `kv_steps` blocks from the first one the q block's window reaches;
    `offs_ref` is then the scalar-prefetched int32 (2,)."""
    qi, step = pl.program_id(2), pl.program_id(3)

    @pl.when(step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    if kv_steps is None:
        q_off = offs_ref[0, 0].astype(jnp.int32)
        kv_off = offs_ref[0, 1].astype(jnp.int32)
        ki, last = step, num_kv - 1
    else:
        q_off, kv_off = offs_ref[0], offs_ref[1]
        ki = _first_kv_block(q_off, kv_off, qi, block_q, block_k,
                             window) + step
        last = kv_steps - 1

    def compute():
        q = q_ref[0, 0, :, :]
        k = k_ref[0, 0, :, :]
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * sm_scale
        if causal:
            q_pos = (q_off + qi * block_q
                     + lax.broadcasted_iota(jnp.int32,
                                            (block_q, block_k), 0))
            k_pos = (kv_off + ki * block_k
                     + lax.broadcasted_iota(jnp.int32,
                                            (block_q, block_k), 1))
            mask = q_pos >= k_pos
            if window is not None:
                mask = mask & (q_pos - k_pos < window)
            s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[:, :1]
        l_prev = l_ref[:, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        if causal:
            p = p * mask  # fully-masked rows must contribute exactly 0
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        v = v_ref[0, 0, :, :]
        pv = lax.dot(p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    if causal:
        # Block skip: whole kv block above the diagonal → no compute.
        last_q = q_off + (qi + 1) * block_q - 1
        first_k = kv_off + ki * block_k
        live = last_q >= first_k
        if window is not None:
            # ... and whole kv block behind every query's window.
            first_q = q_off + qi * block_q
            last_k = first_k + block_k - 1
            live = live & (first_q - last_k < window)
            if kv_steps is not None:
                # ... and a step past kv's last block (its fetch is the
                # last block again).
                live = live & (ki < num_kv)

        @pl.when(live)
        def _():
            compute()
    else:
        compute()

    @pl.when(step == last)
    def _finalize():
        l = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0, 0, :, :] = (acc_ref[...] / l).astype(o_ref.dtype)
        lse_ref[0, 0, :, :] = jnp.broadcast_to(
            m_ref[:, :1] + jnp.log(l), lse_ref.shape[2:])


def _fwd_impl(q, k, v, offs, *, sm_scale, block_q, block_k, causal,
              interpret, window=None) -> Tuple[jax.Array, jax.Array]:
    """q,k,v: (B, H, S, D) (kv heads already expanded). → (out, lse).

    With a window the last grid axis is as long as the kv blocks a q
    block's window can reach (`window` - 1 + `block_q` keys: at most
    that span's blocks and one more, whatever the offsets), not all of
    kv's: a block behind the window or above the diagonal is neither
    fetched nor given a step (8,192 positions under a window of 1,024
    in blocks of 256 x 512: 4 steps a q block where all of kv is 16).
    The offsets then reach the index maps as a prefetched scalar."""
    B, H, Sq, D = q.shape
    Skv = k.shape[2]
    nq, nk = Sq // block_q, Skv // block_k
    kv_steps = None
    if window is not None:
        kv_steps = min(nk, (window + block_q - 3) // block_k + 2)
    kernel = functools.partial(
        _fwd_kernel, sm_scale=sm_scale, block_q=block_q,
        block_k=block_k, num_kv=nk, causal=causal, window=window,
        kv_steps=kv_steps)

    def q_block(b, h, qi, ki, *_):
        return b, h, qi, 0

    def kv_block(b, h, qi, ki, *offs_ref):
        if offs_ref:
            first = _first_kv_block(offs_ref[0][0], offs_ref[0][1], qi,
                                    block_q, block_k, window)
            ki = jnp.minimum(first + ki, nk - 1)
        return b, h, ki, 0

    in_specs = [
        pl.BlockSpec((1, 1, block_q, D), q_block),
        pl.BlockSpec((1, 1, block_k, D), kv_block),
        pl.BlockSpec((1, 1, block_k, D), kv_block),
    ]
    out_specs = [
        pl.BlockSpec((1, 1, block_q, D), q_block),
        pl.BlockSpec((1, 1, block_q, _LANES), q_block),
    ]
    scratch_shapes = [
        pltpu.VMEM((block_q, D), jnp.float32),
        pltpu.VMEM((block_q, _LANES), jnp.float32),
        pltpu.VMEM((block_q, _LANES), jnp.float32),
    ]
    if kv_steps is None:
        grid = dict(
            grid=(B, H, nq, nk),
            in_specs=[pl.BlockSpec((1, 2), lambda b, h, qi, ki: (0, 0),
                                   memory_space=pltpu.SMEM)] + in_specs,
            out_specs=out_specs, scratch_shapes=scratch_shapes)
    else:
        grid = dict(grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(B, H, nq, kv_steps),
            in_specs=in_specs, out_specs=out_specs,
            scratch_shapes=scratch_shapes))
        offs = offs.astype(jnp.int32).reshape(2)
    out, lse = pl.pallas_call(
        kernel,
        out_shape=[
            _sds((B, H, Sq, D), q.dtype, q, k, v, offs),
            _sds((B, H, Sq, _LANES), jnp.float32, q, k, v, offs),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        metadata={"kernel": "flash_fwd"},
        **grid,
    )(offs, q, k, v)
    return out, lse[..., 0]


# ---------------------------------------------------------------------------
# Backward kernels
# ---------------------------------------------------------------------------

def _dq_kernel(offs_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
               dq_ref, dq_acc, *, sm_scale, block_q, block_k, num_kv,
               causal):
    qi, ki = pl.program_id(2), pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    q_off = offs_ref[0, 0].astype(jnp.int32)
    kv_off = offs_ref[0, 1].astype(jnp.int32)

    def compute():
        q = q_ref[0, 0, :, :]
        k = k_ref[0, 0, :, :]
        v = v_ref[0, 0, :, :]
        do = do_ref[0, 0, :, :]
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * sm_scale
        lse = lse_ref[0, 0, :, :1]
        p = jnp.exp(s - lse)
        if causal:
            q_pos = (q_off + qi * block_q
                     + lax.broadcasted_iota(jnp.int32,
                                            (block_q, block_k), 0))
            k_pos = (kv_off + ki * block_k
                     + lax.broadcasted_iota(jnp.int32,
                                            (block_q, block_k), 1))
            p = p * (q_pos >= k_pos)
        dp = lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
        delta = delta_ref[0, 0, :, :1]
        ds = p * (dp - delta) * sm_scale
        dq_acc[...] += lax.dot(ds.astype(k.dtype), k,
                               preferred_element_type=jnp.float32)

    if causal:
        last_q = q_off + (qi + 1) * block_q - 1
        first_k = kv_off + ki * block_k

        @pl.when(last_q >= first_k)
        def _():
            compute()
    else:
        compute()

    @pl.when(ki == num_kv - 1)
    def _finalize():
        dq_ref[0, 0, :, :] = dq_acc[...].astype(dq_ref.dtype)


def _dkv_kernel(offs_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_acc, dv_acc, *, sm_scale, block_q,
                block_k, num_q, causal):
    ki, qi = pl.program_id(2), pl.program_id(3)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q_off = offs_ref[0, 0].astype(jnp.int32)
    kv_off = offs_ref[0, 1].astype(jnp.int32)

    def compute():
        q = q_ref[0, 0, :, :]
        k = k_ref[0, 0, :, :]
        v = v_ref[0, 0, :, :]
        do = do_ref[0, 0, :, :]
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * sm_scale
        lse = lse_ref[0, 0, :, :1]
        p = jnp.exp(s - lse)
        if causal:
            q_pos = (q_off + qi * block_q
                     + lax.broadcasted_iota(jnp.int32,
                                            (block_q, block_k), 0))
            k_pos = (kv_off + ki * block_k
                     + lax.broadcasted_iota(jnp.int32,
                                            (block_q, block_k), 1))
            p = p * (q_pos >= k_pos)
        # dv += p^T do  (contract the q dimension)
        dv_acc[...] += lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
        delta = delta_ref[0, 0, :, :1]
        ds = (p * (dp - delta) * sm_scale).astype(q.dtype)
        dk_acc[...] += lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        last_q = q_off + (qi + 1) * block_q - 1
        first_k = kv_off + ki * block_k

        @pl.when(last_q >= first_k)
        def _():
            compute()
    else:
        compute()

    @pl.when(qi == num_q - 1)
    def _finalize():
        dk_ref[0, 0, :, :] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0, :, :] = dv_acc[...].astype(dv_ref.dtype)


def _bwd_impl(q, k, v, do, out, lse, offs, *, sm_scale, block_q, block_k,
              causal, interpret):
    """→ (dq, dk, dv) for expanded-head layout (B, H, S, D)."""
    B, H, Sq, D = q.shape
    Skv = k.shape[2]
    nq, nk = Sq // block_q, Skv // block_k
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)  # (B, H, Sq)
    # Lane-broadcast the per-row stats: TPU blocks need (…, 8k, 128)-
    # tileable trailing dims.
    lse_l = jnp.broadcast_to(lse[..., None], (B, H, Sq, _LANES))
    delta_l = jnp.broadcast_to(delta[..., None], (B, H, Sq, _LANES))

    smem = pl.BlockSpec((1, 2), lambda b, h, i, j: (0, 0),
                        memory_space=pltpu.SMEM)

    def q_spec(i_of):
        return pl.BlockSpec((1, 1, block_q, D),
                            lambda b, h, i, j, f=i_of: (b, h, f(i, j), 0))

    def k_spec(i_of):
        return pl.BlockSpec((1, 1, block_k, D),
                            lambda b, h, i, j, f=i_of: (b, h, f(i, j), 0))

    def row_spec(i_of):
        return pl.BlockSpec((1, 1, block_q, _LANES),
                            lambda b, h, i, j, f=i_of: (b, h, f(i, j), 0))

    qi_of = lambda i, j: i   # noqa: E731
    kj_of = lambda i, j: j   # noqa: E731

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, sm_scale=sm_scale, block_q=block_q,
                          block_k=block_k, num_kv=nk, causal=causal),
        grid=(B, H, nq, nk),
        in_specs=[smem, q_spec(qi_of), k_spec(kj_of), k_spec(kj_of),
                  q_spec(qi_of), row_spec(qi_of), row_spec(qi_of)],
        out_specs=[q_spec(qi_of)],
        out_shape=[_sds((B, H, Sq, D), q.dtype, q, k, v, do, offs)],
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        metadata={"kernel": "flash_dq"},
    )(offs, q, k, v, do, lse_l, delta_l)[0]

    # dkv grid: kv blocks parallel, q loop innermost/sequential.
    ki_of = lambda i, j: i   # noqa: E731
    qj_of = lambda i, j: j   # noqa: E731
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, sm_scale=sm_scale, block_q=block_q,
                          block_k=block_k, num_q=nq, causal=causal),
        grid=(B, H, nk, nq),
        in_specs=[smem, q_spec(qj_of), k_spec(ki_of), k_spec(ki_of),
                  q_spec(qj_of), row_spec(qj_of), row_spec(qj_of)],
        out_specs=[k_spec(ki_of), k_spec(ki_of)],
        out_shape=[_sds((B, H, Skv, D), k.dtype, q, k, v, do, offs),
                   _sds((B, H, Skv, D), v.dtype, q, k, v, do, offs)],
        scratch_shapes=[pltpu.VMEM((block_k, D), jnp.float32),
                        pltpu.VMEM((block_k, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        metadata={"kernel": "flash_dkv"},
    )(offs, q, k, v, do, lse_l, delta_l)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# Reference fallback (pure jnp — differentiable, XLA-fused)
# ---------------------------------------------------------------------------

def _reference(q, k, v, offs, *, sm_scale, causal, window=None):
    """(B, H, S, D) layout. Returns (out, lse)."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * sm_scale
    if causal:
        Sq, Skv = q.shape[2], k.shape[2]
        q_pos = offs[0, 0].astype(jnp.int32) + jnp.arange(Sq)[:, None]
        k_pos = offs[0, 1].astype(jnp.int32) + jnp.arange(Skv)[None, :]
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
                   nondiff_argnums=(4, 5, 6, 7, 8, 9, 10))
def _flash(q, k, v, offs, causal, sm_scale, block_q, block_k, use_pallas,
           interpret, window=None):
    out, _ = _flash_fwd(q, k, v, offs, causal, sm_scale, block_q, block_k,
                        use_pallas, interpret, window)[0], None
    return out


def _flash_fwd(q, k, v, offs, causal, sm_scale, block_q, block_k,
               use_pallas, interpret, window=None):
    if use_pallas:
        out, lse = _fwd_impl(q, k, v, offs, sm_scale=sm_scale,
                             block_q=block_q, block_k=block_k,
                             causal=causal, interpret=interpret,
                             window=window)
    else:
        out, lse = _reference(q, k, v, offs, sm_scale=sm_scale,
                              causal=causal, window=window)
    return out, (q, k, v, offs, out, lse)


def _flash_fwd_rule(q, k, v, offs, causal, sm_scale, block_q, block_k,
                    use_pallas, interpret, window=None):
    out, res = _flash_fwd(q, k, v, offs, causal, sm_scale, block_q,
                          block_k, use_pallas, interpret, window)
    return out, res


def _flash_bwd_rule(causal, sm_scale, block_q, block_k, use_pallas,
                    interpret, window, res, g):
    if window is not None:
        raise NotImplementedError(
            "flash_attention: the backward pass is not written for a "
            "window (the dq and dkv kernels mask causally only)")
    q, k, v, offs, out, lse = res
    if use_pallas:
        dq, dk, dv = _bwd_impl(q, k, v, g, out, lse, offs,
                               sm_scale=sm_scale, block_q=block_q,
                               block_k=block_k, causal=causal,
                               interpret=interpret)
    else:
        def f(q, k, v):
            return _reference(q, k, v, offs, sm_scale=sm_scale,
                              causal=causal)[0]
        dq, dk, dv = jax.vjp(f, q, k, v)[1](g)
    return dq, dk, dv, jnp.zeros_like(offs)


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def _pick_block(s: int, target: int) -> int:
    """Largest block <= target the TPU lowering accepts for a dimension
    of s rows: all of s, or a multiple of 8 rows that divides s (bf16
    compiles at 8 as well; checked by tests/test_tpu_aot_compile.py).
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


def _expand_kv(x: jax.Array, n_heads: int) -> jax.Array:
    kvh = x.shape[1]
    if kvh == n_heads:
        return x
    return jnp.repeat(x, n_heads // kvh, axis=1)


_XLA_CROSSOVER_SKV = 2048


def flash_attention(q, k, v, *, causal: bool = True,
                    sm_scale: Optional[float] = None,
                    block_q: int = 256, block_k: int = 512,
                    q_offset=0, kv_offset=0,
                    window: Optional[int] = None,
                    interpret: Optional[bool] = None,
                    force_reference: bool = False,
                    force_pallas: bool = False) -> jax.Array:
    """Fused multi-head attention.

    q: (B, Sq, H, D); k, v: (B, Skv, KVH, D) with H % KVH == 0 (GQA).
    Offsets are *global token positions* of element 0 of the q / kv
    sequence — the causal mask is (q_offset + i) >= (kv_offset + j).
    With `window`, a query also sees no key more than `window - 1`
    positions behind it: (q_offset + i) - (kv_offset + j) < window.
    Forward only; kv blocks wholly behind the window are given no grid
    step.
    Returns (B, Sq, H, D).

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

    qt = jnp.swapaxes(q, 1, 2)
    kt = _expand_kv(jnp.swapaxes(k, 1, 2), H)
    vt = _expand_kv(jnp.swapaxes(v, 1, 2), H)

    bq, bk = tileable(Sq, Skv, D, block_q, block_k)
    compiled = on_tpu() if interpret is None else not interpret
    if force_reference:
        path = "reference_forced"
    elif not bq:
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
    offs = jnp.asarray([[q_offset, kv_offset]], jnp.float32)
    out = _flash(qt, kt, vt, offs, causal, sm_scale, bq, bk,
                 path.startswith("pallas"), not compiled, window)
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
