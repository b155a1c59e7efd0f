"""Ring attention — sequence/context parallelism over the ICI ring.

Each device in the `sp` mesh axis holds a contiguous sequence shard of
q/k/v. The kv shard rotates around the ring with `lax.ppermute` (XLA
lowers this to ICI neighbor transfers that overlap with the per-step
flash-attention compute); after N steps every q shard has attended to
the full sequence. Per-step partial outputs are merged with
logsumexp-weighted accumulation, so the result is *exact* attention —
not an approximation.

The whole ring (forward scan + reverse scan) is one custom-VJP: the
backward pass rotates (k, v, dk, dv) together around the ring and uses
the flash backward kernels per step, recomputing scores from the saved
global logsumexp. This is the blockwise-parallel/ring-attention
formulation; memory per device stays O(S/N) activations.

The reference has no sequence parallelism anywhere (SURVEY.md §5
"long-context": delegated to DeepSpeed/vLLM) — this is new, first-class
capability. Must be called inside shard_map with q/k/v sharded along
`axis_name` on the sequence dimension.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from .flash_attention import (
    DISPATCH_COUNTS,
    NEG_INF,
    _bwd_impl,
    _fwd_impl,
    _reference,
    on_tpu,
    tileable,
)


def _step_offsets(my_idx, step, n, s_local):
    """Global positions for ring step: q stays local, kv shard `step`
    hops behind came from device (my_idx - step) mod n."""
    kv_idx = (my_idx - step) % n
    return my_idx * s_local, kv_idx * s_local


def _merge(out1, lse1, out2, lse2):
    """Merge two normalized partial attentions via logsumexp weights."""
    m = jnp.maximum(lse1, lse2)
    w1 = jnp.exp(lse1 - m)
    w2 = jnp.exp(lse2 - m)
    denom = w1 + w2
    lse = m + jnp.log(denom)
    a1 = (w1 / denom)[..., None].astype(out1.dtype)
    a2 = (w2 / denom)[..., None].astype(out2.dtype)
    return out1 * a1 + out2 * a2, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _ring(q, k, v, axis_name, causal, sm_scale, block_q, block_k,
          use_pallas):
    out, _ = _ring_fwd(q, k, v, axis_name, causal, sm_scale, block_q,
                       block_k, use_pallas)
    return out


def _one_step(q, k, v, offs, *, causal, sm_scale, block_q, block_k,
              use_pallas):
    if use_pallas:
        return _fwd_impl(q, k, v, offs, sm_scale=sm_scale,
                         block_q=block_q, block_k=block_k, causal=causal,
                         interpret=False)
    return _reference(q, k, v, offs, sm_scale=sm_scale, causal=causal)


def _ring_fwd(q, k, v, axis_name, causal, sm_scale, block_q, block_k,
              use_pallas):
    n = lax.axis_size(axis_name)
    my_idx = lax.axis_index(axis_name)
    B, H, S, D = q.shape
    perm = [(i, (i + 1) % n) for i in range(n)]

    def body(carry, step):
        k_cur, v_cur, out_acc, lse_acc = carry
        q_off, kv_off = _step_offsets(my_idx, step, n, S)
        offs = jnp.asarray([[q_off, kv_off]], jnp.float32)

        def run(_):
            o, l = _one_step(q, k_cur, v_cur, offs, causal=causal,
                             sm_scale=sm_scale, block_q=block_q,
                             block_k=block_k, use_pallas=use_pallas)
            return _merge(out_acc, lse_acc, o.astype(out_acc.dtype), l)

        if causal:
            # kv shard entirely in the future → skip compute, just rotate.
            needed = kv_off <= q_off + S - 1
            out_new, lse_new = lax.cond(
                needed, run, lambda _: (out_acc, lse_acc), None)
        else:
            out_new, lse_new = run(None)
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        return (k_nxt, v_nxt, out_new, lse_new), None

    out0 = lax.pcast(jnp.zeros((B, H, S, D), jnp.float32), axis_name,
                     to="varying")
    lse0 = lax.pcast(jnp.full((B, H, S), NEG_INF, jnp.float32), axis_name,
                     to="varying")
    (k_back, v_back, out, lse), _ = lax.scan(
        body, (k, v, out0, lse0), jnp.arange(n))
    # n rotations = full circle: k_back/v_back are the original shards.
    out = out.astype(q.dtype)
    return out, (q, k_back, v_back, out, lse)


def _ring_bwd(axis_name, causal, sm_scale, block_q, block_k, use_pallas,
              res, g):
    q, k, v, out, lse = res
    n = lax.axis_size(axis_name)
    my_idx = lax.axis_index(axis_name)
    S = q.shape[2]
    perm = [(i, (i + 1) % n) for i in range(n)]

    def step_grads(k_cur, v_cur, offs):
        if use_pallas:
            return _bwd_impl(q, k_cur, v_cur, g, out, lse, offs,
                             sm_scale=sm_scale, block_q=block_q,
                             block_k=block_k, causal=causal,
                             interpret=False)
        # jnp fallback: unnormalized-softmax gradient against global lse.
        s = (jnp.einsum("bhqd,bhkd->bhqk", q, k_cur)
             .astype(jnp.float32) * sm_scale)
        Sq, Skv = q.shape[2], k_cur.shape[2]
        if causal:
            q_pos = offs[0, 0].astype(jnp.int32) + jnp.arange(Sq)[:, None]
            k_pos = offs[0, 1].astype(jnp.int32) + jnp.arange(Skv)[None, :]
            mask = (q_pos >= k_pos)[None, None]
        p = jnp.exp(s - lse[..., None])
        if causal:
            p = jnp.where(mask, p, 0.0)
        gf = g.astype(jnp.float32)
        dv = jnp.einsum("bhqk,bhqd->bhkd", p, gf)
        dp = jnp.einsum("bhqd,bhkd->bhqk", gf, v_cur.astype(jnp.float32))
        delta = jnp.sum(gf * out.astype(jnp.float32), axis=-1)
        ds = p * (dp - delta[..., None]) * sm_scale
        dq = jnp.einsum("bhqk,bhkd->bhqd", ds, k_cur.astype(jnp.float32))
        dk = jnp.einsum("bhqk,bhqd->bhkd", ds, q.astype(jnp.float32))
        return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)

    def body(carry, step):
        k_cur, v_cur, dk_cur, dv_cur, dq_acc = carry
        q_off, kv_off = _step_offsets(my_idx, step, n, S)
        offs = jnp.asarray([[q_off, kv_off]], jnp.float32)

        def run(_):
            dq_s, dk_s, dv_s = step_grads(k_cur, v_cur, offs)
            return (dq_acc + dq_s.astype(dq_acc.dtype),
                    dk_cur + dk_s.astype(dk_cur.dtype),
                    dv_cur + dv_s.astype(dv_cur.dtype))

        if causal:
            needed = kv_off <= q_off + S - 1
            dq_new, dk_new, dv_new = lax.cond(
                needed, run,
                lambda _: (dq_acc, dk_cur, dv_cur), None)
        else:
            dq_new, dk_new, dv_new = run(None)
        # (k, v, dk, dv) rotate together so each step's gradient lands on
        # the shard that produced it; after n steps they're home.
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        dk_nxt = lax.ppermute(dk_new, axis_name, perm)
        dv_nxt = lax.ppermute(dv_new, axis_name, perm)
        return (k_nxt, v_nxt, dk_nxt, dv_nxt, dq_new), None

    dq0, dk0, dv0 = (
        lax.pcast(jnp.zeros(x.shape, jnp.float32), axis_name,
                  to="varying") for x in (q, k, v))
    (k_b, v_b, dk, dv, dq), _ = lax.scan(
        body, (k, v, dk0, dv0, dq0), jnp.arange(n))
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_ring.defvjp(lambda q, k, v, a, c, s, bq, bk, up:
             _ring_fwd(q, k, v, a, c, s, bq, bk, up),
             _ring_bwd)


def ring_attention(q, k, v, axis_name: str, *, causal: bool = True,
                   sm_scale: Optional[float] = None,
                   block_q: int = 256, block_k: int = 512) -> jax.Array:
    """Exact attention over a sequence sharded along `axis_name`.

    Call inside shard_map. q: (B, S_local, H, D); k, v: (B, S_local,
    KVH, D). Returns (B, S_local, H, D). GQA heads are expanded before
    the ring (gradient reduction over the group is handled by autodiff
    through the expand).
    """
    B, S, H, D = q.shape
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(D)
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    if kt.shape[1] != H:
        rep = H // kt.shape[1]
        kt = jnp.repeat(kt, rep, axis=1)
        vt = jnp.repeat(vt, rep, axis=1)
    # The same decision as flash_attention: the kernels on a TPU, the
    # reference (never the interpreter) anywhere else. A step's offsets
    # are traced, so its forward and dq walk a run of kv blocks a q block
    # (`flash_attention._fwd_grid`) and dkv a run of q blocks a kv block
    # (`_dkv_grid`), all three in the blocks given here.
    bq, bk = tileable(S, S, D, block_q, block_k)
    tpu = on_tpu()
    use_pallas = bool(bq) and tpu
    DISPATCH_COUNTS["ring_pallas" if use_pallas
                    else "ring_reference_untileable" if tpu
                    else "ring_reference_no_tpu"] += 1
    out = _ring(qt, kt, vt, axis_name, causal, sm_scale, bq, bk,
                use_pallas)
    return jnp.swapaxes(out, 1, 2)
