"""Tests for ray_tpu.ops pallas kernels (interpret mode on CPU).

Mirrors the reference's kernel-test style (value + gradient checks
against a dense reference implementation)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P
from jax.experimental.shard_map import shard_map

import importlib

from ray_tpu.ops import flash_attention as _flash_attention
from ray_tpu.ops import ring_attention, ulysses_attention

# Off the TPU flash_attention takes the reference by default; these are
# tests of the kernels, so they ask for the Pallas interpreter.
flash_attention = functools.partial(_flash_attention, interpret=True)
flash_mod = importlib.import_module("ray_tpu.ops.flash_attention")


def dense_ref(q, k, v, causal=True):
    """(B, S, H, D) layout reference."""
    D = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
    s = s / jnp.sqrt(jnp.float32(D))
    if causal:
        Sq, Sk = q.shape[1], k.shape[1]
        mask = jnp.tril(jnp.ones((Sq, Sk), bool))
        s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)


def rand_qkv(key, B=2, S=256, H=4, KVH=None, D=64, dtype=jnp.float32):
    KVH = KVH or H
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, S, H, D), dtype)
    k = jax.random.normal(kk, (B, S, KVH, D), dtype)
    v = jax.random.normal(kv, (B, S, KVH, D), dtype)
    return q, k, v


# (Sq, Skv, q_offset, kv_offset): what a ring step hands `_fwd_impl`
# (kv shards on, below and above the diagonal) and a prefix call.
FWD_OFFSETS = {
    "on_diagonal": (128, 128, 0, 0),
    "shard_on_diagonal": (128, 128, 256, 256),
    "shard_below": (128, 128, 256, 128),
    "shard_above": (128, 128, 128, 256),
    "shard_far_below": (128, 128, 512, 0),
    "prefix": (64, 192, 128, 0),
}

# (sq, skv, block_q, block_k, causal, window, q_offset, kv_offset)
GRID_CASES = {
    "causal": (256, 256, 32, 64, True, None, 0, 0),
    "causal_wide_q": (256, 256, 64, 32, True, None, 0, 0),
    "full": (128, 256, 32, 64, False, None, 0, 0),
    "window_in_a_block": (256, 256, 32, 64, True, 24, 0, 0),
    "window_across_blocks": (256, 256, 32, 64, True, 100, 0, 0),
    "window_as_long_as_kv": (256, 256, 32, 64, True, 256, 0, 0),
    "window_of_one": (128, 128, 32, 32, True, 1, 0, 0),
    "prefix": (64, 320, 32, 64, True, None, 256, 0),
    "prefix_window": (64, 320, 32, 64, True, 96, 256, 0),
    "shard_above": (128, 128, 32, 64, True, None, 0, 128),
    "shard_half_above": (128, 128, 32, 64, True, None, 0, 64),
    "shard_below": (128, 128, 32, 64, True, None, 128, 0),
    "shard_below_window": (128, 128, 32, 64, True, 48, 128, 0),
    "unaligned_offsets": (128, 192, 32, 64, True, 80, 37, 5),
}


def _fwd_both(key, sq, skv, h, kvh, *, causal, window=None, q_off=0,
              kv_off=0, traced=False, blocks=(32, 64)):
    """(out, lse) of the kernel in the interpreter and of the reference,
    (B, H, S, D) layout, and which rows see a key at all."""
    kq, kk, kv_ = jax.random.split(key, 3)
    q = jax.random.normal(kq, (2, h, sq, 32))
    k = jax.random.normal(kk, (2, kvh, skv, 32))
    v = jax.random.normal(kv_, (2, kvh, skv, 32))
    offs = jnp.asarray([[q_off, kv_off]], jnp.float32)
    kw = dict(sm_scale=32 ** -0.5, causal=causal, window=window)
    got = jax.jit(lambda q, k, v, offs: flash_mod._fwd_impl(
        q, k, v, offs, block_q=blocks[0], block_k=blocks[1],
        interpret=True, static_offs=None if traced else (q_off, kv_off),
        **kw))(q, k, v, offs)
    want = flash_mod._reference(q, flash_mod._expand_kv(k, h),
                                flash_mod._expand_kv(v, h), offs, **kw)
    i = q_off + np.arange(sq)[:, None]
    j = kv_off + np.arange(skv)[None, :]
    live = np.ones(sq, bool) if not causal else (
        (i >= j) & (i - j < (window or 1 << 30))).any(axis=1)
    return got, want, live


def _bwd_both(key, sq, skv, h, kvh, *, causal, q_off=0, kv_off=0,
              traced=False, blocks=(32, 64)):
    """(dq, dk, dv) of the two kernels in the interpreter, on the
    residuals the forward kernel leaves, and of `_reference`'s vjp (K and
    V expanded in front, so its dk and dv sum over a kv head's group),
    (B, H, S, D) layout, and which rows see a key at all."""
    kq, kk, kv_, kg = jax.random.split(key, 4)
    q = jax.random.normal(kq, (2, h, sq, 32))
    k = jax.random.normal(kk, (2, kvh, skv, 32))
    v = jax.random.normal(kv_, (2, kvh, skv, 32))
    do = jax.random.normal(kg, (2, h, sq, 32))
    offs = jnp.asarray([[q_off, kv_off]], jnp.float32)
    kw = dict(sm_scale=32 ** -0.5, causal=causal, block_q=blocks[0],
              block_k=blocks[1], interpret=True,
              static_offs=None if traced else (q_off, kv_off))

    @jax.jit
    def kernels(q, k, v, do, offs):
        out, lse = flash_mod._fwd_impl(q, k, v, offs, **kw)
        return flash_mod._bwd_impl(q, k, v, do, out, lse, offs, **kw)

    want = jax.vjp(lambda q, k, v: flash_mod._reference(
        q, flash_mod._expand_kv(k, h), flash_mod._expand_kv(v, h), offs,
        sm_scale=kw["sm_scale"], causal=causal)[0], q, k, v)[1](do)
    i = q_off + np.arange(sq)[:, None]
    j = kv_off + np.arange(skv)[None, :]
    live = np.ones(sq, bool) if not causal else (i >= j).any(axis=1)
    return kernels(q, k, v, do, offs), want, live


def _assert_bwd(got, want, live):
    """Where every row sees a key the three gradients agree; where none
    does (a shard above the diagonal: the reference spreads such a row
    over every key) they are exactly 0."""
    assert live.all() or not live.any()
    for g, w in zip(got, want):
        assert g.shape == w.shape and g.dtype == w.dtype
        if live.all():
            np.testing.assert_allclose(g, w, atol=5e-4, rtol=5e-4)
        else:
            assert not np.asarray(g).any()


def _assert_grads_match_the_reference(q, k, v):
    """dq, dk, dv of sum(attention^2) through the kernels in the
    interpreter against the same through the reference path."""
    def loss(attn):
        return lambda q, k, v: jnp.sum(attn(q, k, v) ** 2)

    g1 = jax.grad(loss(flash_attention), argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss(functools.partial(
        _flash_attention, force_reference=True)),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, atol=5e-4, rtol=5e-4)


def _assert_fwd(got, want, live):
    """Rows that see a key agree in out and lse; a row that sees none
    contributes exactly 0 and its lse stays at the floor (the reference
    divides 0 by 0 there, so it is not asked)."""
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g)[:, :, live],
                                   np.asarray(w)[:, :, live],
                                   atol=2e-5, rtol=2e-5)
    assert not np.asarray(got[0])[:, :, ~live].any()
    assert (np.asarray(got[1])[:, :, ~live] < -1e29).all()


class TestFlashAttention:
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_reference(self, causal):
        q, k, v = rand_qkv(jax.random.key(0))
        out = flash_attention(q, k, v, causal=causal)
        ref = dense_ref(q, k, v, causal=causal)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_gqa(self):
        q, k, v = rand_qkv(jax.random.key(1), H=8, KVH=2)
        out = flash_attention(q, k, v)
        kr = jnp.repeat(k, 4, axis=2)
        vr = jnp.repeat(v, 4, axis=2)
        ref = dense_ref(q, kr, vr)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_grads_match_reference(self):
        q, k, v = rand_qkv(jax.random.key(2), B=1, S=128, H=2)

        def f_flash(q, k, v):
            return jnp.sum(flash_attention(q, k, v) ** 2)

        def f_ref(q, k, v):
            return jnp.sum(dense_ref(q, k, v) ** 2)

        g1 = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(a, b, atol=5e-4, rtol=5e-4)

    def test_gqa_grads(self):
        q, k, v = rand_qkv(jax.random.key(3), B=1, S=128, H=4, KVH=2)

        def f_flash(q, k, v):
            return jnp.sum(flash_attention(q, k, v) ** 2)

        def f_ref(q, k, v):
            kr = jnp.repeat(k, 2, axis=2)
            vr = jnp.repeat(v, 2, axis=2)
            return jnp.sum(dense_ref(q, kr, vr) ** 2)

        g1 = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(a, b, atol=5e-4, rtol=5e-4)

    def test_offsets_decode_step(self):
        # One query token at position 255 attending to a 256-token kv —
        # the paged/decode masking path.
        key = jax.random.key(4)
        q, k, v = rand_qkv(key, B=1, S=256, H=2)
        qlast = q[:, 255:256]
        out = flash_attention(qlast, k, v, causal=True, q_offset=255)
        ref = dense_ref(q, k, v, causal=True)[:, 255:256]
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_ragged_falls_back(self):
        q, k, v = rand_qkv(jax.random.key(5), S=100, D=60)
        out = flash_attention(q, k, v)
        ref = dense_ref(q, k, v)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    # -- the forward's grid follows the mask (PR 33) ----------------------

    @pytest.mark.parametrize("offsets", sorted(FWD_OFFSETS))
    @pytest.mark.parametrize("traced", [False, True],
                             ids=["static", "traced"])
    @pytest.mark.parametrize("mask", ["full", "causal", "window64",
                                      "window_kv"])
    def test_forward_out_and_lse(self, mask, traced, offsets):
        """`_fwd_impl`'s out and lse against `_reference`, K and V
        unexpanded (4 query heads a kv head): no mask, the diagonal, a
        window of 64 and one as long as kv; offsets the trace sees (the
        table of live pairs) and ones it does not (runs of kv blocks: a
        ring step's shard on, below and above the diagonal), and a
        prefix call's Skv > Sq behind a `q_offset`."""
        sq, skv, q_off, kv_off = FWD_OFFSETS[offsets]
        window = {"window64": 64, "window_kv": skv}.get(mask)
        got, want, live = _fwd_both(
            jax.random.key(11), sq, skv, 4, 1, causal=mask != "full",
            window=window, q_off=q_off, kv_off=kv_off, traced=traced)
        _assert_fwd(got, want, live)

    @pytest.mark.parametrize("kvh", [8, 4, 2, 1])
    @pytest.mark.parametrize("window", [None, 64])
    def test_forward_reads_the_group_kv_head(self, window, kvh):
        """Head groups of 1 / 2 / 4 / 8: query head h against kv head
        h // group, read by the kv block's index map."""
        got, want, live = _fwd_both(jax.random.key(12), 128, 128, 8, kvh,
                                    causal=True, window=window)
        _assert_fwd(got, want, live)

    @pytest.mark.parametrize("window", [None, 100])
    @pytest.mark.parametrize("s", [192, 320, 768])
    def test_forward_where_the_large_block_does_not_divide(self, s,
                                                           window):
        """The forward asks for 512 keys a block: 192 = 3 x 64 and 320 =
        5 x 64 fit in one, 768 takes 384 (where the backward takes 256)
        under q blocks of all 768 rows, or of 384 under a window."""
        q, k, v = rand_qkv(jax.random.key(13), B=1, S=s, H=2, KVH=1, D=32)
        blocks = flash_mod.tileable(s, s, 32, *flash_mod._fwd_blocks(
            s, s, 32, window))
        assert blocks == ((384 if window else 768, 384) if s == 768
                          else (s, s))
        out = flash_attention(q, k, v, window=window)
        ref = _flash_attention(q, k, v, window=window,
                               force_reference=True)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    # kvh 2: `slow` since PR 50 (the suite's clock, ROADMAP D18); kvh 1
    # sums a larger group in the same kernels at a third of the time.
    @pytest.mark.parametrize("kvh", [
        pytest.param(2, marks=pytest.mark.slow), 1])
    def test_grads_with_the_forwards_blocks_and_the_backwards(self, kvh):
        """1,024 positions: the forward in its own blocks (1024 x 512),
        dq and dkv in theirs (`_bwd_blocks`), dk and dv summed over a kv
        head's group in the kernel, from the unexpanded residuals."""
        q, k, v = rand_qkv(jax.random.key(14), B=1, S=1024, H=2, KVH=kvh,
                           D=32)
        bwd = flash_mod.tileable(1024, 1024, 32,
                                 *flash_mod._bwd_blocks(1024, 1024, 32))
        assert 0 < bwd[0] < 1024 and flash_mod._fwd_blocks(
            1024, 1024, 32, None) != bwd
        _assert_grads_match_the_reference(q, k, v)

    # -- and the backward's (PR 35) --------------------------------------

    @pytest.mark.parametrize("offsets", sorted(FWD_OFFSETS))
    @pytest.mark.parametrize("traced", [False, True],
                             ids=["static", "traced"])
    @pytest.mark.parametrize("mask", ["full", "causal"])
    def test_backward_dq_dk_dv(self, mask, traced, offsets):
        """`_bwd_impl` against `_reference`'s vjp, K and V unexpanded (4
        query heads a kv head, summed in dkv): no mask and the diagonal;
        offsets the trace sees (dq on the forward's table, dkv on the
        table by kv block) and ones it does not (runs of blocks: a ring
        step's shard on, below and above the diagonal), and a prefix
        call's Skv > Sq behind a `q_offset`."""
        sq, skv, q_off, kv_off = FWD_OFFSETS[offsets]
        _assert_bwd(*_bwd_both(
            jax.random.key(16), sq, skv, 4, 1, causal=mask != "full",
            q_off=q_off, kv_off=kv_off, traced=traced))

    @pytest.mark.parametrize("kvh", [8, 4, 2, 1])
    @pytest.mark.parametrize("traced", [False, True],
                             ids=["static", "traced"])
    def test_backward_sums_the_group_in_the_kernel(self, traced, kvh):
        """Head groups of 1 / 2 / 4 / 8: dk and dv leave as (B, KVH, Skv,
        D), every query head of a kv head's group walked before its
        block is written."""
        got, want, live = _bwd_both(jax.random.key(17), 128, 128, 8, kvh,
                                    causal=True, traced=traced)
        assert got[1].shape == got[2].shape == (2, kvh, 128, 32)
        _assert_bwd(got, want, live)

    # kvh 2: `slow` since PR 50, as above; the three lengths stay at kvh 1.
    @pytest.mark.parametrize("kvh", [
        pytest.param(2, marks=pytest.mark.slow), 1])
    @pytest.mark.parametrize("s", [192, 320, 768])
    def test_grads_where_the_large_block_does_not_divide(self, s, kvh):
        """192 and 320 positions fit in one block; 768 takes 384 x 384
        for dq and dkv under the forward's q block of all 768 rows."""
        q, k, v = rand_qkv(jax.random.key(18), B=1, S=s, H=2, KVH=kvh,
                           D=32)
        assert flash_mod.tileable(s, s, 32, *flash_mod._bwd_blocks(
            s, s, 32)) == ((384, 384) if s == 768 else (s, s))
        _assert_grads_match_the_reference(q, k, v)

    def test_backward_grid_at_the_cells_shapes(self):
        """A head of the train cell's launch in 256 x 512 was 128 steps a
        kernel, 72 live, every live one masked; the call's trace counts
        what dq and dkv are given now, beside the forward's."""
        def steps(bq, bk, by_kv):
            return flash_mod.grid_steps(4096, 4096, bq, bk, causal=True,
                                        by_kv=by_kv, group=2)

        bq, bk = flash_mod._bwd_blocks(4096, 4096, 128)
        assert (bq, bk) == (1024, 1024)
        for by_kv in (False, True):
            assert steps(256, 512, by_kv) == {
                "steps": 72, "live_steps": 72, "masked_steps": 16}
            assert steps(512, 512, by_kv) == {
                "steps": 36, "live_steps": 36, "masked_steps": 8}
            assert steps(bq, bk, by_kv) == {
                "steps": 10, "live_steps": 10, "masked_steps": 4}
        steps = steps(bq, bk, True)
        q = jax.ShapeDtypeStruct((2, 4096, 16, 128), jnp.bfloat16)
        k = jax.ShapeDtypeStruct((2, 4096, 8, 128), jnp.bfloat16)
        before = dict(flash_mod.FLASH_GRID)
        jax.make_jaxpr(jax.grad(lambda q, k, v: jnp.sum(flash_attention(
            q, k, v).astype(jnp.float32)), argnums=(0, 1, 2)))(q, k, k)
        added = {n: c - before.get(n, 0)
                 for n, c in flash_mod.FLASH_GRID.items()
                 if c != before.get(n, 0)}
        assert added == {
            "steps": 2 * 16 * 20, "live_steps": 2 * 16 * 20,
            "masked_steps": 2 * 16 * 8,
            **{f"{kern}_{name}": 2 * 16 * c for kern in ("dq", "dkv")
               for name, c in steps.items()}}
        # Offsets the trace cannot see: a run of blocks, counted apart.
        before = dict(flash_mod.FLASH_GRID)
        jax.make_jaxpr(jax.grad(lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, q_offset=jnp.int32(0)).astype(jnp.float32))))(q, k, k)
        assert {n: c - before.get(n, 0)
                for n, c in flash_mod.FLASH_GRID.items()
                if n.startswith("d") and c != before.get(n, 0)} == {
            f"{kern}_traced_steps": 2 * 16 * (4096 // bq) * (4096 // bk)
            for kern in ("dq", "dkv")}

    @pytest.mark.parametrize("walk", ["by_q", "by_kv"])
    @pytest.mark.parametrize("case", sorted(GRID_CASES))
    def test_grid_is_the_live_blocks(self, case, walk):
        """No pair scheduled whose block holds no live pair (but the one
        step of a q block that sees nothing), every block with a live
        pair scheduled exactly once, in order, and `masked_steps` = the
        blocks that hold a live and a dead pair. `by_kv`, dkv's walk:
        the same pairs by kv block, and the one step is of a kv block
        that no query sees."""
        sq, skv, bq, bk, causal, window, q_off, kv_off = GRID_CASES[case]
        by_kv = walk == "by_kv"
        i = q_off + np.arange(sq)[:, None]
        j = kv_off + np.arange(skv)[None, :]
        seen = np.ones((sq, skv), bool) if not causal else (
            (i >= j) if window is None else (i >= j) & (i - j < window))
        blocks = seen.reshape(sq // bq, bq, skv // bk, bk)
        live, whole = blocks.any(axis=(1, 3)), blocks.all(axis=(1, 3))
        qi, ki, kind = flash_mod._live_pairs(
            sq // bq, skv // bk, bq, bk, causal, window, q_off, kv_off,
            by_kv)
        pairs = list(zip(qi.tolist(), ki.tolist()))
        assert pairs == sorted(set(pairs), key=lambda p: p[::-1 if by_kv
                                                           else 1])
        blind = [(0, b) for b in range(skv // bk) if not live[:, b].any()
                 ] if by_kv else [(a, 0) for a in range(sq // bq)
                                  if not live[a].any()]
        assert set(pairs) == {(int(a), int(b))
                              for a, b in zip(*np.nonzero(live))} \
            | set(blind)
        for a, b, kd in zip(qi, ki, kind):
            assert kd == (flash_mod._DEAD if not live[a, b] else
                          flash_mod._INSIDE if whole[a, b] else
                          flash_mod._EDGE)
        assert flash_mod.grid_steps(
            sq, skv, bq, bk, causal=causal, window=window, q_offset=q_off,
            kv_offset=kv_off, by_kv=by_kv) == {
                "steps": len(pairs), "live_steps": int(live.sum()),
                "masked_steps": int((live & ~whole).sum())}

    def test_grid_at_the_cells_shapes(self):
        """A head of docqa's tile in 256 x 512 was 128 steps, 72 live,
        all of them masked; in the blocks the forward takes, no step is
        dead and the mask is built on the blocks an edge crosses."""
        def steps(s, window=None):
            bq, bk = flash_mod._fwd_blocks(s, s, 128, window)
            return bq, bk, flash_mod.grid_steps(s, s, bq, bk, causal=True,
                                                window=window)

        assert steps(4096) == (1024, 512, {
            "steps": 20, "live_steps": 20, "masked_steps": 8})
        assert steps(8192) == (1024, 1024, {
            "steps": 36, "live_steps": 36, "masked_steps": 8})
        assert steps(8192, window=1024) == (512, 512, {
            "steps": 45, "live_steps": 45, "masked_steps": 30})
        assert flash_mod.grid_steps(4096, 4096, 256, 512, causal=True,
                                    q_offset=None) == {"traced_steps": 128}

    def test_flash_grid_counts_what_a_call_was_given(self):
        q, k, v = rand_qkv(jax.random.key(15), B=2, S=256, H=4, KVH=2,
                           D=32)
        before = dict(flash_mod.FLASH_GRID)
        flash_attention(q, k, v, block_q=64, block_k=64)
        flash_attention(q, k, v, block_q=64, block_k=64,
                        q_offset=jnp.int32(0))
        _flash_attention(q, k, v, force_reference=True)
        added = {n: c - before.get(n, 0)
                 for n, c in flash_mod.FLASH_GRID.items()
                 if c != before.get(n, 0)}
        assert added == {"steps": 2 * 4 * 10, "live_steps": 2 * 4 * 10,
                         "masked_steps": 2 * 4 * 4,
                         "traced_steps": 2 * 4 * 16}


def _sp_mesh(devices, n=4):
    return Mesh(np.array(devices[:n]), ("sp",))


class TestRingAttention:
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_dense(self, cpu_mesh8, causal):
        mesh = _sp_mesh(cpu_mesh8, 4)
        q, k, v = rand_qkv(jax.random.key(6), B=2, S=256, H=2, D=32)

        ring = shard_map(
            functools.partial(ring_attention, axis_name="sp",
                              causal=causal),
            mesh=mesh,
            in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp")),
            out_specs=P(None, "sp"))
        out = ring(q, k, v)
        ref = dense_ref(q, k, v, causal=causal)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_grads_match_dense(self, cpu_mesh8):
        mesh = _sp_mesh(cpu_mesh8, 4)
        q, k, v = rand_qkv(jax.random.key(7), B=1, S=128, H=2, D=32)

        ring = shard_map(
            functools.partial(ring_attention, axis_name="sp"),
            mesh=mesh,
            in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp")),
            out_specs=P(None, "sp"))

        def f_ring(q, k, v):
            return jnp.sum(ring(q, k, v) ** 2)

        def f_ref(q, k, v):
            return jnp.sum(dense_ref(q, k, v) ** 2)

        g1 = jax.grad(f_ring, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(a, b, atol=1e-3, rtol=1e-3)

    def test_gqa(self, cpu_mesh8):
        mesh = _sp_mesh(cpu_mesh8, 4)
        q, k, v = rand_qkv(jax.random.key(8), B=1, S=128, H=4, KVH=2,
                           D=32)
        ring = shard_map(
            functools.partial(ring_attention, axis_name="sp"),
            mesh=mesh,
            in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp")),
            out_specs=P(None, "sp"))
        out = ring(q, k, v)
        kr = jnp.repeat(k, 2, axis=2)
        vr = jnp.repeat(v, 2, axis=2)
        ref = dense_ref(q, kr, vr)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


class TestUlysses:
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_dense(self, cpu_mesh8, causal):
        mesh = _sp_mesh(cpu_mesh8, 4)
        q, k, v = rand_qkv(jax.random.key(9), B=2, S=256, H=4, D=32)
        ul = shard_map(
            functools.partial(ulysses_attention, axis_name="sp",
                              causal=causal),
            mesh=mesh,
            in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp")),
            out_specs=P(None, "sp"))
        out = ul(q, k, v)
        ref = dense_ref(q, k, v, causal=causal)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_grads(self, cpu_mesh8):
        mesh = _sp_mesh(cpu_mesh8, 4)
        q, k, v = rand_qkv(jax.random.key(10), B=1, S=128, H=4, D=32)
        ul = shard_map(
            functools.partial(ulysses_attention, axis_name="sp"),
            mesh=mesh,
            in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp")),
            out_specs=P(None, "sp"))

        g1 = jax.grad(lambda *a: jnp.sum(ul(*a) ** 2),
                      argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(lambda *a: jnp.sum(dense_ref(*a) ** 2),
                      argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(a, b, atol=1e-3, rtol=1e-3)
