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

from ray_tpu.ops import flash_attention as _flash_attention
from ray_tpu.ops import ring_attention, ulysses_attention

# Off the TPU flash_attention takes the reference by default; these are
# tests of the kernels, so they ask for the Pallas interpreter.
flash_attention = functools.partial(_flash_attention, interpret=True)


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
