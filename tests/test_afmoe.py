"""The period stack (`arch="afmoe"`, models/periodic.py), the routed layer
(models/moe.py) and the window in flash attention, at a small size on the
CPU against the plain reference of benchmarks/references/afmoe_decoder.py.
"""

import dataclasses
import hashlib
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import configs, dense, moe, periodic
from ray_tpu.models.generate import (
    compute_prefix_kv,
    decode_multi,
    decode_step,
    first_token_sample,
    first_token_suffix_sample,
    init_kv_cache,
    prefill,
    prefill_sample_batch,
    prefill_suffix_batch,
)
from ray_tpu.models.transformer import (
    TransformerConfig,
    forward,
    init_params,
    loss_fn,
    param_logical_axes,
)
from ray_tpu.ops import flash_attention as fa_fn

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_reference():
    spec = importlib.util.spec_from_file_location(
        "afmoe_decoder_ref", os.path.join(
            ROOT, "benchmarks", "references", "afmoe_decoder.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load_reference()
CFG = configs.tiny_afmoe_test()
ARCH = dataclasses.asdict(CFG)


@pytest.fixture(scope="module")
def params():
    return jax.jit(lambda k: init_params(CFG, k))(jax.random.key(3))


def _rel(got, want):
    err = np.asarray(got, np.float32) - np.asarray(want, np.float32)
    return float(np.sqrt(np.mean(err * err) / np.mean(want * want)))


def test_the_preset_is_the_published_shape_in_small():
    assert CFG.head_dim == 32 != CFG.d_model // CFG.n_heads
    assert periodic.layer_plan(CFG) == [("dense_layers", (1,), False),
                                        ("periods", (1, 4), True)]
    assert periodic.step_kinds(CFG) == [
        ("window",), ("window", "window", "window", "global")]
    assert periodic.cache_layers(CFG) == {"window": 4, "global": 1}
    cache = jax.eval_shape(lambda: init_kv_cache(CFG, 3, 64))
    assert cache.k.shape == (1, 3, 64, 2, 32)
    assert cache.kw.shape == (4, 3, 8, 2, 32)      # a ring of the window
    assert configs.get("tiny_afmoe") == CFG
    assert periodic.routed_layers(CFG) == 4
    assert not dense.routed_layers(configs.tiny_test())


def test_head_dim_defaults_to_the_quotient():
    assert configs.tiny_test().head_dim == 16
    assert TransformerConfig(d_model=96, n_heads=4).head_dim == 24
    with pytest.raises(ValueError, match="whole periods"):
        dataclasses.replace(CFG, n_layers=4)
    with pytest.raises(ValueError, match="arch"):
        TransformerConfig(arch="other")


def test_num_params_counts_the_leaves(params):
    assert CFG.num_params() == sum(x.size for x in jax.tree.leaves(params))
    assert set(params) == {"embed", "lm_head", "final_norm", "dense_layers",
                           "periods"}
    assert params["periods"]["w_gate"].shape == (1, 4, 8, 64, 32)
    assert not np.any(np.asarray(params["periods"]["router_bias"]))


@pytest.mark.parametrize("lens", [[5, 20, 12], [40, 3, 9], [8, 9, 7]])
def test_prefill_then_decode_through_both_caches(params, lens):
    """Prompts shorter and longer than the window of 8 (a bucket longer
    than the ring: the gather keeps the last 8 positions), then 12
    decode steps, which wrap the ring: every logit against the
    reference's full forward over the same tokens."""
    rng = np.random.default_rng(sum(lens))
    slots = 4
    cache = init_kv_cache(CFG, slots, 64)
    seqs = [rng.integers(0, 256, size=n).tolist() for n in lens]
    got = [[] for _ in seqs]
    cur = np.zeros((slots,), np.int32)
    for i, seq in enumerate(seqs):
        b = next(b for b in (8, 16, 32, 64) if b >= len(seq))
        buf = np.zeros((1, b), np.int32)
        buf[0, :len(seq)] = seq
        cache, last = prefill(CFG, params, cache, jnp.asarray(buf),
                              jnp.asarray(len(seq), jnp.int32),
                              jnp.asarray(i, jnp.int32))
        got[i].append(np.asarray(last))
        cur[i] = int(np.argmax(last))
    full = [list(s) + [int(cur[i])] for i, s in enumerate(seqs)]
    for _ in range(12):
        cache, logits = decode_step(CFG, params, cache, jnp.asarray(cur))
        logits = np.asarray(logits)
        for i in range(len(seqs)):
            got[i].append(logits[i])
            cur[i] = int(np.argmax(logits[i]))
            full[i].append(int(cur[i]))
    assert list(np.asarray(cache.seq_lens)[:3]) == [n + 12 for n in lens]
    for i, seq in enumerate(seqs):
        want = np.asarray(ref.forward_logits(ARCH, params, full[i][:-1]))
        assert _rel(np.stack(got[i]), want[len(seq) - 1:]) < 1e-5


def test_tile_first_token_and_block_agree_with_the_reference(params):
    """The admission tile (rows dropped where the slot is out of range),
    the cache-free first token, and a fused decode block with its
    routing stats."""
    rng = np.random.default_rng(7)
    lens = [11, 3, 16]
    toks = np.zeros((4, 16), np.int32)
    for i, n in enumerate(lens):
        toks[i, :n] = rng.integers(0, 256, size=n)
    lengths = jnp.asarray(lens + [1], jnp.int32)
    slots = jnp.asarray([2, 0, 1, 3], jnp.int32)     # 3 = out of range
    temps = jnp.zeros((4,), jnp.float32)
    key = jax.random.key(0)
    cache = init_kv_cache(CFG, 3, 48)
    cache, first, _, _ = prefill_sample_batch(
        CFG, params, cache, jnp.asarray(toks), lengths, slots, 0, temps, key)
    free, _, _ = first_token_sample(CFG, params, jnp.asarray(toks), lengths,
                                    temps, 0, key)
    want = [int(np.argmax(np.asarray(ref.forward_logits(
        ARCH, params, toks[i, :n].tolist()))[-1]))
        for i, n in enumerate(lens)]
    assert list(np.asarray(first)[:3]) == want == list(np.asarray(free)[:3])
    assert list(np.asarray(cache.seq_lens)) == [3, 16, 11]

    cur = jnp.asarray([want[1], want[2], want[0]], jnp.int32)   # by slot
    cache, out, _, extras = decode_multi(CFG, params, cache, cur, temps[:3],
                                         4, 0, key)
    stats = extras.routing
    out = np.asarray(out)
    for slot, i in ((0, 1), (1, 2), (2, 0)):
        seq = toks[i, :lens[i]].tolist() + [want[i]] + out[:, slot].tolist()
        logits = np.asarray(ref.forward_logits(ARCH, params, seq[:-1]))
        assert list(np.argmax(logits[lens[i]:], -1)) == out[:, slot].tolist()
    hit, rows, fullest, taken = (int(x) for x in np.asarray(stats))
    # steps x layers x slots x top 2; no `live`: every slot's are taken
    assert rows == taken == 4 * 4 * 3 * 2
    assert 4 * 4 <= hit <= 4 * 4 * min(8, 3 * 2)
    assert 4 * 4 * 1 <= fullest <= 4 * 4 * 3


def test_the_program_chooses_the_references_experts(params):
    tokens = np.random.default_rng(1).integers(0, 256, size=21).tolist()
    ours = periodic.chosen_experts(CFG, params, tokens)
    theirs = ref.chosen_experts(ARCH, params, tokens)
    assert len(ours) == len(theirs) == 4
    for a, b in zip(ours, theirs):
        assert np.array_equal(np.sort(np.asarray(a), -1),
                              np.sort(np.asarray(b), -1))


# -- float32 activations against bf16 weights ---------------------------------

@pytest.mark.parametrize("shape", [(5, 64), (2, 3, 64)])
def test_two_bf16_terms_carry_a_float32_activation(shape):
    """`moe.dot` of a float32 x against bf16 weights: two bf16 terms in
    one product, within 2^-16 of the float64 answer where a bf16 operand
    is 2^-9 off; the terms themselves sum to x within 2^-17."""
    kx, kw = jax.random.split(jax.random.key(len(shape)))
    x = jax.random.normal(kx, shape, jnp.float32)
    w = jax.random.normal(kw, (64, 48)).astype(jnp.bfloat16)
    terms = moe.bf16_terms(x)
    assert terms.dtype == jnp.bfloat16 and terms.shape == (2,) + shape
    back = np.asarray(terms, np.float64).sum(0)
    assert np.max(np.abs(back - np.asarray(x)) / np.abs(np.asarray(x))) \
        < 2.0 ** -16
    want = np.asarray(x, np.float64) @ np.asarray(w, np.float64)
    got = moe.dot(x, w)
    assert got.dtype == jnp.float32 and got.shape == shape[:-1] + (48,)
    assert _rel(got, want) < 2.0 ** -16
    rounded = moe.dot(x.astype(jnp.bfloat16), w)
    assert 2.0 ** -11 < _rel(rounded, want) < 2.0 ** -7
    assert moe.dot(x, w.astype(jnp.float32)).dtype == jnp.float32


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_grouped_kernel_in_the_interpreter_is_ragged_dot(dtype):
    """`moe.grouped_dot` through megablox's kernel (what a TPU runs, here
    in the pallas interpreter) against `lax.ragged_dot` (what a CPU
    runs): groups with no row, a group over a row tile's edge, float32
    rows as two bf16 terms; and the shapes the kernel does not tile fall
    back."""
    G, D, N, R = 6, 128, 256, 256
    w = (jax.random.normal(jax.random.key(0), (G, D, N)) * 0.1) \
        .astype(jnp.bfloat16)
    a = jax.random.normal(jax.random.key(1), (R, D)).astype(dtype)
    groups = jnp.asarray([0, 100, 0, 56, 90, 10], jnp.int32)
    want = moe.grouped_dot(a, w, groups, kernel=False)
    got = moe.grouped_dot(a, w, groups, kernel="interpret")
    assert got.dtype == jnp.float32 and got.shape == (R, N)
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-6)
    exact = np.concatenate([
        np.asarray(a[s:e], np.float64) @ np.asarray(w[g], np.float64)
        for g, (s, e) in enumerate(zip(np.cumsum([0, 0, 100, 0, 56, 90]),
                                       np.cumsum([0, 100, 0, 56, 90, 10])))])
    assert _rel(got, exact) < (2.0 ** -16 if dtype == jnp.float32
                               else 2.0 ** -7)
    assert moe._gmm_tiling(512, 2048, 1024) == (128, 2048, 512)
    assert moe._gmm_tiling(131072, 1024, 2048) == (256, 1024, 1024)
    assert moe._gmm_tiling(24, 64, 32) is None       # the tiny presets
    odd = moe.grouped_dot(a[:24, :64], w[:, :64, :32],
                          jnp.asarray([4, 0, 8, 2, 10, 0], jnp.int32),
                          kernel="interpret")
    assert odd.shape == (24, 32)


@pytest.mark.parametrize("lens", [[5, 20, 12], [40, 3, 9]])
def test_float32_activations_on_bf16_weights_keep_the_references_experts(
        lens):
    """The benchmark's precision at a small size: bf16 weights, float32
    activations and cache. Prefill then decode against the reference on
    the same bf16 weights: only the head's product rounds (2^-9 an
    operand), and every routed layer chooses the reference's experts;
    with bf16 activations the same stack is ten times further off."""
    cfg = dataclasses.replace(CFG, param_dtype=jnp.bfloat16)
    arch = dataclasses.asdict(cfg)
    w = jax.jit(lambda k: init_params(cfg, k))(jax.random.key(5))
    assert w["periods"]["w_gate"].dtype == jnp.bfloat16
    rng = np.random.default_rng(sum(lens) + 1)
    seqs = [rng.integers(0, 256, size=n).tolist() for n in lens]

    def run(c):
        cache = init_kv_cache(c, 4, 64)
        if c.dtype == jnp.float32:      # two bf16 terms a row
            assert periodic.cache_terms(c) == 2
            assert cache.k.shape[0] == 2 and cache.kw.shape[0] == 8
        else:
            assert cache.k.shape[0] == 1 and cache.kw.shape[0] == 4
        assert cache.kw.dtype == cache.k.dtype == jnp.bfloat16
        got = [[] for _ in seqs]
        cur = np.zeros((4,), np.int32)
        for i, seq in enumerate(seqs):
            b = next(b for b in (8, 16, 32, 64) if b >= len(seq))
            buf = np.zeros((1, b), np.int32)
            buf[0, :len(seq)] = seq
            cache, last = prefill(c, w, cache, jnp.asarray(buf),
                                  jnp.asarray(len(seq), jnp.int32),
                                  jnp.asarray(i, jnp.int32))
            got[i].append(np.asarray(last))
            cur[i] = int(np.argmax(last))
        full = [list(s) + [int(cur[i])] for i, s in enumerate(seqs)]
        for _ in range(10):
            cache, logits = decode_step(c, w, cache, jnp.asarray(cur))
            for i in range(len(seqs)):
                got[i].append(np.asarray(logits[i]))
                cur[i] = int(np.argmax(got[i][-1]))
                full[i].append(int(cur[i]))
        return max(_rel(np.stack(got[i]), np.asarray(ref.forward_logits(
            arch, w, full[i][:-1]))[len(seq) - 1:])
            for i, seq in enumerate(seqs))

    exact = run(cfg)
    assert exact < 4e-3
    for a, b in zip(periodic.chosen_experts(cfg, w, seqs[1]),
                    ref.chosen_experts(arch, w, seqs[1])):
        assert np.array_equal(np.sort(np.asarray(a), -1),
                              np.sort(np.asarray(b), -1))
    assert run(dataclasses.replace(cfg, dtype=jnp.bfloat16)) > 3 * exact


# -- the routed layer ---------------------------------------------------------

def _routed_case(T, D=16, E=6, K=2, F=8, seed=0):
    ks = jax.random.split(jax.random.key(seed), 5)
    lp = {"router": jax.random.normal(ks[0], (D, E)) * 0.5,
          "router_bias": jnp.zeros((E,)),
          "w_gate": jax.random.normal(ks[1], (E, D, F)) * 0.3,
          "w_up": jax.random.normal(ks[2], (E, D, F)) * 0.3,
          "w_down": jax.random.normal(ks[3], (E, F, D)) * 0.3}
    m = jax.random.normal(ks[4], (T, D))
    cfg = TransformerConfig(d_model=D, n_heads=2, moe_experts=E, moe_top_k=K,
                            moe_d_ff=F, score_func="sigmoid",
                            route_scale=2.826, dtype=jnp.float32)
    return cfg, lp, m


def _loop(lp, m, weights, experts):
    """The reference's way: every expert on every token, weighted by a
    matrix that is zero where the token did not choose it."""
    out = np.zeros(m.shape, np.float32)
    for e in range(lp["w_gate"].shape[0]):
        y = (jax.nn.silu(m @ lp["w_gate"][e]) * (m @ lp["w_up"][e])) \
            @ lp["w_down"][e]
        w = jnp.sum(jnp.where(experts == e, weights, 0.0), -1, keepdims=True)
        out += np.asarray(w * y)
    return out


@pytest.mark.parametrize("case", ["spread", "one_expert_takes_every_row",
                                  "softmax"])
def test_routed_layer_against_the_loop(case):
    cfg, lp, m = _routed_case(T=9)
    if case == "one_expert_takes_every_row":
        # Experts 4 and 1 win everywhere: 4 holds every row, 1 too, and
        # four experts hold none.
        lp["router_bias"] = jnp.asarray([0., 5., 0., 0., 9., 0.])
    if case == "softmax":
        cfg = dataclasses.replace(cfg, score_func="softmax",
                                  route_scale=1.0)
    with jax.default_matmul_precision("highest"):
        out, stats, experts = moe.routed_ffn(cfg, lp, m, jnp.float32)
        weights, again = moe.route(cfg, lp, m)
        want = _loop(lp, m, weights, experts)
    assert np.array_equal(np.asarray(experts), np.asarray(again))
    np.testing.assert_allclose(np.asarray(out), want, rtol=2e-5, atol=2e-6)
    sizes = np.bincount(np.asarray(experts).ravel(), minlength=6)
    assert list(np.asarray(stats)) == [int((sizes > 0).sum()), 18,
                                       int(sizes.max()), 18]
    if case == "one_expert_takes_every_row":
        assert list(sizes) == [0, 9, 0, 0, 9, 0]
        # The bias chooses; the weights are the scores without it.
        np.testing.assert_allclose(np.asarray(weights).sum(-1), 2.826,
                                   rtol=1e-5)
    if case == "softmax":
        np.testing.assert_allclose(np.asarray(weights).sum(-1), 1.0,
                                   rtol=1e-5)


def test_a_layers_experts_inside_a_stack_are_not_sliced_out():
    """`grouped_experts` on all layers' experts as one array, told where
    this layer's begin: the same as on the layer's own."""
    cfg, lp, m = _routed_case(T=7, seed=3)
    _, other, _ = _routed_case(T=7, seed=4)
    stack = {k: jnp.concatenate([other[k], lp[k], other[k]])
             for k in moe.EXPERT_LEAVES}
    alone, _, _ = moe.routed_ffn(cfg, lp, m, jnp.float32)
    inside, _, _ = moe.routed_ffn(cfg, lp, m, jnp.float32, stack,
                                  jnp.int32(6))
    np.testing.assert_allclose(np.asarray(inside), np.asarray(alone),
                               rtol=1e-6, atol=1e-7)


def test_ties_go_to_the_lower_index():
    cfg, lp, m = _routed_case(T=3)
    lp["router"] = jnp.zeros_like(lp["router"])     # every score 0.5
    _, experts = moe.route(cfg, lp, m)
    assert np.asarray(experts).tolist() == [[0, 1]] * 3


# -- what stays as it was -----------------------------------------------------

def _digest(*arrays):
    h = hashlib.sha256()
    for a in arrays:
        h.update(np.ascontiguousarray(np.asarray(a)).tobytes())
    return h.hexdigest()[:16]


def _serve_outputs(cfg):
    """Prefill a tile, decode a fused block, sample a cache-free first
    token: the bytes of everything the programs return."""
    params = init_params(cfg, jax.random.key(11))
    toks = jnp.asarray(np.random.default_rng(5).integers(
        0, 256, size=(2, 16)), jnp.int32)
    lengths = jnp.asarray([16, 9], jnp.int32)
    temps = jnp.zeros((2,), jnp.float32)
    key = jax.random.key(1)
    cache = init_kv_cache(cfg, 2, 32)
    cache, first, _, _ = prefill_sample_batch(
        cfg, params, cache, toks, lengths, jnp.asarray([0, 1], jnp.int32),
        0, temps, key)
    cache, block, _, _ = decode_multi(cfg, params, cache, first, temps, 4, 0,
                                      key)
    cache, logits = decode_step(cfg, params, cache, block[-1])
    free, _, _ = first_token_sample(cfg, params, toks, lengths, temps, 0, key)
    return first, block, logits, free, cache.k, cache.v


# The same calls on the parent commit (58e87d5), this machine, jax 0.9.0.
BEFORE = {"tiny": "f3d5485f3b60864c"}


def test_the_dense_configuration_is_bit_equal_to_before():
    assert _digest(*_serve_outputs(configs.tiny_test())) == BEFORE["tiny"]


def test_tiny_moe_serves_what_it_served_where_nothing_was_dropped():
    """`generate._ffn` routes `tiny_moe` through models/moe.py now: the
    same softmax-renormalised routing, nothing dropped. With room for
    every token in every expert the capacity-bounded layer it replaced
    (`transformer.moe_ffn`, still the training path) computes the same."""
    cfg = dataclasses.replace(configs.tiny_moe_test(),
                              moe_capacity_factor=2.0)
    params = init_params(cfg, jax.random.key(11))
    toks = jnp.asarray(np.random.default_rng(5).integers(
        0, 256, size=(2, 16)), jnp.int32)
    cache = init_kv_cache(cfg, 2, 32)
    got = []
    for i in range(2):
        cache, last = prefill(cfg, params, cache, toks[i:i + 1],
                              jnp.asarray(16, jnp.int32),
                              jnp.asarray(i, jnp.int32))
        got.append(np.asarray(last))
    want, _ = forward(cfg, params, toks)          # moe_ffn, nothing over
    np.testing.assert_allclose(np.stack(got), np.asarray(want)[:, -1],
                               rtol=2e-4, atol=2e-5)
    cache, nxt = decode_step(cfg, params, cache,
                             jnp.argmax(jnp.stack(got), -1).astype(jnp.int32))
    assert np.all(np.isfinite(np.asarray(nxt)))


# -- what raises --------------------------------------------------------------

def test_training_and_sharding_raise_for_afmoe(params):
    toks = jnp.zeros((1, 8), jnp.int32)
    with pytest.raises(NotImplementedError, match="served only"):
        forward(CFG, params, toks)
    with pytest.raises(NotImplementedError, match="served only"):
        loss_fn(CFG, params, toks, toks)
    with pytest.raises(NotImplementedError, match="sharding"):
        param_logical_axes(CFG)


def test_prefix_sharing_raises_for_a_windowed_cache(params):
    pk = jnp.zeros((5, 4, 2, 32), jnp.float32)
    toks = jnp.zeros((2, 8), jnp.int32)
    two = jnp.asarray([3, 4], jnp.int32)
    temps = jnp.zeros((2,), jnp.float32)
    key = jax.random.key(0)
    with pytest.raises(NotImplementedError, match="windowed cache"):
        compute_prefix_kv(CFG, params, [1, 2, 3, 4])
    with pytest.raises(NotImplementedError, match="windowed cache"):
        first_token_suffix_sample(CFG, params, pk, pk, toks, two, temps, 0,
                                  key)
    with pytest.raises(NotImplementedError, match="windowed cache"):
        prefill_suffix_batch(CFG, params, init_kv_cache(CFG, 2, 32), pk, pk,
                             toks, two, jnp.asarray([0, 1], jnp.int32), 0,
                             temps, key)


# -- the window in flash attention --------------------------------------------

def _qkv(sq, skv, h=4, kvh=2, d=32, seed=0):
    ks = jax.random.split(jax.random.key(seed), 3)
    return (jax.random.normal(ks[0], (2, sq, h, d)),
            jax.random.normal(ks[1], (2, skv, kvh, d)),
            jax.random.normal(ks[2], (2, skv, kvh, d)))


def _plain(q, k, v, window, q_offset=0):
    rep = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    i = q_offset + jnp.arange(q.shape[1])[:, None]
    j = jnp.arange(k.shape[1])[None, :]
    s = jnp.where((j <= i) & (i - j < window), s, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)


@pytest.mark.parametrize("window", [1, 24, 64, 500])
def test_windowed_reference_is_the_plain_mask(window):
    q, k, v = _qkv(64, 64)
    got = fa_fn(q, k, v, causal=True, window=window, force_reference=True)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(_plain(q, k, v, window)),
                               rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("window,q_offset", [(24, 0), (40, 0), (130, 0),
                                             (24, 64)])
def test_windowed_kernel_in_the_interpreter_against_the_reference(
        window, q_offset):
    """Blocks of 16 x 32 over 128 positions: windows inside one kv
    block, across blocks (whole blocks behind the window are skipped),
    and wider than the sequence; and queries that start at an offset."""
    sq = 128 - q_offset
    q, k, v = _qkv(sq, 128, seed=window)
    kw = dict(causal=True, window=window, q_offset=q_offset, block_q=16,
              block_k=32)
    got = fa_fn(q, k, v, interpret=True, **kw)
    want = fa_fn(q, k, v, force_reference=True, **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(
        np.asarray(want), np.asarray(_plain(q, k, v, window, q_offset)),
        rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("window,q_offset,kv_offset", [
    (33, 0, 0), (64, 0, 0), (100, 0, 0), (511, 0, 0), (64, 160, 0),
    (64, 96, 32)])
def test_windowed_kernel_walks_the_blocks_its_window_reaches(
        window, q_offset, kv_offset):
    """512 keys in blocks of 32 x 64. With offsets the trace cannot see,
    the grid's kv axis is as long as the blocks a q block's window can
    reach (`window` - 1 + 32 keys: 3, 3, 4 of the 8, all 8 for a window
    of 511), wherever the offsets put the window's first block, and a
    step past kv's end adds nothing. With offsets it can see, the grid is
    the blocks that hold a live pair and no other."""
    sq = 512 - q_offset
    q, k, v = _qkv(sq, 512, seed=window + q_offset)
    kw = dict(causal=True, window=window, block_q=32, block_k=64)
    want = fa_fn(q, k, v, force_reference=True, q_offset=q_offset,
                 kv_offset=kv_offset, **kw)
    live = sum(
        any(0 <= (q_offset + i) - (kv_offset + j) < window
            for i in range(qi * 32, qi * 32 + 32)
            for j in (ki * 64, ki * 64 + 63))
        for qi in range(sq // 32) for ki in range(8))
    for offsets, grid in (
            (dict(q_offset=jnp.int32(q_offset),
                  kv_offset=jnp.int32(kv_offset)),
             (2, 4, sq // 32, min(8, (window + 29) // 64 + 2))),
            (dict(q_offset=q_offset, kv_offset=kv_offset), (2, 4, live))):
        got = fa_fn(q, k, v, interpret=True, **offsets, **kw)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-6)
        jaxpr = jax.make_jaxpr(lambda q, k, v: fa_fn(
            q, k, v, interpret=True, **offsets, **kw))(q, k, v)
        grids = [e.params["grid_mapping"].grid for e in _eqns(jaxpr.jaxpr)
                 if e.primitive.name == "pallas_call"]
        assert grids == [grid]


def _eqns(jaxpr):
    for e in jaxpr.eqns:
        yield e
        for sub in jax.core.jaxprs_in_params(e.params):
            yield from _eqns(sub)


def test_no_window_is_the_call_it_was():
    q, k, v = _qkv(32, 32)
    np.testing.assert_array_equal(
        np.asarray(fa_fn(q, k, v, causal=True, force_reference=True)),
        np.asarray(fa_fn(q, k, v, causal=True, window=None,
                         force_reference=True)))


def test_a_window_has_no_backward_and_needs_a_causal_mask():
    q, k, v = _qkv(16, 16)
    with pytest.raises(NotImplementedError, match="backward"):
        jax.grad(lambda q: fa_fn(q, k, v, causal=True, window=4,
                                 force_reference=True).sum())(q)
    with pytest.raises(ValueError, match="causal"):
        fa_fn(q, k, v, causal=False, window=4)
    with pytest.raises(ValueError, match="window"):
        fa_fn(q, k, v, causal=True, window=0)
