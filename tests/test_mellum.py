"""The period stack's second layer (`arch="mellum"`: JetBrains Mellum 2) at
a small size on the CPU against the plain reference of
benchmarks/references/mellum_decoder.py: the walk and the two-kind cache
it shares with `afmoe`, a rotary table a kind of layer (YaRN on the
global ones), softmax routing, the windowed float32 prefill attention,
and the grouped products' tiles at its widths (2304 x 896).
"""

import dataclasses
import importlib.util
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import configs, moe, periodic
from ray_tpu.models.generate import (
    decode_multi,
    decode_step,
    first_token_sample,
    init_kv_cache,
    prefill,
    prefill_sample_batch,
)
from ray_tpu.models.transformer import (
    PERIOD_FORMS,
    STACKS,
    TransformerConfig,
    forward,
    init_params,
    rope_tables,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_reference():
    spec = importlib.util.spec_from_file_location(
        "mellum_decoder_ref", os.path.join(
            ROOT, "benchmarks", "references", "mellum_decoder.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load_reference()
CFG = configs.tiny_mellum_test()
ARCH = dataclasses.asdict(CFG)
# The published rotary sections (the catalog row's `rope_parameters`).
PUBLISHED_ROPE = {
    "full_attention": {
        "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
        "original_max_position_embeddings": 8192, "beta_fast": 32,
        "beta_slow": 1, "attention_factor": 1.2772588722239782},
    "sliding_attention": {"rope_type": "default", "rope_theta": 500000}}


@pytest.fixture(scope="module")
def params():
    return jax.jit(lambda k: init_params(CFG, k))(jax.random.key(3))


def _rel(got, want):
    err = np.asarray(got, np.float32) - np.asarray(want, np.float32)
    return float(np.sqrt(np.mean(err * err) / np.mean(want * want)))


def test_the_preset_is_the_published_shape_in_small(params):
    assert STACKS["mellum"] == STACKS["afmoe"] == "periodic"
    assert CFG.period_form == PERIOD_FORMS["mellum"]
    assert periodic.layer_plan(CFG) == [("periods", (2, 4), True)]
    assert periodic.step_kinds(CFG) == [
        ("window", "window", "window", "global")]
    assert periodic.cache_layers(CFG) == {"window": 6, "global": 2}
    assert periodic.routed_layers(CFG) == 8
    cache = jax.eval_shape(lambda: init_kv_cache(CFG, 3, 64))
    assert cache.k.shape == (2, 3, 64, 2, 32)
    assert cache.kw.shape == (6, 3, 8, 2, 32)      # a ring of the window
    assert configs.get("tiny_mellum") == CFG and hash(CFG) == hash(
        configs.tiny_mellum_test())
    # Two norms a layer, no gate, no selection bias, no shared expert.
    assert set(params["periods"]) == {
        "attn_norm", "wq", "wk", "wv", "wo", "q_norm", "k_norm", "ffn_norm",
        "router", "w_gate", "w_up", "w_down"}
    assert set(params) == {"embed", "lm_head", "final_norm", "periods"}
    assert params["periods"]["w_gate"].shape == (2, 4, 8, 64, 32)
    assert CFG.num_params() == sum(x.size for x in jax.tree.leaves(params))
    with pytest.raises(ValueError, match="whole periods"):
        dataclasses.replace(CFG, n_layers=6)
    with pytest.raises(NotImplementedError, match="served only"):
        forward(CFG, params, jnp.zeros((1, 8), jnp.int32))


# -- the rotary tables --------------------------------------------------------

def _yarn_by_the_formula(positions, rope, D=128):
    """ISSUE 32's formula, transcribed in float64."""
    theta, factor = rope["rope_theta"], rope["factor"]
    L0 = rope["original_max_position_embeddings"]

    def dim(r):
        return D * math.log(L0 / (2 * math.pi * r)) / (2 * math.log(theta))

    low = max(math.floor(dim(rope["beta_fast"])), 0)
    high = min(math.ceil(dim(rope["beta_slow"])), D - 1)
    i = np.arange(D // 2, dtype=np.float64)
    ramp = np.clip((i - low) / (high - low), 0.0, 1.0)
    plain = theta ** (-2.0 * i / D)
    inv = (1.0 - ramp) * plain + ramp * plain / factor
    ang = np.asarray(positions, np.float64)[:, None] * inv[None, :]
    a = rope["attention_factor"]
    return np.sin(ang) * a, np.cos(ang) * a, inv, (low, high)


def test_the_yarn_table_is_the_formula_and_the_sliding_one_is_plain():
    cfg = TransformerConfig(d_model=2304, n_heads=32, n_kv_heads=4,
                            head_dim=128, arch="mellum", n_layers=4,
                            global_attn_every=4,
                            rope_parameters=PUBLISHED_ROPE)
    assert cfg.rope_section("full_attention")["factor"] == 16
    assert cfg.rope_section(None)["rope_theta"] == cfg.rope_theta
    positions = [0, 1, 8191, 8192, 131071]
    sin, cos = (np.asarray(t)[positions] for t in rope_tables(
        cfg, 131072, "full_attention"))
    want_sin, want_cos, inv, (low, high) = _yarn_by_the_formula(
        positions, PUBLISHED_ROPE["full_attention"])
    assert (low, high) == (18, 35)
    # Pairs that turn often keep their frequency, slow ones lose 16x.
    plain = 500000.0 ** (-2.0 * np.arange(64) / 128)
    assert np.allclose(inv[:19], plain[:19]) \
        and np.allclose(inv[35:], plain[35:] / 16)
    # Position 1 pins every frequency to float32's rounding; further out
    # a frequency's 2^-24 is multiplied by the position (131071 x 2^-23
    # of a radian), times the factor on sin and cos.
    for p, row in enumerate(positions):
        tol = 1.3 * (2e-7 + row * 2.0 ** -23)
        assert np.max(np.abs(sin[p] - want_sin[p])) <= tol, row
        assert np.max(np.abs(cos[p] - want_cos[p])) <= tol, row
    assert np.allclose(cos[0], 1.2772588722239782) and not np.any(sin[0])
    # The sliding section: theta 5e5, nothing scaled, the table a
    # configuration without sections makes from `rope_theta`.
    plain_cfg = dataclasses.replace(cfg, rope_parameters=None,
                                    rope_theta=500000.0)
    for a, b in zip(rope_tables(cfg, 9000, "sliding_attention"),
                    rope_tables(plain_cfg, 9000)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    with pytest.raises(ValueError, match="rope_type"):
        rope_tables(dataclasses.replace(cfg, rope_parameters={
            "full_attention": {"rope_type": "ntk", "rope_theta": 1e4}}),
            8, "full_attention")


def test_each_kind_of_layer_rotates_with_its_own_table():
    by_kind = periodic.rope_by_kind(CFG, 64)
    assert set(by_kind) == {"window", "global"}
    assert not np.allclose(by_kind["window"][1], by_kind["global"][1])
    at = periodic.rope_by_kind(CFG, 64, jnp.asarray([5, 40]))
    assert at["global"][0].shape == (2, 1, 16)
    assert np.array_equal(at["global"][0][1, 0], by_kind["global"][0][40])
    # Trinity's layer: window layers alone, from `rope_theta`.
    assert set(periodic.rope_by_kind(configs.tiny_afmoe_test(), 8)) == {
        "window"}


# -- routing ------------------------------------------------------------------

def test_softmax_routing_renormalises_to_one_and_breaks_ties_low():
    D, E, K = 8, 6, 3
    cfg = TransformerConfig(d_model=D, n_heads=2, moe_experts=E,
                            moe_top_k=K, moe_d_ff=4, score_func="softmax",
                            dtype=jnp.float32)
    router = np.zeros((D, E), np.float32)
    router[0] = [1.0, 3.0, 3.0, 0.5, 3.0, 3.0]      # four experts tie
    m = np.zeros((2, D), np.float32)
    m[0, 0], m[1, 0] = 1.0, -1.0                     # and, negated, two
    weights, experts = moe.route(cfg, {"router": jnp.asarray(router)},
                                 jnp.asarray(m))
    assert np.asarray(experts).tolist() == [[1, 2, 4], [3, 0, 1]]
    assert np.allclose(np.sum(np.asarray(weights), -1), 1.0, atol=1e-6)
    assert np.allclose(np.asarray(weights)[0], 1 / 3, atol=1e-6)
    # The reference's stable sort does the same.
    ref_w, ref_e = ref._route(jnp.asarray(m), jnp.asarray(router), K)
    assert np.asarray(ref_e).tolist() == [[1, 2, 4], [3, 0, 1]]
    assert np.allclose(np.sum(np.asarray(ref_w), -1), 1.0, atol=1e-6)


def test_the_program_chooses_the_references_experts(params):
    tokens = np.random.default_rng(1).integers(0, 256, size=37).tolist()
    ours = periodic.chosen_experts(CFG, params, tokens)
    theirs = ref.chosen_experts(ARCH, params, tokens)
    assert len(ours) == len(theirs) == 8
    for a, b in zip(ours, theirs):
        assert a.shape == (37, 2)
        assert np.array_equal(np.sort(np.asarray(a), -1),
                              np.sort(np.asarray(b), -1))


# -- prefill, then decode through both caches ---------------------------------

def _serve(cfg, w, seqs, steps):
    """Each sequence prefilled into its slot, then `steps` decode steps
    of all: the logits of every position served, and the sequences as
    they grew."""
    cache = init_kv_cache(cfg, 4, 64)
    got = [[] for _ in seqs]
    cur = np.zeros((4,), np.int32)
    for i, seq in enumerate(seqs):
        b = next(b for b in (8, 16, 32, 64) if b >= len(seq))
        buf = np.zeros((1, b), np.int32)
        buf[0, :len(seq)] = seq
        cache, last = prefill(cfg, w, cache, jnp.asarray(buf),
                              jnp.asarray(len(seq), jnp.int32),
                              jnp.asarray(i, jnp.int32))
        got[i].append(np.asarray(last))
        cur[i] = int(np.argmax(last))
    full = [list(s) + [int(cur[i])] for i, s in enumerate(seqs)]
    for _ in range(steps):
        cache, logits = decode_step(cfg, w, cache, jnp.asarray(cur))
        for i in range(len(seqs)):
            got[i].append(np.asarray(logits[i]))
            cur[i] = int(np.argmax(got[i][-1]))
            full[i].append(int(cur[i]))
    return got, full, cache


@pytest.mark.parametrize("lens", [[5, 20, 12], [40, 3, 9], [8, 9, 7]])
def test_prefill_then_decode_through_both_caches(params, lens):
    """Prompts shorter than the window of 8 and up to five windows long
    (a bucket longer than the ring: the gather keeps the last 8
    positions), then 12 decode steps, which carry every ring over its
    edge: every logit against the reference's full forward over the same
    tokens. float32 on both sides: what is left is the order of sums."""
    rng = np.random.default_rng(sum(lens))
    seqs = [rng.integers(0, 256, size=n).tolist() for n in lens]
    got, full, cache = _serve(CFG, params, seqs, 12)
    assert list(np.asarray(cache.seq_lens)[:3]) == [n + 12 for n in lens]
    for i, seq in enumerate(seqs):
        want = np.asarray(ref.forward_logits(ARCH, params, full[i][:-1]))
        assert _rel(np.stack(got[i]), want[len(seq) - 1:]) < 1e-5


@pytest.mark.parametrize("lens", [[5, 20, 12], [40, 3, 9]])
def test_bf16_weights_under_float32_and_bf16_activations(lens):
    """The benchmark's precision at a small size: bf16 weights, float32
    activations and a cache of two bf16 terms a row. Against the
    reference on the same bf16 weights only the head's product rounds
    (2^-9 an operand: under 4e-3 of the logits' rms) and every layer
    chooses the reference's experts (readings 0.0016-0.0018). With bf16
    activations every product and the cache round to 2^-9 (readings
    0.0074-0.0082 at these eight layers of width 64): several times
    further off, and under 0.02 while no expert flips (a flip moves a
    token's logits by a tenth of their size)."""
    cfg = dataclasses.replace(CFG, param_dtype=jnp.bfloat16)
    arch = dataclasses.asdict(cfg)
    w = jax.jit(lambda k: init_params(cfg, k))(jax.random.key(5))
    rng = np.random.default_rng(sum(lens) + 1)
    seqs = [rng.integers(0, 256, size=n).tolist() for n in lens]

    def worst(c):
        got, full, cache = _serve(c, w, seqs, 10)
        terms = periodic.cache_terms(c)
        assert cache.k.shape[0] == 2 * terms and cache.kw.shape[0] == 6 * terms
        assert cache.kw.dtype == cache.k.dtype == jnp.bfloat16
        return max(_rel(np.stack(got[i]), np.asarray(ref.forward_logits(
            arch, w, full[i][:-1]))[len(seq) - 1:])
            for i, seq in enumerate(seqs))

    exact = worst(cfg)
    assert exact < 4e-3
    for a, b in zip(periodic.chosen_experts(cfg, w, seqs[0]),
                    ref.chosen_experts(arch, w, seqs[0])):
        assert np.array_equal(np.sort(np.asarray(a), -1),
                              np.sort(np.asarray(b), -1))
    rounded = worst(dataclasses.replace(cfg, dtype=jnp.bfloat16))
    assert 3 * exact < rounded < 0.02


def test_tile_first_token_and_block_agree_and_count_their_routing(params):
    """The admission tile with its routing stats (every position of the
    tile, padding too), the cache-free first token, and a fused decode
    block with its own."""
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
    cache, first, _, extras = prefill_sample_batch(
        CFG, params, cache, jnp.asarray(toks), lengths, slots, 0, temps, key)
    tile = extras.routing
    free, _, _ = first_token_sample(CFG, params, jnp.asarray(toks), lengths,
                                    temps, 0, key)
    want = [int(np.argmax(np.asarray(ref.forward_logits(
        ARCH, params, toks[i, :n].tolist()))[-1]))
        for i, n in enumerate(lens)]
    assert list(np.asarray(first)[:3]) == want == list(np.asarray(free)[:3])
    hit, rows, fullest, taken = (int(x) for x in np.asarray(tile))
    # layers x positions x top 2; a tile's padding is taken too
    assert rows == taken == 8 * 4 * 16 * 2
    assert 8 * 2 <= hit <= 8 * 8 and 8 * 16 <= fullest <= 8 * 64

    cur = jnp.asarray([want[1], want[2], want[0]], jnp.int32)   # by slot
    cache, out, _, extras = decode_multi(CFG, params, cache, cur, temps[:3],
                                         4, 0, key)
    stats = extras.routing
    out = np.asarray(out)
    for slot, i in ((0, 1), (1, 2), (2, 0)):
        seq = toks[i, :lens[i]].tolist() + [want[i]] + out[:, slot].tolist()
        logits = np.asarray(ref.forward_logits(ARCH, params, seq[:-1]))
        assert list(np.argmax(logits[lens[i]:], -1)) == out[:, slot].tolist()
    assert int(stats[1]) == 4 * 8 * 3 * 2   # steps x layers x slots x top 2


def test_the_engine_counts_what_its_tiles_routed(params):
    from ray_tpu.serve.llm import LLMEngine

    engine = LLMEngine(CFG, params, num_slots=2, max_seq_len=64,
                       decode_block=4)
    reqs = [engine.submit(list(range(1, n)), max_new_tokens=3)
            for n in (20, 10)]
    while any(r.finish_ts == 0.0 for r in reqs):
        engine.step()
    c = engine.stats()["counts"]
    # A tile of the 32 bucket is 8 rows, of the 16 bucket 8 too.
    assert c["prefill_moe_rows"] == 8 * 2 * (8 * 32 + 8 * 16)
    assert 0 < c["prefill_moe_experts_hit"] <= 2 * 8 * 8
    assert c["prefill_moe_rows_max"] * 8 >= c["prefill_moe_rows"]
    assert c["moe_rows"] > 0 and not engine._tile_moe


# -- the windowed float32 prefill attention -----------------------------------

def _all_keys_then_mask(q, k, v, window):
    """What `_attention_f32` did before a window cut its keys."""
    G = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, G, axis=2), jnp.repeat(v, G, axis=2)
    hi = jax.lax.Precision.HIGHEST
    s = jnp.einsum("bqhd,bshd->bhqs", q, k, precision=hi) \
        / math.sqrt(q.shape[-1])
    i = jnp.arange(q.shape[1])[:, None]
    j = jnp.arange(q.shape[1])[None, :]
    seen = (j <= i) & (i - j < window) if window else j <= i
    p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqs,bshd->bqhd", p, v, precision=hi)


@pytest.mark.parametrize("S,window", [(1024, 200), (1024, 256), (1536, 300),
                                      (1024, 900), (512, 0), (24, 8)])
def test_windowed_float32_prefill_is_all_keys_then_a_mask(S, window):
    """A block of 256 queries against the keys its window can reach
    (`window` + a block, in whole blocks, where that is fewer than S)
    equals every key and then the mask: a window inside one block, one
    on a block's edge, one over two, one that reaches nearly all S (no
    slice), no window, and a length the blocks do not divide."""
    ks = jax.random.split(jax.random.key(S + window), 3)
    q = jax.random.normal(ks[0], (1, S, 4, 32), jnp.float32)
    k = jax.random.normal(ks[1], (1, S, 2, 32), jnp.float32)
    v = jax.random.normal(ks[2], (1, S, 2, 32), jnp.float32)
    got = jax.jit(periodic._attention_f32, static_argnums=3)(q, k, v, window)
    np.testing.assert_allclose(got, _all_keys_then_mask(q, k, v, window),
                               rtol=0, atol=2e-6)


# -- the grouped products' tiles ----------------------------------------------

TRINITY, MELLUM = (2048, 1024), (2304, 896)


@pytest.mark.parametrize("rows,k,n,want", [
    (512, *TRINITY, (128, 2048, 512)),          # trinity's decode: as it was
    (512, *TRINITY[::-1], (128, 1024, 1024)),
    (131072, *TRINITY, (256, 2048, 512)),       # and its admission tile
    (131072, *TRINITY[::-1], (256, 1024, 1024)),
    (131072, *MELLUM, (256, 2304, 896)),        # mellum's tile: 896 = 7 x 128
    (131072, *MELLUM[::-1], (256, 896, 2304)),  # measured: all of n
    (128, *MELLUM, (128, 2304, 896)),           # a lone caller's 64 rows,
    (128, *MELLUM[::-1], (128, 896, 2304)),     # padded to a row tile
])
def test_gmm_tiles_are_multiples_of_128_that_divide(rows, k, n, want):
    tm, tk, tn = moe._gmm_tiling(rows, k, n)
    assert (tm, tk, tn) == want
    assert tm % 128 == tk % 128 == tn % 128 == 0
    assert rows % tm == 0 and k % tk == 0 and n % tn == 0
    # Two buffers of an expert's slab and of a row tile, two of the
    # output tile and its accumulator, inside the 16 MB a kernel may use
    # (the largest, 256 x 896 x 2304, ran on the chip: PR 32).
    vmem = 2 * 2 * (tk * tn + tm * tk) + 3 * 4 * tm * tn
    assert vmem <= 16 * 2 ** 20


@pytest.mark.parametrize("rows,k,n", [(64, 256, 128), (64, 128, 384),
                                      (200, 256, 128), (256, 256, 128)])
def test_the_interpreted_kernel_is_ragged_dot_at_any_row_count(rows, k, n):
    """Fewer rows than a row tile (a lone caller's decode), a count no
    tile divides, and a whole one: through megablox's kernel in the
    pallas interpreter (rows padded into no group) against
    `lax.ragged_dot`, float32 rows as two bf16 terms."""
    G = 5
    w = (jax.random.normal(jax.random.key(0), (G, k, n)) * 0.1) \
        .astype(jnp.bfloat16)
    a = jax.random.normal(jax.random.key(1), (rows, k), jnp.float32)
    sizes = [rows // 4, 0, rows // 2, 3, 0]
    sizes[-1] = rows - sum(sizes)
    groups = jnp.asarray(sizes, jnp.int32)
    assert moe._gmm_tiling(rows + -rows % 128, k, n) is not None
    want = moe.grouped_dot(a, w, groups, kernel=False)
    got = moe.grouped_dot(a, w, groups, kernel="interpret")
    assert got.shape == (rows, n) and got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-6)
    half = moe.grouped_dot(a.astype(jnp.bfloat16), w, groups,
                           kernel="interpret")
    np.testing.assert_allclose(
        half, moe.grouped_dot(a.astype(jnp.bfloat16), w, groups,
                              kernel=False), rtol=0, atol=2e-6)
