"""The period stack with linear-attention layers (`arch="solar_open2"`:
Solar Open 2) at a small size on the CPU against the plain reference of
benchmarks/references/solar_kda_decoder.py: the chunked scan of
`ops/delta_rule` against the recurrence a token at a time, prefill and
decode through a cache that keeps a recurrent state beside keys and
values, what a tile's padding, a dropped row and a slot nobody owns leave
of a state, a slot's second request, and the share of the experts held.
Logits, never sampled tokens.
"""

import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from ray_tpu.models import configs, moe, periodic
from ray_tpu.models.generate import (
    decode_multi,
    decode_step,
    first_token_sample,
    init_kv_cache,
    prefill,
    prefill_sample_batch,
)
from ray_tpu.models.stackparts import _swiglu
from ray_tpu.models.transformer import PERIOD_FORMS, STACKS, init_params
from ray_tpu.ops import delta_rule

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_reference():
    spec = importlib.util.spec_from_file_location(
        "solar_kda_decoder_ref", os.path.join(
            ROOT, "benchmarks", "references", "solar_kda_decoder.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load_reference()
CFG = configs.tiny_solar_test()
ARCH = dataclasses.asdict(CFG)


@pytest.fixture(scope="module")
def params():
    return jax.jit(lambda k: init_params(CFG, k))(jax.random.key(3))


def _prompt(n, seed=1):
    return np.asarray(jax.random.randint(
        jax.random.key(seed), (n,), 0, CFG.vocab_size))


# -- the shape ----------------------------------------------------------------

def test_the_preset_is_the_published_shape_in_small(params):
    assert STACKS["solar_open2"] == "periodic"
    form = PERIOD_FORMS["solar_open2"]
    assert CFG.period_form == form and form.recurrent == "linear" \
        and form.global_at == 0 and not form.qk_norm and form.rotary == () and form.attn_gate
    assert periodic.layer_plan(CFG) == [("periods", (2, 4), True)]
    assert periodic.step_kinds(CFG) == [
        ("global", "linear", "linear", "linear")]
    assert periodic.cache_layers(CFG) == {"window": 0, "global": 2,
                                          "linear": 6}
    assert [kind for *_, kind, _ in ref.layer_table(ARCH)] == \
        list(periodic.step_kinds(CFG)[0]) * 2
    per = params["periods"]
    assert sorted(k for k, v in per.items() if isinstance(v, dict)) == [
        "global0", "linear0", "linear1", "linear2"]
    assert per["global0"]["wq"].shape == (2, 64, 64)
    assert per["linear2"]["wq"].shape == (2, 64, 32)
    assert "q_norm" not in per["global0"] and "wq" not in per
    # The decays a seed draws are a layer's published initialisation.
    A = np.exp(np.asarray(per["linear1"]["A_log"]))
    dt = np.asarray(jax.nn.softplus(per["linear1"]["dt_bias"]))
    assert 1.0 <= A.min() and A.max() <= 16.0
    assert 0.001 <= dt.min() * 1.001 and dt.max() <= 0.1 * 1.001
    cache = init_kv_cache(CFG, 3, 32)
    assert (cache.s.shape, cache.s.dtype) == ((6, 3, 2, 16, 16), jnp.float32)
    assert (cache.tails.shape, cache.tails.dtype) == ((6, 3, 3, 96),
                                                     CFG.dtype)
    assert cache.k.shape == (2, 3, 32, 2, 16) and cache.kw is None


def test_a_configuration_without_linear_heads_is_refused():
    with pytest.raises(ValueError, match="linear_n_heads"):
        dataclasses.replace(CFG, linear_n_heads=0)
    with pytest.raises(ValueError, match="linear_n_heads"):
        dataclasses.replace(configs.tiny_mellum_test(), linear_n_heads=2)


# -- the chunked scan ---------------------------------------------------------

def _token_by_token(q, k, v, g, beta, lengths):
    B, S, H, dk = k.shape

    def one(S0, xs):
        q, k, v, g, b, t = xs
        o, S1 = delta_rule.step(S0, q, k, v, g, b)
        return jnp.where((t < lengths)[:, None, None, None], S1, S0), o

    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta)) \
        + (jnp.arange(S),)
    last, o = lax.scan(one, jnp.zeros((B, H, dk, v.shape[-1])), xs)
    return jnp.moveaxis(o, 0, 1), last


def _operands(S, seed, strongest_decay=1.5):
    B, H, dk, dv = 2, 3, 32, 16
    ks = jax.random.split(jax.random.key(seed), 5)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (B, S, H, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (B, S, H, dk)))
    v = jax.random.normal(ks[2], (B, S, H, dv))
    # Log-decays from a thousandth to e^1.5 = 4.5 a token: a chunk's
    # running sum passes -280, and exp(280) is no float32.
    g = -jnp.exp(jax.random.uniform(ks[3], (B, S, H, dk), minval=-6.0,
                                    maxval=strongest_decay))
    beta = 2 * jax.nn.sigmoid(2 * jax.random.normal(ks[4], (B, S, H)))
    return q, k, v, g, beta


@pytest.mark.parametrize("S", [8, 40, 64, 192])
def test_the_chunked_scan_is_the_recurrence_a_token_at_a_time(S):
    """beta past 1 (negative eigenvalues), ragged lengths, a tile shorter
    than a chunk, one that is no whole number of blocks and one of three
    chunks: outputs at the real positions and the state behind each
    row's last real one."""
    q, k, v, g, beta = _operands(S, S)
    assert float(beta.max()) > 1.5
    lengths = jnp.asarray([S, max(1, S - 11)])
    o, last = jax.jit(delta_rule.chunk_scan)(q, k, v, g, beta, lengths)
    want_o, want_last = _token_by_token(q, k, v, g, beta, lengths)
    real = (jnp.arange(S)[None, :] < lengths[:, None])[..., None, None]
    assert bool(jnp.isfinite(o).all())
    np.testing.assert_allclose(jnp.where(real, o, 0),
                               jnp.where(real, want_o, 0), rtol=0, atol=5e-6)
    np.testing.assert_allclose(last, want_last, rtol=0, atol=2e-5)


def test_the_scan_carries_a_state_it_is_given():
    q, k, v, g, beta = _operands(128, 7)
    whole, last = delta_rule.chunk_scan(q, k, v, g, beta)
    first = [a[:, :64] for a in (q, k, v, g, beta)]
    rest = [a[:, 64:] for a in (q, k, v, g, beta)]
    o1, s1 = delta_rule.chunk_scan(*first)
    o2, s2 = delta_rule.chunk_scan(*rest, state=s1)
    np.testing.assert_allclose(jnp.concatenate([o1, o2], 1), whole, rtol=0,
                               atol=2e-6)
    np.testing.assert_allclose(s2, last, rtol=0, atol=2e-6)


def test_bf16_operands_stay_near_the_float32_scan():
    q, k, v, g, beta = _operands(192, 9, strongest_decay=-2.0)
    want, want_last = delta_rule.chunk_scan(q, k, v, g, beta)
    bf = jnp.bfloat16
    o, last = delta_rule.chunk_scan(q.astype(bf), k.astype(bf), v.astype(bf),
                                    g, beta)
    rel = lambda a, b: float(jnp.sqrt(jnp.mean((a - b) ** 2)
                                      / jnp.mean(b ** 2)))
    assert rel(o, want) < 0.02 and rel(last, want_last) < 0.02


def test_one_update_of_the_carried_states(params):
    states = jax.random.normal(jax.random.key(0), (3, 4, 2, 16, 16))
    ks = jax.random.split(jax.random.key(1), 5)
    q, k, g = (jax.random.normal(ks[i], (4, 2, 16)) for i in range(3))
    v = jax.random.normal(ks[3], (4, 2, 16))
    beta = 2 * jax.nn.sigmoid(jax.random.normal(ks[4], (4, 2)))
    live = jnp.asarray([True, False, True, True])
    o, new = jax.jit(delta_rule.decode_update)(states, 1, q, k, v,
                                               -jnp.abs(g), beta, live)
    want_o, want = delta_rule.step(states[1], q, k, v, -jnp.abs(g), beta)
    np.testing.assert_array_equal(new[0], states[0])
    np.testing.assert_array_equal(new[2], states[2])
    np.testing.assert_array_equal(new[1, 1], states[1, 1])     # not live
    owned = jnp.asarray([0, 2, 3])
    np.testing.assert_allclose(new[1, owned], want[owned], rtol=1e-6,
                               atol=1e-6)
    np.testing.assert_allclose(o[owned], want_o[owned], rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("live", [[True, False, True, True, False],
                                  [False] * 5, None])
def test_the_update_kernel_is_the_update(live):
    """The kernel in the Pallas interpreter at the real head size: a step
    a (owned slot, 8 heads); a slot nobody owns gets no step and its
    state, like every other layer's, is bit for bit what it was."""
    L, B, H, D = 2, 5, 16, 128
    ks = jax.random.split(jax.random.key(0), 6)
    states = jax.random.normal(ks[0], (L, B, H, D, D))
    q, k = (0.1 * jax.random.normal(ks[i], (B, H, D)) for i in (1, 2))
    v = jax.random.normal(ks[3], (B, H, D))
    g = -0.1 * jnp.abs(jax.random.normal(ks[4], (B, H, D)))
    beta = 2 * jax.nn.sigmoid(jax.random.normal(ks[5], (B, H)))
    live = None if live is None else jnp.asarray(live)
    assert not delta_rule.usable(states)               # no TPU here
    o, new = delta_rule._update_pallas(states, jnp.int32(1), q, k, v, g,
                                       beta, live, interpret=True)
    want_o, want = delta_rule.decode_update(states, jnp.int32(1), q, k, v, g,
                                            beta, live)
    owned = np.ones((B,), bool) if live is None else np.asarray(live)
    np.testing.assert_array_equal(new[0], states[0])
    np.testing.assert_array_equal(new[1][~owned], states[1][~owned])
    np.testing.assert_allclose(new, want, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(o[owned], want_o[owned], rtol=1e-6, atol=1e-6)
    assert not np.asarray(o[~owned]).any()


@pytest.mark.parametrize("live", [[True, False, True, True, False],
                                  [False] * 5, None])
def test_the_tails_kernel_moves_an_owned_slots_tail_a_position(live):
    tails = jax.random.normal(jax.random.key(0), (3, 5, 3, 256))
    new = jax.random.normal(jax.random.key(1), (5, 1, 256))
    live = None if live is None else jnp.asarray(live)
    got = delta_rule.move_tails(tails, jnp.int32(1), new, live,
                                interpret=True)
    want = delta_rule.move_tails(tails, jnp.int32(1), new, live)   # XLA
    np.testing.assert_array_equal(got, want)
    owned = np.ones((5,), bool) if live is None else np.asarray(live)
    np.testing.assert_array_equal(got[1][owned][:, :2], tails[1][owned][:, 1:])
    np.testing.assert_array_equal(got[1][owned][:, 2:], new[owned])
    np.testing.assert_array_equal(got[1][~owned], tails[1][~owned])
    np.testing.assert_array_equal(got[jnp.asarray([0, 2])],
                                  tails[jnp.asarray([0, 2])])


# -- through the cache, against the reference ---------------------------------

def test_prefill_then_sixteen_steps_are_the_references_forward(params):
    n, steps = 21, 16
    toks = _prompt(n + steps)
    want = np.asarray(ref.forward_logits(ARCH, params, toks))
    cache = init_kv_cache(CFG, 2, 64)
    padded = jnp.zeros((1, 32), jnp.int32).at[0, :n].set(toks[:n])
    cache, logits = prefill(CFG, params, cache, padded, jnp.int32(n),
                            jnp.int32(1))
    np.testing.assert_allclose(logits, want[n - 1], rtol=0, atol=2e-5)
    live = jnp.asarray([False, True])
    for t in range(n, n + steps):
        cache, logits = decode_step(
            CFG, params, cache, jnp.asarray([0, toks[t]], jnp.int32), live)
        np.testing.assert_allclose(logits[1], want[t], rtol=0, atol=2e-5)
    assert int(cache.seq_lens[1]) == n + steps
    chose = periodic.chosen_experts(CFG, params, toks)
    for a, b in zip(chose, ref.chosen_experts(ARCH, params, toks)):
        np.testing.assert_array_equal(np.sort(a, -1), np.sort(b, -1))


def test_the_queue_side_first_token_is_the_tiles(params):
    toks = _prompt(20, seed=4)
    tile = jnp.zeros((2, 32), jnp.int32).at[0, :20].set(toks) \
        .at[1, :9].set(toks[:9])
    lengths = jnp.asarray([20, 9], jnp.int32)
    temps, key = jnp.zeros((2,)), jax.random.key(0)
    first, lp, _ = first_token_sample(CFG, params, tile, lengths, temps, 0,
                                      key)
    cache = init_kv_cache(CFG, 2, 64)
    _, got, got_lp, _ = prefill_sample_batch(
        CFG, params, cache, tile, lengths, jnp.asarray([0, 1], jnp.int32), 0,
        temps, key)
    np.testing.assert_array_equal(first, got)
    np.testing.assert_allclose(lp, got_lp, rtol=0, atol=1e-5)
    want = np.asarray(ref.forward_logits(ARCH, params, toks))
    assert int(first[0]) == int(want[19].argmax())


def test_padding_and_a_dropped_row_leave_states_and_tails_alone(params):
    """Two rows of a 32-bucket tile, 11 and 32 real positions: the short
    row's state and tails are what the 11 tokens alone leave (what lies
    behind them in the tile changes neither), and a row whose slot is out
    of range writes nothing anywhere."""
    toks = _prompt(32, seed=5)
    poison = init_kv_cache(CFG, 3, 64)
    poison = poison._replace(s=poison.s + 7.0, tails=poison.tails + 7.0)
    tile = jnp.stack([jnp.asarray(toks).at[11:].set(99), jnp.asarray(toks)])
    cache, _, _ = jax.jit(lambda c, t, n, s: periodic.prefill(
        CFG, params, c, t, n, s))(poison, tile, jnp.asarray([11, 32]),
                                   jnp.asarray([2, 3]))      # 3: no such slot
    alone, _, _ = periodic.prefill(
        CFG, params, init_kv_cache(CFG, 1, 64), jnp.asarray(toks[:11])[None],
        jnp.asarray([11]), jnp.asarray([0]))
    np.testing.assert_allclose(cache.s[:, 2], alone.s[:, 0], rtol=0,
                               atol=1e-6)
    np.testing.assert_allclose(cache.tails[:, 2], alone.tails[:, 0], rtol=0,
                               atol=1e-6)
    for slot in (0, 1):
        np.testing.assert_array_equal(cache.s[:, slot], poison.s[:, slot])
        np.testing.assert_array_equal(cache.tails[:, slot],
                                      poison.tails[:, slot])
    assert cache.seq_lens.tolist() == [0, 0, 11]
    # A prompt shorter than the convolution: its tail's first rows are
    # the zeros before the sequence.
    short, _, _ = periodic.prefill(
        CFG, params, poison, jnp.asarray(toks[:16])[None], jnp.asarray([2]),
        jnp.asarray([0]))
    assert not np.asarray(short.tails[:, 0, 0]).any()
    assert np.asarray(short.tails[:, 0, 1:]).any()


def test_a_dead_slots_state_is_bit_equal_after_a_block(params):
    cache = init_kv_cache(CFG, 3, 64)
    tile = jnp.asarray(_prompt(16, seed=6))[None]
    for slot in range(3):
        cache, _ = prefill(CFG, params, cache, tile, jnp.int32(9 + slot),
                           jnp.int32(slot))
    before = jax.tree.map(np.asarray, cache)
    live = jnp.asarray([True, False, True])
    cache, toks, _, extras = decode_multi(
        CFG, params, cache, jnp.asarray([5, 6, 7], jnp.int32),
        jnp.zeros((3,)), 4, 0, jax.random.key(0), live)
    stats = extras.routing
    for name in ("s", "tails"):
        np.testing.assert_array_equal(getattr(cache, name)[:, 1],
                                      getattr(before, name)[:, 1])
        assert not np.array_equal(getattr(cache, name)[:, 0],
                                  getattr(before, name)[:, 0])
    # Its tokens met no expert: 2 live slots x 4 steps x 8 layers x top 2.
    assert int(stats[3]) <= 2 * 4 * 8 * 2 and int(stats[4]) == 3 * 4 * 8 * 2


def test_a_slots_second_request_gets_what_it_gets_alone(params):
    """Through the engine: one slot, two requests one after the other. The
    state the first left is poisoned before the second is admitted; the
    second's tokens and log-probabilities are those it gets from a fresh
    engine."""
    from ray_tpu.serve.llm import LLMEngine

    def engine():
        return LLMEngine(CFG, params, num_slots=1, max_seq_len=64,
                         decode_block=4)

    def run(eng, prompt, n):
        out = eng.generate([int(t) for t in prompt], max_new_tokens=n,
                           return_logprobs=True)
        return list(out["tokens"]), list(out["logprobs"])

    a, b = _prompt(13, seed=7), _prompt(10, seed=8)
    eng = engine()
    run(eng, a, 9)
    assert float(jnp.abs(eng.cache.s).max()) > 0
    eng.cache = eng.cache._replace(s=eng.cache.s * 0 + 1e4,
                                   tails=eng.cache.tails * 0 + 1e4)
    got, got_lp = run(eng, b, 9)
    want, want_lp = run(engine(), b, 9)
    assert got == want
    np.testing.assert_allclose(got_lp, want_lp, rtol=0, atol=1e-5)
    counts = eng.stats()["counts"]
    assert counts["linear_tokens"] == (13 + 10) * 6
    assert counts["linear_slot_steps"] == counts["linear_slot_steps_live"] \
        == counts["slot_steps"] * 6
    # Two tiles of eight rows, one filled, in a bucket shorter than a
    # chunk of the recurrence, on the XLA walk: every row's one chunk.
    assert (counts["linear_chunks"], counts["linear_chunks_of"]) == (96, 96)


@pytest.mark.parametrize("side,kernel,ran", [
    # Rows of 130, 64 and 1 tokens and a row nobody fills (one token
    # long) in a bucket of 256, where the walk is the kernel: each to the
    # chunk of 64 that holds its last token.
    ("slot", True, 3 + 1 + 1 + 1),
    # On the XLA walk nothing is skipped: asked and ran are equal.
    ("slot", False, 4 * 4),
    # A queue-side tile's rows have no lengths: every chunk of every row.
    ("queue", True, 4 * 4)])
def test_a_tiles_chunks_of_the_recurrence_are_counted(params, monkeypatch,
                                                      side, kernel, ran):
    """`linear_chunks` of `linear_chunks_of`: the (chunk, linear layer)
    pairs a tile's recurrence ran of those it was asked for, in the
    counts and on `engine.prefill_tile`."""
    import types

    from ray_tpu.serve.llm import LLMEngine

    monkeypatch.setattr(delta_rule, "scan_usable", lambda q, k, v: kernel)
    eng = LLMEngine(CFG, params, num_slots=1, max_seq_len=64,
                    decode_block=4)
    reqs = [types.SimpleNamespace(prompt=[1] * n, id=i)
            for i, n in enumerate((130, 64, 1))]
    # Six linear layers, whose recurrence the stack reckons.
    assert periodic.tile_counts(CFG, 256, [256, 65], 321)[1] == dict(
        linear_tokens=321 * 6, linear_chunks_of=4 * 2 * 6,
        linear_chunks=((4 + 2) if kernel else 4 * 2) * 6)
    span = eng._tile_span(side, 256, 4, reqs)
    assert span.attributes["linear_chunks"] == ran * 6
    assert span.attributes["linear_chunks_of"] == 4 * 4 * 6
    assert span.attributes["linear_tokens"] == (130 + 64 + 1) * 6
    counts = eng.stats()["counts"]
    assert (counts["linear_chunks"], counts["linear_chunks_of"]) \
        == (ran * 6, 4 * 4 * 6)


# -- an expert layer that holds a share ---------------------------------------

def test_all_sixteen_shares_add_up_to_the_uncut_layer():
    """The share is tied to the model: over all 16 shares of a 320-expert
    layer (top 8, as published; tiny widths), the routed parts added up,
    with the shared expert counted once, equal the uncut reference's
    layer output on the same tokens."""
    whole = dataclasses.replace(
        configs.tiny_solar_test(router_experts=320, held=320, first=0),
        moe_top_k=8)
    w = jax.jit(lambda k: init_params(whole, k))(jax.random.key(11))
    lp = {k: v[0, 1] for k, v in w["periods"].items()
          if not isinstance(v, dict)}
    m = jax.random.normal(jax.random.key(12), (40, 64), jnp.float32)
    want = ref.routed_layer_output(dataclasses.asdict(whole), lp, m)
    shared = _swiglu(m, lp["shared_gate"], lp["shared_up"],
                     lp["shared_down"])
    total, pairs = np.asarray(shared), 0
    for share in range(16):
        cfg = dataclasses.replace(whole, moe_experts=20,
                                  moe_first_expert=20 * share)
        part = {k: (v[20 * share:20 * share + 20] if k in moe.EXPERT_LEAVES
                    else v) for k, v in lp.items()}
        out, stats, experts = moe.routed_ffn(cfg, part, m, jnp.float32)
        assert experts.shape == (40, 8) and int(stats[4]) == 40 * 8
        alone = ref.routed_layer_output(dataclasses.asdict(cfg), part, m)
        np.testing.assert_allclose(np.asarray(out + shared), alone,
                                   rtol=0, atol=2e-6)
        total = total + np.asarray(out)
        pairs += int(stats[1])
    assert pairs == 40 * 8                     # every pair is some share's
    np.testing.assert_allclose(total, want, rtol=0, atol=5e-6)


def test_long_queue_side_rows_walk_singly_to_the_same_tile(params,
                                                           monkeypatch):
    toks = jnp.asarray(np.stack([_prompt(32, seed=20), _prompt(32, seed=21)]))
    want, want_chosen, _ = periodic.forward_free(CFG, params, toks)
    monkeypatch.setattr(periodic, "_ROW_ALONE", 32)
    got, chosen, _ = periodic.forward_free(CFG, params, toks)
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-5)
    assert jax.tree.structure(chosen) == jax.tree.structure(want_chosen)
    for a, b in zip(jax.tree.leaves(chosen), jax.tree.leaves(want_chosen)):
        np.testing.assert_array_equal(a, b)


def test_float32_activations_over_one_bf16_value_a_cached_row():
    """`cache_dtype`: float32 activations on bf16 weights keep a key or a
    value as one bf16 value, not two terms. A tile attends over itself in
    float32 and never reads the cache, so its logits are the two-term
    program's to the bit; a decode step attends in bf16 over the rounded
    rows and stays near."""
    two = dataclasses.replace(CFG, param_dtype=jnp.bfloat16)
    one = dataclasses.replace(two, cache_dtype="bfloat16")
    assert (periodic.cache_terms(two), periodic.cache_terms(one)) == (2, 1)
    params = jax.jit(lambda k: init_params(two, k))(jax.random.key(3))
    toks = _prompt(24, seed=9)
    got = {}
    for cfg in (two, one):
        cache = init_kv_cache(cfg, 2, 64)
        assert cache.k.dtype == jnp.bfloat16 and cache.tails.dtype == \
            jnp.float32 and cache.k.shape[0] == 2 * periodic.cache_terms(cfg)
        tile = jnp.zeros((1, 32), jnp.int32).at[0, :20].set(toks[:20])
        cache, logits = prefill(cfg, params, cache, tile, jnp.int32(20),
                                jnp.int32(0))
        steps = [logits]
        for t in range(20, 24):
            cache, logits = decode_step(
                cfg, params, cache, jnp.asarray([toks[t], 0], jnp.int32))
            steps.append(logits[0])
        got[cfg.cache_dtype] = np.stack(steps)
    np.testing.assert_array_equal(got["bfloat16"][0], got[None][0])
    err = np.abs(got["bfloat16"][1:] - got[None][1:]).max()
    assert 0 < err < 0.01 * np.abs(got[None]).max()
    with pytest.raises(ValueError, match="cache_dtype"):
        dataclasses.replace(configs.tiny_pangu_test(), cache_dtype="bfloat16")
