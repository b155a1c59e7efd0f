"""The period stack whose global layer is latent (`arch="kimi_linear"`:
Kimi Linear) at a small size on the CPU against the plain reference of
benchmarks/references/kimi_linear_decoder.py: the plan read from the
published lists (and the lists it refuses), prefill and decode through a
cache that keeps recurrent states beside latent rows, the one
implementation of latent attention both stacks call, a slot's second
request, a slot nobody owns, the share of the experts held, the engine's
counters, and that no other preset's programs moved. Logits, never
sampled tokens.
"""

import dataclasses
import hashlib
import importlib.util
import math
import os
import re
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import configs, generate, latent, mla, moe, periodic
from ray_tpu.models.generate import (
    decode_multi,
    decode_step,
    init_kv_cache,
    prefill,
)
from ray_tpu.models.stackparts import _swiglu
from ray_tpu.models.transformer import (
    PERIOD_FORMS,
    STACKS,
    TransformerConfig,
    init_params,
    offered,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_reference():
    spec = importlib.util.spec_from_file_location(
        "kimi_linear_decoder_ref", os.path.join(
            ROOT, "benchmarks", "references", "kimi_linear_decoder.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load_reference()
CFG = configs.tiny_kimi_test()


def _arch(cfg):
    """The configuration as a configuration file states it: the
    published group a dict again."""
    return dict(dataclasses.asdict(cfg),
                linear_attn_config=dict(cfg.linear_attn_config))


ARCH = _arch(CFG)


@pytest.fixture(scope="module")
def params():
    return jax.jit(lambda k: init_params(CFG, k))(jax.random.key(3))


def _prompt(n, seed=1):
    return np.asarray(jax.random.randint(
        jax.random.key(seed), (n,), 0, CFG.vocab_size))


# -- the plan, from the lists -------------------------------------------------

def test_the_plan_is_read_from_the_published_lists():
    assert STACKS["kimi_linear"] == "periodic"
    form = PERIOD_FORMS["kimi_linear"]
    assert CFG.period_form == form and form.recurrent == "linear" \
        and form.latent and not form.neg_eigval and form.rotary == () \
        and form.router_bias and not form.attn_gate and not form.qk_norm
    # Solar's step stays doubled; no other form is latent.
    assert PERIOD_FORMS["solar_open2"].neg_eigval
    assert [a for a, f in PERIOD_FORMS.items() if f.latent] == ["kimi_linear"]
    assert CFG.listed_global == tuple(
        n in (4, 8, 11) for n in range(1, 12)) and CFG.q_lora_rank == 0
    assert periodic.layer_plan(CFG) == [
        ("dense_layers", (1,), False), ("periods", (2, 4), True),
        ("tail_layers", (1, 2), True)]
    assert periodic.step_kinds(CFG) == [
        ("linear",), ("linear", "linear", "global", "linear"),
        ("linear", "global")]
    assert periodic.cache_layers(CFG) == {"window": 0, "global": 3,
                                          "linear": 8}
    assert periodic.routed_layers(CFG) == 10
    table = ref.layer_table(ARCH)
    assert [(t.group, t.kind) for t in table] == [
        (g.key, kind) for g, kinds in zip(periodic.layer_plan(CFG),
                                          periodic.step_kinds(CFG))
        for _ in range(g.lead[0]) for kind in kinds]
    # The published lists themselves: L L L G six times, then L L G.
    full = [4, 8, 12, 16, 20, 24, 27]
    big = configs.tiny_kimi_test(n_layers=27, linear_attn_config={
        "full_attn_layers": full,
        "kda_layers": [n for n in range(1, 28) if n not in full]})
    assert periodic.layer_plan(big) == [
        ("dense_layers", (1,), False), ("periods", (6, 4), True),
        ("tail_layers", (1, 2), True)]
    assert periodic.cache_layers(big) == {"window": 0, "global": 7,
                                          "linear": 20}


@pytest.mark.parametrize("full,kda,match", [
    # Layer 5 in neither list, layer 4 in both, a layer past the stack.
    ([4, 8, 11], [1, 2, 3, 6, 7, 9, 10], "cover the layers"),
    ([4, 8, 11], [1, 2, 3, 4, 5, 6, 7, 9, 10], "cover the layers"),
    ([4, 8, 12], [1, 2, 3, 5, 6, 7, 9, 10, 11], "cover the layers"),
    # Covered once, but the periods are not alike (G at 4, then at 9).
    ([4, 9, 11], [1, 2, 3, 5, 6, 7, 8, 10], "all alike"),
])
def test_lists_it_cannot_group_are_refused(full, kda, match):
    with pytest.raises(ValueError, match=match):
        configs.tiny_kimi_test(linear_attn_config={
            "full_attn_layers": full, "kda_layers": kda})


def test_who_may_list_and_what_plans_as_before():
    # Two leading layers of unlike kinds; lists under a stack that has
    # no linear layers.
    with pytest.raises(ValueError, match="leading layers of one kind"):
        configs.tiny_kimi_test(n_dense_layers=2, n_layers=12,
                               linear_attn_config={
            "full_attn_layers": [2, 6, 10, 12],
            "kda_layers": [1, 3, 4, 5, 7, 8, 9, 11]})
    with pytest.raises(ValueError, match="plans from lists"):
        dataclasses.replace(configs.tiny_ouro_test(), linear_attn_config={
            "full_attn_layers": [1, 2, 3], "kda_layers": []})
    # A configuration without the lists plans as before: whole periods,
    # the form's place for the global layer (solar's group has no lists).
    solar = dataclasses.replace(configs.tiny_solar_test(),
                                linear_attn_config={"num_heads": 2})
    assert solar.listed_global is None
    assert periodic.layer_plan(solar) == periodic.layer_plan(
        configs.tiny_solar_test()) == [("periods", (2, 4), True)]
    whole = configs.tiny_kimi_test(n_layers=8, n_dense_layers=0,
                                   linear_attn_config=None)
    assert periodic.step_kinds(whole) == [
        ("linear", "linear", "linear", "global")]
    with pytest.raises(ValueError, match="no leading dense layer"):
        configs.tiny_kimi_test(n_layers=9, linear_attn_config=None)


def test_the_seeded_weights_are_the_plans(params):
    assert CFG.num_params() == sum(a.size for a in jax.tree.leaves(params))
    assert set(params) == {"embed", "final_norm", "lm_head", "dense_layers",
                           "periods", "tail_layers"}
    assert set(params["periods"]) >= {"global0", "linear0", "linear1",
                                      "linear2", "router", "router_bias"}
    assert set(params["tail_layers"]) >= {"global0", "linear0"} \
        and "linear1" not in params["tail_layers"]
    # The leading layer is a KDA layer whose FFN is a dense SwiGLU.
    lead = params["dense_layers"]
    assert set(lead) == {"attn_norm", "ffn_norm", "linear0"}
    assert lead["linear0"]["w_gate"].shape == (1, 64, 128) \
        and lead["linear0"]["A_log"].shape == (1, 2)
    # No rank on the query, no norm of it; the rows' width in the leaves.
    mla = params["periods"]["global0"]
    assert set(mla) == {"kv_a_norm", "wk_b", "wkv_a", "wo", "wq_nope",
                        "wq_rope", "wv_b"}
    assert mla["wq_nope"].shape == (2, 64, 4 * 16) \
        and mla["wkv_a"].shape == (2, 64, 32 + 8)
    # exp(A_log) in [1, 16], the convolution within +-0.5, as solar's.
    a = np.exp(np.asarray(params["periods"]["linear1"]["A_log"]))
    assert a.min() >= 1 and a.max() <= 16
    assert np.abs(np.asarray(params["periods"]["linear1"]["conv"])).max() \
        <= 0.5
    assert not np.asarray(params["periods"]["router_bias"]).any()


# -- through the cache, against the reference ---------------------------------

def _through_the_cache(cfg, params, toks, n, steps):
    cache = init_kv_cache(cfg, 2, 64)
    padded = jnp.zeros((1, 32), jnp.int32).at[0, :n].set(toks[:n])
    cache, logits = prefill(cfg, params, cache, padded, jnp.int32(n),
                            jnp.int32(1))
    got = [np.asarray(logits)]
    live = jnp.asarray([False, True])
    for t in range(n, n + steps):
        cache, logits = decode_step(
            cfg, params, cache, jnp.asarray([0, toks[t]], jnp.int32), live)
        got.append(np.asarray(logits[1]))
    return cache, np.stack(got)


def test_prefill_then_eight_steps_are_the_references_forward(params):
    n, steps = 21, 8
    toks = _prompt(n + steps)
    want = np.asarray(ref.forward_logits(ARCH, params, toks))
    cache, got = _through_the_cache(CFG, params, toks, n, steps)
    # Float32 throughout, the same sums in another order (the chunked
    # scan against the recurrence a token at a time, the latent step's
    # absorbed products against per-head attention): 3e-7 measured,
    # logits reaching 0.6.
    np.testing.assert_allclose(got, want[n - 1:], rtol=0, atol=2e-5)
    assert int(cache.seq_lens[1]) == n + steps
    assert cache.k is None and cache.v is None and cache.kw is None
    assert cache.c.shape == (3, 2, 64, 128) and cache.s.shape == (
        8, 2, 2, 16, 16) and cache.tails.shape == (8, 2, 3, 96)
    # Rows of 32 + 8 values in a lane of 128: the rest stays zero.
    assert np.asarray(cache.c[:, 1, :n + steps, :40]).any() \
        and not np.asarray(cache.c[..., 40:]).any()
    chose = periodic.chosen_experts(CFG, params, toks)
    theirs = ref.chosen_experts(ARCH, params, toks)
    assert len(chose) == len(theirs) == 10
    for a, b in zip(chose, theirs):
        np.testing.assert_array_equal(np.sort(a, -1), np.sort(b, -1))


def test_the_cells_dtypes_stay_near_the_reference():
    """bf16 weights under the precision the cell states: float32
    activations (two bf16 terms a product) over bf16 latent rows, the
    state and the tails float32."""
    cfg = configs.tiny_kimi_test(periods=1, param_dtype=jnp.bfloat16,
                                 cache_dtype="bfloat16")
    params = jax.jit(lambda k: init_params(cfg, k))(jax.random.key(3))
    n, steps = 21, 4
    toks = _prompt(n + steps)
    want = np.asarray(ref.forward_logits(_arch(cfg), params, toks))[n - 1:]
    cache, got = _through_the_cache(cfg, params, toks, n, steps)
    assert cache.c.dtype == jnp.bfloat16 and cache.s.dtype == jnp.float32 \
        and cache.tails.dtype == jnp.float32
    rel = float(np.sqrt(np.mean((got - want) ** 2) / np.mean(want ** 2)))
    # What is rounded: the head's input (2^-9 = 0.002 relative: its error
    # is continuous) and a decode step's latent rows, query and
    # probabilities, nothing else: 0.0015 measured here, the tile's logits
    # and the steps' alike (bf16 activations read 0.013 at these widths,
    # and 0.070-0.096 at the published ones: PERF.md section 6, PR 57).
    assert 1e-4 < rel < 4e-3


# -- one implementation of latent attention -----------------------------------

def _plain_mla(cfg, lp, x, rotate: bool):
    """Latent attention a head at a time in numpy, float64: with or
    without the query's rank, with or without rotation."""
    f = lambda a: np.asarray(a, np.float64)
    H, nope, rope, vd, kvr = (cfg.n_heads, cfg.qk_nope_head_dim,
                              cfg.qk_rope_head_dim, cfg.v_head_dim,
                              cfg.kv_lora_rank)
    S = x.shape[0]

    def rms(a, w):
        return a / np.sqrt(np.mean(a * a, -1, keepdims=True)
                           + cfg.norm_eps) * f(w)

    def rot(a):                                         # (S, ..., rope)
        half = rope // 2
        inv = cfg.rope_theta ** (-np.arange(half) / half)
        ang = np.arange(S)[:, None] * inv[None, :]
        ang = ang.reshape((S,) + (1,) * (a.ndim - 2) + (half,))
        a1, a2 = a[..., :half], a[..., half:]
        return np.concatenate([a1 * np.cos(ang) - a2 * np.sin(ang),
                               a2 * np.cos(ang) + a1 * np.sin(ang)], -1)

    h = c_q = rms(f(x), lp["attn_norm"])
    if cfg.q_lora_rank:
        c_q = rms(h @ f(lp["wq_a"]), lp["q_a_norm"])
    q_nope = (c_q @ f(lp["wq_nope"])).reshape(S, H, nope)
    q_r = (c_q @ f(lp["wq_rope"])).reshape(S, H, rope)
    kv = h @ f(lp["wkv_a"])
    c, k_r = rms(kv[:, :kvr], lp["kv_a_norm"]), kv[:, kvr:]
    if rotate:
        q_r, k_r = rot(q_r), rot(k_r)
    k_nope = np.einsum("sc,hdc->shd", c, f(lp["wk_b"]))
    v = np.einsum("sc,hcd->shd", c, f(lp["wv_b"]))
    s = (np.einsum("thd,shd->hts", q_nope, k_nope)
         + np.einsum("thd,sd->hts", q_r, k_r)) / math.sqrt(nope + rope)
    s = np.where(np.tril(np.ones((S, S), bool)), s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("hts,shd->thd", p, v).reshape(S, H * vd) @ f(lp["wo"])


@pytest.mark.parametrize("low_rank,rotate", [(False, False), (False, True),
                                             (True, False), (True, True)])
def test_one_attention_half_with_and_without_rank_and_rotation(low_rank,
                                                               rotate):
    """`mla.attention_half`, the function `latent.layer` and the period
    stack's global layer both call, under the four forms: the query
    through its rank or not, the rotary part rotated or not."""
    cfg = dataclasses.replace(configs.tiny_pangu_test(),
                              q_lora_rank=8 if low_rank else None)
    shapes = {"attn_norm": (cfg.d_model,), **mla.attention_shapes(cfg)}
    assert ("wq_a" in shapes) == ("q_a_norm" in shapes) == low_rank
    assert shapes["wq_nope"][0] == (8 if low_rank else cfg.d_model)
    keys = jax.random.split(jax.random.key(5), len(shapes) + 1)
    lp = {name: jax.random.normal(k, shape) * (1.0 if "norm" in name
                                               else 0.1)
          for (name, shape), k in zip(sorted(shapes.items()), keys)}
    x = jax.random.normal(keys[-1], (1, 12, cfg.d_model))
    rope = latent._rope_tables(cfg, 12) if rotate else None
    got, _ = mla.attention_half(
        cfg, lp, x, rope, partial(latent._free_attend, cfg, 0), None)
    np.testing.assert_allclose(got[0], _plain_mla(cfg, lp, x[0], rotate),
                               rtol=0, atol=2e-5)


def test_both_stacks_call_the_one_attention_half(params, monkeypatch):
    calls = []
    real = mla.attention_half

    def counted(cfg, *a):
        calls.append(cfg.arch)
        return real(cfg, *a)

    for module in (latent, mla):        # `latent.layer` calls its import
        monkeypatch.setattr(module, "attention_half", counted)
    toks = jnp.asarray(_prompt(8))[None]
    jax.eval_shape(lambda p: periodic.forward_free(CFG, p, toks), params)
    pangu = configs.tiny_pangu_test()
    jax.eval_shape(lambda k: latent.forward_free(
        pangu, init_params(pangu, k), toks), jax.random.key(0))
    # A scan step is traced once: the period's and the tail's global
    # layer; the latent stack's dense and routed group.
    assert calls == ["kimi_linear"] * 2 + ["pangu_ultra_moe"] * 2
    # No second copy of either mathematics under models/: the latent
    # products are `mla.py`'s alone, the delta rule's walk `periodic.py`'s.
    text = open(periodic.__file__).read()
    assert "wk_b" not in text and "kv_a_norm" not in text
    text = open(latent.__file__).read()
    assert "def _project" not in text and "def _attend_rows" not in text
    assert "chunk_scan" not in text + open(mla.__file__).read()


# -- slots --------------------------------------------------------------------

def test_a_slot_nobody_owns_is_neither_read_nor_written(params):
    cache = init_kv_cache(CFG, 3, 64)
    tile = jnp.asarray(_prompt(16, seed=6))[None]
    for slot in range(3):
        cache, _ = prefill(CFG, params, cache, tile, jnp.int32(9 + slot),
                           jnp.int32(slot))
    before = jax.tree.map(np.asarray, cache)
    live = jnp.asarray([True, False, True])
    toks = jnp.asarray([5, 6, 7], jnp.int32)
    args = (jnp.zeros((3,)), 4, 0, jax.random.key(0), live)
    after, out, _, extras = decode_multi(CFG, params, cache, toks, *args)
    stats = extras.routing
    # A state is rewritten whole by a step, so a slot nobody owns keeps
    # its state and tails bit for bit; of its latent rows, those it held
    # (a step writes its row at `seq_lens`, past them, as every cache of
    # rows here does: `seq_lens` hides it and the next tile overwrites it).
    for name, held in (("s", None), ("tails", None), ("c", 10)):
        np.testing.assert_array_equal(getattr(after, name)[:, 1, :held],
                                      getattr(before, name)[:, 1, :held])
        assert not np.array_equal(getattr(after, name)[:, 0],
                                  getattr(before, name)[:, 0])
    # Its tokens met no expert: 2 live slots x 4 steps x 10 layers x top 2.
    assert int(stats[3]) <= 2 * 4 * 10 * 2 and int(stats[4]) == 3 * 4 * 10 * 2
    # Nor read: the owned slots' tokens are the same with the dead slot's
    # rows, state and tails poisoned.
    poisoned = jax.tree.map(jnp.asarray, before)._replace(
        s=jnp.asarray(before.s).at[:, 1].set(1e4),
        tails=jnp.asarray(before.tails).at[:, 1].set(1e4),
        c=jnp.asarray(before.c).at[:, 1].set(1e4))
    _, again, _, _ = decode_multi(CFG, params, poisoned, toks, *args)
    np.testing.assert_array_equal(np.asarray(out)[:, [0, 2]],
                                  np.asarray(again)[:, [0, 2]])


def test_a_slots_second_request_gets_what_it_gets_alone_and_the_counters(
        params):
    """Through the engine: one slot, two requests one after the other.
    What the first left (states, tails, latent rows) is poisoned before
    the second is admitted; the second's tokens and log-probabilities are
    those it gets from a fresh engine. And the engine's counters of both
    kinds of cache."""
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
    eng.cache = eng.cache._replace(
        s=eng.cache.s * 0 + 1e4, tails=eng.cache.tails * 0 + 1e4,
        c=eng.cache.c * 0 + 1e4)
    got, got_lp = run(eng, b, 9)
    want, want_lp = run(engine(), b, 9)
    assert got == want
    np.testing.assert_allclose(got_lp, want_lp, rtol=0, atol=1e-5)
    counts = eng.stats()["counts"]
    assert counts["linear_tokens"] == (13 + 10) * 8
    assert (counts["linear_chunks"], counts["linear_chunks_of"]) == (128, 128)
    assert counts["linear_slot_steps"] == counts["linear_slot_steps_live"] \
        == counts["slot_steps"] * 8
    # A slot's eight states (2 x 16 x 16 float32) and tails (3 x 96
    # float32), a step; a held token once, its 32 + 8 float32 values over
    # the three latent layers (never the 128 lanes).
    state, row = periodic.cache_bytes(CFG)
    assert (state, row) == (8 * (2 * 16 * 16 * 4 + 3 * 96 * 4),
                            3 * (32 + 8) * 4)
    assert counts["cache_state_bytes_live"] == counts["slot_steps"] * state
    assert counts["cache_row_bytes_held"] == counts["cache_rows_held"] * row
    assert counts["cache_rows_held"] < counts["cache_rows"]
    # Solar's engine counts the same two: states beside keys and values.
    solar = configs.tiny_solar_test()
    assert periodic.cache_bytes(solar) == (
        6 * (2 * 16 * 16 * 4 + 3 * 96 * 4), 2 * 2 * 2 * 16 * 4)


# -- an expert layer that holds a share ---------------------------------------

def test_all_sixteen_shares_add_up_to_the_uncut_layer():
    """The share is tied to the model: over all 16 shares of a 256-expert
    layer (top 8, renormalised x 2.446, as published; tiny widths), the
    routed parts added up, with the shared expert counted once, equal the
    uncut reference's layer output on the same tokens."""
    whole = configs.tiny_kimi_test(router_experts=256, held=256, first=0,
                                   periods=1, moe_top_k=8)
    w = jax.jit(lambda k: init_params(whole, k))(jax.random.key(11))
    lp = {k: v[0, 1] for k, v in w["periods"].items()
          if not isinstance(v, dict)}
    m = jax.random.normal(jax.random.key(12), (40, 64), jnp.float32)
    want = ref.routed_layer_output(_arch(whole), lp, m)
    shared = _swiglu(m, lp["shared_gate"], lp["shared_up"],
                     lp["shared_down"])
    total, pairs = np.asarray(shared), 0
    for share in range(16):
        cfg = dataclasses.replace(whole, moe_experts=16,
                                  moe_first_expert=16 * share)
        part = {k: (v[16 * share:16 * share + 16] if k in moe.EXPERT_LEAVES
                    else v) for k, v in lp.items()}
        out, stats, experts = moe.routed_ffn(cfg, part, m, jnp.float32)
        assert experts.shape == (40, 8) and int(stats[4]) == 40 * 8
        if share in (0, 5):
            alone = ref.routed_layer_output(_arch(cfg), part, m)
            np.testing.assert_allclose(np.asarray(out + shared), alone,
                                       rtol=0, atol=5e-6)
        total = total + np.asarray(out)
        pairs += int(stats[1])
    assert pairs == 40 * 8                     # every pair is some share's
    np.testing.assert_allclose(total, want, rtol=0, atol=1e-5)


# -- what it lacks, and what it left alone ------------------------------------

def test_what_the_hybrid_lacks_is_said():
    for name in ("suffix", "param_logical_axes", "forward_train"):
        with pytest.raises(NotImplementedError) as e:
            offered(CFG, name)
        assert str(e.value) == periodic.MISSING[name]
    assert "latent rows" in periodic.MISSING["suffix"] \
        and "snapshot" in periodic.MISSING["suffix"]
    assert "carried state" in periodic.MISSING["chunked_prefill"] \
        and "indexer" in periodic.MISSING["chunked_prefill"]
    with pytest.raises(ValueError, match="arch must be one of"):
        TransformerConfig(arch="kimi")
    with pytest.raises(ValueError, match="q_lora_rank"):
        dataclasses.replace(configs.tiny_glm_test(), q_lora_rank=None)


# sha256[:16] of the StableHLO text of an admission tile and a decode
# block of the five tiny presets tests/test_ouro.py does not pin, lowered
# on the parent commit (`git archive 83f3847`; this machine, jax 0.9.0,
# the CPU); the four it pins were recomputed there too and stand. Less the
# results' names, as there (`jax.result_info`): taken anew at PR 60's
# parent, whose whole text hashed to what 83f3847's did.
PROGRAMS = {
    "tiny_test": ("9de6f6e8ce8538c7", "46996bdc90f99da7"),
    "tiny_sdar_test": ("2a6bcf9404f82a45", "c44bed089e45dccb"),
    "tiny_pangu_test": ("181b8cf97d2759bc", "401496e8b164ac7a"),
    "tiny_glm_test": ("3007ecf218b2c974", "8f236a6f0a5ca0da"),
    "tiny_ouro_test": ("51a372692823c094", "9526664e221c0229"),
}


def program_hashes(preset: str):
    cfg = getattr(configs, preset)()
    p = jax.eval_shape(lambda k: init_params(cfg, k), jax.random.key(0))
    cache = jax.eval_shape(lambda: generate.init_kv_cache(cfg, 2, 32))
    i32, f32 = jnp.int32, jnp.float32
    sds = jax.ShapeDtypeStruct
    key = jax.eval_shape(lambda: jax.random.key(0))
    if cfg.block_length:
        Bd = cfg.block_length
        blocks = jax.eval_shape(lambda: generate.init_block_state(cfg, 2))
        tile = generate.prefill_block_batch.lower(
            cfg, p, cache, blocks, sds((2, 16), i32), sds((2,), i32),
            sds((2,), i32), sds((2, Bd), i32), sds((2, Bd), bool),
            sds((2,), i32), sds((2,), i32), sds((2,), f32))
        block = generate.decode_block_multi.lower(
            cfg, p, cache, blocks, sds((2,), f32), 2, 0, key,
            sds((2,), bool))
    else:
        tile = generate.prefill_sample_batch.lower(
            cfg, p, cache, sds((2, 16), i32), sds((2,), i32), sds((2,), i32),
            0, sds((2,), f32), key)
        block = generate.decode_multi.lower(
            cfg, p, cache, sds((2,), i32), sds((2,), f32), 2, 0, key,
            sds((2,), bool))
    return tuple(hashlib.sha256(re.sub(
        r'jax\.result_info = "[^"]*"', "", x.as_text()).encode())
        .hexdigest()[:16] for x in (tile, block))


@pytest.mark.parametrize("preset", sorted(PROGRAMS))
def test_the_programs_of_the_other_tiny_presets_are_the_parents(preset):
    assert program_hashes(preset) == PROGRAMS[preset]
