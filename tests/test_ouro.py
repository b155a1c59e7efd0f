"""The looped walk of the period stack (`cfg.ut_steps` > 1, arch "ouro")
at a tiny size on the CPU, float32: three sandwich-normed layers walked
three times a token over one set of weights, nine cache slabs, an exit
gate a pass. Prefill and then decode through the cache against the plain
reference's full forward (`benchmarks/references/ouro_looped_decoder.py`):
logits, exit mass and exit pass; which pass writes and reads which slab;
and that one pass is the walk every other configuration had."""

import dataclasses
import hashlib
import importlib.util
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import configs, generate, periodic, stackparts
from ray_tpu.models.transformer import (STACKS, TransformerConfig,
                                        init_params, offered)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
L, T = 3, 3
PROMPT, STEPS, SLOTS, S_MAX = 12, 8, 2, 64


@pytest.fixture(scope="module")
def ref():
    spec = importlib.util.spec_from_file_location(
        "ouro_looped_decoder", os.path.join(
            ROOT, "benchmarks", "references", "ouro_looped_decoder.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def params():
    """Seeded weights with a gate twenty times as wide as the seed draws
    it: at d_model 64 the seeded gate reads 0.49-0.50 at every pass and a
    threshold would pick one pass for every token."""
    p = init_params(configs.tiny_ouro_test(), jax.random.key(1))
    gate = p["exit_gate"]
    return dict(p, exit_gate={"w": gate["w"] * 20.0, "b": gate["b"]})


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(0).integers(
        0, 256, size=PROMPT + STEPS).tolist()


def _arch(cfg):
    return dataclasses.asdict(cfg)


def _through_the_cache(cfg, params, seq):
    """The prompt through `prefill` into slot 1, then a token a step
    through `decode_step` with slot 0 owned by nobody -> (logits at the
    prompt's last position and at every step, the cache)."""
    cache = generate.init_kv_cache(cfg, SLOTS, S_MAX)
    buf = np.zeros((1, 16), np.int32)
    buf[0, :PROMPT] = seq[:PROMPT]
    cache, last = generate.prefill(cfg, params, cache, jnp.asarray(buf),
                                   jnp.int32(PROMPT), jnp.int32(1))
    got = [np.asarray(last)]
    live = jnp.asarray([False, True])
    for tok in seq[PROMPT:-1]:
        cache, logits = generate.decode_step(
            cfg, params, cache, jnp.asarray([0, tok], jnp.int32), live)
        got.append(np.asarray(logits[1]))
    return np.stack(got), cache


def _rel(got, want):
    return float(np.sqrt(np.mean((got - want) ** 2) / np.mean(want ** 2)))


@pytest.mark.parametrize("threshold", [1.0, 0.5])
def test_prefill_then_decode_give_the_references_logits(ref, params, tokens,
                                                        threshold):
    cfg = configs.tiny_ouro_test(threshold=threshold)
    got, cache = _through_the_cache(cfg, params, tokens)
    want = np.asarray(ref.forward_logits(_arch(cfg), params, tokens[:-1]))
    assert got.shape == (STEPS, 256)
    assert _rel(got, want[PROMPT - 1:]) < 1e-5
    assert cache.k.shape == (T * L, SLOTS, S_MAX, 4, 16)
    assert np.asarray(cache.seq_lens).tolist() == [STEPS - 1,
                                                   PROMPT + STEPS - 1]
    # Slot 1 holds a row a token in every slab, and none behind them.
    held = np.asarray(cache.k[:, 1]).any(axis=(2, 3))
    assert held[:, :PROMPT + STEPS - 1].all() and not held[
        :, PROMPT + STEPS - 1:].any()


def test_the_exit_mass_and_the_exit_pass_are_the_references(ref, params,
                                                            tokens):
    cfg = configs.tiny_ouro_test(threshold=0.5)
    seq = tokens[:16]
    _, _, extras = jax.jit(lambda p, t: periodic.forward_free(cfg, p, t))(
        params, jnp.asarray([seq], jnp.int32))
    exits = extras.exits
    states = ref.pass_states(_arch(cfg), params, seq)
    _, mine, mass = stackparts.exit_select(cfg, params, states)
    want = np.asarray(ref.exit_mass(_arch(cfg), params, seq))
    assert want.shape == (16, T)
    np.testing.assert_allclose(want.sum(-1), 1.0, atol=1e-6)
    np.testing.assert_allclose(np.asarray(mass), want, atol=1e-6)
    at = np.asarray(ref.exit_pass(_arch(cfg), want))
    # The widened gate spreads the tokens over the passes, and none of
    # them stands within rounding of the threshold.
    assert len(set(at.tolist())) >= 2
    assert np.min(np.abs(np.cumsum(want, -1)[:, :-1] - 0.5)) > 1e-4
    assert np.asarray(mine).tolist() == at.tolist()
    assert np.asarray(exits[0]).tolist() == at.tolist()
    # At the published threshold every token leaves at the last pass.
    one = dict(_arch(cfg), early_exit_threshold=1.0)
    assert np.asarray(ref.exit_pass(one, want)).tolist() == [T - 1] * 16


def test_pass_t_writes_slab_t_of_a_layer_and_no_later_pass_is_read(
        ref, params, tokens):
    cfg = configs.tiny_ouro_test()
    _, cache = _through_the_cache(cfg, params, tokens[:PROMPT + 1])
    # Written by pass t: layer 0's values of pass t are its norm of the
    # pass's input (the embedding, then the pass before's normed output)
    # through `wv`, and they lie in slab t x L.
    states = np.asarray(ref.pass_states(_arch(cfg), params, tokens[:PROMPT]))
    inputs = [np.asarray(params["embed"])[tokens[:PROMPT]]] + list(states)
    lp = {k: np.asarray(v[0, 0]) for k, v in params["periods"].items()}
    for t in range(T):
        h = inputs[t]
        h = h / np.sqrt(np.mean(h * h, -1, keepdims=True) + cfg.norm_eps) \
            * lp["attn_norm"]
        np.testing.assert_allclose(
            np.asarray(cache.v[t * L, 1, :PROMPT]).reshape(PROMPT, -1),
            h @ lp["wv"], atol=2e-5)
    # Read by pass t: with every row of the last pass's slabs spoiled,
    # a step writes into the earlier passes' slabs what it wrote with
    # them whole (no earlier pass reads them), and the logits move.
    live = jnp.asarray([False, True])
    step = jnp.asarray([0, tokens[PROMPT]], jnp.int32)
    def fresh(spoil):
        # A cache of its own buffers: the programs donate theirs.
        return cache._replace(
            k=cache.k.at[(T - 1) * L:, :, :PROMPT].add(spoil),
            v=cache.v.at[(T - 1) * L:, :, :PROMPT].add(spoil),
            seq_lens=cache.seq_lens + 0)

    clean, logits = generate.decode_step(cfg, params, fresh(0.0), step, live)
    dirty, moved = generate.decode_step(cfg, params, fresh(3.0), step, live)
    before = slice(0, (T - 1) * L)
    for a, b in ((clean.k, dirty.k), (clean.v, dirty.v)):
        np.testing.assert_array_equal(np.asarray(a[before, 1, PROMPT]),
                                      np.asarray(b[before, 1, PROMPT]))
        assert np.abs(np.asarray(a[-1, 1, PROMPT])
                      - np.asarray(b[-1, 1, PROMPT])).max() > 1e-3
    assert np.abs(np.asarray(logits[1]) - np.asarray(moved[1])).max() > 1e-3
    # A token that leaves at the first pass does not see them at all.
    first = configs.tiny_ouro_test(threshold=0.0)
    _, a = generate.decode_step(first, params, fresh(0.0), step, live)
    _, b = generate.decode_step(first, params, fresh(3.0), step, live)
    np.testing.assert_array_equal(np.asarray(a[1]), np.asarray(b[1]))


def test_one_pass_is_the_same_layers_walked_once(params, tokens):
    looped = configs.tiny_ouro_test(ut_steps=1)
    assert periodic.cache_layers(looped) == {"window": 0, "global": L}
    assert periodic.cache_layers(configs.tiny_ouro_test()) == {
        "window": 0, "global": T * L}
    once = {k: v for k, v in params.items() if k != "exit_gate"}
    assert jax.tree.structure(init_params(looped, jax.random.key(1))) \
        == jax.tree.structure(once)
    got, cache = _through_the_cache(looped, once, tokens)
    assert cache.k.shape[0] == L
    # The first pass of the looped walk is that walk: its slabs hold the
    # same rows.
    _, three = _through_the_cache(configs.tiny_ouro_test(), params, tokens)
    np.testing.assert_allclose(np.asarray(three.k[:L]), np.asarray(cache.k),
                               atol=1e-6)
    # And a walk returns no exit pass where there is one pass.
    out = jax.eval_shape(lambda p, t: periodic.forward_free(looped, p, t),
                         once, jax.ShapeDtypeStruct((1, 8), jnp.int32))
    assert out[2] == stackparts.Extras()


# sha256[:16] of the StableHLO text of two serving programs of each tiny
# preset, lowered on the commit before the looped walk (5cc9f06; this
# machine, jax 0.9.0, the CPU): with `ut_steps` 1 the walk traces what it
# traced. Less the results' names (`jax.result_info`), which say where in
# the pytree a program returns each result lies and nothing of the
# program: PR 60 gave the by-products a named record (`result[3]` reads
# `result[3].routing` since) and took these digests at its parent, whose
# whole text hashed to what 5cc9f06's did.
PROGRAMS = {
    "tiny_afmoe_test": ("4335fde28375b850", "df8d1b7d5500d4ef"),
    "tiny_mellum_test": ("7afe3d56a5f0efb4", "0de5a0e483b9956a"),
    "tiny_solar_test": ("92934df6c3d045f1", "9ac4f91a731d3423"),
    "tiny_jamba_test": ("c680660220d06060", "25612fba57d3fd1e"),
}


def program_hashes(preset: str):
    cfg = getattr(configs, preset)()
    p = jax.eval_shape(lambda k: init_params(cfg, k), jax.random.key(0))
    cache = jax.eval_shape(lambda: generate.init_kv_cache(cfg, 2, 32))
    i32, f32 = jnp.int32, jnp.float32
    sds = jax.ShapeDtypeStruct
    key = jax.eval_shape(lambda: jax.random.key(0))
    tile = generate.prefill_sample_batch.lower(
        cfg, p, cache, sds((2, 16), i32), sds((2,), i32), sds((2,), i32), 0,
        sds((2,), f32), key)
    block = generate.decode_multi.lower(
        cfg, p, cache, sds((2,), i32), sds((2,), f32), 2, 0, key,
        sds((2,), bool))
    return tuple(hashlib.sha256(re.sub(
        r'jax\.result_info = "[^"]*"', "", x.as_text()).encode())
        .hexdigest()[:16] for x in (tile, block))


@pytest.mark.parametrize("preset", sorted(PROGRAMS))
def test_the_programs_of_the_other_tiny_presets_are_what_they_were(preset):
    assert program_hashes(preset) == PROGRAMS[preset]


def test_the_seeded_weights_take_the_depth_walked_and_a_gate():
    cfg = configs.tiny_ouro_test()
    p = init_params(cfg, jax.random.key(0))
    once = init_params(configs.tiny_ouro_test(ut_steps=1), jax.random.key(0))
    assert set(p) - set(once) == {"exit_gate"}
    assert p["exit_gate"]["w"].shape == (64,) and p["exit_gate"]["b"].shape == ()
    assert cfg.num_params() == sum(a.size for a in jax.tree.leaves(p))
    # Every leaf but the residual outputs is the one-pass draw; those are
    # scaled by the depth walked, 1 / sqrt(2 x 3 layers x 3 passes).
    for leaf, a in p["periods"].items():
        b = np.asarray(once["periods"][leaf])
        if leaf in ("wo", "w_down"):
            np.testing.assert_allclose(np.asarray(a) * np.sqrt(T), b,
                                       rtol=1e-6)
        else:
            np.testing.assert_array_equal(np.asarray(a), b)
    np.testing.assert_array_equal(np.asarray(p["embed"]),
                                  np.asarray(once["embed"]))


@pytest.mark.parametrize("preset", ["tiny_test", "tiny_pangu_test",
                                    "tiny_jamba_test", "tiny_afmoe_test",
                                    "tiny_sdar_test"])
def test_a_stack_that_does_not_walk_loops_refuses_them(preset):
    cfg = getattr(configs, preset)()
    with pytest.raises(ValueError, match="ut_steps 2"):
        dataclasses.replace(cfg, ut_steps=2)
    assert dataclasses.replace(cfg, ut_steps=1) == cfg


def test_what_a_looped_walk_does_not_serve_is_said():
    cfg = configs.tiny_ouro_test()
    assert STACKS["ouro"] == "periodic"
    for name in ("early_stop", "shared_slabs", "forward_train", "suffix"):
        with pytest.raises(NotImplementedError) as e:
            offered(cfg, name)
        assert str(e.value) == periodic.MISSING[name]
    assert "looped" in periodic.MISSING["forward_train"]
    with pytest.raises(ValueError, match="ut_steps 0"):
        TransformerConfig(arch="ouro", global_attn_every=1, ut_steps=0)


@pytest.mark.parametrize("threshold", [1.0, 0.5])
def test_the_engine_counts_every_delivered_tokens_passes_and_exit(
        params, threshold):
    """Five requests through two slots (so some first tokens come from the
    queue side and all from a tile once): every delivered token is counted
    once, with `ut_steps` passes walked for it and the pass it left at."""
    from ray_tpu.serve.llm import LLMEngine

    cfg = configs.tiny_ouro_test(threshold=threshold)
    engine = LLMEngine(cfg, params, num_slots=2, max_seq_len=64, seed=0,
                       decode_block=4)
    reqs = [engine.submit(list(range(3 + i, 9 + 2 * i)),
                          max_new_tokens=5 + i, temperature=0.0,
                          eos_token=None) for i in range(5)]
    for _ in range(400):
        if all(r.finish_ts for r in reqs):
            break
        engine.step()
    assert [len(r.tokens) for r in reqs] == [5, 6, 7, 8, 9]
    counts = engine.stats()["counts"]
    assert counts["queue_side_first_tokens"] > 0
    assert engine.tokens_out == 35 == sum(counts["loop_exit_hist"])
    assert counts["loop_passes"] == T * 35
    if threshold == 1.0:
        assert counts["loop_exit_hist"] == [0, 0, 35]
    else:
        assert counts["loop_exit_hist"][-1] < 35
    # A configuration that walks once has neither counter.
    once = LLMEngine(configs.tiny_ouro_test(ut_steps=1),
                     {k: v for k, v in params.items() if k != "exit_gate"},
                     num_slots=2, max_seq_len=64, seed=0, decode_block=4)
    assert "loop_passes" not in once.counts
