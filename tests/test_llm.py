"""LLM inference path: KV-cache decode equivalence, continuous batching,
serve deployment integration."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import configs
from ray_tpu.models.generate import (
    decode_multi,
    decode_step,
    greedy_generate,
    init_kv_cache,
    prefill,
)
from ray_tpu.models.transformer import forward, init_params
from ray_tpu.serve.llm import LLMEngine, default_buckets


@pytest.fixture(scope="module")
def tiny_model():
    cfg = configs.tiny_test()
    return cfg, init_params(cfg, jax.random.key(0))


def params_of(cfg):
    return init_params(cfg, jax.random.key(0))


def test_decode_logits_match_full_forward(tiny_model):
    """Prefill+decode must reproduce the full forward's logits exactly
    (dense model; bf16-free test config)."""
    cfg, params = tiny_model
    toks = jax.random.randint(jax.random.key(1), (14,), 0, cfg.vocab_size)

    cache = init_kv_cache(cfg, 1, 32)
    padded = jnp.zeros((1, 16), jnp.int32).at[0, :10].set(toks[:10])
    cache, l0 = prefill(cfg, params, cache, padded,
                        jnp.int32(10), jnp.int32(0))
    inc = [np.asarray(l0)]
    for i in range(10, 14):
        cache, lg = decode_step(cfg, params, cache, toks[i][None])
        inc.append(np.asarray(lg[0]))

    full, _ = forward(cfg, params, toks[None])
    for step, (a, i) in enumerate(zip(inc, range(9, 14))):
        np.testing.assert_allclose(a, np.asarray(full[0, i]),
                                   atol=2e-5, rtol=2e-4,
                                   err_msg=f"step {step}")


@pytest.mark.parametrize("group", [1, 2, 4])
@pytest.mark.parametrize("kind", ["dense", "moe"])
def test_in_place_decode_is_the_plain_reference(kind, group):
    """The decode programs write one row a slot a layer into the carried
    cache and read it in place. Against the full-sequence forward and a
    cache made by prefilling the whole sequence: same logits, the same
    greedy tokens from `decode_step` x n and from one `decode_multi`
    block of n, the reference's K/V in every row a request owns, and
    every other row bit for bit what it was before the block."""
    S, n = 16, 3
    base = configs.tiny_test() if kind == "dense" else dataclasses.replace(
        configs.tiny_moe_test(), moe_capacity_factor=4.0)  # nothing dropped
    cfg = dataclasses.replace(base, n_kv_heads=base.n_heads // group,
                              max_seq_len=S)
    params = init_params(cfg, jax.random.key(0))
    # Slots at unequal lengths: empty, short, a padded bucket, one row
    # left (owned for one step, then past the end), and one no request
    # owns whose position already stands at S.
    prompt_lens = [0, 5, 9, S - 1]
    owned_steps = [n, n, n, 1]
    idle, B = len(prompt_lens), len(prompt_lens) + 1
    rng = np.random.RandomState(group)
    prompts = [rng.randint(0, cfg.vocab_size, size=m) for m in prompt_lens]

    def start():
        shape = (cfg.n_layers, B, S, cfg.n_kv_heads, cfg.head_dim)
        kk, kv = jax.random.split(jax.random.key(7))
        cache = init_kv_cache(cfg, B, S)._replace(
            k=jax.random.normal(kk, shape, cfg.dtype),
            v=jax.random.normal(kv, shape, cfg.dtype))
        first = [3]                      # the empty slot is fed a token
        for slot, p in enumerate(prompts[1:], start=1):
            bucket = max(8, 1 << (len(p) - 1).bit_length())
            padded = jnp.zeros((1, bucket), jnp.int32).at[0, :len(p)].set(p)
            cache, logits = prefill(cfg, params, cache, padded,
                                    jnp.int32(len(p)), jnp.int32(slot))
            first.append(int(jnp.argmax(logits)))
        cache = cache._replace(seq_lens=cache.seq_lens.at[idle].set(S))
        return cache, jnp.asarray(first + [0], jnp.int32)

    cache, tok = start()
    before_k, before_v = np.asarray(cache.k), np.asarray(cache.v)
    fed, step_logits = [], []
    for _ in range(n):
        fed.append(np.asarray(tok))
        cache, logits = decode_step(cfg, params, cache, tok)
        step_logits.append(np.asarray(logits))
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    fed.append(np.asarray(tok))

    cache_b, tok_b = start()
    cache_b, toks_b, _, _ = decode_multi(cfg, params, cache_b, tok_b,
                                         jnp.zeros((B,), jnp.float32), n, 0,
                                         jax.random.key(5))
    toks_b = np.asarray(toks_b)

    touched = np.zeros((B, S), bool)
    for slot, (p, steps) in enumerate(zip(prompts, owned_steps)):
        m = len(p)
        seq = np.concatenate([p, [f[slot] for f in fed[:steps]]])
        full, _ = forward(cfg, params, jnp.asarray(seq, jnp.int32)[None])
        for i in range(steps):
            np.testing.assert_allclose(
                step_logits[i][slot], np.asarray(full[0, m + i]),
                atol=2e-5, rtol=2e-4, err_msg=f"slot {slot} step {i}")
            best = int(np.argmax(np.asarray(full[0, m + i])))
            assert fed[i + 1][slot] == best == toks_b[i, slot]
        # The reference cache: the whole sequence through prefill.
        ref = init_kv_cache(cfg, 1, S)
        padded = jnp.zeros((1, S), jnp.int32).at[0, :len(seq)].set(seq)
        ref, _ = prefill(cfg, params, ref, padded, jnp.int32(len(seq)),
                         jnp.int32(0))
        touched[slot, m:m + steps] = True
        for got in (cache, cache_b):
            for have, want in ((got.k, ref.k), (got.v, ref.v)):
                np.testing.assert_allclose(
                    np.asarray(have)[:, slot, :len(seq)],
                    np.asarray(want)[:, 0, :len(seq)],
                    atol=2e-5, rtol=2e-4, err_msg=f"slot {slot}")
    for got in (cache, cache_b):
        np.testing.assert_array_equal(
            np.asarray(got.seq_lens), np.asarray(prompt_lens + [S]) + n)
        for have, was in ((got.k, before_k), (got.v, before_v)):
            have = np.asarray(have)
            assert np.array_equal(have[:, ~touched], was[:, ~touched])
            assert not np.array_equal(have[:, touched], was[:, touched])


def test_moe_decode_finite():
    cfg = configs.tiny_moe_test()
    params = init_params(cfg, jax.random.key(0))
    prompt = jax.random.randint(jax.random.key(1), (6,), 0, cfg.vocab_size)
    out = greedy_generate(cfg, params, prompt, 4)
    assert out.shape == (4,)
    assert all(0 <= int(t) < cfg.vocab_size for t in out)


def test_continuous_batching_matches_single_seq(tiny_model):
    """More requests than slots, mixed prompt lengths: every request's
    output must equal its standalone greedy generation."""
    cfg, params = tiny_model
    eng = LLMEngine(cfg, params, num_slots=3, max_seq_len=64)
    rng = np.random.RandomState(0)
    prompts = [list(rng.randint(0, cfg.vocab_size, size=n))
               for n in (5, 11, 7, 20, 3)]
    reqs = [eng.submit(p, max_new_tokens=6) for p in prompts]
    while eng.step():
        pass
    for p, r in zip(prompts, reqs):
        ref = list(np.asarray(greedy_generate(
            cfg, params, jnp.asarray(p, jnp.int32), 6)))
        assert r.result(timeout=1) == ref
    st = eng.stats()
    assert st["finished"] == 5
    assert st["tokens_out"] == 30


def test_engine_slot_reuse_after_finish(tiny_model):
    """A slot freed by one request must serve a later request correctly
    (stale-KV regression: decode overwrites, never accumulates)."""
    cfg, params = tiny_model
    eng = LLMEngine(cfg, params, num_slots=1, max_seq_len=64)
    p1 = [1, 2, 3, 4, 5, 6, 7, 8]
    p2 = [9, 8, 7]
    r1 = eng.submit(p1, max_new_tokens=4)
    r2 = eng.submit(p2, max_new_tokens=4)
    while eng.step():
        pass
    ref1 = list(np.asarray(greedy_generate(
        cfg, params, jnp.asarray(p1, jnp.int32), 4)))
    ref2 = list(np.asarray(greedy_generate(
        cfg, params, jnp.asarray(p2, jnp.int32), 4)))
    assert r1.result(timeout=1) == ref1
    assert r2.result(timeout=1) == ref2


def test_engine_eos_and_streaming(tiny_model):
    cfg, params = tiny_model
    eng = LLMEngine(cfg, params, num_slots=2, max_seq_len=64)
    eng.start()
    try:
        # Use the model's own greedy continuation as EOS so generation
        # stops early on it.
        eos = int(greedy_generate(
            cfg, params_of(cfg), jnp.asarray([1, 2, 3], jnp.int32), 1)[0])
        r = eng.submit([1, 2, 3], max_new_tokens=50, eos_token=eos)
        toks = list(iter(r))
        assert toks[-1] == eos and len(toks) < 50
        r2 = eng.submit([4, 5], max_new_tokens=5, temperature=0.7)
        assert len(r2.result(timeout=30)) == 5
    finally:
        eng.stop()


def test_engine_failure_unblocks_clients(tiny_model, monkeypatch):
    """If a device step raises, waiting clients must get an error rather
    than hang."""
    cfg, params = tiny_model
    eng = LLMEngine(cfg, params, num_slots=1, max_seq_len=64)

    def boom(*a, **k):
        raise RuntimeError("synthetic device OOM")

    monkeypatch.setattr("ray_tpu.serve.llm.prefill_sample_batch", boom)
    r = eng.submit([1, 2, 3], max_new_tokens=4)
    t = eng.start()
    t.join(timeout=10)
    with pytest.raises(RuntimeError, match="synthetic device OOM"):
        r.result(timeout=5)
    with pytest.raises(RuntimeError, match="stopped"):
        eng.submit([4, 5])


def test_prompt_too_long_rejected(tiny_model):
    cfg, params = tiny_model
    eng = LLMEngine(cfg, params, num_slots=1, max_seq_len=32)
    with pytest.raises(ValueError):
        eng.submit(list(range(32)))


def test_default_buckets():
    assert default_buckets(100) == [16, 32, 64, 100]
    assert default_buckets(16) == [16]


# rows(bucket, terms) = clamp(_TILE_POSITIONS // terms // bucket, 1,
# _ADMIT_TILE) at _TILE_POSITIONS = 512, for every bucket of a 4096-row
# engine and of a 640-row one, under the one bf16 term a bf16 engine
# multiplies a position as (the rows every PR since 29 has run) and
# under a float32 engine's two on bf16 weights.
TILE_ROWS = {
    1: {16: 8, 32: 8, 64: 8, 128: 4, 256: 2, 512: 1, 640: 1, 1024: 1,
        2048: 1, 4096: 1},
    2: {16: 8, 32: 8, 64: 4, 128: 2, 256: 1, 512: 1, 640: 1, 1024: 1,
        2048: 1, 4096: 1},
}


@pytest.mark.parametrize("terms", sorted(TILE_ROWS))
def test_tile_rows_covers_every_bucket(terms):
    assert sorted(TILE_ROWS[terms]) == sorted(
        set(default_buckets(4096)) | set(default_buckets(640)))


@pytest.mark.parametrize("bucket", sorted(TILE_ROWS[1]))
@pytest.mark.parametrize("terms", sorted(TILE_ROWS))
def test_tile_rows_by_bucket(bucket, terms):
    rows = LLMEngine._tile_rows(bucket, terms)
    assert rows == TILE_ROWS[terms][bucket]
    if terms == 1:
        # What a caller that names no terms gets: the rows of a bf16
        # engine, bucket by bucket.
        assert rows == LLMEngine._tile_rows(bucket)
    assert 1 <= rows <= LLMEngine._ADMIT_TILE
    # As many positions as the constant allows, and never an empty tile.
    positions = LLMEngine._TILE_POSITIONS // terms
    assert rows * bucket <= max(positions, bucket)
    assert rows == LLMEngine._ADMIT_TILE or (rows + 1) * bucket > positions


@pytest.mark.parametrize("dtype,param_dtype,terms", [
    (jnp.bfloat16, jnp.bfloat16, 1), (jnp.float32, jnp.float32, 1),
    (jnp.bfloat16, jnp.float32, 1), (jnp.float32, jnp.bfloat16, 2)])
def test_an_engine_reads_its_terms_from_its_two_dtypes(dtype, param_dtype,
                                                       terms):
    """The one reading (`moe.dot_terms`) as the engine, `moe.dot`'s split
    and the period stack's cache make it of the same configuration."""
    from ray_tpu.models import moe, periodic

    cfg = dataclasses.replace(configs.tiny_afmoe_test(), dtype=dtype,
                              param_dtype=param_dtype)
    eng = LLMEngine(cfg, init_params(cfg, jax.random.key(0)), num_slots=1,
                    max_seq_len=32)
    assert eng._dot_terms == terms == periodic.cache_terms(cfg)
    assert moe._split(jnp.zeros((1,), dtype),
                      jnp.zeros((1,), param_dtype)) == (terms == 2)
    assert periodic.cache_terms(
        dataclasses.replace(cfg, cache_dtype=jnp.bfloat16)) == 1


def _single_row(cfg, params, prompt, new):
    """Tokens and their log-probs from the single-row `prefill` program
    and `decode_step`, greedy: what a tile of any width has to give."""
    from ray_tpu.models.generate import token_logp

    S = len(prompt)
    bucket = max(8, 1 << (S - 1).bit_length())
    cache = init_kv_cache(cfg, 1, bucket + new)
    padded = jnp.zeros((1, bucket), jnp.int32).at[0, :S].set(
        jnp.asarray(prompt, jnp.int32))
    cache, logits = prefill(cfg, params, cache, padded, jnp.int32(S),
                            jnp.int32(0))
    toks, lps = [], []
    for _ in range(new):
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32).reshape(1)
        toks.append(int(tok[0]))
        lps.append(float(token_logp(logits.reshape(1, -1), tok)[0]))
        cache, logits = decode_step(cfg, params, cache, tok)
    return toks, lps


# Prompt lengths in the buckets 16, 128, 256, 512 and 640 of a 640-row
# engine: slot tiles of 8, 4, 2, 1 and 1 rows under one term.
TILE_LENS = (5, 100, 200, 300, 600)
# Under two terms, the buckets whose width they change (16, 64, 128 and
# 256: tiles of 8, 4, 2 and 1 rows), a lone request each and each full.
TWO_TERM_LENS = {"lone": (5, 60, 100, 200),
                 "full": (5,) * 8 + (60,) * 4 + (100,) * 2 + (200,)}


def _f32_on_bf16(cfg):
    """float32 activations on bf16 weights: two bf16 terms a product."""
    return dataclasses.replace(cfg, param_dtype=jnp.bfloat16)


TILE_CASES = {
    # case: (configuration, terms, the engine's decode block)
    "dense": (configs.tiny_test, 1, 4),
    "period_stack": (configs.tiny_afmoe_test, 1, 4),
    "lp_twin": (configs.tiny_test, 1, 1),
    "registered_prefix": (configs.tiny_test, 1, 4),
    "period_stack_f32_on_bf16": (
        lambda: _f32_on_bf16(configs.tiny_afmoe_test()), 2, 4),
    "looped_f32_on_bf16": (
        lambda: _f32_on_bf16(configs.tiny_ouro_test(ut_steps=2)), 2, 4),
}


@pytest.mark.parametrize("case,fill", [
    ("dense", "lone"), ("period_stack", "lone"), ("lp_twin", "lone"),
    ("registered_prefix", "lone"),
    ("period_stack_f32_on_bf16", "lone"),
    ("period_stack_f32_on_bf16", "full"),
    ("looped_f32_on_bf16", "lone"), ("looped_f32_on_bf16", "full")])
def test_a_tile_of_any_width_gives_the_single_row_programs_tokens(case,
                                                                  fill):
    """Temperature 0: the first token and every later one, and the
    log-probability of each, are those of the single-row `prefill`
    program, whatever the width of the tile the bucket and the model's
    terms gave, a lone request in it or as many as it has rows
    (`lp_twin`: blocks of one step, whose tokens the engine's own sampler
    draws; and behind a registered prefix, where the suffix's bucket
    gives the width)."""
    make, terms, decode_block = TILE_CASES[case]
    cfg = make()
    params = init_params(cfg, jax.random.key(2))
    rng = np.random.RandomState(3)
    prefix = list(rng.randint(0, cfg.vocab_size, size=13))
    head = prefix if case == "registered_prefix" else []
    lens = TILE_LENS if terms == 1 else TWO_TERM_LENS[fill]
    prompts = [head + list(rng.randint(0, cfg.vocab_size, size=n))
               for n in lens]
    eng = LLMEngine(cfg, params, num_slots=len(prompts), max_seq_len=640,
                    decode_block=decode_block)
    assert eng._dot_terms == terms
    if head:
        eng.register_prefix(prefix)
    reqs = [eng.submit(p, max_new_tokens=5) for p in prompts]
    while eng.step():
        pass
    c = eng.stats()["counts"]
    # 640 - 13 < 600 + 13: the longest prompt cannot sit behind the
    # prefix and takes the full path's 640 bucket; the others' suffixes
    # fall in the same buckets as the whole prompts do.
    tiles, tile_rows = (5, 8 + 4 + 2 + 2) if terms == 1 else (4, 8 + 4 + 2 + 1)
    assert (c["prefill_tiles"], c["prefill_tile_rows"]) == (tiles, tile_rows)
    assert c["prefill_rows"] == (tiles if fill == "lone" else tile_rows)
    assert eng.stats()["prefix_hits"] == (4 if head else 0)
    # Under two terms an activation is hi + lo to 2^-17 (`bf16_terms`):
    # where two tile shapes sum in another order, a last digit of x can
    # move lo by its own last place, 2^-16 of x, which float32 products
    # never see: ten times their room, on log-probabilities near 5.
    atol = 2e-5 if terms == 1 else 2e-4
    for p, r in zip(prompts, reqs):
        toks, lps = _single_row(cfg, params, p, 5)
        assert r.result(timeout=1) == toks
        np.testing.assert_allclose(r.logprobs, lps, atol=atol)


def test_llm_serve_deployment(ray_start):
    """LLMServer behind a serve deployment handle."""
    serve = __import__("ray_tpu.serve", fromlist=["serve"])
    from ray_tpu.serve.llm import LLMServer

    cfg = configs.tiny_test()

    app = serve.deployment(LLMServer).bind(cfg, num_slots=2,
                                           max_seq_len=64)
    handle = serve.run(app, name="llm-test")
    try:
        params = init_params(cfg, jax.random.key(0))
        ref = list(np.asarray(greedy_generate(
            cfg, params, jnp.asarray([1, 2, 3], jnp.int32), 4)))
        out = handle.generate.remote([1, 2, 3], max_new_tokens=4).result(
            timeout=120)
        assert out["tokens"] == ref
        assert out["ttft_s"] >= 0
    finally:
        serve.shutdown()


def test_result_is_idempotent(tiny_model):
    """Review-of-use finding: a second result() call must return the
    cached tokens, not block forever on the drained stream."""
    cfg, params = tiny_model
    from ray_tpu.serve.llm import LLMEngine

    eng = LLMEngine(cfg, params, num_slots=2, max_seq_len=64)
    eng.start()
    try:
        req = eng.submit(list(range(1, 9)), max_new_tokens=6)
        first = req.result(timeout=60)
        second = req.result(timeout=1)  # must not block
        assert first == second and len(first) == 6
    finally:
        eng.stop()


def test_result_after_streaming_iteration(tiny_model):
    """result() after consuming via __iter__ returns all tokens
    instead of blocking on the drained stream."""
    cfg, params = tiny_model
    from ray_tpu.serve.llm import LLMEngine

    eng = LLMEngine(cfg, params, num_slots=2, max_seq_len=64)
    eng.start()
    try:
        req = eng.submit(list(range(1, 9)), max_new_tokens=5)
        streamed = list(req)          # __iter__ drains the stream
        assert len(streamed) == 5
        assert req.result(timeout=1) == streamed  # no block, full list
    finally:
        eng.stop()


def test_iteration_replay_after_drain(tiny_model):
    """A second iteration (or iteration after result()) replays the
    cached tokens instead of blocking on the drained stream."""
    cfg, params = tiny_model
    from ray_tpu.serve.llm import LLMEngine

    eng = LLMEngine(cfg, params, num_slots=2, max_seq_len=64)
    eng.start()
    try:
        req = eng.submit(list(range(1, 9)), max_new_tokens=4)
        toks = req.result(timeout=60)
        assert list(req) == toks  # does not hang, replays
    finally:
        eng.stop()


def test_queue_side_first_token_matches_slot_path():
    """first_token_sample (cache-free, queue-side TTFT path) must agree
    with the prefill path's greedy first token — including with
    NON-unit final_norm gains (a double-norm bug would only show on
    trained-like weights)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import configs
    from ray_tpu.models.generate import (
        first_token_sample,
        init_kv_cache,
        prefill_sample_batch,
    )
    from ray_tpu.models.transformer import init_params

    cfg = configs.tiny_test()
    params = init_params(cfg, jax.random.key(0))
    # Perturb the final norm gain so a double-norm diverges.
    params["final_norm"] = params["final_norm"] * 3.0 + 0.5

    prompt = jax.random.randint(jax.random.key(1), (24,), 0,
                                cfg.vocab_size)
    bucket = 32
    padded = jnp.zeros((1, bucket), jnp.int32).at[0, :24].set(prompt)

    cache = init_kv_cache(cfg, 2, 64)
    _, tok_slot, lp_slot, _ = prefill_sample_batch(
        cfg, params, cache, padded, jnp.full((1,), 24, jnp.int32),
        jnp.zeros((1,), jnp.int32), 0, jnp.zeros((1,), jnp.float32),
        jax.random.key(2))

    toks, lps, _ = first_token_sample(
        cfg, params, jnp.broadcast_to(padded, (4, bucket)),
        jnp.full((4,), 24, jnp.int32), jnp.zeros((4,), jnp.float32), 0,
        jax.random.key(3))
    assert int(toks[0]) == int(tok_slot[0])
    assert lps.shape == toks.shape
    np.testing.assert_allclose(lps[0], lp_slot[0], atol=2e-5)


def test_oversubscribed_burst_first_tokens_before_slots_free():
    """Queued requests get a first token while every slot is busy, and
    full results still complete correctly."""
    import jax

    from ray_tpu.models import configs
    from ray_tpu.models.transformer import init_params
    from ray_tpu.serve.llm import LLMEngine

    cfg = configs.tiny_test()
    params = init_params(cfg, jax.random.key(0))
    engine = LLMEngine(cfg, params, num_slots=2, max_seq_len=64)
    prompts = [[1 + i, 2, 3] for i in range(6)]
    reqs = [engine.submit(p, max_new_tokens=8) for p in prompts]
    # Run steps manually until all finish.
    for _ in range(200):
        if all(r.finish_ts for r in reqs):
            break
        engine.step()
    outs = [r.result(timeout=10) for r in reqs]
    assert all(len(o) == 8 for o in outs)
    # Every request (including over-subscribed ones) got a TTFT stamp.
    assert all(r.first_token_ts > 0 for r in reqs)
    # The first emitted token equals the full result's first token.
    for r, o in zip(reqs, outs):
        assert o[0] == r.tokens[0]


class TestPrefixCaching:
    """Registered-prefix KV reuse (capability of vLLM's prefix caching;
    the reference delegates serving to vLLM,
    doc/source/serve/doc_code/vllm_example.py): admission copies the
    prefix KV and prefills only the suffix — outputs must be identical
    to the full-prefill path."""

    def _model(self):
        from ray_tpu.models import configs
        from ray_tpu.models.transformer import init_params

        cfg = configs.tiny_test()
        return cfg, init_params(cfg, jax.random.key(0))

    def test_outputs_match_full_prefill_exactly(self):
        cfg, params = self._model()
        rng = np.random.RandomState(1)
        prefix = list(rng.randint(0, cfg.vocab_size, size=13))
        prompts = [prefix + list(rng.randint(0, cfg.vocab_size, size=n))
                   for n in (4, 9, 1, 6)]
        prompts.append(list(rng.randint(0, cfg.vocab_size, size=8)))

        base = LLMEngine(cfg, params, num_slots=3, max_seq_len=64)
        base_reqs = [base.submit(p, max_new_tokens=5) for p in prompts]
        while base.step():
            pass
        expected = [r.result(timeout=5) for r in base_reqs]

        eng = LLMEngine(cfg, params, num_slots=3, max_seq_len=64)
        eng.register_prefix(prefix)
        reqs = [eng.submit(p, max_new_tokens=5) for p in prompts]
        while eng.step():
            pass
        for exp, r in zip(expected, reqs):
            assert r.result(timeout=5) == exp
        st = eng.stats()
        # >= 4: each matched prompt hits at admission, and any that
        # queued also hit the prefix-aware early-first-token path.
        assert st["prefix_hits"] >= 4
        assert st["prefix_tokens_saved"] >= 4 * len(prefix)
        assert st["cached_prefixes"] == 1

    def test_exact_prefix_prompt_uses_full_path(self):
        """A prompt EQUAL to the prefix has no suffix token — it must
        fall back to full prefill, not crash."""
        cfg, params = self._model()
        rng = np.random.RandomState(2)
        prefix = list(rng.randint(0, cfg.vocab_size, size=10))
        eng = LLMEngine(cfg, params, num_slots=2, max_seq_len=64)
        eng.register_prefix(prefix)
        ref = list(np.asarray(greedy_generate(
            cfg, params, jnp.asarray(prefix, jnp.int32), 4)))
        r = eng.submit(prefix, max_new_tokens=4)
        while eng.step():
            pass
        assert r.result(timeout=5) == ref
        assert eng.stats()["prefix_hits"] == 0

    def test_longest_prefix_wins_and_lru_caps(self):
        cfg, params = self._model()
        rng = np.random.RandomState(3)
        p_short = list(rng.randint(0, cfg.vocab_size, size=6))
        p_long = p_short + list(rng.randint(0, cfg.vocab_size, size=6))
        eng = LLMEngine(cfg, params, num_slots=2, max_seq_len=64)
        eng.register_prefix(p_short)
        eng.register_prefix(p_long)
        prompt = p_long + [1, 2, 3]
        r = eng.submit(prompt, max_new_tokens=3)
        while eng.step():
            pass
        r.result(timeout=5)
        # Longest prefix matched (every hit saved len(p_long) tokens).
        assert eng.prefix_tokens_saved % len(p_long) == 0
        assert eng.prefix_tokens_saved >= len(p_long)
        # LRU cap evicts oldest
        eng.max_cached_prefixes = 2
        eng.register_prefix([5] * 4)
        assert eng.stats()["cached_prefixes"] == 2

    def test_register_validation(self):
        cfg, params = self._model()
        eng = LLMEngine(cfg, params, num_slots=1, max_seq_len=32)
        with pytest.raises(ValueError, match="empty"):
            eng.register_prefix([])
        with pytest.raises(ValueError, match="room"):
            eng.register_prefix([1] * 40)

    def test_auto_capture_registers_hot_prefixes(self):
        """auto_prefix_min_hits: a block-length prefix seen N times
        registers itself; later prompts hit it and outputs stay
        identical to an uncached engine."""
        cfg, params = self._model()
        rng = np.random.RandomState(6)
        hot = list(rng.randint(0, cfg.vocab_size, size=8))
        prompts = [hot + list(rng.randint(0, cfg.vocab_size, size=n))
                   for n in (3, 5, 2, 7, 4)]

        base = LLMEngine(cfg, params, num_slots=2, max_seq_len=64)
        expected = []
        for p in prompts:
            r = base.submit(p, max_new_tokens=4)
            while base.step():
                pass
            expected.append(r.result(timeout=5))

        eng = LLMEngine(cfg, params, num_slots=2, max_seq_len=64,
                        auto_prefix_min_hits=2, auto_prefix_lens=(8,))
        got = []
        for p in prompts:
            r = eng.submit(p, max_new_tokens=4)
            while eng.step():
                pass
            got.append(r.result(timeout=5))
        assert got == expected
        st = eng.stats()
        assert st["cached_prefixes"] == 1      # hot prefix captured
        assert st["prefix_hits"] >= 2          # later prompts hit it

    def test_auto_capture_divergent_continuations(self):
        """The feature's main target: a hot SHORT system prompt with
        varied longer content. Longest-length keys are all distinct —
        the short length must still be counted and captured."""
        cfg, params = self._model()
        rng = np.random.RandomState(8)
        hot = list(rng.randint(0, cfg.vocab_size, size=8))
        eng = LLMEngine(cfg, params, num_slots=2, max_seq_len=64,
                        auto_prefix_min_hits=2,
                        auto_prefix_lens=(8, 16))
        for i in range(4):
            # 16+ tokens each, all continuations distinct.
            user = list(rng.randint(0, cfg.vocab_size, size=12))
            r = eng.submit(hot + user, max_new_tokens=2)
            while eng.step():
                pass
            r.result(timeout=5)
        st = eng.stats()
        assert st["cached_prefixes"] >= 1
        assert tuple(hot) in eng._prefixes     # the short key, not a 16-key
        assert st["prefix_hits"] >= 1

    def test_auto_capture_burst_dedup(self):
        """A burst of identical prompts must enqueue ONE registration,
        not one per submission past the threshold."""
        cfg, params = self._model()
        eng = LLMEngine(cfg, params, num_slots=2, max_seq_len=64,
                        auto_prefix_min_hits=2, auto_prefix_lens=(8,))
        hot = list(range(1, 9))
        reqs = [eng.submit(hot + [10 + i], max_new_tokens=2)
                for i in range(10)]          # all before the first tick
        assert len(eng._auto_pending) == 1
        while eng.step():
            pass
        for r in reqs:
            r.result(timeout=5)
        assert eng.stats()["cached_prefixes"] == 1
        assert not eng._auto_pending and not eng._auto_inflight

    def test_auto_capture_off_by_default(self):
        cfg, params = self._model()
        eng = LLMEngine(cfg, params, num_slots=1, max_seq_len=64)
        for _ in range(3):
            r = eng.submit([1, 2, 3, 4, 5, 6, 7, 8, 9], max_new_tokens=2)
            while eng.step():
                pass
            r.result(timeout=5)
        assert eng.stats()["cached_prefixes"] == 0

    def test_temperature_rides_suffix_path(self):
        """Sampled (non-greedy) requests through the prefix path run to
        completion with valid tokens."""
        cfg, params = self._model()
        rng = np.random.RandomState(4)
        prefix = list(rng.randint(0, cfg.vocab_size, size=8))
        eng = LLMEngine(cfg, params, num_slots=2, max_seq_len=64)
        eng.register_prefix(prefix)
        reqs = [eng.submit(prefix + [7, 8], max_new_tokens=4,
                           temperature=0.8) for _ in range(3)]
        while eng.step():
            pass
        for r in reqs:
            toks = r.result(timeout=5)
            assert len(toks) == 4
            assert all(0 <= t < cfg.vocab_size for t in toks)
        assert eng.stats()["prefix_hits"] >= 3


@pytest.mark.parametrize("arch", ["dense", "period_stack"])
def test_after_the_benchmarks_warm_up_admissions_compile_nothing(arch):
    """The benchmark warms the engine's eager first-token fusion on int32
    avals only (benchmarks/lib/serving.warm_up). The engine fuses the
    tokens alone and fetches the log-probabilities as their programs
    returned them, so after that warm-up bursts of every size, slot-side
    and queue-side, compile nothing."""
    import os
    import sys

    import jax.monitoring as mon

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir,
                                    "benchmarks"))
    try:
        from lib import serving, traffic
    finally:
        sys.path.pop(0)

    cfg = (configs.tiny_afmoe_test() if arch == "period_stack"
           else configs.tiny_test())
    params = init_params(cfg, jax.random.key(0))
    eng = LLMEngine(cfg, params, num_slots=3, max_seq_len=64,
                    decode_block=4)
    trace = [traffic.Request(i, 0.0, n, 6) for i, n in enumerate((5, 20, 40))]
    serving.warm_up(eng, trace, queueing=True)

    compiled = []

    def on_duration(event, duration_secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiled.append(duration_secs)

    mon.register_event_duration_secs_listener(on_duration)
    try:
        rng = np.random.RandomState(0)
        for burst in (1, 2, 3, 5, 7):
            reqs = [eng.submit(list(rng.randint(1, cfg.vocab_size,
                                                size=rng.choice((5, 20, 40)))),
                               max_new_tokens=int(rng.randint(1, 7)))
                    for _ in range(burst)]
            while eng.step():
                pass
            assert all(len(r.logprobs) == len(r.result(timeout=1)) > 0
                       for r in reqs)
    finally:
        mon.unregister_event_duration_listener(on_duration)
    assert eng.stats()["counts"]["queue_side_first_tokens"] > 0
    assert compiled == []


@pytest.mark.parametrize("arch", ["mellum", "pangu", "sdar"])
def test_a_lone_request_on_four_slots_meets_its_own_experts_only(arch):
    """One request on four slots: three slots are dead in every decode
    block. The routers score every slot (`moe_rows`, `moe_pairs`), the
    experts take the owned slot's pairs alone (`moe_rows_taken`) and no
    more experts are fetched than those pairs can name
    (`moe_experts_hit`), on the block's span as in the counts."""
    import threading

    from ray_tpu.models.transformer import stack
    from ray_tpu.util import tracing

    cfg = {"mellum": configs.tiny_mellum_test, "pangu": configs.tiny_pangu_test,
           "sdar": configs.tiny_sdar_test}[arch]()
    params = init_params(cfg, jax.random.key(0))
    eng = LLMEngine(cfg, params, num_slots=4, max_seq_len=64, decode_block=4)
    spans, own = [], threading.get_ident()
    tracing.setup_tracing(lambda e: threading.get_ident() == own
                          and spans.append(e))
    try:
        req = eng.submit(list(range(1, 10)), max_new_tokens=8)
        while eng.step():
            pass
    finally:
        tracing.clear_tracing()
    assert len(req.result(timeout=5)) == 8
    blocks = [e["args"] for e in spans if e["name"] == "engine.process_block"]
    c = eng.stats()["counts"]
    names = ("moe_rows", "moe_rows_taken", "moe_experts_hit", "moe_pairs",
             "moe_pairs_held")
    assert blocks and all(b["active"] == 1 for b in blocks)
    assert {n: c[n] for n in names} == {n: sum(b[n] for b in blocks)
                                        for n in names}
    for b in blocks:
        # Positions a step x top k x routed layers, over the block's steps.
        scored = b["k"] * b["slots"] * cfg.moe_top_k \
            * stack(cfg).routed_layers(cfg)
        assert b["moe_pairs"] == scored
        if arch == "pangu":     # of its pairs, those on an expert held
            assert b["moe_rows_taken"] <= b["moe_rows"] <= scored
            assert b["moe_rows_taken"] <= scored // 4
        else:
            assert b["moe_rows"] == scored == 4 * b["moe_rows_taken"]
        assert b["moe_experts_hit"] <= b["moe_rows_taken"]
    assert c["moe_experts_hit"] <= c["moe_rows_taken"] < c["moe_pairs"]
    assert c["prefill_moe_rows_taken"] == c["prefill_moe_rows"] > 0


# What the issue that brought the rule wrote out: the blocks a lone
# budget takes, in order.
_LONE_BLOCKS = {48: [32, 16], 33: [32, 1], 40: [32, 8], 56: [32, 16, 8],
                31: [32], 5: [8], 17: [16, 1], 24: [16, 8], 100: [64, 32, 4],
                **{b: [64] for b in range(57, 65)}}


@pytest.mark.parametrize("budget", range(1, 131))
def test_block_steps_of_a_budget(budget):
    """The sizing rule alone: every size a power of two within the cap
    and the cache's headroom, fewer than `_ROUND_DOWN_FROM` steps past
    the budget, an exact power of two one block, and a lone budget
    covered in at most log2 of it dispatches, none but the last under 16
    steps."""
    import math

    from ray_tpu.serve.llm import _ROUND_DOWN_FROM, block_steps

    def pow2(n):
        return n > 0 and n & (n - 1) == 0

    for cap in (1, 4, 48, 64, 256):
        for headroom in (0, 1, 5, 40, 64, 10 ** 6):
            k, up = block_steps(budget, cap, headroom)
            assert pow2(k) and pow2(up) and k <= up
            assert k <= min(cap, max(1, headroom))
            assert k - budget < _ROUND_DOWN_FROM
            # `up`: what rounding up alone runs (the rule until PR 49).
            want = 1
            while want < budget:
                want *= 2
            want = min(want, cap, max(1, headroom))
            while want & (want - 1):
                want &= want - 1
            assert up == want
            # Rounded down only where the cap and the headroom did not
            # already hold the block under the budget.
            assert k == up or (k == up // 2 and up - budget
                               >= _ROUND_DOWN_FROM)
    if pow2(budget):
        assert block_steps(budget, 256, 10 ** 6) == (budget, budget)
    left, ks = budget, []
    while left > 0:
        ks.append(block_steps(left, 256, 10 ** 6)[0])
        left -= ks[-1]
    assert len(ks) <= max(1, int(math.log2(budget)))
    assert -left < _ROUND_DOWN_FROM
    assert all(k >= 16 for k in ks[:-1])
    assert ks == _LONE_BLOCKS.get(budget, ks)
    # A slot whose budget the blocks in flight cover counts as 1.
    assert block_steps(-budget, 64, 64) == (1, 1)


def test_a_budget_of_48_runs_as_32_and_16(tiny_model):
    """One request of 48 tokens under `decode_block` 64: the first token
    leaves with the tile and the block is sized before it counts, so the
    budget is 48: a block of 32 and one of 16 behind it, one step past
    the request's end, where one block of 64 ran 17 past it. The tokens
    are those of an engine that runs a step a block."""
    import threading

    from ray_tpu.util import tracing

    cfg, params = tiny_model
    prompt = list(range(1, 10))
    eng = LLMEngine(cfg, params, num_slots=4, max_seq_len=128,
                    decode_block=64)
    spans, own = [], threading.get_ident()
    tracing.setup_tracing(lambda e: threading.get_ident() == own
                          and spans.append(e))
    try:
        req = eng.submit(prompt, max_new_tokens=48)
        while eng.step():
            pass
    finally:
        tracing.clear_tracing()
    c = eng.stats()["counts"]
    assert c["blocks_by_k"] == {32: 1, 16: 1}
    assert c["tokens_discarded"] == 1
    assert c["blocks_rounded_down"] == 1
    blocks = [e["args"] for e in spans if e["name"] == "engine.dispatch_block"]
    assert [(b["k"], b.get("short_of")) for b in blocks] == [(32, 64),
                                                              (16, None)]
    one = LLMEngine(cfg, params, num_slots=4, max_seq_len=128, decode_block=1)
    ref = one.submit(prompt, max_new_tokens=48)
    while one.step():
        pass
    assert one.stats()["counts"]["blocks_rounded_down"] == 0
    toks = req.result(timeout=5)
    assert len(toks) == 48 and toks == ref.result(timeout=5)
