"""Generation by diffusion over blocks (`TransformerConfig.block_length`)
at a tiny size on the CPU, float32 weights and activations: the walks
through the cache against the plain reference's full forward, the engine
against the reference's generation loop token for token and pass for
pass, and the two kernels' new shapes in the Pallas interpreter.

Tolerances. Program and reference both compute in float32 and differ in
the order of their sums (a scan over layers and a grouped product against
a loop over experts): logits agree to 1e-4 of their size; tokens and the
passes that unmasked them are compared exactly (the tiny model's
confidences differ by far more than that between positions)."""

import importlib
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import configs, generate, stackparts
from ray_tpu.models.transformer import (REMASK_RULES, TransformerConfig,
                                        init_params, stack)
from ray_tpu.ops import decode_attention as da
from ray_tpu.serve.llm import LLMEngine

fa = importlib.import_module("ray_tpu.ops.flash_attention")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REL_TOL = 1e-4


def _reference():
    path = os.path.join(ROOT, "benchmarks", "references",
                        "sdar_block_decoder.py")
    spec = importlib.util.spec_from_file_location("sdar_block_decoder", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _reference()


def _arch(cfg: TransformerConfig):
    """The configuration as a file would give it to the reference."""
    return dict(
        n_layers=cfg.n_layers, n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
        head_dim=cfg.head_dim, d_model=cfg.d_model, norm_eps=cfg.norm_eps,
        rope_theta=cfg.rope_theta, moe_top_k=cfg.moe_top_k,
        moe_experts=cfg.moe_experts, moe_d_ff=cfg.moe_d_ff,
        vocab_size=cfg.vocab_size, block_length=cfg.block_length,
        mask_token_id=cfg.mask_token_id, denoise_steps=cfg.denoise_steps,
        remask=cfg.remask, confidence_threshold=cfg.confidence_threshold,
        score_func=cfg.score_func, route_norm=cfg.route_norm,
        tie_embeddings=cfg.tie_embeddings, sliding_window=0,
        global_attn_every=1)


@pytest.fixture(scope="module")
def model():
    cfg = configs.tiny_sdar_test()
    return cfg, init_params(cfg, jax.random.key(7))


def _sure(params, scale=60.0):
    """The same model, surer of itself: a seeded head's confidences sit
    near 1 / V, so no threshold would ever pass."""
    return dict(params, lm_head=params["lm_head"] * scale)


def _rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(np.mean((got - want) ** 2) / np.mean(want ** 2)))


# -- the walks ---------------------------------------------------------------

@pytest.mark.parametrize("prompt_len", [8, 9, 11])
def test_prefill_and_passes_through_the_cache_follow_the_reference(
        model, prompt_len):
    """Prompts of length 0, 1 and 3 mod 4: the whole blocks prefilled,
    then every pass of three blocks through the cache (two denoising
    passes under the static rule and the commit), each against the full
    forward over the committed tokens and the pass's block."""
    cfg, params = model
    arch, Bd, mask_id = _arch(cfg), cfg.block_length, cfg.mask_token_id
    rng = np.random.default_rng(prompt_len)
    prompt = rng.integers(0, cfg.vocab_size - 1, size=prompt_len).tolist()
    whole = prompt_len // Bd * Bd
    slots, slot = 3, 1
    cache = generate.init_kv_cache(cfg, slots, 64)
    state = generate.init_block_state(cfg, slots)
    buf = np.zeros((2, 16), np.int32)
    buf[0, :whole] = prompt[:whole]
    cache, state, _ = generate.prefill_block_batch(
        cfg, params, cache, state, jnp.asarray(buf),
        jnp.asarray([whole, 1], jnp.int32), jnp.asarray([slot, slots]),
        jnp.full((2, Bd), mask_id, jnp.int32), jnp.ones((2, Bd), bool),
        jnp.ones((2,), jnp.int32), jnp.zeros((2,), jnp.int32),
        jnp.zeros((2,), jnp.float32))
    assert cache.seq_lens.tolist() == [0, whole, 0]
    done = prompt[:whole]
    block = prompt[whole:] + [mask_id] * (Bd - prompt_len + whole)
    masked = [j >= prompt_len - whole for j in range(Bd)]
    live = jnp.asarray([False, True, False])
    for _ in range(3 * 3):
        tokens = np.full((slots, Bd), mask_id, np.int32)
        tokens[slot] = block
        p0 = np.zeros((slots,), np.int32)
        p0[slot] = len(done)
        cache, logits = generate.decode_block_step(
            cfg, params, cache, jnp.asarray(tokens), jnp.asarray(p0), live)
        want = ref.forward_logits(arch, params, done + block)[len(done):]
        assert _rel(logits[slot], want) < REL_TOL
        assert not np.any(np.asarray(cache.k[:, 0]))    # not live: no write
        if not any(masked):                             # the commit pass
            done, block, masked = done + block, [mask_id] * Bd, [True] * Bd
            continue
        x0 = np.argmax(np.asarray(logits[slot]), axis=-1)
        for j in [j for j in range(Bd) if masked[j]][:2]:
            block[j], masked[j] = int(x0[j]), False
    assert len(done) >= whole + 2 * Bd


def test_a_block_of_one_is_the_shared_layers_autoregressive_walk(model):
    """`block_length` 1 makes the block-causal mask the causal one: on
    the same weights the block walk gives what the `mellum` form's
    prefill and decode give (the layer is the shared stack's)."""
    cfg, params = model
    one = configs.tiny_sdar_test(block_length=1, denoise_steps=1)
    ar = configs.tiny_sdar_test(arch="mellum", block_length=0,
                                denoise_steps=0)
    rng = np.random.default_rng(3)
    toks = rng.integers(0, 255, size=(2, 16)).astype(np.int32)
    lens, slots = jnp.asarray([12, 7], jnp.int32), jnp.asarray([0, 2])
    caches, hidden = [], []
    for c in (one, ar):
        cache, x, _ = jax.jit(lambda p, k, c=c: stack(c).prefill(
            c, p, k, jnp.asarray(toks), lens, slots))(
                params, generate.init_kv_cache(c, 3, 32))
        caches.append(cache)
        hidden.append(x)
    np.testing.assert_allclose(hidden[0], hidden[1], rtol=1e-5, atol=1e-6)
    step = jnp.asarray([5, 0, 9], jnp.int32)
    live = jnp.asarray([True, False, True])
    got_cache, got = generate.decode_block_step(
        one, params, caches[0], step[:, None],
        jnp.array(caches[0].seq_lens), live)
    want_cache, want = generate.decode_step(ar, params, caches[1], step, live)
    np.testing.assert_allclose(got[:, 0][jnp.asarray([0, 2])],
                               want[jnp.asarray([0, 2])],
                               rtol=1e-5, atol=1e-6)
    for s, n in ((0, 12), (2, 7)):
        np.testing.assert_allclose(got_cache.k[:, s, :n + 1],
                                   want_cache.k[:, s, :n + 1],
                                   rtol=1e-5, atol=1e-6)


def test_one_token_a_step_is_refused_with_the_reason(model):
    cfg, params = model
    cache = generate.init_kv_cache(cfg, 2, 32)
    with pytest.raises(NotImplementedError, match="block of positions"):
        generate.decode_step(cfg, params, cache, jnp.zeros((2,), jnp.int32))
    with pytest.raises(NotImplementedError, match="block of positions"):
        generate.decode_multi(cfg, params, cache, jnp.zeros((2,), jnp.int32),
                              jnp.zeros((2,)), 2, 0, jax.random.key(0))
    with pytest.raises(ValueError, match="power of two"):
        configs.tiny_sdar_test(block_length=3)
    with pytest.raises(ValueError, match="period stack"):
        configs.tiny_test().__class__(block_length=4)


# -- the engine against the reference's loop ---------------------------------

def _serve(cfg, params, requests, slots=3, stagger=0, **engine_kw):
    """[(prompt, max_new_tokens, options)] through one engine, a request
    submitted every `stagger` ticks (0: all at once) -> GenRequests."""
    eng = LLMEngine(cfg, params, num_slots=slots, max_seq_len=64,
                    decode_block=8, **engine_kw)
    reqs = []
    for prompt, n, kw in requests:
        reqs.append(eng.submit(prompt, max_new_tokens=n, **kw))
        for _ in range(stagger):
            eng.step()
    for _ in range(400):
        if all(r.finish_ts for r in reqs):
            break
        eng.step()
    assert all(r.finish_ts and r.error is None for r in reqs)
    return eng, reqs


def _prompts(lens, seed=0, vocab=255):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, size=n).tolist() for n in lens]


@pytest.mark.parametrize("rule,steps,threshold", [
    ("low_confidence_static", 2, 0.9),
    ("low_confidence_static", 3, 0.9),
    ("sequential", 4, 0.9),
    ("sequential", 2, 0.9),
    ("low_confidence_dynamic", 4, 0.995),
    ("low_confidence_dynamic", 2, 0.9)])
def test_the_engine_is_the_references_loop_token_for_token_pass_for_pass(
        model, rule, steps, threshold):
    """Prompts of 0, 1, 2 and 3 mod 4 (one shorter than a block), answers
    that are no multiple of four, four requests on three slots. The
    dynamic rule runs on logits scaled until some blocks finish in one
    pass and others fall back to the schedule."""
    cfg, params = model
    params = _sure(params)
    kw = dict(denoise_steps=steps, remask=rule,
              confidence_threshold=threshold)
    work = [(p, n, kw) for p, n in zip(_prompts([8, 5, 3, 14]),
                                       [10, 7, 9, 6])]
    _, reqs = _serve(cfg, params, work)
    passes = []
    for (prompt, n, _), req in zip(work, reqs):
        toks, at = ref.generate(_arch(cfg), params, prompt, n, steps, rule,
                                threshold)
        assert req.tokens == toks and req.unmasked_at == at
        assert len(req.tokens) == len(req.logprobs) == n
        passes += at
    if rule == "low_confidence_dynamic" and steps == 4:
        # Both branches of the rule ran.
        assert 1 in passes and max(passes) > 1
        blocks = [at for _, req in zip(work, reqs)
                  for at in [req.unmasked_at[i:i + 4]
                             for i in range(0, len(req.unmasked_at), 4)]]
        assert any(set(b) == {1} for b in blocks)


def test_the_configurations_defaults_are_what_a_request_gets(model):
    cfg, params = model
    params = _sure(params)
    prompt = _prompts([6], seed=4)[0]
    _, (req,) = _serve(cfg, params, [(prompt, 9, {})])
    assert (req.denoise_steps, req.remask, req.confidence_threshold) == (
        4, "low_confidence_dynamic", 0.9)
    assert (req.tokens, req.unmasked_at) == ref.generate(
        _arch(cfg), params, prompt, 9)


def test_an_eos_inside_a_block_ends_the_answer_there(model):
    cfg, params = model
    prompt = _prompts([9], seed=5)[0]
    kw = dict(denoise_steps=2, remask="low_confidence_static")
    _, (free,) = _serve(cfg, params, [(prompt, 12, kw)])
    # The token in the middle of the second block as the end of sequence.
    eos = free.tokens[5]
    first = free.tokens.index(eos)
    eng, (req,) = _serve(cfg, params,
                         [(prompt, 12, dict(kw, eos_token=eos))])
    assert req.tokens == free.tokens[:first + 1]
    toks, at = ref.generate(_arch(cfg), params, prompt, 12, 2,
                            "low_confidence_static", eos_token=eos)
    assert (req.tokens, req.unmasked_at) == (toks, at)
    assert eng.counts["tokens_truncated"] > 0


def test_a_prompt_that_holds_the_mask_token_stays_a_prompt(model):
    """Which positions are masked is state beside the tokens: a prompt's
    mask-token ids, in its whole blocks and in what opens the first
    block, are tokens like any other."""
    cfg, params = model
    mask_id = cfg.mask_token_id
    prompt = [3, mask_id, 9, 11, mask_id, 17]
    kw = dict(denoise_steps=2, remask="low_confidence_static")
    _, (req,) = _serve(cfg, params, [(prompt, 6, kw)])
    toks, at = ref.generate(_arch(cfg), params, prompt, 6, 2,
                            "low_confidence_static")
    assert (req.tokens, req.unmasked_at) == (toks, at)
    assert all(a > 0 for a in req.unmasked_at)


def test_slots_admitted_at_different_passes_give_what_each_gives_alone(model):
    cfg, params = model
    kw = dict(denoise_steps=2, remask="low_confidence_static")
    work = [(p, n, kw) for p, n in zip(_prompts([8, 5, 3, 14, 7], seed=6),
                                       [10, 7, 9, 6, 11])]
    eng, together = _serve(cfg, params, work, stagger=1)
    for item, req in zip(work, together):
        _, (alone,) = _serve(cfg, params, [item], slots=1)
        assert (req.tokens, req.unmasked_at, req.logprobs) == (
            alone.tokens, alone.unmasked_at, alone.logprobs)
    c = eng.stats()["counts"]
    # Passes, positions and rows as the counters state them.
    assert eng.decode_ticks == sum(k * n for k, n in c["blocks_by_k"].items())
    assert c["slot_steps"] == eng.decode_ticks * 3 * 4
    assert c["blocks_committed"] == sum(
        -(-(len(p) % 4 + n) // 4) for p, n, _ in work)
    assert c["commit_passes"] >= c["blocks_committed"]
    assert c["positions_unmasked"] >= sum(n for _, n, _ in work)
    assert 0 < c["cache_rows_held"] < c["cache_rows"]
    assert eng.tokens_out == sum(n for _, n, _ in work)


def test_the_cache_end_stops_a_request_at_a_whole_block(model):
    cfg, params = model
    kw = dict(denoise_steps=1, remask="sequential")
    prompt = _prompts([50], seed=8)[0]
    _, (req,) = _serve(cfg, params, [(prompt, 40, kw)])
    # 64 rows: blocks at 48 (two of its positions the prompt's), 52, 56, 60.
    assert len(req.tokens) == 14
    toks, at = ref.generate(_arch(cfg), params, prompt, 40, 1, "sequential",
                            max_seq_len=64)
    assert (req.tokens, req.unmasked_at) == (toks, at)


def test_block_options_are_checked_at_submit(model):
    cfg, params = model
    eng = LLMEngine(cfg, params, num_slots=1, max_seq_len=32)
    with pytest.raises(ValueError, match="denoise_steps"):
        eng.submit([1, 2], denoise_steps=5)
    with pytest.raises(ValueError, match="remask"):
        eng.submit([1, 2], remask="random")
    assert set(REMASK_RULES) == set(ref.RULES)
    plain = configs.tiny_test()
    eng = LLMEngine(plain, init_params(plain, jax.random.key(0)),
                    num_slots=1, max_seq_len=32)
    with pytest.raises(ValueError, match="one token a step"):
        eng.submit([1, 2], denoise_steps=2)


def test_a_temperature_samples_and_keeps_the_schedule(model):
    cfg, params = model
    kw = dict(denoise_steps=2, remask="low_confidence_static")
    prompt = _prompts([8], seed=9)[0]
    eng = LLMEngine(cfg, params, num_slots=2, max_seq_len=64, seed=3)
    hot = eng.submit(prompt, max_new_tokens=12, temperature=1.5, **kw)
    cold = eng.submit(prompt, max_new_tokens=12, **kw)
    while not (hot.finish_ts and cold.finish_ts):
        eng.step()
    assert cold.tokens == ref.generate(_arch(cfg), params, prompt, 12, 2,
                                       "low_confidence_static")[0]
    assert hot.tokens != cold.tokens and len(hot.tokens) == 12
    assert sorted(hot.unmasked_at[:4]) == [1, 1, 2, 2]


# -- the kernels' new shapes -------------------------------------------------

@pytest.mark.parametrize("Bd", [4, 32])
@pytest.mark.parametrize("offsets", [(0, 0), (128, 0)])
def test_flash_forward_block_causal_mask_is_the_references(Bd, offsets):
    """The forward kernel in the interpreter against `_reference`, blocks
    of 64 x 64 so that the diagonal's blocks are masked and the others
    not; with a q offset, keys before the queries' first block."""
    rng = np.random.default_rng(Bd)
    q_off, kv_off = offsets
    Sq, Skv = 128, 128 + q_off
    q = jnp.asarray(rng.normal(size=(1, Sq, 4, 32)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, Skv, 2, 32)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, Skv, 2, 32)), jnp.float32)
    before = dict(fa.FLASH_GRID)
    got = fa.flash_attention(q, k, v, causal=True, block=Bd, q_offset=q_off,
                             kv_offset=kv_off, block_q=64, block_k=64,
                             interpret=True)
    want = fa.flash_attention(q, k, v, causal=True, block=Bd, q_offset=q_off,
                              kv_offset=kv_off, force_reference=True)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    # The mask itself, spelled out.
    i = (q_off + np.arange(Sq))[:, None] // Bd
    j = (kv_off + np.arange(Skv))[None, :] // Bd
    s = np.einsum("qhd,khd->hqk", np.asarray(q[0]),
                  np.repeat(np.asarray(k[0]), 2, axis=1)) / np.sqrt(32)
    s = np.where((j <= i)[None], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    plain = np.einsum("hqk,khd->qhd", p, np.repeat(np.asarray(v[0]), 2, 1))
    np.testing.assert_allclose(want[0], plain, rtol=2e-5, atol=2e-5)
    # The table is the causal one: the same steps, live and masked blocks.
    causal = fa.grid_steps(Sq, Skv, 64, 64, causal=True, q_offset=q_off,
                           kv_offset=kv_off)
    for name, n in causal.items():
        assert fa.FLASH_GRID[name] - before.get(name, 0) == 4 * n


def test_flash_block_mask_refuses_what_it_cannot_do():
    q = jnp.zeros((1, 64, 2, 32))
    for kw in (dict(block=3), dict(block=4, window=8),
               dict(block=4, q_offset=2), dict(block=4, causal=False),
               dict(block=4, q_offset=jnp.int32(0))):
        with pytest.raises(ValueError):
            fa.flash_attention(q, q, q, **{"causal": True, **kw})
    with pytest.raises(NotImplementedError):
        jax.grad(lambda q: fa.flash_attention(
            q, q, q, block=4, force_reference=True).sum())(q)


@pytest.mark.parametrize("Bd", [1, 4])
def test_decode_attention_takes_a_blocks_queries_beside_the_heads(Bd):
    """G x Bd query rows a KV head over the rows a slot holds, against
    the einsum; a slot with no row reads zeros."""
    rng = np.random.default_rng(Bd)
    L, B, S, KVH, G, Dh = 2, 3, 256, 2, 4, 128
    k_all = jnp.asarray(rng.normal(size=(L, B, S, KVH, Dh)), jnp.float32)
    v_all = jnp.asarray(rng.normal(size=(L, B, S, KVH, Dh)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(B, KVH, G * Bd, Dh)), jnp.float32)
    n_rows = jnp.asarray([200, 0, 37], jnp.int32)
    got = da.decode_attention(q, k_all, v_all, jnp.int32(1), n_rows,
                              interpret=True, rows=128)
    s = jnp.einsum("bkgd,bskd->bkgs", q, k_all[1]) / np.sqrt(Dh)
    p = stackparts.masked_softmax(s, n_rows, n_rows > 0)
    want = jnp.einsum("bkgs,bskd->bkgd", p, v_all[1])
    np.testing.assert_allclose(got.reshape(want.shape), want, rtol=2e-5,
                               atol=2e-5)
    assert not np.any(np.asarray(got[1]))
