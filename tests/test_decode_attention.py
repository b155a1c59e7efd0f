"""`ops/decode_attention`: the kernel in the Pallas interpreter against the
XLA code it stands in for (`stackparts._attend_cache`, `periodic.
_attend_terms`), the rows it must not read, and `live` through the decode
programs and the engine. On the CPU the models take the XLA code; the
tests that drive the kernel through a program steer it there themselves.
"""

import dataclasses
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import configs, generate, periodic, stackparts
from ray_tpu.models.moe import bf16_terms
from ray_tpu.models.transformer import TransformerConfig, init_params
from ray_tpu.ops import decode_attention as da
from ray_tpu.serve.llm import LLMEngine

S, ROWS, DH, LAYERS = 256, 128, 128, 2
# The three serving cells' heads: (KV heads, queries a group, bf16 terms).
HEADS = {"internlm2": (8, 2, 1), "mistral": (8, 4, 1), "trinity": (4, 8, 2)}
# Positions a slot, the last one ownerless. Global: rows held 1, a block's
# edge, one past it, all S. Ring: gone round, so every row is held.
POSITIONS = {"global": [0, ROWS - 1, ROWS, S - 1, 5],
             "ring": [S, ROWS - 1, 3 * S + 5, S - 1, S + 3]}


def _case(heads: str, seed: int = 0):
    KVH, G, terms = HEADS[heads]
    cfg = types.SimpleNamespace(n_heads=KVH * G, n_kv_heads=KVH, head_dim=DH)
    dt = jnp.float32 if terms == 2 else jnp.bfloat16
    B = len(POSITIONS["global"])
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (B, 1, KVH * G, DH), jnp.float32).astype(dt)
    k = jax.random.normal(ks[1], (B, 1, KVH, DH), jnp.float32).astype(dt)
    v = jax.random.normal(ks[2], (B, 1, KVH, DH), jnp.float32).astype(dt)
    shape = (LAYERS, B, S, KVH, DH)
    k_all = jax.random.normal(ks[3], shape, jnp.float32)
    v_all = jax.random.normal(ks[4], shape, jnp.float32)
    if terms == 2:
        k_all = jnp.concatenate(list(bf16_terms(k_all)))
        v_all = jnp.concatenate(list(bf16_terms(v_all)))
    else:
        k_all, v_all = k_all.astype(dt), v_all.astype(dt)
    xla = periodic._attend_terms if terms == 2 else stackparts._attend_cache
    return cfg, xla, q, k, v, k_all, v_all


@pytest.mark.parametrize("rows", [ROWS, None], ids=["two_blocks", "chosen"])
@pytest.mark.parametrize("kind", sorted(POSITIONS))
@pytest.mark.parametrize("heads", sorted(HEADS))
def test_kernel_is_the_xla_code(heads, kind, rows):
    cfg, xla, q, k, v, k_all, v_all = _case(heads)
    pos = jnp.asarray(POSITIONS[kind], jnp.int32)
    live = jnp.asarray([True] * (len(pos) - 1) + [False])
    KVH, G, terms = HEADS[heads]
    for l in range(LAYERS):
        want, k_new, v_new = xla(cfg, q, k, v, k_all, v_all, jnp.int32(l),
                                 pos % S, pos, live)
        n_rows = stackparts.rows_held(pos, S, live)
        got = da.decode_attention(
            q.reshape(-1, KVH, G, DH), k_new, v_new, jnp.int32(l), n_rows,
            interpret=True, rows=rows)
        assert got.shape == want.shape and got.dtype == want.dtype
        tol = 2e-5 if terms == 2 else 2e-2       # float32 / a bf16 output
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   atol=tol, rtol=tol)
        assert not np.asarray(got[-1], np.float32).any()   # ownerless: zeros


@pytest.mark.parametrize("heads", sorted(HEADS))
def test_a_row_past_the_rows_held_is_never_read(heads):
    """NaN in every row at or past `n_rows`, in the block that is cut and
    in the blocks behind it: the output is what the clean cache gives."""
    cfg, _, q, _, _, k_all, v_all = _case(heads, seed=1)
    KVH, G, _ = HEADS[heads]
    n_rows = jnp.asarray([0, 1, ROWS, ROWS + 1, S], jnp.int32)
    past = (jnp.arange(S)[None, :] >= n_rows[:, None])[None, :, :, None, None]
    qg = q.reshape(-1, KVH, G, DH)
    for l in range(LAYERS):
        clean = da.decode_attention(qg, k_all, v_all, jnp.int32(l), n_rows,
                                    interpret=True, rows=ROWS)
        dirty = da.decode_attention(
            qg, jnp.where(past, jnp.nan, k_all),
            jnp.where(past, jnp.nan, v_all), jnp.int32(l), n_rows,
            interpret=True, rows=ROWS)
        np.testing.assert_array_equal(np.asarray(dirty, np.float32),
                                      np.asarray(clean, np.float32))


def test_work_list_has_a_step_for_every_block_held_and_no_other():
    """Slot after slot, block after block; a slot holding no row gives no
    step; with no row anywhere one step is left, which writes zeros."""
    def steps(n_rows, rows, blocks):
        count, slot, block = da._work_list(
            jnp.asarray(n_rows, jnp.int32), rows, blocks)
        assert slot.shape == block.shape == (len(n_rows) * blocks,)
        return list(zip(np.asarray(slot)[:int(count)].tolist(),
                        np.asarray(block)[:int(count)].tolist()))

    assert steps([0, 0, 300, 0, 128, 0], 128, 4) == [
        (2, 0), (2, 1), (2, 2), (4, 0)]
    assert steps([256, 256], 128, 2) == [(0, 0), (0, 1), (1, 0), (1, 1)]
    assert steps([1, 129], 128, 2) == [(0, 0), (1, 0), (1, 1)]
    assert steps([0, 0, 0], 128, 2) == [(2, 0)]


def test_block_rows_and_where_the_kernel_runs():
    assert da.block_rows(1024, 128) == 256 and da.block_rows(4096, 128) == 256
    assert da.block_rows(384, 128) == 128
    assert da.block_rows(200, 128) == 0 and da.block_rows(1024, 64) == 0
    k_all = jnp.zeros((1, 1, 1024, 1, 128), jnp.bfloat16)
    assert not da.usable(k_all, 128)        # this is not a TPU
    with pytest.raises(ValueError, match="do not tile"):
        da.decode_attention(jnp.zeros((1, 1, 1, 64)),
                            jnp.zeros((1, 1, 200, 1, 64)),
                            jnp.zeros((1, 1, 200, 1, 64)), jnp.int32(0),
                            jnp.ones((1,), jnp.int32), interpret=True)


# ---------------------------------------------------------------------------
# `live` through the decode programs (the XLA code, as the CPU takes it)
# ---------------------------------------------------------------------------

ARCHS = {"dense": configs.tiny_test, "routed": configs.tiny_moe_test,
         "period_f32": configs.tiny_afmoe_test,
         "period_two_terms": lambda: dataclasses.replace(
             configs.tiny_afmoe_test(), param_dtype=jnp.bfloat16)}


def _prefilled(cfg, slots=3, seed=0):
    params = init_params(cfg, jax.random.PRNGKey(seed))
    cache = generate.init_kv_cache(cfg, slots, 64)
    toks = jax.random.randint(jax.random.PRNGKey(seed + 1), (slots, 16), 0,
                              cfg.vocab_size)
    for s in range(slots):
        cache, _ = generate.prefill(cfg, params, cache, toks[s:s + 1],
                                    jnp.int32(9 + s), jnp.int32(s))
    return params, cache, toks[:, 0]


# `period_f32`: `slow` since PR 50 (the suite's clock, ROADMAP D18): the
# period stack's walk is `period_two_terms`'s too, float32 rows `dense`'s.
@pytest.mark.parametrize("arch", [
    pytest.param(a, marks=pytest.mark.slow) if a == "period_f32" else a
    for a in sorted(ARCHS)])
def test_an_ownerless_slot_changes_no_owned_slots_logits(arch):
    cfg = ARCHS[arch]()
    params, cache, tok = _prefilled(cfg)
    copy = lambda c: jax.tree.map(jnp.copy, c)       # programs donate it
    _, four = generate.decode_step(cfg, params, copy(cache), tok)
    c_all, all_live = generate.decode_step(cfg, params, copy(cache), tok,
                                           jnp.ones((3,), bool))
    np.testing.assert_array_equal(np.asarray(four), np.asarray(all_live))
    live = jnp.asarray([True, False, True])
    c_two, two = generate.decode_step(cfg, params, copy(cache), tok, live)
    np.testing.assert_array_equal(np.asarray(two)[[0, 2]],
                                  np.asarray(four)[[0, 2]])
    assert np.isfinite(np.asarray(two)).all()
    # Every slot advances as before; the owned ones' rows are the same.
    np.testing.assert_array_equal(np.asarray(c_two.seq_lens),
                                  np.asarray(c_all.seq_lens))
    np.testing.assert_array_equal(np.asarray(c_two.k)[:, [0, 2]],
                                  np.asarray(c_all.k)[:, [0, 2]])
    # The fused block takes `live` last, and gives the step's tokens.
    _, toks, _, _ = generate.decode_multi(
        cfg, params, copy(cache), tok, jnp.zeros((3,)), 2, 0,
        jax.random.PRNGKey(0), live)
    np.testing.assert_array_equal(
        np.asarray(toks[0])[[0, 2]],
        np.asarray(jnp.argmax(four, axis=-1))[[0, 2]])


# ---------------------------------------------------------------------------
# The kernel (interpreted) inside the programs: tokens as before
# ---------------------------------------------------------------------------

@pytest.fixture
def kernel_in_the_programs(monkeypatch):
    """Steer `_attend_cache` / `_attend_terms` onto the interpreted kernel
    wherever the shapes tile, as a TPU would take the compiled one."""
    taken = []

    def usable(k_all, Dh):
        ok = da.block_rows(k_all.shape[2], Dh) > 0
        taken.append(ok)
        return ok

    real = da.decode_attention
    monkeypatch.setattr(da, "usable", usable)
    monkeypatch.setattr(
        da, "decode_attention",
        lambda *a, **kw: real(*a, **dict(kw, interpret=True)))
    jax.clear_caches()
    yield taken
    jax.clear_caches()


def _wide_cfg(**kw) -> TransformerConfig:
    """A head of 128 lanes, so that the kernel's shapes tile."""
    return TransformerConfig(**dict(dict(
        vocab_size=128, d_model=64, n_layers=2, n_heads=2, n_kv_heads=1,
        head_dim=128, d_ff=64, max_seq_len=256, dtype=jnp.float32,
        param_dtype=jnp.float32, remat=False, tie_embeddings=False), **kw))


def _tokens(cfg, prompts, new=6, slots=4):
    params = init_params(cfg, jax.random.PRNGKey(3))
    eng = LLMEngine(cfg, params, num_slots=slots, max_seq_len=256,
                    decode_block=4)
    reqs = [eng.submit(p, max_new_tokens=new) for p in prompts]
    while eng.step():
        pass
    counts = eng.stats()["counts"]
    return [r.result(timeout=5) for r in reqs], counts


# `slow` since PR 50 (the suite's clock, ROADMAP D18): 45 s; `afmoe` holds
# the engine over the period stack's caches, and the two terms' arithmetic
# is `test_an_ownerless_slot...[period_two_terms]`'s and test_afmoe.py's.
@pytest.mark.parametrize("arch", ["llama", "afmoe", pytest.param(
    "afmoe_two_terms", marks=pytest.mark.slow)])
def test_engine_and_greedy_generate_give_the_tokens_they_gave(
        arch, request):
    kw = {}
    if arch != "llama":
        kw = dict(arch="afmoe", n_layers=5, n_dense_layers=1,
                  global_attn_every=4, sliding_window=128, moe_experts=4,
                  moe_top_k=2, moe_d_ff=32, moe_shared_experts=1,
                  score_func="sigmoid")
    cfg = _wide_cfg(**kw)
    if arch == "afmoe_two_terms":
        cfg = dataclasses.replace(cfg, param_dtype=jnp.bfloat16)
    rng = np.random.default_rng(0)
    # Two callers on four slots (two ownerless); one prompt past the
    # window, so the ring has gone round when decode reads it.
    prompts = [rng.integers(0, 128, n).tolist() for n in (150, 9)]
    before, _ = _tokens(cfg, prompts)
    params = init_params(cfg, jax.random.PRNGKey(3))
    greedy_before = generate.greedy_generate(
        cfg, params, jnp.asarray(prompts[0][:100], jnp.int32), 128)[:8]

    taken = request.getfixturevalue("kernel_in_the_programs")
    after, counts = _tokens(cfg, prompts)
    assert taken and all(taken)
    assert after == before
    assert 0 < counts["cache_rows_held"] < counts["cache_rows"]
    greedy_after = generate.greedy_generate(
        cfg, params, jnp.asarray(prompts[0][:100], jnp.int32), 128)[:8]
    np.testing.assert_array_equal(np.asarray(greedy_after),
                                  np.asarray(greedy_before))


def test_engine_counts_the_rows_its_slots_hold():
    """Two requests of 9 and 20 tokens on four slots of 64 rows, one
    block of 4 steps each: rows held at a step are position + 1."""
    cfg = configs.tiny_test()
    params = init_params(cfg, jax.random.PRNGKey(0))
    eng = LLMEngine(cfg, params, num_slots=4, max_seq_len=64, decode_block=4)
    for n in (9, 20):
        eng.submit(list(range(1, n + 1)), max_new_tokens=5)
    eng.step()
    c = eng.stats()["counts"]
    assert c["blocks"] == 1 and c["cache_rows"] == 4 * 4 * 64
    assert c["cache_rows_held"] == sum(
        n + t + 1 for n in (9, 20) for t in range(4))
