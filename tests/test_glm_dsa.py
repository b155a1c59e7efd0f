"""The latent stack's second architecture (`arch="glm_moe_dsa"`: GLM-5) at a
small size on the CPU against the plain reference of
benchmarks/references/glm_dsa_decoder.py: a learned indexer scores every
row a query may see and the query attends its `index_topk` best, through
a second cache of one indexer key a token a layer; a tile walked a chunk
at a time against both caches; the kernels of ops/sparse_attention.py in
the Pallas interpreter; and the tie to the stack's first architecture:
with every row chosen the attention is openPangu's. Logits and chosen
sets, never sampled tokens.
"""

import dataclasses
import importlib.util
import json
import math
import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import configs, latent
from ray_tpu.models.generate import (
    decode_multi,
    decode_step,
    first_token_sample,
    init_kv_cache,
    prefill,
    prefill_sample_batch,
)
from ray_tpu.models.transformer import (
    LATENT_FORMS,
    STACKS,
    TransformerConfig,
    forward,
    init_params,
)
from ray_tpu.ops import sparse_attention as sa
from ray_tpu.ops.flash_attention import NEG_INF

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_reference(name):
    spec = importlib.util.spec_from_file_location(
        name + "_ref", os.path.join(ROOT, "benchmarks", "references",
                                    name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load_reference("glm_dsa_decoder")
CFG = configs.tiny_glm_test()            # index_topk 8
ARCH = dataclasses.asdict(CFG)


@pytest.fixture(scope="module")
def params():
    w = jax.jit(lambda k: init_params(CFG, k))(jax.random.key(41))
    # A checkpoint's selection bias is not zero: one that moves choices.
    bias = jnp.linspace(-0.2, 0.2, 16, dtype=jnp.float32)
    w["routed_layers"]["router_bias"] = jnp.stack([bias, bias[::-1]])
    return w


@pytest.fixture
def chunk_of_16(monkeypatch):
    """Tiles longer than 16 rows walk 16 at a time."""
    monkeypatch.setattr(latent, "PREFILL_CHUNK", 16)


def _rel(got, want):
    err = np.asarray(got, np.float32) - np.asarray(want, np.float32)
    return float(np.sqrt(np.mean(err * err) / np.mean(want * want)))


def test_the_preset_is_the_published_shape_in_small(params):
    assert STACKS["glm_moe_dsa"] == STACKS["pangu_ultra_moe"] == "latent"
    assert LATENT_FORMS["glm_moe_dsa"].post_norms is False
    assert LATENT_FORMS["glm_moe_dsa"].router_bias is True
    assert LATENT_FORMS["pangu_ultra_moe"].post_norms is True
    assert latent.layer_plan(CFG) == [("dense_layers", (1,), False),
                                      ("routed_layers", (2,), True)]
    assert latent.routed_layers(CFG) == 2 and latent.routing_stats(CFG) == 5
    cache = jax.eval_shape(lambda: init_kv_cache(CFG, 3, 64))
    # Two arrays of rows: the latent vector with its rotary key in whole
    # lanes, and the indexer's one key a token a layer.
    assert cache.c.shape == (3, 3, 64, 128) and cache.ki.shape == (3, 3, 64,
                                                                   16)
    assert cache.k is None and cache.v is None and cache.kw is None
    attn = {"attn_norm", "wq_a", "q_a_norm", "wq_nope", "wq_rope", "wkv_a",
            "kv_a_norm", "wk_b", "wv_b", "wo", "ffn_norm",   # two norms
            "idx_wq", "idx_wk", "idx_k_norm", "idx_k_bias", "idx_wp"}
    assert set(params["dense_layers"]) == attn | {"w_gate", "w_up", "w_down"}
    assert set(params["routed_layers"]) == attn | {
        "router", "router_bias", "w_gate", "w_up", "w_down", "shared_gate",
        "shared_up", "shared_down"}
    assert params["routed_layers"]["idx_wq"].shape == (2, 32, 2 * 16)
    assert params["routed_layers"]["idx_wp"].shape == (2, 64, 2)
    assert CFG.num_params() == sum(x.size for x in jax.tree.leaves(params))
    # The first architecture's cache and leaves are what they were.
    pangu = configs.tiny_pangu_test()
    assert jax.eval_shape(lambda: init_kv_cache(pangu, 3, 64)).ki is None
    assert "post_attn_norm" in latent._layer_shapes(pangu, False)
    assert not any(k.startswith("idx_") or k == "router_bias"
                   for k in latent._layer_shapes(pangu, True))
    with pytest.raises(NotImplementedError, match="served only"):
        forward(CFG, params, jnp.zeros((1, 8), jnp.int32))
    with pytest.raises(ValueError, match="only the latent stack"):
        TransformerConfig(index_topk=8, index_n_heads=2, index_head_dim=16)


def test_the_published_widths_count_what_the_issue_reckoned():
    """The configuration file's widths give the parameters ISSUE 41
    counted: 165.0 M of latent attention and 9.37 M of indexer a layer,
    a dense layer 400.9 M, a routed one 817.7 M, 3.91 B in all, 7.82 GB
    in bf16; the file says the same."""
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "glm-5-l5-ep16.json")) as f:
        arch = json.load(f)
    from benchmarks.lib import modelcfg

    cfg = modelcfg.transformer_config(arch, {})
    assert cfg.arch == "glm_moe_dsa" and cfg.index_topk == 2048
    shapes = latent._layer_shapes(cfg, True)
    mla = sum(math.prod(shapes[k]) for k in (
        "wq_a", "wq_nope", "wq_rope", "wkv_a", "wk_b", "wv_b", "wo"))
    indexer = sum(math.prod(shapes[k]) for k in ("idx_wq", "idx_wk",
                                                  "idx_wp"))
    assert round(mla / 1e6, 1) == 165.0 and round(indexer / 1e6, 2) == 9.37
    dense = sum(math.prod(s) for s in latent._layer_shapes(cfg, False)
                .values())
    routed = sum(math.prod(s) for s in shapes.values())
    assert round(dense / 1e6, 1) == 400.9 and round(routed / 1e6, 1) == 817.7
    assert 3.909e9 < cfg.num_params() < 3.911e9
    assert "3.91 B parameters" in arch["deployment"] \
        and "7.82 GB" in arch["deployment"]
    assert (latent.cache_width(cfg), latent.cache_lanes(cfg)) == (576, 640)


# -- prefill, then decode through both caches ----------------------------------

def _serve(cfg, w, seqs, steps, rows=96):
    cache = init_kv_cache(cfg, 4, rows)
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


# Lengths either side of index_topk = 8: 5 tokens never choose, 40 choose
# a fifth of their rows at the end; decode crosses 8 rows for the first.
SEQS = (5, 40, 23)


@pytest.mark.parametrize("dtype,index_dtype,limit", [
    ("float32", None, 2e-5), ("bfloat16", None, 0.08),
    ("bfloat16", "float32", 0.08)])
def test_prefill_then_decode_match_the_reference(params, dtype, index_dtype,
                                                 limit, chunk_of_16):
    """The choice is made in the activation dtype unless the
    configuration names another (a name, as a cell's file gives it)."""
    cfg = dataclasses.replace(CFG, dtype=jnp.dtype(dtype).type,
                              index_dtype=index_dtype)
    rng = np.random.default_rng(7)
    seqs = [rng.integers(0, 256, size=n).tolist() for n in SEQS]
    got, full, cache = _serve(cfg, params, seqs, 6)
    assert cache.c.dtype == cfg.dtype
    assert cache.ki.dtype == jnp.dtype(index_dtype or dtype)
    for i, seq in enumerate(seqs):
        want = np.asarray(ref.forward_logits(ARCH, params, full[i][:-1]))
        assert _rel(np.stack(got[i]), want[len(seq) - 1:]) < limit, SEQS[i]


def test_the_fused_block_and_the_admission_tile_serve_the_same(params):
    rng = np.random.default_rng(8)
    seq = rng.integers(0, 256, size=27).tolist()
    buf = np.zeros((1, 32), np.int32)
    buf[0, :27] = seq
    cache = init_kv_cache(CFG, 2, 64)
    cache, toks, lps, extras = prefill_sample_batch(
        CFG, params, cache, jnp.asarray(buf), jnp.asarray([27]),
        jnp.asarray([1]), 0, jnp.zeros((1,)), jax.random.key(0))
    want = np.asarray(ref.forward_logits(ARCH, params, seq))
    assert int(toks[0]) == int(np.argmax(want[-1]))
    assert extras.routing.shape == (5,) and extras.exits is None
    early, _, _ = first_token_sample(CFG, params, jnp.asarray(buf),
                                     jnp.asarray([27]), jnp.zeros((1,)), 0,
                                     jax.random.key(0))
    assert int(early[0]) == int(toks[0])        # the queue side's, no cache
    cur = jnp.asarray([0, int(toks[0])], jnp.int32)
    live = jnp.asarray([False, True])
    cache, out, _, extras = decode_multi(
        CFG, params, cache, cur, jnp.zeros((2,)), 4, 0, jax.random.key(1),
        live)
    routed = extras.routing
    full = seq + [int(toks[0])] + [int(t) for t in out[:3, 1]]
    want = np.asarray(ref.forward_logits(ARCH, params, full))
    assert [int(t) for t in out[:, 1]] == [
        int(np.argmax(want[i])) for i in range(27, 31)]
    assert routed.shape == (5,) and int(routed[4]) == 4 * 2 * 2 * 2


# -- the chosen sets -------------------------------------------------------------

def test_a_tile_chooses_the_rows_the_reference_chooses(params, chunk_of_16):
    rng = np.random.default_rng(9)
    tokens = rng.integers(0, 256, size=64).tolist()
    ours = np.asarray(latent.chosen_rows(CFG, params, tokens))
    theirs = ref.chosen_rows(ARCH, params, tokens)
    assert ours.shape == (3, 64, 64)
    for l in range(3):
        assert np.array_equal(ours[l], np.asarray(theirs[l])), l
    counts = ours.sum(-1)
    # Every row a query sees while it sees no more than it may choose.
    assert np.array_equal(counts[0], np.minimum(np.arange(64) + 1, 8))
    assert not np.any(np.triu(ours[0], 1))              # none ahead
    ours_e = latent.chosen_experts(CFG, params, tokens)
    for a, b in zip(ours_e, ref.chosen_experts(ARCH, params, tokens)):
        assert np.array_equal(np.sort(np.asarray(a), -1),
                              np.sort(np.asarray(b), -1))


def test_a_decode_step_chooses_the_rows_the_reference_chooses(params):
    rng = np.random.default_rng(10)
    seq = rng.integers(0, 256, size=30).tolist()
    buf = np.zeros((1, 32), np.int32)
    buf[0, :30] = seq
    cache = init_kv_cache(CFG, 3, 64)
    cache, _ = prefill(CFG, params, cache, jnp.asarray(buf), jnp.int32(30),
                       jnp.int32(2))
    step = jax.jit(partial(latent.decode_chosen_rows, CFG))
    nxt = rng.integers(0, 256, size=4).tolist()
    theirs = ref.chosen_rows(ARCH, params, seq + nxt)
    for t, tok in enumerate(nxt):
        cur = jnp.asarray([0, 0, tok], jnp.int32)
        live = jnp.asarray([False, True, True])
        cache, _, rows = step(params, cache, cur, live)
        rows = np.asarray(rows)
        assert rows.shape == (3, 3, 8)
        for l in range(3):
            want = np.flatnonzero(np.asarray(theirs[l])[30 + t])
            assert sorted(rows[l, 2].tolist()) == want.tolist(), (t, l)
    # Slot 1 is owned and holds 4 rows by now: all of them, first.
    assert sorted(rows[0, 1, :4].tolist()) == [0, 1, 2, 3]


def test_ties_go_to_the_lower_row():
    scores = jnp.asarray([[[1.0, 3.0, 3.0, 0.5, 3.0, 3.0, 2.0, -jnp.inf],
                           [0.0, 0.0, 0.0, 0.0, 0.0, 0.0, 0.0, 0.0],
                           [5.0, -jnp.inf, -jnp.inf, -jnp.inf, -jnp.inf,
                            -jnp.inf, -jnp.inf, -jnp.inf]]])
    chosen = np.asarray(sa.topk_bias(scores, 3, dtype=jnp.float32)) == 0
    assert chosen[0, 0].tolist() == [False, True, True, False, True, False,
                                     False, False]
    assert chosen[0, 1].tolist() == [True] * 3 + [False] * 5
    assert chosen[0, 2].tolist() == [True] + [False] * 7   # no -inf chosen
    _, idx = jax.lax.top_k(scores[0], 3)                   # a step's choice
    assert np.asarray(idx)[0].tolist() == [1, 2, 4]
    assert np.asarray(idx)[1].tolist() == [0, 1, 2]


# -- a tile in chunks ------------------------------------------------------------

def test_a_chunked_tile_equals_the_same_tile_in_one_chunk(params,
                                                          monkeypatch):
    rng = np.random.default_rng(11)
    tokens = jnp.asarray(rng.integers(0, 256, size=(2, 64)), jnp.int32)
    lengths, slots = jnp.asarray([40, 20]), jnp.asarray([2, 0])

    def run(chunk):
        monkeypatch.setattr(latent, "PREFILL_CHUNK", chunk)
        assert latent.chunk_rows(CFG, 64) == min(chunk, 64)
        cache = init_kv_cache(CFG, 3, 96)
        return jax.jit(partial(latent.prefill, CFG))(
            params, cache, tokens, lengths, slots)

    (one, x1, e1), (many, x2, e2) = run(64), run(16)
    s1, s2 = e1.routing, e2.routing
    # 40 tokens end in the third chunk of 16: the fourth is not run.
    assert latent.prefill_chunks(CFG, 64, 40) == (3, 4)
    assert np.allclose(np.asarray(x1)[:, :48], np.asarray(x2)[:, :48],
                       atol=2e-5)
    assert not np.any(np.asarray(x2)[:, 48:])
    for a, b in ((one.c, many.c), (one.ki, many.ki)):
        assert np.allclose(np.asarray(a)[:, :, :48], np.asarray(b)[:, :, :48],
                           atol=2e-5)
        assert not np.any(np.asarray(b)[:, :, 48:])       # never written
        assert not np.any(np.asarray(b)[:, 1])            # nobody's slot
    assert np.array_equal(np.asarray(one.seq_lens), np.asarray(many.seq_lens))
    # The routing counts are over the positions run: 48 of 64 a row.
    assert int(s2[4]) * 4 == int(s1[4]) * 3
    # A stack without an indexer never chunks.
    assert latent.chunk_rows(configs.tiny_pangu_test(), 64) == 64


def test_a_tile_counts_the_columns_its_choices_count(params, chunk_of_16):
    """`engine.prefill_tile`'s `choice_columns` of `choice_columns_of`:
    what `_attend_chunk` tells `topk_bias` of where each block of queries
    stands, reckoned on the host."""
    from types import SimpleNamespace

    from ray_tpu.serve.llm import LLMEngine

    real = dataclasses.replace(CFG, index_topk=2048)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(latent, "PREFILL_CHUNK", 2048)
        # 20,000 tokens run ten chunks of the 32,768 bucket's sixteen:
        # twenty blocks of 1,024 queries see 1,024 ... 20,480 columns.
        assert latent.choice_columns(real, 32768, 20000) == (
            210 * 1024, 640 * 1024)
        assert latent.choice_columns(real, 32768, 32768) == (
            528 * 1024, 1024 * 1024)
        assert latent.choice_columns(real, 16384, 9000) == (
            55 * 1024, 160 * 1024)
        # One chunk no longer than `index_topk`: every row is chosen.
        assert latent.choice_columns(real, 2048, 1500) == (0, 0)
        assert latent._choice_rows(2048) == 1024
        assert sa.columns_counted(np.asarray([4096, 5120]), 1024,
                                  32768).tolist() == [5120, 6144]
    # 70 tokens: five chunks of 16 of the 128 bucket's eight, one block of
    # queries each, one block of 128 columns counted for each.
    eng = LLMEngine(CFG, params, num_slots=2, max_seq_len=128, seed=0)
    req = SimpleNamespace(prompt=[1] * 70, id=7)
    tile = eng._tile_span("slot", 128, 1, [req])
    assert (tile.attributes["choice_columns"],
            tile.attributes["choice_columns_of"]) == (5 * 128, 5 * 128)
    eng._tile_span("queue", 128, 1, [req])
    assert (eng.counts["choice_columns"],
            eng.counts["choice_columns_of"]) == (13 * 128, 13 * 128)
    # A stack without an indexer chooses nothing and counts nothing.
    pangu = configs.tiny_pangu_test()
    eng = LLMEngine(pangu, jax.jit(lambda k: init_params(pangu, k))(
        jax.random.key(2)), num_slots=2, max_seq_len=128, seed=0)
    tile = eng._tile_span("slot", 128, 1, [req])
    assert not {"choice_columns", "chunks"} & set(tile.attributes)
    assert not {"choice_columns", "prefill_chunks"} & set(eng.counts)


# -- the tie between the stack's two architectures --------------------------------

def test_with_every_row_chosen_the_attention_is_the_first_architectures(
        params):
    """`index_topk` >= the rows held: a tile attends itself causally
    through `_attend_tile` and a step reads every held row, as the stack
    without an indexer does; the reference of that architecture, given
    the same attention weights (its two post norms at gain one are not
    this layer's, so the comparison is of the attention branch alone)."""
    cfg = dataclasses.replace(CFG, index_topk=64)
    rng = np.random.default_rng(12)
    x = jnp.asarray(rng.normal(size=(1, 24, 64)), jnp.float32)
    lp = jax.tree.map(lambda a: a[0], params["dense_layers"])
    rope = latent._rope_tables(cfg, 24)
    q_nope, q_r, row, h = latent._project(cfg, lp, x, rope)
    pangu = configs.tiny_pangu_test()
    want = latent._attend_tile(pangu, lp, q_nope, q_r, row)
    idx = latent._index_project(cfg, lp, h, rope)
    c, ki = latent._scratch(cfg, 1, 32)
    got, (c, ki, _) = latent._attend_chunk(
        cfg, jnp.arange(1), 0, 24, jnp.int32(0), lp, q_nope, q_r, row, idx,
        (c, ki, None))
    assert np.array_equal(np.asarray(got), np.asarray(want))
    # A bucket longer than index_topk with every row still chosen: the
    # masked order gives the same sum.
    cfg8 = dataclasses.replace(CFG, index_topk=23)
    got8, _ = latent._attend_chunk(
        cfg8, jnp.arange(1), 0, 24, jnp.int32(0), lp, q_nope, q_r, row,
        latent._index_project(cfg8, lp, h, rope),
        latent._scratch(cfg8, 1, 32) + (None,))
    assert np.allclose(np.asarray(got8)[:, :23], np.asarray(want)[:, :23],
                       atol=1e-6)
    # A step against the rows the tile wrote, every one chosen, against
    # the same step of the first architecture.
    x1 = jnp.asarray(rng.normal(size=(1, 1, 64)), jnp.float32)
    pos = jnp.asarray([24])
    rope1 = latent._rope_tables(cfg, 32, pos)
    q1, r1, row1, h1 = latent._project(cfg, lp, x1, rope1)
    want1, _ = latent._attend_rows(pangu, pos, None, jnp.int32(0), lp, q1,
                                   r1, row1, None, (c, None, None))
    got1, _ = latent._attend_rows(
        cfg, pos, None, jnp.int32(0), lp, q1, r1, row1,
        latent._index_project(cfg, lp, h1, rope1), (c, ki, None))
    assert np.allclose(np.asarray(got1), np.asarray(want1), atol=1e-6)


# -- the sixteen shares -----------------------------------------------------------

def test_the_shares_add_up_to_the_uncut_layer(params):
    """Four chips of four experts each: their parts, with the shared
    expert counted once, are the layer that holds all sixteen, selection
    bias and all."""
    rng = np.random.default_rng(13)
    m = jnp.asarray(rng.normal(size=(12, 64)), jnp.float32)
    lp = jax.tree.map(lambda a: a[0], params["routed_layers"])
    key = jax.random.key(5)
    every = {n: 0.02 * jax.random.normal(jax.random.fold_in(key, i), (
        16,) + lp[n].shape[1:]) for i, n in enumerate(
        ("w_gate", "w_up", "w_down"))}
    uncut = ref.routed_layer_output(
        dict(ARCH, moe_experts=16, moe_first_expert=0), dict(lp, **every), m)
    shared = ref._swiglu(m, *(lp[n] for n in ("shared_gate", "shared_up",
                                              "shared_down")))
    total = shared
    for first in (0, 4, 8, 12):
        part = {n: every[n][first:first + 4] for n in every}
        total = total + ref.routed_layer_output(
            dict(ARCH, moe_first_expert=first), dict(lp, **part), m) - shared
    assert np.allclose(np.asarray(total), np.asarray(uncut), atol=1e-6)
    # The bias moved at least one choice, and entered no weight.
    plain = ref._route(m, lp["router"], jnp.zeros((16,)), 2, True, 2.5)
    biased = ref._route(m, lp["router"], lp["router_bias"], 2, True, 2.5)
    assert not np.array_equal(np.asarray(plain[1]), np.asarray(biased[1]))
    sc = jax.nn.sigmoid(m @ lp["router"])
    picked = np.take_along_axis(np.asarray(sc), np.asarray(biased[1]), -1)
    assert np.allclose(np.asarray(biased[0]).sum(-1), 2.5, atol=1e-5)
    assert np.allclose(
        np.take_along_axis(np.asarray(biased[0]), np.asarray(biased[1]), -1),
        2.5 * picked / picked.sum(-1, keepdims=True), atol=1e-6)


# -- the kernels, in the interpreter ----------------------------------------------

@pytest.mark.parametrize("dtype,tol", [(jnp.bfloat16, 2e-2),
                                       (jnp.float32, 2e-5)])
def test_the_decode_scorer_scores_the_rows_held_and_no_other(dtype, tol):
    """One sum in XLA: what a row past a slot's last holds counts for
    nothing, and such a row scores `-inf`."""
    rng = np.random.default_rng(14)
    k_all = rng.normal(size=(2, 3, 256, 128)).astype(np.float32)
    q = rng.normal(size=(3, 4, 128)).astype(np.float32)
    w = rng.normal(size=(3, 4)).astype(np.float32)
    n_rows = np.asarray([200, 0, 129])
    held = np.arange(256)[None, :] < n_rows[:, None]
    want = np.einsum("bh,bhs->bs", w, np.maximum(np.einsum(
        "bhd,bsd->bhs", np.asarray(jnp.asarray(q, dtype), np.float32),
        np.asarray(jnp.asarray(k_all[1], dtype), np.float32)), 0.0))
    poisoned = jnp.where(held[None, :, :, None], jnp.asarray(k_all, dtype),
                         jnp.nan)
    got = np.asarray(sa.index_scores_rows(
        jnp.asarray(q, dtype), jnp.asarray(w), poisoned, jnp.int32(1),
        jnp.asarray(n_rows)))
    assert np.array_equal(np.isfinite(got), held)
    assert np.all(got[~held] == -np.inf)
    assert np.allclose(got[held], want[held], rtol=tol, atol=tol * 10)


def test_a_decode_step_reads_no_latent_row_it_did_not_choose(params):
    """Every held row outside a layer's chosen set poisoned: the step's
    logits do not move, so the attention read the chosen rows alone."""
    rng = np.random.default_rng(19)
    seq = rng.integers(0, 256, size=30).tolist()
    buf = np.zeros((1, 32), np.int32)
    buf[0, :30] = seq
    cache = init_kv_cache(CFG, 2, 64)
    cache, _ = prefill(CFG, params, cache, jnp.asarray(buf), jnp.int32(30),
                       jnp.int32(1))
    step = jax.jit(partial(latent.decode_chosen_rows, CFG))
    cur = jnp.asarray([0, 7], jnp.int32)
    live = jnp.asarray([False, True])
    _, logits, rows = step(params, cache, cur, live)
    rows = np.asarray(rows)[:, 1]                       # (L, 8) of slot 1
    unchosen = np.ones((3, 64), bool)
    unchosen[np.arange(3)[:, None], rows] = False
    unchosen[:, 30:] = False        # the step's own row, and rows not held
    # 8 of a layer's 31 rows chosen, the step's own perhaps among them.
    assert 3 * 22 <= unchosen.sum() <= 3 * 23
    c = jnp.where(jnp.asarray(unchosen)[:, None, :, None]
                  & (jnp.arange(2) == 1)[None, :, None, None],
                  jnp.nan, cache.c)
    _, poisoned, again = step(params, cache._replace(c=c), cur, live)
    assert np.array_equal(np.asarray(again)[:, 1], rows)
    assert np.array_equal(np.asarray(poisoned)[1], np.asarray(logits)[1])
    assert np.all(np.isfinite(np.asarray(poisoned)[1]))


@pytest.mark.parametrize("dtype,tol", [(jnp.bfloat16, 2e-2),
                                       (jnp.float32, 2e-4)])
def test_the_tile_scorer_kernel_applies_the_causal_edge(dtype, tol):
    rng = np.random.default_rng(15)
    q = jnp.asarray(rng.normal(size=(1, 256, 2, 128)), dtype)
    w = jnp.asarray(rng.normal(size=(1, 256, 2)), jnp.float32)
    keys = jnp.asarray(rng.normal(size=(1, 1024, 128)), dtype)
    got = np.asarray(sa.index_scores_tile(q, w, keys, 512, interpret=True))
    want = np.asarray(sa.index_scores_tile(q, w, keys, 512))
    seen = np.arange(1024)[None, :] <= 512 + np.arange(256)[:, None]
    assert np.array_equal(np.isfinite(got[0]), seen)
    assert np.allclose(got[0][seen], want[0][seen], rtol=tol, atol=tol * 10)


@pytest.mark.parametrize("ties", [False, True])
def test_the_threshold_kernel_chooses_exactly_k(ties):
    rng = np.random.default_rng(16)
    scores = rng.normal(size=(1, 16, 512)).astype(np.float32)
    if ties:
        scores = np.round(scores * 2) / 2          # dozens at the threshold
    seen = np.arange(512)[None, :] <= 40 * np.arange(16)[:, None] + 3
    scores = jnp.asarray(np.where(seen[None], scores, -np.inf))
    got = np.asarray(sa.topk_bias(scores, 64, dtype=jnp.float32,
                                  interpret=True))
    want = np.asarray(sa.topk_bias(scores, 64, dtype=jnp.float32))
    assert np.array_equal(got, want)
    assert np.array_equal((got == 0).sum(-1)[0],
                          np.minimum(seen.sum(-1), 64))
    assert set(np.unique(got)) == {0.0, np.float32(NEG_INF)}


# What a block of queries at `q_offset` hands `topk_bias`: (S, T, k,
# q_offset, what the scores hold). Columns come 1,024 to a block, so 3,072
# are three; T = 32 is two blocks of the kernel's rows.
CHOICES = {
    # Rows see 1 ... 32 columns: fewer than k, k and more than k.
    "first_block": (2048, 32, 16, 0, "normal"),
    "k_above_every_row": (2048, 32, 64, 0, "normal"),
    "mid_bucket": (3072, 32, 64, 1024, "normal"),
    "last_block": (3072, 32, 64, 3072 - 32, "normal"),
    # The last query's column is the 1,031st: two column blocks counted.
    "edge_inside_a_column_block": (3072, 32, 64, 1000, "normal"),
    "k_of_the_bucket": (2048, 32, 2048, 2048 - 32, "normal"),
    "no_offset": (2048, 32, 64, None, "normal"),
    # Whatever stands past the counted columns is not read.
    "unread_past_the_edge": (3072, 32, 64, 1000, "poison"),
    # Dozens at the threshold in every second row (the cond's path).
    "ties_beside_none": (3072, 32, 64, 1024, "ties"),
    "ties_in_the_first_block": (2048, 32, 16, 0, "ties"),
    # The k-th place at 1e-40, 0.0, -0.0, -1e-40, -0.5 or -0.75 by the row,
    # equal negative values below it; then the same values drawn, so that
    # rows tie at them; then fewer columns than k of them.
    "threshold_at_zeros_and_denormals": (2048, 32, 48, 1024, "ladder"),
    "ties_at_zeros_and_denormals": (2048, 32, 48, 1024, "drawn"),
    "zeros_below_k_columns": (2048, 32, 48, 0, "drawn"),
}
_LADDER = [1e-40, 0.0, -0.0, -1e-40, -0.5, -0.75]


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("case", sorted(CHOICES))
def test_the_choice_kernel_is_the_definition_bit_for_bit(case, dtype):
    """`topk_bias` as one kernel that counts the columns its block may
    see (the interpreter) against its definition in XLA over whole
    rows."""
    S, T, k, q_offset, holds = CHOICES[case]
    rng = np.random.default_rng(sorted(CHOICES).index(case))
    scores = rng.normal(size=(1, T, S)).astype(np.float32)
    if holds == "ties":
        scores[:, ::2] = np.round(scores[:, ::2] * 2) / 2
    if holds == "drawn":
        scores = rng.choice(np.asarray(_LADDER + [-1.5, 0.5], np.float32),
                            size=(1, T, S))
    if holds == "ladder":
        for t in range(T):
            row = np.full(q_offset + t + 1, -1.5, np.float32)
            above = k - 1 - t % len(_LADDER)
            row[:above] = 1 + rng.permutation(above) / above
            row[above:above + len(_LADDER)] = _LADDER
            scores[0, t, :len(row)] = rng.permutation(row)
    first = 0 if q_offset is None else q_offset
    seen = np.arange(S)[None, :] <= first + np.arange(T)[:, None] \
        if q_offset is not None else np.ones((T, S), bool)
    scores = np.where(seen[None], scores, -np.inf).astype(np.float32)
    want = np.asarray(sa._topk_bias_xla(jnp.asarray(scores), k, dtype))
    if holds == "poison":
        counted = sa.columns_counted(q_offset, T, S)
        assert counted == 2048 and not seen[:, counted:].any()
        scores[..., counted:] = np.nan
    got = np.asarray(jax.jit(partial(sa.topk_bias, k=k, dtype=dtype,
                                     interpret=True))(
        jnp.asarray(scores), q_offset=q_offset))
    assert got.dtype == want.dtype == dtype
    assert np.array_equal(got.view(np.uint8), want.view(np.uint8))
    assert np.array_equal((got == 0).sum(-1)[0],
                          np.minimum(seen.sum(-1), k))


def test_the_masked_attention_kernel_and_the_merge_of_its_parts():
    rng = np.random.default_rng(17)
    q, k, v = (jnp.asarray(rng.normal(size=(1, 128, 2, 128)), jnp.bfloat16)
               for _ in range(3))
    chosen = rng.random(size=(1, 128, 128)) < 0.2
    chosen[0, 7] = False                              # a query with no row
    bias = jnp.asarray(np.where(chosen, 0.0, NEG_INF), jnp.bfloat16)
    out, lse = sa.masked_attention(q, k, v, bias, 0.09, interpret=True)
    want, want_lse = sa.masked_attention(q, k, v, bias, 0.09)
    assert np.allclose(np.asarray(out), np.asarray(want), atol=2e-2)
    assert np.allclose(np.asarray(lse), np.asarray(want_lse), atol=2e-2)
    assert not np.any(np.asarray(out)[0, 7]) \
        and np.all(np.asarray(lse)[0, 7] == np.float32(NEG_INF))
    # Two halves of the keys, merged, are the whole (float32: a bf16
    # probability is rounded against its own half's sum).
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    want, want_lse = sa.masked_attention(q, k, v, bias, 0.09)
    halves = [sa.masked_attention(q, k[:, s], v[:, s], bias[:, :, s], 0.09)
              for s in (slice(0, 64), slice(64, 128))]
    both, both_lse = sa.merge_parts(*halves[0], *halves[1])
    assert np.allclose(np.asarray(both), np.asarray(want), atol=1e-5)
    assert np.allclose(np.asarray(both_lse)[0, :7],
                       np.asarray(want_lse)[0, :7], atol=1e-5)
    assert not np.any(np.asarray(both)[0, 7])


# -- the engine -------------------------------------------------------------------

def test_the_engine_serves_it_and_counts_the_rows_read(params, chunk_of_16):
    from ray_tpu.serve.llm import LLMEngine

    eng = LLMEngine(CFG, params, num_slots=2, max_seq_len=128, seed=0,
                    decode_block=4)
    rng = np.random.default_rng(18)
    prompts = [rng.integers(0, 256, size=n).tolist() for n in (70, 20)]
    reqs = [eng.submit(p, max_new_tokens=6, temperature=0.0)
            for p in prompts]
    while any(r.finish_ts == 0.0 for r in reqs):
        eng.step()
    for p, r in zip(prompts, reqs):
        want = np.asarray(ref.forward_logits(ARCH, params,
                                             p + r.tokens[:-1]))
        assert r.tokens == [int(np.argmax(want[i]))
                            for i in range(len(p) - 1, len(p) + 5)]
    c = eng.stats()["counts"]
    assert "index_rows_scored" not in c    # = `cache_rows_held`: one name
    # Every step of either slot holds more than 8 rows: 8 read a step.
    assert 0 < c["sparse_rows_read"] < c["cache_rows_held"]
    assert c["sparse_rows_read"] % 8 == 0
    # 70 tokens: five chunks of 16 of the 128 bucket's eight; 20: two of
    # the 32 bucket's two.
    assert (c["prefill_chunks"], c["prefill_chunks_of"]) == (5 + 2, 8 + 2)
