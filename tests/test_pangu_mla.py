"""The latent-attention stack (`arch="pangu_ultra_moe"`: openPangu-Ultra-MoE)
at a small size on the CPU against the plain reference of
benchmarks/references/pangu_mla_decoder.py: prefill and decode through a
cache of one vector a token a layer, the two orders of the same products
(a tile attends per head after the up-projection, a decode step in the
latent space), an expert layer that holds a share of the experts its
router scores, and the two kernels at the shapes' small analogues in the
Pallas interpreter. Logits, never sampled tokens.
"""

import dataclasses
import importlib.util
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import configs, latent, moe
from ray_tpu.models.generate import (
    decode_multi,
    decode_step,
    first_token_sample,
    init_kv_cache,
    prefill,
    prefill_sample_batch,
)
from ray_tpu.models.transformer import STACKS, forward, init_params
from ray_tpu.ops import decode_attention as da
from ray_tpu.ops import flash_attention

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_reference():
    spec = importlib.util.spec_from_file_location(
        "pangu_mla_decoder_ref", os.path.join(
            ROOT, "benchmarks", "references", "pangu_mla_decoder.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load_reference()
CFG = configs.tiny_pangu_test()
ARCH = dataclasses.asdict(CFG)


@pytest.fixture(scope="module")
def params():
    return jax.jit(lambda k: init_params(CFG, k))(jax.random.key(3))


def _rel(got, want):
    err = np.asarray(got, np.float32) - np.asarray(want, np.float32)
    return float(np.sqrt(np.mean(err * err) / np.mean(want * want)))


def test_the_preset_is_the_published_shape_in_small(params):
    assert STACKS["pangu_ultra_moe"] == "latent"
    assert latent.layer_plan(CFG) == [("dense_layers", (1,), False),
                                      ("routed_layers", (2,), True)]
    assert latent.routed_layers(CFG) == 2 and latent.routing_stats(CFG) == 5
    cache = jax.eval_shape(lambda: init_kv_cache(CFG, 3, 64))
    # One vector a token a layer, latent + rotary key in whole lanes, and
    # nothing a head.
    assert cache.c.shape == (3, 3, 64, 128) and latent.cache_width(CFG) == 40
    assert cache.k is None and cache.v is None and cache.kw is None
    assert (cache.max_seq_len, cache.num_slots) == (64, 3)
    assert configs.get("tiny_pangu") == CFG and hash(CFG) == hash(
        configs.tiny_pangu_test())
    assert set(params) == {"embed", "lm_head", "final_norm", "dense_layers",
                           "routed_layers"}
    attn = {"attn_norm", "wq_a", "q_a_norm", "wq_nope", "wq_rope", "wkv_a",
            "kv_a_norm",
            "wk_b", "wv_b", "wo", "post_attn_norm", "ffn_norm",
            "post_ffn_norm"}                    # four norms a layer + MLA's
    assert set(params["dense_layers"]) == attn | {"w_gate", "w_up", "w_down"}
    assert set(params["routed_layers"]) == attn | {
        "router", "w_gate", "w_up", "w_down", "shared_gate", "shared_up",
        "shared_down"}
    # The router keeps its whole width; the layer holds 4 of its 16.
    assert params["routed_layers"]["router"].shape == (2, 64, 16)
    assert params["routed_layers"]["w_gate"].shape == (2, 4, 64, 32)
    assert CFG.num_params() == sum(x.size for x in jax.tree.leaves(params))
    with pytest.raises(NotImplementedError, match="served only"):
        forward(CFG, params, jnp.zeros((1, 8), jnp.int32))


def test_the_published_widths_count_what_the_issue_reckoned():
    """The configuration file's widths give the parameters ISSUE 34
    counted: 196.58 M of latent attention a layer, 4,918.9 M in all."""
    import json

    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "openpangu-ultra-moe-l5-ep16.json")) as f:
        arch = json.load(f)
    from benchmarks.lib import modelcfg

    cfg = modelcfg.transformer_config(arch, {})
    shapes = latent._layer_shapes(cfg, True)
    mla = sum(math.prod(shapes[k]) for k in (
        "wq_a", "wq_nope", "wq_rope", "wkv_a", "wk_b", "wv_b", "wo"))
    assert round(mla / 1e6, 2) == 196.58
    # The issue's count is of matmul parameters; the norms add 0.2 M.
    assert 4918.9 < cfg.num_params() / 1e6 < 4919.2
    assert (latent.cache_width(cfg), latent.cache_lanes(cfg)) == (576, 640)


# -- prefill, then decode through the latent cache ----------------------------

def _serve(cfg, w, seqs, steps):
    """Each sequence prefilled into its slot, then `steps` decode steps of
    all: the logits of every position served, and the sequences as they
    grew."""
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
def test_prefill_then_decode_through_the_latent_cache(params, lens):
    """A tile attends per head after the up-projection, the 12 decode
    steps behind it in the latent space over the rows the tile left:
    every logit against the reference's full forward (no cache, no
    absorption) over the same tokens, given the same share of the
    experts. float32 on both sides: what is left is the order of sums."""
    rng = np.random.default_rng(sum(lens))
    seqs = [rng.integers(0, 256, size=n).tolist() for n in lens]
    got, full, cache = _serve(CFG, params, seqs, 12)
    assert list(np.asarray(cache.seq_lens)[:3]) == [n + 12 for n in lens]
    for i, seq in enumerate(seqs):
        want = np.asarray(ref.forward_logits(ARCH, params, full[i][:-1]))
        assert _rel(np.stack(got[i]), want[len(seq) - 1:]) < 1e-5


def _fp8(w):
    """Matmul weights rounded to 8-bit floats (2^-4 a rounding)."""
    return jax.tree.map(
        lambda a: a.astype(jnp.float8_e4m3fn).astype(a.dtype)
        if a.ndim >= 2 else a, w)


@pytest.mark.parametrize("lens", [[5, 20, 12], [40, 3, 9]])
def test_bf16_weights_under_float32_and_bf16_activations(lens):
    """The benchmark's precision at a small size: bf16 weights. Float32
    activations (two bf16 terms a product, a float32 cache): against the
    reference on the same weights only the head's product rounds (2^-9 an
    operand: under 4e-3 of the logits' rms at every position). bf16
    activations: every product and the cached row round to 2^-9, the
    absorbed query and the weighted latent once more each: a position
    reads 0.005-0.012 at these three layers of width 64, several times
    further off, except where a rounded score chooses another expert than
    the reference's and the expert is one of the 4 held (one position in
    sixty here, 0.14: a flip moves a token's logits by a tenth of their
    size). So the bound is on the median position, and on how many
    positions may lie past it. The control, the same program on weights
    rounded to 8-bit floats against the reference on the sound ones,
    reads over 0.03 at its median position: past either limit."""
    cfg = dataclasses.replace(CFG, param_dtype=jnp.bfloat16)
    arch = dataclasses.asdict(cfg)
    w = jax.jit(lambda k: init_params(cfg, k))(jax.random.key(5))
    rng = np.random.default_rng(sum(lens) + 1)
    seqs = [rng.integers(0, 256, size=n).tolist() for n in lens]

    def by_position(c, weights):
        got, full, cache = _serve(c, weights, seqs, 10)
        assert cache.c.dtype == c.dtype and cache.c.shape[-1] == 128
        assert not np.any(np.asarray(cache.c[..., 40:], np.float32))
        errs = []
        for i, seq in enumerate(seqs):
            want = np.asarray(ref.forward_logits(
                arch, w, full[i][:-1]))[len(seq) - 1:]
            err = np.stack(got[i]) - want
            errs.append(np.sqrt(np.mean(err * err, -1)
                                / np.mean(want * want, -1)))
        return np.concatenate(errs)

    exact = by_position(cfg, w)
    assert exact.max() < 4e-3
    half = dataclasses.replace(cfg, dtype=jnp.bfloat16)
    rounded = by_position(half, w)
    assert 2 * np.median(exact) < np.median(rounded) < 0.012
    assert np.sum(rounded > 0.02) <= 2
    assert np.median(by_position(half, _fp8(w))) > 0.03
    assert np.median(by_position(cfg, _fp8(w))) > 0.03


def test_the_program_chooses_the_references_experts(params):
    tokens = np.random.default_rng(1).integers(0, 256, size=37).tolist()
    ours = latent.chosen_experts(CFG, params, tokens)
    theirs = ref.chosen_experts(ARCH, params, tokens)
    assert len(ours) == len(theirs) == 2
    for a, b in zip(ours, theirs):
        assert a.shape == (37, 2) and int(np.max(a)) >= 8   # of all 16
        assert np.array_equal(np.sort(np.asarray(a), -1),
                              np.sort(np.asarray(b), -1))


# -- the two orders of the same products --------------------------------------

def test_absorbed_decode_is_unabsorbed_attention_over_the_same_rows(params):
    """`W_UK` folded into the query and `W_UV` onto the weighted rows
    against up-projecting every held row and attending per head, on the
    same latent rows of one layer."""
    lp = jax.tree.map(lambda a: a[0], params["dense_layers"])
    H, kvr, nope, rope, vd = 4, 32, 16, 8, 16
    B, S = 3, 24
    ks = jax.random.split(jax.random.key(9), 4)
    c_all = jnp.pad(jax.random.normal(ks[0], (1, B, S, kvr + rope),
                                      jnp.float32),
                    ((0, 0),) * 3 + ((0, 128 - kvr - rope),))
    q_nope = jax.random.normal(ks[1], (B, 1, H, nope), jnp.float32)
    q_r = jax.random.normal(ks[2], (B, 1, H, rope), jnp.float32)
    row = jnp.pad(jax.random.normal(ks[3], (B, 1, kvr + rope), jnp.float32),
                  ((0, 0), (0, 0), (0, 128 - kvr - rope)))
    positions = jnp.asarray([5, 23, 11], jnp.int32)
    got, (after, _, _) = latent._attend_rows(
        CFG, positions, None, jnp.int32(0), lp, q_nope, q_r, row, None,
        (c_all, None, None))
    assert np.array_equal(after[0, 1, 23], row[1, 0])
    rows = np.asarray(after[0])
    want = np.zeros((B, H, vd), np.float32)
    wk, wv = np.asarray(lp["wk_b"]), np.asarray(lp["wv_b"])
    for b in range(B):
        held = rows[b, :int(positions[b]) + 1]
        k_nope = np.einsum("sc,hdc->shd", held[:, :kvr], wk)
        v = np.einsum("sc,hcd->shd", held[:, :kvr], wv)
        s = (np.einsum("hd,shd->hs", np.asarray(q_nope[b, 0]), k_nope)
             + np.asarray(q_r[b, 0]) @ held[:, kvr:kvr + rope].T) \
            / math.sqrt(24)
        p = np.exp(s - s.max(-1, keepdims=True))
        want[b] = np.einsum("hs,shd->hd", p / p.sum(-1, keepdims=True), v)
    np.testing.assert_allclose(np.asarray(got).reshape(B, H, vd), want,
                               rtol=0, atol=2e-5)


# -- an expert layer that holds a share ---------------------------------------

def _routed_layer(cfg, key):
    w = jax.jit(lambda k: init_params(cfg, k))(key)
    return jax.tree.map(lambda a: a[0], w["routed_layers"])


def test_all_sixteen_shares_add_up_to_the_uncut_layer():
    """The share is tied to the model: over all 16 shares of a 256-expert
    layer (top 8, as published; tiny widths), the routed parts added up,
    with the shared expert counted once, equal the uncut reference's
    layer output on the same tokens."""
    whole = configs.tiny_pangu_test(router_experts=256, held=256, first=0)
    whole = dataclasses.replace(whole, moe_top_k=8)
    lp = _routed_layer(whole, jax.random.key(11))
    m = jax.random.normal(jax.random.key(12), (40, 64), jnp.float32)
    want = ref.routed_layer_output(dataclasses.asdict(whole), lp, m)
    shared = latent._swiglu(m, lp["shared_gate"], lp["shared_up"],
                            lp["shared_down"])
    total, pairs = np.asarray(shared), 0
    for share in range(16):
        cfg = dataclasses.replace(whole, moe_experts=16,
                                  moe_first_expert=16 * share)
        part = {k: (v[16 * share:16 * share + 16] if k in moe.EXPERT_LEAVES
                    else v) for k, v in lp.items()}
        out, stats, experts = moe.routed_ffn(cfg, part, m, jnp.float32)
        assert experts.shape == (40, 8) and int(stats[4]) == 40 * 8
        # The reference given the same share leaves out the same experts.
        alone = ref.routed_layer_output(dataclasses.asdict(cfg), part, m)
        np.testing.assert_allclose(np.asarray(out + shared), alone,
                                   rtol=0, atol=2e-6)
        total = total + np.asarray(out)
        pairs += int(stats[1])
        assert int(stats[3]) == int(stats[1])      # each one taken
    assert pairs == 40 * 8                     # every pair is some share's
    np.testing.assert_allclose(total, want, rtol=0, atol=5e-6)
    # And with every expert held, the layer takes the path that holds all.
    out, stats, _ = moe.routed_ffn(whole, lp, m, jnp.float32)
    assert stats.shape == (4,)
    np.testing.assert_allclose(np.asarray(out + shared), want, rtol=0,
                               atol=5e-6)


def _pinned_router(lp, experts):
    """A router whose 2 largest scores are `experts`' for every token."""
    router = np.full(lp["router"].shape, -1.0, np.float32)
    router[:, list(experts)] = 1.0
    return dict(lp, router=jnp.asarray(router))


@pytest.mark.parametrize("tokens", [5, 200])
def test_every_token_on_held_experts_and_none_is_dropped(tokens):
    """A load in which every token chooses held experts only: 2 x tokens
    pairs kept, twice what a pass takes at 200 tokens (the loop runs
    more than one), and the result is the reference's."""
    lp = _pinned_router(_routed_layer(CFG, jax.random.key(13)), (5, 6))
    m = jnp.abs(jax.random.normal(jax.random.key(14), (tokens, 64))) + 0.1
    out, stats, experts = moe.routed_ffn(CFG, lp, m, jnp.float32)
    assert set(np.asarray(experts).ravel()) == {5, 6}
    assert [int(x) for x in stats] == [2, 2 * tokens, tokens,
                                     2 * tokens, 2 * tokens]
    if tokens == 200:
        assert moe.held_pass_rows(400, 4, 16) == 256 < 2 * tokens
    want = ref.routed_layer_output(ARCH, lp, m) - latent._swiglu(
        m, lp["shared_gate"], lp["shared_up"], lp["shared_down"])
    np.testing.assert_allclose(out, want, rtol=0, atol=2e-6)


def test_no_token_on_a_held_expert_and_no_product_runs(monkeypatch):
    """A load in which no token chooses a held expert: the routed part is
    exactly zero, and no grouped product runs at all (a product over rows
    no group owns would read garbage where the kernel writes nothing)."""
    lp = _pinned_router(_routed_layer(CFG, jax.random.key(13)), (0, 9))
    m = jnp.abs(jax.random.normal(jax.random.key(15), (33, 64))) + 0.1
    calls = []
    real = moe.lax.ragged_dot

    def counted(*a, **kw):
        jax.debug.callback(lambda: calls.append(1))
        return real(*a, **kw)

    monkeypatch.setattr(moe.lax, "ragged_dot", counted)
    out, stats, _ = moe.routed_ffn(CFG, lp, m, jnp.float32)
    jax.effects_barrier()
    assert not np.any(np.asarray(out)) and not calls
    assert [int(x) for x in stats] == [0, 0, 0, 0, 66]
    # The same trap does spring when a pair is kept.
    held = _pinned_router(lp, (4, 9))
    out, stats, _ = moe.routed_ffn(CFG, held, m, jnp.float32)
    jax.effects_barrier()
    assert np.any(np.asarray(out)) and len(calls) == 3 and int(stats[1]) == 33


def test_tile_first_token_and_block_agree_and_count_their_routing(params):
    """The admission tile with its routing stats (every position of the
    tile, padding too), the cache-free first token, and a fused decode
    block with its own: pairs kept of pairs routed."""
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
    hit, kept, fullest, taken, pairs = (int(x) for x in np.asarray(tile))
    assert taken == kept                    # a tile's padding is taken too
    assert pairs == 2 * 4 * 16 * 2          # layers x positions x top 2
    assert 0 < kept < pairs and 0 < hit <= 2 * 4 and fullest <= kept

    cur = jnp.asarray([want[1], want[2], want[0]], jnp.int32)   # by slot
    cache, out, _, extras = decode_multi(CFG, params, cache, cur, temps[:3],
                                         4, 0, key)
    stats = extras.routing
    out = np.asarray(out)
    for slot, i in ((0, 1), (1, 2), (2, 0)):
        seq = toks[i, :lens[i]].tolist() + [want[i]] + out[:, slot].tolist()
        logits = np.asarray(ref.forward_logits(ARCH, params, seq[:-1]))
        assert list(np.argmax(logits[lens[i]:], -1)) == out[:, slot].tolist()
    assert int(stats[4]) == 4 * 2 * 3 * 2   # steps x layers x slots x top 2
    assert int(stats[3]) == int(stats[1]) <= int(stats[4])


def test_the_engine_counts_pairs_routed_and_pairs_held(params):
    from ray_tpu.serve.llm import LLMEngine

    engine = LLMEngine(CFG, params, num_slots=2, max_seq_len=64,
                       decode_block=4)
    reqs = [engine.submit(list(range(1, n)), max_new_tokens=6)
            for n in (20, 10)]
    while any(r.finish_ts == 0.0 for r in reqs):
        engine.step()
    c = engine.stats()["counts"]
    # A tile of the 32 bucket is 8 rows, of the 16 bucket 8 too.
    assert c["prefill_moe_pairs"] == 2 * 2 * (8 * 32 + 8 * 16)
    assert 0 < c["prefill_moe_pairs_held"] == c["prefill_moe_rows"] \
        < c["prefill_moe_pairs"]
    steps = sum(k * n for k, n in c["blocks_by_k"].items())
    assert c["moe_pairs"] == steps * 2 * 2 * 2    # layers x slots x top 2
    assert c["moe_pairs_held"] == c["moe_rows"] <= c["moe_pairs"]
    assert c["moe_expert_steps"] == steps * 2 * 4     # over the 4 held
    assert not engine._tile_moe


def test_a_queue_side_tile_keeps_to_its_positions(params, monkeypatch):
    """A queue-side tile holds `_QUEUE_TILE_POSITIONS` at most (eight rows
    of the 8,192 bucket do not fit the chip beside this configuration):
    fewer rows a tile, the same first tokens, and the results padded to
    the width the first-token fusion is warmed for."""
    from ray_tpu.serve.llm import LLMEngine

    assert [LLMEngine._queue_tile_rows(b) for b in
            (16, 1024, 2048, 4096, 8192, 10240)] == [8, 8, 4, 2, 1, 1]

    def serve():
        engine = LLMEngine(CFG, params, num_slots=1, max_seq_len=64,
                           decode_block=4)
        reqs = [engine.submit(list(range(1, n)), max_new_tokens=3)
                for n in (20, 19, 18, 10)]
        while any(r.finish_ts == 0.0 for r in reqs):
            engine.step()
        return [r.tokens for r in reqs], engine.stats()["counts"]

    wide, c8 = serve()
    monkeypatch.setattr(LLMEngine, "_QUEUE_TILE_POSITIONS", 64)
    narrow, c2 = serve()
    assert narrow == wide and all(len(t) == 3 for t in wide)
    assert c8["queue_side_first_tokens"] == c2["queue_side_first_tokens"] \
        == 3
    # Buckets 32 (two requests) and 16 (one): eight rows a tile, or 64
    # positions a tile.
    assert c8["prefill_tile_rows"] - c2["prefill_tile_rows"] \
        == (8 - 2) + (8 - 4)


# -- the kernels, interpreted, against their plain forms ----------------------

@pytest.mark.parametrize("n_rows", [[300, 0, 257, 512], [1, 256, 511, 40]])
def test_decode_kernel_with_one_array_for_keys_and_values(n_rows):
    """One KV head under 16 query heads, rows 128 + 64 wide whose first
    128 columns are the values (the cell: 128 heads, 512 + 64): the
    kernel in the Pallas interpreter, each block fetched once, against
    the plain products over the rows held. A slot that holds no row reads
    nothing and returns zeros; the scores' scale is the caller's."""
    L, B, S, C, Dv, H = 2, 4, 512, 192, 128, 16
    ks = jax.random.split(jax.random.key(sum(n_rows)), 2)
    c_all = jax.random.normal(ks[0], (L, B, S, C), jnp.bfloat16)
    q = jax.random.normal(ks[1], (B, 1, H, C), jnp.bfloat16)
    n = jnp.asarray(n_rows, jnp.int32)
    assert da.block_rows(S, C, Dv) == 512 and da.block_rows(S, 576, 512)
    assert not da.block_rows(S, C, 200) and not da.block_rows(S, 200, 128)
    scale = 1.0 / math.sqrt(48)
    got = da.decode_attention(q, c_all, None, jnp.int32(1), n,
                              interpret=True, sm_scale=scale, v_width=Dv)
    assert got.shape == (B, 1, H * Dv)
    rows = c_all[1].astype(jnp.float32)
    s = jnp.einsum("bhc,bsc->bhs", q[:, 0].astype(jnp.float32), rows) * scale
    seen = jnp.arange(S)[None, None, :] < n[:, None, None]
    p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
    p = jnp.where(seen, p, 0.0).astype(jnp.bfloat16).astype(jnp.float32)
    want = jnp.einsum("bhs,bsc->bhc", p, rows[..., :Dv]).reshape(B, 1, -1)
    np.testing.assert_allclose(np.asarray(got, np.float32), want, rtol=0,
                               atol=0.03)
    with pytest.raises(ValueError, match="v_width"):
        da.decode_attention(q, c_all, None, jnp.int32(0), n, interpret=True)


@pytest.mark.parametrize("S,dk,dv", [(512, 48, 32), (1024, 192, 128)])
def test_flash_forward_with_values_narrower_than_keys(S, dk, dv):
    """The forward kernel, interpreted, with keys 192 wide and values 128
    (and a small analogue), against the plain form; the backward refuses."""
    ks = jax.random.split(jax.random.key(S), 3)
    q = jax.random.normal(ks[0], (1, S, 2, dk), jnp.bfloat16)
    k = jax.random.normal(ks[1], (1, S, 2, dk), jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, S, 2, dv), jnp.bfloat16)
    scale = 1.0 / math.sqrt(dk)
    got = flash_attention(q, k, v, causal=True, sm_scale=scale,
                          interpret=True, block_q=256, block_k=256)
    want = flash_attention(q, k, v, causal=True, sm_scale=scale,
                           force_reference=True)
    assert got.shape == want.shape == (1, S, 2, dv)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=0,
                               atol=0.03)
    with pytest.raises(NotImplementedError, match="another width"):
        jax.grad(lambda v: jnp.sum(flash_attention(
            q, k, v, causal=True, interpret=True, block_q=256,
            block_k=256).astype(jnp.float32)))(v)


@pytest.mark.parametrize("rows,k,n,want", [
    (128, 7680, 2048, (128, 7680, 128)),        # a decode pass's row tile
    (128, 2048, 7680, (128, 2048, 512)),
    (8192, 7680, 2048, (256, 7680, 128)),       # an admission tile's pass
    (8192, 2048, 7680, (256, 2048, 512)),
])
def test_gmm_tiles_at_the_published_expert_widths(rows, k, n, want):
    tm, tk, tn = moe._gmm_tiling(rows, k, n)
    assert (tm, tk, tn) == want
    assert rows % tm == 0 and k % tk == 0 and n % tn == 0
    # Two buffers of an expert's slab and of a row tile, two of the output
    # tile and its accumulator, inside the 16 MB a kernel may use.
    vmem = 2 * 2 * (tk * tn + tm * tk) + 3 * 4 * tm * tn
    assert vmem <= 16 * 2 ** 20


@pytest.mark.parametrize("pairs,held,routed,want", [
    (256, 16, 256, 128), (65536, 16, 256, 8192), (16, 4, 16, 128),
    (400, 4, 16, 256), (1000, 16, 16, 1024)])
def test_a_pass_takes_twice_the_uniform_share_in_row_tiles(pairs, held,
                                                           routed, want):
    assert moe.held_pass_rows(pairs, held, routed) == want
