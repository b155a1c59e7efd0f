"""A routed layer takes the rows somebody owns (`moe.routed_ffn`'s
`rows`; a decode step's `live`): a pair of any other row chooses no
expert. Tiny sizes on the CPU: the grouped products through
`lax.ragged_dot` and through megablox's kernel in the Pallas interpreter.

An owned row's result is compared to the bit with the unmasked call's:
its pairs meet the same experts' matrices in the same products, only
their place in the sorted rows moves. An unowned row's is compared with
zero exactly, whatever the products left where they wrote nothing."""

import collections
import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import configs, generate, moe
from ray_tpu.models.transformer import init_params, loss_fn, stack
from ray_tpu.ops import grouped_swiglu, moe_combine
from ray_tpu.ops.grouped_swiglu import gmm_swiglu

T, K, E, D, F = 6, 2, 4, 128, 128
ROUTED, FIRST_HELD = 8, 2           # `held_experts`: experts [2, 6) of 8
OWNED = np.asarray([False, True, False, True, True, False])


def _case(dtype, seed=0):
    """Two layers' experts in one array, the layer under test the second
    (`first` = E); x, weights and the experts chosen, drawn apart from
    any router."""
    ks = jax.random.split(jax.random.key(seed), 6)
    wdt = jnp.float32 if dtype == "float32" else jnp.bfloat16
    xdt = jnp.bfloat16 if dtype == "bfloat16" else jnp.float32
    w = {"w_gate": jax.random.normal(ks[0], (2 * E, D, F)) * 0.1,
         "w_up": jax.random.normal(ks[1], (2 * E, D, F)) * 0.1,
         "w_down": jax.random.normal(ks[2], (2 * E, F, D)) * 0.1}
    w = {k: v.astype(wdt) for k, v in w.items()}
    x = jax.random.normal(ks[3], (T, D)).astype(xdt)
    weights = jax.random.uniform(ks[4], (T, K), minval=0.1)
    return w, x, weights, ks[5]


def _grouped(w, x, weights, key, rows):
    experts = jax.random.randint(key, (T, K), 0, E)
    out, sizes, chose = moe.grouped_experts(w, x, weights, experts, E, E,
                                            rows)
    return out, sizes, chose, np.asarray(experts)


def _held(w, x, weights, key, rows):
    experts = jax.random.randint(key, (T, K), 0, ROUTED)
    out, sizes, chose = moe.held_experts(w, x, weights, experts, E,
                                         FIRST_HELD, ROUTED, E, rows)
    local = np.asarray(experts) - FIRST_HELD
    return out, sizes, chose, np.where((local >= 0) & (local < E), local, E)


LAYERS = {"grouped": _grouped, "held": _held}


@pytest.fixture
def products(request, monkeypatch):
    """The grouped products as a CPU runs them, or the kernels in the
    interpreter: megablox's for a product, and for a bf16 layer's gate,
    up and activation the one of `ops/grouped_swiglu`; and, `poison`
    "written_nowhere", every row past the last group filled with NaN
    behind either: what a kernel that writes no such row may leave
    there."""
    path, poison = request.param
    force = "interpret" if path == "interpret" else None

    def forced(product, operands):
        def run(a, *rest):
            rest = rest[:operands]          # the caller's `kernel` goes
            y = product(a, *rest, force)
            if poison != "written_nowhere":
                return y
            past = jnp.arange(a.shape[0]) >= jnp.sum(rest[-1])
            return jnp.where(past[:, None], jnp.nan, y)
        return run

    if path == "interpret" or poison == "written_nowhere":
        monkeypatch.setattr(moe, "grouped_dot", forced(moe.grouped_dot, 2))
        monkeypatch.setattr(moe, "grouped_swiglu",
                            forced(moe.grouped_swiglu, 3))
    if path == "interpret":
        # And a bf16 layer's down product and the rows' return as the
        # pair of `ops/moe_combine`.
        down, apart = moe.down_and_combine, moe_combine.gmm_rows_apart

        def rows_apart(a, w, groups, tiling, interpret):
            y = apart(a, w, groups, tiling, interpret=interpret)
            if poison != "written_nowhere":
                return y
            past = jnp.arange(a.shape[0]) >= jnp.sum(groups)
            return jnp.where(past[:, None, None], jnp.nan, y)

        monkeypatch.setattr(moe_combine, "gmm_rows_apart", rows_apart)
        monkeypatch.setattr(moe_combine, "MIN_ROW_BYTES", 0)    # 48 rows
        monkeypatch.setattr(moe, "down_and_combine",
                            lambda *operands: down(*operands, force))
    return path, poison


def _counts(keys, owned):
    """(rows each expert took, pairs that chose it) from the pairs' local
    expert numbers (T, K), E where absent."""
    taken = np.bincount(keys[owned].ravel(), minlength=E + 1)[:E]
    return taken, np.bincount(keys.ravel(), minlength=E + 1)[:E]


# float32 weights never reach the kernel: no such case in the interpreter.
CASES = [(dtype, (path, poison))
         for dtype in ("float32", "bfloat16", "float32_over_bf16")
         for path in ("ragged", "interpret")
         for poison in ("a_nan_row_of_x", "written_nowhere")
         if (dtype, path) != ("float32", "interpret")]


@pytest.mark.parametrize(
    "dtype,products", CASES, indirect=["products"],
    ids=["-".join((dtype,) + products) for dtype, products in CASES])
@pytest.mark.parametrize("layer", sorted(LAYERS))
def test_owned_rows_to_the_bit_and_the_others_zero(layer, dtype, products,
                                                    monkeypatch):
    path, poison = products
    w, x, weights, key = _case(dtype)
    rows = jnp.asarray(OWNED)
    if path == "interpret":
        # The kernel does run: it tiles these shapes.
        assert moe._gmm_tiling(128, D, F) is not None
    run = LAYERS[layer]
    fused = []

    def counted(*args, **kw):
        fused.append(args[0].shape)
        return gmm_swiglu(*args, **kw)

    monkeypatch.setattr(grouped_swiglu, "gmm_swiglu", counted)
    combined, combine = [], moe_combine.moe_combine

    def returned(*args, **kw):
        combined.append(args[0].shape)
        return combine(*args, block_tokens=8, **kw)   # 6 tokens a call

    monkeypatch.setattr(moe_combine, "moe_combine", returned)
    want, all_sizes, all_chose, keys = run(w, x, weights, key, None)
    if poison == "a_nan_row_of_x":
        x = jnp.where(rows[:, None], x, jnp.nan)
    got, sizes, chose, _ = run(w, x, weights, key, rows)
    got, want = np.asarray(got), np.asarray(want)
    assert got.dtype == np.float32 and np.all(np.isfinite(want))
    assert np.array_equal(got[OWNED], want[OWNED]) and np.any(want[OWNED])
    assert not np.any(got[~OWNED])                  # zero, not NaN
    taken, scored = _counts(keys, OWNED)
    assert np.asarray(sizes).tolist() == taken.tolist()
    assert np.asarray(chose).tolist() == scored.tolist() \
        == np.asarray(all_sizes).tolist() == np.asarray(all_chose).tolist()
    assert 0 < taken.sum() < scored.sum()
    # bf16 rows in the interpreter: gate, up and the activation between
    # them were the one kernel, in both calls of the layer.
    assert bool(fused) == ((dtype, path) == ("bfloat16", "interpret"))
    # And `grouped_experts`' rows came back through the combine kernel
    # (`held_experts` adds a pass's rows by a 0 / 1 product).
    assert bool(combined) == (
        (layer, dtype, path) == ("grouped", "bfloat16", "interpret"))


@pytest.mark.parametrize("products", [("ragged", "written_nowhere"),
                                      ("interpret", "written_nowhere")],
                         indirect=True, ids="-".join)
@pytest.mark.parametrize("layer", sorted(LAYERS))
def test_no_owned_row_is_zeros_and_no_group(layer, products):
    """What `LLMEngine.warm_decode_blocks` runs: no slot owned."""
    w, x, weights, key = _case("float32_over_bf16", seed=1)
    out, sizes, chose, keys = LAYERS[layer](
        w, x, weights, key, jnp.zeros((T,), bool))
    assert not np.any(np.asarray(out)) and not np.any(np.asarray(sizes))
    assert np.asarray(chose).tolist() == _counts(keys, OWNED)[1].tolist()


def test_the_stats_tell_pairs_scored_from_rows_taken():
    cfg = configs.tiny_mellum_test()
    lp = jax.tree.map(lambda a: a[0, 0],
                      init_params(cfg, jax.random.key(3))["periods"])
    m = jax.random.normal(jax.random.key(4), (T, cfg.d_model))
    want, every, experts = moe.routed_ffn(cfg, lp, m, jnp.float32)
    got, stats, again = moe.routed_ffn(cfg, lp, m, jnp.float32,
                                       rows=jnp.asarray(OWNED))
    assert np.array_equal(np.asarray(experts), np.asarray(again))
    assert np.array_equal(np.asarray(got)[OWNED], np.asarray(want)[OWNED])
    hit, rows, fullest, taken = (int(n) for n in stats)
    owned = np.asarray(experts)[OWNED]
    assert (hit, taken) == (len(np.unique(owned)), owned.size)
    assert [rows, fullest] == [int(n) for n in every[1:3]]
    assert int(every[3]) == rows == T * cfg.moe_top_k


# -- the walks ----------------------------------------------------------------

B, S = 4, 32


def _slot(tree, i):
    """Slot i's rows of every cache array (layers, slots, ...)."""
    return [np.asarray(a[:, i]) for a in jax.tree.leaves(tree._replace(
        seq_lens=None))]


def _stepped(cfg):
    """(the walk of one step under `live`, routed layers x top k: the
    pairs a slot's row has a step)."""
    st = stack(cfg)
    params = st.init_params(cfg, jax.random.key(5))
    toks = jnp.asarray(np.random.default_rng(6).integers(
        0, cfg.vocab_size - 1, size=(B, 8)), jnp.int32)
    cache = generate.init_kv_cache(cfg, B, S)
    if cfg.block_length:
        Bd = cfg.block_length
        # A committed block behind the one the pass works on.
        cache, _, _ = st.decode_block(cfg, params, cache, toks[:, :Bd],
                                      jnp.zeros((B,), jnp.int32))
        walk = jax.jit(lambda live: st.decode_block(
            cfg, params, cache, toks[:, Bd:2 * Bd],
            jnp.full((B,), Bd, jnp.int32), live))
        return walk, st.routed_layers(cfg) * cfg.moe_top_k * Bd
    cache, _, _ = st.prefill(cfg, params, cache, toks,
                             jnp.asarray([8, 5, 7, 3], jnp.int32),
                             jnp.arange(B))
    walk = jax.jit(lambda live: st.decode(cfg, params, cache, toks[:, 0],
                                          live))
    return walk, st.routed_layers(cfg) * cfg.moe_top_k


TINY = {"afmoe": configs.tiny_afmoe_test, "mellum": configs.tiny_mellum_test,
        "pangu": configs.tiny_pangu_test, "sdar": configs.tiny_sdar_test}


@pytest.mark.parametrize("name", sorted(TINY))
def test_a_lone_slots_step_is_its_step_among_four(name):
    """`live` = one slot of four, each in turn: the slot's logits and
    cache rows are the all-live step's to the bit, the experts took its
    pairs and no other, and the pairs scored stay every slot's."""
    cfg = TINY[name]()
    walk, pairs = _stepped(cfg)
    cache, logits, extras = walk(jnp.ones((B,), bool))
    every = [int(n) for n in extras.routing]
    assert every[3] == every[1]                 # all owned: all taken
    assert (every[4] if name == "pangu" else every[1]) == B * pairs
    taken = 0
    for i in range(B):
        alone, got, extras = walk(jnp.arange(B) == i)
        assert np.array_equal(np.asarray(got[i]), np.asarray(logits[i]))
        for a, b in zip(_slot(alone, i), _slot(cache, i)):
            assert np.array_equal(a, b)
        assert np.all(np.isfinite(np.asarray(got)))
        stats = [int(n) for n in extras.routing]
        if name == "pangu":     # of its pairs, those on an expert held
            assert stats[0] <= stats[3] <= stats[1] <= stats[4] == B * pairs
            assert stats[3] <= pairs
        else:
            assert stats[0] <= stats[3] == pairs and stats[1] == B * pairs
        taken += stats[3]
    # A slot routes alone as it does among four: each pair is some run's.
    assert taken == every[3]


# -- what lowers as it did ----------------------------------------------------

def _shapes(cfg):
    def i32(*s):
        return jax.ShapeDtypeStruct(s, jnp.int32)

    def f32(*s):
        return jax.ShapeDtypeStruct(s, jnp.float32)

    params = jax.eval_shape(lambda: init_params(cfg, jax.random.key(0)))
    cache = jax.eval_shape(lambda: generate.init_kv_cache(cfg, B, S))
    key = jax.eval_shape(lambda: jax.random.key(0))
    live = jax.ShapeDtypeStruct((B,), jnp.bool_)
    return params, cache, key, live, i32, f32


def _lowered(cfg, program):
    params, cache, key, live, i32, f32 = _shapes(cfg)
    if program == "prefill_sample_batch":
        return generate.prefill_sample_batch.lower(
            cfg, params, cache, i32(2, 16), i32(2), i32(2), 0, f32(2), key)
    if program == "first_token_sample":
        return generate.first_token_sample.lower(
            cfg, params, i32(2, 16), i32(2), f32(2), 0, key)
    if program == "decode_multi":
        return generate.decode_multi.lower(
            cfg, params, cache, i32(B), f32(B), 4, 0, key, live)
    if program == "decode_step":
        return generate.decode_step.lower(cfg, params, cache, i32(B), live)
    assert program == "train"

    def step(p, t, y):
        return jax.value_and_grad(lambda p: loss_fn(cfg, p, t, y)[0])(p)

    return jax.jit(step).lower(params, i32(2, 16), i32(2, 16))


def _digest(lowered):
    return hashlib.sha256(lowered.as_text().encode()).hexdigest()[:16]


def _ops(lowered):
    return collections.Counter(re.findall(
        r"= \"?((?:stablehlo|chlo|func)\.[a-z_]+)", lowered.as_text()))


# sha256 of the StableHLO the same calls lower to on the parent commit
# (d6f4b5d), this machine, jax 0.9.0. The dense stack's programs and the
# train step, with and without a routed FFN; and of the routed stacks
# the cache-free forward, which passes no rows and reports no stats.
# Re-recorded in PR 47, whose count of the experts' sizes is a compare
# and a sum where `jnp.bincount`'s scatter-add stood: the four that run
# `grouped_experts` / `held_experts` (tiny_moe's tile, and the routed
# stacks' forwards); the dense programs and the train step stand.
BEFORE = {
    ("tiny", "prefill_sample_batch"): "466e821c31a284bd",
    ("tiny", "first_token_sample"): "8e9ea74f930637f4",
    ("tiny", "decode_multi"): "908831b3d941ae47",
    ("tiny", "decode_step"): "f1b991cb2d35fd75",
    ("tiny", "train"): "b45b1592417db270",
    ("tiny_moe", "prefill_sample_batch"): "6765e6a7cfa2d324",
    ("tiny_moe", "first_token_sample"): "cbecddc8aacbbe0d",
    ("tiny_moe", "train"): "3c401085a21ea84a",
    ("tiny_mellum", "first_token_sample"): "0cd4e54fe52c79fa",
    ("tiny_afmoe", "first_token_sample"): "1d087938fd2e6b95",
    ("tiny_pangu", "first_token_sample"): "8c0556e08c68c7e7",
}


@pytest.mark.parametrize("name,program", sorted(BEFORE),
                         ids=["-".join(k) for k in sorted(BEFORE)])
def test_a_program_without_rows_lowers_as_before(name, program):
    assert _digest(_lowered(configs.get(name), program)) \
        == BEFORE[name, program]


# A routed stack's admission tile on the parent commit (628ebf5, PR 46):
# its text's digest, the digest of its operations counted by name, the
# routed layers its text holds (a scan's body once), and what the count's
# new lines (`moe._count`, PR 47) add to and take from those operations.
TILE_BEFORE = {
    "tiny_mellum": ("210976664ae7adeb", "4f4d13dd86098e73", 4, {
        "add": -8, "broadcast_in_dim": -5, "constant": -16, "convert": 3,
        "iota": 4, "maximum": -1, "reduce": 4, "scatter": -4, "select": -4}),
    "tiny_afmoe": ("11b93b322c076877", "a7df48e2341025e0", 4, {
        "add": -8, "broadcast_in_dim": -5, "constant": -16, "convert": 3,
        "iota": 4, "maximum": -1, "reduce": 4, "scatter": -4, "select": -4}),
    "tiny_pangu": ("c3dd8ec7937154e2", "78fa5f270005236f", 1, {
        "add": -2, "broadcast_in_dim": -2, "constant": -4, "iota": 1,
        "maximum": -1, "reduce": 1, "scatter": -1, "select": -1}),
}


@pytest.mark.parametrize("name", sorted(TILE_BEFORE))
def test_a_routed_tile_counts_without_a_scatter_and_nothing_else(name):
    """The tile passes no rows: every position's pairs are taken, padding
    too. Its program is the parent's but for the experts' sizes: a
    layer's scatter-add (`jnp.bincount`, with its clamp, its select and
    their constants) is gone, a compare against an iota and a sum stand
    in its place, and no operation of any other name is more or fewer:
    off the TPU the rows come back by the lines that stood."""
    now = _lowered(configs.get(name), "prefill_sample_batch")
    text, counted, layers, moved = TILE_BEFORE[name]
    assert _digest(now) != text
    assert (moved["scatter"], moved["iota"], moved["reduce"]) \
        == (-layers, layers, layers)
    ops = _ops(now)
    for op, n in moved.items():
        ops["stablehlo." + op] -= n
    assert hashlib.sha256(repr(sorted(
        (op, n) for op, n in ops.items() if n)).encode()) \
        .hexdigest()[:16] == counted
