"""The period stack with state-space layers (`arch="jamba"`: AI21
Jamba2-3B's shape) at a small size on the CPU against the plain reference
of benchmarks/references/jamba_decoder.py: the tile's scan of
`ops/selective_scan` against the recurrence a token at a time, both
kernels in the Pallas interpreter, prefill and decode through a cache
that keeps a recurrent state beside one KV head's keys and values, what a
tile's padding, a dropped row and a slot nobody owns leave of a state, a
slot's second request, where the global layer stands, and the tied head.
Logits, never sampled tokens.
"""

import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import configs, periodic
from ray_tpu.models.generate import (
    decode_multi,
    decode_step,
    first_token_sample,
    init_kv_cache,
    prefill,
    prefill_sample_batch,
)
from ray_tpu.models.transformer import (PERIOD_FORMS, STACKS, init_params,
                                        offered)
from ray_tpu.ops import selective_scan as ss

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_reference():
    spec = importlib.util.spec_from_file_location(
        "jamba_decoder_ref", os.path.join(
            ROOT, "benchmarks", "references", "jamba_decoder.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load_reference()
CFG = configs.tiny_jamba_test()
ARCH = dataclasses.asdict(CFG)


@pytest.fixture(scope="module")
def params():
    return jax.jit(lambda k: init_params(CFG, k))(jax.random.key(3))


def _prompt(n, seed=1):
    return np.asarray(jax.random.randint(
        jax.random.key(seed), (n,), 0, CFG.vocab_size))


# -- the shape ----------------------------------------------------------------

def test_the_preset_is_the_published_shape_in_small(params):
    assert STACKS["jamba"] == "periodic"
    form = PERIOD_FORMS["jamba"]
    assert CFG.period_form == form and form.recurrent == "ssm" \
        and form.global_at is None \
        and not form.qk_norm and form.rotary == () and not form.attn_gate
    # Dense FFNs all through (no period configuration ran one before),
    # a period a scan step as every period stack has it: s s g s | s s g s.
    assert periodic.layer_plan(CFG) == [("periods", (2, 4), False)]
    assert periodic.step_kinds(CFG) == [("ssm", "ssm", "global", "ssm")]
    assert periodic.routed_layers(CFG) == 0
    assert periodic.cache_layers(CFG) == {"window": 0, "global": 2, "ssm": 6}
    assert set(params) == {"embed", "final_norm", "periods"}  # the head: tied
    periods = params["periods"]
    assert {k for k, v in periods.items() if isinstance(v, dict)} == {
        "global0", "ssm0", "ssm1", "ssm2"}
    ssm, attn = periods["ssm1"], periods["global0"]
    assert ssm["A_log"].shape == (2, 16, 128)
    # A dense FFN's matrices lie with the layer's own leaves, a norm
    # every layer has over the period's layers.
    assert ssm["w_gate"].shape == attn["w_gate"].shape == (2, 64, 128)
    assert periods["ffn_norm"].shape == (2, 4, 64)
    assert attn["wk"].shape == (2, 64, 16)                  # one KV head
    assert "wq" not in ssm and "w_in" not in attn
    # The published initialisation of what a scaled normal would make
    # degenerate: exp(A_log) = 1 .. 16 down a channel, D one, the step's
    # bias the inverse softplus of [0.001, 0.1].
    np.testing.assert_allclose(jnp.exp(ssm["A_log"][1, :, 5]),
                               np.arange(1, 17), rtol=1e-6)
    assert np.all(np.asarray(ssm["D"]) == 1.0)
    step = np.asarray(jax.nn.softplus(periods["ssm0"]["dt_bias"]))
    assert 0.001 <= step.min() and step.max() <= 0.1 + 1e-6
    cache = init_kv_cache(CFG, 3, 64)
    assert cache.s.shape == (6, 3, 16, 128) and cache.s.dtype == jnp.float32
    assert cache.tails.shape == (6, 3, 3, 128)
    assert cache.k.shape == (2, 3, 64, 1, 16)
    for name in ("suffix", "forward_train"):
        with pytest.raises(NotImplementedError, match="selective scan"):
            offered(CFG, name)


@pytest.mark.parametrize("offset", [0, 2, 3])
def test_the_global_layer_stands_where_the_configuration_says(offset):
    cfg = configs.tiny_jamba_test(offset=offset)
    table = ref.layer_table(dataclasses.asdict(cfg))
    kinds = [kind for _, _, kind, _ in table]
    (group,), (period,) = periodic.layer_plan(cfg), periodic.step_kinds(cfg)
    assert kinds == list(period) * group.lead[0]
    assert kinds.index("global") == offset == periodic.global_place(cfg)
    # The reference reads a layer's own leaves where the plan keeps them:
    # under its kind and its place among the period's layers of the kind.
    assert [(p, j) for p, j, *_ in table] == [
        (p, j) for p in range(group.lead[0]) for j in range(group.lead[1])]
    assert {f"{kind}{own}" for *_, kind, own in table} == {
        k for k, v in periodic._layer_shapes(cfg, group).items()
        if isinstance(v, dict)}
    assert [own for _, j, kind, own in table] == [
        period[:j].count(kind) for _, j, kind, _ in table]


def test_the_forms_of_the_other_architectures_keep_their_places():
    for name, place in (("tiny_afmoe_test", -1), ("tiny_mellum_test", -1),
                        ("tiny_solar_test", 0)):
        cfg = getattr(configs, name)()
        assert periodic.global_place(cfg) == place % cfg.global_attn_every
        assert periodic.step_kinds(cfg)[-1][periodic.global_place(cfg)] \
            == "global"


def test_a_configuration_without_a_state_is_refused():
    with pytest.raises(ValueError, match="mamba_d_state"):
        dataclasses.replace(CFG, mamba_d_state=0)
    with pytest.raises(ValueError, match="mamba_d_state"):
        dataclasses.replace(configs.tiny_mellum_test(), mamba_d_state=16)
    with pytest.raises(ValueError, match="attn_layer_offset"):
        dataclasses.replace(CFG, attn_layer_offset=4)
    with pytest.raises(ValueError, match="mamba_proj_bias"):
        dataclasses.replace(CFG, mamba_proj_bias=True)


# -- the scan -----------------------------------------------------------------

def _operands(B, S, C, N, seed):
    """Steps from 1e-4 to 4: a state that barely moves a token and one
    that forgets all of itself in one."""
    ks = jax.random.split(jax.random.key(seed), 6)
    dt = jnp.exp(jax.random.uniform(ks[0], (B, S, C), minval=np.log(1e-4),
                                    maxval=np.log(4.0)))
    A = -jnp.exp(jax.random.uniform(ks[4], (N, C), minval=0.0,
                                    maxval=np.log(16.0)))
    return (dt, jax.random.normal(ks[1], (B, S, C)),
            jax.random.normal(ks[2], (B, S, N)),
            jax.random.normal(ks[3], (B, S, N)), A,
            jax.random.normal(ks[5], (B, N, C)))


def _token_by_token(dt, u, Bm, Cm, A, lengths, state):
    y, last = [], []
    for b in range(dt.shape[0]):
        n = int(lengths[b])
        yb, hb = ref.recurrence(dt[b, :n], u[b, :n], Bm[b, :n], Cm[b, :n], A,
                                state[b])
        y.append(yb)
        last.append(hb)
    return y, jnp.stack(last)


@pytest.mark.parametrize("interpret", [None, True], ids=["xla", "kernel"])
def test_the_tiles_scan_is_the_recurrence_a_token_at_a_time(interpret):
    """Ragged lengths and a carried state; S = 20 is no whole group of 8
    positions, so the kernel's tile is padded behind."""
    dt, u, Bm, Cm, A, state = _operands(2, 20, 256, 16, seed=0)
    lengths = jnp.asarray([20, 13])
    assert float(dt.min()) < 2e-4 and float(dt.max()) > 3.5
    y, last = ss.scan(dt, u, Bm, Cm, A, lengths, state, interpret=interpret)
    want, want_last = _token_by_token(dt, u, Bm, Cm, A, lengths, state)
    for b, n in enumerate((20, 13)):
        np.testing.assert_allclose(y[b, :n], want[b], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(last, want_last, rtol=1e-5, atol=1e-5)
    # From nothing: the state the tile's scan starts a prompt from.
    zero, _ = ss.scan(dt, u, Bm, Cm, A, interpret=interpret)
    np.testing.assert_allclose(
        zero[0], ref.recurrence(dt[0], u[0], Bm[0], Cm[0], A)[0], rtol=1e-5,
        atol=1e-5)


def _step_operands(B, C, N, seed, layers=3, dtype=jnp.bfloat16):
    """One token a slot: `_operands`' steps, inputs, B, C and A, the
    carried states and tails of `layers` layers, the token's input
    before its convolution, its step before the bias and the softplus,
    its gate and the skip."""
    dt, u, Bm, Cm, A, _ = _operands(B, 1, C, N, seed)
    ks = jax.random.split(jax.random.key(seed + 100), 5)
    return dict(
        states=jax.random.normal(ks[0], (layers, B, N, C)),
        tails=jax.random.normal(ks[1], (layers, B, 3, C)).astype(dtype),
        new=jax.random.normal(ks[2], (B, C)).astype(dtype),
        pre=jnp.log(jnp.expm1(dt[:, 0])) - 0.3, bias=jnp.full((C,), 0.3),
        u=u[:, 0], Bm=Bm[:, 0], Cm=Cm[:, 0],
        z=jax.random.normal(ks[3], (B, C)), A=A,
        D=1.0 + 0.1 * jax.random.normal(ks[4], (C,)))


def _update(o, l, live, interpret):
    return ss.decode_update(
        o["states"], o["tails"], jnp.int32(l), o["new"], o["pre"], o["bias"],
        o["u"], o["Bm"], o["Cm"], o["z"], o["A"], o["D"], live,
        interpret=interpret)


def _the_old_path(o, l, live):
    """What the step made of the same operands before one call did it
    all: the softplus, the recurrence a slot, the skip, the gate and the
    cast beside it, `delta_rule.move_tails` for the tail."""
    from ray_tpu.ops import delta_rule

    dt = jax.nn.softplus(o["pre"] + o["bias"])
    y, h = zip(*(ref.recurrence(dt[b:b + 1], o["u"][b:b + 1],
                                o["Bm"][b:b + 1], o["Cm"][b:b + 1], o["A"],
                                o["states"][l, b])
                 for b in range(o["u"].shape[0])))
    out = (jnp.concatenate(y) + o["D"] * o["u"]) * jax.nn.silu(o["z"])
    return (out.astype(o["new"].dtype), jnp.stack(h), delta_rule.move_tails(
        o["tails"], jnp.int32(l), o["new"][:, None], live))


@pytest.mark.parametrize("interpret", [None, True], ids=["xla", "kernel"])
@pytest.mark.parametrize("live", [None, [True, False, True, False, False]])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_one_update_of_the_carried_states(interpret, live, dtype):
    """Layer 1 of three: the owned slots' states are the recurrence's,
    their tails `delta_rule.move_tails`', their output rows `(y + D u)
    silu(z)` of the path that made each apart; a slot nobody owns and
    the other layers keep state and tail bit for bit, and such a slot's
    output row is finite."""
    o = _step_operands(5, 128, 16, seed=1, dtype=dtype)
    mask = None if live is None else jnp.asarray(live)
    out, states, tails = jax.jit(
        lambda o, m: _update(o, 1, m, interpret))(o, mask)
    want, want_h, want_tails = _the_old_path(o, 1, mask)
    assert out.dtype == tails.dtype == dtype and states.dtype == jnp.float32
    tol = 1e-5 if dtype == jnp.float32 else 1e-2
    for b in range(5):
        if live is None or live[b]:
            np.testing.assert_allclose(states[1, b], want_h[b], rtol=1e-6,
                                       atol=1e-6)
            np.testing.assert_allclose(out[b].astype(jnp.float32),
                                       want[b].astype(jnp.float32),
                                       rtol=tol, atol=tol)
        else:
            np.testing.assert_array_equal(states[1, b], o["states"][1, b])
            np.testing.assert_array_equal(tails[1, b], o["tails"][1, b])
            np.testing.assert_array_equal(out[b], o["new"][b])   # finite
    np.testing.assert_array_equal(tails, want_tails)
    for other in (0, 2):
        np.testing.assert_array_equal(states[other], o["states"][other])
        np.testing.assert_array_equal(tails[other], o["tails"][other])


@pytest.mark.parametrize("interpret", [None, True], ids=["xla", "kernel"])
def test_no_owned_slot_leaves_every_state_as_it_was(interpret):
    """Nor any tail; the output's rows are the rows the token's input
    came in: nobody's, and finite."""
    o = _step_operands(2, 128, 16, seed=3, layers=2)
    out, states, tails = jax.jit(lambda o: _update(
        o, 0, jnp.asarray([False, False]), interpret))(o)
    np.testing.assert_array_equal(states, o["states"])
    np.testing.assert_array_equal(tails, o["tails"])
    np.testing.assert_array_equal(out, o["new"])


# -- the stack against the reference -------------------------------------------

def test_prefill_then_sixteen_steps_are_the_references_forward(params):
    """Tolerance 2e-5 on logits of size 1: float32 on both sides, the
    program's products in another order than the reference's (a tile's
    scan from a zero state, then one update a step from the carried one;
    the reference walks all 37 positions in one scan)."""
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
    assert not np.asarray(cache.s[:, 0]).any()
    # The tied head: the logits are the final hidden state against the
    # embedding's rows.
    x, _, _ = periodic.forward_free(CFG, params, jnp.asarray(toks)[None])
    np.testing.assert_allclose(
        x[0, -1] @ params["embed"].T, want[-1], rtol=0, atol=2e-5)


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
    behind them in the tile changes neither), a row whose slot is out of
    range writes nothing anywhere, and the tile is the slot's reset: what
    the slot held before does not reach what it holds after."""
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
    # No routed layer and one pass: the block hands back nothing beside.
    assert extras.routing is None and extras.exits is None
    for name in ("s", "tails"):
        np.testing.assert_array_equal(getattr(cache, name)[:, 1],
                                      getattr(before, name)[:, 1])
        assert not np.array_equal(getattr(cache, name)[:, 0],
                                  getattr(before, name)[:, 0])


def test_a_slots_second_request_gets_what_it_gets_alone(params):
    """Through the engine: one slot, two requests one after the other. The
    state the first left is poisoned before the second is admitted; the
    second's tokens and log-probabilities are those it gets from a fresh
    engine. The engine counts this stack's state updates under the names
    a delta-rule stack's have: it asks for the cache's `s`, not for an
    architecture."""
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
    # A state-space layer's scan is not cut in chunks: nothing to count.
    assert "linear_chunks" not in counts
    assert counts["linear_slot_steps"] == counts["linear_slot_steps_live"] \
        == counts["slot_steps"] * 6
    assert "moe_rows" not in counts
