"""A tile's walk of the gated delta rule as a kernel
(`ops/delta_rule._scan_pallas`, `kda_scan`), in the Pallas interpreter on
the CPU at the kernel's own head size (128 x 128) and the two cells' head
counts: against the XLA walk it replaces on a TPU (`_chunk_scan_xla`) and
against the recurrence a token at a time, rows of four kinds in one tile
(full, ragged, empty, ending on a chunk's edge), a carried state, bf16
operands, a decay no single factorisation survives, what lies past a
row's length, the chunks it skips, which path `chunk_scan` takes where,
and what the kernel costs a program that calls it at several sites.

One compiled program a (head count, dtype): lengths and the state are
arguments."""

import functools
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from ray_tpu.models import configs, periodic
from ray_tpu.models.transformer import init_params
from ray_tpu.ops import delta_rule

fa = importlib.import_module("ray_tpu.ops.flash_attention")

S, D = 192, 128
# A row that fills the tile, one that ends inside its second chunk, one
# with no token at all, one that ends on a chunk's edge.
ROWS = {"full": 192, "ragged": 100, "empty": 0, "edge": 64}
LENGTHS = tuple(ROWS.values())
B = len(LENGTHS)

_kernel = functools.partial(delta_rule._scan_pallas, interpret=True)
_xla = jax.jit(delta_rule._chunk_scan_xla)


def _operands(seed, heads, rows=B, n=S, strongest_decay=1.5):
    ks = jax.random.split(jax.random.key(seed), 6)
    shape = (rows, n, heads)

    def unit(a):
        return a / jnp.linalg.norm(a, axis=-1, keepdims=True)

    q = unit(jax.random.normal(ks[0], shape + (D,))) * D ** -0.5
    k = unit(jax.random.normal(ks[1], shape + (D,)))
    v = jax.random.normal(ks[2], shape + (D,))
    # Log-decays from a thousandth to e^1.5 = 4.5 a token.
    g = -jnp.exp(jax.random.uniform(ks[3], shape + (D,), minval=-6.0,
                                    maxval=strongest_decay))
    beta = 2 * jax.nn.sigmoid(2 * jax.random.normal(ks[4], shape))
    carried = 0.1 * jax.random.normal(ks[5], (rows, heads, D, D))
    return (q, k, v, g, beta), carried


def _real(lengths, n=S):
    return (jnp.arange(n)[None, :] < jnp.asarray(lengths)[:, None]
            )[..., None, None]


def _rel(a, b):
    return float(jnp.sqrt(jnp.mean((a - b) ** 2) / jnp.mean(b ** 2)))


@functools.lru_cache(maxsize=None)
def _tile(heads, dtype, state, lengths=LENGTHS):
    """(operands, the state the rows start from, the kernel's (o, last),
    the XLA walk's) of the tile of four rows; `lengths` None: every
    position real."""
    strongest = 1.5 if dtype == "float32" else -2.0
    (q, k, v, g, beta), carried = _operands(heads, heads,
                                            strongest_decay=strongest)
    q, k, v = (a.astype(dtype) for a in (q, k, v))
    s0 = carried if state == "carried" else None
    n = None if lengths is None else jnp.asarray(lengths, jnp.int32)
    zeros = jnp.zeros_like(carried)
    got = _kernel(q, k, v, g, beta,
                  jnp.full((B,), S, jnp.int32) if n is None else n,
                  zeros if s0 is None else s0)
    return (q, k, v, g, beta), s0, got, _xla(q, k, v, g, beta, n, s0)


# -- the kernel is the XLA walk -----------------------------------------------

@pytest.mark.parametrize("row", list(ROWS))
@pytest.mark.parametrize("state", ["zeros", "carried"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("heads", [32, 64])
def test_the_kernel_is_the_xla_walk(heads, dtype, state, row):
    """kimi's 32 heads and solar's 64, beta past 1, decays up to 4.5 a
    token (float32), a row of each kind in one tile: `o` at the row's
    real positions and the state behind its last one."""
    ops, s0, (o, last), (want_o, want_last) = _tile(heads, dtype, state)
    i = list(ROWS).index(row)
    assert float(ops[4].max()) > 1.5
    assert o.shape == ops[2].shape and o.dtype == last.dtype == jnp.float32
    assert bool(jnp.isfinite(o).all()) and bool(jnp.isfinite(last).all())
    n = ROWS[row]
    if row == "empty":
        # No token: the state it was given, bit for bit.
        np.testing.assert_array_equal(
            last[i], 0 * last[i] if s0 is None else s0[i])
    if dtype == "float32":
        np.testing.assert_allclose(o[i, :n], want_o[i, :n], rtol=0,
                                   atol=5e-6)
        np.testing.assert_allclose(last[i], want_last[i], rtol=0, atol=3e-5)
    else:
        # The two orders round a bf16 operand in other places.
        if n:
            assert _rel(o[i, :n], want_o[i, :n]) < 0.02
        if n or s0 is not None:
            assert _rel(last[i], want_last[i]) < 0.02


@pytest.mark.parametrize("state", ["zeros", "carried"])
@pytest.mark.parametrize("heads", [32, 64])
def test_no_lengths_every_position_is_real(heads, state):
    ops, s0, (o, last), (want_o, want_last) = _tile(heads, "float32", state,
                                                    None)
    np.testing.assert_allclose(o, want_o, rtol=0, atol=5e-6)
    np.testing.assert_allclose(last, want_last, rtol=0, atol=3e-5)


def test_the_kernel_is_the_recurrence_a_token_at_a_time():
    (q, k, v, g, beta), _ = _operands(5, 8, rows=2, n=128)
    lengths = jnp.asarray([128, 70], jnp.int32)
    zeros = jnp.zeros((2, 8, D, D), jnp.float32)

    def one(S0, xs):
        q, k, v, g, b, t = xs
        o, S1 = delta_rule.step(S0, q, k, v, g, b)
        return jnp.where((t < lengths)[:, None, None, None], S1, S0), o

    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta)) \
        + (jnp.arange(128),)
    want_last, want_o = lax.scan(one, zeros, xs)
    o, last = _kernel(q, k, v, g, beta, lengths, zeros)
    real = _real(lengths, 128)
    np.testing.assert_allclose(jnp.where(real, o, 0), jnp.where(
        real, jnp.moveaxis(want_o, 0, 1), 0), rtol=0, atol=5e-6)
    np.testing.assert_allclose(last, want_last, rtol=0, atol=3e-5)


def test_a_decay_of_one_and_a_half_a_token_for_a_chunk():
    """exp(1.5 x 64) is no float32: no exponent is ever positive, so no
    inf and no nan, and the XLA walk's answer."""
    (q, k, v, g, beta), _ = _operands(3, 8, rows=1, n=128)
    g = jnp.full_like(g, -1.5)
    lengths = jnp.full((1,), 128, jnp.int32)
    o, last = _kernel(q, k, v, g, beta, lengths,
                      jnp.zeros((1, 8, D, D), jnp.float32))
    want_o, want_last = _xla(q, k, v, g, beta, lengths)
    assert bool(jnp.isfinite(o).all()) and bool(jnp.isfinite(last).all())
    np.testing.assert_allclose(o, want_o, rtol=0, atol=2e-6)
    np.testing.assert_allclose(last, want_last, rtol=0, atol=2e-6)


# -- what lies past a row's length ---------------------------------------------

@pytest.mark.parametrize("poison", [float("nan"), 1e30])
@pytest.mark.parametrize("state", ["zeros", "carried"])
def test_a_position_past_its_rows_length_leaves_the_state_bit_for_bit(
        state, poison):
    """g and beta at or past a row's length are never read into its
    state, nor is anything of a chunk wholly past it (its blocks are not
    loaded); q, k and v inside the row's last chunk have to be numbers,
    as the XLA walk asks of padding too."""
    (q, k, v, g, beta), s0, (o, last), _ = _tile(32, "float32", state)
    lengths = jnp.asarray(LENGTHS, jnp.int32)
    real = _real(lengths)
    g2 = jnp.where(real, g, poison)
    beta2 = jnp.where(real[..., 0], beta, poison)
    in_last_chunk = _real((lengths + 63) // 64 * 64)
    q2, k2, v2 = (jnp.where(real, a, jnp.where(in_last_chunk, 1.0, poison))
                  for a in (q, k, v))
    o2, last2 = _kernel(q2, k2, v2, g2, beta2, lengths,
                        jnp.zeros_like(last) if s0 is None else s0)
    np.testing.assert_array_equal(last2, last)
    np.testing.assert_array_equal(jnp.where(real, o2, 0),
                                  jnp.where(real, o, 0))


@pytest.mark.parametrize("row", list(ROWS))
def test_a_rows_skipped_chunks_are_what_the_counter_says(row):
    """A chunk wholly past its row's last token gets no work: its `o` is
    zeros, where a chunk that ran wrote padding's; `scan_chunks` counts
    the same, a row and the tile."""
    _, _, (o, _), _ = _tile(32, "float32", "carried")
    i = list(ROWS).index(row)
    ran = [bool(o[i, c * 64:(c + 1) * 64].any()) for c in range(S // 64)]
    assert (sum(ran), len(ran)) == delta_rule.scan_chunks(S, [ROWS[row]])
    assert ran == sorted(ran, reverse=True)          # the row's first ones
    assert delta_rule.scan_chunks(S, LENGTHS) == (3 + 2 + 0 + 1, 12)
    # A bucket that is no whole number of chunks, a length past it.
    assert delta_rule.scan_chunks(100, [100, 65, 64, 1, 900]) \
        == (2 + 2 + 1 + 1 + 2, 10)


def test_the_kernel_carries_a_state_it_is_given():
    """A tile split in two equals the tile whole, bit for bit: 128
    positions, then the last 64 from the state the first left."""
    ops, _ = _operands(7, 8, rows=2)
    zeros = jnp.zeros((2, 8, D, D), jnp.float32)
    whole, last = _kernel(*ops, jnp.full((2,), S, jnp.int32), zeros)
    first, s1 = _kernel(*ops, jnp.full((2,), 128, jnp.int32), zeros)
    rest = [jnp.concatenate([a[:, 128:], a[:, :128]], axis=1) for a in ops]
    second, s2 = _kernel(*rest, jnp.full((2,), 64, jnp.int32), s1)
    np.testing.assert_array_equal(first[:, :128], whole[:, :128])
    np.testing.assert_array_equal(second[:, :64], whole[:, 128:])
    np.testing.assert_array_equal(s2, last)
    assert float(jnp.abs(s1 - last).max()) > 1e-3


# -- which path `chunk_scan` takes ----------------------------------------------

@pytest.fixture
def taken(monkeypatch):
    """`chunk_scan` as on the chip, both walks replaced by recorders."""
    calls = []

    def walk(name):
        def record(q, k, v, g, beta, lengths=None, state=None):
            calls.append((name, k.shape))
            return name, lengths if name == "xla" else lengths.shape
        return record

    monkeypatch.setattr(fa, "on_tpu", lambda: True)
    monkeypatch.setattr(delta_rule, "_scan_pallas", walk("kernel"))
    monkeypatch.setattr(delta_rule, "_chunk_scan_xla", walk("xla"))
    return calls


@pytest.mark.parametrize("n,heads,d,dtype,path", [
    (128, 8, 128, jnp.float32, "kernel"),
    (4096, 32, 128, jnp.float32, "kernel"),   # kimi's longest tile
    (2048, 64, 128, jnp.bfloat16, "kernel"),  # solar's, in bf16
    (96, 8, 128, jnp.float32, "xla"),         # no whole number of chunks
    (128, 8, 64, jnp.float32, "xla"),         # a head is not 128 x 128
    (128, 4, 128, jnp.float32, "xla"),        # no whole block of heads
    (6, 32, 128, jnp.float32, "xla"),         # shorter than a block
    (128, 8, 128, jnp.float16, "xla")])
def test_the_kernel_runs_where_the_input_lets_it(taken, n, heads, d, dtype,
                                                 path):
    """By the backend and the operands' shapes alone; the kernel is
    handed lengths and a state whatever the caller left out, so every
    site of a program calls it with equal avals."""
    q = k = v = jax.ShapeDtypeStruct((2, n, heads, d), dtype)
    assert delta_rule.chunk_scan(q, k, v, None, None) \
        == ((path, (2,)) if path == "kernel" else (path, None))
    assert taken == [(path, (2, n, heads, d))]


def test_off_the_tpu_the_xla_walk_stands(monkeypatch):
    def never(*a, **kw):
        raise AssertionError("the kernel off the TPU")

    monkeypatch.setattr(delta_rule, "_scan_pallas", never)
    ops, _ = _operands(5, 8, rows=1, n=128)
    assert not delta_rule.scan_usable(*ops[:3])
    o, last = delta_rule.chunk_scan(*ops)
    assert o.shape == (1, 128, 8, D) and last.shape == (1, 8, D, D)


# -- what the kernel costs a program ---------------------------------------------

def _kimi_with_kernel_heads():
    """The tiny kimi preset with delta-rule heads the kernel takes: 8
    heads of 128 at its nine linear layers (five sites of a program: the
    leading layer, three of the scanned period, the tail's)."""
    tiny = configs.tiny_kimi_test()
    return configs.tiny_kimi_test(
        linear_n_heads=8, linear_head_dim=128, max_seq_len=256,
        linear_attn_config=dict(tiny.linear_attn_config, head_dim=128,
                                num_heads=8))


def _for_the_tpu(fn, *args):
    """`fn`'s StableHLO as lowered for a TPU, from this CPU."""
    return jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()


@pytest.fixture
def as_on_the_chip(monkeypatch):
    monkeypatch.setattr(fa, "on_tpu", lambda: True)


@pytest.mark.parametrize("rows,positions", [(1, 128), (4, 512)])
def test_a_tile_program_holds_one_kernel_body(as_on_the_chip, rows,
                                              positions):
    """Nine linear layers at five sites of the program (4 x 512: each
    walked a row at a time by `forward_free`'s `lax.map`) call the kernel
    with equal avals: one trace and one lowering to Mosaic, one body in
    the module, whatever calls it."""
    cfg = _kimi_with_kernel_heads()
    assert positions < periodic._ROW_ALONE or rows > 1
    params = jax.eval_shape(lambda k: init_params(cfg, k), jax.random.key(0))
    tokens = jax.ShapeDtypeStruct((rows, positions), jnp.int32)
    text = _for_the_tpu(
        lambda p, t: periodic.forward_free(cfg, p, t)[0], params, tokens)
    assert text.count("tpu_custom_call") == 1 and "kda_scan" in text
    # The one body, called from every site.
    assert sum("call @_scan_pallas" in line
               for line in text.splitlines()) == 5
    assert len(text.splitlines()) < 3000


def test_the_kernels_body_stays_small():
    """What a program pays to trace and lower the kernel follows its
    body: 1,086 lines of jaxpr at PR 59 (PR 58's, five times a program:
    4,100)."""
    ops, carried = _operands(1, 8, rows=1, n=128)
    jaxpr = jax.make_jaxpr(functools.partial(
        delta_rule._scan_pallas.__wrapped__, interpret=False))(
            *ops, jnp.full((1,), 128, jnp.int32), carried)
    (call,) = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert dict(call.params["metadata"]) == {"kernel": "kda_scan"}
    assert len(str(call.params["jaxpr"]).splitlines()) < 1300
