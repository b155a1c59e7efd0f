"""`moe.grouped_swiglu`: a routed layer's gate and up products and the
activation between them as one grouped kernel (`ops/grouped_swiglu`).

On the CPU the kernel runs in the pallas interpreter and is held to the
path it replaces, through the same interpreter: two `grouped_dot` calls
(megablox's kernel under the same tiling, so the same sums in the same
order), XLA's `silu(gate) * up` in float32 and one rounding to the rows'
dtype. A row of a group is compared to bf16's last bit or one unit of
it; a row of no group is nobody's and is not compared."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import moe
from ray_tpu.ops.grouped_swiglu import gmm_swiglu


def _operands(R, D, F, G, dtype=jnp.bfloat16, seed=0):
    ks = jax.random.split(jax.random.key(seed), 3)
    a = jax.random.normal(ks[0], (R, D)).astype(dtype)
    gate = (jax.random.normal(ks[1], (G, D, F)) * D ** -0.5) \
        .astype(jnp.bfloat16)
    up = (jax.random.normal(ks[2], (G, D, F)) * D ** -0.5) \
        .astype(jnp.bfloat16)
    return a, gate, up


def _two_products(a, gate, up, groups, kernel):
    """What `_grouped_swiglu` did before the kernel."""
    h = jax.nn.silu(moe.grouped_dot(a, gate, groups, kernel)) \
        * moe.grouped_dot(a, up, groups, kernel)
    return h.astype(a.dtype)


def _units(x):
    """bf16 -> integers that step by one a representable value."""
    b = np.asarray(jax.lax.bitcast_convert_type(x, jnp.int16)).astype(int)
    return np.where(b < 0, -(b & 0x7FFF), b)


def _same_rows(got, want, n):
    assert got.dtype == want.dtype == jnp.bfloat16
    assert got.shape == want.shape
    assert np.all(np.isfinite(np.asarray(want[:n], np.float32)))
    assert np.any(np.asarray(want[:n], np.float32))
    apart = np.abs(_units(got[:n]) - _units(want[:n]))
    assert apart.max() <= 1, (apart.max(), int((apart > 1).sum()))


E = 4
# name -> (rows, D, F, rows a group): the groups of a call, G = their
# number.
CASES = {
    # Groups of uneven size, empty ones among them and at both ends.
    "uneven_and_empty": (384, 256, 384, [0, 100, 0, 57, 130, 0, 97, 0]),
    # One row a group, as a decode step's.
    "a_row_a_group": (128, 128, 256, [1, 1, 0, 1, 0, 0, 1, 1]),
    # The rows end inside a row tile: `grouped_dot`'s pad.
    "rows_end_inside_a_tile": (300, 256, 128, [40, 0, 111, 149]),
    "fewer_rows_than_a_tile": (24, 128, 128, [5, 0, 19]),
    # A stack's whole expert array: this layer's E experts are groups
    # [E, 2E) of 3E, the others take no row.
    "first_past_zero": (256, 128, 256, [0] * E + [70, 0, 90, 96] + [0] * E),
    # Rows past the last group: absent pairs, sorted last.
    "rows_of_no_group": (512, 128, 128, [3, 200, 0, 80]),
    # The row tile is 256 from 4,096 rows on.
    "row_tile_256": (4096, 128, 128, [1000, 0, 2000, 1096]),
    # The cells' tile shapes, at few rows: mellum's (all of n a tile)
    # and openpangu's (16 tiles of 128 over n).
    "mellum_2304x896": (128, 2304, 896, [50, 0, 78]),
    "openpangu_7680x2048": (128, 7680, 2048, [100, 28]),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_the_fused_call_is_the_two_products_and_the_activation(name):
    R, D, F, sizes = CASES[name]
    a, gate, up = _operands(R, D, F, len(sizes))
    groups = jnp.asarray(sizes, jnp.int32)
    assert moe._gmm_tiling(R + -R % 128, D, F) is not None
    got = moe.grouped_swiglu(a, gate, up, groups, "interpret")
    _same_rows(got, _two_products(a, gate, up, groups, "interpret"),
               sum(sizes))


@pytest.mark.parametrize("name", ["rows_of_no_group", "rows_end_inside_a_tile",
                                  "first_past_zero"])
def test_a_row_of_no_group_is_not_read(name):
    """The `written_nowhere` case of `tests/test_moe_rows.py` from the
    other side: NaN in every row past the last group reaches no row of a
    group, which come out to the bit as without it."""
    R, D, F, sizes = CASES[name]
    a, gate, up = _operands(R, D, F, len(sizes), seed=1)
    n = sum(sizes)
    if n == R:                  # leave the last group's last rows out
        sizes, n = sizes[:-1] + [sizes[-1] - 9], n - 9
    groups = jnp.asarray(sizes, jnp.int32)
    clean = moe.grouped_swiglu(a, gate, up, groups, "interpret")
    poisoned = jnp.where(jnp.arange(R)[:, None] >= n, jnp.nan, a)
    got = moe.grouped_swiglu(poisoned, gate, up, groups, "interpret")
    assert np.all(np.isfinite(np.asarray(got[:n], np.float32)))
    assert np.array_equal(_units(got[:n]), _units(clean[:n]))


def test_no_group_at_all_runs_and_writes_nothing_it_must():
    """What a warm-up's decode block runs: no slot owned."""
    a, gate, up = _operands(128, 128, 128, 4)
    out = moe.grouped_swiglu(a, gate, up, jnp.zeros((4,), jnp.int32),
                             "interpret")
    assert out.shape == (128, 128) and out.dtype == jnp.bfloat16


def _names(fn, *args):
    """The names of the jitted functions and kernels `fn(*args)` traces
    to, nested ones too."""
    seen = set()

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if "name" in eqn.params:
                seen.add(eqn.params["name"])
            if eqn.primitive.name == "pallas_call":
                seen.add(eqn.params["metadata"]["kernel"]
                         if eqn.params.get("metadata") else "pallas_call")
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return seen


def test_where_the_kernel_engages_and_where_the_fall_back_does():
    """One kernel where `grouped_dot` would have taken megablox's for
    both products; the two `grouped_dot` calls anywhere else."""
    a, gate, up = _operands(128, 128, 128, 4)
    groups = jnp.asarray([28, 0, 60, 40], jnp.int32)

    def through(kernel, a=a, gate=gate, up=up):
        return _names(lambda *xs: moe.grouped_swiglu(*xs, groups, kernel),
                      a, gate, up)

    assert through("interpret") >= {"gmm_swiglu"}
    assert "gmm" not in through("interpret")
    # Off the TPU (what None means here) and forced off.
    for kernel in (None, False):
        assert not through(kernel) & {"gmm_swiglu", "gmm"}
    # A shape that does not tile: 192 is no multiple of 128.
    odd = _operands(128, 192, 128, 4)
    assert not through("interpret", *odd) & {"gmm_swiglu", "gmm"}
    # float32 rows over bf16 weights: two products of two bf16 terms.
    split = through("interpret", a.astype(jnp.float32))
    assert "gmm" in split and "gmm_swiglu" not in split
    # float32 weights never reach a kernel.
    assert not through("interpret", a.astype(jnp.float32),
                       gate.astype(jnp.float32),
                       up.astype(jnp.float32)) & {"gmm_swiglu", "gmm"}


@pytest.mark.parametrize("kernel", [False, "interpret"])
def test_float32_rows_over_bf16_weights_take_the_fall_back(kernel):
    """Trinity's rows: each product sums its two bf16 terms a row before
    the activation, which a fused store cannot do. The result is
    float32 and is the two `grouped_dot` calls' to the bit."""
    a, gate, up = _operands(256, 128, 256, 4, dtype=jnp.float32)
    groups = jnp.asarray([100, 0, 56, 90], jnp.int32)
    got = moe.grouped_swiglu(a, gate, up, groups, kernel)
    want = _two_products(a, gate, up, groups, kernel)
    assert got.dtype == jnp.float32
    assert np.array_equal(np.asarray(got[:246]), np.asarray(want[:246]))


def test_a_contraction_walked_in_tiles_accumulates_both_products():
    """`_gmm_tiling` gives the whole contraction a tile; the kernel keeps
    megablox's k axis all the same, and with it the two accumulators."""
    from jax.experimental.pallas.ops.tpu import megablox

    a, gate, up = _operands(256, 512, 256, 3)
    groups = jnp.asarray([100, 30, 126], jnp.int32)
    tiling = (128, 128, 128)
    got = gmm_swiglu(a, gate, up, groups, tiling, interpret=True)
    g, u = (megablox.gmm(a, w, groups, jnp.float32, tiling, interpret=True)
            for w in (gate, up))
    _same_rows(got, (jax.nn.silu(g) * u).astype(jnp.bfloat16), 256)


@pytest.mark.parametrize("tiling,up_shape", [((128, 128, 96), (3, 256, 256)),
                                             ((128, 128, 128), (3, 256, 128)),
                                             ((128, 192, 128), (3, 256, 256))])
def test_operands_the_kernel_cannot_take_are_refused(tiling, up_shape):
    a, gate, _ = _operands(128, 256, 256, 3)
    up = jnp.zeros(up_shape, jnp.bfloat16)
    with pytest.raises(ValueError):
        gmm_swiglu(a, gate, up, jnp.asarray([28, 60, 40], jnp.int32), tiling,
                   interpret=True)


def test_the_layer_reaches_the_fused_call(monkeypatch):
    """`grouped_experts` over bf16 rows with the kernels forced: a layer's
    three products are `gmm_swiglu` and one `gmm`, and its sum is the
    layer's with two products and XLA's activation, to a unit of the
    bf16 rounding of h."""
    T, K, D, F = 6, 2, 128, 128
    ks = jax.random.split(jax.random.key(2), 4)
    a, gate, up = _operands(T, D, F, E, seed=3)
    w = {"w_gate": gate, "w_up": up,
         "w_down": (jax.random.normal(ks[0], (E, F, D)) * 0.1)
         .astype(jnp.bfloat16)}
    weights = jax.random.uniform(ks[1], (T, K), minval=0.1)
    experts = jax.random.randint(ks[2], (T, K), 0, E)
    dot, fused = moe.grouped_dot, moe.grouped_swiglu

    def layer():
        return moe.grouped_experts(w, a, weights, experts, E)[0]

    monkeypatch.setattr(moe, "grouped_dot",
                        lambda a, w, g, kernel=None: dot(a, w, g, "interpret"))
    monkeypatch.setattr(moe, "grouped_swiglu",
                        lambda a, wg, wu, g, kernel=None:
                        _two_products(a, wg, wu, g, "interpret"))
    want = layer()
    monkeypatch.setattr(moe, "grouped_swiglu",
                        lambda a, wg, wu, g, kernel=None:
                        fused(a, wg, wu, g, "interpret"))
    names = _names(layer)
    assert {"gmm_swiglu", "gmm"} <= names
    got = layer()
    assert np.allclose(np.asarray(got), np.asarray(want), rtol=0, atol=2e-2)
    assert np.any(np.asarray(want))
