"""`moe.down_and_combine`: a routed layer's rows back to their tokens in
one pass (`ops/moe_combine`: the down product writes each row by itself,
one kernel fetches a token's rows, weights and adds them), and the
experts' sizes without a scatter-add (`moe._count`).

On the CPU the kernels run in the pallas interpreter and are held to the
lines they replace (`moe.combine` over `moe.grouped_dot`): the product to
the bit (megablox's kernel under the same tiling: the same sums in the
same order), the weighted sum to float32 rounding, and to the bit against
the same products added in the order j = 0 .. K-1, which is the
kernel's. An unowned token's row is compared with zero exactly, whatever
lies in the rows its pairs point at."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas.ops.tpu import megablox

from ray_tpu.models import moe
from ray_tpu.ops import moe_combine
from ray_tpu.ops.moe_combine import gmm_rows_apart
from tests.test_grouped_swiglu import _names


def _owned(T, rows):
    if rows == "none":
        return None
    if rows == "none_owned":
        return jnp.zeros((T,), bool)
    return jnp.arange(T) % 3 != 1               # "some"


def _pairs(T, K, E, owned, seed=0):
    """A layer's routing as `grouped_experts` sorts it: (weights (T, K),
    inv (T * K,), the rows the groups hold)."""
    ks = jax.random.split(jax.random.key(seed), 2)
    experts = jax.random.randint(ks[0], (T, K), 0, E).reshape(T * K)
    key = experts if owned is None else jnp.where(
        jnp.repeat(owned, K), experts, E + experts)
    order = jnp.argsort(key, stable=True)
    weights = jax.random.uniform(ks[1], (T, K), minval=0.1)
    held = T * K if owned is None else int(jnp.sum(owned)) * K
    return weights, jnp.argsort(order), held, key


@jax.jit
def _in_order(ys, inv, weights, rows):
    """`moe.combine`, a token's rows added in the order j = 0 .. K-1
    (jitted: the compiler contracts a product and a sum alike here and
    in the interpreted kernel)."""
    T, K = weights.shape
    picked = ys[inv].reshape(T, K, -1)
    acc = picked[:, 0] * weights[:, 0:1]
    for j in range(1, K):
        acc = acc + picked[:, j] * weights[:, j:j + 1]
    return acc if rows is None else jnp.where(rows[:, None], acc, 0.0)


@pytest.mark.parametrize("rows", ["none", "some", "none_owned"])
@pytest.mark.parametrize("D", [256, 2304])
@pytest.mark.parametrize("K", [4, 8])
@pytest.mark.parametrize("pairs", [32, 2048, 8192])
def test_the_kernel_is_the_lines_that_stand(pairs, K, D, rows):
    T = pairs // K
    owned = _owned(T, rows)
    weights, inv, held, _ = _pairs(T, K, 8, owned)
    ys = jax.random.normal(jax.random.key(1), (pairs, D), jnp.float32)
    # What a product that writes no row past its groups may leave there.
    ys = jnp.where(jnp.arange(pairs)[:, None] >= held, jnp.nan, ys)
    # Blocks of 8 tokens: the interpreter's program is a quarter of the
    # default's to compile (the other sizes: the next test).
    got = moe_combine.moe_combine(ys[:, None], inv, weights, owned,
                                  block_tokens=8, interpret=True)
    assert got.shape == (T, D) and got.dtype == jnp.float32
    assert np.all(np.isfinite(np.asarray(got)))
    want = moe.combine(ys, inv, weights, owned)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)
    assert np.array_equal(np.asarray(got),
                          np.asarray(_in_order(ys, inv, weights, owned)))
    if owned is not None:
        unowned = ~np.asarray(owned)
        assert unowned.any() and not np.asarray(got)[unowned].any()
        assert (rows == "none_owned") or np.asarray(got)[~unowned].any()


@pytest.mark.parametrize("block_tokens", [16, 24, 32])
def test_any_block_of_tokens_gives_the_same_rows(block_tokens):
    """Tokens that end inside a block (100 = 6 x 16 + 4 = 4 x 24 + 4 = 3
    x 32 + 4)."""
    T, K, D = 100, 8, 384
    owned = _owned(T, "some")
    weights, inv, held, _ = _pairs(T, K, 8, owned, seed=3)
    ys = jax.random.normal(jax.random.key(4), (T * K, 1, D), jnp.float32)
    got = moe_combine.moe_combine(ys, inv, weights, owned,
                                  block_tokens=block_tokens, interpret=True)
    assert np.array_equal(np.asarray(got), np.asarray(
        _in_order(ys[:, 0], inv, weights, owned)))


# name -> (rows, F, D, rows a group), as `tests/test_grouped_swiglu.py`'s.
PRODUCTS = {
    "uneven_and_empty": (384, 256, 384, [0, 100, 0, 57, 130, 0, 97, 0]),
    "a_row_a_group": (128, 128, 256, [1, 1, 0, 1, 0, 0, 1, 1]),
    "rows_of_no_group": (512, 128, 128, [3, 200, 0, 80]),
    "first_past_zero": (256, 128, 256, [0] * 4 + [70, 0, 90, 96] + [0] * 4),
    "row_tile_256": (4096, 128, 128, [1000, 0, 2000, 1096]),
    "mellum_896x2304": (128, 896, 2304, [50, 0, 78]),
    "no_group_at_all": (128, 128, 128, [0, 0, 0, 0]),
}


def _product(name, seed=0):
    R, F, D, sizes = PRODUCTS[name]
    ks = jax.random.split(jax.random.key(seed), 2)
    h = jax.random.normal(ks[0], (R, F)).astype(jnp.bfloat16)
    w = (jax.random.normal(ks[1], (len(sizes), F, D)) * F ** -0.5) \
        .astype(jnp.bfloat16)
    return h, w, jnp.asarray(sizes, jnp.int32), sum(sizes)


@pytest.mark.parametrize("name", sorted(PRODUCTS))
def test_the_rows_apart_are_megablox_rows(name):
    h, w, groups, n = _product(name)
    tiling = moe._gmm_tiling(h.shape[0], h.shape[1], w.shape[2])
    got = gmm_rows_apart(h, w, groups, tiling, interpret=True)
    assert got.shape == (h.shape[0], 1, w.shape[2])
    assert got.dtype == jnp.float32
    want = megablox.gmm(h, w, groups, jnp.float32, tiling, interpret=True)
    assert n == 0 or np.any(np.asarray(want[:n]))
    assert np.array_equal(np.asarray(got[:n, 0]), np.asarray(want[:n]))


@pytest.fixture
def at_any_size(monkeypatch):
    """The pair whatever the rows' bytes: a test's rows are few; and
    blocks of 8 tokens, a quarter of the default's program for the
    interpreter to compile."""
    monkeypatch.setattr(moe_combine, "MIN_ROW_BYTES", 0)
    monkeypatch.setattr(moe_combine, "moe_combine", functools.partial(
        moe_combine.moe_combine, block_tokens=8))


@pytest.mark.parametrize("rows", ["none", "some", "none_owned"])
@pytest.mark.parametrize("T,K,E,F,D", [(4, 8, 8, 128, 256),
                                       (64, 4, 8, 128, 384),
                                       (300, 8, 16, 256, 128)])
def test_the_pair_is_the_product_and_the_lines(T, K, E, F, D, rows,
                                               at_any_size):
    """`down_and_combine` through both kernels against `moe.combine` of
    megablox's product: the layer's result as `grouped_experts` makes
    it, the unowned pairs sorted past the groups."""
    owned = _owned(T, rows)
    weights, inv, held, key = _pairs(T, K, E, owned, seed=5)
    groups = jnp.bincount(key, length=2 * E)[:E].astype(jnp.int32)
    ks = jax.random.split(jax.random.key(6), 2)
    h = jax.random.normal(ks[0], (T * K, F)).astype(jnp.bfloat16)
    w = (jax.random.normal(ks[1], (E, F, D)) * F ** -0.5).astype(jnp.bfloat16)
    got = moe.down_and_combine(h, w, groups, inv, weights, owned, "interpret")
    ys = moe.grouped_dot(h, w, groups, "interpret")
    ys = jnp.where(jnp.arange(T * K)[:, None] >= held, 0.0, ys)
    assert np.array_equal(np.asarray(got), np.asarray(
        _in_order(ys, inv, weights, owned)))
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(moe.combine(ys, inv, weights, owned)),
        rtol=1e-6, atol=1e-6)


def test_where_the_kernels_engage_and_where_the_fall_back_does(monkeypatch):
    """Both kernels where `grouped_dot` would have taken megablox's for
    bf16 rows and the rows are an admission tile's; `grouped_dot` and
    the lines of `combine` anywhere else."""
    K, E = 8, 4
    pair = {"gmm_rows_apart", "moe_combine"}

    def through(kernel, T=8192, F=128, D=2304, dtype=jnp.bfloat16,
                wdtype=jnp.bfloat16):
        def arr(shape, dt):
            return jax.ShapeDtypeStruct(shape, dt)
        return _names(
            lambda h, w, g, inv, weights: moe.down_and_combine(
                h, w, g, inv, weights, None, kernel),
            arr((T * K, F), dtype), arr((E, F, D), wdtype),
            arr((E,), jnp.int32), arr((T * K,), jnp.int32),
            arr((T, K), jnp.float32))

    assert through("interpret") >= pair
    assert "gmm" not in through("interpret")
    # Off the TPU (what None means here) and forced off.
    for kernel in (None, False):
        assert not through(kernel) & (pair | {"gmm"})
    # Fewer rows than XLA's gather sends through HBM: a decode step's 32
    # pairs, a block pass's 2,048, and 8,192 (75 MB of 128 MiB).
    for T in (4, 256, 1024):
        few = through("interpret", T=T)
        assert "gmm" in few and not few & pair
    assert through("interpret", T=2048) >= pair
    # A width that does not tile: 2,240 is no multiple of 128.
    assert not through("interpret", D=2240) & (pair | {"gmm"})
    assert not through("interpret", F=192) & (pair | {"gmm"})
    # Float32 rows over bf16 weights: megablox's kernel over the two bf16
    # terms, XLA's sum of them, and the lines of `combine`.
    split = through("interpret", dtype=jnp.float32)
    assert "gmm" in split and not split & pair
    # Float32 weights never reach a kernel.
    assert not through("interpret", dtype=jnp.float32, wdtype=jnp.float32) \
        & (pair | {"gmm"})
    # More pairs than the kernel's scalar memory holds.
    monkeypatch.setattr(moe_combine, "MAX_PAIRS", 8192 * K - 1)
    many = through("interpret")
    assert "gmm" in many and not many & pair


# -- the experts' sizes -------------------------------------------------------

def _draw(kind, T, K, E, seed):
    key = jax.random.key(seed)
    if kind == "uniform":
        return jax.random.randint(key, (T, K), 0, E)
    if kind == "skewed":            # most pairs on two experts
        p = jnp.asarray([0.6, 0.3] + [0.1 / (E - 2)] * (E - 2))
        return jax.random.choice(key, E, (T, K), p=p)
    assert kind == "empty_experts"  # experts 1 and E - 1 chosen by nobody
    some = jnp.asarray([e for e in range(E) if e not in (1, E - 1)])
    return some[jax.random.randint(key, (T, K), 0, some.size)]


@pytest.mark.parametrize("n,keys", [
    (8, [0, 7, 7, 3, 0, 0]), (4, []), (5, [4] * 9), (3, [1, 0, 2, 1]),
    (130, list(range(0, 130, 7)) * 3)],
    ids=["some", "no_key", "one_value", "every_value", "wide"])
def test_count_is_bincount(n, keys):
    keys = jnp.asarray(keys, jnp.int32)
    got = moe._count(keys, n)
    assert got.dtype == jnp.int32 and got.shape == (n,)
    assert np.array_equal(np.asarray(got),
                          np.bincount(np.asarray(keys), minlength=n))


@pytest.mark.parametrize("rows", ["none", "some", "none_owned"])
@pytest.mark.parametrize("kind", ["uniform", "skewed", "empty_experts"])
@pytest.mark.parametrize("layer", ["grouped", "held"])
def test_the_sizes_are_the_counts(layer, kind, rows):
    """`sizes`: the rows each expert took (owned rows' pairs), `chose`:
    the pairs that chose it, owned or not; for `held_experts` over the
    experts held, the pairs of any other expert absent."""
    T, K, E, D, F = 24, 4, 8, 128, 128
    held, first_held = 4, 2
    owned = _owned(T, rows)
    experts = _draw(kind, T, K, E, seed=7)
    ks = jax.random.split(jax.random.key(8), 5)
    w = {"w_gate": jax.random.normal(ks[0], (E, D, F)) * 0.1,
         "w_up": jax.random.normal(ks[1], (E, D, F)) * 0.1,
         "w_down": jax.random.normal(ks[2], (E, F, D)) * 0.1}
    x = jax.random.normal(ks[3], (T, D))
    weights = jax.random.uniform(ks[4], (T, K), minval=0.1)
    picked = np.asarray(experts)
    mask = np.ones((T,), bool) if owned is None else np.asarray(owned)
    if layer == "grouped":
        out, sizes, chose = moe.grouped_experts(w, x, weights, experts, E,
                                                0, owned)
        n = E
    else:
        w = {k: v[:held] for k, v in w.items()}
        out, sizes, chose = moe.held_experts(w, x, weights, experts, held,
                                             first_held, E, 0, owned)
        picked, n = picked - first_held, held
    assert sizes.dtype == chose.dtype == jnp.int32
    inside = (picked >= 0) & (picked < n)
    want_chose = np.bincount(picked[inside], minlength=n)
    want_sizes = np.bincount(picked[inside & mask[:, None]], minlength=n)
    assert np.array_equal(np.asarray(chose), want_chose)
    assert np.array_equal(np.asarray(sizes), want_sizes)
    if kind == "empty_experts" and layer == "grouped":
        assert want_chose[1] == want_chose[E - 1] == 0 and want_chose.any()
    assert not np.asarray(out)[~mask].any()
    assert rows == "none_owned" or np.asarray(out)[mask].any()
