"""The chunked loss head's own differentiation rule
(`transformer._chunked_nll`): the pass that makes a chunk's logits makes
both of its gradients while the logits are there, so differentiating
`loss_fn` multiplies a chunk's hidden states by the head once (the old
body sat under `jax.checkpoint` and multiplied them again in the
backward pass). The harness's training check sees the loss at step 0 and
that later losses are finite, which a wrong gradient passes: these tests
are what holds the gradient, against the un-chunked loss
(`token_cross_entropy`) that autodiff differentiates unaided."""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from ray_tpu.models import configs
from ray_tpu.models import transformer as T
from ray_tpu.parallel import ParallelPlan, make_mesh
from ray_tpu.parallel.sharding import logical_to_sharding, tree_shardings

B, S, CHUNK, VOCAB = 4, 64, 16, 320      # VOCAB is no other axis's length


def _cfg(tied=True, routed=False, chunk=CHUNK, **kw):
    base = (configs.tiny_moe_test if routed else configs.tiny_test)(VOCAB)
    return dataclasses.replace(base, tie_embeddings=tied, ce_chunk=chunk,
                               **kw)


def _batch(mask="given", batch=B):
    tok = jax.random.randint(jax.random.key(1), (batch, S), 0, VOCAB)
    tgt = jax.random.randint(jax.random.key(2), (batch, S), 0, VOCAB)
    if mask == "none":
        return tok, tgt, None
    m = jax.random.uniform(jax.random.key(3), (batch, S)) > 0.3
    if mask == "empty_chunks":       # the second and the last chunk whole
        pos = jnp.arange(S) // CHUNK
        m = m & (pos != 1) & (pos != S // CHUNK - 1)
    return tok, tgt, m.astype(jnp.float32)


def _value_and_grad(cfg, batch, scale=1.0):
    """Of `scale * loss + 1`: at 3 the rule is handed a cotangent other
    than 1."""
    return jax.jit(jax.value_and_grad(
        lambda p: scale * T.loss_fn(cfg, p, *batch)[0] + 1.0))


def _assert_trees_close(got, want, **tol):
    got, want = jax.tree.leaves(got), jax.tree.leaves(want)
    assert len(got) == len(want)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), **tol)


@pytest.mark.parametrize("routed", [False, True], ids=["aux0", "aux"])
@pytest.mark.parametrize("mask", ["given", "none", "empty_chunks"])
@pytest.mark.parametrize("tied", [True, False], ids=["tied", "untied"])
def test_value_and_gradients_equal_the_unchunked_loss(tied, mask, routed):
    cfg = _cfg(tied, routed)
    params = T.init_params(cfg, jax.random.key(0))
    batch = _batch(mask)
    want = T.loss_fn(dataclasses.replace(cfg, ce_chunk=0), params, *batch)
    got = T.loss_fn(cfg, params, *batch)
    assert (float(want[1]["aux"]) > 0) == routed
    assert sorted(got[1]) == sorted(want[1])
    for k in want[1]:
        np.testing.assert_allclose(float(got[1][k]), float(want[1][k]),
                                   rtol=1e-6, err_msg=k)
    l0, g0 = _value_and_grad(dataclasses.replace(cfg, ce_chunk=0), batch,
                             3.0)(params)
    l1, g1 = _value_and_grad(cfg, batch, 3.0)(params)
    np.testing.assert_allclose(float(l1), float(l0), rtol=1e-6)
    _assert_trees_close(g1, g0, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("tied", [True, False], ids=["tied", "untied"])
def test_gradients_of_the_hidden_states_and_the_head(tied):
    """The head alone, so the hidden states are an argument: d/dx and
    d/dhead (through the cast and, tied, the transpose) against the
    un-chunked loss's."""
    cfg = _cfg(tied)
    params = T.init_params(cfg, jax.random.key(0))
    _, tgt, mask = _batch()
    x = jax.random.normal(jax.random.key(4), (B, S, cfg.d_model))
    aux = jnp.float32(0.25)

    def chunked(x, p):
        return 3.0 * T.chunked_cross_entropy(cfg, p, x, tgt, mask, aux,
                                             CHUNK)[0] + 1.0

    def whole(x, p):
        return 3.0 * T.token_cross_entropy(T._logits(cfg, p, x), tgt, mask,
                                           aux)[0] + 1.0

    got = jax.jit(jax.value_and_grad(chunked, argnums=(0, 1)))(x, params)
    want = jax.jit(jax.value_and_grad(whole, argnums=(0, 1)))(x, params)
    np.testing.assert_allclose(float(got[0]), float(want[0]), rtol=1e-6)
    _assert_trees_close(got[1], want[1], rtol=1e-5, atol=1e-6)
    head = "embed" if tied else "lm_head"
    assert float(jnp.abs(got[1][1][head]).max()) > 0
    assert float(jnp.abs(got[1][0]).max()) > 0


def test_bf16_activations_stay_within_the_float32_paths_tolerance():
    """The cell's split (float32 parameters, bf16 activations) on the head
    alone: `p` is rounded to the activation dtype before its two
    products, as the transposes of `xc @ head` round theirs. So the
    chunked gradients sit on the un-chunked bf16 ones, and as near the
    float32 path's as `test_chunked_cross_entropy_matches_full` asks."""
    cfg = _cfg(tied=False)
    half = dataclasses.replace(cfg, dtype=jnp.bfloat16)
    params = T.init_params(cfg, jax.random.key(0))
    _, tgt, mask = _batch()
    x = jax.random.normal(jax.random.key(4), (B, S, cfg.d_model),
                          jnp.bfloat16)
    aux = jnp.float32(0)

    def whole(cfg):
        return jax.jit(jax.value_and_grad(
            lambda x, p: T.token_cross_entropy(
                T._logits(cfg, p, x), tgt, mask, aux)[0], argnums=(0, 1)))

    got = jax.jit(jax.value_and_grad(
        lambda x, p: T.chunked_cross_entropy(half, p, x, tgt, mask, aux,
                                             CHUNK)[0],
        argnums=(0, 1)))(x, params)
    assert got[1][0].dtype == jnp.bfloat16
    assert got[1][1]["lm_head"].dtype == jnp.float32
    same = whole(half)(x, params)
    np.testing.assert_allclose(float(got[0]), float(same[0]), rtol=1e-6)
    _assert_trees_close(got[1], same[1], rtol=8e-3, atol=1e-6)
    f32 = whole(cfg)(x.astype(jnp.float32), params)
    assert abs(float(got[0]) - float(f32[0])) < 1e-4
    _assert_trees_close(got[1], f32[1], rtol=2e-3, atol=2e-4)


def test_twenty_steps_follow_the_unchunked_loss_curve():
    """A wrong gradient shows as a curve that leaves the other."""
    batch = _batch()

    def curve(cfg):
        opt = optax.adam(1e-2)
        params = T.init_params(cfg, jax.random.key(0))
        state = opt.init(params)

        @jax.jit
        def step(params, state):
            loss, grads = jax.value_and_grad(
                lambda p: T.loss_fn(cfg, p, *batch)[0])(params)
            updates, state = opt.update(grads, state, params)
            return optax.apply_updates(params, updates), state, loss

        losses = []
        for _ in range(20):
            params, state, loss = step(params, state)
            losses.append(float(loss))
        return losses

    chunked, whole = curve(_cfg()), curve(_cfg(chunk=0))
    assert chunked[-1] < chunked[0] - 0.5       # it learns the batch
    np.testing.assert_allclose(chunked, whole, rtol=2e-5)


# -- the product is made once ------------------------------------------------

def _vocab_dots(jaxpr, path=(), scope=""):
    """(path of enclosing primitives, scope path) of every `dot_general`
    with an operand or a result that carries the vocabulary axis. An
    inner jaxpr's name stacks start at the equation that holds it."""
    for e in jaxpr.eqns:
        stack = f"{scope}/{e.source_info.name_stack}"
        if e.primitive.name == "dot_general" and any(
                VOCAB in v.aval.shape for v in e.invars + e.outvars):
            yield path, stack
        for sub in jax.core.jaxprs_in_params(e.params):
            yield from _vocab_dots(sub, path + (e.primitive.name,), stack)


@pytest.mark.parametrize("tied", [True, False], ids=["tied", "untied"])
def test_a_differentiated_chunk_multiplies_by_the_head_once(tied):
    """Three products carry the vocabulary axis, the mathematics' three
    (logits, d/dx, d/dhead), all in the one scan's body and none under a
    checkpoint; the gradient half names itself `head_grad`."""
    cfg = _cfg(tied)
    params = T.init_params(cfg, jax.random.key(0))
    batch = _batch()
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda p: T.loss_fn(cfg, p, *batch)[0]))(params)
    dots = list(_vocab_dots(jaxpr.jaxpr))
    assert len(dots) == 3, dots
    assert len({path for path, _ in dots}) == 1
    for path, stack in dots:
        assert path.count("scan") == 1
        assert not {"checkpoint", "remat", "remat2"} & set(path)
        assert "loss_head" in stack and "rematted_computation" not in stack
    assert sorted("head_grad" in stack for _, stack in dots) \
        == [False, True, True]
    assert "checkpoint" not in T.chunked_cross_entropy.__code__.co_names


def test_an_evaluation_pays_for_no_gradient():
    """Undifferentiated, `loss_fn` lowers with the logits' product and
    neither gradient product."""
    cfg = _cfg(tied=False)
    params = T.init_params(cfg, jax.random.key(0))
    batch = _batch()
    lowered = jax.jit(lambda p: T.loss_fn(cfg, p, *batch)[1]).lower(params)
    dots = [line for line in lowered.as_text().splitlines()
            if "stablehlo.dot_general" in line
            and re.search(rf"\b{VOCAB}x|x{VOCAB}x", line)]
    assert len(dots) == 1, dots
    assert "head_grad" not in lowered.as_text(debug_info=True)


# -- sharded -----------------------------------------------------------------

def _sharded_value_and_grad(cfg, plan, params, batch):
    mesh = make_mesh(plan, devices=jax.devices()[:4])
    with jax.sharding.set_mesh(mesh):
        placed = jax.device_put(
            params, tree_shardings(T.param_logical_axes(cfg), mesh))
        args = jax.device_put(batch, logical_to_sharding(("batch", "seq"),
                                                         mesh))
        step = jax.jit(jax.value_and_grad(
            lambda p, *batch: T.loss_fn(cfg, p, *batch)[0] + 1.0))
        compiled = step.lower(placed, *args).compile()
        return compiled(placed, *args), compiled.as_text()


@pytest.mark.parametrize("plan", [{"fsdp": 4}, {"fsdp": 2, "sp": 2},
                                  {"fsdp": 2, "tp": 2}],
                         ids=["fsdp4", "fsdp2-sp2", "fsdp2-tp2"])
def test_sharded_gradients_equal_the_single_device_ones(plan):
    cfg = _cfg(tied=False)
    params = T.init_params(cfg, jax.random.key(0))
    batch = _batch(batch=8)
    want = _value_and_grad(dataclasses.replace(cfg, ce_chunk=0),
                           batch)(params)
    got, _ = _sharded_value_and_grad(cfg, ParallelPlan(**plan), params,
                                     batch)
    np.testing.assert_allclose(float(got[0]), float(want[0]), rtol=1e-6)
    _assert_trees_close(got[1], want[1], rtol=1e-5, atol=1e-6)


def _computations(text):
    """{name: lines} of a compiled module's computations."""
    comps, name = {}, None
    for line in text.splitlines():
        m = re.match(r"(?:ENTRY )?%?([\w.\-]+) .*\{\s*$", line)
        if m:
            name = m.group(1)
            comps[name] = []
        elif line.startswith("}"):
            name = None
        elif name:
            comps[name].append(line)
    return comps


def _inside_loops(comps):
    """Names of the computations a `while` body reaches."""
    called = re.compile(
        r"(?:body|condition|to_apply|calls|branch_computations)="
        r"\{?%?([\w.\-]+)")
    todo = [b for lines in comps.values()
            for b in re.findall(r"body=%?([\w.\-]+)", "\n".join(lines))]
    seen = set()
    while todo:
        name = todo.pop()
        if name not in seen:
            seen.add(name)
            todo += called.findall("\n".join(comps.get(name, ())))
    return seen


def test_no_collective_of_the_heads_shape_runs_inside_the_chunk_loop():
    """Under fsdp=4 a device holds a quarter of the head and a partial
    sum of its gradient: the compiled module gathers the one before the
    scan and reduces the other behind it, once each, not once a chunk."""
    cfg = _cfg(tied=False)
    params = T.init_params(cfg, jax.random.key(0))
    _, text = _sharded_value_and_grad(cfg, ParallelPlan(fsdp=4), params,
                                      _batch(batch=8))
    comps = _computations(text)
    loops = _inside_loops(comps)
    assert loops, "the chunk scan compiled to no loop"
    head = rf"\[({cfg.d_model}|{cfg.d_model // 4}),{VOCAB}\]"

    def where(collective):
        return [name in loops for name, lines in comps.items()
                for line in lines
                if re.search(rf"= .*{head}.* {collective}(-start)?\(", line)]

    assert where("(all-reduce|reduce-scatter)") == [False]
    gathers = where("all-gather")
    assert gathers and not any(gathers), gathers
