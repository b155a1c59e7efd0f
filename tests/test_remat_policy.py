"""What `jax.checkpoint` keeps of a dense layer in training
(`transformer._remat`): the attention half by name where its bytes fit a
device beside the training state, nothing where they do not. The rule
as pure functions, the values against full remat's, and what the
gradient's jaxpr recomputes."""

import collections
import dataclasses
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import configs
from ray_tpu.models import transformer as T

fa = importlib.import_module("ray_tpu.ops.flash_attention")

# internlm2-1.8b as `internlm2-1b8-train-fsdp4` trains it.
CELL = T.TransformerConfig(
    vocab_size=92544, d_model=2048, n_layers=24, n_heads=16, n_kv_heads=8,
    d_ff=8192, max_seq_len=4096, dtype=jnp.bfloat16, tie_embeddings=False)
# q, out 4,096 B each + lse 64 + k, v 2,048 each + the stream 4,096, a
# token a layer.
CELL_KEPT = 8192 * 24 * 16448


@pytest.fixture
def full_remat(monkeypatch):
    """No shape fits: what `remat` meant before."""
    monkeypatch.setattr(T, "REMAT_DEVICE_BYTES", -1)


def _shapes(cfg):
    return jax.eval_shape(lambda k: T.init_params(cfg, k), jax.random.key(0))


@pytest.mark.parametrize("batch, seq, mesh, want", [
    (8, 4096, {"fsdp": 4}, CELL_KEPT),                 # the cell
    (2, 4096, {}, CELL_KEPT),                          # one chip of it
    (1, 32768, {"sp": 4}, CELL_KEPT),                  # -train-32k-sp4
    (8, 4096, {"dp": 2, "fsdp": 2}, CELL_KEPT),
    (16, 4096, {"fsdp": 4}, 2 * CELL_KEPT),
    # tp shards the heads of q, k, v, out and lse, not the stream.
    (8, 4096, {"fsdp": 2, "tp": 2},
     16384 * 24 * (12352 // 2 + 4096)),
    (8, 4096, {"pp": 4}, 4 * CELL_KEPT),               # no axis of these
])
def test_kept_bytes_a_device(batch, seq, mesh, want):
    assert T.remat_kept_bytes(CELL, batch, seq, mesh) == want


def test_kept_bytes_follow_the_activation_dtype_and_the_depth():
    f32 = dataclasses.replace(CELL, dtype=jnp.float32, n_layers=12)
    assert T.remat_kept_bytes(f32, 8, 4096, {"fsdp": 4}) \
        == 8192 * 12 * (2 * 16448 - 64)


@pytest.mark.parametrize("mesh, parts", [
    ({}, 1), ({"fsdp": 4}, 4), ({"dp": 4}, 1), ({"fsdp": 2, "tp": 2}, 4)])
def test_parameter_bytes_a_device(mesh, parts):
    """float32 weights over the axes `param_logical_axes` shards them on;
    the norms' scales (a 49th of a percent) stay whole under tp."""
    whole = 4 * CELL.num_params()
    got = T.param_bytes(CELL, _shapes(CELL), mesh)
    assert got == pytest.approx(whole / parts, rel=1e-3)
    assert T.param_bytes(CELL, _shapes(CELL), {}) == whole


@pytest.mark.parametrize("name, cfg, batch, seq, mesh, fits", [
    # The cell: 7.56e9 of state + 3.23e9 kept.
    ("cell", CELL, 8, 4096, {"fsdp": 4}, True),
    ("three sequences a chip", CELL, 12, 4096, {"fsdp": 4}, False),
    ("twice the tokens", CELL, 16, 4096, {"fsdp": 4}, False),
    ("four times the tokens", CELL, 32, 4096, {"fsdp": 4}, False),
    ("the cell on one chip", CELL, 2, 4096, {}, False),
    # chip_smoke's and bench.py's one-chip steps ran at the edge of a
    # chip under full remat and stay there; over four chips there is room.
    ("llama-654m, one chip", configs.llama_654m(), 8, 1024, {}, False),
    ("llama-654m, fsdp=4", configs.llama_654m(), 8, 1024, {"fsdp": 4}, True),
    ("llama-1b4, one chip", configs.llama_1b4(), 8, 1024, {}, False),
    ("tiny", configs.tiny_test(), 8, 128, {}, True),
])
def test_what_fits_beside_the_training_state(name, cfg, batch, seq, mesh,
                                             fits):
    assert T.remat_fits(cfg, _shapes(cfg), batch, seq, mesh) == fits


def test_the_budget_passes_the_compiles_count_of_the_cell_and_not_twice():
    """The described compile counts the cell's kept values at 3.27e9."""
    state = T.STATE_COPIES * T.param_bytes(CELL, _shapes(CELL), {"fsdp": 4})
    assert state + 3.27e9 <= T.REMAT_DEVICE_BYTES < state + 2 * 3.27e9


def _eqns(jaxpr):
    for e in jaxpr.eqns:
        yield e
        for sub in jax.core.jaxprs_in_params(e.params):
            yield from _eqns(sub)


def test_a_layer_names_the_flash_calls_residuals_and_the_stream():
    cfg = configs.tiny_test()
    params = jax.eval_shape(lambda k: T.init_params(cfg, k),
                            jax.random.key(0))
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda p, t: T.loss_fn(cfg, p, t, t, None)[0]))(
            params, jax.ShapeDtypeStruct((2, 32), jnp.int32))
    names = {e.params["name"] for e in _eqns(jaxpr.jaxpr)
             if e.primitive.name == "name"}
    assert names == set(fa.RESIDUAL_NAMES) | {T.ATTN_STREAM}
    assert len(fa.RESIDUAL_NAMES) == 5


def _recomputed(cfg, batch, seq):
    """(primitive -> count in the gradient's jaxpr, the same under
    `rematted_computation`): a scanned layer counts once."""
    params = jax.eval_shape(lambda k: T.init_params(cfg, k),
                            jax.random.key(0))
    tok = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda p, t: T.loss_fn(cfg, p, t, t, None)[0]))(params, tok)
    every, again = collections.Counter(), collections.Counter()
    for e in _eqns(jaxpr.jaxpr):
        every[e.primitive.name] += 1
        if "rematted_computation" in str(e.source_info.name_stack):
            again[e.primitive.name] += 1
    return every, again


KERNELS = dataclasses.replace(
    configs.tiny_test(), d_model=256, n_heads=2, n_kv_heads=1,
    head_dim=128, max_seq_len=2048, dtype=jnp.bfloat16, remat=True)


def test_the_recomputation_holds_the_ffns_two_products(monkeypatch):
    """With the kernels on (traced, never lowered): forward, dq and dkv
    once each, and under `rematted_computation` w_gate's and w_up's
    products alone (nothing reads w_down's output in the backward)."""
    monkeypatch.setattr(fa, "on_tpu", lambda: True)
    every, again = _recomputed(KERNELS, 1, 2048)
    assert every["pallas_call"] == 3
    assert again["pallas_call"] == 0 and again["dot_general"] == 2
    assert again["name"] == 0


def test_full_remat_runs_the_attention_half_again(monkeypatch, full_remat):
    monkeypatch.setattr(fa, "on_tpu", lambda: True)
    every, again = _recomputed(KERNELS, 1, 2048)
    assert every["pallas_call"] == 4 and again["pallas_call"] == 1
    # wq, wk, wv, wo, w_gate, w_up.
    assert again["dot_general"] == 6


def test_the_reference_path_keeps_its_residuals_too():
    """Off the TPU attention is two einsums: neither runs again."""
    cfg = dataclasses.replace(configs.tiny_test(), remat=True)
    _, again = _recomputed(cfg, 2, 32)
    assert again["dot_general"] == 2


def test_without_remat_nothing_is_checkpointed_or_recomputed():
    every, again = _recomputed(configs.tiny_test(), 2, 32)
    assert not configs.tiny_test().remat
    assert not again and every["checkpoint"] == 0
    # A name outside a checkpoint is an identity that stays in the jaxpr
    # and leaves the lowered program.
    assert every["name"] > 0


def test_an_unknown_policy_is_refused():
    cfg = dataclasses.replace(configs.tiny_test(), remat=True,
                              remat_policy="everything")
    with pytest.raises(ValueError, match="remat_policy"):
        _recomputed(cfg, 2, 32)


def _loss_and_grads(cfg, params, tokens):
    return jax.jit(jax.value_and_grad(
        lambda p: T.loss_fn(cfg, p, tokens, tokens, None)[0]))(params)


@pytest.mark.parametrize("ce_chunk", [0, 16])
@pytest.mark.parametrize("experts", [0, 4])
def test_loss_and_gradients_equal_full_remats(monkeypatch, ce_chunk,
                                              experts):
    """The same operations on the same values in the same order: kept,
    recomputed whole, or never checkpointed, the numbers agree."""
    cfg = dataclasses.replace(configs.tiny_test(), remat=True,
                              ce_chunk=ce_chunk, moe_experts=experts)
    params = T.init_params(cfg, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (2, 32), 0,
                                cfg.vocab_size)
    kept = _loss_and_grads(cfg, params, tokens)
    plain = _loss_and_grads(dataclasses.replace(cfg, remat=False), params,
                            tokens)
    monkeypatch.setattr(T, "REMAT_DEVICE_BYTES", -1)
    full = _loss_and_grads(cfg, params, tokens)
    assert float(kept[0]) == float(full[0])
    for a, b in zip(jax.tree.leaves(kept[1]), jax.tree.leaves(full[1])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_allclose(float(kept[0]), float(plain[0]), rtol=1e-6)
    for a, b in zip(jax.tree.leaves(kept[1]), jax.tree.leaves(plain[1])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=1e-7)


def test_the_rule_reads_the_mesh_it_is_traced_under(monkeypatch):
    """Under fsdp=4 a device holds a quarter of the batch and of the
    weights: a budget between the two counts keeps the names on the mesh
    and not off it."""
    from ray_tpu.parallel import ParallelPlan, make_mesh

    cfg = dataclasses.replace(configs.tiny_test(), remat=True)

    def needs(mesh):
        return T.remat_kept_bytes(cfg, 8, 32, mesh) \
            + T.STATE_COPIES * T.param_bytes(cfg, _shapes(cfg), mesh)

    assert needs({"fsdp": 4}) < needs({}) // 2
    monkeypatch.setattr(T, "REMAT_DEVICE_BYTES", needs({}) // 2)
    _, again = _recomputed(cfg, 8, 32)
    assert again["dot_general"] == 6 + 2        # full remat: the einsums too
    mesh = make_mesh(ParallelPlan(fsdp=4), devices=jax.devices()[:4])
    with jax.sharding.set_mesh(mesh):
        _, again = _recomputed(cfg, 8, 32)
    assert again["dot_general"] == 2
