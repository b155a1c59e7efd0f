"""The benchmark's heaviest programs at their real sizes, compiled for a
TPU v5e that is described and not attached (on-chip-measurement guide,
section 2): what the chip's compiler would refuse costs no chip time.
Nothing runs, so nothing here says anything about results or speed.

`ops/flash_attention.on_tpu()` answers for the CPU host here, so the
tests answer for it: on the chip the models take the pallas kernels at
kv >= 2048.
"""

import importlib
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmarks")
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

from lib import modelcfg  # noqa: E402
from lib.spec import Spec  # noqa: E402

HBM = 16e9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler, or its lock
        pytest.skip(f"cannot describe a v5e:2x2 topology: {e}")


@pytest.fixture(scope="module", autouse=True)
def _no_compilation_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def kernels_on(monkeypatch):
    fa = importlib.import_module("ray_tpu.ops.flash_attention")
    monkeypatch.setattr(fa, "on_tpu", lambda: True)


@pytest.fixture(scope="module")
def chat(topo, kept_chat_spec):
    """mistral-7b-v0.3-l16 at 24 slots x 2048 on one described chip."""
    from ray_tpu.models.generate import init_kv_cache
    from ray_tpu.models.transformer import init_params

    spec = kept_chat_spec
    cfg = modelcfg.transformer_config(spec.config, spec.sizes)
    one = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one), tree)

    key = on_chip(jax.eval_shape(lambda: jax.random.key(0)))
    params = on_chip(jax.eval_shape(lambda k: init_params(cfg, k), key))
    slots, max_seq = spec.sizes["slots"], spec.sizes["max_seq_len"]
    cache = on_chip(jax.eval_shape(
        lambda: init_kv_cache(cfg, slots, max_seq)))
    return cfg, one, key, params, cache, slots


def _fits(compiled):
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        + mem.output_size_in_bytes - mem.alias_size_in_bytes
    assert used < HBM, used
    return used


def test_chat_decode_block_compiles_and_fits(chat, kernels_on):
    from ray_tpu.models.generate import decode_multi

    cfg, one, key, params, cache, slots = chat
    toks = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one)
    temps = jax.ShapeDtypeStruct((slots,), jnp.float32, sharding=one)
    used = _fits(decode_multi.lower(cfg, params, cache, toks, temps, 8, 0,
                                    key).compile())
    # 7.52 GB of bf16 weights and 3.22 GB of cache are in there.
    assert used > 10e9


def test_chat_prefill_tile_compiles_and_fits(chat, kernels_on):
    """The largest admission tile of the cell: 8 prompts in the 2048
    bucket, where attention takes the pallas kernel."""
    from ray_tpu.models.generate import prefill_sample_batch
    from ray_tpu.serve.llm import LLMEngine

    cfg, one, key, params, cache, _slots = chat
    W = LLMEngine._ADMIT_TILE

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    compiled = prefill_sample_batch.lower(
        cfg, params, cache, arr((W, 2048), jnp.int32), arr((W,), jnp.int32),
        arr((W,), jnp.int32), 0, arr((W,), jnp.float32), key).compile()
    _fits(compiled)
    assert "tpu_custom_call" in compiled.as_text()


def test_train_step_compiles_and_fits_on_2x2(topo, kernels_on):
    """internlm2-1.8b, fsdp=4, 8 x 4096: state sharded four ways, the
    pallas kernels in the step."""
    from __graft_entry__ import _aot_compile_step
    from ray_tpu.parallel import ParallelPlan

    spec = Spec(ROOT, "internlm2-1b8-train-fsdp4")
    cfg = modelcfg.transformer_config(spec.config, spec.sizes)
    compiled = _aot_compile_step(
        cfg, ParallelPlan(**spec.sizes["plan"]),
        batch=spec.traffic["batch_size"], seq=spec.traffic["seq_len"],
        devices=topo.devices[:4])
    used = _fits(compiled)
    assert used > 16 * cfg.num_params() / 4     # f32 weights, grads, Adam
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "all-gather" in text and "reduce-scatter" in text
