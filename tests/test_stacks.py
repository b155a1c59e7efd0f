"""The seam between the serving programs (models/generate.py) and the
model stacks (`transformer.STACKS`): every stack offers the interface the
programs call, with the documented shapes, and nothing above the seam
asks which architecture it serves."""

import ast
import os

import jax
import jax.numpy as jnp
import pytest

import ray_tpu
from ray_tpu.models import configs, generate
from ray_tpu.models.transformer import STACKS, init_params, offered, stack

# The tiny preset of each architecture of the table.
TINY = {"llama": configs.tiny_test, "afmoe": configs.tiny_afmoe_test,
        "mellum": configs.tiny_mellum_test,
        "pangu_ultra_moe": configs.tiny_pangu_test,
        "sdar_moe": configs.tiny_sdar_test,
        "glm_moe_dsa": configs.tiny_glm_test}
OPTIONAL = ("suffix", "param_logical_axes", "forward_train")
ROOT = os.path.dirname(ray_tpu.__file__)


def test_every_architecture_of_the_table_has_a_tiny_preset():
    assert set(TINY) == set(STACKS)
    assert all(TINY[arch]().arch == arch for arch in STACKS)


@pytest.mark.parametrize("arch", sorted(STACKS))
def test_a_stack_offers_the_interface_with_the_documented_shapes(arch):
    cfg = TINY[arch]()
    st = stack(cfg)
    assert st.__name__ == "ray_tpu.models." + STACKS[arch]
    W, S, B, S_max = 2, 16, 3, 32
    D, V = cfg.d_model, cfg.vocab_size
    params = jax.eval_shape(lambda k: init_params(cfg, k), jax.random.key(0))
    assert cfg.num_params() == sum(
        p.size for p in jax.tree.leaves(params))
    cache = jax.eval_shape(lambda: st.init_cache(cfg, B, S_max))
    assert isinstance(cache, generate.KVCache)
    assert cache.seq_lens.shape == (B,) and cache.max_seq_len == S_max
    assert cache.num_slots == B

    toks = jax.ShapeDtypeStruct((W, S), jnp.int32)
    rows = jax.ShapeDtypeStruct((W,), jnp.int32)
    filled, x, tile_stats = jax.eval_shape(
        lambda p, c, t, n, s: st.prefill(cfg, p, c, t, n, s),
        params, cache, toks, rows, rows)
    assert jax.tree.map(lambda a: (a.shape, a.dtype), filled) == \
        jax.tree.map(lambda a: (a.shape, a.dtype), cache)
    assert x.shape == (W, S, D)
    free, _chosen = jax.eval_shape(
        lambda p, t: st.forward_free(cfg, p, t), params, toks)
    assert free.shape == (W, S, D)
    logits = jax.eval_shape(
        lambda p, x, n: st.last_logits(cfg, p, x, n), params, x, rows)
    assert (logits.shape, logits.dtype) == ((W, V), jnp.float32)

    def decode():
        return jax.eval_shape(
            lambda p, c, t: st.decode(cfg, p, c, t), params, cache,
            jax.ShapeDtypeStruct((B,), jnp.int32))

    if cfg.block_length:
        # A block of positions a slot a pass is its walk; one token a
        # step is refused with the reason, here and in the programs.
        Bd = cfg.block_length
        with pytest.raises(NotImplementedError) as e:
            decode()
        assert str(e.value) == st.NOT_ITS_WALK["decode"]
        with pytest.raises(NotImplementedError):
            generate.decode_step.lower(
                cfg, params, cache, jax.ShapeDtypeStruct((B,), jnp.int32))
        stepped, logits, stats = jax.eval_shape(
            lambda p, c, t, p0: st.decode_block(cfg, p, c, t, p0), params,
            cache, jax.ShapeDtypeStruct((B, Bd), jnp.int32),
            jax.ShapeDtypeStruct((B,), jnp.int32))
        assert (logits.shape, logits.dtype) == ((B, Bd, V), jnp.float32)
    else:
        stepped, logits, stats = decode()
        assert (logits.shape, logits.dtype) == ((B, V), jnp.float32)
        if hasattr(st, "decode_block"):
            with pytest.raises(NotImplementedError) as e:
                st.decode_block(cfg, params, cache, None, None)
            assert str(e.value) == st.NOT_ITS_WALK["decode_block"]
    assert jax.tree.structure(stepped) == jax.tree.structure(cache)
    assert (stats is None) == (st.routed_layers(cfg) == 0)
    assert jax.tree.map(lambda a: (a.shape, a.dtype), tile_stats) == \
        jax.tree.map(lambda a: (a.shape, a.dtype), stats)
    assert generate.routed_layers(cfg) == st.routed_layers(cfg)
    if stats is not None:
        # Four sums; a stack whose layers hold a share of their experts
        # adds the pairs routed (`routing_stats`).
        n = st.routing_stats(cfg)
        assert n in (4, 5)
        assert (stats.shape, stats.dtype) == ((n,), jnp.int32)

    # What a stack lacks, it says why; what it has, `offered` hands over.
    for name in OPTIONAL:
        if hasattr(st, name):
            assert offered(cfg, name) is getattr(st, name)
        else:
            with pytest.raises(NotImplementedError) as e:
                offered(cfg, name)
            assert str(e.value) == st.MISSING[name]
    if hasattr(st, "suffix"):
        Sp = 8
        pk = jax.ShapeDtypeStruct(
            (cfg.n_layers, Sp, cfg.n_kv_heads, cfg.head_dim), cfg.dtype)
        x, ks, vs = jax.eval_shape(
            lambda p, k, v, t: st.suffix(cfg, p, k, v, t), params, pk, pk,
            toks)
        assert x.shape == (W, S, D)
        assert ks.shape == vs.shape == (
            cfg.n_layers, W, S, cfg.n_kv_heads, cfg.head_dim)


def _tree(path):
    with open(os.path.join(ROOT, path)) as f:
        return ast.parse(f.read())


def _touches_arch(node):
    return any(isinstance(n, ast.Attribute) and n.attr == "arch"
               for n in ast.walk(node))


@pytest.mark.parametrize("path", ["models/generate.py", "serve/llm.py"])
def test_nothing_above_the_seam_asks_which_architecture_it_serves(path):
    names = set(STACKS.values())
    for node in ast.walk(_tree(path)):
        assert not (isinstance(node, ast.Compare) and _touches_arch(node)), \
            f"{path}:{node.lineno} compares cfg.arch"
        if isinstance(node, ast.Import):
            imported = [a.name for a in node.names]
        elif isinstance(node, ast.ImportFrom):
            imported = [node.module or ""] + [a.name for a in node.names]
        else:
            continue
        assert not names & {part for name in imported
                            for part in name.split(".")}, \
            f"{path}:{node.lineno} imports a stack module by name"


def test_arch_is_compared_and_looked_up_in_transformer_py_alone():
    found = set()
    for folder, _, files in os.walk(ROOT):
        for name in files:
            if not name.endswith(".py"):
                continue
            path = os.path.relpath(os.path.join(folder, name), ROOT)
            for node in ast.walk(_tree(path)):
                if isinstance(node, (ast.Compare, ast.Subscript)) \
                        and _touches_arch(node):
                    found.add(path)
    assert found == {os.path.join("models", "transformer.py")}
