"""The seam between the serving programs (models/generate.py) and the
model stacks (`transformer.STACKS`): every stack offers the interface the
programs call, with the documented shapes, and nothing above the seam
asks which architecture it serves."""

import ast
import hashlib
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import ray_tpu
from ray_tpu.models import configs, generate, stackparts
from ray_tpu.models.transformer import STACKS, init_params, offered, stack

# The tiny preset of each architecture of the table.
TINY = {"llama": configs.tiny_test, "afmoe": configs.tiny_afmoe_test,
        "mellum": configs.tiny_mellum_test,
        "pangu_ultra_moe": configs.tiny_pangu_test,
        "sdar_moe": configs.tiny_sdar_test,
        "glm_moe_dsa": configs.tiny_glm_test,
        "solar_open2": configs.tiny_solar_test,
        "jamba": configs.tiny_jamba_test,
        "ouro": configs.tiny_ouro_test,
        "kimi_linear": configs.tiny_kimi_test}
OPTIONAL = ("suffix", "param_logical_axes", "forward_train")
# What `transformer.STACKS` documents of a stack module: nothing above the
# seam calls anything else of one.
COUNTS = ("counters", "tile_counts", "block_counts", "result_counts",
          "by_products")
INTERFACE = ("init_params", "num_params", "init_cache", "prefill",
             "forward_free", "decode", "decode_block", "last_logits",
             "routed_layers", "routing_stats") + COUNTS + OPTIONAL
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

    def typed(tree):
        return jax.tree.map(lambda a: (a.shape, a.dtype), tree)

    # Every walk returns `Extras` last, whatever the configuration: a
    # looped one's `exits` has each row's exit pass, any other's is None.
    looped = cfg.ut_steps > 1

    def exits_of(extras, shape):
        assert isinstance(extras, stackparts.Extras)
        assert typed(extras.exits) == ((shape, jnp.int32) if looped else None)

    filled, x, tile = jax.eval_shape(
        lambda p, c, t, n, s: st.prefill(cfg, p, c, t, n, s),
        params, cache, toks, rows, rows)
    exits_of(tile, (W, S))
    assert typed(filled) == typed(cache)
    assert x.shape == (W, S, D)
    free, _chosen, extras = jax.eval_shape(
        lambda p, t: st.forward_free(cfg, p, t), params, toks)
    assert free.shape == (W, S, D) and extras.routing is None
    exits_of(extras, (W, S))
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
        stepped, logits, step = jax.eval_shape(
            lambda p, c, t, p0: st.decode_block(cfg, p, c, t, p0), params,
            cache, jax.ShapeDtypeStruct((B, Bd), jnp.int32),
            jax.ShapeDtypeStruct((B,), jnp.int32))
        assert (logits.shape, logits.dtype) == ((B, Bd, V), jnp.float32)
        assert isinstance(step, stackparts.Extras) and step.exits is None
    else:
        stepped, logits, step = decode()
        assert (logits.shape, logits.dtype) == ((B, V), jnp.float32)
        exits_of(step, (B,))
        if hasattr(st, "decode_block"):
            with pytest.raises(NotImplementedError) as e:
                st.decode_block(cfg, params, cache, None, None)
            assert str(e.value) == st.NOT_ITS_WALK["decode_block"]
    assert jax.tree.structure(stepped) == jax.tree.structure(cache)
    stats = step.routing
    assert (stats is None) == (st.routed_layers(cfg) == 0)
    assert typed(tile.routing) == typed(stats)
    if stats is not None:
        # Four sums; a stack whose layers hold a share of their experts
        # adds the pairs routed (`routing_stats`).
        n = st.routing_stats(cfg)
        assert n in (4, 5)
        assert (stats.shape, stats.dtype) == ((n,), jnp.int32)
    assert st.by_products(cfg) == (stats is not None or looped)

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


# -- what a stack counts on the host -----------------------------------------

# A tile of three rows in the 128 bucket holding 65, 40 and 1 tokens, 106
# real; a block of 4 steps over 3 slots of 32 rows, two of them owned,
# holding 3 and 10 rows at its first step and 50 over all of it. What each
# stack's span says of them, reckoned by hand (the latent stack's chunk is
# cut to 32 rows): `tile_counts(...)[1]`, `block_counts(...)[1]`.
TILE, BLOCK = (128, [65, 40, 1], 106), (4, 3, 32, [3, 10], 50)
RECKONED = {
    # 65 tokens end in the third chunk of four; a slot reads min(rows
    # held, index_topk = 8) a step: 3 + 4 + 5 + 6, and 4 x 8.
    "glm_moe_dsa": (dict(chunks=3, chunks_of=4),
                    dict(sparse_rows_read=18 + 32)),
    # Six linear layers: 106 tokens each; chunks of 64, 2 + 1 + 1 of the
    # rows' 3 x 2 (all six on the XLA walk, which runs every chunk); a
    # slot's states and tails 12,288 + 6,912 bytes, a held row 2 layers
    # x k and v x 2 heads x 16 x two bf16 terms.
    "solar_open2": (dict(linear_tokens=636, linear_chunks=36,
                         linear_chunks_of=36),
                    dict(linear_slot_steps=72, linear_slot_steps_live=48,
                         cache_state_bytes_live=4 * 2 * 19200,
                         cache_row_bytes_held=50 * 512)),
    # Six state-space layers, no chunks: 49,152 + 9,216 bytes a slot.
    "jamba": (dict(linear_tokens=636),
              dict(linear_slot_steps=72, linear_slot_steps_live=48,
                   cache_state_bytes_live=4 * 2 * 58368,
                   cache_row_bytes_held=50 * 256)),
    # Eight linear layers and three latent ones of 40 float32 values a row.
    "kimi_linear": (dict(linear_tokens=848, linear_chunks=48,
                         linear_chunks_of=48),
                    dict(linear_slot_steps=96, linear_slot_steps_live=64,
                         cache_state_bytes_live=4 * 2 * 25600,
                         cache_row_bytes_held=50 * 480)),
}


@pytest.mark.parametrize("arch", sorted(STACKS))
def test_a_stack_counts_its_own_mechanism_under_names_it_declared(
        arch, monkeypatch):
    cfg = TINY[arch]()
    st = stack(cfg)
    if arch == "glm_moe_dsa":
        monkeypatch.setattr(st, "PREFILL_CHUNK", 32)
    declared = st.counters(cfg)
    assert all(n == 0 or n == [0] * cfg.ut_steps for n in declared.values())
    # A block of two steps' by-products, and a tile's (`k` 0): three
    # slots, of which the first was given both steps' tokens, the second
    # one, the third none.
    n = st.routing_stats(cfg) if st.routed_layers(cfg) else 0
    routing = np.array([5, 40, 9, 30, 160][:n]) if n else None
    exits = np.array([[0, 2, 1], [2, 2, 0]]) if cfg.ut_steps > 1 else None
    extras = stackparts.Extras(routing, exits)
    tile, block = st.tile_counts(cfg, *TILE), st.block_counts(cfg, *BLOCK)
    read = st.result_counts(cfg, 2, extras, [2, 1, 0])
    first = st.result_counts(cfg, 0, stackparts.Extras(
        routing, None if exits is None else exits[0]), [1, 1, 0])
    counted = [found[0] for found in (tile, block, read, first)]
    assert set().union(*counted) == set(declared)
    assert st.by_products(cfg) == bool(read[0])

    want_tile, want_block = RECKONED.get(arch, ({}, {}))
    assert {k: tile[1][k] for k in want_tile} == want_tile
    assert block[1] == want_block
    if arch == "glm_moe_dsa":
        # The one pair a span and the counters name apart, paired here.
        cols = dict(zip(("choice_columns", "choice_columns_of"),
                        st.choice_columns(cfg, 128, 65)))
        assert tile == (dict(prefill_chunks=3, prefill_chunks_of=4, **cols),
                        dict(chunks=3, chunks_of=4, **cols))
    else:
        assert tile[0] == tile[1] == want_tile and block[0] == block[1]

    sums = {}
    if n:
        sums = dict(moe_experts_hit=5, moe_rows=40, moe_rows_max=9,
                    moe_rows_taken=30, moe_pairs=160 if n == 5 else 40,
                    moe_pairs_held=40)
        assert first[0] == first[1] == {
            "prefill_" + name: v for name, v in sums.items()}
        sums["moe_expert_steps"] = 2 * st.routed_layers(cfg) * cfg.moe_experts
    if exits is None:
        assert read[0] == read[1] == sums
    else:
        # Delivered: passes 0 and 2 of the first slot, 2 of the second.
        assert read == (dict(loop_passes=9, loop_exit_hist=[1, 0, 2]),
                        dict(loop_passes=9, loop_exit_p1=1, loop_exit_p2=0,
                             loop_exit_p3=2))
        assert first[0] == dict(loop_passes=6, loop_exit_hist=[1, 0, 1])


def _tree(path):
    with open(os.path.join(ROOT, path)) as f:
        return ast.parse(f.read())


def _imported(node):
    """The names an import statement mentions, dotted ones in parts."""
    if isinstance(node, ast.Import):
        names = [a.name for a in node.names]
    elif isinstance(node, ast.ImportFrom):
        names = [node.module or ""] + [a.name for a in node.names]
    else:
        return set()
    return {part for name in names for part in name.split(".")}


def _touches_arch(node):
    return any(isinstance(n, ast.Attribute) and n.attr == "arch"
               for n in ast.walk(node))


@pytest.mark.parametrize("path", ["models/generate.py", "serve/llm.py"])
def test_nothing_above_the_seam_asks_which_architecture_it_serves(path):
    names = set(STACKS.values())
    for node in ast.walk(_tree(path)):
        assert not (isinstance(node, ast.Compare) and _touches_arch(node)), \
            f"{path}:{node.lineno} compares cfg.arch"
        assert not names & _imported(node), \
            f"{path}:{node.lineno} imports a stack module by name"


# What a stack's mechanism is configured by and kept in: the stack's to
# read, and nobody's above the seam.
MECHANISM = ("index_topk", "ut_steps", "early_exit_threshold", "moe_experts")
MECHANISM_PREFIXES = ("linear_", "mamba_")
STATE = ("s", "c", "ki", "tails")


def _source(node):
    return ast.unparse(node)


@pytest.mark.parametrize("path", ["models/generate.py", "serve/llm.py"])
def test_nothing_above_the_seam_reads_a_stack_s_mechanism(path):
    """The programs and the engine read no configuration field that says
    how a stack attends, loops or routes, no kind of cache entry but keys
    and values, and call on `stack(cfg)` the documented interface alone:
    what a mechanism counts, its stack reckons (`stackparts.counters`)."""
    tree = _tree(path)
    # Whatever `stack(...)` was assigned to (`st`, `self._stack`).
    stacks = {"stack(cfg)", "stack(self.cfg)"}
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign) and isinstance(node.value, ast.Call) \
                and _source(node.value.func) == "stack":
            stacks |= {_source(t) for t in node.targets}
    called = set()
    for node in ast.walk(tree):
        if not isinstance(node, ast.Attribute):
            continue
        where = f"{path}:{node.lineno} reads .{node.attr}"
        assert node.attr not in MECHANISM, where
        assert not node.attr.startswith(MECHANISM_PREFIXES), where
        owner = _source(node.value)
        assert not (node.attr in STATE and "cache" in owner), where
        if owner in stacks:
            called.add(node.attr)
            assert node.attr in INTERFACE, where + " of a stack"
    assert called, f"{path} reaches no stack"


@pytest.mark.parametrize("module", sorted(set(STACKS.values()))
                         + ["moe", "stackparts", "mla"])
def test_imports_under_the_seam_point_down(module):
    """The programs reach a stack through `transformer.stack`; a stack
    reaches neither the programs nor another stack, and what the stacks
    share (`stackparts.py`, `moe.py`, `mla.py`) imports no stack at all."""
    others = set(STACKS.values()) - {module}
    for node in ast.walk(_tree(f"models/{module}.py")):
        names = _imported(node)
        assert "generate" not in names, \
            f"models/{module}.py:{node.lineno} imports the programs"
        assert not names & others, \
            f"models/{module}.py:{node.lineno} imports a stack"


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


# -- the weights a seed makes -------------------------------------------------

# sha256[:16] over every leaf's path, shape, dtype and bytes of
# `init_params(preset(), jax.random.key(0))` on commit 13cf627, before the
# routed stacks' builders became `stackparts.init_params` (this machine,
# jax 0.9.0, the CPU). The dense stack's builder is its own and splits
# its key in another order: its two rows are here so that whoever folds
# it in sees what that changes.
SEEDED = {
    "tiny_test": "ff43afc140b2a4be",
    "tiny_moe_test": "82d30b27bc52ff21",
    "tiny_afmoe_test": "e233d94d4af14af3",
    "tiny_mellum_test": "1179e5f4795df8ba",
    "tiny_sdar_test": "52fb45e14a6e01c2",
    "tiny_pangu_test": "a01e545ed20b21c1",
    "tiny_glm_test": "05192f44f3a1ebda",
    # PR 46's preset, on the tree that added it (kinds of layer with
    # leaves of their own: a key a layer's dict, of it a key a leaf).
    "tiny_solar_test": "dff022f1f7c5bb71",
    # PR 50's preset, on the tree that added it (a dense FFN's matrices
    # under the layer's own dict).
    "tiny_jamba_test": "b5ca64afbe25f7eb",
    # PR 55's preset, on the tree that added it (a looped walk: the exit
    # gate from a key folded out of the seed, residual outputs scaled by
    # the depth walked).
    "tiny_ouro_test": "c715eedf9dc5f33f",
    # PR 57's preset, on the tree that added it (a plan of three groups
    # read from lists: a leading layer's own leaves under its kind, a
    # tail's beside a period's).
    "tiny_kimi_test": "fe7ce9e5b5630662",
}


@pytest.mark.parametrize("preset", sorted(SEEDED))
def test_a_seed_makes_the_weights_it_made(preset):
    params = init_params(getattr(configs, preset)(), jax.random.key(0))
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_leaves_with_path(params):
        a = np.asarray(leaf)
        h.update(f"{jax.tree_util.keystr(path)} {a.shape} {a.dtype}\n"
                 .encode())
        h.update(a.tobytes())
    assert h.hexdigest()[:16] == SEEDED[preset]


# -- the walk over a plan -----------------------------------------------------

def test_the_walk_tells_each_layer_its_place_once_and_in_order():
    """Two leading layers, a layer a step, then three steps of two routed
    layers: `layer_at` is asked for each place of a group once (a scan's
    body is traced once), the layers run in stack order, a routed layer
    is handed the stack's expert matrices whole with its own first
    expert, a step's leaves without them, and the stats add up."""
    cfg = configs.tiny_afmoe_test()
    E = cfg.moe_experts
    plan = [stackparts.Group("lead", (2,), False),
            stackparts.Group("pairs", (3, 2), True)]
    params = {"lead": {"w": jnp.ones((2, 5))},
              "pairs": {"w": jnp.ones((3, 2, 5)),
                        **{leaf: jnp.zeros((3, 2, E, 1, 1))
                           for leaf in stackparts.EXPERT_LEAVES}}}
    asked = []

    def layer_at(i, g, j):
        asked.append((i, j))

        def layer(lp, x, experts_at, state):
            assert set(lp) == {"w"} and lp["w"].shape == (5,)
            log, n = state
            state = log.at[n].set(jnp.stack([i, g, j])), n + 1
            if experts_at is None:
                assert not plan[i].routed
                return x + 1, state, None, None
            w, first = experts_at
            assert plan[i].routed and set(w) == set(stackparts.EXPERT_LEAVES)
            assert all(a.shape == (3 * 2 * E, 1, 1) for a in w.values())
            return x + 1, state, jnp.ones((4,), jnp.int32), first[None, None]

        return layer

    x, (log, n), stats, chosen = jax.jit(
        lambda p: stackparts.run(
            cfg, p, plan, jnp.zeros(()), layer_at,
            (jnp.zeros((8, 3), jnp.int32), jnp.int32(0))))(params)
    assert asked == [(0, 0), (1, 0), (1, 1)]
    assert int(x) == int(n) == 8
    assert log.tolist() == [[0, 0, 0], [0, 1, 0]] + [
        [1, g, j] for g in range(3) for j in range(2)]
    assert stats.tolist() == [6] * 4
    assert [len(group) for group in chosen] == [0, 2]
    assert all(a.shape == (3, 1, 1) for a in chosen[1])
    assert [int(a[0, 0]) for a in stackparts.chosen_by_layer(chosen)] == [
        l * E for l in range(6)]
    assert stackparts.routed_layers(plan) == 6


@pytest.mark.parametrize("preset", ["tiny_afmoe_test", "tiny_pangu_test"])
def test_chosen_experts_come_back_a_routed_layer_in_layer_order(preset):
    """The interface's shapes: for tokens (S,), one (S, K) array a routed
    layer, numbered as the router numbers its experts. The leading dense
    layer adds none; a layer's choice depends on the layers before it,
    so no two layers' arrays are alike."""
    cfg = getattr(configs, preset)()
    st = stack(cfg)
    params = init_params(cfg, jax.random.key(0))
    tokens = np.arange(3, 15) % cfg.vocab_size
    chosen = st.chosen_experts(cfg, params, tokens)
    assert len(chosen) == st.routed_layers(cfg) \
        == cfg.n_layers - cfg.n_dense_layers
    for a in chosen:
        assert a.shape == (len(tokens), cfg.moe_top_k)
        assert jnp.issubdtype(a.dtype, jnp.integer)
        assert 0 <= int(a.min()) and int(a.max()) < cfg.router_experts
    assert len({np.asarray(a).tobytes() for a in chosen}) == len(chosen)
