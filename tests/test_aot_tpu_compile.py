"""The main path's programs, compiled at real widths for a TPU v5e that is
described and not attached (on-chip-measurement guide, section 2).

Interpret mode on the CPU cannot see what the TPU's compiler refuses: a
block that is not aligned to the tiling, too much fast memory, an eager
op on a device that is not there. These compiles can, at no chip time.
Nothing runs, so nothing here says anything about results or speed.
Skipped as a whole where the topology cannot be described.

One file, so that one process loads the TPU's compiler at a time: split a
family a file under six `--dist loadfile` workers, a compile aborted inside
libtpu and took its worker down (my run, PR 50). Named to sort early: the
file is ten minutes of one worker, and `loadfile` hands files out in
order, so as `test_tpu_aot_compile.py` it began last and every other
worker waited for it.
"""

import dataclasses
import importlib
import json
import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or libtpu logs to /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from ray_tpu.models import configs

fa = importlib.import_module("ray_tpu.ops.flash_attention")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
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


def _qkv(topo, sq, skv, h, kvh, d):
    one = SingleDeviceSharding(topo.devices[0])
    return (jax.ShapeDtypeStruct((1, sq, h, d), jnp.bfloat16, sharding=one),
            jax.ShapeDtypeStruct((1, skv, kvh, d), jnp.bfloat16,
                                 sharding=one))


def _fwd_bwd(q, k, v, **kw):
    def loss(q, k, v):
        return jnp.sum(fa.flash_attention(
            q, k, v, causal=True, interpret=False, **kw
        ).astype(jnp.float32))
    return jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)


GEOMETRY = {"llama-654m": (12, 4, 128), "gpt2-125m": (12, 12, 64)}


@pytest.mark.parametrize("model", sorted(GEOMETRY))
@pytest.mark.parametrize("seq", [1024, 8192])
def test_flash_fwd_bwd_compiles(topo, model, seq):
    """Forward and both backward kernels at the model's head geometry.
    S=1024 is below the kv crossover (the models take XLA's attention
    there), so the kernels are forced; S=8192 takes them by itself."""
    q, k = _qkv(topo, seq, seq, *GEOMETRY[model])
    text = jax.jit(_fwd_bwd, static_argnames="force_pallas").lower(
        q, k, k, force_pallas=seq < fa._XLA_CROSSOVER_SKV
    ).compile().as_text()
    assert text.count("tpu_custom_call") >= 3   # fwd, dq, dkv


def test_flash_fwd_alone_compiles(topo):
    q, k = _qkv(topo, 8192, 8192, *GEOMETRY["llama-654m"])
    text = jax.jit(lambda q, k, v: fa.flash_attention(
        q, k, v, causal=True, interpret=False)).lower(
            q, k, k).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("sq,skv,blocks", [
    # S=1000: the largest divisors were 250 and 500, neither a multiple
    # of 8 rows, and the lowering refused them.
    (1000, 1000, (200, 200)),
    # The serve suffix path, prefix 2048 + suffix bucket 16: kv length
    # 2064 = 2^4·3·43 used to pick 516.
    (16, 2064, (16, 344)),
])
def test_formerly_unaligned_blocks_compile(topo, sq, skv, blocks):
    assert fa.tileable(sq, skv, 128, 256, 512) == blocks
    q, k = _qkv(topo, sq, skv, *GEOMETRY["llama-654m"])
    text = jax.jit(lambda q, k, v: fa.flash_attention(
        q, k, v, causal=True, q_offset=skv - sq, interpret=False,
        force_pallas=True)).lower(q, k, k).compile().as_text()
    assert "tpu_custom_call" in text


def test_untileable_shape_is_counted_not_refused(topo):
    """No multiple of 8 divides 2^2·503 = 2012 under the block target:
    the call takes the reference, visibly, instead of failing in
    Mosaic."""
    assert fa.tileable(2012, 2012, 128, 256, 512) == (0, 0)
    q, k = _qkv(topo, 2012, 2012, *GEOMETRY["llama-654m"])
    before = fa.DISPATCH_COUNTS["reference_untileable"]
    text = jax.jit(lambda q, k, v: fa.flash_attention(
        q, k, v, causal=True, interpret=False, force_pallas=True)).lower(
            q, k, k).compile().as_text()
    assert fa.DISPATCH_COUNTS["reference_untileable"] == before + 1
    assert "tpu_custom_call" not in text


# (B, S, H, KVH, window, trains): the flash calls of the benchmark's cells:
# docqa's one-row tile, a chip's share of the train step's batch, and
# mellum's tile on a global and on a window layer.
CELL_FLASH = {
    "mistral7b-docqa-lone": (1, 4096, 32, 8, None, False),
    "internlm2-1b8-train-fsdp4": (2, 4096, 16, 8, None, True),
    "mellum2-repoctx-lone.global": (1, 8192, 32, 4, None, False),
    "mellum2-repoctx-lone.window": (1, 8192, 32, 4, 1024, False),
}


@pytest.mark.parametrize("cell", sorted(CELL_FLASH))
def test_flash_compiles_at_the_cells_shapes(topo, cell):
    """The forward in the blocks it chooses itself, K and V unexpanded,
    its grid the table of live pairs (`FLASH_GRID`: no step without a
    live pair); where the cell trains, dq and dkv behind it in theirs
    (`_bwd_blocks`), on tables of their own, K and V of 8 heads in and
    dk and dv of 8 heads out."""
    B, S, H, KVH, window, trains = CELL_FLASH[cell]
    one = SingleDeviceSharding(topo.devices[0])
    q = jax.ShapeDtypeStruct((B, S, H, 128), jnp.bfloat16, sharding=one)
    k = jax.ShapeDtypeStruct((B, S, KVH, 128), jnp.bfloat16, sharding=one)
    before = dict(fa.FLASH_GRID)
    if trains:
        lowered = jax.jit(_fwd_bwd).lower(q, k, k)
    else:
        lowered = jax.jit(lambda q, k, v: fa.flash_attention(
            q, k, v, causal=True, window=window, interpret=False)).lower(
                q, k, k)
    grid = {n: c - before.get(n, 0) for n, c in fa.FLASH_GRID.items()}
    for kernel in ("", "dq_", "dkv_") if trains else ("",):
        assert grid[kernel + "steps"] == grid[kernel + "live_steps"] \
            > grid[kernel + "masked_steps"] > 0
        assert not grid.get(kernel + "traced_steps")
    jaxpr_kernels = [e.params["metadata"]["kernel"] for e in _eqns(
        jax.make_jaxpr(lambda q, k, v: fa.flash_attention(
            q, k, v, causal=True, window=window, interpret=False))(
                q, k, k).jaxpr) if e.primitive.name == "pallas_call"]
    assert jaxpr_kernels == ["flash_fwd"]       # one call a forward
    text = lowered.compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == \
        (3 if trains else 1)
    for kernel in ("flash_fwd",) + (("flash_dq", "flash_dkv")
                                    if trains else ()):
        assert f'"kernel":"{kernel}"' in text.replace(" ", "")
    if trains:
        _assert_backward_reads_kv_heads(text, B, S, H, KVH)


def _flash_calls(text):
    """{kernel: [(result shapes, operand shapes)]} of a compiled text's
    pallas calls, by their `kernel_metadata`."""
    calls = {}
    for m in re.finditer(
            r'= ([^\n]*?) custom-call\([^\n]*?custom_call_target='
            r'"tpu_custom_call", operand_layout_constraints=\{(.*?)\}, '
            r'frontend_attributes.*?"kernel"\s*:\s*"(\w+)"', text, re.S):
        calls.setdefault(m[3], []).append(tuple(
            re.findall(r"\w+\[[\d,]*\]", part) for part in m.group(1, 2)))
    return calls


def _assert_backward_reads_kv_heads(text, B, S, H, KVH):
    """dq and dkv take K and V of KVH heads and dkv writes dk and dv of
    KVH heads: nothing expands them in front or sums them behind; the
    row statistics arrive a q block a row, not 128 lanes wide."""
    q, kv = f"bf16[{B},{H},{S},128]", f"bf16[{B},{KVH},{S},128]"
    calls = _flash_calls(text)
    (dq_out, dq_in), = calls["flash_dq"]
    (dkv_out, dkv_in), = calls["flash_dkv"]
    assert dq_out == [q] and dkv_out == [kv, kv]
    for operands in (dq_in, dkv_in):
        wide = [x for x in operands if not x.startswith("s32")]
        assert wide[:4] == [q, kv, kv, q]               # q, k, v, do
        assert all(re.fullmatch(rf"f32\[{B},{H},\d+,1,\d+\]", x)
                   for x in wide[4:]) and len(wide) == 6, wide


def _eqns(jaxpr):
    for e in jaxpr.eqns:
        yield e
        for sub in jax.core.jaxprs_in_params(e.params):
            yield from _eqns(sub)


TRAIN_STEPS = {"as-the-cell-runs": (8, 1), "over-the-budget": (12, 2)}


@pytest.mark.parametrize("case", sorted(TRAIN_STEPS))
def test_train_step_holds_one_flash_forward_where_its_residuals_fit(
        topo, as_on_the_chip, case, record_property):
    """The cell's whole step (`internlm2-1b8-train-fsdp4`: 24 scanned
    layers under `remat`, fsdp=4, sequences of 4,096). Two sequences a
    chip, as the cell runs: the layer keeps the attention half
    (`transformer._remat`), so the compiled text holds the forward,
    dq and dkv each once, nothing expanded K and V for either
    (`_assert_backward_reads_kv_heads`), and the step fits the described
    chip. Three a chip do not fit beside the state (`remat_fits`): full
    remat, whose second forward is in the text."""
    from ray_tpu.models import transformer

    batch, forwards = TRAIN_STEPS[case]
    cfg = _train_cell_config()
    kept = transformer.remat_kept_bytes(cfg, batch, 4096, {"fsdp": 4})
    shapes = jax.eval_shape(lambda k: transformer.init_params(cfg, k),
                            jax.random.key(0))
    assert transformer.remat_fits(cfg, shapes, batch, 4096, {"fsdp": 4}) \
        == (forwards == 1)
    compiled = _aot_compile_step(topo, cfg, 4, batch=batch, seq=4096)
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') \
        == forwards + 2
    calls = _flash_calls(text)
    assert sorted(calls) == ["flash_dkv", "flash_dq", "flash_fwd"]
    assert len(calls["flash_fwd"]) == forwards
    _assert_backward_reads_kv_heads(text, batch // 4, 4096, 16, 8)
    mem = compiled.memory_analysis()
    record_property("kept_gb", kept / 1e9)
    record_property("temp_gb", mem.temp_size_in_bytes / 1e9)
    record_property("argument_gb", mem.argument_size_in_bytes / 1e9)
    if forwards == 1:
        assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75e9


def test_train_step_makes_the_heads_gradients_where_it_makes_the_logits(
        topo, as_on_the_chip, record_property):
    """The same step (`internlm2-1b8-train-fsdp4`, two sequences a chip):
    the loss head's scan sits in the forward pass with its gradient half
    (`head_grad`) and nothing of it runs again in the backward pass; the
    head is gathered before the scan and its gradient, a partial sum a
    device, reduced once behind it, neither once a chunk inside it; and
    the whole still fits the described chip, with the partial sums gone
    before the backward."""
    cfg = _train_cell_config()
    compiled = _aot_compile_step(topo, cfg, 4, batch=8, seq=4096)
    text = compiled.as_text()
    scopes = set(re.findall(r'op_name="([^"]*loss_head[^"]*)"', text))
    assert any("/jvp(loss_head)/while/body/" in s and "/head_grad/" in s
               for s in scopes)
    assert not [s for s in scopes if "rematted_computation" in s
                or "transpose(jvp(loss_head))/while" in s]
    head = f"[{cfg.d_model},{cfg.vocab_size}]"

    def scopes_of(collective):
        """Scope paths of the collectives whose result has the head's
        shape."""
        return [m.group(2) for m in re.finditer(
            rf'= (\S+) {collective}\(.*?op_name="([^"]*)"', text)
            if head in m.group(1)]

    reduces, gathers = scopes_of("all-reduce"), scopes_of("all-gather")
    assert len(reduces) == 1 and gathers
    assert not [s for s in reduces + gathers
                if "loss_head" not in s or "/while/" in s], (reduces, gathers)
    mem = compiled.memory_analysis()
    record_property("temp_gb", mem.temp_size_in_bytes / 1e9)
    record_property("argument_gb", mem.argument_size_in_bytes / 1e9)
    total = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert total < 15.75e9, (
        f"arguments {mem.argument_size_in_bytes / 1e9:.2f} GB + temporaries "
        f"{mem.temp_size_in_bytes / 1e9:.2f} GB = {total / 1e9:.2f} GB a "
        f"device (output {mem.output_size_in_bytes / 1e9:.2f}, aliased "
        f"{mem.alias_size_in_bytes / 1e9:.2f})")


def _train_cell_config():
    with open(os.path.join(ROOT, "benchmarks", "cells",
                           "internlm2-1b8-train-fsdp4.json")) as f:
        return _benchmark_config("internlm2-1.8b", json.load(f))


def _benchmark_config(name, sizes):
    """A configuration of benchmarks/configs/ at a cell's sizes."""
    sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
    try:
        from lib import modelcfg
    finally:
        sys.path.remove(os.path.join(ROOT, "benchmarks"))
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           name + ".json")) as f:
        return modelcfg.transformer_config(json.load(f), sizes)


def _bf16(max_seq):
    return {"model": {"dtype": "bfloat16", "param_dtype": "bfloat16",
                      "max_seq_len": max_seq}}


def _serve_structs(topo, cfg, slots, max_seq):
    from ray_tpu.models.generate import init_kv_cache
    from ray_tpu.models.transformer import init_params

    one = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one), tree)

    key = on_chip(jax.eval_shape(lambda: jax.random.key(0)))
    params = on_chip(jax.eval_shape(lambda k: init_params(cfg, k), key))
    cache = on_chip(jax.eval_shape(
        lambda: init_kv_cache(cfg, slots, max_seq)))
    return one, key, params, cache


@pytest.fixture(scope="module")
def serve_654m(topo):
    cfg = dataclasses.replace(configs.llama_654m(),
                              param_dtype=jnp.bfloat16, max_seq_len=1024)
    return (cfg,) + _serve_structs(topo, cfg, 16, 1024)


def test_decode_multi_compiles_at_654m(serve_654m):
    """The engine's fused decode block (8 steps, 16 slots, S_max 1024,
    bf16 weights) fits and compiles for one chip."""
    from ray_tpu.models.generate import decode_multi

    cfg, one, key, params, cache = serve_654m
    toks = jax.ShapeDtypeStruct((16,), jnp.int32, sharding=one)
    temps = jax.ShapeDtypeStruct((16,), jnp.float32, sharding=one)
    mem = decode_multi.lower(cfg, params, cache, toks, temps, 8, 0,
                             key).compile().memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9


# The serving cells' shapes (benchmarks/cells/*.json), and the one the
# batch cell's file says could not compile while decode copied the cache.
DECODE_SHAPES = {
    "batch-closed": ("internlm2-1.8b", 32, 1024),
    "docqa-lone": ("mistral-7b-v0.3-l16", 4, 4096),
    "batch-closed-at-64-slots": ("internlm2-1.8b", 64, 1024),
}
_HLO_INSTR = re.compile(
    r"^\s+(?:ROOT )?%(?P<name>[\w.\-]+) = \w+\[(?P<dims>[\d,]*)\]\S* "
    r"(?P<op>[\w\-]+)\((?:.*calls=%(?P<calls>[\w.\-]+))?")


def _device_writes(text):
    """{result dims: [(name, opcode, opcodes inside a fusion's body)]} of
    the instructions outside any fused computation: what the device
    writes out, the lines of a trace's operation list."""
    bodies, current = {}, None
    for line in text.splitlines():
        if line.startswith(("%", "ENTRY")):
            current = line.split()[line.startswith("ENTRY")].lstrip("%")
            bodies[current] = []
        elif current and (m := _HLO_INSTR.match(line)):
            bodies[current].append(m)
    fused = {m["calls"] for ms in bodies.values() for m in ms
             if m["op"] == "fusion"}

    def ops_of(comp):
        ops = set()
        for m in bodies.get(comp, ()):
            ops.add(m["op"])
            if m["calls"]:
                ops |= ops_of(m["calls"])
        return ops

    out = {}
    for comp, ms in bodies.items():
        for m in ms if comp not in fused else ():
            dims = tuple(int(d) for d in m["dims"].split(",") if d)
            out.setdefault(dims, []).append(
                (m["name"], m["op"],
                 ops_of(m["calls"]) if m["op"] == "fusion" else set()))
    return out


@pytest.mark.parametrize("cell", sorted(DECODE_SHAPES))
def test_decode_block_updates_the_cache_in_place(topo, cell):
    """`decode_multi` (k = 8) at a serving cell's real shapes, bf16: the
    cache is carried and aliased, one scatter a layer writes K's rows
    and one V's, and nothing else produces an array of the cache's
    shape; of a layer slab's shape only the two reads that feed the
    einsums. The compiler stages those reads (`constant_dynamic-slice_
    fusion`, PERF.md section 5): they are counted here, not hidden."""
    from ray_tpu.models.generate import decode_multi

    name, slots, max_seq = DECODE_SHAPES[cell]
    cfg = _benchmark_config(name, _bf16(max_seq))
    one, key, params, cache = _serve_structs(topo, cfg, slots, max_seq)
    toks = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one)
    temps = jax.ShapeDtypeStruct((slots,), jnp.float32, sharding=one)
    compiled = decode_multi.lower(cfg, params, cache, toks, temps, 8, 0,
                                  key).compile()
    mem, writes = compiled.memory_analysis(), _device_writes(
        compiled.as_text())

    def nbytes(x):
        return x.size * x.dtype.itemsize

    # (a) What has the whole cache's shape: parameters, the loops'
    # carried tuples, and one in-place scatter each for K and V.
    whole = [w for w in writes.get(cache.k.shape, ())
             if w[1] not in ("parameter", "get-tuple-element", "bitcast")]
    assert len(whole) == 2, whole
    assert all(op == "fusion" and "scatter" in body
               and not body & {"copy", "dynamic-update-slice"}
               for _, op, body in whole), whole
    # A layer slab: read once for K and once for V, never written back.
    slab = cache.k.shape[1:]
    slabs = [w for w in writes.get(slab, []) + writes.get((1,) + slab, [])
             if w[1] != "bitcast"]
    assert len(slabs) <= 2, slabs
    assert all(op == "fusion" and "dynamic-slice" in body
               and not body & {"copy", "dynamic-update-slice", "scatter"}
               for _, op, body in slabs), slabs
    # (b) Temporaries: the compiler's own relayout of the stacked wq,
    # wk and wv once a block (more than K alone only at docqa's widths)
    # and at most one layer slab (at 64 slots a slab is 128 MiB and no
    # longer fits the core's fast memory).
    relayout = sum(nbytes(params["layers"][w]) for w in ("wq", "wk", "wv"))
    assert (mem.temp_size_in_bytes - relayout
            < nbytes(cache.k) // cfg.n_layers + 2e6)
    # (c) The output is the donated cache, and the program fits.
    assert mem.alias_size_in_bytes >= 2 * nbytes(cache.k)
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75e9


# The carried caches of the three serving cells (leading dimension: layers
# x bf16 terms a row), with the query's heads and dtype.
ATTN_SHAPES = {
    "batch-closed": ((24, 32, 1024, 8, 128), 2, jnp.bfloat16),
    "docqa-lone": ((16, 4, 4096, 8, 128), 4, jnp.bfloat16),
    "trinity-global": ((2, 32, 4096, 4, 128), 8, jnp.float32),
    "trinity-ring": ((8, 32, 2048, 4, 128), 8, jnp.float32),
    # chip_smoke.py's server: 12 query heads, not a whole tile of them.
    "llama-654m": ((20, 16, 1024, 4, 128), 3, jnp.bfloat16),
}


@pytest.mark.parametrize("case", sorted(ATTN_SHAPES))
def test_decode_attention_kernel_compiles(topo, case):
    """`ops/decode_attention` over a cache carried through a layer loop,
    a row written before each read as the decode programs do it: one
    kernel, and no copy of the cache or of a layer of it beside it."""
    from ray_tpu.ops import decode_attention as da

    shape, G, q_dtype = ATTN_SHAPES[case]
    Lt, B, S, KVH, Dh = shape
    one = SingleDeviceSharding(topo.devices[0])
    L = Lt // da.terms_of(q_dtype, jnp.bfloat16)

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    def walk(q, k_all, v_all, n_rows):
        def layer(carry, l):
            k_all, v_all, acc = carry
            k_all = k_all.at[l, jnp.arange(B), n_rows % S].set(
                jnp.ones((B, KVH, Dh), k_all.dtype), mode="drop")
            out = da.decode_attention(q, k_all, v_all, l, n_rows)
            return (k_all, v_all, acc + out.astype(jnp.float32)), None
        acc = jnp.zeros((B, 1, KVH * G * Dh), jnp.float32)
        return jax.lax.scan(layer, (k_all, v_all, acc), jnp.arange(L))[0]

    compiled = jax.jit(walk, donate_argnums=(1, 2)).lower(
        arr((B, KVH, G, Dh), q_dtype), arr(shape, jnp.bfloat16),
        arr(shape, jnp.bfloat16), arr((B,), jnp.int32)).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert text.count("tpu_custom_call") == 1 and "decode_attn" in text
    assert mem.temp_size_in_bytes < 2e6


@pytest.mark.parametrize("cell", ["batch-closed", "docqa-lone"])
def test_decode_block_reads_the_cache_through_the_kernel(
        topo, as_on_the_chip, cell):
    """`decode_multi` (k = 8) as the chip compiles it, told which slots
    are owned: attention is the kernel, nothing of a layer slab's shape
    is produced any more, and the cache is still written in place."""
    from ray_tpu.models.generate import decode_multi

    name, slots, max_seq = DECODE_SHAPES[cell]
    cfg = _benchmark_config(name, _bf16(max_seq))
    one, key, params, cache = _serve_structs(topo, cfg, slots, max_seq)
    toks = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one)
    temps = jax.ShapeDtypeStruct((slots,), jnp.float32, sharding=one)
    live = jax.ShapeDtypeStruct((slots,), jnp.bool_, sharding=one)
    compiled = decode_multi.lower(cfg, params, cache, toks, temps, 8, 0,
                                  key, live).compile()
    text = compiled.as_text()
    mem, writes = compiled.memory_analysis(), _device_writes(text)
    assert text.count("tpu_custom_call") == 1 and "decode_attn" in text
    slab = cache.k.shape[1:]
    assert not [w for w in writes.get(slab, []) + writes.get((1,) + slab, [])
                if w[1] != "bitcast"]
    whole = [w for w in writes.get(cache.k.shape, ())
             if w[1] not in ("parameter", "get-tuple-element", "bitcast")]
    assert len(whole) == 2 and all(
        op == "fusion" and "scatter" in body
        and not body & {"copy", "dynamic-update-slice"}
        for _, op, body in whole), whole
    assert mem.alias_size_in_bytes >= 2 * cache.k.size * 2
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75e9


def test_prefill_sample_batch_compiles_at_654m(serve_654m):
    """One admission tile of the 128 bucket, as wide as the engine
    builds it (4 rows)."""
    from ray_tpu.models.generate import prefill_sample_batch
    from ray_tpu.serve.llm import LLMEngine

    cfg, one, key, params, cache = serve_654m
    W = LLMEngine._tile_rows(128)

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    prefill_sample_batch.lower(
        cfg, params, cache, arr((W, 128), jnp.int32), arr((W,), jnp.int32),
        arr((W,), jnp.int32), 0, arr((W,), jnp.float32), key).compile()


# The document cell's admission tile (benchmarks/cells/mistral7b-docqa-
# lone.json: 4 slots x 4096), as the engine builds it, and the tile and
# cache the cell would need at 8,192: (bucket, slots x max_seq_len,
# most temporaries in GB). Read here (PR 29): 0.64 GB at 1 x 4096 (an
# 8 x 4096 tile: 5.64), 1.41 GB at 1 x 8192 beside 9.66 GB of arguments.
DOCQA_TILES = {"as-the-cell-runs": (4096, 4, 4096, 0.8),
               "at-8192": (8192, 4, 8192, 1.8)}


@pytest.mark.parametrize("case", sorted(DOCQA_TILES))
def test_docqa_admission_tile_is_one_row_and_fits(topo, as_on_the_chip,
                                                  case, record_property):
    """`prefill_sample_batch` on `mistral-7b-v0.3-l16`, bf16, one row of
    the bucket: it compiles for the described chip with the pallas
    forward kernel in it, its temporaries are an eighth of the eight-row
    tile's, and at 8,192 the tile, the weights and a 4 x 8192 cache fit
    one chip (what a `benchmark` PR needs to lift the cell there)."""
    from ray_tpu.models.generate import prefill_sample_batch
    from ray_tpu.serve.llm import LLMEngine

    bucket, slots, max_seq, temp_gb = DOCQA_TILES[case]
    cfg = _benchmark_config("mistral-7b-v0.3-l16", _bf16(max_seq))
    one, key, params, cache = _serve_structs(topo, cfg, slots, max_seq)
    W = LLMEngine._tile_rows(bucket)
    assert W == 1

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    compiled = prefill_sample_batch.lower(
        cfg, params, cache, arr((W, bucket), jnp.int32), arr((W,), jnp.int32),
        arr((W,), jnp.int32), 0, arr((W,), jnp.float32), key).compile()
    mem = compiled.memory_analysis()
    record_property("temp_gb", mem.temp_size_in_bytes / 1e9)
    record_property("argument_gb", mem.argument_size_in_bytes / 1e9)
    assert "tpu_custom_call" in compiled.as_text()
    assert mem.temp_size_in_bytes < temp_gb * 1e9
    assert mem.alias_size_in_bytes >= 2 * cache.k.size * 2   # donated
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75e9


def _aot_compile_step(topo, cfg, n_chips, **kw):
    sys.path.insert(0, ROOT)
    try:
        from __graft_entry__ import _aot_compile_step as compile_step
    finally:
        sys.path.remove(ROOT)
    from ray_tpu.parallel import ParallelPlan

    plan = ParallelPlan.auto(n_chips) if n_chips > 1 else ParallelPlan()
    return compile_step(cfg, plan, devices=topo.devices[:n_chips], **kw)


def test_train_step_compiles_on_described_chips(topo):
    """`_aot_compile_step` hands the whole sharded train step to the
    TPU's compiler without dispatching anything onto devices that are
    not attached (a `jax.random.key(0)` under the mesh used to). Tiny
    width over four chips here; the 654M step, on one chip and on four,
    is the slow case below."""
    _aot_compile_step(topo, configs.tiny_test(), 4, batch=8, seq=128)


@pytest.mark.slow
@pytest.mark.parametrize("n_chips", [1, 4])
def test_654m_train_step_compiles_and_fits(topo, n_chips):
    """llama-654m, batch 8 x 1024: about a quarter of a minute each, so
    outside tier-1. Per-device bytes must fit a 16 GB chip."""
    mem = _aot_compile_step(topo, configs.llama_654m(), n_chips, batch=8,
                            seq=1024).memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9


def test_ring_attention_compiles_with_kernels_on_four_chips(topo,
                                                            monkeypatch):
    """The ring calls the same kernels with the same blocks, inside
    shard_map: forward and backward over an sp=4 mesh of the described
    chips, 654M geometry, 2048 positions a chip. `on_tpu()` answers for
    the CPU host here, so the test answers for it."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    ring = importlib.import_module("ray_tpu.ops.ring_attention")
    monkeypatch.setattr(ring, "on_tpu", lambda: True)
    mesh = Mesh(np.asarray(topo.devices[:4]), ("sp",))
    seq = NamedSharding(mesh, P(None, "sp"))
    h, kvh, d = GEOMETRY["llama-654m"]
    q = jax.ShapeDtypeStruct((1, 8192, h, d), jnp.bfloat16, sharding=seq)
    k = jax.ShapeDtypeStruct((1, 8192, kvh, d), jnp.bfloat16, sharding=seq)

    def loss(q, k, v):
        out = jax.shard_map(
            lambda q, k, v: ring.ring_attention(q, k, v, "sp"),
            mesh=mesh, in_specs=(P(None, "sp"),) * 3,
            out_specs=P(None, "sp"), check_vma=False)(q, k, v)
        return jnp.sum(out.astype(jnp.float32))

    before = fa.DISPATCH_COUNTS["ring_pallas"]
    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(
        q, k, k).compile().as_text()
    assert fa.DISPATCH_COUNTS["ring_pallas"] == before + 1
    assert "tpu_custom_call" in text and "collective-permute" in text


# -- the routed period stack (benchmarks/cells/trinity-mini-reason-closed) ---

@pytest.fixture(scope="module")
def serve_trinity(topo):
    with open(os.path.join(ROOT, "benchmarks", "cells",
                           "trinity-mini-reason-closed.json")) as f:
        sizes = json.load(f)
    cfg = _benchmark_config("trinity-mini-l5", sizes)
    slots, max_seq = sizes["slots"], sizes["max_seq_len"]
    return (cfg, slots) + _serve_structs(topo, cfg, slots, max_seq)


@pytest.fixture
def as_on_the_chip(monkeypatch):
    """The grouped products take megablox's kernel, as on the chip
    (`models/moe.grouped_dot` asks `on_tpu()`; so does
    `ops/delta_rule.usable`)."""
    monkeypatch.setattr(fa, "on_tpu", lambda: True)


def _fused(text, loop=False):
    """Whether the routed layers' gate and up products are the one
    kernel of `ops/grouped_swiglu`, beside a grouped kernel for the down
    product (`loop`: inside `held_experts`' loop over passes)."""
    at = "moe_experts/while/body/" if loop else "moe_experts/"
    assert at + "jit(gmm)" in text or _combined(text)
    assert "ragged-dot" not in text
    return at + "jit(gmm_swiglu)" in text and '"kernel":"gmm_swiglu"' in text


def _combined(text):
    """Whether `grouped_experts`' down product writes its rows apart and
    one kernel brings them back to their tokens (`ops/moe_combine`): both
    or neither, and then megablox's `gmm` is in no routed layer."""
    pair = [f"moe_experts/jit({name})" in text and f'"kernel":"{name}"' in text
            for name in ("gmm_rows_apart", "moe_combine")]
    assert pair[0] == pair[1], pair
    assert not (pair[0] and "moe_experts/jit(gmm)" in text)
    return pair[0]


def test_trinity_decode_block_fits_and_updates_both_caches_in_place(
        serve_trinity, as_on_the_chip):
    """`decode_multi` (k = 8) at the cell's 32 slots x 4096, bf16 weights
    under float32 activations, all 128 experts of four layers and the
    200,192-row head on one chip: it fits; both kinds of cache (global
    rows of S_max, window rings of 2,048; two bf16 terms a row, so twice
    the layers) are carried and aliased, written by scatters only and
    never copied whole; and no layer's experts are sliced out of the
    stack or cast to float32 (a copy of one layer's three matrices is
    0.8 GB: the temporaries stay below one)."""
    from ray_tpu.models.generate import decode_multi

    cfg, slots, one, key, params, cache = serve_trinity
    assert cfg.dtype == jnp.float32 and cfg.param_dtype == jnp.bfloat16
    assert cache.k.shape == (2, 32, 4096, 4, 128)
    assert cache.kw.shape == (8, 32, 2048, 4, 128)
    assert cache.k.dtype == cache.kw.dtype == jnp.bfloat16
    toks = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one)
    temps = jax.ShapeDtypeStruct((slots,), jnp.float32, sharding=one)
    compiled = decode_multi.lower(cfg, params, cache, toks, temps, 8, 0,
                                  key).compile()
    text = compiled.as_text()
    mem, writes = compiled.memory_analysis(), _device_writes(text)
    assert "moe_experts/jit(gmm)" in text and "ragged-dot" not in text
    # Float32 rows sum their two bf16 terms before the activation: the
    # three products stay megablox's, and XLA brings their rows back.
    assert not _fused(text) and not _combined(text)

    def nbytes(x):
        return x.size * x.dtype.itemsize

    held = sum(nbytes(x) for x in (cache.k, cache.v, cache.kw, cache.vw))
    for whole_shape in (cache.k.shape, cache.kw.shape):
        shapes = {whole_shape}
        whole = [w for shape in shapes for w in writes.get(shape, ())
                 if w[1] not in ("parameter", "get-tuple-element", "bitcast")]
        assert whole, whole_shape
        assert all(op == "fusion" and "scatter" in body
                   and not body & {"copy", "dynamic-update-slice"}
                   for _, op, body in whole), whole
    experts = params["periods"]["w_gate"]
    one_layer = nbytes(experts) // experts.shape[1]
    assert not [w for dims in writes
                if len(dims) >= 3 and dims[-3:] == experts.shape[-3:]
                for w in writes[dims]
                if w[1] not in ("parameter", "get-tuple-element", "bitcast")]
    # 0.55 GB while XLA staged cache slabs of 67 and 134 MB and scores of
    # 33 MB; `as_on_the_chip` takes ops/decode_attention's kernel, which
    # stages none.
    assert mem.temp_size_in_bytes < 1.5 * one_layer
    assert mem.alias_size_in_bytes >= held
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75e9


def _trinity_lowerings(serve_trinity):
    from ray_tpu.models.generate import (first_token_sample, prefill,
                                         prefill_sample_batch)
    from ray_tpu.serve.llm import LLMEngine

    cfg, slots, one, key, params, cache = serve_trinity
    W, T = LLMEngine._ADMIT_TILE, LLMEngine._tile_rows(1024)

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    return {
        "tile": lambda: prefill_sample_batch.lower(
            cfg, params, cache, arr((T, 1024), jnp.int32),
            arr((T,), jnp.int32), arr((T,), jnp.int32), 0,
            arr((T,), jnp.float32), key),
        "queue_side": lambda: first_token_sample.lower(
            cfg, params, arr((W, 1024), jnp.int32), arr((W,), jnp.int32),
            arr((W,), jnp.float32), 0, key),
        "long_prefill": lambda: prefill.lower(
            cfg, params, cache, arr((1, 4096), jnp.int32),
            arr((), jnp.int32), arr((), jnp.int32)),
    }


@pytest.mark.parametrize("program", [
    "tile",
    pytest.param("queue_side", marks=pytest.mark.slow),
    pytest.param("long_prefill", marks=pytest.mark.slow)])
def test_trinity_prefill_programs_fit(serve_trinity, as_on_the_chip,
                                      program):
    """The admission tile of the 1024 bucket as the engine builds it,
    one row (8,192 token-expert pairs a layer through the grouped
    products); with `-m slow` also the queue side's cache-free first
    token at 8 x 1024 and the reference check's one-row prefill at 4096
    (some 20 s of compile each). `on_tpu()` is false here, so the
    long prefill takes the reference path in this compile; the kernel
    with a window is compiled below."""
    mem = _trinity_lowerings(serve_trinity)[program]().compile() \
        .memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75e9


@pytest.mark.parametrize("S,window", [(4096, 2048), (8192, 1024)])
def test_windowed_flash_forward_compiles(topo, S, window):
    """The forward kernel with a window of 2,048 at trinity's long
    prefill and of 1,024 at mellum's admission tile (32 Q / 4 KV heads
    of 128), as `periodic._flash` calls it, and with offsets the trace
    cannot see: the kv axis of the grid is then the blocks a window
    reaches, found from the prefetched offsets."""
    q, k = _qkv(topo, S, S, 32, 4, 128)
    for offset in (0, jnp.int32(0)):
        text = jax.jit(lambda q, k, v: fa.flash_attention(
            q, k, v, causal=True, window=window, q_offset=offset,
            interpret=False)).lower(q, k, k).compile().as_text()
        assert "tpu_custom_call" in text


# -- the period stack's other layer (benchmarks/cells/mellum2-repoctx-lone) --

@pytest.fixture(scope="module")
def serve_mellum(topo):
    with open(os.path.join(ROOT, "benchmarks", "cells",
                           "mellum2-repoctx-lone.json")) as f:
        sizes = json.load(f)
    cfg = _benchmark_config("mellum2-12b-l8", sizes)
    slots, max_seq = sizes["slots"], sizes["max_seq_len"]
    return (cfg, slots) + _serve_structs(topo, cfg, slots, max_seq)


def _mellum_under(serve_mellum, activations):
    """The cell's configuration and cache (bf16 activations) or the
    float32 form PERF.md compares it with (two bf16 terms a cache row,
    so twice the layers' entries)."""
    from ray_tpu.models.generate import init_kv_cache

    cfg, slots, one, key, params, cache = serve_mellum
    assert cfg.dtype == cfg.param_dtype == jnp.bfloat16
    assert cache.k.shape == (2, 4, 8192, 4, 128)
    assert cache.kw.shape == (6, 4, 1024, 4, 128)
    if activations == "float32":
        cfg = dataclasses.replace(cfg, dtype=jnp.float32)
        cache = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one),
            jax.eval_shape(lambda: init_kv_cache(cfg, slots, 8192)))
        assert cache.kw.shape == (12, 4, 1024, 4, 128)
    return cfg, slots, one, key, params, cache


@pytest.mark.parametrize("activations", ["float32", "bfloat16"])
def test_mellum_decode_block_reaches_the_grouped_kernel_under_128_rows(
        serve_mellum, as_on_the_chip, activations):
    """`decode_multi` (k = 8) at the cell's 4 slots x 8192, and under
    float32 activations: 4 slots x 8 pairs = 32 (two bf16 terms: 64)
    rows a grouped product, padded to the kernel's row tile:
    megablox's kernel at 2304 x 896 and 896 x 2304 and no `ragged-dot`
    (bf16: gate, up and the activation one kernel, `gmm_swiglu`, and
    megablox's for the down product);
    both kinds of cache aliased; all 64 experts of eight layers and the
    98,304-row head on one chip."""
    from ray_tpu.models.generate import decode_multi

    cfg, slots, one, key, params, cache = _mellum_under(serve_mellum,
                                                        activations)
    toks = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one)
    temps = jax.ShapeDtypeStruct((slots,), jnp.float32, sharding=one)
    live = jax.ShapeDtypeStruct((slots,), jnp.bool_, sharding=one)
    compiled = decode_multi.lower(cfg, params, cache, toks, temps, 8, 0,
                                  key, live).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert "moe_experts/jit(gmm)" in text and "ragged-dot" not in text
    assert _fused(text) == (activations == "bfloat16")
    # 32 rows of 2,304 are far under what the pair of `ops/moe_combine`
    # is faster from: XLA brings a step's rows back.
    assert not _combined(text)
    assert "decode_attn" in text
    held = sum(x.size * x.dtype.itemsize
               for x in (cache.k, cache.v, cache.kw, cache.vw))
    assert mem.alias_size_in_bytes >= held
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75e9


# The bf16 tile's temporaries on the parent commit (628ebf5), bytes: this
# test's `temp_gb` there, read in PR 47.
PARENT_TILE_TEMP = 1.443774464e9


@pytest.mark.parametrize("activations", ["float32", "bfloat16"])
def test_mellum_admission_tile_fits_beside_weights_and_cache(
        serve_mellum, as_on_the_chip, record_property, activations):
    """The one-row tile of the 8,192 bucket: 65,536 token-expert pairs a
    grouped product through megablox's kernel (131,072 rows as two bf16
    terms under float32 activations); attention through the flash
    kernel, a window layer's with the window (float32: products over
    slices of 1,280 keys, and all 8,192 on a global layer); it compiles
    for the described chip beside 7.6 GB of weights and the cell's cache
    (bf16 7.77 GB of arguments + 1.45 of temporaries; float32 7.96 +
    3.29: read here in PR 32)."""
    from ray_tpu.models.generate import prefill_sample_batch
    from ray_tpu.serve.llm import LLMEngine

    cfg, slots, one, key, params, cache = _mellum_under(serve_mellum,
                                                        activations)
    W = LLMEngine._tile_rows(8192)
    assert W == 1

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    before = fa.DISPATCH_COUNTS["pallas"]
    compiled = prefill_sample_batch.lower(
        cfg, params, cache, arr((W, 8192), jnp.int32), arr((W,), jnp.int32),
        arr((W,), jnp.int32), 0, arr((W,), jnp.float32), key).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    record_property("temp_gb", mem.temp_size_in_bytes / 1e9)
    record_property("argument_gb", mem.argument_size_in_bytes / 1e9)
    assert _fused(text) == _combined(text) == (activations == "bfloat16")
    if activations == "bfloat16":
        # The pairs' float32 rows are written once, a row apart, by the
        # down product and read once by the combine: no fusion makes the
        # (65536, 2304) array again, and the gathered copy is gone from
        # the temporaries: 1.444 GB on the parent commit
        # (PARENT_TILE_TEMP), 0.961 here.
        made = re.findall(r"= f32\[65536,(?:1,)?2304\]\S* (\S+)\(", text)
        assert made and set(made) == {"custom-call"}, made
        assert mem.temp_size_in_bytes < PARENT_TILE_TEMP - 0.45e9
    for scope in ("attn_window", "attn_global", "moe_router"):
        assert scope in text, scope
    # The flash kernel a layer of the period (three window layers and
    # a global one; the periods are a scan), traced once each.
    assert fa.DISPATCH_COUNTS["pallas"] - before == \
        (4 if activations == "bfloat16" else 0)
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75e9


@pytest.mark.parametrize("rows,k,n,experts", [
    (65536, 2304, 896, 64), (32, 2304, 896, 64), (1024, 7680, 2048, 16)],
    ids=["mellum_tile", "mellum_decode", "openpangu_pass"])
def test_gate_up_and_activation_compile_as_one_kernel(topo, as_on_the_chip,
                                                      rows, k, n, experts):
    """`moe.grouped_swiglu` alone at the cells' shapes. A tile of
    mellum's takes row tiles of 256 and all of 2304 x 896 a weight tile:
    two of them double-buffered are 16.5 MB, so the compile stands on
    the `vmem_limit_bytes` the call states. The result is bf16 and no
    float32 (rows, n) array is left in the program."""
    from ray_tpu.models import moe
    from ray_tpu.ops import grouped_swiglu

    one = SingleDeviceSharding(topo.devices[0])
    tm, tk, tn = moe._gmm_tiling(rows + -rows % 128, k, n)
    assert (tm, tk) == (256 if rows == 65536 else 128, k)
    assert tn == (896 if n == 896 else 128)
    held = 2 * 2 * tk * tn * 2 + 2 * tm * tk * 2 + 2 * tm * tn * 4 \
        + 2 * tm * tn * 2
    assert held < grouped_swiglu.VMEM_LIMIT_BYTES
    assert (held > 16 * 2 ** 20) == (n == 896)
    a = jax.ShapeDtypeStruct((rows, k), jnp.bfloat16, sharding=one)
    w = jax.ShapeDtypeStruct((experts, k, n), jnp.bfloat16, sharding=one)
    groups = jax.ShapeDtypeStruct((experts,), jnp.int32, sharding=one)
    compiled = jax.jit(moe.grouped_swiglu).lower(a, w, w, groups).compile()
    text = compiled.as_text()
    assert '"kernel":"gmm_swiglu"' in text and "ragged-dot" not in text
    assert "jit(gmm)" not in text
    assert f"f32[{rows + -rows % 128},{n}]" not in text
    assert jax.eval_shape(moe.grouped_swiglu, a, w, w, groups).dtype \
        == jnp.bfloat16


@pytest.mark.parametrize("masked", [False, True], ids=["every_row", "rows"])
def test_down_product_and_return_compile_as_the_pair(topo, as_on_the_chip,
                                                     masked):
    """`moe.down_and_combine` alone at mellum's tile: the down product
    writes 65,536 float32 rows of 2,304 a row apart, (65536, 1, 2304) in
    tiles of one row, the combine copies each from there by its index
    (the 65,536 indices prefetched whole: 256 KB of scalar memory) and
    writes (8192, 2304) once; no (65536, 2304) float32 array is made, and
    the program's temporaries are the rows once (604 MB) and the result."""
    from ray_tpu.models import moe
    from ray_tpu.ops import moe_combine

    one = SingleDeviceSharding(topo.devices[0])
    T, K, F, D, E = 8192, 8, 896, 2304, 64
    assert 4 * T * K * D >= moe_combine.MIN_ROW_BYTES
    assert T * K <= moe_combine.MAX_PAIRS

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    args = [arr((T * K, F), jnp.bfloat16), arr((E, F, D), jnp.bfloat16),
            arr((E,), jnp.int32), arr((T * K,), jnp.int32),
            arr((T, K), jnp.float32)]
    if masked:
        args.append(arr((T,), jnp.bool_))
    compiled = jax.jit(moe.down_and_combine).lower(*args).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    for name in ("gmm_rows_apart", "moe_combine"):
        assert f'"kernel":"{name}"' in text, name
    assert "jit(gmm)" not in text and "ragged-dot" not in text
    assert "f32[65536,1,2304]" in text and "f32[65536,2304]" not in text
    assert 4 * T * K * D <= mem.temp_size_in_bytes < 4 * T * (K + 2) * D


# -- openpangu-longgen-closed: a latent cache and a share of the experts ----

@pytest.fixture(scope="module")
def serve_pangu(topo):
    with open(os.path.join(ROOT, "benchmarks", "cells",
                           "openpangu-longgen-closed.json")) as f:
        sizes = json.load(f)
    cfg = _benchmark_config("openpangu-ultra-moe-l5-ep16", sizes)
    slots, max_seq = sizes["slots"], sizes["max_seq_len"]
    return (cfg, slots) + _serve_structs(topo, cfg, slots, max_seq)


def _pangu_fits(serve_pangu, mem, record_property):
    cfg, slots, one, key, params, cache = serve_pangu
    weights = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    held = cache.c.size * cache.c.dtype.itemsize
    # 9.84 GB of weights beside 32 x 10,240 rows of 512 + 64 values in
    # whole lanes (640), five layers: 2.10 GB.
    assert 9.83e9 < weights < 9.85e9 and cache.c.shape == (5, 32, 10240, 640)
    assert cache.k is None and cache.v is None and 2.09e9 < held < 2.10e9
    record_property("argument_gb", mem.argument_size_in_bytes / 1e9)
    record_property("temp_gb", mem.temp_size_in_bytes / 1e9)
    print(f"arguments {mem.argument_size_in_bytes / 1e9:.2f} GB, "
          f"temporaries {mem.temp_size_in_bytes / 1e9:.2f} GB")
    # The whole cache aliased: no program copies it in or out.
    assert mem.alias_size_in_bytes >= held
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75e9


def test_pangu_decode_block_reads_latent_rows_through_the_kernel(
        serve_pangu, as_on_the_chip, record_property):
    """`decode_multi` (k = 8) at the cell's 32 slots x 10,240: the decode
    kernel over one array for keys and values (rows of 640 lanes, 128
    query heads), megablox's kernel at 7680 x 2048 and 2048 x 7680 inside
    the loop over the kept pairs' passes, no `ragged-dot`, the cache
    updated in place (11.94 GB of arguments + 0.42 of temporaries: read
    here in PR 34)."""
    from ray_tpu.models.generate import decode_multi

    cfg, slots, one, key, params, cache = serve_pangu
    toks = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one)
    temps = jax.ShapeDtypeStruct((slots,), jnp.float32, sharding=one)
    live = jax.ShapeDtypeStruct((slots,), jnp.bool_, sharding=one)
    compiled = decode_multi.lower(cfg, params, cache, toks, temps, 8, 0,
                                  key, live).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert "moe_experts/while/body/jit(gmm)" in text
    assert "ragged-dot" not in text and "decode_attn" in text
    assert _fused(text, loop=True)
    for scope in ("attn_latent", "mla_proj", "moe_router", "moe_shared"):
        assert scope in text, scope
    _pangu_fits(serve_pangu, mem, record_property)
    assert mem.temp_size_in_bytes < 1e9


def test_pangu_admission_tile_fits_beside_weights_and_latent_cache(
        serve_pangu, as_on_the_chip, record_property):
    """The one-row tile of the 8,192 bucket: per-head attention through
    the flash kernel with keys 192 wide over values 128 wide, a layer one
    launch; 65,536 token-expert pairs of which a pass takes 8,192 through
    megablox's kernel; it compiles for the described chip beside 9.84 GB
    of weights and the 2.10 GB cache (11.94 GB of arguments + 2.54 of
    temporaries: read here in PR 34), the cache rows-major throughout."""
    from ray_tpu.models.generate import prefill_sample_batch
    from ray_tpu.serve.llm import LLMEngine

    cfg, slots, one, key, params, cache = serve_pangu
    W = LLMEngine._tile_rows(8192)
    assert W == 1

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    before = fa.DISPATCH_COUNTS["pallas"]
    compiled = prefill_sample_batch.lower(
        cfg, params, cache, arr((W, 8192), jnp.int32), arr((W,), jnp.int32),
        arr((W,), jnp.int32), 0, arr((W,), jnp.float32), key).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert "moe_experts/while/body/jit(gmm)" in text
    assert _fused(text, loop=True)
    assert "ragged-dot" not in text and "flash_fwd" in text
    # One trace a group of the plan (the dense layer, the routed layers).
    assert fa.DISPATCH_COUNTS["pallas"] - before == 2
    _pangu_fits(serve_pangu, mem, record_property)


# -- sdar-blockgen-closed: a block of four positions a slot a pass -----------

@pytest.fixture(scope="module")
def serve_sdar(topo):
    with open(os.path.join(ROOT, "benchmarks", "cells",
                           "sdar-blockgen-closed.json")) as f:
        sizes = json.load(f)
    cfg = _benchmark_config("sdar-30b-a3b-l7", sizes)
    slots, max_seq = sizes["slots"], sizes["max_seq_len"]
    return (cfg, slots) + _serve_structs(topo, cfg, slots, max_seq)


def _sdar_blocks(cfg, slots, one):
    from ray_tpu.models.generate import init_block_state

    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one),
        jax.eval_shape(lambda: init_block_state(cfg, slots)))


def _sdar_fits(serve_sdar, mem, record_property):
    cfg, slots, one, key, params, cache = serve_sdar
    weights = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    held = 2 * cache.k.size * cache.k.dtype.itemsize
    # ISSUE 39's arithmetic: 4.984 B parameters, 9.97 GB in bf16, beside
    # 64 slots x 2,048 rows of 2 x 4 x 128 values over seven layers.
    assert 9.96e9 < weights < 9.98e9
    assert cache.k.shape == (7, 64, 2048, 4, 128) and cache.kw is None
    assert 1.87e9 < held < 1.89e9
    record_property("argument_gb", mem.argument_size_in_bytes / 1e9)
    record_property("temp_gb", mem.temp_size_in_bytes / 1e9)
    print(f"arguments {mem.argument_size_in_bytes / 1e9:.2f} GB, "
          f"temporaries {mem.temp_size_in_bytes / 1e9:.2f} GB")
    # The whole cache aliased: no program copies it in or out.
    assert mem.alias_size_in_bytes >= held
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75e9


def test_sdar_block_program_runs_four_positions_a_slot_through_the_kernels(
        serve_sdar, as_on_the_chip, record_property):
    """`decode_block_multi` (k = 8 passes) at the cell's 64 slots x 2,048:
    the decode kernel with a block's four queries beside the eight heads
    of their group (32 query rows a KV head), megablox's kernel over 256
    rows x top 8, the head and the sampler under their scopes, cache and
    block state updated in place."""
    from ray_tpu.models.generate import decode_block_multi

    cfg, slots, one, key, params, cache = serve_sdar
    assert (cfg.block_length, cfg.denoise_steps, cfg.remask) == (
        4, 2, "low_confidence_static")
    temps = jax.ShapeDtypeStruct((slots,), jnp.float32, sharding=one)
    live = jax.ShapeDtypeStruct((slots,), jnp.bool_, sharding=one)
    compiled = decode_block_multi.lower(
        cfg, params, cache, _sdar_blocks(cfg, slots, one), temps, 8, 0, key,
        live).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert "moe_experts" in text and "jit(gmm)" in text
    assert "jit(gmm_swiglu)" in text and not _combined(text)
    assert "ragged-dot" not in text and "decode_attn" in text
    for scope in ("attn_global", "moe_router", "block_head", "block_sample"):
        assert scope in text, scope
    _sdar_fits(serve_sdar, mem, record_property)


@pytest.mark.parametrize("bucket", [64, 128, 256, 512])
def test_sdar_admission_tiles_fit_beside_weights_and_cache(
        serve_sdar, as_on_the_chip, record_property, bucket):
    """The tiles of the buckets the cell's prompts (64-508 tokens) reach,
    as the engine builds them (`_tile_rows`: 512 positions a tile), under
    the block-causal mask: no head, no sample."""
    from ray_tpu.models.generate import prefill_block_batch
    from ray_tpu.serve.llm import LLMEngine

    cfg, slots, one, key, params, cache = serve_sdar
    W = LLMEngine._tile_rows(bucket)
    assert W * bucket == 512

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    compiled = prefill_block_batch.lower(
        cfg, params, cache, _sdar_blocks(cfg, slots, one),
        arr((W, bucket), jnp.int32), arr((W,), jnp.int32),
        arr((W,), jnp.int32), arr((W, 4), jnp.int32), arr((W, 4), jnp.bool_),
        arr((W,), jnp.int32), arr((W,), jnp.int32),
        arr((W,), jnp.float32)).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert "jit(gmm)" in text and "ragged-dot" not in text
    assert not _combined(text)          # 4,096 rows of 2,048: 34 MB
    assert "jit(gmm_swiglu)" in text
    _sdar_fits(serve_sdar, mem, record_property)


# -- glm5-longctx-closed: rows an indexer chooses, a tile walked in chunks ---

@pytest.fixture(scope="module")
def serve_glm(topo):
    with open(os.path.join(ROOT, "benchmarks", "cells",
                           "glm5-longctx-closed.json")) as f:
        sizes = json.load(f)
    cfg = _benchmark_config("glm-5-l5-ep16", sizes)
    slots, max_seq = sizes["slots"], sizes["max_seq_len"]
    return (cfg, slots) + _serve_structs(topo, cfg, slots, max_seq)


def _glm_fits(serve_glm, mem, record_property):
    cfg, slots, one, key, params, cache = serve_glm
    weights = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    held = sum(x.size * x.dtype.itemsize for x in (cache.c, cache.ki))
    # 7.82 GB of weights beside 16 x 32,768 rows of 512 + 64 values in
    # whole lanes (640), bf16, and of the indexer's 128 in the dtype the
    # cell states for the choice (`model.index_dtype`: float32), five
    # layers: 3.36 + 1.34.
    assert 7.81e9 < weights < 7.83e9
    assert cache.c.shape == (5, 16, 32768, 640)
    assert cache.ki.shape == (5, 16, 32768, 128)
    assert cfg.dtype == jnp.bfloat16 and cfg.index_dtype == "float32"
    assert cache.ki.dtype == jnp.float32 and cache.c.dtype == jnp.bfloat16
    assert cache.k is None and cache.v is None and 4.69e9 < held < 4.70e9
    record_property("argument_gb", mem.argument_size_in_bytes / 1e9)
    record_property("temp_gb", mem.temp_size_in_bytes / 1e9)
    print(f"arguments {mem.argument_size_in_bytes / 1e9:.2f} GB, "
          f"temporaries {mem.temp_size_in_bytes / 1e9:.2f} GB")
    # Both caches aliased: no program copies either in or out.
    assert mem.alias_size_in_bytes >= held
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75e9


def test_glm_decode_block_scores_chooses_and_reads_the_chosen_rows(
        serve_glm, as_on_the_chip, record_property):
    """`decode_multi` (k = 8) at the cell's 16 slots x 32,768: the
    indexer's scorer over its slots' keys (one sum in XLA), an exact
    top-k (no `approx`), the decode kernel over the 2,048 gathered rows
    of 640 lanes under 64 query heads, megablox's kernel for the held
    experts, both caches updated in place."""
    from ray_tpu.models.generate import decode_multi

    cfg, slots, one, key, params, cache = serve_glm
    toks = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one)
    temps = jax.ShapeDtypeStruct((slots,), jnp.float32, sharding=one)
    live = jax.ShapeDtypeStruct((slots,), jnp.bool_, sharding=one)
    compiled = decode_multi.lower(cfg, params, cache, toks, temps, 8, 0,
                                  key, live).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert "moe_experts/while/body/jit(gmm)" in text
    assert _fused(text, loop=True)
    assert "ragged-dot" not in text and "approx" not in text.lower()
    assert "decode_attn" in text
    for scope in ("attn_index", "attn_sparse", "mla_proj", "moe_router",
                  "moe_shared"):
        assert scope in text, scope
    assert "attn_latent" not in text
    _glm_fits(serve_glm, mem, record_property)
    assert mem.temp_size_in_bytes < 1e9


def test_glm_admission_tile_walks_the_longest_bucket_in_chunks(
        serve_glm, as_on_the_chip, record_property):
    """The one-row tile of the 32,768 bucket: sixteen chunks of 2,048 in
    one program (a loop the device counts), each chunk's scorer, exact
    threshold and masked per-head attention as kernels; it compiles for
    the described chip beside 7.82 GB of weights and 4.70 GB of caches,
    both rows-major throughout. A chunk's temporaries, 2.44 GB: a block
    of 1,024 queries' float32 scores against the bucket (134 MB), the
    two blocks' biases as the threshold kernel writes them and as the
    chunk stacks them (bf16, 67 MB a block), the layers' activations and
    the attention's parts; the scores' ordered keys, the chosen set as a
    bool and the select over (1,024 x 32,768) exist on the tie path of
    `topk_bias`'s `cond` alone (2.67 GB while XLA made them for every
    block)."""
    from ray_tpu.models import latent
    from ray_tpu.models.generate import prefill_sample_batch
    from ray_tpu.serve.llm import LLMEngine

    cfg, slots, one, key, params, cache = serve_glm
    W = LLMEngine._tile_rows(32768)
    assert W == 1 and latent.prefill_chunks(cfg, 32768, 20000) == (
        -(-20000 // latent.PREFILL_CHUNK), 32768 // latent.PREFILL_CHUNK)

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    compiled = prefill_sample_batch.lower(
        cfg, params, cache, arr((W, 32768), jnp.int32), arr((W,), jnp.int32),
        arr((W,), jnp.int32), 0, arr((W,), jnp.float32), key).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert "moe_experts/while/body/jit(gmm)" in text
    assert _fused(text, loop=True)
    assert "ragged-dot" not in text and "approx" not in text.lower()
    for kernel in ("index_scores_tile", "topk_threshold",
                   "sparse_prefill_attn"):
        assert kernel in text, kernel
    passes = [line for line in text.splitlines()
              if "[1,1024,32768]" in line
              and re.search(r" (xor|select|compare)\(", line)]
    assert passes and all("cond/branch_1_fun" in line for line in passes)
    _glm_fits(serve_glm, mem, record_property)
    assert mem.temp_size_in_bytes < 2.5e9


# -- solar-open2-rollout-closed: a recurrent state beside keys and values ----

@pytest.fixture(scope="module")
def serve_solar(topo):
    with open(os.path.join(ROOT, "benchmarks", "cells",
                           "solar-open2-rollout-closed.json")) as f:
        sizes = json.load(f)
    cfg = _benchmark_config("solar-open2-l8-ep16", sizes)
    slots, max_seq = sizes["slots"], sizes["max_seq_len"]
    return (cfg, slots) + _serve_structs(topo, cfg, slots, max_seq)


def _solar_fits(serve_solar, mem, record_property, cached=True):
    cfg, slots, one, key, params, cache = serve_solar
    weights = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    # 7.80 GB of weights beside 96 slots' states (6 linear layers x 64
    # heads x 128 x 128 float32: 2.42 GB), their convolutions' tails (6
    # x 3 x 24,576 in the activations' float32: 0.17) and two GQA
    # layers' 4,096 rows of 8 KV heads x 128, keys and values, one bf16
    # value a row (`model.cache_dtype`: 3.22; two terms would be 6.44).
    assert 7.79e9 < weights < 7.81e9
    assert cfg.dtype == jnp.float32 and cfg.cache_dtype == "bfloat16"
    assert (cache.s.shape, cache.s.dtype) == ((6, 96, 64, 128, 128),
                                              jnp.float32)
    assert (cache.tails.shape, cache.tails.dtype) == ((6, 96, 3, 24576),
                                                     jnp.float32)
    assert cache.k.shape == cache.v.shape == (2, 96, 4096, 8, 128)
    assert cache.k.dtype == jnp.bfloat16 and cache.kw is None
    held = sum(x.size * x.dtype.itemsize
               for x in (cache.s, cache.tails, cache.k, cache.v))
    assert 5.80e9 < held < 5.81e9
    record_property("argument_gb", mem.argument_size_in_bytes / 1e9)
    record_property("temp_gb", mem.temp_size_in_bytes / 1e9)
    print(f"arguments {mem.argument_size_in_bytes / 1e9:.2f} GB, "
          f"temporaries {mem.temp_size_in_bytes / 1e9:.2f} GB")
    if cached:
        # States, tails, keys and values aliased: no program copies one
        # in or out.
        assert mem.alias_size_in_bytes >= held
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        + (0 if cached else held) < 15.75e9


def test_solar_decode_block_updates_states_and_rows_in_place(
        serve_solar, as_on_the_chip, record_property):
    """`decode_multi` (k = 64, the engine's largest block) at the cell's
    96 slots x 4,096: the linear layers' update under `attn_linear` as
    the kernels of `ops/delta_rule` (the states and the convolutions'
    tails aliased in and out), the
    two GQA layers' rows through the decode kernel, megablox's kernel
    for the held experts; every kind of state updated in place."""
    from ray_tpu.models.generate import decode_multi

    cfg, slots, one, key, params, cache = serve_solar
    toks = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one)
    temps = jax.ShapeDtypeStruct((slots,), jnp.float32, sharding=one)
    live = jax.ShapeDtypeStruct((slots,), jnp.bool_, sharding=one)
    compiled = decode_multi.lower(cfg, params, cache, toks, temps, 64, 0,
                                  key, live).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    # Float32 rows over bf16 experts: two terms a row through megablox's
    # kernel, the activation between the products XLA's.
    assert "moe_experts/while/body/jit(gmm)" in text
    assert not _fused(text, loop=True)
    assert "ragged-dot" not in text and "decode_attn" in text
    assert '"kernel":"kda_update"' in text and '"kernel":"kda_tails"' in text
    for scope in ("attn_linear", "attn_global", "moe_router", "moe_shared"):
        assert scope in text, scope
    assert "attn_window" not in text
    _solar_fits(serve_solar, mem, record_property)
    assert mem.temp_size_in_bytes < 0.8e9


def _scan_is_the_kernel(text):
    """Under the scope `kda_scan` a tile's recurrence is the kernel of
    that name (`ops/delta_rule._scan_pallas`, one jitted callee for every
    site of the program), and the `lax.scan` of the XLA walk, a `while`
    under the scope, is gone from the program."""
    assert '"kernel":"kda_scan"' in text
    assert re.search(
        r'op_name="[^"]*attn_linear/kda_scan/jit\(_scan_pallas\)/pallas_call',
        text)
    assert "kda_scan/while" not in text


@pytest.mark.parametrize("program,rows,bucket", [
    ("prefill_sample_batch", 1, 2048), ("first_token_sample", 4, 2048)])
def test_solar_tiles_fit_beside_weights_states_and_rows(
        serve_solar, as_on_the_chip, record_property, program, rows, bucket):
    """The longest bucket's admission tile (one row of 2,048: the chunked
    scan under `kda_scan`, the flash kernel for the GQA layers) and the
    queue-side tile of the longest bucket, which runs beside the cache
    and not through it, its rows walked singly."""
    from ray_tpu.models import generate
    from ray_tpu.serve.llm import LLMEngine

    cfg, slots, one, key, params, cache = serve_solar
    cached = program == "prefill_sample_batch"
    assert rows == (LLMEngine._tile_rows(bucket) if cached
                    else LLMEngine._queue_tile_rows(bucket))

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    tile, n, temps = (arr((rows, bucket), jnp.int32), arr((rows,), jnp.int32),
                      arr((rows,), jnp.float32))
    if cached:
        lowered = generate.prefill_sample_batch.lower(
            cfg, params, cache, tile, n, n, 0, temps, key)
    else:
        lowered = generate.first_token_sample.lower(
            cfg, params, tile, n, temps, 0, key)
    compiled = lowered.compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert "attn_linear" in text
    _scan_is_the_kernel(text)
    assert "moe_experts/while/body/jit(gmm)" in text and "ragged-dot" not in text
    _solar_fits(serve_solar, mem, record_property, cached)


@pytest.fixture(scope="module")
def serve_jamba(topo):
    with open(os.path.join(ROOT, "benchmarks", "cells",
                           "jamba2-reason-wide-closed.json")) as f:
        sizes = json.load(f)
    cfg = _benchmark_config("jamba2-3b", sizes)
    slots, max_seq = sizes["slots"], sizes["max_seq_len"]
    return (cfg, slots) + _serve_structs(topo, cfg, slots, max_seq)


def _jamba_fits(serve_jamba, mem, record_property, cached=True):
    cfg, slots, one, key, params, cache = serve_jamba
    weights = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    # 6.06 GB of weights, whole, beside 256 slots' states (26 Mamba
    # layers x 16 x 5,120 float32: 2.18 GB), their convolutions' tails
    # (26 x 3 x 5,120 bf16: 0.20) and two MQA layers' 5,120 rows of one
    # KV head x 128, keys and values (1.34).
    assert 6.05e9 < weights < 6.07e9 and slots == 256
    assert cfg.dtype == cfg.param_dtype == jnp.bfloat16
    assert (cache.s.shape, cache.s.dtype) == ((26, 256, 16, 5120),
                                              jnp.float32)
    assert (cache.tails.shape, cache.tails.dtype) == ((26, 256, 3, 5120),
                                                     jnp.bfloat16)
    assert cache.k.shape == cache.v.shape == (2, 256, 5120, 1, 128)
    assert cache.k.dtype == jnp.bfloat16 and cache.kw is None
    held = sum(x.size * x.dtype.itemsize
               for x in (cache.s, cache.tails, cache.k, cache.v))
    assert 3.72e9 < held < 3.74e9
    record_property("argument_gb", mem.argument_size_in_bytes / 1e9)
    record_property("temp_gb", mem.temp_size_in_bytes / 1e9)
    print(f"arguments {mem.argument_size_in_bytes / 1e9:.2f} GB, "
          f"temporaries {mem.temp_size_in_bytes / 1e9:.2f} GB")
    if cached:
        # States, tails, keys and values aliased: no program copies one
        # in or out.
        assert mem.alias_size_in_bytes >= held
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        + (0 if cached else held) < 15.75e9


def test_jamba_decode_block_updates_states_and_rows_in_place(
        serve_jamba, as_on_the_chip, record_property):
    """`decode_multi` (k = 64, the engine's largest block) at the cell's
    256 slots x 5,120: the Mamba layers' update under `attn_ssm` as the
    one kernel of `ops/selective_scan`, which moves the convolution's
    tail in the grid step that holds the slot's state (both aliased in
    and out; the tails have no kernel of their own), the two MQA layers'
    rows through the decode kernel (20 query heads under one KV head); a
    period a scan step, so thirteen bodies of the state's kernel a
    program; no stacked leaf is copied out of its stack (a period's
    SwiGLUs stacked over its layers and cut out by the step were 2.0 GB
    of temporaries more: they lie a layer under its place)."""
    from ray_tpu.models.generate import decode_multi

    cfg, slots, one, key, params, cache = serve_jamba
    toks = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one)
    temps = jax.ShapeDtypeStruct((slots,), jnp.float32, sharding=one)
    live = jax.ShapeDtypeStruct((slots,), jnp.bool_, sharding=one)
    compiled = decode_multi.lower(cfg, params, cache, toks, temps, 64, 0,
                                  key, live).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert '"kernel":"decode_attn"' in text
    for scope in ("attn_ssm", "attn_global", "ssm_in", "ssm_conv", "ssm_dt",
                  "ssm_out"):
        assert scope in text, scope
    assert "attn_linear" not in text and "moe_experts" not in text
    # A body a place (the name stands on the call and on each of the
    # three results read off it), and one work list for all thirteen.
    assert len(re.findall(r'custom-call\([^\n]*\n"kernel":"ssm_update"',
                          text)) == 13
    assert "kda_tails" not in text and text.count(" sort(") == 1
    _jamba_fits(serve_jamba, mem, record_property)
    assert mem.temp_size_in_bytes < 0.5e9


@pytest.mark.parametrize("program", ["prefill_sample_batch",
                                     "first_token_sample"])
def test_jamba_tiles_fit_beside_weights_states_and_rows(
        serve_jamba, as_on_the_chip, record_property, program):
    """The longest bucket's admission tile (1,024: the scan's kernel
    under `ssm_scan`; the MQA layers attend through XLA, a tile of 1,024
    lies under the flash kernel's crossover) and the queue-side tile of
    that bucket, which runs beside the cache and not through it, its
    rows walked singly."""
    from ray_tpu.models import generate
    from ray_tpu.serve.llm import LLMEngine

    cfg, slots, one, key, params, cache = serve_jamba
    cached = program == "prefill_sample_batch"
    bucket = 1024
    rows = LLMEngine._tile_rows(bucket) if cached \
        else LLMEngine._queue_tile_rows(bucket)

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    tile, n, temps = (arr((rows, bucket), jnp.int32), arr((rows,), jnp.int32),
                      arr((rows,), jnp.float32))
    if cached:
        lowered = generate.prefill_sample_batch.lower(
            cfg, params, cache, tile, n, n, 0, temps, key)
    else:
        lowered = generate.first_token_sample.lower(
            cfg, params, tile, n, temps, 0, key)
    compiled = lowered.compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert '"kernel":"ssm_scan"' in text and "attn_ssm/ssm_scan" in text
    assert "ssm_update" not in text
    _jamba_fits(serve_jamba, mem, record_property, cached)


def test_the_scans_kernels_compile_alone_at_the_cells_widths(topo):
    """A row of 2,048 positions x 5,120 channels from a carried state, and
    one position of 256 slots (the issue's width) through layer `l` of 26,
    the states, the convolutions' tails and the row that comes back (in
    the new row's buffer) all aliased."""
    from ray_tpu.ops import selective_scan as ss

    one = SingleDeviceSharding(topo.devices[0])

    def arr(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    B, S, C, N, L, slots = 1, 2048, 5120, 16, 26, 256
    text = jax.jit(ss._scan_pallas).lower(
        arr(B, S, C), arr(B, S, C), arr(B, S, N), arr(B, S, N), arr(N, C),
        arr(B, N, C)).compile().as_text()
    assert '"kernel":"ssm_scan"' in text
    bf16 = jnp.bfloat16
    compiled = jax.jit(ss._update_pallas, donate_argnums=(0, 1, 3)).lower(
        arr(L, slots, N, C), arr(L, slots, 3, C, dtype=bf16),
        arr(dtype=jnp.int32), arr(slots, C, dtype=bf16), arr(slots, C),
        arr(C), arr(slots, C), arr(slots, N), arr(slots, N), arr(slots, C),
        arr(N, C), arr(C), arr(slots, dtype=jnp.bool_)).compile()
    assert '"kernel":"ssm_update"' in compiled.as_text()
    assert compiled.memory_analysis().alias_size_in_bytes \
        >= L * slots * C * (N * 4 + 3 * 2)


def test_one_kv_head_under_twenty_compiles_through_both_attention_kernels(
        topo):
    """MQA, 20 query heads of 128 over one KV head: the decode kernel at
    (2 layers, 256 slots, 5,120 rows), which `usable()` takes
    (`block_rows` 256), and the flash forward at a tile of 2,048, the
    first bucket at its crossover."""
    from ray_tpu.ops import decode_attention as da

    one = SingleDeviceSharding(topo.devices[0])
    assert da.block_rows(5120, 128) == 256
    q = jax.ShapeDtypeStruct((256, 1, 20, 128), jnp.bfloat16, sharding=one)
    rows = jax.ShapeDtypeStruct((2, 256, 5120, 1, 128), jnp.bfloat16,
                                sharding=one)
    text = jax.jit(lambda q, k, v, l, n: da.decode_attention(
        q, k, v, l, n)).lower(
            q, rows, rows, jax.ShapeDtypeStruct((), jnp.int32, sharding=one),
            jax.ShapeDtypeStruct((256,), jnp.int32, sharding=one)
    ).compile().as_text()
    assert '"kernel":"decode_attn"' in text
    qs = jax.ShapeDtypeStruct((1, 2048, 20, 128), jnp.bfloat16, sharding=one)
    ks = jax.ShapeDtypeStruct((1, 2048, 1, 128), jnp.bfloat16, sharding=one)
    text = jax.jit(lambda q, k, v: fa.flash_attention(
        q, k, v, causal=True, interpret=False)).lower(
            qs, ks, ks).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.fixture(scope="module")
def serve_ouro(topo):
    with open(os.path.join(ROOT, "benchmarks", "cells",
                           "ouro-2b6-mathqa-closed.json")) as f:
        sizes = json.load(f)
    cfg = _benchmark_config("ouro-2.6b", sizes)
    slots, max_seq = sizes["slots"], sizes["max_seq_len"]
    return (cfg, slots) + _serve_structs(topo, cfg, slots, max_seq)


@pytest.mark.parametrize("program,bucket", [
    ("decode_multi", None), ("prefill_sample_batch", 256),
    ("prefill_sample_batch", 128), ("prefill_sample_batch", 64),
    ("prefill", 256)])
def test_ouro_programs_fit_beside_the_weights_and_192_slabs(
        serve_ouro, as_on_the_chip, record_property, program, bucket):
    """The looped cell's fused block (k = 64, the engine's largest) and
    its admission tiles at 8 slots x 640, each bucket of its prompts at
    the rows the engine gives under the cell's two terms (256 positions
    a tile: 256 x 1, written with `periodic._put`'s twin row as the
    check's `prefill` is, 128 x 2 and 64 x 4): 5.34 GB
    of weights beside K and V of 4 passes x 48 layers = 192 slabs, both
    aliased in and out; a pass a scan step under `ut_pass` with one layer
    scan inside it (one body for all 192 layer walks), the rows read
    through the decode kernel at one query head a KV head. The width
    rule's evidence (ISSUE 55): arguments + temporaries under the chip's
    15.75 GB: 13.39 GB of arguments and 0.002 GB of temporaries for the
    block, 0.011 for the 256 x 1 and 128 x 2 tiles and for the check's
    `prefill`, 0.010 for 64 x 4, at the cell's float32 activations
    (under bf16 ones every launch copied wq, wk and wv into another
    layout, 1.21 GB: PERF.md section 6).
    `prefill`: the one-row tile the harness's check runs, which written
    as a one-index scatter copied the whole cache into another layout
    (20.4 GB: `periodic._put`)."""
    from ray_tpu.models import generate, moe, periodic
    from ray_tpu.serve.llm import LLMEngine

    cfg, slots, one, key, params, cache = serve_ouro

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    weights = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    assert 5.33e9 < weights < 5.34e9 and cfg.ut_steps == 4
    assert periodic.cache_layers(cfg)["global"] == 192
    assert cache.k.shape == cache.v.shape == (192, slots, 640, 16, 128)
    assert cache.k.dtype == jnp.bfloat16 and cache.kw is None
    assert cfg.dtype == jnp.float32 and cfg.param_dtype == jnp.bfloat16
    held = 2 * cache.k.size * 2
    assert held == 192 * slots * 640 * 4096 * 2
    if program == "decode_multi":
        compiled = generate.decode_multi.lower(
            cfg, params, cache, arr((slots,), jnp.int32),
            arr((slots,), jnp.float32), 64, 0, key,
            arr((slots,), jnp.bool_)).compile()
    elif program == "prefill":
        one_row = (arr((1, bucket), jnp.int32), arr((), jnp.int32),
                   arr((), jnp.int32))
        compiled = generate.prefill.lower(cfg, params, cache,
                                          *one_row).compile()
        # Under bf16 activations too, where the copy was first met.
        bf16 = dataclasses.replace(cfg, dtype=jnp.bfloat16, cache_dtype=None)
        mem = generate.prefill.lower(bf16, params, cache,
                                     *one_row).compile().memory_analysis()
        assert mem.temp_size_in_bytes < 1.3e9 \
            and mem.alias_size_in_bytes >= held
    else:
        rows = LLMEngine._tile_rows(
            bucket, moe.dot_terms(cfg.dtype, cfg.param_dtype))
        assert rows * bucket == 256
        n = arr((rows,), jnp.int32)
        compiled = generate.prefill_sample_batch.lower(
            cfg, params, cache, arr((rows, bucket), jnp.int32), n, n, 0,
            arr((rows,), jnp.float32), key).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    for scope in ("ut_pass", "attn_global", "ffn"):
        assert scope in text, scope
    assert ('"kernel":"decode_attn"' in text) == (program == "decode_multi")
    record_property("argument_gb", mem.argument_size_in_bytes / 1e9)
    record_property("temp_gb", mem.temp_size_in_bytes / 1e9)
    print(f"{program} {bucket}: arguments "
          f"{mem.argument_size_in_bytes / 1e9:.2f} GB, "
          f"temporaries {mem.temp_size_in_bytes / 1e9:.3f} GB")
    assert mem.alias_size_in_bytes >= held
    assert mem.temp_size_in_bytes < 0.1e9
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75e9


# -- kimi-linear-docgen-closed: states and tails beside latent rows ----------

@pytest.fixture(scope="module")
def serve_kimi(topo):
    with open(os.path.join(ROOT, "benchmarks", "cells",
                           "kimi-linear-docgen-closed.json")) as f:
        sizes = json.load(f)
    cfg = _benchmark_config("kimi-linear-48b-ep16", sizes)
    slots, max_seq = sizes["slots"], sizes["max_seq_len"]
    return (cfg, slots) + _serve_structs(topo, cfg, slots, max_seq)


@pytest.mark.parametrize("program", ["decode_multi", "prefill_sample_batch"])
def test_kimi_programs_fit_beside_weights_states_and_latent_rows(
        serve_kimi, as_on_the_chip, record_property, program):
    """The fused decode block (k = 64) beside the whole cache: twenty KDA
    layers through the kernels of `ops/delta_rule` (states and tails
    aliased in and out), seven MLA layers' rows through the decode kernel
    with one array, megablox's kernel for the held experts; and the
    longest bucket's admission tile, one row of 4,096: twenty chunked
    scans, seven layers of per-head attention, 26 routed layers."""
    from ray_tpu.models import generate
    from ray_tpu.serve.llm import LLMEngine

    cfg, slots, one, key, params, cache = serve_kimi

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    if program == "decode_multi":
        lowered = generate.decode_multi.lower(
            cfg, params, cache, arr((slots,), jnp.int32),
            arr((slots,), jnp.float32), 64, 0, key, arr((slots,), jnp.bool_))
    else:
        rows = LLMEngine._tile_rows(4096, 2 if cfg.dtype == jnp.float32
                                    else 1)
        assert rows == 1
        lowered = generate.prefill_sample_batch.lower(
            cfg, params, cache, arr((1, 4096), jnp.int32),
            arr((1,), jnp.int32), arr((1,), jnp.int32), 0,
            arr((1,), jnp.float32), key)
    compiled = lowered.compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    weights = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    # 8.59 GB of weights beside the slots' states (20 x 32 heads x 128 x
    # 128 float32 a slot), their tails and seven layers' rows of 640
    # lanes: 3.15-3.20 GB at 32 slots.
    assert 8.59e9 < weights < 8.60e9
    assert cache.k is None and cache.c.shape == (7, slots, 6144, 640)
    assert cache.s.shape == (20, slots, 32, 128, 128)
    held = sum(x.size * x.dtype.itemsize
               for x in (cache.s, cache.tails, cache.c))
    for scope in ("attn_linear", "attn_latent", "mla_proj", "moe_router",
                  "moe_shared"):
        assert scope in text, scope
    assert "attn_global" not in text and "ragged-dot" not in text
    assert "jit(gmm)" in text
    if program == "decode_multi":
        assert '"kernel":"kda_update"' in text \
            and '"kernel":"kda_tails"' in text and "decode_attn" in text
    else:
        _scan_is_the_kernel(text)
    record_property("argument_gb", mem.argument_size_in_bytes / 1e9)
    record_property("temp_gb", mem.temp_size_in_bytes / 1e9)
    print(f"arguments {mem.argument_size_in_bytes / 1e9:.2f} GB, "
          f"temporaries {mem.temp_size_in_bytes / 1e9:.2f} GB")
    # Both kinds of cache aliased: no program copies one in or out.
    assert mem.alias_size_in_bytes >= held
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75e9

