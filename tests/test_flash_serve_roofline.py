"""`kernels.flash_fwd_roofline_pct.serve` (PR 33): the forward flash
kernel against its roofline in a serving cell, from the shapes of the
stretch's prefill tiles and the device time of the events whose
`kernel_metadata` reads `flash_fwd`; on a made-up profile, and on traces
that lack the kernel or the tiles (each reads as nothing, and raises
nothing). Outside tests/benchmark/, which a PR that adds no cell leaves
as it is."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = "kernels.flash_fwd_roofline_pct.serve"
CELL = "mistral7b-docqa-lone"
MS = 1e6          # ns
KERNEL = ('%closed_call.{n} = bf16[1,32,4096,128]{{3,2,1,0}} custom-call(), '
          'custom_call_target="tpu_custom_call", frontend_attributes='
          '{{kernel_metadata={{"kernel":"{k}"}}}}')


@pytest.fixture
def bench():
    """benchmarks/lib, importable as the harness imports it."""
    sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
    try:
        from lib import progspans
        from lib.spec import Spec
        yield progspans, Spec(ROOT, CELL)
    finally:
        sys.path.remove(os.path.join(ROOT, "benchmarks"))


class _Ctx:
    trace, rehearse, out_dir = True, False, "/nonexistent"


class _Dev:
    device_kind = "TPU v5 lite"


def _read(bench, ops, spans, rehearse=False):
    progspans, spec = bench
    ps = progspans.reduce_profile({
        "spans": spans, "window": (0.0, 100 * MS), "scopes": {},
        "devices": {"/device:TPU:0": {
            "ops": ops, "modules": [("jit_prefill_sample_batch(3)", 0.0,
                                     40 * MS)]}}})
    ctx = _Ctx()
    ctx.spec, ctx.rehearse = spec, rehearse
    m = {"ctx": ctx, "program_spans": ps, "arch": spec.config,
         "devices": [_Dev()]}
    return spec.load_module("layer_metrics", NAME).read({"name": NAME}, m)


def _tile(progspans, **stats):
    return progspans.Span("engine.prefill_tile", 0.0, 1.0, "t", stats)


def test_the_entry_is_the_last_and_lists_the_one_cell(bench):
    _, spec = bench
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        per_layer = json.load(f)["per_layer"]
    # Appended behind everything PR 32 left; later PRs' entries go behind
    # it, so it is held to its place and not to the end.
    names = [m["name"] for m in per_layer]
    assert names.index(NAME) == names.index(
        "kernels.moe_experts_roofline_pct.online") + 1
    entry = per_layer[names.index(NAME)]
    assert entry == {"name": NAME, "unit": "%", "better": "higher",
                     "source": "device_trace", "layer": "Kernels",
                     "moves": "ttft_p90_ms", "workloads": [CELL]}
    assert NAME in {m["name"] for m in spec.metrics("per_layer")}
    # Its own file, beside the training reader's: the longest prefix wins.
    assert spec.load_module("layer_metrics", NAME).__file__.endswith(
        NAME + ".py")
    assert spec.load_module(
        "layer_metrics", "kernels.flash_fwd_roofline_pct.train"
    ).__file__.endswith("kernels.flash_fwd_roofline_pct.py")


def test_reads_the_tiles_shape_against_the_kernels_time(bench):
    """Two one-row tiles of the 4,096 bucket, 32 launches (16 layers a
    tile) in 64 ms: a launch's 137 GFLOP at 197 TFLOP/s are 0.70 ms of
    its 2 ms."""
    progspans, spec = bench
    ops = [(KERNEL.format(n=10, k="flash_fwd"), i * 2 * MS, 2 * MS)
           for i in range(32)]
    ops.append((KERNEL.format(n=14, k="decode_attn"), 70 * MS, 9 * MS))
    spans = [_tile(progspans, tile_rows=1, bucket=4096, tokens=3000),
             _tile(progspans, tile_rows=1, bucket=4096, tokens=2100)]
    a = spec.config
    assert (a["n_heads"], a["n_kv_heads"], a["d_model"]) == (32, 8, 4096)
    flops = 4 * 32 * 4096 * 4096 * 128 / 2
    got = _read(bench, ops, spans)
    assert got == pytest.approx(100 * (flops / 197e12) / 2e-3)
    assert 34 < got < 36
    # Padding included: a two-row tile is twice the work a launch.
    spans = [_tile(progspans, tile_rows=2, bucket=4096, tokens=3000)]
    assert _read(bench, ops, spans) == pytest.approx(2 * got)


def test_reads_as_nothing_without_the_kernel_the_tiles_or_a_chip(bench):
    progspans, _ = bench
    kernel = [(KERNEL.format(n=10, k="flash_fwd"), 0.0, 2 * MS)]
    fusion = [("%fusion.2 = f32[4] fusion()", 0.0, 5 * MS)]
    tile = [_tile(progspans, tile_rows=1, bucket=4096)]
    assert _read(bench, kernel, tile) is not None
    assert _read(bench, kernel, tile, rehearse=True) is None
    assert _read(bench, fusion, tile) is None
    assert _read(bench, kernel, []) is None
    assert _read(bench, kernel, [_tile(progspans, rows=1)]) is None
    assert _read(bench, [], []) is None
