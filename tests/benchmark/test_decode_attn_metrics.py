"""The readers PR 31 added beside the kernel of `ops/decode_attention`:
`engine.cache_held_pct.*` from the counters the `engine.dispatch_block`
spans carry, `kernels.decode_attn_roofline_pct.*` from those counters
and the device time of the events whose `kernel_metadata` reads
`decode_attn`; on a made-up profile, on a trace without either (the
parent commit), and through a traced rehearsal of the tiny cells."""

import io
import json

import pytest

import run
from conftest import ROOT
from lib import progspans
from lib.spec import Spec

MS = 1e6          # ns
KERNEL = ('%closed_call.{n} = bf16[32,16,128]{{2,1,0}} custom-call(), '
          'custom_call_target="tpu_custom_call", frontend_attributes='
          '{{kernel_metadata={{"kernel":"{k}"}}}}')
CELLS = {"batch": "internlm2-1b8-batch-closed",
         "online": "mistral7b-docqa-lone"}
NEW = ("engine.cache_held_pct", "kernels.decode_attn_roofline_pct")


class _Ctx:
    trace, rehearse, out_dir = True, False, "/nonexistent"


class _Dev:
    device_kind = "TPU v5 lite"


def _measure(suffix, ops, spans):
    spec = Spec(ROOT, CELLS[suffix])
    ps = progspans.reduce_profile({
        "spans": spans, "window": (0.0, 100 * MS), "scopes": {},
        "devices": {"/device:TPU:0": {
            "ops": ops, "modules": [("jit_decode_k4(7)", 0.0, 40 * MS)]}}})
    ctx = _Ctx()
    ctx.spec = spec
    m = {"ctx": ctx, "program_spans": ps, "arch": spec.config,
         "devices": [_Dev()]}

    def read(stem):
        name = f"{stem}.{suffix}"
        assert name in {x["name"] for x in spec.metrics("per_layer")}
        return spec.load_module("layer_metrics", name).read({"name": name}, m)

    return spec, read


@pytest.mark.parametrize("suffix", sorted(CELLS))
def test_readers_on_a_made_up_profile(suffix):
    """Two blocks of 4 and 2 steps holding 900 and 300 of 4,096 and 2,048
    rows; the kernel ran 3 + 1 ms under one launch of a 4-step block."""
    ops = [(KERNEL.format(n=1, k="decode_attn"), 0.0, 3 * MS),
           ("%fusion.2 = f32[4] fusion()", 3 * MS, 5 * MS),
           (KERNEL.format(n=1, k="decode_attn"), 8 * MS, 1 * MS),
           (KERNEL.format(n=3, k="flash_fwd"), 9 * MS, 7 * MS)]
    spans = [progspans.Span("engine.dispatch_block", 0.0, 1.0, "t", {
                 "k": 4, "cache_rows": 4096, "cache_rows_held": 900}),
             progspans.Span("engine.dispatch_block", 2.0, 1.0, "t", {
                 "k": 2, "cache_rows": 2048, "cache_rows_held": 300})]
    spec, read = _measure(suffix, ops, spans)
    assert read(NEW[0]) == pytest.approx(100 * 1200 / 6144)
    a = spec.config
    head = a["d_model"] // a["n_heads"]
    # 200 rows a step held: K and V, every layer, bf16, at 819 GB/s, over
    # the kernel's 1 ms a device step.
    least_ms = (200 * a["n_kv_heads"] * head * 2 * 2 * a["n_layers"]
                / 819e9 * 1e3)
    assert read(NEW[1]) == pytest.approx(100 * least_ms / 1.0)
    assert 0 < read(NEW[1]) < 100


@pytest.mark.parametrize("suffix", sorted(CELLS))
def test_the_parent_commit_reads_as_nothing(suffix):
    """No counter on the spans and no such kernel in the trace: each
    reader returns None and raises nothing; so with one of the two."""
    fusion = [("%fusion.2 = f32[4] fusion()", 0.0, 5 * MS)]
    bare = [progspans.Span("engine.dispatch_block", 0.0, 1.0, "t",
                           {"k": 4, "slots": 4})]
    _, read = _measure(suffix, fusion, bare)
    assert read(NEW[0]) is None and read(NEW[1]) is None
    counted = [progspans.Span("engine.dispatch_block", 0.0, 1.0, "t", {
        "k": 4, "cache_rows": 4096, "cache_rows_held": 900})]
    _, read = _measure(suffix, fusion, counted)
    assert read(NEW[0]) is not None and read(NEW[1]) is None
    kernel = [(KERNEL.format(n=1, k="decode_attn"), 0.0, 3 * MS)]
    _, read = _measure(suffix, kernel, bare)
    assert read(NEW[1]) is None
    _, read = _measure(suffix, [], [])
    assert read(NEW[0]) is None and read(NEW[1]) is None


def test_a_windowed_stack_or_a_float32_cache_is_told_from_the_files():
    mod = Spec(ROOT, CELLS["batch"]).load_module("layer_metrics", NEW[1])
    arch = {"n_layers": 2, "n_kv_heads": 4, "n_heads": 8, "d_model": 1024}
    bf16 = {"dtype": "bfloat16", "param_dtype": "bfloat16"}
    assert mod.held_bytes_step(arch, bf16, 10) == 10 * 4 * 128 * 2 * 2 * 2
    assert mod.held_bytes_step(dict(arch, head_dim=64), bf16, 10) \
        == 10 * 4 * 64 * 2 * 2 * 2
    two = {"dtype": "float32", "param_dtype": "bfloat16"}
    assert mod.held_bytes_step(arch, two, 10) \
        == 2 * mod.held_bytes_step(arch, bf16, 10)
    f32 = {"dtype": "float32", "param_dtype": "float32"}
    assert mod.held_bytes_step(arch, f32, 10) \
        == 2 * mod.held_bytes_step(arch, bf16, 10)


@pytest.mark.parametrize("workload,suffix", [("tiny-closed", "batch"),
                                             ("tiny-lone", "online")])
def test_traced_rehearsal_reports_the_held_share(tiny_root, workload,
                                                 suffix):
    """Through `run.py` on the CPU: the engine's spans carry the counters
    and the reader finds them; the kernel's share needs a device."""
    out = io.StringIO()
    rc = run.main(["--workload", workload, "--seed", str(2**31 + 31),
                   "--seconds", "2", "--trace", "1"], root=tiny_root,
                  rehearse=True, out=out)
    assert rc == 0
    got = json.loads(out.getvalue().strip().splitlines()[-1])["rehearsal"]
    assert 0 < got[f"{NEW[0]}.{suffix}"]["value"] <= 100
    assert f"{NEW[1]}.{suffix}" not in got
