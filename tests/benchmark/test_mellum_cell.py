"""The lone caller's routed, windowed prefill cell (`mellum2-repoctx-lone`)
at a tiny size on the CPU: its reference, its driver, its readers and its
check script, through `run.py`, with the real cell's metrics; the readers
of the prefill programs' scopes and counters on a small made-up profile;
and the real configuration's keys against the catalog row.

The tiny copy of the benchmark (`conftest.make_tiny_root`) maps the cell
to `tiny-mellum-lone` (tests/conftest.py names the stand-in); the fixture
below adds that cell's files and its tiny `mellum` configuration."""

import dataclasses
import io
import json
import os

import pytest

import run
from conftest import ROOT, make_tiny_root
from lib import prefilltime, progspans
from lib.spec import Spec

REAL = "mellum2-repoctx-lone"
CELL = "tiny-mellum-lone"
# (name, unit, better, source, layer, moves): what the cell appended.
NEW = [
    ("model.prefill_mfu_pct.online", "%", "higher", "device_trace", "Model",
     "ttft_p90_ms"),
    ("model.moe_dev_ms_req.prefill", "ms", "lower", "device_trace", "Model",
     "ttft_p90_ms"),
    ("model.attn_dev_ms_req.window", "ms", "lower", "device_trace", "Model",
     "ttft_p90_ms"),
    ("model.attn_dev_ms_req.global", "ms", "lower", "device_trace", "Model",
     "ttft_p90_ms"),
    ("kernels.moe_prefill_roofline_pct.online", "%", "higher",
     "device_trace", "Kernels", "ttft_p90_ms"),
    ("engine.moe_prefill_load_max_over_mean.online", "ratio", "lower",
     "program_counter", "Engine", "ttft_p90_ms"),
    ("model.moe_dev_ms_step.online", "ms", "lower", "device_trace", "Model",
     "tpot_p90_ms"),
    ("engine.moe_experts_hit_pct.online", "%", "lower", "program_counter",
     "Engine", "tpot_p90_ms"),
    ("kernels.moe_experts_roofline_pct.online", "%", "higher",
     "device_trace", "Kernels", "tpot_p90_ms")]
NEW_NAMES = [m[0] for m in NEW]
# Accepted metrics whose `workloads` gain the cell: readers that fit it
# unchanged. Not `engine.cache_held_pct.online` and
# `kernels.decode_attn_roofline_pct.online` (rows of one cache length),
# nor `kernels.flash_dev_pct.serve` (every pallas event in a scanned
# layer: here megablox's grouped products beside the flash kernel).
LISTED_IN = [
    "ttft_p90_ms", "tpot_p90_ms", "runtime.handoff_p50_ms",
    "engine.queue_p90_ms", "model.prefill_dev_ms_req",
    "model.decode_dev_ms_step.online", "device.idle_pct.online",
    "device.peak_mem_pct.online", "device.compiles_in_window.online",
    "engine.host_self_ms_tick.online", "engine.prefill_useful_pct.online",
    "engine.decode_useful_pct.online", "engine.idle_named_pct.online",
    "model.decode_dev_ms_step_exact.online"]


def _tiny_mellum_config():
    from ray_tpu.models import configs

    cfg = dataclasses.asdict(configs.tiny_mellum_test())
    for key in ("dtype", "param_dtype", "max_seq_len", "remat"):
        del cfg[key]
    # As a config.json gives them: a dict a section.
    rope = {k: dict(v) for k, v in cfg["rope_parameters"]}
    return dict(cfg, rope_parameters=rope, reference="mellum_decoder")


@pytest.fixture(scope="module")
def mellum_root(tmp_path_factory):
    """The tiny benchmark with the real cell's entries pointed at a tiny
    `mellum` configuration: same driver, same reference, same metrics."""
    root = make_tiny_root(str(tmp_path_factory.mktemp("mellum")))
    bdir = os.path.join(root, "benchmarks")
    with open(os.path.join(bdir, "configs", "tiny-mellum.json"), "w") as f:
        json.dump(_tiny_mellum_config(), f)
    with open(os.path.join(bdir, "cells", "tiny-lone.json")) as f:
        sizes = json.load(f)
    # Prompts of five windows of 8, of one to two, and under one, whose
    # decode crosses the ring's edge.
    sizes["check"] = {"prompt_lens": [40, 12, 6], "decode_steps": 6,
                      "window_requests": 2}
    with open(os.path.join(bdir, "cells", CELL + ".json"), "w") as f:
        json.dump(sizes, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    listed = {m["name"] for kind in ("end_to_end", "per_layer")
              for m in bench[kind] if CELL in m.get("workloads", ())}
    assert listed == set(LISTED_IN) | set(NEW_NAMES)
    bench["configs"].append({
        "name": "tiny-mellum", "source": "test only", "reduced": [],
        "file": "benchmarks/configs/tiny-mellum.json", "why": "test only"})
    bench["workloads"].append({
        "name": CELL, "config": "tiny-mellum", "traffic": "tiny-lone",
        "chips": 1, "why": "test only"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


@pytest.fixture(scope="module")
def real_spec():
    return Spec(ROOT, REAL)


def _run(root, trace, seed=2**31 + 3201, seconds=2):
    out = io.StringIO()
    rc = run.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                   str(seconds), "--trace", str(trace)], root=root,
                  rehearse=True, out=out)
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


def test_the_entries_are_appended_to_benchmark_json(bench):
    # Behind everything the benchmark had (trinity's were its last); a
    # later PR's entries go behind these, so nothing is pinned to the end.
    names = [c["name"] for c in bench["configs"]]
    cells = [w["name"] for w in bench["workloads"]]
    assert names.index("mellum2-12b-l8") == names.index(
        "trinity-mini-l5") + 1
    assert cells.index(REAL) == cells.index(
        "trinity-mini-reason-closed") + 1
    config = bench["configs"][names.index("mellum2-12b-l8")]
    cell = bench["workloads"][cells.index(REAL)]
    assert (config["name"], config["reduced"]) == ("mellum2-12b-l8",
                                                   ["n_layers"])
    assert cell == {"name": REAL, "config": "mellum2-12b-l8",
                    "traffic": "repoctx-lone", "chips": 1,
                    "why": cell["why"]}
    assert all(len(e["why"]) <= 200 for e in (config, cell))
    mine = [m for m in bench["per_layer"] if m["name"] in NEW_NAMES]
    assert [(m["name"], m["unit"], m["better"], m["source"], m["layer"],
             m["moves"]) for m in mine] == NEW
    assert all(m["workloads"] == [REAL] for m in mine)
    # Appended behind everything the benchmark had.
    names = [m["name"] for m in bench["per_layer"]]
    assert names.index(NEW_NAMES[0]) > names.index(
        "kernels.decode_attn_roofline_pct.online")
    for kind in ("end_to_end", "per_layer"):
        for m in bench[kind]:
            if m["name"] in LISTED_IN:
                assert m["workloads"].index(REAL) == m["workloads"].index(
                    "mistral7b-docqa-lone") + 1
            elif m["name"] not in NEW_NAMES:
                assert REAL not in m.get("workloads", [])


def test_the_real_cell_names_its_files_and_every_reader(real_spec):
    spec = real_spec
    assert spec.reference.__file__.endswith("references/mellum_decoder.py")
    assert spec.traffic["driver"] == "serve_closed"
    assert {m["name"] for m in spec.metrics("end_to_end")} == {
        "ttft_p90_ms", "tpot_p90_ms", "setup_s"}
    assert {m["name"] for m in spec.metrics("per_layer")} == (
        set(LISTED_IN) - {"ttft_p90_ms", "tpot_p90_ms"}) | set(NEW_NAMES)
    for m in spec.metrics("per_layer"):
        reader = spec.load_module("layer_metrics", m["name"])
        assert reader is not None and callable(reader.read), m["name"]
    # The `.online` twins reach the accepted readers by the loader's
    # longest-prefix rule; the prefill readers are files of their own.
    for name, stem in (("model.moe_dev_ms_step.online",
                        "model.moe_dev_ms_step"),
                       ("kernels.moe_experts_roofline_pct.online",
                        "kernels.moe_experts_roofline_pct"),
                       ("model.attn_dev_ms_req.global",
                        "model.attn_dev_ms_req")):
        assert spec.load_module("layer_metrics", name).__file__.endswith(
            stem + ".py")
    for fn in ("forward_logits", "loss", "train_flops_per_token",
               "chosen_experts", "moe_experts_min_bytes",
               "moe_experts_flops", "prefill_flops"):
        assert callable(getattr(spec.reference, fn)), fn


def test_the_traffic_and_the_sizes_are_the_issues(real_spec):
    tr, sizes = real_spec.traffic, real_spec.sizes
    assert (tr["clients"], tr["measure"], tr["n_requests"]) == (
        1, "sent_in_window", 96)
    assert tr["prompt_len"] == {"dist": "loguniform", "min": 4096,
                                "max": 8000}
    assert tr["output_len"] == {"dist": "fixed", "value": 48}
    assert (tr["max_total_len"], tr["lead_in_s"], tr["drain_limit_s"]) == (
        8191, 4.0, 8.0)
    assert (sizes["slots"], sizes["max_seq_len"]) == (4, 8192)
    # bf16 activations are `correct` here, so the cell states them
    # (ISSUE 32's rule; REVIEW of PR 32).
    assert sizes["model"] == {"dtype": "bfloat16", "param_dtype": "bfloat16",
                              "max_seq_len": 8192}
    from lib import traffic

    lens = [r.prompt_len for r in traffic.make_trace(tr)]
    assert 4096 <= min(lens) and max(lens) <= 8000   # all in one bucket
    window = real_spec.config["sliding_window"]
    a, b, c = sizes["check"]["prompt_lens"]
    assert a > 4 * window and window < b < 2 * window and c < window
    assert c + sizes["check"]["decode_steps"] > window   # the ring's edge


def test_the_configuration_is_the_catalog_row(real_spec):
    cfg = real_spec.config
    assert cfg["source"] == ("https://huggingface.co/JetBrains/"
                             "Mellum2-12B-A2.5B-Instruct/blob/main/"
                             "config.json")
    assert cfg["reduced"] == ["n_layers"] and cfg["assumed"] \
        and cfg["deployment"]
    assert all(k in cfg for k in cfg["published"])
    assert (cfg["hidden_size"], cfg["head_dim"], cfg["num_experts"],
            cfg["moe_intermediate_size"], cfg["num_hidden_layers"],
            cfg["vocab_size"], cfg["num_experts_per_tok"],
            cfg["sliding_window"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["rms_norm_eps"]) == (
        2304, 128, 64, 896, 28, 98304, 8, 1024, 32, 4, 1e-6)
    assert cfg["rope_parameters"] == {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default",
                              "rope_theta": 500000}}
    assert cfg["layer_types"] == (["sliding_attention"] * 3
                                  + ["full_attention"]) * 7
    assert set(cfg["mlp_layer_types"]) == {"sparse"}
    # The program's keys are the published ones under its own names;
    # depth is the one cut: two whole periods.
    assert (cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"],
            cfg["moe_d_ff"], cfg["moe_experts"], cfg["moe_top_k"],
            cfg["norm_eps"], cfg["n_layers"], cfg["global_attn_every"]) == (
        2304, 32, 4, 896, 64, 8, 1e-6, 8, 4)
    from lib import modelcfg

    program = modelcfg.transformer_config(cfg, real_spec.sizes)
    assert program.arch == "mellum" and program.num_params() == 3794968832
    assert program.rope_section("full_attention")["rope_type"] == "yarn"
    ref = real_spec.reference
    assert ref.layer_kinds(cfg) == cfg["layer_types"][:8]
    # ISSUE 32's count for a tile of 8,192 tokens: ~11.2 TFLOP.
    assert 11.0e12 < ref.prefill_flops(cfg, 8192) < 11.4e12


def test_the_tiny_cell_is_correct_against_its_own_reference(mellum_root,
                                                            capsys):
    line = _run(mellum_root, trace=0)
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 0
    assert set(line["rehearsal"]) == {"ttft_p90_ms", "tpot_p90_ms",
                                      "setup_s"}
    logged = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
              if ln.startswith('{"phase"')]
    check = next(d for d in logged if d["phase"] == "serve_setup")["check"]
    assert check["positions"] == 3 * 7 and check["logit_rel_rms_err"] < 1e-4


def test_a_traced_rehearsal_reads_the_routing_counters(mellum_root):
    line = _run(mellum_root, trace=1)
    got = line["rehearsal"]
    # What the program counts comes through the spans on any backend:
    # 8 experts, top 2, the fullest expert holds at least the mean.
    assert got["engine.moe_prefill_load_max_over_mean.online"]["value"] >= 1
    assert 0 < got["engine.moe_experts_hit_pct.online"]["value"] <= 100
    assert got["engine.prefill_useful_pct.online"]["value"] > 0
    # No device on a CPU: the device-trace readers return nothing.
    assert not [n for n in got if n.startswith(("model.", "kernels."))]
    spans = json.load(open(os.path.join(
        mellum_root, ".bench_out", CELL, "program_spans.json")))
    sums = spans["span_attribute_sums"]["engine.deliver_first"]
    # A tile's rows: every position of the tile x top 2 x 8 layers.
    assert sums["prefill_moe_rows"] % (2 * 8) == 0 and sums["moe_tiles"] > 0
    assert sums["prefill_moe_experts_hit"] <= sums["moe_tiles"] * 8 * 8
    tiles = spans["span_attribute_sums"]["engine.prefill_tile"]
    assert tiles["tokens"] > 0


def test_the_check_script_reads_both_dtypes_control_and_flips(mellum_root):
    from checks import routed_logits

    def read(*extra):
        out = io.StringIO()
        assert routed_logits.main(
            ["--workload", CELL, "--seeds", "5,2147483653", "--control",
             "1", "--control-len", "40", *extra], root=mellum_root,
            rehearse=True, out=out) == 0
        return json.loads(out.getvalue().splitlines()[-1])

    last = read()
    assert last["seeds"] == 2 and last["limit"] == 0.08
    assert last["dtype"] == "float32" and last["over_limit"] == 0
    assert last["sound_largest_rel_rms_err"] < 1e-4
    # float32 program against float32 reference: the same experts.
    assert last["routing_pairs"] == 2 * 8 * 40 and last["routing_flips"] == 0
    # Weights rounded to 8-bit floats are told apart: 0.056 at this
    # width of 64, where the error of eight 2^-4 roundings is still under
    # the harness's 0.08 (it grows with the width; PERF.md has the
    # chip's reading at 2304, which has to pass the limit).
    assert last["control_smallest_rel_rms_err"] > 0.03 \
        > 100 * last["sound_largest_rel_rms_err"]
    rounded = read("--dtype", "bfloat16")
    assert rounded["dtype"] == "bfloat16"
    assert rounded["sound_largest_rel_rms_err"] \
        > 10 * last["sound_largest_rel_rms_err"]
    assert rounded["routing_flip_share"] is not None


class _Ctx:
    trace, rehearse, out_dir = True, False, "/nonexistent"


def test_prefill_readers_on_a_made_up_profile(monkeypatch, real_spec):
    """Device time by scope inside the prefill programs, per request;
    the roofline and peak shares from the counters and the reference's
    counts; nothing from a trace without the scopes."""
    ms = 1e6
    tile = "jit_prefill_sample_batch(7)"
    ops = [("%a = f32[] fusion(1)", 0.0, 20 * ms),
           ("%gmm.1 = f32[] custom-call(2)", 20 * ms, 100 * ms),
           ("%c = f32[] fusion(3)", 120 * ms, 60 * ms),
           ("%d = f32[] fusion(4)", 180 * ms, 120 * ms),
           ("%e = f32[] fusion(5)", 300 * ms, 50 * ms),
           ("%f = f32[] fusion(6)", 400 * ms, 30 * ms),   # a decode's
           ("%while.1 = () while(8)", 0.0, 350 * ms)]
    scopes = {
        ops[0][0]: "jit(prefill_sample_batch)/while/body/moe_router/dot",
        ops[1][0]: "jit(prefill_sample_batch)/while/body/moe_experts/"
                   "jit(gmm)/pallas_call",
        ops[2][0]: "jit(prefill_sample_batch)/while/body/attn_window/dot",
        ops[3][0]: "jit(prefill_sample_batch)/while/body/attn_global/dot",
        ops[4][0]: "jit(prefill_sample_batch)/dot_general",
        ops[5][0]: "jit(decode_k64)/while/body/moe_experts/jit(gmm)/x",
        ops[6][0]: "jit(prefill_sample_batch)/while"}
    raw = {"spans": [], "window": (0.0, 500 * ms), "scopes": scopes,
           "devices": {"/device:TPU:0": {
               "ops": ops, "modules": [
                   (tile, 0.0, 350 * ms),
                   ("jit_decode_k64(9)", 380 * ms, 100 * ms)]}}}
    monkeypatch.setattr(progspans, "read_profile", lambda path: raw)
    monkeypatch.setattr(prefilltime.xplane, "find_xplane",
                        lambda d: "x.pb")
    ps = progspans.reduce_profile(raw)
    rows = 8 * 8192 * 8                     # layers x positions x top 8
    ps.spans = [
        progspans.Span("engine.prefill_tile", 0.0, 1.0, "t", {
            "side": "slot", "bucket": 8192, "rows": 1, "tile_rows": 1,
            "tokens": 6000, "req_ids": "41"}),
        progspans.Span("engine.deliver_first", 2.0, 1.0, "t", {
            "tokens": 1, "moe_tiles": 1, "prefill_moe_rows": rows,
            "prefill_moe_experts_hit": 8 * 64,
            "prefill_moe_rows_max": 8 * 9000})]
    spec = real_spec

    class Dev:
        device_kind = "TPU v5 lite"

    ctx = _Ctx()
    ctx.spec = spec
    m = {"ctx": ctx, "program_spans": ps, "arch": spec.config,
         "devices": [Dev()]}

    def read(name):
        return spec.load_module("layer_metrics", name).read(
            {"name": name}, m)

    assert prefilltime.launches(ps) == 1 and prefilltime.requests(ps) == 1
    assert read("model.moe_dev_ms_req.prefill") == pytest.approx(120.0)
    assert read("model.attn_dev_ms_req.window") == pytest.approx(60.0)
    assert read("model.attn_dev_ms_req.global") == pytest.approx(120.0)
    assert read("engine.moe_prefill_load_max_over_mean.online") == \
        pytest.approx(8 * 9000 * 64 / rows)
    ref = spec.reference
    # Bound by operations at 8,192 rows an expert-layer: 6.5 TFLOP at
    # 197 TFLOP/s over the 100 ms under `moe_experts`.
    least_s = ref.moe_experts_flops(spec.config, rows) / 197e12
    assert least_s > ref.moe_experts_min_bytes(
        spec.config, 8 * 64, rows) / 819e9
    assert read("kernels.moe_prefill_roofline_pct.online") == \
        pytest.approx(100 * least_s / 0.1)
    assert read("model.prefill_mfu_pct.online") == pytest.approx(
        100 * ref.prefill_flops(spec.config, 6000) / 0.35 / 197e12)
    assert 0 < read("model.prefill_mfu_pct.online") < 100
    # A trace of a program without the scopes (the parent's, another
    # architecture's): every scope reader is silent, and nothing raises.
    raw["scopes"] = {k: "jit(prefill_sample_batch)/dot_general"
                     for k in scopes}
    m.pop("prefill_scope_s")
    for name in ("model.moe_dev_ms_req.prefill",
                 "model.attn_dev_ms_req.window",
                 "model.attn_dev_ms_req.global",
                 "kernels.moe_prefill_roofline_pct.online"):
        assert read(name) is None, name
    # And one whose spans carry no counters: the counter readers too.
    ps.spans = ps.spans[:1]
    assert read("engine.moe_prefill_load_max_over_mean.online") is None
    assert read("kernels.moe_prefill_roofline_pct.online") is None
