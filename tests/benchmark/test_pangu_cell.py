"""The latent-attention cell (`openpangu-longgen-closed`) at a tiny size on
the CPU: its reference, its driver, its readers and its check script,
through `run.py`, with the real cell's metrics; the new readers on a small
made-up profile; and the real configuration's keys against the catalog
row.

The tiny copy of the benchmark (`conftest.make_tiny_root`) maps the cell
to `tiny-pangu-closed` (tests/conftest.py names the stand-in); the fixture
below adds that cell's files and its tiny `pangu_ultra_moe` configuration."""

import dataclasses
import io
import json
import os

import pytest

import run
from conftest import ROOT, make_tiny_root
from lib import prefilltime, progspans, scopetime
from lib.spec import Spec

REAL = "openpangu-longgen-closed"
CONFIG = "openpangu-ultra-moe-l5-ep16"
CELL = "tiny-pangu-closed"
# (name, unit, better, source, layer): what the cell appended; all move
# `serve_out_tok_s` and list the cell alone.
NEW = [
    ("model.attn_dev_ms_step.latent", "ms", "lower", "device_trace", "Model"),
    ("model.mla_proj_dev_ms_step.batch", "ms", "lower", "device_trace",
     "Model"),
    ("kernels.latent_attn_roofline_pct.batch", "%", "higher", "device_trace",
     "Kernels"),
    ("kernels.latent_prefill_attn_roofline_pct.batch", "%", "higher",
     "device_trace", "Kernels"),
    ("engine.moe_pairs_held_pct.batch", "%", "lower", "program_counter",
     "Engine"),
    ("model.prefill_mfu_pct.batch", "%", "higher", "device_trace", "Model")]
NEW_NAMES = [m[0] for m in NEW]
# Accepted metrics whose `workloads` gain the cell, behind trinity's:
# readers that fit it unchanged. Not `kernels.decode_attn_roofline_pct
# .batch` (K and V a head: the latent kernel's reader counts rows of 576).
LISTED_BEHIND_TRINITY = [
    "serve_out_tok_s", "engine.occupancy_pct.batch",
    "engine.delivery_tok_s.batch", "model.decode_dev_ms_step.batch",
    "model.decode_dev_ms_step_exact.batch", "device.idle_pct.batch",
    "device.peak_mem_pct.batch", "device.compiles_in_window.batch",
    "engine.host_self_ms_tick.batch", "engine.prefill_useful_pct.batch",
    "engine.decode_useful_pct.batch", "engine.admit_wait_steps_p90.batch",
    "engine.idle_named_pct.batch", "model.moe_dev_ms_step.batch",
    "engine.moe_experts_hit_pct.batch",
    "engine.moe_load_max_over_mean.batch",
    "kernels.moe_experts_roofline_pct.batch"]
LISTED_BEHIND_INTERNLM = ["engine.cache_held_pct.batch"]
LISTED_IN = LISTED_BEHIND_TRINITY + LISTED_BEHIND_INTERNLM


def _tiny_pangu_config():
    from ray_tpu.models import configs

    cfg = dataclasses.asdict(configs.tiny_pangu_test())
    for key in ("dtype", "param_dtype", "max_seq_len", "remat"):
        del cfg[key]
    return dict(cfg, reference="pangu_mla_decoder")


@pytest.fixture(scope="module")
def pangu_root(tmp_path_factory):
    """The tiny benchmark with the real cell's entries pointed at a tiny
    `pangu_ultra_moe` configuration: same driver, same reference, same
    metrics."""
    root = make_tiny_root(str(tmp_path_factory.mktemp("pangu")))
    bdir = os.path.join(root, "benchmarks")
    with open(os.path.join(bdir, "configs", "tiny-pangu.json"), "w") as f:
        json.dump(_tiny_pangu_config(), f)
    with open(os.path.join(bdir, "cells", "tiny-closed.json")) as f:
        sizes = json.load(f)
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
        "name": "tiny-pangu", "source": "test only", "reduced": [],
        "file": "benchmarks/configs/tiny-pangu.json", "why": "test only"})
    bench["workloads"].append({
        "name": CELL, "config": "tiny-pangu", "traffic": "tiny-closed",
        "chips": 1, "why": "test only"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


@pytest.fixture(scope="module")
def real_spec():
    return Spec(ROOT, REAL)


def _run(root, trace, seed=2**31 + 3401, seconds=2):
    out = io.StringIO()
    rc = run.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                   str(seconds), "--trace", str(trace)], root=root,
                  rehearse=True, out=out)
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


def test_the_entries_are_appended_to_benchmark_json(bench):
    # Behind everything the benchmark had (mellum's were its last); a
    # later PR's entries go behind these, so nothing is pinned to the end.
    names = [c["name"] for c in bench["configs"]]
    cells = [w["name"] for w in bench["workloads"]]
    assert names.index(CONFIG) == names.index("mellum2-12b-l8") + 1
    assert cells.index(REAL) == cells.index("mellum2-repoctx-lone") + 1
    config = bench["configs"][names.index(CONFIG)]
    cell = bench["workloads"][cells.index(REAL)]
    assert (config["name"], config["reduced"]) == (
        CONFIG, ["n_layers", "n_dense_layers", "moe_experts", "vocab_size"])
    assert config["file"] == f"benchmarks/configs/{CONFIG}.json"
    assert cell == {"name": REAL, "config": CONFIG,
                    "traffic": "longgen-closed", "chips": 1,
                    "why": cell["why"]}
    assert all(len(e["why"]) <= 200 for e in (config, cell))
    mine = [m for m in bench["per_layer"] if m["name"] in NEW_NAMES]
    assert [(m["name"], m["unit"], m["better"], m["source"], m["layer"])
            for m in mine] == NEW
    assert all(m["workloads"] == [REAL] and m["moves"] == "serve_out_tok_s"
               for m in mine)
    names = [m["name"] for m in bench["per_layer"]]
    assert names.index(NEW_NAMES[0]) == names.index(
        "kernels.flash_fwd_roofline_pct.serve") + 1
    for kind in ("end_to_end", "per_layer"):
        for m in bench[kind]:
            if m["name"] in LISTED_IN:
                before = "internlm2-1b8-batch-closed" \
                    if m["name"] in LISTED_BEHIND_INTERNLM \
                    else "trinity-mini-reason-closed"
                assert m["workloads"].index(REAL) == m["workloads"].index(
                    before) + 1
            elif m["name"] not in NEW_NAMES:
                assert REAL not in m.get("workloads", [])


def test_the_real_cell_names_its_files_and_every_reader(real_spec):
    spec = real_spec
    assert spec.reference.__file__.endswith(
        "references/pangu_mla_decoder.py")
    assert spec.traffic["driver"] == "serve_closed"
    assert {m["name"] for m in spec.metrics("end_to_end")} == {
        "serve_out_tok_s", "setup_s"}
    assert {m["name"] for m in spec.metrics("per_layer")} == (
        set(LISTED_IN) - {"serve_out_tok_s"}) | set(NEW_NAMES)
    for m in spec.metrics("per_layer"):
        reader = spec.load_module("layer_metrics", m["name"])
        assert reader is not None and callable(reader.read), m["name"]
    # Two reach accepted readers by the loader's longest-prefix rule (the
    # scope comes from the suffix); four are files of their own.
    for name, stem in (
            ("model.attn_dev_ms_step.latent", "model.attn_dev_ms_step"),
            ("model.prefill_mfu_pct.batch", "model.prefill_mfu_pct"),
            ("model.mla_proj_dev_ms_step.batch",
             "model.mla_proj_dev_ms_step"),
            ("kernels.latent_attn_roofline_pct.batch",
             "kernels.latent_attn_roofline_pct"),
            ("kernels.latent_prefill_attn_roofline_pct.batch",
             "kernels.latent_prefill_attn_roofline_pct"),
            ("engine.moe_pairs_held_pct.batch",
             "engine.moe_pairs_held_pct")):
        assert spec.load_module("layer_metrics", name).__file__.endswith(
            stem + ".py")
    for fn in ("forward_logits", "chosen_experts", "prefill_flops",
               "moe_experts_min_bytes", "moe_experts_flops",
               "latent_attn_min_bytes", "latent_attn_flops",
               "prefill_attn_flops_bytes", "train_flops_per_token"):
        assert callable(getattr(spec.reference, fn)), fn


def test_the_traffic_and_the_sizes_are_the_issues(real_spec):
    tr, sizes = real_spec.traffic, real_spec.sizes
    assert (tr["clients"], tr["measure"], tr["n_requests"]) == (
        32, "ended_in_window", 160)
    assert tr["prompt_len"] == {"dist": "loguniform", "min": 4096,
                                "max": 8000}
    assert tr["output_len"] == {"dist": "lognormal", "median": 1536,
                                "sigma": 0.3, "min": 768, "max": 2048}
    assert (tr["max_total_len"], tr["lead_in_s"], tr["drain_limit_s"]) == (
        10239, 30.0, 0.0)
    assert tr["trace_seed"] not in (2801, 3201)        # of its own
    assert (sizes["slots"], sizes["max_seq_len"]) == (32, 10240)
    assert sizes["model"] == {"dtype": "bfloat16", "param_dtype": "bfloat16",
                              "max_seq_len": 10240}
    assert sizes["check"] == {"prompt_lens": [6000, 2500, 900],
                              "decode_steps": 16, "window_requests": 2}
    assert sizes["trace_seconds"] == 8.0 and sizes["slots_why"]
    from lib import traffic
    from ray_tpu.serve.llm import default_buckets

    trace = traffic.make_trace(tr)
    lens = [r.prompt_len for r in trace]
    assert 4096 <= min(lens) and max(lens) <= 8000   # all in one bucket
    assert all(r.prompt_len + r.output_len <= 10239 for r in trace)
    assert all(768 <= r.output_len <= 2048 for r in trace)
    buckets = default_buckets(10240)
    assert buckets[-2:] == [8192, 10240] and buckets[0] == 16


def test_the_configuration_is_the_catalog_row(real_spec):
    cfg = real_spec.config
    assert cfg["source"] == ("https://huggingface.co/FreedomIntelligence/"
                             "openPangu-Ultra-MoE-718B/blob/main/config.json")
    assert cfg["reduced"] == ["n_layers", "n_dense_layers", "moe_experts",
                              "vocab_size"]
    assert cfg["assumed"] and cfg["deployment"] and cfg["left_out"]
    assert all(k in cfg for k in cfg["published"])
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "openPangu-Ultra-MoE-718B")
        assert cfg["source"] == row["source_url"]
        assert sorted(row["config"]) == cfg["published"]
        differ = {k for k, v in row["config"].items() if cfg[k] != v}
        assert differ == {"vocab_size"}             # listed in `reduced`
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["q_lora_rank"], cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"],
            cfg["intermediate_size"], cfg["moe_intermediate_size"],
            cfg["n_routed_experts"], cfg["num_experts_per_tok"],
            cfg["num_hidden_layers"], cfg["first_k_dense_replace"],
            cfg["routed_scaling_factor"], cfg["rope_theta"]) == (
        7680, 128, 1536, 512, 128, 64, 128, 18432, 2048, 256, 8, 61, 3, 2.5,
        25600000)
    assert cfg["published_counts"] == {
        "num_hidden_layers": 61, "first_k_dense_replace": 3,
        "n_routed_experts": 256, "vocab_size": 153600,
        "num_nextn_predict_layers": 1}
    # The program's keys: the published widths under its own names, the
    # router's published width beside the 16 experts held.
    assert (cfg["d_model"], cfg["n_heads"], cfg["d_ff"], cfg["moe_d_ff"],
            cfg["moe_router_experts"], cfg["moe_experts"], cfg["moe_top_k"],
            cfg["moe_shared_experts"], cfg["route_scale"], cfg["n_layers"],
            cfg["n_dense_layers"], cfg["vocab_size"]) == (
        7680, 128, 18432, 2048, 256, 16, 8, 1, 2.5, 5, 1, 19200)
    assert 0 <= cfg["moe_first_expert"] <= 256 - 16 \
        and cfg["moe_first_expert"] % 16 == 0
    assert cfg["vocab_size"] * 8 == 153600
    from lib import modelcfg

    program = modelcfg.transformer_config(cfg, real_spec.sizes)
    assert program.arch == cfg["model_type"] == "pangu_ultra_moe"
    assert 4918.9e6 < program.num_params() < 4919.2e6
    ref = real_spec.reference
    assert [r for _, _, r in ref.layer_table(cfg)] == [False] + [True] * 4
    # ISSUE 34's counts: a tile of 8,192 tokens is 27.8 TFLOP of products
    # and 13.7 of per-head attention; a decode step over 32 x 6,600 held
    # rows reads 1.22 GB of latent rows and computes as long as it reads.
    attn = 5 * ref.prefill_attn_flops_bytes(cfg, 1, 8192)["flops"]
    assert 13.6e12 < attn < 13.9e12
    assert 27.6e12 < ref.prefill_flops(cfg, 8192) - attn < 28.0e12
    rows = 32 * 6600
    assert 1.20e9 < ref.latent_attn_min_bytes(cfg, rows) < 1.23e9
    by_bytes = ref.latent_attn_min_bytes(cfg, rows) / 819e9
    by_flops = ref.latent_attn_flops(cfg, rows) / 197e12
    assert abs(by_bytes / by_flops - 1) < 0.02
    # 10 of 16 experts hit a layer a step: 3.85 GB over four layers.
    assert 3.7e9 < ref.moe_experts_min_bytes(cfg, 4 * 10.2, 4 * 16) < 3.9e9


def test_the_tiny_cell_is_correct_against_its_own_reference(pangu_root,
                                                            capsys):
    line = _run(pangu_root, trace=0)
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 0
    assert set(line["rehearsal"]) == {"serve_out_tok_s", "setup_s"}
    logged = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
              if ln.startswith('{"phase"')]
    check = next(d for d in logged if d["phase"] == "serve_setup")["check"]
    assert check["positions"] == 3 * 7 and check["logit_rel_rms_err"] < 1e-4


def test_a_traced_rehearsal_reads_the_routing_counters(pangu_root):
    line = _run(pangu_root, trace=1)
    got = line["rehearsal"]
    # What the program counts comes through the spans on any backend: 4 of
    # 16 experts held, top 2, so a quarter of the pairs under a uniform
    # router; seeded weights lean, so anything between a tenth and a half.
    assert 10 < got["engine.moe_pairs_held_pct.batch"]["value"] < 50
    assert 0 < got["engine.moe_experts_hit_pct.batch"]["value"] <= 100
    assert got["engine.moe_load_max_over_mean.batch"]["value"] >= 1
    assert 0 < got["engine.cache_held_pct.batch"]["value"] <= 100
    # No device on a CPU: the device-trace readers return nothing.
    assert not [n for n in got if n.startswith(("model.", "kernels."))]
    spans = json.load(open(os.path.join(
        pangu_root, ".bench_out", CELL, "program_spans.json")))
    sums = spans["span_attribute_sums"]["engine.process_block"]
    # Pairs routed: steps x 2 routed layers x 4 slots x top 2.
    assert sums["moe_pairs"] == sums["k"] * 2 * 4 * 2
    assert sums["moe_pairs_held"] == sums["moe_rows"] < sums["moe_pairs"]
    assert sums["moe_expert_steps"] == sums["k"] * 2 * 4    # over 4 held
    first = spans["span_attribute_sums"]["engine.deliver_first"]
    assert 0 < first["prefill_moe_pairs_held"] < first["prefill_moe_pairs"]


def test_the_check_script_reads_both_dtypes_control_and_flips(pangu_root):
    from checks import routed_logits

    def read(*extra):
        out = io.StringIO()
        assert routed_logits.main(
            ["--workload", CELL, "--seeds", "5,2147483653", "--control",
             "1", "--control-len", "40", *extra], root=pangu_root,
            rehearse=True, out=out) == 0
        return json.loads(out.getvalue().splitlines()[-1])

    last = read()
    assert last["seeds"] == 2 and last["limit"] == 0.08
    assert last["dtype"] == "float32" and last["over_limit"] == 0
    assert last["sound_largest_rel_rms_err"] < 1e-4
    # float32 program against float32 reference: the same experts, of all
    # 16 the router scores.
    assert last["routing_pairs"] == 2 * 2 * 40 and last["routing_flips"] == 0
    assert last["control_smallest_rel_rms_err"] > 0.03 \
        > 100 * last["sound_largest_rel_rms_err"]
    rounded = read("--dtype", "bfloat16")
    assert rounded["dtype"] == "bfloat16"
    assert rounded["sound_largest_rel_rms_err"] \
        > 10 * last["sound_largest_rel_rms_err"]
    assert rounded["routing_flip_share"] is not None


class _Ctx:
    trace, rehearse, out_dir = True, False, "/nonexistent"


def test_the_new_readers_on_a_made_up_profile(monkeypatch, real_spec):
    """Device time by scope inside the decode and the prefill programs;
    the two roofline shares and the tile's share of the peak from the
    counters and the reference's counts; nothing from a trace without the
    scopes, the kernel or the counters."""
    ms = 1e6
    tile, block = "jit_prefill_sample_batch(7)", "jit_decode_k8(9)"
    ops = [("%a = f32[] fusion(1)", 0.0, 100 * ms),          # tile: proj
           ("%flash.1 = f32[] custom-call(2)", 100 * ms, 150 * ms),
           ("%c = f32[] fusion(3)", 250 * ms, 50 * ms),      # tile: other
           ("%d = f32[] fusion(4)", 400 * ms, 16 * ms),      # decode: proj
           ("%attn.1 = f32[] custom-call(5)", 416 * ms, 24 * ms),
           ("%e = f32[] fusion(6)", 440 * ms, 40 * ms)]      # decode: rest
    scopes = {
        ops[0][0]: "jit(prefill_sample_batch)/while/body/mla_proj/dot",
        ops[1][0]: "jit(prefill_sample_batch)/while/body/attn_latent/"
                   "pallas_call",
        ops[2][0]: "jit(prefill_sample_batch)/dot_general",
        ops[3][0]: "jit(decode_k8)/while/body/mla_proj/bhd,hdc->bhc/dot",
        ops[4][0]: "jit(decode_k8)/while/body/attn_latent/pallas_call",
        ops[5][0]: "jit(decode_k8)/while/body/moe_experts/while/body/"
                   "jit(gmm)/x"}
    raw = {"spans": [], "window": (0.0, 500 * ms), "scopes": scopes,
           "devices": {"/device:TPU:0": {
               "ops": ops, "modules": [(tile, 0.0, 300 * ms),
                                       (block, 400 * ms, 80 * ms)]}}}
    monkeypatch.setattr(progspans, "read_profile", lambda path: raw)
    for lib in (prefilltime, scopetime):
        monkeypatch.setattr(lib.xplane, "find_xplane", lambda d: "x.pb")
    ps = progspans.reduce_profile(raw)
    ps.kernel_s = {"decode_attn": 0.024}
    held = 8 * 32 * 6600                    # rows the 8 steps' slots hold
    ps.spans = [
        progspans.Span("engine.prefill_tile", 0.0, 1.0, "t", {
            "side": "slot", "bucket": 8192, "rows": 1, "tile_rows": 1,
            "tokens": 6000, "req_ids": "41"}),
        progspans.Span("engine.dispatch_block", 2.0, 1.0, "t", {
            "k": 8, "cache_rows": 8 * 32 * 10240, "cache_rows_held": held}),
        progspans.Span("engine.process_block", 3.0, 1.0, "t", {
            "k": 8, "moe_pairs": 8 * 4 * 32 * 8, "moe_pairs_held": 512,
            "moe_rows": 512, "moe_experts_hit": 8 * 4 * 10,
            "moe_expert_steps": 8 * 4 * 16})]
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

    assert ps.decode_steps() == 8
    assert read("model.attn_dev_ms_step.latent") == pytest.approx(3.0)
    assert read("model.mla_proj_dev_ms_step.batch") == pytest.approx(2.0)
    assert read("engine.moe_pairs_held_pct.batch") == pytest.approx(6.25)
    assert read("engine.cache_held_pct.batch") == pytest.approx(
        100 * 6600 / 10240)
    ref = spec.reference
    rows = held / 8
    least_s = max(ref.latent_attn_min_bytes(spec.config, rows) / 819e9,
                  ref.latent_attn_flops(spec.config, rows) / 197e12)
    assert read("kernels.latent_attn_roofline_pct.batch") == pytest.approx(
        100 * least_s / 0.003)
    assert 45 < read("kernels.latent_attn_roofline_pct.batch") < 55
    fb = ref.prefill_attn_flops_bytes(spec.config, 1, 8192)
    assert fb["flops"] / 197e12 > fb["bytes"] / 819e9     # bound by FLOPs
    assert read("kernels.latent_prefill_attn_roofline_pct.batch") == \
        pytest.approx(100 * 5 * fb["flops"] / 197e12 / 0.15)
    assert read("model.prefill_mfu_pct.batch") == pytest.approx(
        100 * ref.prefill_flops(spec.config, 6000) / 0.3 / 197e12)
    for name in ("kernels.latent_prefill_attn_roofline_pct.batch",
                 "model.prefill_mfu_pct.batch"):
        assert 0 < read(name) < 100, name
    # A trace of a program without the scopes or the kernel (the
    # parent's, another architecture's): every one of them is silent, and
    # nothing raises.
    raw["scopes"] = {k: "jit(x)/dot_general" for k in scopes}
    m.pop("prefill_scope_s")
    m.pop("decode_scope_s")
    ps.kernel_s = {}
    for name in ("model.attn_dev_ms_step.latent",
                 "model.mla_proj_dev_ms_step.batch",
                 "kernels.latent_attn_roofline_pct.batch",
                 "kernels.latent_prefill_attn_roofline_pct.batch"):
        assert read(name) is None, name
    # And one whose spans carry no counters: the counter reader too.
    ps.spans = ps.spans[:1]
    assert read("engine.moe_pairs_held_pct.batch") is None
