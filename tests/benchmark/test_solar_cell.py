"""The linear-attention cell (`solar-open2-rollout-closed`) at a tiny size
on the CPU: its reference, its driver, its readers and its check script,
through `run.py`, with the real cell's metrics; the new readers on a small
made-up profile; and the real configuration's keys against the catalog
row.

The tiny copy of the benchmark (`conftest.make_tiny_root`) maps the cell
to `tiny-solar-closed` (tests/conftest.py names the stand-in); the fixture
below adds that cell's files and its tiny `solar_open2` configuration."""

import dataclasses
import io
import json
import os

import pytest

import run
from conftest import ROOT, make_tiny_root
from lib import prefilltime, progspans, scopetime
from lib.spec import Spec

REAL = "solar-open2-rollout-closed"
CONFIG = "solar-open2-l8-ep16"
CELL = "tiny-solar-closed"
# (name, unit, better, source, layer): what the cell appended; all move
# `serve_out_tok_s` and list the cell alone.
NEW = [
    ("model.attn_dev_ms_step.linear", "ms", "lower", "device_trace", "Model"),
    ("model.attn_dev_ms_req.linear", "ms", "lower", "device_trace", "Model"),
    ("kernels.linear_attn_roofline_pct.batch", "%", "higher", "device_trace",
     "Kernels"),
    ("kernels.linear_prefill_attn_roofline_pct.batch", "%", "higher",
     "device_trace", "Kernels"),
    ("engine.linear_state_live_pct.batch", "%", "higher", "program_counter",
     "Engine")]
NEW_NAMES = [m[0] for m in NEW]
# Accepted metrics whose `workloads` gain the cell, behind glm5's: readers
# that read true for it unchanged.
LISTED_BEHIND_GLM = [
    "serve_out_tok_s", "engine.occupancy_pct.batch",
    "engine.delivery_tok_s.batch", "model.decode_dev_ms_step.batch",
    "model.decode_dev_ms_step_exact.batch", "device.idle_pct.batch",
    "device.peak_mem_pct.batch", "device.compiles_in_window.batch",
    "engine.host_self_ms_tick.batch", "engine.prefill_useful_pct.batch",
    "engine.decode_useful_pct.batch", "engine.admit_wait_steps_p90.batch",
    "engine.idle_named_pct.batch", "model.moe_dev_ms_step.batch",
    "engine.moe_experts_hit_pct.batch",
    "engine.moe_load_max_over_mean.batch",
    "kernels.moe_experts_roofline_pct.batch", "engine.cache_held_pct.batch",
    "engine.moe_pairs_held_pct.batch", "model.prefill_mfu_pct.batch"]
# And behind the last cell of a period stack that listed it.
LISTED_BEHIND_OTHERS = ["model.attn_dev_ms_step.global"]
LISTED_IN = LISTED_BEHIND_GLM + LISTED_BEHIND_OTHERS
ENTRIES = {
    "config": {
        "name": CONFIG,
        "source": "https://huggingface.co/upstage/Solar-Open2-250B/blob/"
                  "main/config.json",
        "file": f"benchmarks/configs/{CONFIG}.json",
        "reduced": ["n_layers", "moe_experts", "vocab_size"],
        "why": "solar_open2 250B-A15B at its widths: 2 periods of a NoPE "
               "gated GQA layer + 3 gated delta-rule layers (64 heads, a "
               "128 x 128 f32 state each), 20 of 320 experts held (top 8) "
               "+ 1 shared: 1 of 16 chips"},
    "workload": {
        "name": REAL, "config": CONFIG, "traffic": "rollout-closed",
        "chips": 1,
        "why": "closed loop, 96 callers on 96 slots x 4096, prompts "
               "512-2000, answers ~1536: a step rewrites 6 x 4.2 MB of state "
               "a slot whatever its length beside 2 GQA layers' rows; 20 of "
               "320 experts held"}}


def _tiny_solar_config():
    from ray_tpu.models import configs

    cfg = dataclasses.asdict(configs.tiny_solar_test())
    for key in ("dtype", "param_dtype", "max_seq_len", "remat"):
        del cfg[key]
    return dict(cfg, reference="solar_kda_decoder")


@pytest.fixture(scope="module")
def solar_root(tmp_path_factory):
    """The tiny benchmark with the real cell's entries pointed at a tiny
    `solar_open2` configuration: same driver, same reference, same
    metrics."""
    root = make_tiny_root(str(tmp_path_factory.mktemp("solar")))
    bdir = os.path.join(root, "benchmarks")
    with open(os.path.join(bdir, "configs", "tiny-solar.json"), "w") as f:
        json.dump(_tiny_solar_config(), f)
    with open(os.path.join(bdir, "cells", "tiny-closed.json")) as f:
        sizes = json.load(f)
    # Past a chunk of 64, under one, and shorter than the convolution.
    sizes["check"] = {"prompt_lens": [70, 12, 2], "decode_steps": 6,
                      "window_requests": 2}
    with open(os.path.join(bdir, "cells", CELL + ".json"), "w") as f:
        json.dump(sizes, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    listed = {m["name"] for kind in ("end_to_end", "per_layer")
              for m in bench[kind] if CELL in m.get("workloads", ())}
    assert listed == set(LISTED_IN) | set(NEW_NAMES)
    bench["configs"].append({
        "name": "tiny-solar", "source": "test only", "reduced": [],
        "file": "benchmarks/configs/tiny-solar.json", "why": "test only"})
    bench["workloads"].append({
        "name": CELL, "config": "tiny-solar", "traffic": "tiny-closed",
        "chips": 1, "why": "test only"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


@pytest.fixture(scope="module")
def real_spec():
    return Spec(ROOT, REAL)


def _run(root, trace, seed=2**31 + 4601, seconds=2):
    out = io.StringIO()
    rc = run.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                   str(seconds), "--trace", str(trace)], root=root,
                  rehearse=True, out=out)
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


def test_the_entries_are_appended_to_benchmark_json(bench):
    # Behind everything the benchmark had (glm5's were its last cell,
    # configuration and metrics); a later PR's entries go behind these, so
    # nothing is pinned to the end.
    names = [c["name"] for c in bench["configs"]]
    cells = [w["name"] for w in bench["workloads"]]
    assert names.index(CONFIG) == names.index("glm-5-l5-ep16") + 1
    assert cells.index(REAL) == cells.index("glm5-longctx-closed") + 1
    assert bench["configs"][names.index(CONFIG)] == ENTRIES["config"]
    assert bench["workloads"][cells.index(REAL)] == ENTRIES["workload"]
    assert all(len(e["why"]) <= 200 for e in ENTRIES.values())
    assert len(cells) >= 9 and sum(
        w["chips"] == 4 for w in bench["workloads"]) == 1
    mine = [m for m in bench["per_layer"] if m["name"] in NEW_NAMES]
    assert [(m["name"], m["unit"], m["better"], m["source"], m["layer"])
            for m in mine] == NEW
    assert all(m["workloads"] == [REAL] and m["moves"] == "serve_out_tok_s"
               for m in mine)
    order = [m["name"] for m in bench["per_layer"]]
    assert order.index(NEW_NAMES[0]) == order.index(
        "kernels.sparse_prefill_attn_roofline_pct.batch") + 1
    assert [order.index(n) for n in NEW_NAMES] == list(range(
        order.index(NEW_NAMES[0]), order.index(NEW_NAMES[0]) + len(NEW)))
    for kind in ("end_to_end", "per_layer"):
        for m in bench[kind]:
            if m["name"] in LISTED_BEHIND_GLM:
                assert m["workloads"].index(REAL) == m["workloads"].index(
                    "glm5-longctx-closed") + 1, m["name"]
            elif m["name"] in LISTED_BEHIND_OTHERS:
                assert m["workloads"][-1] == REAL
            elif m["name"] not in NEW_NAMES:
                assert REAL not in m.get("workloads", [])
    # The decode kernel's share of its roofline counts K and V bytes over
    # `n_layers`, and two of this stack's eight layers keep rows: the
    # reader would read four times too high, so the cell is not listed.
    kernel = next(m for m in bench["per_layer"]
                  if m["name"] == "kernels.decode_attn_roofline_pct.batch")
    assert REAL not in kernel["workloads"]


def test_the_real_cell_names_its_files_and_every_reader(real_spec):
    spec = real_spec
    assert spec.reference.__file__.endswith(
        "references/solar_kda_decoder.py")
    assert spec.traffic["driver"] == "serve_closed"
    assert {m["name"] for m in spec.metrics("end_to_end")} == {
        "serve_out_tok_s", "setup_s"}
    assert {m["name"] for m in spec.metrics("per_layer")} == (
        set(LISTED_IN) - {"serve_out_tok_s"}) | set(NEW_NAMES)
    for m in spec.metrics("per_layer"):
        reader = spec.load_module("layer_metrics", m["name"])
        assert reader is not None and callable(reader.read), m["name"]
    # Two reach accepted readers by the loader's longest-prefix rule (the
    # scope comes from the suffix); three are files of their own.
    for name, stem in (
            ("model.attn_dev_ms_step.linear", "model.attn_dev_ms_step"),
            ("model.attn_dev_ms_req.linear", "model.attn_dev_ms_req"),
            ("kernels.linear_attn_roofline_pct.batch",
             "kernels.linear_attn_roofline_pct"),
            ("kernels.linear_prefill_attn_roofline_pct.batch",
             "kernels.linear_prefill_attn_roofline_pct"),
            ("engine.linear_state_live_pct.batch",
             "engine.linear_state_live_pct")):
        assert spec.load_module("layer_metrics", name).__file__.endswith(
            stem + ".py")
    for fn in ("forward_logits", "chosen_experts", "prefill_flops",
               "moe_experts_min_bytes", "moe_experts_flops",
               "kda_state_bytes", "kda_flops_bytes", "routed_layer_output",
               "loss", "train_flops_per_token"):
        assert callable(getattr(spec.reference, fn)), fn
    # The reference stands on its own: nothing of the program's, no
    # cache, no kernel, no chunks: the recurrence a token at a time.
    with open(spec.reference.__file__) as f:
        text = f.read()
    assert "ray_tpu" not in text.replace("`ray_tpu/models`", "") \
        .replace("`ray_tpu/ops`", "")
    for word in ("pallas", "cumsum", "import ray"):
        assert word not in text, word
    assert "lax.scan(one" in text and '"highest"' in text


def test_the_traffic_and_the_sizes_are_the_issues(real_spec):
    tr, sizes = real_spec.traffic, real_spec.sizes
    assert (tr["clients"], tr["measure"], tr["n_requests"]) == (
        96, "ended_in_window", 576)
    assert tr["prompt_len"] == {"dist": "loguniform", "min": 512,
                                "max": 2000}
    assert tr["output_len"] == {"dist": "lognormal", "median": 1536,
                                "sigma": 0.3, "min": 768, "max": 2048}
    assert (tr["max_total_len"], tr["lead_in_s"], tr["drain_limit_s"]) == (
        4095, 30.0, 0.0)
    others = [json.load(open(os.path.join(ROOT, "benchmarks", "traffic", f)))
              for f in os.listdir(os.path.join(ROOT, "benchmarks", "traffic"))
              if f != "rollout-closed.json"]
    assert tr["trace_seed"] not in [o.get("trace_seed") for o in others]
    assert (sizes["slots"], sizes["max_seq_len"]) == (96, 4096)
    assert sizes["model"] == {"dtype": "float32", "param_dtype": "bfloat16",
                              "max_seq_len": 4096, "cache_dtype": "bfloat16"}
    assert sizes["check"] == {"prompt_lens": [1800, 700, 6],
                              "decode_steps": 16, "window_requests": 2}
    assert sizes["trace_seconds"] == 8.0 and len(sizes["slots_why"]) > 200
    from lib import modelcfg, traffic
    from ray_tpu.models import periodic
    from ray_tpu.serve.llm import LLMEngine, default_buckets

    trace = traffic.make_trace(tr)
    lens = [r.prompt_len for r in trace]
    assert 512 <= min(lens) and max(lens) <= 2000
    assert all(r.prompt_len + r.output_len <= 4095 for r in trace)
    assert all(768 <= r.output_len <= 2048 for r in trace)
    buckets = default_buckets(4096)
    assert {next(b for b in buckets if b >= n) for n in lens} == {
        1024, 2048}
    # A slot-side tile of these buckets is one row; a queue-side one is
    # several, and the stack walks its rows singly.
    assert all(LLMEngine._tile_rows(b) == 1 for b in (1024, 2048))
    assert LLMEngine._queue_tile_rows(2048) == 4 and periodic._ROW_ALONE == 512
    # Resident: 7.80 GB of weights, 2.42 GB of states, 0.17 of tails and
    # 3.22 GB of the two GQA layers' rows: 85% of the chip.
    cfg = modelcfg.transformer_config(real_spec.config, sizes)
    assert periodic.cache_layers(cfg) == {"window": 0, "global": 2,
                                          "linear": 6}
    state = 6 * 96 * 64 * 128 * 128 * 4
    tails = 6 * 96 * 3 * 3 * 64 * 128 * 4
    rows = 2 * 96 * 4096 * 8 * 128 * 2 * 2
    assert 2.41e9 < state < 2.42e9 and 3.22e9 < rows < 3.23e9
    assert 0.84 < (state + tails + rows + 2 * cfg.num_params()) / 16e9 < 0.86


def test_the_configuration_is_the_catalog_row(real_spec):
    cfg = real_spec.config
    assert cfg["source"] == ENTRIES["config"]["source"]
    assert cfg["reduced"] == ENTRIES["config"]["reduced"]
    assert cfg["assumed"] and cfg["deployment"] and cfg["left_out"] \
        and cfg["use"] and cfg["program_keys"]
    assert "16 chips share each layer" in cfg["deployment"]
    assert sum("(guess" in s for s in cfg["assumed"]) >= 2
    assert all(k in cfg for k in cfg["published"])
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Solar-Open2-250B")
        assert cfg["source"] == row["source_url"]
        assert sorted(row["config"]) == cfg["published"]
        differ = {k for k, v in row["config"].items() if cfg[k] != v}
        assert differ == {"vocab_size"}             # listed in `reduced`
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["linear_attn_config"], cfg["moe_intermediate_size"],
            cfg["n_routed_experts"], cfg["num_experts_per_tok"],
            cfg["num_hidden_layers"], cfg["gqa_interval"],
            cfg["use_rope"], cfg["use_gqa_gate"], cfg["kda_use_full_proj"],
            cfg["kda_allow_neg_eigval"]) == (
        4096, 64, 8, 128, {"short_conv_kernel_size": 4, "head_dim": 128,
                           "num_heads": 64, "num_kv_heads": None},
        1280, 320, 8, 48, 3, False, True, False, True)
    assert cfg["published_counts"] == {
        "num_hidden_layers": 48, "n_routed_experts": 320,
        "vocab_size": 196608}
    # The program's keys: the published widths under its own names, the
    # router's published width beside the 20 experts held.
    assert (cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"],
            cfg["moe_d_ff"], cfg["moe_router_experts"], cfg["moe_experts"],
            cfg["moe_top_k"], cfg["moe_shared_experts"], cfg["route_scale"],
            cfg["n_layers"], cfg["global_attn_every"], cfg["linear_n_heads"],
            cfg["linear_head_dim"], cfg["linear_conv_kernel"],
            cfg["vocab_size"]) == (
        4096, 64, 8, 1280, 320, 20, 8, 1, 1.0, 8, 4, 64, 128, 4, 24576)
    assert 0 <= cfg["moe_first_expert"] <= 320 - 20 \
        and cfg["moe_first_expert"] % 20 == 0
    assert cfg["vocab_size"] * 8 == 196608
    assert cfg["global_attn_every"] == cfg["gqa_interval"] + 1 \
        and cfg["gqa_layers"] == list(range(0, 48, 4))
    from lib import modelcfg

    program = modelcfg.transformer_config(cfg, real_spec.sizes)
    assert program.arch == cfg["model_type"] == "solar_open2"
    assert 3.86e9 < program.num_params() < 3.94e9          # 3.90 B +- 1%
    ref = real_spec.reference
    assert [kind for *_, kind, _ in ref.layer_table(cfg)] == [
        "global", "linear", "linear", "linear"] * 2
    assert ref.linear_layers(cfg) == 6
    # A state is 64 x 128 x 128 float32, read and written: 8.4 MB an
    # update; 96 slots' six layers a step are 4.8 GB, 5.9 ms at the peak.
    assert ref.kda_state_bytes(cfg, 1) == 2 * 4 * 64 * 128 * 128
    step = ref.kda_state_bytes(cfg, 96 * 6)
    assert 4.8e9 < step < 4.9e9 and 5.8e-3 < step / 819e9 < 6.0e-3
    # The recurrence a token a layer: 7.3 MFLOP against 99 KB of q, k, v,
    # g, beta and o moved, 74 operations a byte under the chip's 240:
    # bound by those bytes (the state itself stays on the chip).
    one = ref.kda_flops_bytes(cfg, 1)
    assert one["flops"] == 64 * 7 * 128 * 128 and one["bytes"] == 64 * (
        2 * 4 * 128 + 4 * 128 + 4)
    # A 2,000-token prompt: the products dominate, the recurrence is 2%.
    n = 2000
    scan = ref.kda_flops_bytes(cfg, 6 * n)["flops"]
    assert 0.01 < scan / ref.prefill_flops(cfg, n) < 0.05


def test_the_tiny_cell_is_correct_against_its_own_reference(solar_root,
                                                            capsys):
    line = _run(solar_root, trace=0)
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 0
    assert set(line["rehearsal"]) == {"serve_out_tok_s", "setup_s"}
    logged = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
              if ln.startswith('{"phase"')]
    check = next(d for d in logged if d["phase"] == "serve_setup")["check"]
    assert check["positions"] == 3 * 7 and check["logit_rel_rms_err"] < 1e-4


def test_a_traced_rehearsal_reads_the_state_updates_owned(solar_root):
    line = _run(solar_root, trace=1)
    got = line["rehearsal"]
    # What the host counts comes through the spans on any backend.
    assert 0 < got["engine.linear_state_live_pct.batch"]["value"] <= 100
    assert 10 < got["engine.moe_pairs_held_pct.batch"]["value"] < 50
    assert 0 < got["engine.cache_held_pct.batch"]["value"] <= 100
    # No device on a CPU: the device-trace readers return nothing.
    assert not [n for n in got if n.startswith(("model.", "kernels."))]
    spans = json.load(open(os.path.join(
        solar_root, ".bench_out", CELL, "program_spans.json")))
    sums = spans["span_attribute_sums"]["engine.dispatch_block"]
    assert sums["linear_slot_steps"] == 6 * 4 * sums["k"]
    assert 0 < sums["linear_slot_steps_live"] <= sums["linear_slot_steps"]
    tiles = spans["span_attribute_sums"]["engine.prefill_tile"]
    assert tiles["linear_tokens"] == 6 * tiles["tokens"]


def test_the_check_script_reads_both_dtypes_control_and_flips(solar_root):
    from checks import routed_logits

    def read(seeds, *extra):
        out = io.StringIO()
        assert routed_logits.main(
            ["--workload", CELL, "--seeds", seeds, "--control", "1",
             "--control-len", "64", *extra], root=solar_root,
            rehearse=True, out=out) == 0
        return json.loads(out.getvalue().splitlines()[-1])

    last = read("5,2147483653")
    assert last["seeds"] == 2 and last["limit"] == 0.08
    assert last["dtype"] == "float32" and last["over_limit"] == 0
    assert last["sound_largest_rel_rms_err"] < 1e-4
    assert last["routing_pairs"] > 0 and last["routing_flips"] == 0
    assert last["control_smallest_rel_rms_err"] > 0.03 \
        > 100 * last["sound_largest_rel_rms_err"]
    rounded = read("5", "--dtype", "bfloat16")
    assert rounded["dtype"] == "bfloat16"
    assert rounded["sound_largest_rel_rms_err"] \
        > 10 * last["sound_largest_rel_rms_err"]


class _Ctx:
    trace, rehearse, out_dir = True, False, "/nonexistent"


def test_the_new_readers_on_a_made_up_profile(monkeypatch, real_spec):
    """Device time by scope inside the decode and the prefill programs;
    the two roofline shares and the share of state updates owned from the
    counters and the reference's counts; nothing from a trace without the
    scopes or the counters."""
    ms = 1e6
    tile, block = "jit_prefill_sample_batch(7)", "jit_decode_k8(9)"
    ops = [("%a = f32[] fusion(1)", 0.0, 20 * ms),         # tile: the scan
           ("%b = f32[] fusion(2)", 20 * ms, 30 * ms),     # tile: linear, rest
           ("%c = f32[] fusion(3)", 50 * ms, 50 * ms),     # tile: other
           ("%d = f32[] fusion(4)", 200 * ms, 80 * ms),    # decode: linear
           ("%e = f32[] custom-call(5)", 280 * ms, 16 * ms),  # decode: global
           ("%f = f32[] fusion(6)", 296 * ms, 64 * ms)]    # decode: rest
    scopes = {
        ops[0][0]: "jit(prefill_sample_batch)/while/body/attn_linear/"
                   "kda_scan/while/body/dot_general",
        ops[1][0]: "jit(prefill_sample_batch)/while/body/attn_linear/"
                   "dot_general",
        ops[2][0]: "jit(prefill_sample_batch)/dot_general",
        ops[3][0]: "jit(decode_k8)/while/body/while/body/attn_linear/while/"
                   "body/mul",
        ops[4][0]: "jit(decode_k8)/while/body/while/body/attn_global/"
                   "pallas_call",
        ops[5][0]: "jit(decode_k8)/while/body/moe_experts/while/body/"
                   "jit(gmm)/x"}
    raw = {"spans": [], "window": (0.0, 400 * ms), "scopes": scopes,
           "devices": {"/device:TPU:0": {
               "ops": ops, "modules": [(tile, 0.0, 100 * ms),
                                       (block, 200 * ms, 160 * ms)]}}}
    monkeypatch.setattr(progspans, "read_profile", lambda path: raw)
    for lib in (prefilltime, scopetime):
        monkeypatch.setattr(lib.xplane, "find_xplane", lambda d: "x.pb")
    ps = progspans.reduce_profile(raw)
    ps.spans = [
        progspans.Span("engine.prefill_tile", 0.0, 1.0, "t", {
            "side": "slot", "bucket": 2048, "rows": 1, "tile_rows": 1,
            "tokens": 1500, "req_ids": "41", "linear_tokens": 6 * 1500}),
        progspans.Span("engine.dispatch_block", 2.0, 1.0, "t", {
            "k": 8, "cache_rows": 8 * 96 * 4096,
            "cache_rows_held": 8 * 90 * 2000,
            "linear_slot_steps": 8 * 96 * 6,
            "linear_slot_steps_live": 8 * 90 * 6})]
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
    assert read("engine.linear_state_live_pct.batch") == pytest.approx(
        100 * 90 / 96)
    assert read("model.attn_dev_ms_step.linear") == pytest.approx(10.0)
    assert read("model.attn_dev_ms_step.global") == pytest.approx(2.0)
    assert read("model.attn_dev_ms_req.linear") == pytest.approx(50.0)
    ref = spec.reference
    assert read("kernels.linear_attn_roofline_pct.batch") == pytest.approx(
        100 * ref.kda_state_bytes(spec.config, 90 * 6) / 819e9 / 0.010)
    asked = ref.kda_flops_bytes(spec.config, 6 * 1500)
    assert asked["flops"] / 197e12 < asked["bytes"] / 819e9
    assert read("kernels.linear_prefill_attn_roofline_pct.batch") == \
        pytest.approx(100 * asked["bytes"] / 819e9 / 0.020)
    assert read("model.prefill_mfu_pct.batch") == pytest.approx(
        100 * ref.prefill_flops(spec.config, 1500) / 0.1 / 197e12)
    for name in ("kernels.linear_attn_roofline_pct.batch",
                 "kernels.linear_prefill_attn_roofline_pct.batch",
                 "model.prefill_mfu_pct.batch"):
        assert 0 < read(name) < 100, name
    # A trace of a program without the scopes (the parent's, another
    # architecture's): every one of them is silent, and nothing raises.
    raw["scopes"] = {k: "jit(x)/dot_general" for k in scopes}
    m.pop("prefill_scope_s")
    m.pop("decode_scope_s")
    for name in NEW_NAMES[:4]:
        assert read(name) is None, name
    # And one whose spans carry no counters: the counter reader too.
    ps.spans = []
    assert read("engine.linear_state_live_pct.batch") is None
