"""The state-space cell (`jamba2-reason-wide-closed`) at a tiny size on
the CPU: its reference, its driver and its readers, through `run.py`,
with the real cell's metrics; the two new readers on a
small made-up profile; and the real configuration's keys against the
catalog row.

The tiny copy of the benchmark (`conftest.make_tiny_root`) maps the cell
to `tiny-jamba-closed` (tests/conftest.py names the stand-in); the fixture
below adds that cell's files and its tiny `jamba` configuration."""

import dataclasses
import io
import json
import os

import pytest

import run
from conftest import ROOT, make_tiny_root
from lib import prefilltime, progspans, scopetime
from lib.spec import Spec

REAL = "jamba2-reason-wide-closed"
CONFIG = "jamba2-3b"
CELL = "tiny-jamba-closed"
# (name, unit, better, source, layer): what the cell appended; all move
# `serve_out_tok_s` and list the cell alone.
NEW = [
    ("model.attn_dev_ms_step.ssm", "ms", "lower", "device_trace", "Model"),
    ("model.attn_dev_ms_req.ssm", "ms", "lower", "device_trace", "Model"),
    ("kernels.ssm_update_roofline_pct.batch", "%", "higher", "device_trace",
     "Kernels"),
    ("kernels.ssm_scan_roofline_pct.batch", "%", "higher", "device_trace",
     "Kernels")]
NEW_NAMES = [m[0] for m in NEW]
# Accepted metrics whose `workloads` gain the cell, behind solar-open2's:
# readers that read true for it unchanged.
LISTED_IN = [
    "serve_out_tok_s", "engine.occupancy_pct.batch",
    "engine.delivery_tok_s.batch", "model.decode_dev_ms_step.batch",
    "model.decode_dev_ms_step_exact.batch", "device.idle_pct.batch",
    "device.peak_mem_pct.batch", "device.compiles_in_window.batch",
    "engine.host_self_ms_tick.batch", "engine.prefill_useful_pct.batch",
    "engine.decode_useful_pct.batch", "engine.admit_wait_steps_p90.batch",
    "engine.idle_named_pct.batch", "engine.cache_held_pct.batch",
    "model.prefill_mfu_pct.batch", "model.attn_dev_ms_step.global",
    "engine.linear_state_live_pct.batch"]
ENTRIES = {
    "config": {
        "name": CONFIG,
        "source": "https://huggingface.co/ai21labs/AI21-Jamba2-3B/blob/main/"
                  "config.json",
        "file": f"benchmarks/configs/{CONFIG}.json",
        "reduced": [],
        "why": "jamba 3B whole at its widths: 26 Mamba-1 layers (5120 "
               "channels x 16 f32 state) + 2 NoPE MQA layers (20 heads, 1 KV "
               "head), dense SwiGLU 8192, tied 65536-row head; 6.06 GB bf16, "
               "nothing cut"},
    "workload": {
        "name": REAL, "config": CONFIG, "traffic": "reason-wide-closed",
        "chips": 1,
        "why": "closed loop, 256 callers on 256 slots x 5120, prompts "
               "128-1024, answers ~2048 (1024-4096): a decode step rewrites "
               "26 x 0.33 MB of f32 state a slot at any length beside 2 MQA "
               "layers' rows; 3B whole"}}


def _tiny_jamba_config():
    from ray_tpu.models import configs

    cfg = dataclasses.asdict(configs.tiny_jamba_test())
    for key in ("dtype", "param_dtype", "max_seq_len", "remat"):
        del cfg[key]
    return dict(cfg, reference="jamba_decoder")


@pytest.fixture(scope="module")
def jamba_root(tmp_path_factory):
    """The tiny benchmark with the real cell's entries pointed at a tiny
    `jamba` configuration: same driver, same reference, same metrics."""
    root = make_tiny_root(str(tmp_path_factory.mktemp("jamba")))
    bdir = os.path.join(root, "benchmarks")
    with open(os.path.join(bdir, "configs", "tiny-jamba.json"), "w") as f:
        json.dump(_tiny_jamba_config(), f)
    with open(os.path.join(bdir, "cells", "tiny-closed.json")) as f:
        sizes = json.load(f)
    # One that fills a bucket, one inside one, one shorter than the
    # convolution.
    sizes["check"] = {"prompt_lens": [32, 12, 2], "decode_steps": 6,
                      "window_requests": 2}
    with open(os.path.join(bdir, "cells", CELL + ".json"), "w") as f:
        json.dump(sizes, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    listed = {m["name"] for kind in ("end_to_end", "per_layer")
              for m in bench[kind] if CELL in m.get("workloads", ())}
    assert listed == set(LISTED_IN) | set(NEW_NAMES)
    bench["configs"].append({
        "name": "tiny-jamba", "source": "test only", "reduced": [],
        "file": "benchmarks/configs/tiny-jamba.json", "why": "test only"})
    bench["workloads"].append({
        "name": CELL, "config": "tiny-jamba", "traffic": "tiny-closed",
        "chips": 1, "why": "test only"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


@pytest.fixture(scope="module")
def real_spec():
    return Spec(ROOT, REAL)


def _run(root, trace, seed=2**31 + 5001, seconds=2):
    out = io.StringIO()
    rc = run.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                   str(seconds), "--trace", str(trace)], root=root,
                  rehearse=True, out=out)
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


def test_the_entries_are_appended_to_benchmark_json(bench):
    # Behind everything the benchmark had (solar-open2's were its last
    # cell, configuration and metrics); a later PR's entries go behind
    # these, so nothing is pinned to the end.
    names = [c["name"] for c in bench["configs"]]
    cells = [w["name"] for w in bench["workloads"]]
    assert names.index(CONFIG) == names.index("solar-open2-l8-ep16") + 1
    assert cells.index(REAL) == cells.index("solar-open2-rollout-closed") + 1
    assert bench["configs"][names.index(CONFIG)] == ENTRIES["config"]
    assert bench["workloads"][cells.index(REAL)] == ENTRIES["workload"]
    assert all(len(e["why"]) <= 200 for e in ENTRIES.values())
    assert len(names) >= 9 and len(cells) >= 10 and sum(
        w["chips"] == 4 for w in bench["workloads"]) == 1
    mine = [m for m in bench["per_layer"] if m["name"] in NEW_NAMES]
    assert [(m["name"], m["unit"], m["better"], m["source"], m["layer"])
            for m in mine] == NEW
    assert all(m["workloads"] == [REAL] and m["moves"] == "serve_out_tok_s"
               for m in mine)
    order = [m["name"] for m in bench["per_layer"]]
    assert order.index(NEW_NAMES[0]) == order.index(
        "engine.linear_state_live_pct.batch") + 1
    assert [order.index(n) for n in NEW_NAMES] == list(range(
        order.index(NEW_NAMES[0]), order.index(NEW_NAMES[0]) + len(NEW)))
    for kind in ("end_to_end", "per_layer"):
        for m in bench[kind]:
            if m["name"] in LISTED_IN:
                assert m["workloads"].index(REAL) == m["workloads"].index(
                    "solar-open2-rollout-closed") + 1, m["name"]
            elif m["name"] not in NEW_NAMES:
                assert REAL not in m.get("workloads", [])
    # The decode kernel's share of its roofline counts K and V bytes over
    # `n_layers`, and two of this stack's 28 layers keep rows: the reader
    # would read fourteen times too high, so the cell is not listed.
    kernel = next(m for m in bench["per_layer"]
                  if m["name"] == "kernels.decode_attn_roofline_pct.batch")
    assert REAL not in kernel["workloads"]


def test_the_real_cell_names_its_files_and_every_reader(real_spec):
    spec = real_spec
    assert spec.reference.__file__.endswith("references/jamba_decoder.py")
    assert spec.traffic["driver"] == "serve_closed"
    assert {m["name"] for m in spec.metrics("end_to_end")} == {
        "serve_out_tok_s", "setup_s"}
    assert {m["name"] for m in spec.metrics("per_layer")} == (
        set(LISTED_IN) - {"serve_out_tok_s"}) | set(NEW_NAMES)
    for m in spec.metrics("per_layer"):
        reader = spec.load_module("layer_metrics", m["name"])
        assert reader is not None and callable(reader.read), m["name"]
    # Two reach accepted readers by the loader's longest-prefix rule (the
    # scope comes from the suffix); two are files of their own.
    for name, stem in (
            ("model.attn_dev_ms_step.ssm", "model.attn_dev_ms_step"),
            ("model.attn_dev_ms_req.ssm", "model.attn_dev_ms_req"),
            ("kernels.ssm_update_roofline_pct.batch",
             "kernels.ssm_update_roofline_pct"),
            ("kernels.ssm_scan_roofline_pct.batch",
             "kernels.ssm_scan_roofline_pct")):
        assert spec.load_module("layer_metrics", name).__file__.endswith(
            stem + ".py")
    for fn in ("forward_logits", "prefill_flops", "ssm_state_bytes",
               "ssm_scan_bytes", "ssm_layers", "layer_table", "recurrence",
               "loss", "train_flops_per_token"):
        assert callable(getattr(spec.reference, fn)), fn
    # The reference stands on its own: nothing of the program's, no
    # cache, no kernel, no chunks: the recurrence a token at a time.
    with open(spec.reference.__file__) as f:
        text = f.read()
    assert "ray_tpu" not in text.replace("`ray_tpu/models`", "") \
        .replace("`ray_tpu/ops`", "")
    for word in ("pallas", "cumsum", "associative_scan", "import ray"):
        assert word not in text, word
    assert "lax.scan(one" in text and '"highest"' in text


def test_the_traffic_and_the_sizes_are_the_issues(real_spec):
    tr, sizes = real_spec.traffic, real_spec.sizes
    # The issue's 256 callers on 256 slots (`slots_why` has the readings
    # of set-up, warm and cold, that its rule for the width asks for).
    assert (tr["clients"], tr["measure"], tr["n_requests"]) == (
        256, "ended_in_window", 2048)
    assert tr["prompt_len"] == {"dist": "loguniform", "min": 128,
                                "max": 1024}
    assert tr["output_len"] == {"dist": "lognormal", "median": 2048,
                                "sigma": 0.3, "min": 1024, "max": 4096}
    assert (tr["max_total_len"], tr["lead_in_s"], tr["drain_limit_s"]) == (
        5119, 30.0, 0.0)
    others = [json.load(open(os.path.join(ROOT, "benchmarks", "traffic", f)))
              for f in os.listdir(os.path.join(ROOT, "benchmarks", "traffic"))
              if f != "reason-wide-closed.json"]
    assert tr["trace_seed"] not in [o.get("trace_seed") for o in others]
    assert (sizes["slots"], sizes["max_seq_len"]) == (256, 5120)
    assert sizes["model"] == {"dtype": "bfloat16", "param_dtype": "bfloat16",
                              "max_seq_len": 5120}
    assert sizes["check"] == {"prompt_lens": [1000, 300, 3],
                              "decode_steps": 16, "window_requests": 2}
    assert sizes["trace_seconds"] == 8.0 and len(sizes["slots_why"]) > 200
    from lib import modelcfg, traffic
    from ray_tpu.models import periodic
    from ray_tpu.serve.llm import default_buckets

    trace = traffic.make_trace(tr)
    lens = [r.prompt_len for r in trace]
    assert 128 <= min(lens) and max(lens) <= 1024
    assert all(r.prompt_len + r.output_len <= 5119 for r in trace)
    assert all(1024 <= r.output_len <= 4096 for r in trace)
    buckets = default_buckets(5120)
    assert {next(b for b in buckets if b >= n) for n in lens} == {
        128, 256, 512, 1024}
    # Resident: 6.06 GB of weights, 2.18 GB of states, 0.20 of tails and
    # 1.34 GB of the two MQA layers' rows: 61% of the chip.
    cfg = modelcfg.transformer_config(real_spec.config, sizes)
    assert periodic.cache_layers(cfg) == {"window": 0, "global": 2,
                                          "ssm": 26}
    assert periodic.layer_plan(cfg) == [("periods", (2, 14), False)]
    state = 26 * 256 * 16 * 5120 * 4
    tails = 26 * 256 * 3 * 5120 * 2
    rows = 2 * 256 * 5120 * 1 * 128 * 2 * 2
    assert 2.18e9 < state < 2.19e9 and 1.34e9 < rows < 1.35e9
    assert 0.60 < (state + tails + rows + 2 * cfg.num_params()) / 16e9 < 0.62


def test_the_configuration_is_the_catalog_row(real_spec):
    cfg = real_spec.config
    assert cfg["source"] == ENTRIES["config"]["source"]
    assert cfg["reduced"] == ENTRIES["config"]["reduced"] == []
    assert cfg["assumed"] and cfg["deployment"] and cfg["left_out"] \
        and cfg["program_keys"]
    assert set(cfg["assumed"]) >= {"layer_order", "seeded_draws",
                                   "mamba_inner_norms", "state_layout"}
    assert all(k in cfg for k in cfg["published"])
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "AI21-Jamba2-3B")
        assert cfg["source"] == row["source_url"]
        assert sorted(row["config"]) == cfg["published"]
        assert not {k for k, v in row["config"].items() if cfg[k] != v}
    assert cfg["published_counts"] == {"num_hidden_layers": 28,
                                       "vocab_size": 65536}
    assert (cfg["d_model"], cfg["n_layers"], cfg["n_heads"],
            cfg["n_kv_heads"], cfg["head_dim"], cfg["d_ff"],
            cfg["global_attn_every"], cfg["attn_layer_offset"],
            cfg["mamba_d_state"], cfg["mamba_expand"], cfg["mamba_dt_rank"],
            cfg["mamba_d_conv"], cfg["vocab_size"], cfg["tie_embeddings"]
            ) == (2560, 28, 20, 1, 128, 8192, 14, 7, 16, 2, 160, 4, 65536,
                  True)
    from lib import modelcfg

    program = modelcfg.transformer_config(cfg, real_spec.sizes)
    assert program.arch == cfg["model_type"] == "jamba"
    assert 3.02e9 < program.num_params() < 3.04e9
    ref = real_spec.reference
    table = ref.layer_table(cfg)
    assert [i for i, (*_, k, _) in enumerate(table) if k == "global"] == [
        7, 21]
    assert [table[i] for i in (0, 6, 7, 8, 21, 27)] == [
        (0, 0, "ssm", 0), (0, 6, "ssm", 6), (0, 7, "global", 0),
        (0, 8, "ssm", 7), (1, 7, "global", 0), (1, 13, "ssm", 12)]
    assert ref.ssm_layers(cfg) == 26
    # A layer's state is 16 x 5120 float32, read and written: 0.66 MB an
    # update; 256 slots' 26 layers a step are 4.36 GB, 5.3 ms at the peak.
    assert ref.ssm_state_bytes(cfg, 1) == 2 * 4 * 16 * 5120
    step = ref.ssm_state_bytes(cfg, 256 * 26)
    assert 4.35e9 < step < 4.37e9 and 5.3e-3 < step / 819e9 < 5.4e-3
    # A token a layer streams 72 KB; a row's state out is 0.33 MB.
    assert ref.ssm_scan_bytes(cfg, 1) == 5120 * 14 + 2 * 16 * 4
    assert ref.ssm_scan_bytes(cfg, 0, 1) == 4 * 16 * 5120
    # A 1,000-token prompt: the products dominate, the scan's operations
    # are a fifth of a percent of them.
    n = 1000
    assert 5.6e12 < ref.prefill_flops(cfg, n) < 5.9e12
    assert 26 * n * 6 * 16 * 5120 / ref.prefill_flops(cfg, n) < 0.003


def test_a_traced_rehearsal_is_correct_and_reads_the_state_updates_owned(
        jamba_root, capsys):
    line = _run(jamba_root, trace=1)
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 0
    logged = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
              if ln.startswith('{"phase"')]
    check = next(d for d in logged if d["phase"] == "serve_setup")["check"]
    assert check["positions"] == 3 * 7 and check["logit_rel_rms_err"] < 1e-4
    got = line["rehearsal"]
    # What the host counts comes through the spans on any backend.
    assert 0 < got["engine.linear_state_live_pct.batch"]["value"] <= 100
    assert 0 < got["engine.cache_held_pct.batch"]["value"] <= 100
    # No device on a CPU: the device-trace readers return nothing.
    assert not [n for n in got if n.startswith(("model.", "kernels."))]
    spans = json.load(open(os.path.join(
        jamba_root, ".bench_out", CELL, "program_spans.json")))
    sums = spans["span_attribute_sums"]["engine.dispatch_block"]
    assert sums["linear_slot_steps"] == 6 * 4 * sums["k"]
    assert 0 < sums["linear_slot_steps_live"] <= sums["linear_slot_steps"]
    tiles = spans["span_attribute_sums"]["engine.prefill_tile"]
    assert tiles["linear_tokens"] == 6 * tiles["tokens"]


class _Ctx:
    trace, rehearse, out_dir = True, False, "/nonexistent"


def test_the_new_readers_on_a_made_up_profile(monkeypatch, real_spec):
    """Device time by scope inside the decode and the prefill programs,
    the two kernels' by their `kernel_metadata`; the two shares from the
    counters and the reference's counts; nothing from a trace without the
    scopes, the kernels or the counters."""
    ms = 1e6
    tile, block = "jit_prefill_sample_batch(7)", "jit_decode_k8(9)"

    def kernel(name, n):
        return (f'%k{n} = f32[] custom-call({n}), custom_call_target='
                f'"tpu_custom_call", frontend_attributes='
                f'{{kernel_metadata={{"kernel":"{name}"}}}}')

    ops = [(kernel("ssm_scan", 1), 0.0, 20 * ms),          # tile: the scan
           ("%b = f32[] fusion(2)", 20 * ms, 30 * ms),     # tile: ssm, rest
           ("%c = f32[] fusion(3)", 50 * ms, 50 * ms),     # tile: other
           (kernel("ssm_update", 4), 200 * ms, 48 * ms),   # decode: update
           ("%d = f32[] fusion(5)", 248 * ms, 32 * ms),    # decode: ssm, rest
           ("%e = f32[] custom-call(6)", 280 * ms, 16 * ms),  # decode: global
           ("%f = f32[] fusion(7)", 296 * ms, 64 * ms)]    # decode: rest
    scopes = {
        ops[0][0]: "jit(prefill_sample_batch)/while/body/attn_ssm/ssm_scan/"
                   "pallas_call",
        ops[1][0]: "jit(prefill_sample_batch)/while/body/attn_ssm/"
                   "dot_general",
        ops[2][0]: "jit(prefill_sample_batch)/dot_general",
        ops[3][0]: "jit(decode_k8)/while/body/while/body/attn_ssm/"
                   "pallas_call",
        ops[4][0]: "jit(decode_k8)/while/body/while/body/attn_ssm/"
                   "dot_general",
        ops[5][0]: "jit(decode_k8)/while/body/while/body/attn_global/"
                   "pallas_call",
        ops[6][0]: "jit(decode_k8)/while/body/while/body/dot_general"}
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
            "side": "slot", "bucket": 1024, "rows": 1, "tile_rows": 1,
            "tokens": 700, "req_ids": "41", "linear_tokens": 26 * 700}),
        progspans.Span("engine.dispatch_block", 2.0, 1.0, "t", {
            "k": 8, "cache_rows": 8 * 256 * 5120,
            "cache_rows_held": 8 * 240 * 2000,
            "linear_slot_steps": 8 * 256 * 26,
            "linear_slot_steps_live": 8 * 240 * 26})]
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
    assert ps.kernel_s == pytest.approx({"ssm_scan": 0.020,
                                         "ssm_update": 0.048})
    assert read("engine.linear_state_live_pct.batch") == pytest.approx(
        100 * 240 / 256)
    assert read("model.attn_dev_ms_step.ssm") == pytest.approx(10.0)
    assert read("model.attn_dev_ms_step.global") == pytest.approx(2.0)
    assert read("model.attn_dev_ms_req.ssm") == pytest.approx(50.0)
    ref = spec.reference
    # The update kernel's own time (6 ms a step), not its scope's (10).
    assert read("kernels.ssm_update_roofline_pct.batch") == pytest.approx(
        100 * ref.ssm_state_bytes(spec.config, 240 * 26) / 819e9 / 0.006)
    assert read("kernels.ssm_scan_roofline_pct.batch") == pytest.approx(
        100 * ref.ssm_scan_bytes(spec.config, 26 * 700, 26) / 819e9 / 0.020)
    assert read("model.prefill_mfu_pct.batch") == pytest.approx(
        100 * ref.prefill_flops(spec.config, 700) / 0.1 / 197e12)
    for name in NEW_NAMES[2:] + ["model.prefill_mfu_pct.batch"]:
        assert 0 < read(name) < 100, name
    # A trace of a program without the scopes and the kernels (the
    # parent's, another architecture's): every one of them is silent, and
    # nothing raises.
    raw["scopes"] = {k: "jit(x)/dot_general" for k in scopes}
    ps.kernel_s = {}
    m.pop("prefill_scope_s")
    m.pop("decode_scope_s")
    for name in NEW_NAMES:
        assert read(name) is None, name
    # And one whose spans carry no counters.
    ps.kernel_s = {"ssm_scan": 0.020, "ssm_update": 0.048}
    ps.spans = []
    for name in NEW_NAMES[2:]:
        assert read(name) is None, name
