"""The block-generation cell (`sdar-blockgen-closed`) at a tiny size on the
CPU: its reference, its driver of its own, its readers and its check
script, through `run.py`, with the real cell's metrics; the new readers
on a small made-up profile; and the real configuration's keys against
the catalog row.

The tiny copy of the benchmark (`conftest.make_tiny_root`) maps the cell
to `tiny-sdar-closed` (tests/conftest.py names the stand-in); the fixture
below adds that cell's files and its tiny `sdar_moe` configuration."""

import dataclasses
import io
import json
import os

import pytest

import run
from conftest import ROOT, make_tiny_root
from lib import progspans, scopetime, serving
from lib.spec import Spec

REAL = "sdar-blockgen-closed"
CONFIG = "sdar-30b-a3b-l7"
CELL = "tiny-sdar-closed"
# (name, unit, better, source, layer): what the cell appended; all move
# `serve_out_tok_s` and list the cell alone.
NEW = [
    ("engine.blockgen_tok_per_pass.batch", "tokens", "higher",
     "program_counter", "Engine"),
    ("engine.blockgen_commit_pass_pct.batch", "%", "lower",
     "program_counter", "Engine"),
    ("model.blockgen_mfu_pct.batch", "%", "higher", "device_trace",
     "Model")]
NEW_NAMES = [m[0] for m in NEW]
# Accepted metrics whose `workloads` gain the cell, behind the last cell
# each listed: readers that read true for it unchanged (a step of its
# decode programs is a pass, `slots` of `engine.process_block` the
# positions a pass computes).
LISTED_BEHIND_PANGU = [
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
    "model.prefill_mfu_pct.batch"]
LISTED_BEHIND_TRINITY = ["model.attn_dev_ms_step.global"]
LISTED_BEHIND_INTERNLM = ["kernels.decode_attn_roofline_pct.batch"]
LISTED_IN = LISTED_BEHIND_PANGU + LISTED_BEHIND_TRINITY \
    + LISTED_BEHIND_INTERNLM
ENTRIES = {
    "config": {
        "name": CONFIG,
        "source": "https://huggingface.co/JetLM/SDAR-30B-A3B-Chat/blob/main/"
                  "config.json",
        "file": f"benchmarks/configs/{CONFIG}.json", "reduced": ["n_layers"],
        "why": "sdar_moe 30B-A3B at published widths: 7 of 48 full-attention "
               "layers, 128 softmax-routed experts of 768 top 8, 151936-row "
               "vocabulary, generation by diffusion over blocks of 4: one "
               "pipeline stage"},
    "workload": {
        "name": REAL, "config": CONFIG, "traffic": "blockgen-closed",
        "chips": 1,
        "why": "closed loop, 64 callers on 64 slots x 2048, prompts ~256, "
               "answers 512-1536; 2 static denoising passes + a commit a "
               "block of 4 (dynamic rule: CPU tests): 256 rows a pass, 16 an "
               "expert, rows read once"}}


def _tiny_sdar_config():
    from ray_tpu.models import configs

    cfg = dataclasses.asdict(configs.tiny_sdar_test())
    for key in ("dtype", "param_dtype", "max_seq_len", "remat"):
        del cfg[key]
    # As a config.json gives it: no window is a null.
    return dict(cfg, sliding_window=None, reference="sdar_block_decoder")


@pytest.fixture(scope="module")
def sdar_root(tmp_path_factory):
    """The tiny benchmark with the real cell's entries pointed at a tiny
    `sdar_moe` configuration: same driver, same reference, same metrics."""
    root = make_tiny_root(str(tmp_path_factory.mktemp("sdar")))
    bdir = os.path.join(root, "benchmarks")
    with open(os.path.join(bdir, "configs", "tiny-sdar.json"), "w") as f:
        json.dump(_tiny_sdar_config(), f)
    with open(os.path.join(bdir, "traffic", "tiny-closed.json")) as f:
        tr = json.load(f)
    with open(os.path.join(bdir, "traffic", "tiny-blockgen.json"), "w") as f:
        json.dump(dict(tr, driver="serve_closed_blocks", trace_seed=39), f)
    with open(os.path.join(bdir, "cells", "tiny-closed.json")) as f:
        sizes = json.load(f)
    sizes["model"] = dict(sizes.get("model", {}), denoise_steps=2,
                          remask="low_confidence_static")
    sizes["check"] = {"prompt_lens": [41, 18, 6], "blocks": 3,
                      "window_requests": 2, "window_blocks": 4}
    sizes["decode_block"] = 8
    with open(os.path.join(bdir, "cells", CELL + ".json"), "w") as f:
        json.dump(sizes, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    listed = {m["name"] for kind in ("end_to_end", "per_layer")
              for m in bench[kind] if CELL in m.get("workloads", ())}
    assert listed == set(LISTED_IN) | set(NEW_NAMES)
    bench["configs"].append({
        "name": "tiny-sdar", "source": "test only", "reduced": [],
        "file": "benchmarks/configs/tiny-sdar.json", "why": "test only"})
    bench["workloads"].append({
        "name": CELL, "config": "tiny-sdar", "traffic": "tiny-blockgen",
        "chips": 1, "why": "test only"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


@pytest.fixture(scope="module")
def real_spec():
    return Spec(ROOT, REAL)


def _run(root, trace, seed=2**31 + 3901, seconds=2):
    out = io.StringIO()
    rc = run.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                   str(seconds), "--trace", str(trace)], root=root,
                  rehearse=True, out=out)
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


def test_the_entries_are_appended_to_benchmark_json(bench):
    # Behind everything the benchmark had (pangu's were its last cell and
    # configuration); a later PR's entries go behind these, so nothing is
    # pinned to the end.
    names = [c["name"] for c in bench["configs"]]
    cells = [w["name"] for w in bench["workloads"]]
    assert names.index(CONFIG) == names.index(
        "openpangu-ultra-moe-l5-ep16") + 1
    assert cells.index(REAL) == cells.index("openpangu-longgen-closed") + 1
    assert bench["configs"][names.index(CONFIG)] == ENTRIES["config"]
    assert bench["workloads"][cells.index(REAL)] == ENTRIES["workload"]
    assert all(len(e["why"]) <= 200 for e in ENTRIES.values())
    assert len(cells) >= 7 and sum(
        w["chips"] == 4 for w in bench["workloads"]) == 1
    mine = [m for m in bench["per_layer"] if m["name"] in NEW_NAMES]
    assert [(m["name"], m["unit"], m["better"], m["source"], m["layer"])
            for m in mine] == NEW
    assert all(m["workloads"] == [REAL] and m["moves"] == "serve_out_tok_s"
               for m in mine)
    order = [m["name"] for m in bench["per_layer"]]
    assert order.index(NEW_NAMES[0]) == order.index("trainer.remat_dev_pct") \
        + 1
    for kind in ("end_to_end", "per_layer"):
        for m in bench[kind]:
            if m["name"] in LISTED_IN:
                before = "internlm2-1b8-batch-closed" \
                    if m["name"] in LISTED_BEHIND_INTERNLM \
                    else "trinity-mini-reason-closed" \
                    if m["name"] in LISTED_BEHIND_TRINITY \
                    else "openpangu-longgen-closed"
                assert m["workloads"].index(REAL) == m["workloads"].index(
                    before) + 1, m["name"]
            elif m["name"] not in NEW_NAMES:
                assert REAL not in m.get("workloads", [])


def test_the_real_cell_names_its_files_and_every_reader(real_spec):
    spec = real_spec
    assert spec.reference.__file__.endswith(
        "references/sdar_block_decoder.py")
    assert spec.traffic["driver"] == "serve_closed_blocks"
    driver = spec.load_module("drivers", "serve_closed_blocks")
    assert all(callable(getattr(driver, fn)) for fn in (
        "run", "build", "finish", "check_blocks", "check_window_blocks"))
    assert {m["name"] for m in spec.metrics("end_to_end")} == {
        "serve_out_tok_s", "setup_s"}
    assert {m["name"] for m in spec.metrics("per_layer")} == (
        set(LISTED_IN) - {"serve_out_tok_s"}) | set(NEW_NAMES)
    for m in spec.metrics("per_layer"):
        reader = spec.load_module("layer_metrics", m["name"])
        assert reader is not None and callable(reader.read), m["name"]
    for name in NEW_NAMES:
        assert spec.load_module("layer_metrics", name).__file__.endswith(
            name.rsplit(".", 1)[0] + ".py")
    for fn in ("forward_logits", "generate", "chosen_experts", "schedule",
               "pass_flops", "prefill_flops", "moe_experts_min_bytes",
               "moe_experts_flops", "block_attn_min_bytes", "loss",
               "train_flops_per_token"):
        assert callable(getattr(spec.reference, fn)), fn
    # The reference stands on its own: nothing of the program's.
    with open(spec.reference.__file__) as f:
        assert "ray_tpu" not in f.read().replace("`ray_tpu/models`", "")


def test_the_traffic_and_the_sizes_are_the_issues(real_spec):
    tr, sizes = real_spec.traffic, real_spec.sizes
    assert (tr["clients"], tr["measure"], tr["n_requests"]) == (
        64, "ended_in_window", 448)
    assert tr["prompt_len"] == {"dist": "lognormal", "median": 256,
                                "sigma": 0.5, "min": 64, "max": 508}
    assert tr["output_len"] == {"dist": "loguniform", "min": 512,
                                "max": 1536}
    assert (tr["max_total_len"], tr["lead_in_s"], tr["drain_limit_s"]) == (
        2047, 20.0, 0.0)
    assert tr["trace_seed"] == 3901
    assert (sizes["slots"], sizes["max_seq_len"]) == (64, 2048)
    assert sizes["model"] == {
        "dtype": "bfloat16", "param_dtype": "bfloat16", "max_seq_len": 2048,
        "denoise_steps": 2, "remask": "low_confidence_static"}
    assert sizes["check"] == {"prompt_lens": [700, 300, 6], "blocks": 4,
                              "window_requests": 2, "window_blocks": 16}
    assert sizes["trace_seconds"] == 8.0 and sizes["slots_why"]
    from lib import traffic
    from ray_tpu.serve.llm import LLMEngine, default_buckets

    trace = traffic.make_trace(tr)
    lens = [r.prompt_len for r in trace]
    assert 64 <= min(lens) and max(lens) <= 508
    assert all(r.prompt_len + r.output_len <= 2047 for r in trace)
    assert all(512 <= r.output_len <= 1536 for r in trace)
    # Not multiples of four on purpose: a prompt's remainder opens a block
    # and the last block is cut.
    assert {n % 4 for n in lens} == {0, 1, 2, 3}
    assert {(r.prompt_len % 4 + r.output_len) % 4 for r in trace} == {
        0, 1, 2, 3}
    # The tiles the AOT test compiles are the ones the prompts reach.
    buckets = default_buckets(2048)
    reached = {next(b for b in buckets if b >= n // 4 * 4) for n in lens}
    assert reached == {64, 128, 256, 512}
    assert all(LLMEngine._tile_rows(b) * b == 512 for b in reached)


def test_the_configuration_is_the_catalog_row(real_spec):
    cfg = real_spec.config
    assert cfg["source"] == ENTRIES["config"]["source"]
    assert cfg["reduced"] == ["n_layers"]
    assert cfg["assumed"] and cfg["deployment"] and cfg["use"]
    assert all(k in cfg for k in cfg["published"])
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "SDAR-30B-A3B-Chat")
        assert cfg["source"] == row["source_url"]
        assert sorted(row["config"]) == cfg["published"]
        assert not {k for k, v in row["config"].items() if cfg[k] != v}
        assert set(row["not_given"]) == {"block length", "noise schedule"}
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["intermediate_size"], cfg["moe_intermediate_size"],
            cfg["num_experts"], cfg["num_experts_per_tok"],
            cfg["num_hidden_layers"], cfg["vocab_size"], cfg["rope_theta"],
            cfg["sliding_window"], cfg["model_type"]) == (
        2048, 32, 4, 128, 6144, 768, 128, 8, 48, 151936, 1000000, None,
        "sdar_moe")
    # The program's keys: the published widths under its own names.
    assert (cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"], cfg["d_ff"],
            cfg["moe_d_ff"], cfg["moe_experts"], cfg["moe_top_k"],
            cfg["n_layers"], cfg["global_attn_every"], cfg["norm_eps"],
            cfg["tie_embeddings"], cfg["score_func"], cfg["route_norm"]) == (
        2048, 32, 4, 6144, 768, 128, 8, 7, 1, 1e-6, False, "softmax", True)
    # What the row does not give, assumed: a line each.
    assert (cfg["block_length"], cfg["mask_token_id"], cfg["denoise_steps"],
            cfg["remask"], cfg["confidence_threshold"]) == (
        4, 151669, 4, "low_confidence_dynamic", 0.9)
    said = " ".join(cfg["assumed"])
    for word in ("block_length 4", "mask_token_id 151669", "No shift",
                 "QK-norm", "commit pass", "Seeded weights"):
        assert word in said, word
    from lib import modelcfg

    program = modelcfg.transformer_config(cfg, real_spec.sizes)
    assert program.arch == cfg["model_type"] and program.sliding_window == 0
    # The cell's schedule over the configuration's defaults.
    assert (program.block_length, program.denoise_steps, program.remask) == (
        4, 2, "low_confidence_static")
    assert program.num_params() == 7 * 623120640 + 2 * 151936 * 2048 + 2048
    ref = real_spec.reference
    # ISSUE 39's counts: a pass of 256 rows reads 7 layers of experts.
    assert ref.moe_experts_min_bytes(cfg, 7 * 128, 7 * 256 * 8) \
        == pytest.approx(2 * (7 * 128 * 3 * 2048 * 768 + 7 * 2048 * 2 * 2048))
    assert 8.4e9 < ref.moe_experts_min_bytes(cfg, 7 * 128, 7 * 2048) < 8.6e9
    assert ref.block_attn_min_bytes(cfg, 64 * 1000) == 64 * 1000 * 14336
    # Pairs under the block diagonal: 8 tokens in blocks of 4 are 16 + 32.
    used = 2 * 2048 * 4096 + 2 * 2048 * 512 + 2048 * 128 + 3 * 2048 * 768 * 8
    assert ref.prefill_flops(cfg, 8) == 2.0 * 8 * used * 7 \
        + 4.0 * 4096 * 48 * 7
    assert ref.pass_flops(cfg, 0, 1000, 0) == 4.0 * 4096 * 1000 * 4 * 7
    assert ref.pass_flops(cfg, 0, 0, 256) == 2.0 * 256 * 2048 * 151936


def test_the_tiny_cell_is_correct_against_its_own_reference(sdar_root,
                                                            capsys):
    line = _run(sdar_root, trace=0)
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 0
    assert set(line["rehearsal"]) == {"serve_out_tok_s", "setup_s"}
    logged = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
              if ln.startswith("{")]
    setup = next(d for d in logged if d.get("phase") == "serve_setup")
    check = setup["check"]
    # Three prompts x three blocks of two denoising passes and a commit;
    # 18 and 6 leave two tokens over, so their first block has two masks
    # and is done in one denoising pass (41 leaves one: two passes).
    assert check["passes"] == 9 + 8 + 8 and check["positions"] == 25 * 4
    assert check["shortest_prompt"] == 6 and check["ok"]
    assert check["logit_rel_rms_err"] < 1e-4
    assert check["logit_rel_rms_err_shortest"] < 1e-4
    # Every fused size ran before the window opened.
    counts = next(d for d in logged if "engine_counts" in d)["engine_counts"]
    assert {int(k) for k in counts["blocks_by_k"]} == {1, 2, 4, 8}
    assert set(setup["warm"]["block_sizes_forced"]) <= {1, 2, 4, 8}
    assert counts["blocks_committed"] <= counts["commit_passes"]
    window = next(d for d in logged
                  if d.get("phase") == "window_tokens_check")
    assert window["ok"] and window["requests"] == 2
    assert window["positions"] > 0 and window["passes"] > 0
    # float32 program against float32 reference: the reference's own best
    # at every position.
    assert window["argmax_agree"] == window["positions"]
    assert window["token_deficit_max"] == 0.0
    for name in ("rows.jsonl", "stamps.json"):
        assert os.path.exists(os.path.join(sdar_root, ".bench_out", CELL,
                                           name))


def test_a_traced_rehearsal_reads_the_pass_counters(sdar_root):
    line = _run(sdar_root, trace=1)
    got = line["rehearsal"]
    # Two denoising passes and a commit a block of four: four tokens in
    # three passes while a slot generates, less what a request's end cuts
    # and what an owned slot runs past it.
    assert 0.5 < got["engine.blockgen_tok_per_pass.batch"]["value"] <= 4 / 3
    assert 25 < got["engine.blockgen_commit_pass_pct.batch"]["value"] < 50
    # Tokens over positions computed: a third at most, under 100.
    assert 0 < got["engine.decode_useful_pct.batch"]["value"] <= 100 / 3
    assert 0 < got["engine.cache_held_pct.batch"]["value"] <= 100
    assert 0 < got["engine.moe_experts_hit_pct.batch"]["value"] <= 100
    assert got["engine.moe_load_max_over_mean.batch"]["value"] >= 1
    assert 0 < got["engine.prefill_useful_pct.batch"]["value"] <= 100
    # No device on a CPU: the device-trace readers return nothing.
    assert not [n for n in got if n.startswith(("model.", "kernels."))]
    spans = json.load(open(os.path.join(
        sdar_root, ".bench_out", CELL, "program_spans.json")))
    done = spans["span_attribute_sums"]["engine.process_block"]
    sent = spans["span_attribute_sums"]["engine.dispatch_block"]
    # `slots` are the positions a pass computes: 4 slots x a block of 4.
    assert done["slots"] == 16 * spans["span_counts"]["engine.process_block"]
    assert sent["passes"] == sent["k"] and sent["block_length"] == 4 * \
        spans["span_counts"]["engine.dispatch_block"]
    assert done["blocks_committed"] <= done["commit_passes"]
    assert done["positions_unmasked"] >= done["emitted"] > 0
    assert done["moe_rows"] == done["k"] * 2 * 16 * 2     # layers, rows, top
    tiles = spans["span_attribute_sums"]["engine.prefill_tile"]
    assert tiles["tokens"] % 4 == 0


def test_the_check_fails_under_a_causal_mask_and_under_fp8_weights(
        sdar_root):
    from checks import blockgen_logits

    out = io.StringIO()
    assert blockgen_logits.main(
        ["--workload", CELL, "--seeds", "5,2147483653", "--control", "1",
         "--control-len", "40"], root=sdar_root, rehearse=True, out=out) == 0
    lines = [json.loads(ln) for ln in out.getvalue().splitlines()]
    last = lines[-1]
    assert last["seeds"] == 2 and last["limit"] == serving.LOGIT_REL_TOL
    assert last["over_limit"] == 0
    assert last["sound_largest_rel_rms_err"] < 1e-4
    assert last["sound_largest_rel_rms_err_shortest"] < 1e-4
    # The six-token prompt under the wrong mask: a causal reference hides
    # up to three of a row's keys, and the check says so.
    assert lines[0]["control_causal_ok"] is False
    assert last["control_causal_smallest_rel_rms_err_shortest"] \
        > serving.LOGIT_REL_TOL
    # 8-bit weights: at this width a tenth of what the chip's reads (the
    # error grows with the width), a thousand times the sound reading.
    assert last["control_fp8_smallest_rel_rms_err"] > 0.02 \
        > 100 * last["sound_largest_rel_rms_err"]
    assert "control_fp8_rel_rms_err" not in lines[1]


class _Ctx:
    trace, rehearse, out_dir = True, False, "/nonexistent"

    def __init__(self):
        self.notes = {}


def test_the_new_readers_on_a_made_up_profile(monkeypatch, real_spec):
    """Tokens a pass, the commit passes' share, and the whole pass's
    share of the peak from the counters and the reference's counts;
    nothing from a program whose spans carry no such counters."""
    ms = 1e6
    block = "jit_decode_k8(9)"
    ops = [("%attn.1 = f32[] custom-call(5)", 416 * ms, 24 * ms),
           ("%e = f32[] fusion(6)", 440 * ms, 40 * ms),
           ("%h = f32[] fusion(7)", 480 * ms, 16 * ms)]
    scopes = {
        ops[0][0]: "jit(decode_k8)/while/body/attn_global/pallas_call",
        ops[1][0]: "jit(decode_k8)/while/body/moe_experts/while/body/"
                   "jit(gmm)/x",
        ops[2][0]: "jit(decode_k8)/while/body/block_head/dot_general"}
    raw = {"spans": [], "window": (0.0, 500 * ms), "scopes": scopes,
           "devices": {"/device:TPU:0": {
               "ops": ops, "modules": [(block, 400 * ms, 96 * ms)]}}}
    monkeypatch.setattr(progspans, "read_profile", lambda path: raw)
    monkeypatch.setattr(scopetime.xplane, "find_xplane", lambda d: "x.pb")
    ps = progspans.reduce_profile(raw)
    ps.kernel_s = {"decode_attn": 0.024}
    held = 8 * 64 * 1000                    # rows the 8 passes' slots read
    ps.spans = [
        progspans.Span("engine.dispatch_block", 2.0, 1.0, "t", {
            "k": 8, "passes": 8, "block_length": 4,
            "cache_rows": 8 * 64 * 2048, "cache_rows_held": held}),
        progspans.Span("engine.process_block", 3.0, 1.0, "t", {
            "k": 8, "slots": 256, "active": 60, "block_length": 4,
            "emitted": 600, "denoise_passes": 320, "commit_passes": 160,
            "blocks_committed": 158, "positions_unmasked": 640,
            "tokens_truncated": 8, "moe_rows": 8 * 7 * 256 * 8,
            "moe_experts_hit": 8 * 7 * 128,
            "moe_expert_steps": 8 * 7 * 128})]
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

    assert ps.decode_steps() == 8 and ps.decode_ms_step() == 12.0
    assert read("engine.blockgen_tok_per_pass.batch") == pytest.approx(
        600 / (8 * 60))
    assert read("engine.blockgen_commit_pass_pct.batch") == pytest.approx(
        100 / 3)
    assert read("engine.decode_useful_pct.batch") == pytest.approx(
        100 * 600 / (8 * 256))
    ref = spec.reference
    asked = ref.pass_flops(spec.config, 60 * 4, held / 8, 40 * 4)
    assert read("model.blockgen_mfu_pct.batch") == pytest.approx(
        100 * asked / 0.012 / 197e12)
    assert 0 < read("model.blockgen_mfu_pct.batch") < 100
    assert ctx.notes["decode_scope_ms_pass"] == pytest.approx({
        "attn_global": 3.0, "moe_router": 0.0, "moe_experts": 5.0,
        "block_head": 2.0, "block_sample": 0.0, "all": 12.0})
    # The accepted readers read a pass as a step: the held rows' bytes
    # over the kernel's time a pass, the experts hit a pass.
    least_s = ref.block_attn_min_bytes(spec.config, held / 8) / 819e9
    assert read("kernels.decode_attn_roofline_pct.batch") == pytest.approx(
        100 * least_s / 0.003)
    assert read("kernels.moe_experts_roofline_pct.batch") == pytest.approx(
        100 * ref.moe_experts_min_bytes(spec.config, 7 * 128, 7 * 2048)
        / 819e9 / 0.005)
    assert read("model.attn_dev_ms_step.global") == pytest.approx(3.0)
    # A program that generates one token a step (the parent's, another
    # configuration's): its spans carry none of the counters, every new
    # reader is silent and nothing raises.
    ps.spans = [
        progspans.Span("engine.dispatch_block", 2.0, 1.0, "t", {
            "k": 8, "cache_rows": 8 * 64 * 2048, "cache_rows_held": held}),
        progspans.Span("engine.process_block", 3.0, 1.0, "t", {
            "k": 8, "slots": 64, "active": 60, "emitted": 480})]
    for name in NEW_NAMES:
        assert read(name) is None, name
